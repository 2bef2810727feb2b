"""Generation 2's own stamps: process start -> `train()` entered
(imports, backend start, Trainer construction with its sharded init)."""

from benchmark import readers

NAME, UNIT, SOURCE = "trainer.startup_s", "s", "program_span"
LAYER, MOVES = "trainer loop", "resume_s"


def read(trace, events, ledgers, cell):
    g = readers.measured_gen(events)
    start = readers.first(events, "worker_start", gen=g)
    enter = readers.first(events, "train_enter", gen=g)
    if g is None or start is None or enter is None:
        return None
    return enter["t"] - start["t_proc0"]
