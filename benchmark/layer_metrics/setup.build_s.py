"""Seconds inside `trainer:build` spans that ended before the window
opened: `Trainer.__init__` — the plan, the state init, the checkpoint
engine, the profiler."""

from benchmark import program

NAME, UNIT, SOURCE = "setup.build_s", "s", "program_span"
LAYER, MOVES = "trainer loop", "setup_s"


def read(trace, events, ledgers, cell):
    return program.setup_span_s(events, "trainer:build")
