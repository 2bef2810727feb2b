"""`trainer:train`'s start -> `open`: the restore, the first dispatch,
the fused-K decision and the warm-up steps."""

from benchmark import setup_chain

NAME, UNIT, SOURCE = "setup.warmup_s", "s", "program_span"
LAYER, MOVES = "trainer loop", "setup_s"


def read(trace, events, ledgers, cell):
    return setup_chain.warmup_s(events)
