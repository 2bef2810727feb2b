"""Seconds of `jax:backend_compile` for the step program's own function
before the window opened: XLA's compile on a cold cache, the load out of
the persistent cache on a warm one (`jax:cache_load` is the retrieval
inside it and is not added again)."""

from benchmark import program

NAME, UNIT, SOURCE = "setup.compile_load_s", "s", "program_span"
LAYER, MOVES = "compile cache", "setup_s"


def read(trace, events, ledgers, cell):
    return program.setup_step_durations_s(events, ("jax:backend_compile",))
