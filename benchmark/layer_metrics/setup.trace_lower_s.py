"""Seconds JAX spent tracing (`jax:trace`) and lowering (`jax:lower`) the
step program's own function before the window opened: the part of the
first dispatch that no cache saves."""

from benchmark import program

NAME, UNIT, SOURCE = "setup.trace_lower_s", "s", "program_span"
LAYER, MOVES = "compile cache", "setup_s"


def read(trace, events, ledgers, cell):
    return program.setup_step_durations_s(events, ("jax:trace", "jax:lower"))
