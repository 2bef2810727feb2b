"""Seconds of `jax:trace` + `jax:lower` + `jax:backend_compile` before
`open` for every function that is NOT the step's: the init functions,
eager helpers, the harness's reference.  An overlay across the four
parts of set-up, not a part."""

from benchmark import setup_chain

NAME, UNIT, SOURCE = "setup.other_programs_s", "s", "program_span"
LAYER, MOVES = "compile cache", "setup_s"


def read(trace, events, ledgers, cell):
    return setup_chain.other_programs_s(events)
