"""Seconds inside `accelerate:init_state` spans that ended before the
window opened: the sharded-by-construction `PRNGKey(0)` init (compile or
cache load, then the device) that a seeded or resumed run throws away.
The span waits for the device (`block_until_ready`) under every strategy
— plain, `optimizer_offload` (the moments' hop to pinned host included)
and `local_sgd` (the parameters; the DiLoCo state is built after it) —
so the figure is the init's own time, not its dispatch."""

from benchmark import program

NAME, UNIT, SOURCE = "setup.state_init_s", "s", "program_span"
LAYER, MOVES = "strategy -> step", "setup_s"


def read(trace, events, ledgers, cell):
    return program.setup_span_s(events, "accelerate:init_state")
