"""`trainer:iteration` minus the part its child spans cover (`trainer:data`,
`trainer:dispatch`, `trainer:log_submit`, `trainer:save`, `trainer:stage`,
`trainer:eval`, `trainer:policy_poll`), per optimizer step inside the
window: the loop's own work — cadence checks, ledger credits, the perf
observatory's gate."""

from benchmark import program

NAME, UNIT, SOURCE = "trainer.loop_self_ms", "ms", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.loop_self_ms(events)
