"""Mean `trainer:dispatch` span per optimizer step inside the window: the
wall time of the step's dispatch CALL on the loop's thread — the host
work of handing one step to the runtime (argument handling, the
enqueue).  The call returns before the device has run the step; where
the device's back-pressure reaches the loop it shows in the spans that
wait (the pump's full queue under `trainer:log_submit`), not here."""

from benchmark import program

NAME, UNIT, SOURCE = "trainer.dispatch_ms", "ms", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.window_ms_per_step(events, "trainer:dispatch")
