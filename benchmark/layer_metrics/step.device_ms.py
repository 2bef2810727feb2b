"""Device duration of one optimizer step: the median `XLA Modules`
event of the train-step program over the traced steps (slowest chip)."""

from benchmark import xtrace

NAME, UNIT, SOURCE = "step.device_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return xtrace.step_device_ms(trace) if trace else None
