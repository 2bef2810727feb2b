"""Summed device duration per step, on device 0, of the ops named
all-gather*, all-reduce*, all-to-all*, collective-permute*,
reduce-scatter*.  A TOTAL, not the exposed part: time a collective
overlaps compute is counted in full."""

from benchmark import xtrace

NAME, UNIT, SOURCE = "step.collective_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace or cell["chips"] < 2:
        return None
    return xtrace.per_step_ms(trace, xtrace.COLLECTIVE_PREFIXES)
