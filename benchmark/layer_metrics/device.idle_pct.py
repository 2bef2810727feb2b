"""1 - (union of op intervals on `XLA Ops`, averaged over chips) / the
traced window (first op start to last op end), over the traced steps."""

from benchmark import readers

NAME, UNIT, SOURCE = "device.idle_pct", "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    busy, win = readers.traced_busy_window(trace, events)
    return 100.0 * (1.0 - busy / win) if win else None
