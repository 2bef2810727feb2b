"""`device.idle_pct` over the traced save cycle of a cell that saves:
the share of the traced stretch (one whole cycle and one save boundary)
in which no operation ran on the chip — the loop dispatching late while
a drain runs shows here."""

from benchmark import cells

NAME, UNIT, SOURCE = "ckpt.device_idle_pct", "%", "device_trace"
LAYER, MOVES = "checkpoint", "saving_tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "device.idle_pct").read(
        trace, events, ledgers, cell)
