"""The selective scan's share of its roofline: the least time one chip
could take for the RECURRENCE's operations and bytes of one step
(forward + backward, every Mamba-1 layer, this chip's share of the
batch; from shapes, by the model class's `sscan_cost_per_step`: 7
operations a (channel, state) pair a token forward, twice that backward;
x, dt and y at d_inner and B and C at d_state moved once each way;
recomputation not counted as useful) over `step.ssm_scan_ms`.  The time
holds the convolution, the recomputed forward and the backward kernel's
own walk forward through a chunk, the count none of them; and the
operations are the vector and exponent units', which `peaks.json` gives
no peak, held here to the matrix unit's: the share errs low, never high.
A model class without `sscan_cost_per_step` reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.sscan_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "sscan_cost_per_step", None)
    if cost_fn is None:
        return None
    ms = cells.load_module("layer_metrics", "step.ssm_scan_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    cost = cost_fn(cell["config"], cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
