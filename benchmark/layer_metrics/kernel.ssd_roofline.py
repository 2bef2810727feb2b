"""The state-space scan's share of its roofline: the least time one chip
could take for the RECURRENCE's FLOPs and bytes of one step (forward +
backward, every Mamba-2 layer, this chip's share of the batch; from
shapes, by the model class's `ssd_cost_per_step`: 6*P*N operations a head
a token forward, each of x, B, C, the step size and y moved once each
way; recomputation not counted as useful) over `step.ssm_scan_ms`.  The
time holds the convolution and every pass of the chunked form, the count
neither, so the share errs low, never high.  At the published sizes the
two bounds lie close (bytes by a few percent).  A model class without
`ssd_cost_per_step` reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.ssd_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "ssd_cost_per_step", None)
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
