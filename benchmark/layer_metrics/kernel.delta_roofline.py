"""The gated delta rule's share of its roofline: the least time one chip
could take for the RECURRENCE's FLOPs and bytes of one step (forward +
backward, every linear-attention layer, the heads held, this chip's
share of the batch; from shapes, by the model class's
`delta_cost_per_step`: 7*dk*dv operations a head a token forward, each
of q, k, v, o, the decay and the write gate moved once each way;
recomputation not counted as useful) over `step.linattn_scan_ms`.  The
time holds the convolution, the L2 norms and every pass of the chunked
form (the solve, the (dk x dk) transitions), the count none of them, so
the share errs low, never high.  At the published sizes the bytes bound
it (1.04 ms against 0.72 ms of operations at 15 heads, one sequence of
8192).  A model class without `delta_cost_per_step` reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.delta_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "delta_cost_per_step", None)
    if cost_fn is None:
        return None
    ms = cells.load_module("layer_metrics", "step.linattn_scan_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    cost = cost_fn(cell["config"], cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
