"""The expert pass's share of its roofline: the least time one chip
could take for the grouped matmuls' FLOPs and bytes of one step (forward
+ backward, every layer, this chip's share of the batch; from shapes, by
the model class's `moe_cost_per_step`; recomputation not counted as
useful) over `step.moe_experts_ms`.  The time holds the gating product
(silu x up) as well as the matmuls, so the share errs low, never high.
At OLMoE's 64 experts of 1024 the bound is compute from a few thousand
tokens a step on.  A model class without `moe_cost_per_step` reports
nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.moe_gmm_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "moe_cost_per_step", None)
    if cost_fn is None:
        return None
    ms = cells.load_module("layer_metrics", "step.moe_experts_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    cost = cost_fn(cell["config"], cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
