"""The indexer's SCORES' share of their roofline: the least time one
chip could take for the scores' work of one step — the LONGER of their
FLOPs at the MXU's peak (one product of 2 x 64 a causal pair and indexer
head forward, two backward) and their least bytes at the HBM peak (the
operands, one float32 score a causal pair written, one cotangent a kept
pair read), every layer, this chip's share of the batch, from the
configuration and the batch ALONE by the model class's
`index_cost_per_step`, never from the program's choices — over the
device time of whatever ops lie under the scoring scope (the component
`scores` under `sparse_attn`: `dwt_idx_scores` forward and recomputed,
`dwt_idx_bwd` backward, on the kernel route; a fusion's on any other):
the same work whatever implements it.  The time holds the recomputed
forward, the ReLU, the weighting and the backward's recomputed product,
the count none of them, so the share errs low, never high.  At the
published sizes the products bound it (512 FLOPs a byte forward).  A
model class without `index_cost_per_step` reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.attn_index_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "index_cost_per_step", None)
    if cost_fn is None:
        return None
    split = cells.load_module("layer_metrics", "step.attn_index_ms") \
        .sparse_split(trace, cell)
    ms = (split or {}).get("attn_scores")
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    cost = cost_fn(cell["config"], cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
