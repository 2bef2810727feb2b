"""Attention's share of its roofline: the least time one chip could take
for the causal-attention FLOPs and bytes of one step (forward +
backward, every layer, this chip's share of the batch; from shapes, by
`flops.causal_attention_cost`; recomputation not counted as useful)
over `kernel.attn_ms`.  At head size 64 and sequence 1024 in bf16 the
bound is compute (7.1 ms against 6.6 ms of HBM traffic for
gpt2_124m at batch 24)."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.attn_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    ms = cells.load_module("layer_metrics", "kernel.attn_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    model = cells.load_module("models", cell["config"]["model_class"])
    cost = model.attention_cost_per_step(cell["config"],
                                         cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
