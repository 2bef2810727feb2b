"""Device time of everything under the `moe` scope that is not the
experts' own (`step.moe_experts_ms`, whose file holds the split): the
router's matmul and softmax, the top-k, the sort, the gather into expert
order, the weighting and the scatter-add back, the auxiliary losses, and
their gradients.  With `step.moe_experts_ms` it sums to `step.mlp_ms`.
Device 0, the ops inside train-step modules as `kernel.attn_ms` takes
them, ms per optimizer step, a TOTAL.  A model class whose scopes file
has no `moe_parts` reports nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "step.moe_route_ms", "ms", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    experts = cells.load_module("layer_metrics", "step.moe_experts_ms")
    return experts.moe_part_ms(trace, cell, "moe_route")
