"""Device time of a sparse attention's CHOICE of keys: every op owned by
the scope `sparse_attn/select` — the exact top-k of each query's causal
scores (`dwt_idx_select` on the kernel route: a bitwise threshold search
over a block of rows in VMEM, the tie's cut, the mask and the kept
scores' log-sum) and the tiles' counts — forward and recomputed forward
(the choice has no backward), in every layer.  An overlay of the class's
`sparse_parts` beside `step.attn_index_ms`, read the same way.  Device
0, ms per optimizer step, a TOTAL.  A model class whose scopes file has
no `sparse_parts`, or a program whose step holds no such scope, reports
nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "step.attn_select_ms", "ms", "device_trace"
LAYER, MOVES = "sparse-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    split = cells.load_module("layer_metrics", "step.attn_index_ms") \
        .sparse_split(trace, cell)
    return (split or {}).get("attn_select") or None
