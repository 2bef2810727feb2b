"""Device time of a sparse attention's INDEXER and of its KL term: every
op owned by the scopes `sparse_attn/index` — the indexer's three
products, its key's LayerNorm, the rotation, the scores
(`sparse_attn/index/scores`: `dwt_idx_scores` on the kernel route) — and
`sparse_attn/index_loss` — the KL term (`dwt_idx_kl`), its sum and the
scores' backward (`dwt_idx_bwd`) — forward, recomputed forward and
backward, in every layer.  What the choice of keys costs beside the
choice itself (`step.attn_select_ms`) and the attention over it
(`kernel.attn_ms`: the `dwt_fa_sp_*` kernels).  The class's scopes file
names the scopes under `sparse_parts`; `program.split_ms` runs with those
rules as it does for `step.shortconv_gated_ms`.  Device 0, ms per
optimizer step, a TOTAL.  A model class whose scopes file has no
`sparse_parts`, or a program whose step holds no such scope, reports
nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.attn_index_ms", "ms", "device_trace"
LAYER, MOVES = "sparse-attention layer", "tokens_per_s"
PARTS = ("attn_scores", "attn_index")


def sparse_split(trace, cell):
    """{part: ms a step} of the class's `sparse_parts`, or None."""
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("sparse_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    return program.split_ms(trace, table, rules)


def read(trace, events, ledgers, cell):
    split = sparse_split(trace, cell)
    return sum((split or {}).get(part, 0.0) for part in PARTS) or None
