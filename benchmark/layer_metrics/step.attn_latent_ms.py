"""Device time of what latent attention costs AROUND its kernels: ops
scoped `attention/kv_a_proj` (the down-projection to the latent and the
one rotated key part), `attention/kv_a_norm`, `attention/kv_b_proj` (the
up-projection to every head's k_nope and v), `attention/rope` and
`attention/assemble` (the cut of the projections into their parts, the
broadcast of `k_rope` to the heads, the joins into the 192-wide q and
k), and their gradients — neither `q_proj` / `o_proj`, which any
attention has, nor a `dwt_fa_*` kernel.  An OVERLAY over `step.
attn_dense_ms` (the two projections) and `step.unscoped_ms` (the rest),
not a part beside them.  The class's scopes file names the scopes under
`attn_parts`; `program.split_ms` runs with those rules as it does for
`step.ssm_scan_ms`.  Device 0, ms per optimizer step, a TOTAL.  A model
class whose scopes file has no `attn_parts` reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.attn_latent_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("attn_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("attn_latent") or None
