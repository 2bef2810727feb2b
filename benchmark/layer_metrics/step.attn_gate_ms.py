"""Device time of the attention's OUTPUT GATE: every op scoped
`attention/g_proj` (the product of the block's normalised input with a
(hidden x heads) matrix, one logit a head and token) or
`attention/gate` (the sigmoid, the multiply of each head's 128 lanes of
what the kernels wrote by its gate, and their backward: the weighted
cotangent and <a, d_out> a head), forward, recomputed forward and
backward, in every layer.  An OVERLAY over `step.attn_dense_ms` (the
product) and `step.unscoped_ms` (the rest), not a part beside them.  The
class's scopes file names the scopes under `gate_parts`;
`program.split_ms` runs with those rules as it does for
`step.attn_latent_ms`.  Device 0, ms per optimizer step, a TOTAL.  A
model class whose scopes file has no `gate_parts`, or a program whose
step holds no such scope, reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.attn_gate_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("gate_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("attn_gate") or None
