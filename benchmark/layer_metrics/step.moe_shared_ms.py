"""Device time of the expert layers' SHARED expert: every op scoped
`moe/shared` — the always-on SwiGLU's three products, its activation,
and where the layer gates it the (hidden x 1) product, the sigmoid and
the multiply — forward, recomputed forward and backward, in every layer.
An OVERLAY over `step.moe_route_ms` (which holds everything under `moe`
that is no routed expert's), not a part beside it.  The class's scopes
file names the scope under `shared_parts`; `program.split_ms` runs with
those rules as it does for `step.attn_gate_ms`.  Device 0, ms per
optimizer step, a TOTAL.  A model class whose scopes file has no
`shared_parts`, or a program whose step holds no such scope, reports
nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.moe_shared_ms", "ms", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("shared_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("moe_shared") or None
