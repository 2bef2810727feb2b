"""Device time of differential attention's COMBINATION: every op scoped
`attention/diff` — lam from the four lambda vectors, a1 - lam * a2 of
the two maps the kernels wrote, the sub-norm over a pair's 128 lanes,
the (1 - lam0) scale, the turn back to the projections' layout — forward,
recomputed forward and backward, in every attention layer.  An OVERLAY
over `step.unscoped_ms`, not a part beside it; the maps themselves are
`kernel.attn_ms`'s.  The class's scopes file names the scope under
`diff_parts`; `program.split_ms` runs with those rules as it does for
`step.attn_gate_ms`.  Device 0, ms per optimizer step, a TOTAL.  A model
class whose scopes file has no `diff_parts`, or a program whose step
holds no such scope, reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.attn_diff_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("diff_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("attn_diff") or None
