"""Device time of the recurrence's own share of `step.linattn_ms`: ops
scoped `linear_attention/conv` (the short causal depthwise convolution
and its silu) and `linear_attention/delta` (the L2 norms, the chunked
gated delta rule: the within-chunk products, the triangular solve, the
chunk-to-chunk scan), and their gradients — what a kernel would replace;
the projections, the gates and the output norm are the rest of
`step.linattn_ms`.  The class's scopes file names it under
`linattn_parts`; `program.split_ms` runs with those rules as it does for
`step.ssm_scan_ms`.  Device 0, ms per optimizer step, a TOTAL.  A model
class whose scopes file has no `linattn_parts` reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.linattn_scan_ms", "ms", "device_trace"
LAYER, MOVES = "linear-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("linattn_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("linattn_scan") or None
