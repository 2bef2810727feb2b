"""Device time of what lies BETWEEN the gated short convolution's two
products, an overlay on `step.shortconv_ms`: ops owned by
`short_conv/gated` alone — B * X, the shifted sums of the three-tap
filter, C * c, and their gradients (the filter's among them) — forward,
recomputed forward and backward: what a kernel for the gated form would
replace.  A fusion that holds a projection's product is the PROJECTION's
(`analysis/hlo_scopes.owners`: the matmul decides what a fusion costs),
whatever of the gates the compiler fused into it, so this errs low and
`step.shortconv_ms` does not.  The class's scopes file names the scope
under `shortconv_parts`; `program.split_ms` runs with those rules as it
does for `step.linattn_scan_ms`.  Device 0, ms per optimizer step, a
TOTAL.  A model class whose scopes file has no `shortconv_parts`, or a
program whose step holds no such scope, reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.shortconv_gated_ms", "ms", "device_trace"
LAYER, MOVES = "short-convolution layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("shortconv_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("shortconv_gated") or None
