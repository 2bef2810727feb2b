"""Device time of the scan's own share of `step.ssm_ms`: ops scoped
`mamba/conv` (the short causal depthwise convolution and its silu) and
`mamba/ssd` (step sizes, decays, the chunked scan), and their gradients —
what a scan kernel would replace; the projections and the gate are the
rest of `step.ssm_ms`.  The class's scopes file names it under
`ssm_parts`; `program.split_ms` runs with those rules as it does for
`step.moe_experts_ms`.  Device 0, ms per optimizer step, a TOTAL.  A
model class whose scopes file has no `ssm_parts` reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.ssm_scan_ms", "ms", "device_trace"
LAYER, MOVES = "state-space layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("ssm_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get("ssm_scan") or None
