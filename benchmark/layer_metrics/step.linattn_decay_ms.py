"""Device time of what a decay a CHANNEL adds to a decay a head in front
of the recurrence, a part of `step.linattn_ms`: ops scoped
`linear_attention/f_proj` (the decay's own projection, hidden x heads x
128 where a decay a head has hidden x heads) and `linear_attention/decay`
(the safe gate's activation on (tokens, heads, 128) float32 numbers), and
their gradients.  The decay's running sums and every product they scale
are the recurrence's (`step.linattn_scan_ms`).  The class's scopes file
names it under `linattn_parts`; `program.split_ms` runs with those rules
as it does for `step.linattn_scan_ms`.  Device 0, ms per optimizer step,
a TOTAL.  A model class whose scopes file has no such part, or a program
whose step holds no such scope, reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.linattn_decay_ms", "ms", "device_trace"
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
    return (split or {}).get("linattn_decay") or None
