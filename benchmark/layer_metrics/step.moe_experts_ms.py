"""Device time of the experts' grouped matmuls and gating product: ops
whose scope lies under `moe/experts`, and the kernels the TPU compiler
puts in place of `lax.ragged_dot` (`ragged-dot-*`: it writes its own
name over the traced one, so `analysis/hlo_scopes.py` can only call them
`ragged_dot`).  One half of `step.mlp_ms` on a class whose feed-forward
is an expert layer; `step.moe_route_ms` is the other, and the two sum to
it: the class's scopes file splits its `mlp` part once more under
`moe_parts`, and `moe_part_ms` here runs `program.split_ms` with those
rules.  Device 0, the ops inside train-step modules as `kernel.attn_ms`
takes them, ms per optimizer step, a TOTAL: forward, backward and —
under remat — the recomputed forward.  A model class whose scopes file
has no `moe_parts` reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.moe_experts_ms", "ms", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s"


def moe_part_ms(trace, cell, part):
    """One of the class's `moe_parts`, as `program.part_ms` reads one of
    its `parts`; None where there is no trace, no such key, no scope
    table, or no op under the part."""
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("moe_parts")
    table = program.scope_table()
    if rules is None or table is None:
        return None
    split = program.split_ms(trace, table, rules)
    return (split or {}).get(part) or None


def read(trace, events, ledgers, cell):
    return moe_part_ms(trace, cell, "moe_experts")
