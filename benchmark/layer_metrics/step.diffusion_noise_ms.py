"""Device time of a block-diffusion model's NOISING: every op owned by
the scope `diffusion/noise` — the sequences' hashes, the draw of t and m
(`jax.random` on the device), the noised copy, the two copies laid end
to end and the weights m / t — forward and backward, ms per optimizer
step.  What making the objective's inputs costs beside the trunk that
runs on them; it should stay a rounding of the step.  The class's scopes
file names the scope under `diffusion_parts`; `program.split_ms` runs
with those rules as it does for `step.attn_index_ms`.  Device 0, a
TOTAL.  A model class whose scopes file has no `diffusion_parts`, or a
program whose step holds no such scope, reports nothing."""

import json
import os

from benchmark import cells, program

NAME, UNIT, SOURCE = "step.diffusion_noise_ms", "ms", "device_trace"
LAYER, MOVES = "block-diffusion layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    path = os.path.join(cells.HERE, "models",
                        cell["config"]["model_class"] + ".scopes.json")
    if not trace or not os.path.isfile(path):
        return None
    with open(path) as f:
        rules = json.load(f).get("diffusion_parts")
    table = program.scope_table()
    if rules is None or table is None \
            or not any("diffusion" in scope.split("/")
                       for scope in table.values()):
        return None
    split = program.split_ms(trace, table, rules)
    return split.get("diffusion_noise") if split else None
