"""Device time of the gated memory units: ops whose scope lies under
`gmu` (the gate's projection of the block's input, its silu, the product
with the memory an earlier layer's scan handed on, the output
projection, and their gradients — the memory's cotangent among them).
Device 0, the ops inside train-step modules as `kernel.attn_ms` takes
them, ms per optimizer step, a TOTAL: forward, backward and — under
remat — the recomputed forward.  The part is the model class's to name
(`gmu` in its scopes file); a class without it reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "step.gmu_ms", "ms", "device_trace"
LAYER, MOVES = "state-space layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "gmu") or None
