"""Device time of the linear-attention layers: ops whose scope lies under
`linear_attention` (a gated delta-rule mixer's seven projections, its two
gates, the convolution, the L2 norms, the chunked recurrence, the output
norm and its gate, and their gradients).  Device 0, the ops inside
train-step modules as `kernel.attn_ms` takes them, ms per optimizer
step, a TOTAL: forward, backward and — under remat — the recomputed
forward.  The part is the model class's to name (`linattn` in its scopes
file); a class without it reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "step.linattn_ms", "ms", "device_trace"
LAYER, MOVES = "linear-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "linattn") or None
