"""Device time of the state-space layers: ops whose scope lies under
`mamba` (a Mamba-2 mixer's two projections, its convolution, the chunked
scan, the gate and its grouped norm, and their gradients).  Device 0,
the ops inside train-step modules as `kernel.attn_ms` takes them, ms per
optimizer step, a TOTAL: forward, backward and — under remat — the
recomputed forward.  The part is the model class's to name (`ssm` in its
scopes file); a class without it reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "step.ssm_ms", "ms", "device_trace"
LAYER, MOVES = "state-space layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "ssm") or None
