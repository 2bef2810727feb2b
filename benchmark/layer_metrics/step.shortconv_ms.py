"""Device time of the gated short-convolution mixers: every op owned by
the `short_conv/*` scopes of `models/lfm2.py` — `short_conv/in_proj` (the
product of the block's normalised input with a hidden x 3 hidden matrix),
`short_conv/gated` (B * X, the three-tap causal filter, C * c) and
`short_conv/out_proj` — forward, recomputed forward and backward, in
every conv layer.  A PART of the class's scopes file (`shortconv`), beside
`mlp` and `attn_dense`; `program.part_ms` reads it as it reads those.
Device 0, ms per optimizer step, a TOTAL.  A model class whose scopes
file has no such part, or a program whose step holds no such scope,
reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "step.shortconv_ms", "ms", "device_trace"
LAYER, MOVES = "short-convolution layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "shortconv") or None
