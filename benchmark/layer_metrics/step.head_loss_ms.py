"""Device time of the tied LM head and the loss: ops under the `head` and
`loss` scopes — logits, cross-entropy forward and backward, and the
head's share of `wte`'s gradient (the `bwd/.../head` matmul; the
embedding's scatter-add is `unscoped`).  Device 0, the ops inside
train-step modules as `kernel.attn_ms` takes them, ms per optimizer step, a
TOTAL.  The scope of each op comes from the compiled step's own text
(`benchmark/program.py`, `analysis/hlo_scopes.py`); forward, backward
and — under remat — the recomputed forward all count in their part."""

from benchmark import program

NAME, UNIT, SOURCE = "step.head_loss_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "head_loss")
