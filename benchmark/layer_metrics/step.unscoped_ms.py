"""Device time of every other op of the step that is neither a `dwt_fa_*`
kernel nor named as a collective: norms, embeddings, residual adds, the
reshapes around the attention kernel, copies, and whatever lost its
name.  The check on the other four parts: if this is large the naming is
not done.  Device 0, the ops inside train-step modules as
`kernel.attn_ms` takes them, ms per optimizer step, a TOTAL.  The scope
of each op comes from the compiled step's own text
(`benchmark/program.py`, `analysis/hlo_scopes.py`); forward, backward
and — under remat — the recomputed forward all count in their part."""

from benchmark import program

NAME, UNIT, SOURCE = "step.unscoped_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "unscoped")
