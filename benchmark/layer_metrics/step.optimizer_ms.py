"""Device time of the `optimizer` scope: gradient global norm, clip, AdamW
update and `apply_updates`.  Device 0, the ops inside train-step modules
as `kernel.attn_ms` takes them, ms per optimizer step, a TOTAL.  The
scope of each op comes from the compiled step's own text
(`benchmark/program.py`, `analysis/hlo_scopes.py`); forward, backward
and — under remat — the recomputed forward all count in their part."""

from benchmark import program

NAME, UNIT, SOURCE = "step.optimizer_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "optimizer")
