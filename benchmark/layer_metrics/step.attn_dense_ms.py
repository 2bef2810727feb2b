"""Device time of the attention block's dense layers: ops whose scope lies
under `attn/c_attn` or `attn/c_proj` (QKV and output projections, their
bias and weight gradients).  The attention kernels themselves are
`kernel.attn_ms`.  Device 0, the ops inside train-step modules as
`kernel.attn_ms` takes them, ms per optimizer step, a TOTAL.  The scope
of each op comes from the compiled step's own text
(`benchmark/program.py`, `analysis/hlo_scopes.py`); forward, backward
and — under remat — the recomputed forward all count in their part."""

from benchmark import program

NAME, UNIT, SOURCE = "step.attn_dense_ms", "ms", "device_trace"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return program.part_ms(trace, cell, "attn_dense")
