"""The temporaries of the step program the loop dispatched, per device,
in GiB: `temp_bytes` of `telemetry.perf.step_memory()` — the
activations kept for the backward, a kernel's scratch, a residual kept
across a recomputation: what remat and a kept buffer trade.  Read as
`step.hbm_live_gib` reads its own key."""

from benchmark import cells

NAME, UNIT, SOURCE = "step.hbm_temp_gib", "GiB", "program_counter"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "step.hbm_live_gib") \
        .budget_gib("temp_bytes")
