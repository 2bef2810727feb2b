"""The arguments of the step program the loop dispatched, per device, in
GiB: `argument_bytes` of `telemetry.perf.step_memory()` — parameters,
optimizer moments, the batch (the donated state comes back as the
outputs that alias it).  Read as `step.hbm_live_gib` reads its own
key."""

from benchmark import cells

NAME, UNIT, SOURCE = "step.hbm_args_gib", "GiB", "program_counter"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "step.hbm_live_gib") \
        .budget_gib("argument_bytes")
