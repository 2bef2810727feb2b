"""The mean of the gated memory units' gates, silu(u W_1) over units,
tokens and channels, as the step program counted it (`gmu_gate_mean`:
each `models/phi4flash.GatedMemoryUnit` sows its own mean,
`collect_phi4flash_stats` takes the units'), averaged over the logging
boundaries inside the measured stretch.  A seeded state reads about 0.2
(silu of a unit-variance logit); a gate that closes everywhere (0) cuts
the cross-decoder off the memory, and would show here before it shows in
the loss.  Read from the same `trainer:step_metrics` span events as
`attn.gate_mean`; a program without the counter, or a model without a
unit, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "gmu.gate_mean", "ratio", "program_counter"
LAYER, MOVES = "state-space layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    means = [s["attrs"]["gmu_gate_mean"] for s in program.setup_spans()
             if s["name"] == "trainer:step_metrics"
             and bounds[0] <= s["t_mono"] <= bounds[1]
             and "gmu_gate_mean" in s["attrs"]]
    return sum(means) / len(means) if means else None
