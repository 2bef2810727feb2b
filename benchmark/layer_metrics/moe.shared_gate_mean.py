"""The mean of the shared experts' gates, sigmoid(u w_s) over tokens and
layers, as the step program counted it (`moe_shared_gate_mean`: each
expert layer under `MoEConfig.shared_gate` sows its own mean,
`models/moe.collect_moe_stats` takes the layers' mean), averaged over
the logging boundaries inside the measured stretch.  A seeded state reads
about 0.5 (the logit has unit variance and no bias); a gate that closes
(0) takes the shared expert out of the model and one that opens (1)
makes it the ungated form, and either would show here before it shows in
the loss.  Read from the same `trainer:step_metrics` span events as
`moe.load_max_over_mean`; a program without the counter, or a model
whose shared expert is ungated, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "moe.shared_gate_mean", "ratio", "program_counter"
LAYER, MOVES = "expert layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    means = [s["attrs"]["moe_shared_gate_mean"]
             for s in program.setup_spans()
             if s["name"] == "trainer:step_metrics"
             and bounds[0] <= s["t_mono"] <= bounds[1]
             and "moe_shared_gate_mean" in s["attrs"]]
    return sum(means) / len(means) if means else None
