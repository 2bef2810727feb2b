"""The mean of the attention layers' output gates, sigmoid(h @ g_proj)
over heads, tokens and layers, as the step program counted it
(`attn_gate_mean`: each gated `LlamaAttention` sows its own mean,
`models/attention.collect_attention_stats` takes the layers' mean),
averaged over the logging boundaries inside the measured stretch.  A
seeded state reads about 0.5 (the logits have unit variance and no
bias); where training drives it is the model's to say, and a gate that
closes (0) or opens (1) everywhere would show here before it shows in
the loss.  Read from the same `trainer:step_metrics` span events as
`moe.load_max_over_mean`; a program without the counter, or a model
without a gated layer, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "attn.gate_mean", "ratio", "program_counter"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    means = [s["attrs"]["attn_gate_mean"] for s in program.setup_spans()
             if s["name"] == "trainer:step_metrics"
             and bounds[0] <= s["t_mono"] <= bounds[1]
             and "attn_gate_mean" in s["attrs"]]
    return sum(means) / len(means) if means else None
