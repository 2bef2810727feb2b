"""The share of the KDA mixers' decay channels (heads x 128 a token, all
layers) whose log-decay lies within 1% of the lower bound
(`kda_lower_bound`: g <= 0.99 x -5, the safe gate's sigmoid over 0.99),
as the step program counted it (`kda_decay_floor_share`:
`models/kda.collect_kda_stats`), averaged over the logging boundaries
inside the measured stretch.  A channel at the floor forgets its state in
a step (alpha = e^-5); a gate that saturates there everywhere would show
here before it shows in the loss, and the floor is also what the chunked
form's sub-blocks are sized by.  Read from the same `trainer:step_metrics`
span events as `attn.gate_mean`; a program without the counter, or a
model without such a mixer, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "linattn.decay_floor_share", "%", "program_counter"
LAYER, MOVES = "linear-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"]["kda_decay_floor_share"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and "kda_decay_floor_share" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
