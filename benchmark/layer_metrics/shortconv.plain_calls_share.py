"""The share of the gated short convolutions (every conv layer's, a
step) that ran the plain `jax.numpy` lines and no kernel:
`shortconv_plain_calls / shortconv_calls`, as the step program counted
them (`models/lfm2.py`: each mixer sows which lines its gates and its
filter ran; static numbers, as the attention's tiles are), averaged over
the logging boundaries inside the measured stretch.  100% today: no
kernel computes the gated form (`ops/short_conv.py`'s pair is
silu(conv(x) + bias)); a later one moves it, and `step.shortconv_gated_ms`
with it.  Read from the same `trainer:step_metrics` span events as
`moe.held_rows_share`; a program without the counters, or a model without
such a mixer, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "shortconv.plain_calls_share", "%", "program_counter"
LAYER, MOVES = "short-convolution layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"]["shortconv_plain_calls"]
              / s["attrs"]["shortconv_calls"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get("shortconv_calls")
              and "shortconv_plain_calls" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
