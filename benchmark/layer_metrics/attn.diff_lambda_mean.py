"""The mean of differential attention's lam = exp(lq1 . lk1) - exp(lq2 .
lk2) + lam0 over the attention layers, as the step program counted it
(`attn_diff_lambda_mean`: each `models/phi4flash.DiffAttention` sows its
own, `collect_phi4flash_stats` takes the layers' mean), averaged over the
logging boundaries inside the measured stretch.  A seeded state reads
about the layers' mean lam0 (0.65 over layers 1, 17 and 19); a lam
driven to 0 is attention without the subtraction, and would show here
before it shows in the loss.  Read from the same `trainer:step_metrics`
span events as `attn.gate_mean`; a program without the counter, or a
model without such a layer, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "attn.diff_lambda_mean", "ratio", "program_counter"
LAYER, MOVES = "strategy -> step", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    means = [s["attrs"]["attn_diff_lambda_mean"] for s in program.setup_spans()
             if s["name"] == "trainer:step_metrics"
             and bounds[0] <= s["t_mono"] <= bounds[1]
             and "attn_diff_lambda_mean" in s["attrs"]]
    return sum(means) / len(means) if means else None
