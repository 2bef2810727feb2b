"""The share of the lanes the gated delta rule's products run that the
model's widths do not ask for: `1 - delta_lanes_model / delta_lanes_run`,
as the step program counted them (`models/gated_delta.py`: the products
that meet a head's state over the key side and the value side as
`ops/delta_rule.py`'s route lays them, beside the 96 + 192 the model
asks), averaged over the logging boundaries inside the measured stretch.
0 on the `jax.numpy` routes, which pad no head; a kernel that laid keys
of 96 on a 128-lane slab would read 10%.  It is the PLAN's share, a
static number, and bounds `kernel.delta_roofline` from the plan's side.
Read from the same `trainer:step_metrics` span events as
`attn.padded_lanes_share`; a program without the counters, or a model
without a linear-attention layer, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "linattn.padded_lanes_share", "%", "program_counter"
LAYER, MOVES = "linear-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [1.0 - s["attrs"]["delta_lanes_model"]
              / s["attrs"]["delta_lanes_run"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get("delta_lanes_run")
              and "delta_lanes_model" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
