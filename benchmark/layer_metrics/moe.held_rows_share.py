"""The share of the router's assignments that fall on experts this chip
holds: `moe_rows_held / (moe_rows_held + moe_rows_absent)`, all expert
layers, as the step program counted them (`models/moe.py`, a layer told
which of the experts it holds), averaged over the logging boundaries
inside the measured stretch.  Under even routing it is held / published
experts (8 / 128 = 6.25%); the grouped matmuls' rows, and so the
routed experts' time, go with it.  Read from the same
`trainer:step_metrics` span events as `moe.load_max_over_mean`; a
program without the event, or a model that holds all of its experts,
reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "moe.held_rows_share", "%", "program_counter"
LAYER, MOVES = "expert layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    inside = [s["attrs"] for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and "moe_rows_held" in s["attrs"]
              and "moe_rows_absent" in s["attrs"]]
    shares = [a["moe_rows_held"] / (a["moe_rows_held"] + a["moe_rows_absent"])
              for a in inside if a["moe_rows_held"] + a["moe_rows_absent"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
