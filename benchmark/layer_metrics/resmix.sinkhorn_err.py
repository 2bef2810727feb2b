"""The largest deviation of a row or column sum of any sublayer's
`H_res` from 1 over a step's tokens, as the step program counted it
(`models/hyper_connection.py::sinkhorn_err`, sown by every block,
`resmix_sinkhorn_err` in the step's metrics): what `hc_sinkhorn_iters`
rounds leave.  The LARGEST over the logging boundaries inside the
measured stretch.  A Sinkhorn that stops early is a different result,
not a faster one: this is where it shows.  Read from the same
`trainer:step_metrics` span events as `moe.held_rows_share`; a program
without the event, or a model with one residual lane, reports
nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "resmix.sinkhorn_err", "abs", "program_counter"
LAYER, MOVES = "residual path", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    inside = [s["attrs"]["resmix_sinkhorn_err"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and "resmix_sinkhorn_err" in s["attrs"]]
    return max(inside) if inside else None
