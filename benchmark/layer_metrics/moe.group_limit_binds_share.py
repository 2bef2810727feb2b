"""The share of tokens (all expert layers) whose k experts under the
router's group limit (`n_group` / `topk_group`: the k experts come from
the token's best groups alone) differ from the k an unlimited choice
would take, as the step program counted it (`moe_group_limit_binds`:
`models/moe.collect_moe_stats`; an expert outside the kept groups scores
over the least of the chosen), averaged over the logging boundaries
inside the measured stretch.  0% would mean the limit is idle and the
cell routes as the cells without one; the held experts' rows, and so the
routed experts' time, go with where the limit sends the tokens.  Read
from the same `trainer:step_metrics` span events as
`moe.held_rows_share`; a program without the counter, or a model whose
router has no group limit, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "moe.group_limit_binds_share", "%", "program_counter"
LAYER, MOVES = "expert layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"]["moe_group_limit_binds"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and "moe_group_limit_binds" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
