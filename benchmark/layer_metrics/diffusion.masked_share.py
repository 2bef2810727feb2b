"""The share of the data tokens the diffusion objective's draw MASKS:
the step program's `diffusion_masked_share` (the mean of m over the
batch, `models/sdar.py`), averaged over the logging boundaries inside
the measured stretch.  The loss's support: under one t a block, uniform
on [1e-3, 1], about 50%; a count that reads anything else says the draw
is not the schedule's.  Read from the same `trainer:step_metrics` span
events as `moe.held_rows_share`; a program without the counter, or a
model with no such objective, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "diffusion.masked_share", "%", "program_counter"
LAYER, MOVES = "block-diffusion layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"]["diffusion_masked_share"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and "diffusion_masked_share" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
