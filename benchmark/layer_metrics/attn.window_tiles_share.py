"""The share of a causal call's score tiles that the windowed attention
layers' kernels compute: `attn_tiles_window / attn_tiles_causal`, as the
step program counted them (`models/attention.window_tiles`: both from
`ops/flash_attention.causal_tile_count`, the count the kernels' grids
are planned from, at the kernels' own blocks), averaged over the logging
boundaries inside the measured stretch.  Blocks wholly below the window
are no grid step and blocks a diagonal crosses run the tiles it leaves,
so at a window of 4,096 in 16,384 it reads 47.7%.  It is the PLAN's
share, a static number: a kernel that masked what the plan skips would
read the same here and show in `kernel.attn_window_ms` and
`kernel.attn_window_roofline` instead.  Read from the same
`trainer:step_metrics` span events as `moe.load_max_over_mean`; a
program without the counters, or a model without a windowed layer,
reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "attn.window_tiles_share", "%", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"]["attn_tiles_window"] / s["attrs"]["attn_tiles_causal"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get("attn_tiles_causal")
              and "attn_tiles_window" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
