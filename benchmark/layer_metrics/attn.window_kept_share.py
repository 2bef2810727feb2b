"""The share of the (query, key) pairs in the score tiles the windowed
attention layers' kernels compute that the window KEEPS:
`attn_pairs_kept / attn_pairs_computed`, as the step program counted
them (`models/attention.window_pairs`: the band's window x T - window x
(window - 1) / 2 pairs a head, over `ops/flash_attention.
causal_tile_count`'s tiles at the side they are counted in), averaged
over the logging boundaries inside the measured stretch.  What the
tile's grain costs: at a window of 512 under tiles of 512 a query block
runs 4 tiles for 1,024 x 512 kept pairs and it reads about 50%; at a
window of 4,096 it would read 88%.  The PLAN's share, a static number,
as `attn.window_tiles_share` is: what the kernels make of the tiles is
`kernel.attn_window_roofline`'s to say.  Read from the same
`trainer:step_metrics` span events as `attn.window_tiles_share`; a
program without the counters, or a model without a windowed layer that
sows them, reports nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "attn.window_kept_share", "%", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"]["attn_pairs_kept"] / s["attrs"]["attn_pairs_computed"]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get("attn_pairs_computed")
              and "attn_pairs_kept" in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
