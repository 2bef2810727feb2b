"""The score tiles block-diffusion's attention COMPUTES over the tiles
that hold a kept pair: `attn_bd_tiles_run / attn_bd_tiles_live`, all
layers, as the step program counted them from its own plan
(`ops/block_attention.bd_tile_count`: the plan's pieces beside the live
tiles counted from the mask's rule, tile by tile), averaged over the
logging boundaries inside the measured stretch.  100% when no dead tile
is walked — 288 of the 1,024 tiles of 512 at 8,192 tokens; a causal
call over the 16,384 positions would read 183%, every tile 356%.  LOWER
is better, 100 the floor.  Read as `attn.sparse_kept_share` is; a
program without the counters reports nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "attn.bd_tiles_run_share", "%", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "attn.sparse_kept_share") \
        .share(events, "attn_bd_tiles_run", "attn_bd_tiles_live")
