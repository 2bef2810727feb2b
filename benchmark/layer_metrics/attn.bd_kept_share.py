"""The (query, key) pairs block-diffusion's mask KEEPS over the pairs in
the score tiles its attention computes: `attn_bd_pairs_kept /
attn_bd_pairs_computed`, all layers, as the step program counted them
(`ops/block_attention.bd_tile_count`), averaged over the logging
boundaries inside the measured stretch.  What a tile's grain costs a
staircase of step L: T^2 + T L kept pairs in 288 tiles of 512^2 is 88.9%
at 8,192 tokens and L = 4 (a smaller tile on the staircase would raise
it, a walked dead tile lowers it).  Read as `attn.sparse_kept_share` is;
a program without the counters reports nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "attn.bd_kept_share", "%", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "attn.sparse_kept_share") \
        .share(events, "attn_bd_pairs_kept", "attn_bd_pairs_computed")
