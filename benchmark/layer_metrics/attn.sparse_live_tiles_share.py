"""The share of the causal score tiles (the attention kernels' block,
512 x 512) that hold AT LEAST ONE kept pair: `attn_sparse_live_tiles /
attn_sparse_tiles_causal`, all layers — DATA, counted in the step from
the step's own choice, not from shapes — averaged over the logging
boundaries inside the measured stretch.  What a grid that skips tiles
without a kept pair could at best leave: near 100% while the indexer is
untrained and its top-k scatter over every tile, lower as a trained one
concentrates them.  Read as `attn.sparse_kept_share` is; a program
without the counters reports nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "attn.sparse_live_tiles_share", "%", "program_counter"
LAYER, MOVES = "sparse-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "attn.sparse_kept_share") \
        .share(events, "attn_sparse_live_tiles", "attn_sparse_tiles_causal")
