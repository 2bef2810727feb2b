"""The share of the causal score tiles the sparse attention's
implementation COMPUTES: `attn_sparse_tiles_run /
attn_sparse_tiles_causal`, all layers, averaged over the logging
boundaries inside the measured stretch.  100% for the masked form (every
causal tile computed, the choice applied as a mask inside the kernels);
a grid that skips tiles without a kept pair brings it down toward
`attn.sparse_live_tiles_share`, and `kernel.attn_ms` with it.  LOWER is
better.  Read as `attn.sparse_kept_share` is; a program without the
counters reports nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "attn.sparse_tiles_run_share", "%", "program_counter"
LAYER, MOVES = "sparse-attention layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "attn.sparse_kept_share") \
        .share(events, "attn_sparse_tiles_run", "attn_sparse_tiles_causal")
