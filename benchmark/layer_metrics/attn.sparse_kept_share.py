"""The share of the causal (query, key) pairs the sparse attention
KEEPS: `attn_sparse_kept / attn_sparse_causal`, all layers, as the step
program counted them from its own choice (`ops/sparse_attention.py`: the
kept pairs of the step's mask, tile by tile; `models/attention.
collect_attention_stats`), averaged over the logging boundaries inside
the measured stretch.  min(topk, t + 1) keys a query: 23.4% at T =
16,384 under topk 2,048, whatever the indexer chooses — a count that
reads anything else says the choice is not the rule's.  What the
mathematics asks of the attention kernels, beside what they compute
(`attn.sparse_tiles_run_share`).  Read from the same
`trainer:step_metrics` span events as `moe.held_rows_share`; a program
without the counters, or a model without such a layer, reports
nothing."""

from benchmark import program

NAME, UNIT, SOURCE = "attn.sparse_kept_share", "%", "program_counter"
LAYER, MOVES = "sparse-attention layer", "tokens_per_s"


def share(events, over: str, under: str):
    """100 x mean of `over` / `under` of the window's step metrics, or
    None where no boundary carries both."""
    bounds = program.window_bounds(events)
    if bounds is None:
        return None
    shares = [s["attrs"][over] / s["attrs"][under]
              for s in program.setup_spans()
              if s["name"] == "trainer:step_metrics"
              and bounds[0] <= s["t_mono"] <= bounds[1]
              and s["attrs"].get(under) and over in s["attrs"]]
    return 100.0 * sum(shares) / len(shares) if shares else None


def read(trace, events, ledgers, cell):
    return share(events, "attn_sparse_kept", "attn_sparse_causal")
