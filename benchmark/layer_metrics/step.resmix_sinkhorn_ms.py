"""Device time of Sinkhorn's part of the residual mixing: the ops under
`hc/sinkhorn` (`models/hyper_connection.py`: exp of the clipped 4 x 4
logits, `hc_sinkhorn_iters` rounds of a column and a row normalisation,
and their backward), all sublayers.  A part of `step.resmix_ms`, whose
file holds the split.  Small arrays (16 numbers a token) in many ops:
what it costs is launches and latency, not bytes.  Device 0, ms per
optimizer step, a TOTAL.  A model class whose scopes file has no
`resmix_parts` reports nothing."""

from benchmark import cells

NAME, UNIT, SOURCE = "step.resmix_sinkhorn_ms", "ms", "device_trace"
LAYER, MOVES = "residual path", "tokens_per_s"


def read(trace, events, ledgers, cell):
    resmix = cells.load_module("layer_metrics", "step.resmix_ms")
    split = resmix.resmix_parts_ms(trace, cell)
    return (split or {}).get("resmix_sinkhorn") or None
