"""The gated short-convolution mixers' share of their roofline: the
least time one chip could take for the mixers' work of one step — the
LONGER of their two products' FLOPs at the MXU's peak and their least
bytes at the HBM peak (the matrices once a phase, the normalised input
read, the mixer's output written; forward + recomputed forward +
backward at twice the forward; every conv layer, this chip's share of
the batch), from the configuration and the batch ALONE by the model
class's `shortconv_cost_per_step`, never from the program's choices —
over `step.shortconv_ms`.  The WHOLE mixer on both sides: no fusion of a
gate into a product can move time out of the denominator, and the work
is the same whatever later implements it.  The time holds the gates'
and the filter's passes, the count none of them, so the share errs low,
never high.  At the published sizes the products bound it (22 ms a layer
against 1.5 ms of bytes at 32,768 tokens).  A model class without
`shortconv_cost_per_step` reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "shortconv.roofline", "%", "device_trace"
LAYER, MOVES = "short-convolution layer", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "shortconv_cost_per_step", None)
    if cost_fn is None:
        return None
    ms = cells.load_module("layer_metrics", "step.shortconv_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    cost = cost_fn(cell["config"], cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
