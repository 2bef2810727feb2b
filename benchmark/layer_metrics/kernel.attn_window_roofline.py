"""The windowed attention layers' share of their roofline: the least
time one chip could take for the FLOPs and bytes of the KEPT (query, key)
pairs of the windowed layers of one step (forward + backward, this
chip's share of the batch; from shapes, by the model class's
`window_attention_cost_per_step`: a band of window x T - window x
(window - 1) / 2 pairs a head, never the causal triangle; recomputation
not counted as useful) over `kernel.attn_window_ms`.  A kernel that
masked the entries below the window without skipping them would spend
the triangle's time on the band's work and read about half.  A model
class without the function, or a program without windowed kernels,
reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "kernel.attn_window_roofline", "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    cost_fn = getattr(model, "window_attention_cost_per_step", None)
    if cost_fn is None:
        return None
    ms = cells.load_module("layer_metrics", "kernel.attn_window_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    cost = cost_fn(cell["config"], cell["global_batch"])
    least = flops.roofline(cost["flops"] / cell["chips"],
                           cost["bytes"] / cell["chips"], flops.peaks(kind))
    return 100.0 * least["seconds"] * 1e3 / ms
