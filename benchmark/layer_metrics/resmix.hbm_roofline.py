"""The residual mixing's share of its memory roofline: the LEAST bytes
any implementation must move for the mixing of one step — each stream
element read once for the pre-mix, read once and written once for the
post/residual mix, the branch's input written and its output read,
forward + recomputed forward + backward at twice the forward; from the
configuration and the batch ALONE, by the model class's
`resmix_bytes_per_step`, never from the program's choices — over the
chip's HBM peak (`peaks.json`), divided by `step.resmix_ms`.  The time
holds the coefficients' product, the sigmoids and Sinkhorn as well as
the two mixes, so the share errs low, never high.  A model class
without `resmix_bytes_per_step` reports nothing."""

from benchmark import cells, flops, readers

NAME, UNIT, SOURCE = "resmix.hbm_roofline", "%", "device_trace"
LAYER, MOVES = "residual path", "tokens_per_s"


def read(trace, events, ledgers, cell):
    if not trace:
        return None
    model = cells.load_module("models", cell["config"]["model_class"])
    bytes_fn = getattr(model, "resmix_bytes_per_step", None)
    if bytes_fn is None:
        return None
    ms = cells.load_module("layer_metrics", "step.resmix_ms").read(
        trace, events, ledgers, cell)
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    least_s = bytes_fn(cell["config"], cell["global_batch"]) \
        / cell["chips"] / flops.peaks(kind)["hbm_bytes_per_s"]
    return 100.0 * least_s * 1e3 / ms
