"""Model FLOP/s utilisation on device time: FLOPs one token requires
(forward + backward, causal attention counted once, the tied head
through `wte`, recomputation NOT counted) x tokens per step /
`step.device_ms` / published bf16 peak / chips."""

from benchmark import cells, flops, readers, xtrace

NAME, UNIT, SOURCE = "device.mfu_pct", "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s"


def read(trace, events, ledgers, cell):
    ms = xtrace.step_device_ms(trace) if trace else None
    if not ms:
        return None
    kind = readers.measured(ledgers)["device"]["kind"]
    model = cells.load_module("models", cell["config"]["model_class"])
    per_step = model.train_flops_per_token(cell["config"]) \
        * cell["global_batch"] * cell["seq_len"]
    return 100.0 * per_step / (ms / 1e3) \
        / flops.peaks(kind)["bf16_flops_per_s"] / cell["chips"]
