"""What one save costs the loop in all: the median time from one save's
end to the next one's (N steps and one save, on device-synchronised
instants) minus N x `step.device_ms`.  Throughput in this cell is
N·B·T / (N·step + this)."""

from benchmark import readers, xtrace

NAME, UNIT, SOURCE = "ckpt.save_cost_ms", "ms", "host_clock"
LAYER, MOVES = "checkpoint", "saving_tokens_per_s"


def read(trace, events, ledgers, cell):
    every = readers.save_cadence(cell)
    cycles = readers.save_cycles(events, every)
    step_ms = xtrace.step_device_ms(trace) if trace else None
    if not cycles or not step_ms:
        return None
    return readers.median(cycles) * 1e3 - every * step_ms
