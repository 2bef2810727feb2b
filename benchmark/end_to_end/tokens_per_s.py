"""Optimizer steps completed on the device between the window's two
device-synchronised instants, times tokens per step, per second, per
chip.  Saves inside the window count against it."""

from benchmark import readers

NAME, UNIT, SOURCE = "tokens_per_s", "tokens/s/chip", "host_clock"


def read(trace, events, ledgers, cell):
    o, c = readers.window(events)
    if o is None or c is None or c["t_sync"] <= o["t_sync"]:
        return None
    steps = c["step"] - o["step"]
    return (steps * cell["global_batch"] * cell["seq_len"]
            / (c["t_sync"] - o["t_sync"]) / cell["chips"])
