"""Ledger `data_stall` per optimizer step inside the window: the loop
blocked on the next batch (generation + host-to-device placement).  The
tap's own waits sit inside the same ledger window and are taken out."""

from benchmark import readers

NAME, UNIT, SOURCE = "trainer.data_stall_ms", "ms", "program_span"
LAYER, MOVES = "trainer loop", "tokens_per_s"


def read(trace, events, ledgers, cell):
    stall = readers.window_delta(events, "data_stall")
    steps = readers.window_steps(events)
    if stall is None or not steps:
        return None
    o, e = readers.last(events, "open"), readers.window_end(events)
    tap = e["tap_overhead_s"] - o["tap_overhead_s"]
    return max(0.0, stall - tap) / steps * 1e3
