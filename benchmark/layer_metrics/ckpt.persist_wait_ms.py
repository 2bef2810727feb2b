"""Ledger `ckpt_persist` per save inside the window: the loop blocked in
a save waiting out EARLIER stagings (the drain chain at its bound)."""

from benchmark import readers

NAME, UNIT, SOURCE = "ckpt.persist_wait_ms", "ms", "program_span"
LAYER, MOVES = "checkpoint", "saving_tokens_per_s"


def read(trace, events, ledgers, cell):
    total, n = readers.window_delta(events, "ckpt_persist"), \
        readers.window_saves(events, readers.save_cadence(cell))
    return total / n * 1e3 if total is not None and n else None
