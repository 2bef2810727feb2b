"""Ledger `ckpt_stage` per save inside the window: the loop blocked in a
save on its own staging — the wait for the step in flight (the tap has
just synchronised, so one whole step), the on-device snapshot, the
hand-off to the drain thread."""

from benchmark import readers

NAME, UNIT, SOURCE = "ckpt.stage_ms", "ms", "program_span"
LAYER, MOVES = "checkpoint", "saving_tokens_per_s"


def read(trace, events, ledgers, cell):
    total, n = readers.window_delta(events, "ckpt_stage"), \
        readers.window_saves(events, readers.save_cadence(cell))
    return total / n * 1e3 if total is not None and n else None
