"""`trainer.data_stall_ms` in a cell that saves: the loop blocked on the
next batch per step — its host-to-device copy shares the link with the
drain's copy off the device."""

from benchmark import cells

NAME, UNIT, SOURCE = "ckpt.data_stall_ms", "ms", "program_span"
LAYER, MOVES = "checkpoint", "saving_tokens_per_s"


def read(trace, events, ledgers, cell):
    return cells.load_module("layer_metrics", "trainer.data_stall_ms").read(
        trace, events, ledgers, cell)
