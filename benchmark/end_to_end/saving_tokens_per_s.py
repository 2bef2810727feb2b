"""`tokens_per_s` of a cell that saves inside its window: the same count
of steps between the window's two device-synchronised instants, every
save's cost in it.  A metric of its own because its run-to-run spread
(2-4% here, 5.6% in the driver's check: a save costs the loop 20 ms or
1 s as the drain thread falls) is a thousand times the save-free
cells', and one metric has one bound.  PARKED (parked.json): that
spread is over half the largest bound the contract admits."""

from benchmark import cells

NAME, UNIT, SOURCE = "saving_tokens_per_s", "tokens/s/chip", "host_clock"


def read(trace, events, ledgers, cell):
    return cells.load_module("end_to_end", "tokens_per_s").read(
        trace, events, ledgers, cell)
