"""How long the loop stands still AT a save: the median step interval
that holds a save boundary (bracketed by two device-synchronised
instants of the tap) minus `step.device_ms`.  What a save costs after
the boundary — the drain's copy off the device, the persist — is not in
it; `ckpt.save_cost_ms` has the whole."""

from benchmark import readers, xtrace

NAME, UNIT, SOURCE = "ckpt.stall_ms", "ms", "host_clock"
LAYER, MOVES = "checkpoint", "saving_tokens_per_s"


def read(trace, events, ledgers, cell):
    every = readers.save_cadence(cell)
    with_save, _ = readers.save_intervals(events, every)
    step_ms = xtrace.step_device_ms(trace) if trace else None
    if not with_save or not step_ms:
        return None
    return readers.median(with_save) * 1e3 - step_ms
