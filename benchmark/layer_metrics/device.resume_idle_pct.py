"""Idle share of the chip from the SIGKILL to the end of generation 2's
traced stretch (its first steps): 1 - device busy time in generation
2's trace / host-clock seconds from the kill to the trace's stop.  The
chip is idle by construction until generation 2 attaches."""

from benchmark import readers

NAME, UNIT, SOURCE = "device.resume_idle_pct", "%", "device_trace"
LAYER, MOVES = "device", "resume_s"


def read(trace, events, ledgers, cell):
    if not trace or readers.first(events, "kill") is None \
            or readers.first(events, "trace_stop") is None:
        return None
    busy, win = readers.traced_busy_window(trace, events)
    return 100.0 * (1.0 - busy / win)
