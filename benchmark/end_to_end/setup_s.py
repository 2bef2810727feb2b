"""Process start to window open: backend start, Trainer construction,
seeded init, the correctness check, compilation (or its load from the
cache) and the warm-up steps; in a fault cell everything up to the
kill."""

from benchmark import readers

NAME, UNIT, SOURCE = "setup_s", "s", "host_clock"


def read(trace, events, ledgers, cell):
    start = readers.first(events, "proc_start")
    opened = readers.first(events, "window_open")
    if start is None or opened is None:
        return None
    return opened["t"] - start["t"]
