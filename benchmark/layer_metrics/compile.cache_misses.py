"""`auto.compile_cache.counters.misses` of the resumed generation at the
window's end: programs the persistent cache did not hold.  0 after a
cell's first run."""

from benchmark import readers

NAME, UNIT, SOURCE = "compile.cache_misses", "count", "program_counter"
LAYER, MOVES = "compile cache", "resume_s"


def read(trace, events, ledgers, cell):
    g = readers.measured_gen(events)
    end = readers.window_end(events)
    if g is None or end is None or end.get("gen") != g:
        return None
    return float(end["cache_misses"])
