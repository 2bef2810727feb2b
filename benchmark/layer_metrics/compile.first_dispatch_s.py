"""Generation 2's ledger `compile`: its first dispatch of the step —
trace, lower, and the executable's load from the persistent cache."""

from benchmark import readers

NAME, UNIT, SOURCE = "compile.first_dispatch_s", "s", "program_span"
LAYER, MOVES = "compile cache", "resume_s"


def read(trace, events, ledgers, cell):
    g = readers.measured_gen(events)
    rec = ledgers.get(g) if g is not None else None
    if not rec or "ledger" not in rec:
        return None
    return rec["ledger"]["states"].get("compile")
