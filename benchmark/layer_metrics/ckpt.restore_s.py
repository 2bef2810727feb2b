"""Generation 2's ledger restore time, whichever tier served it
(`restore_shm` where the segment survived the kill)."""

from benchmark import readers

NAME, UNIT, SOURCE = "ckpt.restore_s", "s", "program_span"
LAYER, MOVES = "checkpoint", "resume_s"


def read(trace, events, ledgers, cell):
    g = readers.measured_gen(events)
    rec = ledgers.get(g) if g is not None else None
    if not rec or "ledger" not in rec:
        return None
    st = rec["ledger"]["states"]
    total = sum(st.get(k, 0.0) for k in
                ("restore_shm", "restore_replica", "restore_storage"))
    return total or None
