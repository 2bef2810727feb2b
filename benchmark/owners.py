"""Who owns each device op of the step that ran (PR 35).

`analysis.hlo_scopes.owners` reads the compiled step's text into
{instruction name: {"scope", "via", "kind", "members"}}: the scope
`program.scope_table` already splits the step by, how the table came to
say so (`via`: the op's own name, its matmul's, its members', its
reader's or producer's, or `none`), whether the op only MOVES data (a
copy, a transpose, an async copy's halves, a fusion of nothing else) and
the scopes a fusion's instructions carried.  Three overlays of the split
read it here — `step.move_ms`, `step.mixed_ms`, `step.unowned_ms` — over
the op set and per-step division of `program.split_ms`: device 0, the
ops inside train-step modules, `dwt_fa_*` and collectives left out.

None where the program has no `owners` (the parent commit of PR 35) or
kept no executable: the reader then leaves its metric out.

Parity: no reference counterpart — the reference reads per-op time off
`torch.profiler`'s module hierarchy (`analysis/hlo_scopes.py` says what
stands in for it here).
"""

from __future__ import annotations

import sys
import time

from benchmark import program

_owners = None  # the text of a step is tens of MB: parse it once


def owners():
    """The owners of the step program that ran, as `program.scope_table`
    finds its scopes (the executable kept last), or None."""
    global _owners
    kept = getattr(sys.modules.get(program._PKG + "telemetry.perf"),
                   "step_executables", None)
    if _owners is None and kept is not None:
        try:
            from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
                owners as parse,
            )
        except ImportError:
            return None
        programs = kept()
        if programs:
            t0 = time.monotonic()
            _owners = parse(list(programs.values())[-1].as_text())
            print(f"benchmark: the step's text read into {len(_owners)} "
                  f"owners in {time.monotonic() - t0:.1f} s",
                  file=sys.stderr)
    # as `program.scope_table`: an executable from before the scopes
    if not _owners or not any(e["scope"] == "optimizer"
                              for e in _owners.values()):
        return None
    return _owners


def ms_per_step(trace, pick):
    """Device ms per optimizer step of the ops whose owner entry `pick`
    accepts, a TOTAL, by `program.split_ms` itself (its op set, its
    division): the picked ops are the one part of a two-way split."""
    table = owners() if trace else None
    if table is None:
        return None
    marks = {name: "picked" for name, entry in table.items() if pick(entry)}
    split = program.split_ms(trace, marks, {"picked": [["picked"]]})
    return split["picked"] if split else None
