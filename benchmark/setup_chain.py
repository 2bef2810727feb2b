"""Set-up as a chain (PR 49): what the `setup.*` readers of the
partition take from the program's spans of its own start.

The program writes, on its main thread, `proc:boot` (the kernel's start
of the process -> `Trainer.__init__` entered), `trainer:build`, and
`trainer:train` (`train()` entered -> returned); between the last two
the program is not running: the caller's gap.  With the tap's `open`
that makes four parts which partition `setup_s`:

    boot | build | caller | warm-up
    proc:boot.start .. build.start .. build.end .. train.start .. open

`trainer:train`'s record is written when `train()` returns, AFTER the
window: it is found by its start, not by its end.  Everything answers
None where the program has no such span (a parent of PR 49) or the
Trainer ran in another process.

Parity: no reference counterpart — the reference times a restart from
its pods' logs; these readers take the program's own spans.
"""

from __future__ import annotations

from benchmark import program, readers

PHASES = ("jax:trace", "jax:lower", "jax:backend_compile")


def _last(name: str, before: float, end: bool):
    """The newest span `name` that began (or, with `end`, ended) before
    the instant `before` on `time.monotonic()`."""
    hits = [s for s in program.setup_spans() if s["name"] == name
            and s["t_mono"] + (s["dur_s"] if end else 0.0) <= before]
    return max(hits, key=lambda s: s["t_mono"]) if hits else None


def _open(events: list):
    o = readers.last(events, "open")
    return o["t_sync"] if o else None


def boot_s(events: list):
    """`proc:boot`: interpreter, imports, the backend's start, the
    caller's preparation."""
    t_open = _open(events)
    boot = None if t_open is None else _last("proc:boot", t_open, end=True)
    return boot["dur_s"] if boot else None


def caller_s(events: list):
    """`trainer:build`'s end -> `trainer:train`'s start: the program is
    not running (here: the seeded state, the check's one execution of
    the step, the plain reference)."""
    t_open = _open(events)
    if t_open is None:
        return None
    build = _last("trainer:build", t_open, end=True)
    train = _last("trainer:train", t_open, end=False)
    if build is None or train is None:
        return None
    return train["t_mono"] - (build["t_mono"] + build["dur_s"])


def warmup_s(events: list):
    """`trainer:train`'s start -> `open`: restore, first dispatch, the
    fused-K decision, the warm-up steps."""
    t_open = _open(events)
    train = None if t_open is None \
        else _last("trainer:train", t_open, end=False)
    return t_open - train["t_mono"] if train else None


def _covered_s(intervals: list) -> float:
    """Length of the union of (start, end) intervals."""
    covered, reach = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        covered += max(0.0, hi - max(lo, reach))
        reach = max(reach, hi)
    return covered


def other_programs_s(events: list):
    """Seconds JAX spent before `open` tracing, lowering and compiling
    (or loading) every function that is NOT the step's: init functions,
    eager helpers, the harness's reference.  Lies across the parts.
    Covered time, not a sum: a function traced inside another's trace
    has a record of its own within the outer one — and one inside the
    STEP's trace is the step's, and is left out."""
    t_open = _open(events)
    recs = getattr(program._module("auto.compile_cache"), "durations", None)
    if t_open is None or recs is None:
        return None
    step, other = [], []
    for r in list(recs):
        end = r["t_mono"] + r["dur_s"]
        if r["name"] not in PHASES or end > t_open:
            continue
        fun = r["fun_name"].removeprefix("jit(").removesuffix(")")
        (step if fun in program.STEP_FUNCTIONS else other).append(
            (r["t_mono"], end))
    other = [(lo, hi) for lo, hi in other
             if not any(a <= lo and hi <= b for a, b in step)]
    return _covered_s(other) if other else None


def parts_s(events: list):
    """(boot, build, caller, warm-up) in seconds, or None if one is
    missing."""
    parts = (boot_s(events), program.setup_span_s(events, "trainer:build"),
             caller_s(events), warmup_s(events))
    return None if None in parts else parts


def unnamed_s(events: list):
    """|`setup_s`' own interval of this run - the four parts|: what
    still lies under no span (the kernel's start of the process is some
    tens of ms before the harness's own first stamp)."""
    start = readers.first(events, "proc_start")
    opened = readers.first(events, "window_open")
    parts = parts_s(events)
    if start is None or opened is None or parts is None:
        return None
    return abs(opened["t"] - start["t"] - sum(parts))
