"""What a metric reader takes from INSIDE the program (PR 24).

The in-process driver (`drivers/trainer_inproc.py`) runs the Trainer in
the benchmark's own process, so a reader's `read(trace, events, ledgers,
cell)` runs where the program's process-global telemetry lives, as
`worker.py` already reads `get_ledger()` and `compile_cache.counters`:

- `telemetry.spans`: the span buffer (`trainer:build`, `accelerate:*`,
  `ckpt:*`) and the per-step ring (`trainer:iteration` and its children,
  `pump:*`), each record with a start on `time.monotonic()` — the clock
  of `events` — so a reader cuts them to the window (`open.t_sync` ..
  `window_end.t_sync`) or to set-up (ended before `open`);
- `auto.compile_cache.durations`: one record per `jax.monitoring`
  duration event (`jax:trace`, `jax:lower`, `jax:backend_compile`) with
  the function's name;
- `telemetry.perf.step_executables()`: the `Compiled` of the step
  program that ran (found again in JAX's in-memory caches when asked
  for, here, after the run), whose text
  `analysis.hlo_scopes.scope_table` turns into {instruction name:
  scope}.  The compact trace keeps instruction
  names only, so the table is what says which fusion is the MLP's.
  Which scope names are "the MLP's" is the model class's to say, in
  `models/<model_class>.scopes.json` beside its `models/<model_class>.py`:
  a class with other module names (models/llama.py: `attention`,
  `feed_forward`) adds its file and edits none.

Everything here answers None where the program has no such buffer —
the parent commit of PR 24 has none of them, a driver whose Trainer
runs in another process sees empty ones — and the reader then leaves
its metric out.  Nothing is imported that the run has not imported: a
module of the program that is not in `sys.modules` was not used, and
has nothing to read.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark import cells, readers, xtrace

_PKG = "dlrover_wuqiong_tpu."
STEP_FUNCTIONS = ("train_step", "fused_train_step")
UNSCOPED = "unscoped"  # what no part of the model class's rules claims


def _module(name: str):
    return sys.modules.get(_PKG + name)


def window_bounds(events: list):
    """(open, end) of the measured stretch on `time.monotonic()`."""
    o, e = readers.last(events, "open"), readers.window_end(events)
    if o is None or e is None:
        return None
    return o["t_sync"], e["t_sync"]


def step_spans() -> list:
    """The per-step ring, oldest first (dicts: name, t_mono, dur_s,
    span_id, parent_span, trace_id, tid)."""
    mod = _module("telemetry.spans")
    snap = getattr(mod, "hot_spans_snapshot", None)
    return snap() if snap else []


def setup_spans() -> list:
    """The span buffer's records that carry a monotonic start."""
    mod = _module("telemetry.spans")
    snap = getattr(mod, "spans_snapshot", None)
    return [s for s in (snap() if snap else []) if "t_mono" in s]


def _inside(rec: dict, lo: float, hi: float) -> bool:
    return rec["t_mono"] >= lo and rec["t_mono"] + rec["dur_s"] <= hi


def window_ms_per_step(events: list, name: str):
    """Summed duration of the per-step spans `name` that lie inside the
    window, per optimizer step, in ms."""
    bounds, steps = window_bounds(events), readers.window_steps(events)
    if bounds is None or not steps:
        return None
    hits = [s["dur_s"] for s in step_spans()
            if s["name"] == name and _inside(s, *bounds)]
    return sum(hits) / steps * 1e3 if hits else None


def loop_self_ms(events: list, name: str = "trainer:iteration"):
    """`name`'s duration minus what its direct children cover, per
    optimizer step inside the window, in ms: the loop's own work."""
    bounds, steps = window_bounds(events), readers.window_steps(events)
    if bounds is None or not steps:
        return None
    spans = step_spans()
    parents = {s["span_id"]: s["dur_s"] for s in spans
               if s["name"] == name and _inside(s, *bounds)}
    if not parents:
        return None
    covered = sum(s["dur_s"] for s in spans
                  if s["parent_span"] in parents)
    return max(0.0, sum(parents.values()) - covered) / steps * 1e3


def setup_span_s(events: list, name: str):
    """Seconds inside spans `name` that ended before the window opened."""
    o = readers.last(events, "open")
    if o is None:
        return None
    hits = [s["dur_s"] for s in setup_spans()
            if s["name"] == name and s["t_mono"] + s["dur_s"] <= o["t_sync"]]
    return sum(hits) if hits else None


def setup_step_durations_s(events: list, names: tuple):
    """Seconds JAX spent, before the window opened, in the `names`
    phases (`jax:trace`, ...) of the step program's own function."""
    o = readers.last(events, "open")
    mod = _module("auto.compile_cache")
    recs = getattr(mod, "durations", None)
    if o is None or recs is None:
        return None
    hits = [r["dur_s"] for r in list(recs)
            if r["name"] in names
            and r["fun_name"].removeprefix("jit(").removesuffix(")")
            in STEP_FUNCTIONS
            and r["t_mono"] + r["dur_s"] <= o["t_sync"]]
    return sum(hits) if hits else None


# ------------------------------------------------------ the scope split

_table = None  # the text of a 124M step is tens of MB: parse it once


def scope_table():
    """{instruction name: scope} of the step program that ran, or None.
    Where the process kept several (a fused-K or variant cutover), the
    one kept last: the program running when the trace was taken."""
    global _table
    kept = getattr(_module("telemetry.perf"), "step_executables", None)
    if _table is None and kept is not None:
        from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
            scope_table as parse,
        )

        t0 = time.monotonic()
        programs = kept()
        t1 = time.monotonic()
        if programs:
            _table = parse(list(programs.values())[-1].as_text())
            print(f"benchmark: the step's executable found again in "
                  f"{(t1 - t0) * 1e3:.1f} ms, its text read into "
                  f"{len(_table)} scopes in {time.monotonic() - t1:.1f} s",
                  file=sys.stderr)
    # an executable from before the scopes (the persistent cache does not
    # key on op metadata) would put the whole step under "unscoped"
    if not _table or "optimizer" not in _table.values():
        return None
    return _table


def part_rules(model_class: str):
    """{part: alternatives} from `models/<model_class>.scopes.json`, in
    the file's order, or None where the class has no such file: its
    cells then report no split, instead of a silent `unscoped`."""
    path = os.path.join(cells.HERE, "models", model_class + ".scopes.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)["parts"]


def part_of(scope: str, rules: dict) -> str:
    """Which part a scope (`bwd/GPT/h/mlp/c_fc`) belongs to: the first
    with an alternative whose every component is on the path."""
    on_path = set(scope.split("/"))
    for part, alternatives in rules.items():
        if any(on_path.issuperset(alt) for alt in alternatives):
            return part
    return UNSCOPED


def split_ms(trace: dict, table: dict, rules: dict,
             match: str = "train_step"):
    """{part: ms per step} on device 0 over the ops inside train-step
    modules, taken exactly as `kernel.attn_ms` takes them.  Totals.  The
    Pallas attention kernels (`dwt_fa_*`) and the ops named as
    collectives are in none of the parts: `kernel.attn_ms` and
    `step.collective_ms` hold them.  `unscoped` is the remainder."""
    devs = xtrace.device_ids(trace)
    if not devs:
        return None
    ops, n = xtrace.ops_in_steps(trace, devs[0], match)
    if not n:
        return None
    out = dict.fromkeys((*rules, UNSCOPED), 0.0)
    for name, _, dur in ops:
        if name.startswith("dwt_fa_") or \
                name.startswith(xtrace.COLLECTIVE_PREFIXES):
            continue
        out[part_of(table.get(name, ""), rules)] += dur
    return {k: v / n / 1e6 for k, v in out.items()}


def part_ms(trace, cell: dict, part: str):
    """One part of the split of a traced run, or None."""
    if not trace:
        return None
    rules = part_rules(cell["config"]["model_class"])
    table = scope_table()
    if rules is None or table is None:
        return None
    split = split_ms(trace, table, rules)
    return split.get(part) if split else None
