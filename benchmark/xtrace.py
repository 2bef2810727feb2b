"""From a profiler trace to numbers: the benchmark's own reducer.

`load(trace_dir)` reads the newest `.xplane.pb` under a `jax.profiler`
trace directory with `jax.profiler.ProfileData` and keeps, per TPU
device plane, two lines only — `XLA Modules` (one event per executed
program) and `XLA Ops` (one event per device operation) — plus the host
annotations the benchmark itself wrote (`bench.*`).  A TPU device plane
lays the same device time out on several more lines (Steps, Async XLA
Ops, TC Overlay); summing them would count every second more than once
(PERF.md, PR 22 finding 7).

The result is a plain dict ("compact trace"), which is also what the
tests keep a small recorded copy of:

    {"devices": {"<id>": {"modules": [[name, start_ns, dur_ns], ...],
                          "ops":     [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

Op names are cut to the HLO instruction's own name (`%fusion.12`), which
is what the event name holds before ` = `.  The reductions below use
only what is sound today: module durations, named kernels (`dwt_fa_*`),
collective instruction names, and the union of op intervals.  There is
NO category split here on purpose: a fusion is named `%fusion.N`
whatever it holds.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def instruction_name(name: str) -> str:
    """`%fusion.12 = f32[8]{0} fusion(...)` -> `fusion.12`."""
    head = name.split(" = ", 1)[0] if name.startswith("%") else name
    return head.lstrip("%").strip()


def load(trace_dir: str) -> dict:
    """Compact trace of the newest profiler run under `trace_dir`."""
    from jax.profiler import ProfileData

    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*")))
    if not runs:
        raise FileNotFoundError(f"no profiler run under {trace_dir}")
    out = {"devices": {}, "host": []}
    for pb in sorted(glob.glob(os.path.join(runs[-1], "*.xplane.pb"))):
        for plane in ProfileData.from_file(pb).planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                dev = out["devices"].setdefault(
                    m.group(1), {"modules": [], "ops": []})
                for line in plane.lines:
                    key = {"XLA Modules": "modules",
                           "XLA Ops": "ops"}.get(line.name)
                    if key is None:
                        continue
                    for ev in line.events:
                        name = ev.name if key == "modules" \
                            else instruction_name(ev.name)
                        dev[key].append([name, float(ev.start_ns),
                                         float(ev.duration_ns)])
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            out["host"].append([ev.name, float(ev.start_ns),
                                                float(ev.duration_ns)])
    for dev in out["devices"].values():
        dev["modules"].sort(key=lambda e: e[1])
        dev["ops"].sort(key=lambda e: e[1])
    out["host"].sort(key=lambda e: e[1])
    return out


# ------------------------------------------------------------ reductions


def device_ids(trace: dict) -> list:
    return sorted(trace["devices"], key=int)


def step_modules(trace: dict, device: str, match: str = "train_step"
                 ) -> list:
    """The train-step executions on one device, in time order.  The
    Trainer's jitted step is the module whose name holds `match`; should
    a refactor rename it, the module that took most device time stands
    in (a traced window of a training loop holds little else)."""
    mods = trace["devices"][device]["modules"]
    hits = [m for m in mods if match in m[0]]
    if hits:
        return hits
    total = {}
    for name, _, dur in mods:
        base = name.split("(")[0]
        total[base] = total.get(base, 0.0) + dur
    if not total:
        return []
    top = max(total, key=total.get)
    return [m for m in mods if m[0].split("(")[0] == top]


def step_device_ms(trace: dict, match: str = "train_step") -> float | None:
    """Median device duration of one optimizer step (`XLA Modules`),
    the slowest device's median where there are several."""
    meds = []
    for dev in device_ids(trace):
        durs = [m[2] for m in step_modules(trace, dev, match)]
        if durs:
            meds.append(statistics.median(durs) / 1e6)
    return max(meds) if meds else None


def ops_in_steps(trace: dict, device: str, match: str = "train_step"
                 ) -> tuple:
    """(ops that ran inside a train-step execution, number of steps)."""
    steps = step_modules(trace, device, match)
    spans = [(m[1], m[1] + m[2]) for m in steps]
    ops, i = [], 0
    for op in trace["devices"][device]["ops"]:
        while i < len(spans) and op[1] >= spans[i][1]:
            i += 1
        if i < len(spans) and op[1] >= spans[i][0]:
            ops.append(op)
    return ops, len(steps)


def per_step_ms(trace: dict, prefixes: tuple, device: str | None = None,
                match: str = "train_step") -> float | None:
    """Summed device duration, per optimizer step, of the ops whose
    instruction name starts with one of `prefixes` (a TOTAL: time that
    overlaps other ops is counted in full)."""
    dev = device or (device_ids(trace) or [None])[0]
    if dev is None:
        return None
    ops, n = ops_in_steps(trace, dev, match)
    if not n:
        return None
    tot = sum(o[2] for o in ops if o[0].startswith(prefixes))
    return tot / n / 1e6


def union_ns(intervals: list) -> float:
    """Length of the union of [start, start+dur) intervals."""
    busy, end = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy


def busy_window_s(trace: dict, t0_ns: float | None = None,
                  t1_ns: float | None = None) -> tuple:
    """(busy_s averaged over devices, window_s).  Busy is the union of
    the op intervals on `XLA Ops`; the window is [t0, t1] when given
    (ops are clipped to it), else first op start to last op end over all
    devices."""
    devs = device_ids(trace)
    all_ops = [o for d in devs for o in trace["devices"][d]["ops"]]
    if not all_ops:
        return 0.0, 0.0
    lo = min(o[1] for o in all_ops) if t0_ns is None else t0_ns
    hi = max(o[1] + o[2] for o in all_ops) if t1_ns is None else t1_ns
    busy = []
    for d in devs:
        clipped = []
        for _, start, dur in trace["devices"][d]["ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                clipped.append((a, b - a))
        busy.append(union_ns(clipped))
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def top_device_ops(trace: dict, k: int = 10, device: str | None = None
                   ) -> list:
    """[[instruction name, seconds], ...] — the k ops with most summed
    device time on one device."""
    dev = device or (device_ids(trace) or [None])[0]
    if dev is None:
        return []
    tot = {}
    for name, _, dur in trace["devices"][dev]["ops"]:
        tot[name] = tot.get(name, 0.0) + dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s / 1e9] for n, s in top]


def idle_gaps(trace: dict, k: int = 10, device: str | None = None) -> list:
    """[[what the host was doing, seconds], ...] — the k longest gaps
    between device ops on one device.  A gap is named after the
    benchmark's own host annotation that covers most of it
    (`host:bench.data`, ...), else after the ops on either side."""
    dev = device or (device_ids(trace) or [None])[0]
    if dev is None:
        return []
    ops = trace["devices"][dev]["ops"]
    gaps, end, prev = [], None, ""
    for name, start, dur in ops:
        if end is not None and start > end:
            gaps.append((start - end, end, start, prev, name))
        if end is None or start + dur > end:
            end, prev = start + dur, name
    gaps.sort(reverse=True)
    out = []
    for length, a, b, before, after in gaps[:k]:
        label, best = f"{before}->{after}", None
        for hname, hs, hd in trace.get("host", []):
            cover = min(b, hs + hd) - max(a, hs)
            # the annotation has to be ABOUT this gap: it covers most of
            # it and is not many times longer (a wait that spans ten
            # steps explains none of the gaps between them)
            if cover >= 0.5 * length and hd <= 4 * length and \
                    (best is None or hd < best):
                label, best = f"host:{hname}", hd
        out.append([label, length / 1e9])
    return out
