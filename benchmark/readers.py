"""Small helpers the metric readers share (events, windows, counts)."""

from __future__ import annotations

import statistics


def first(events: list, ev: str, gen: int | None = None):
    return next((e for e in events if e["ev"] == ev
                 and (gen is None or e.get("gen") == gen)), None)


def last(events: list, ev: str, gen: int | None = None):
    hit = None
    for e in events:
        if e["ev"] == ev and (gen is None or e.get("gen") == gen):
            hit = e
    return hit


def window(events: list):
    """(open, close) events of the measured window, or (None, None)."""
    return last(events, "open"), first(events, "close")


def window_end(events: list):
    """The mark that ends what a run measured: the traced stretch's end
    in a traced run, else the window's close."""
    return first(events, "trace_stop") or first(events, "close")


def window_delta(events: list, state: str) -> float | None:
    """Seconds the ledger credited to `state` between the window's open
    and its end (the marks carry the cumulative states)."""
    o, e = last(events, "open"), window_end(events)
    if o is None or e is None or "ledger" not in o or "ledger" not in e:
        return None
    return e["ledger"].get(state, 0.0) - o["ledger"].get(state, 0.0)


def window_steps(events: list) -> int:
    o, e = last(events, "open"), window_end(events)
    return e["step"] - o["step"] if o and e else 0


def window_saves(events: list, every: int) -> int:
    """Save boundaries the loop passed inside the window."""
    o, e = last(events, "open"), window_end(events)
    if o is None or e is None or not every:
        return 0
    return e["step"] // every - o["step"] // every


def measured_gen(events: list) -> int | None:
    """The generation the measured kill brought up."""
    kill = first(events, "kill")
    return kill["measured_gen"] if kill else None


def resumed(events: list):
    """The measured generation's first completed step, if it got there."""
    g = measured_gen(events)
    return first(events, "first_step_done", gen=g) if g is not None else None


def traced_busy_window(trace: dict, events: list) -> tuple:
    """(device busy seconds averaged over chips, window seconds) of a
    traced run: first op start to last op end — or, in a fault cell,
    from the kill (the chip is idle by construction until the resumed
    generation attaches) to the traced stretch's end, on the host clock."""
    from benchmark import xtrace

    busy, win = xtrace.busy_window_s(trace)
    kill, stop = first(events, "kill"), first(events, "trace_stop")
    if kill is not None and stop is not None:
        win = stop["t_sync"] - kill["t"]
    return busy, win


def measured(ledgers: dict) -> dict:
    """The record of the generation that ran the window (the newest)."""
    return ledgers[max(ledgers)] if ledgers else {}


def steps_run(rec: dict) -> int:
    """Optimizer steps the measured process dispatched, start to stop."""
    return max(0, int(rec.get("stopped_at", 0))
               - int(rec.get("first_data_step") or 0))


def save_cadence(cell: dict) -> int:
    """Steps between two saves of the cell's traffic (0 = none)."""
    return int(cell["traffic"]["training_args"].get("flash_stage_steps")
               or 0)


def save_intervals(events: list, every: int) -> tuple:
    """(intervals that hold a save, per-step intervals without one), in
    seconds, from the tap's device-synchronised stamps inside the
    window: `pre_save` at data(s) with s+1 a save boundary, `post_save`
    at data(s) with s the boundary just saved."""
    o, c = last(events, "open"), window_end(events)
    if o is None or not every:
        return [], []
    lo = o["t_sync"]
    hi = c["t_sync"] if c else float("inf")
    pre = {e["step"]: e["t_sync"] for e in events
           if e["ev"] == "pre_save" and lo <= e["t_sync"] <= hi}
    post = {e["step"]: e["t_sync"] for e in events
            if e["ev"] == "post_save" and lo <= e["t_sync"] <= hi}
    with_save = [post[s] - pre[s - 1] for s in sorted(post) if s - 1 in pre]
    without = [(pre[s + every - 1] - post[s]) / (every - 1)
               for s in sorted(post) if s + every - 1 in pre]
    return with_save, without


def save_cycles(events: list, every: int) -> list:
    """Seconds from one save's end to the next one's (`every` steps and
    one save), inside the window."""
    o, c = last(events, "open"), window_end(events)
    if o is None or not every:
        return []
    hi = c["t_sync"] if c else float("inf")
    post = {e["step"]: e["t_sync"] for e in events
            if e["ev"] == "post_save" and o["t_sync"] <= e["t_sync"] <= hi}
    return [post[s + every] - post[s] for s in sorted(post)
            if s + every in post]


def median(xs):
    return statistics.median(xs) if xs else None
