"""Driver `trainer_inproc`: the cell's Trainer in the benchmark's own
process — `Trainer(...).train()` once, the window inside it."""

from __future__ import annotations

import os

from benchmark import readers, worker


def run(cell: dict, args, run_dir: str, t_proc0: float) -> dict:
    traffic = cell["traffic"]
    traced = bool(args.trace)
    cadence = readers.save_cadence(cell)
    plan = {
        "mode": "window", "seed": args.seed, "trace": traced,
        "seconds": args.seconds,
        "untraced_steps": traffic["traced"]["untraced_steps"],
        "warm_steps": traffic["window"]["warm_steps"],
        "trace_steps": traffic["traced"]["trace_steps"],
        # the bracketing waits serve the per-layer metrics only: an
        # untraced run lets the loop run free across its saves
        "sync_every": (cadence if traced
                       and traffic["window"]["sync_at_saves"] else 0),
        "trace_dir": os.path.join(run_dir, "trace"),
    }
    events = worker.Events()
    events.add("proc_start", t=t_proc0)
    res = worker.train_process(cell, plan, run_dir, events)
    opened = next((e for e in res["events"] if e["ev"] == "open"), None)
    if opened is not None:
        events.add("window_open", t=opened["t_sync"])
    trace = res.pop("trace")
    # the checkpoint engine leaves its shm segment for a restart to find
    # (by design); a run in this process has none, so it goes here
    for path in worker.shm_leftovers(os.environ["DWT_JOB_NAME"]):
        os.unlink(path)
    return {"gens": {0: res}, "events": events.items, "trace": trace,
            "measured_gen": 0, "extra_ok": True, "notes": {},
            "leftovers": []}
