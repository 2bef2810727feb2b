"""benchmark/run.py — one cell, one run, one new process.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix, driver
and metric readers by name (`cells.py`), runs the driver once, and
prints as the LAST line of stdout one JSON object:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics (a short untraced stretch, then a `jax.profiler`
window of a few steps).  A line before the last one (`{"info": ...}`)
carries what the numbers were made from.  There is no CPU mode: off the
chip, on the wrong number of chips, or without the program in the
checkout, the exit code is not 0 and no result is printed.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, readers  # noqa: E402


def prepare_environment(run_dir: str) -> None:
    """Everything the program caches or leaves lands inside the checkout
    (or this run's TMPDIR): set before the program is imported."""
    # the program keeps its compile cache where JAX's own variable says,
    # else at <checkout>/.jax_cache — a FIXED path, part of the cache key
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["DWT_JOB_NAME"] = f"bm{os.getpid()}"  # names the shm segment
    # AF_UNIX paths cap at 107 bytes and a checkout can sit anywhere:
    # a path RELATIVE to the checkout (every process of a run keeps the
    # checkout as its working directory) is short wherever that is
    os.chdir(ROOT)
    os.environ["DWT_SOCKET_DIR"] = os.path.join(
        os.path.relpath(run_dir, ROOT), "s")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(run_dir, "tpu_logs"))
    os.environ.setdefault("DWT_WARM_POOL", "0")  # no child may want the chip
    # a machine's size cap on the cache (192 MiB on the chip tool's) is
    # under what one cell's programs take; an evicted step costs minutes
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def _ledgers(run: dict) -> dict:
    keep = ("ledger", "counters", "memory_peak_bytes", "first_data_step",
            "stopped_at", "device", "probes")
    return {int(g): {k: r[k] for k in keep if k in r}
            for g, r in run["gens"].items()}


def _loss_band(cell: dict, run: dict) -> dict:
    spec = cell["config"]["correct"]
    step, band = int(spec["loss_band_step"]), spec["loss_band"]
    seen = [r[1] for g in run["gens"].values() for r in g["losses"]
            if int(r[0]) == step]
    hold = readers.first(run["events"], "hold")
    seen += [r[1] for r in (hold or {}).get("losses", [])
             if int(r[0]) == step]
    out = {"step": step, "band": band, "seen": seen}
    if not band or band[0] >= band[1]:
        out["ok"], out["unset"] = True, True  # not fixed yet (calibration)
    else:
        # a run too short to reach the step (a traced run) checks nothing
        out["ok"] = all(band[0] <= v <= band[1] for v in seen)
    return out


def _slim(mark):
    return {k: v for k, v in mark.items() if k != "ledger"} if mark else None


def assemble(cell: dict, run: dict, traced: bool) -> tuple:
    """(info, result line) from one driver run."""
    events, trace = run["events"], run["trace"]
    ledgers = _ledgers(run)
    gen = run["measured_gen"]
    rec = run["gens"].get(gen) or run["gens"].get(str(gen))
    if rec is None:
        raise SystemExit("benchmark: the measured process left no record")
    kind, wanted = (("layer_metrics", cell["per_layer"]) if traced
                    else ("end_to_end", cell["end_to_end"]))
    metrics = {}
    for m in wanted:
        value = cells.load_module(kind, m["name"]).read(
            trace, events, ledgers, cell)
        if value is None:
            continue  # nothing to read: the metric is left out
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # ---- attempted / failed: steps, saves, kills
    o, c = readers.window(events)
    end = readers.window_end(events)
    # a fault cell has no open mark: the resumed generation's own steps
    steps = readers.window_steps(events) if o is not None and end \
        else readers.steps_run(rec)
    lo = o["t_sync"] if o else -math.inf
    losses = [r for g in run["gens"].values() for r in g["losses"]]
    nonfinite = sum(1 for r in losses
                    if r[2] >= lo and not math.isfinite(r[1]))
    saves = readers.window_saves(events, readers.save_cadence(cell))
    failed_saves = len(set(rec["disk_saves_wanted"])
                       - set(rec["disk_saves_committed"])) \
        + (1 if rec["save_error"] else 0)
    fault = cell["traffic"].get("fault") or {}
    kills = int(fault.get("kills", 0))
    resumed = readers.resumed(events) is not None
    failed_resumes = kills if kills and not resumed else 0
    attempted = steps + saves + kills
    failed = nonfinite + failed_saves + failed_resumes

    # ---- correct
    # the generation that drew the state from the seed checked it (in a
    # fault cell that generation is killed; its stamp stays)
    init_ev = readers.first(events, "init_checked")
    init_ok = bool(init_ev and init_ev["ok"])
    checks = [g["init_check"] for g in run["gens"].values()
              if g.get("init_check")]
    finite = all(g["all_finite"] for g in run["gens"].values())
    band = _loss_band(cell, run)
    compiled_in_window = None
    if o is not None and end is not None:
        compiled_in_window = (end["cache_hits"] + end["cache_misses"]
                              - o["cache_hits"] - o["cache_misses"])
    correct = bool(init_ok and finite and band["ok"] and run["extra_ok"]
                   and not run["leftovers"] and not failed
                   and compiled_in_window in (None, 0))

    device = dict(rec["device"])
    device["memory_peak_bytes"] = max(
        int(g["memory_peak_bytes"]) for g in run["gens"].values())
    line = {"correct": correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if traced and trace:
        from benchmark import xtrace

        device["busy_s"], device["window_s"] = readers.traced_busy_window(
            trace, events)
        line["breakdown"] = {"device_ops": xtrace.top_device_ops(trace),
                             "idle_gaps": xtrace.idle_gaps(trace)}
    info = {
        "cell": cell["name"], "traced": traced, "steps": steps,
        "saves_in_window": saves, "failed_saves": failed_saves,
        "save_error": rec["save_error"], "nonfinite_losses": nonfinite,
        "window": {"open": _slim(o), "close": _slim(c)},
        "init_check": checks or "by generation 1 (killed)",
        "loss_band": band, "compiled_in_window": compiled_in_window,
        "counters": {g: r["counters"] for g, r in run["gens"].items()},
        "ledger_states_s": {g: r["ledger"]["states"]
                            for g, r in run["gens"].items()},
        "probes": rec.get("probes"), "notes": run["notes"],
        "memory_stats": rec.get("memory_stats"),
        "leftovers": run["leftovers"],
        "stamps": [{k: e[k] for k in ("ev", "t", "gen", "step",
                                      "stop_trace_s") if k in e}
                   for e in events
                   if e["ev"] not in ("pre_save", "post_save")],
    }
    every = readers.save_cadence(cell)
    with_save, without = readers.save_intervals(events, every)
    if with_save:
        info["save_intervals_ms"] = {
            "with_save": [d * 1e3 for d in with_save],
            "without_per_step": [d * 1e3 for d in without],
            "cycles": [d * 1e3 for d in readers.save_cycles(events, every)]}
    return info, line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="also write the traced run's compact trace here "
                         "(how tests/data/ was recorded)")
    args = ap.parse_args()
    if not os.path.isfile(os.path.join(ROOT, "dlrover_wuqiong_tpu",
                                       "__init__.py")):
        raise SystemExit("benchmark: the program (dlrover_wuqiong_tpu/) is "
                         "not in this checkout; there is nothing to measure")
    cell = cells.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell["run_seconds"])
    run_dir = os.path.join(HERE, "out", cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    prepare_environment(run_dir)
    sock_dir = os.environ["DWT_SOCKET_DIR"]
    run = {}
    try:
        driver = cells.load_module("drivers", cell["traffic"]["driver"])
        run = driver.run(cell, args, run_dir, T_PROC0)
        info, line = assemble(cell, run, bool(args.trace))
        if args.dump_trace and run["trace"]:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump_trace)),
                        exist_ok=True)
            with open(args.dump_trace, "w") as f:
                json.dump(run["trace"], f)
    finally:
        # nothing large stays behind: checkpoints, traces, shm, sockets
        for path in run.get("leftovers", []):
            if path.startswith("/dev/shm/"):
                try:
                    os.unlink(path)
                except OSError:
                    pass
        shutil.rmtree(run_dir, ignore_errors=True)
        shutil.rmtree(sock_dir, ignore_errors=True)
    print(json.dumps({"info": info}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
