"""Driver `elastic_cli`: the cell's training as a worker under
`python -m dlrover_wuqiong_tpu.run`, killed once by the harness.

This process NEVER imports JAX (a chip belongs to one process at a
time): it starts the CLI, follows the worker's stamps in
`<run_dir>/events.jsonl`, sends SIGKILL to the worker's process group
when generation 1 holds at the kill step and the tracker names the
committed save, and waits for generation 2 to resume, train to the
window's end and exit.  The window opens at the SIGKILL.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time

from benchmark import cells

TRACKER = "latest_checkpointed_iteration.txt"
SETUP_LIMIT_S = 900   # generation 1, cold compile included
TAIL_LIMIT_S = 150    # after the window's end: teardown of the CLI
WORKER_SCRIPT = os.path.join(cells.HERE, "worker.py")


def _read_events(path: str) -> list:
    try:
        with open(path) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except OSError:
        return []


def _tracker_step(run_dir: str) -> int:
    try:
        with open(os.path.join(run_dir, "train", "checkpoints",
                               TRACKER)) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1


def _max_restarts(cli_args: list) -> int:
    for a in cli_args:
        if a.startswith("--max_restarts="):
            return int(a.split("=", 1)[1])
    return 3  # the CLI's default


def _kill_group(pgid: int) -> None:
    try:
        os.killpg(pgid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _wait_hold(proc, ev_path, run_dir, gen, fault, worker_pgids) -> dict:
    """Generation `gen` holds at the kill step and the tracker names the
    committed save — or the run fails."""
    hold = None
    t_end = time.monotonic() + SETUP_LIMIT_S
    while time.monotonic() < t_end and proc.poll() is None:
        evs = _read_events(ev_path)
        worker_pgids |= {e["pgid"] for e in evs if e["ev"] == "worker_start"}
        hold = next((e for e in evs if e["ev"] == "hold"
                     and e["gen"] == gen), None)
        if hold and _tracker_step(run_dir) >= fault["wait_committed_step"]:
            return hold
        time.sleep(0.05)
    raise RuntimeError(f"generation {gen + 1} never reached the kill step")


def run(cell: dict, args, run_dir: str, t_proc0: float) -> dict:
    traffic, fault = cell["traffic"], cell["traffic"]["fault"]
    harness = [{"ev": "proc_start", "t": t_proc0, "gen": -1}]

    plan = {"workload": cell["name"], "seed": args.seed,
            "seconds": args.seconds, "trace": bool(args.trace),
            "run_dir": run_dir, "warm_steps": 0, "sync_every": 0,
            "last_gen": _max_restarts(traffic["cli_args"]),
            "trace_steps": traffic["traced"]["trace_steps"]}
    plan_path = os.path.join(run_dir, "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    ev_path = os.path.join(run_dir, "events.jsonl")
    cli_log = os.path.join(run_dir, "cli.log")
    env = dict(os.environ)
    env["PYTHONPATH"] = cells.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "dlrover_wuqiong_tpu.run",
           *traffic["cli_args"], "--log_dir", os.path.join(run_dir, "logs"),
           WORKER_SCRIPT, plan_path]
    with open(cli_log, "wb") as logf:
        proc = subprocess.Popen(cmd, env=env, cwd=cells.ROOT, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    worker_pgids = set()
    notes = {}
    try:
        # ---- set-up: generation 1 trains to the kill step and holds,
        # the save is committed; then the kill.  A resumed generation
        # that had to COMPILE (the cell's first run in a checkout: the
        # resumed path's program has a cache key of its own) says so
        # after its first step, trains on to the kill step and holds —
        # it was set-up too, and the harness kills once more.  The
        # window opens at the LAST kill.
        gen = 0
        while True:
            hold = _wait_hold(proc, ev_path, run_dir, gen, fault,
                              worker_pgids)
            committed = _tracker_step(run_dir)
            pgid = next(e["pgid"] for e in _read_events(ev_path)
                        if e["ev"] == "worker_start" and e["gen"] == gen)
            t_kill = time.monotonic()
            with open(os.path.join(run_dir, "kill.json"), "w") as f:
                json.dump({"t_kill": t_kill}, f)
            os.killpg(pgid, getattr(signal, fault["signal"]))
            measured = gen + 1
            # follow the relaunch and the resumed generation's first step
            relaunch_t, first = None, None
            t_end = t_kill + args.seconds + TAIL_LIMIT_S
            while time.monotonic() < t_end and proc.poll() is None:
                if relaunch_t is None:
                    with open(cli_log, errors="replace") as f:
                        n = len(re.findall(r"launched worker pid=",
                                           f.read()))
                    if n >= measured + 1:
                        relaunch_t = time.monotonic()
                first = next((e for e in _read_events(ev_path)
                              if e["gen"] == measured and e["ev"] in
                              ("first_step_done", "cold_resume")), None)
                if first is not None:
                    break
                time.sleep(0.02)
            if first is not None and first["ev"] == "cold_resume":
                harness.append({"ev": "warmup_kill", "t": t_kill, "gen": -1,
                                "cache_misses": first["cache_misses"]})
                gen = measured
                continue
            break
        harness.append({"ev": "kill", "t": t_kill, "gen": -1,
                        "committed_step": committed,
                        "held_step": hold["step"],
                        "measured_gen": measured})
        harness.append({"ev": "window_open", "t": t_kill, "gen": -1})
        if relaunch_t is not None:
            harness.append({"ev": "relaunch_seen", "t": relaunch_t,
                            "gen": -1})
        # ---- the measured generation trains to the window's end, exits
        t_end = t_kill + args.seconds + TAIL_LIMIT_S
        while time.monotonic() < t_end and proc.poll() is None:
            time.sleep(0.05)
        if proc.poll() is None:
            notes["cli_timeout"] = True
        for e in _read_events(ev_path):
            if e["ev"] == "worker_start":
                worker_pgids.add(e["pgid"])
    finally:
        if proc.poll() is None:
            _kill_group(proc.pid)
        rc = proc.wait()
        for pg in worker_pgids:  # workers lead their own sessions
            _kill_group(pg)
    notes["cli_rc"] = rc
    evs = _read_events(ev_path)
    gens = {}
    path = os.path.join(run_dir, f"result_gen{measured}.json")
    if os.path.isfile(path):
        with open(path) as f:
            gens[measured] = json.load(f)
    trace = None
    if os.path.isfile(os.path.join(run_dir, "trace.json")):
        with open(os.path.join(run_dir, "trace.json")) as f:
            trace = json.load(f)
    with open(cli_log, errors="replace") as f:
        log = f.read()
    notes["launches"] = len(re.findall(r"launched worker pid=", log))
    notes["node_check_children"] = len(re.findall(r"node check child:", log))
    job = os.environ["DWT_JOB_NAME"]
    sock_dir = os.environ["DWT_SOCKET_DIR"]
    from benchmark import worker  # noqa: PLC0415 — imports no JAX itself

    leftovers = worker.shm_leftovers(job) + (
        sorted(os.listdir(sock_dir)) if os.path.isdir(sock_dir) else [])

    # ---- the fault's own correctness: resumed from the committed step,
    # first data step equals it, nothing compiled, reworked loss equal
    g2 = gens.get(measured, {})
    # programs the cache did not hold up to the window's end (what the
    # traced run's probes compile afterwards is not the resume's)
    end = next((e for e in evs if e["gen"] == measured
                and e["ev"] in ("close", "trace_stop")), {})
    misses = end.get("cache_misses")
    hold_losses = {int(r[0]): r[1] for r in hold.get("losses", [])}
    g2_losses = {int(r[0]): r[1] for r in g2.get("losses", [])}
    shared = sorted(set(hold_losses) & set(g2_losses))
    rework_equal = bool(shared) and all(
        hold_losses[s] == g2_losses[s] for s in shared)
    notes.update(
        committed_step_at_kill=committed, held_step=hold["step"],
        resumed_step=g2.get("resumed_step"),
        restore_tier=g2.get("restore_tier"),
        first_data_step=g2.get("first_data_step"),
        resumed_cache_misses=misses,
        reworked_steps_compared=shared, rework_losses_equal=rework_equal,
        gen1_losses={str(s): hold_losses[s] for s in shared},
        gen2_losses={str(s): g2_losses[s] for s in shared})
    notes["measured_gen"] = measured
    extra_ok = (rc == 0 and notes["launches"] == measured + 1 and bool(g2)
                and g2.get("resumed_step") == committed
                and g2.get("first_data_step") == committed
                and misses == 0
                and rework_equal and not leftovers)
    if not extra_ok:
        sys.stderr.write(log[-6000:] + "\n")
        for name in sorted(os.listdir(os.path.join(run_dir, "logs"))
                           if os.path.isdir(os.path.join(run_dir, "logs"))
                           else []):
            with open(os.path.join(run_dir, "logs", name),
                      errors="replace") as f:
                sys.stderr.write(f"---- {name}\n{f.read()[-4000:]}\n")
    return {"gens": gens, "events": harness + evs, "trace": trace,
            "measured_gen": measured, "extra_ok": bool(extra_ok), "notes": notes,
            "leftovers": leftovers}
