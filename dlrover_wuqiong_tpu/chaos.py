"""Scripted chaos scenarios with goodput-style recovery invariants.

Parity: reference `docs/tech_report/fault_tolerance_exps.md:27-80` — the
chaosblade experiments (pod delete / CPU-stressed straggler / network
break / process corruption) run against a live job, checking that training
restores and the damaged node is excluded.

Here each scenario is a callable returning an invariant report (dict), so
it is equally a CI test body (tests/test_chaos.py) and an operator tool:

    python -m dlrover_wuqiong_tpu.chaos pod-kill
    python -m dlrover_wuqiong_tpu.chaos straggler
    python -m dlrover_wuqiong_tpu.chaos network-partition
    python -m dlrover_wuqiong_tpu.chaos preempt-warm   # re-mesh compile win
    python -m dlrover_wuqiong_tpu.chaos preempt-fused  # K-step boundaries
    python -m dlrover_wuqiong_tpu.chaos preempt-adaptive  # policy loop
    python -m dlrover_wuqiong_tpu.chaos serve-drain    # kill decode worker

pod-kill drives the REAL stack — `run` CLI → master → agent → worker with
flash checkpoints — and hard-SIGKILLs the worker process group externally
mid-save-window.  The other two exercise the master's detection machinery
directly (fake platform backend), mirroring how the reference report reads
its k8s experiments.

The pod-kill worker deliberately parallels (but is distinct from)
tests/test_elastic_e2e.py's crash worker: that one injects an IN-PROCESS
fault (`os._exit` at a fixed step, deterministic), this one takes an
EXTERNAL asynchronous SIGKILL — the chaosblade `kubectl delete pod`
equivalent, which can land mid-checkpoint-write and therefore also proves
the torn-state invariant.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict

from .common.log import get_logger

logger = get_logger("chaos")

_launch_seq = 0


def _launch_standalone(prefix: str, worker_src: str, args,
                       max_restarts: int, extra_env=None):
    """Shared scaffolding for scenarios that drive the REAL stack: fresh
    workdir + markers, fresh DWT_JOB_NAME / DWT_SOCKET_DIR (CLAUDE.md:
    shm segments and control sockets persist across hard kills), and the
    `run --standalone` CLI as a Popen.

    Returns (proc, workdir, ckpt_dir, marker_dir, job_name)."""
    work = tempfile.mkdtemp(prefix=f"dwt-chaos-{prefix}-")
    ckpt_dir = os.path.join(work, "ckpt")
    marker = os.path.join(work, "markers")
    os.makedirs(marker)
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(worker_src)
    # unique per INVOCATION, not just per process: preempt-warm runs two
    # drills back-to-back and a shared name would re-attach the second
    # run to the first's kill-surviving shm segments (CLAUDE.md)
    global _launch_seq
    _launch_seq += 1
    job = f"{prefix}{os.getpid()}n{_launch_seq}"
    env = dict(
        os.environ, DWT_JOB_NAME=job, JAX_PLATFORMS="cpu",
        DWT_SOCKET_DIR=os.path.join(work, "sockets"),
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "dlrover_wuqiong_tpu.run", "--standalone",
         "--nproc_per_node=1", f"--max_restarts={max_restarts}", script,
         ckpt_dir, marker] + [str(a) for a in args],
        env=env, cwd=work, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    return proc, work, ckpt_dir, marker, job


# ------------------------------------------------------------------ pod kill


_POD_KILL_WORKER = r"""
import os, sys, time
import numpy as np

from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)

ckpt_dir, marker_dir, total_steps = sys.argv[1], sys.argv[2], int(sys.argv[3])
ctx = init_elastic()
restart = ctx.world.restart_count
ckpt = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"])
template = {"w": np.zeros((64, 64), np.float32),
            "step": np.zeros((), np.int64)}
state = ckpt.load_checkpoint(template)
start = int(state["step"]) + 1 if state is not None else 0
with open(os.path.join(marker_dir, f"start_r{restart}"), "w") as f:
    f.write(str(start))
with open(os.path.join(marker_dir, f"pid_r{restart}"), "w") as f:
    f.write(str(os.getpid()))
step = start - 1  # loop may be empty when the kill landed after the
                  # final checkpoint committed
for step in range(start, total_steps):
    w = np.full((64, 64), float(step), np.float32)
    ckpt.save_checkpoint(step, {"w": w, "step": np.int64(step)},
                         storage_type=StorageType.DISK)
    ctx.report_step(step)
    with open(os.path.join(marker_dir, "progress"), "w") as f:
        f.write(str(step))
    time.sleep(0.05)
ok = ckpt.wait_latest_checkpoint(60)
with open(os.path.join(marker_dir, "done"), "w") as f:
    f.write(f"{ok} {step}")
"""


def pod_kill(kill_at_step: int = 8, total_steps: int = 20,
             timeout: float = 240.0) -> Dict:
    """External SIGKILL of the training process mid-save-window.

    Invariants: the job completes after an automatic restart; the resumed
    run starts at a checkpointed step (goodput: lost work is bounded by the
    save cadence); the final checkpoint is complete and consistent (the
    done-dir commit never exposes a torn state)."""
    import numpy as np

    from .checkpoint.checkpointer import FlashCheckpointer

    cli, work, ckpt_dir, marker, job = _launch_standalone(
        "chaos", _POD_KILL_WORKER, [total_steps], max_restarts=2)

    deadline = time.monotonic() + timeout
    killed_pid = None
    killed_at = -1  # the step actually OBSERVED when the kill landed —
    # polling can overshoot kill_at_step on a loaded host, so invariants
    # bound against this, not the request
    progress = os.path.join(marker, "progress")
    while time.monotonic() < deadline and killed_pid is None:
        try:
            seen = int(open(progress).read())
            if seen >= kill_at_step:
                killed_pid = int(open(os.path.join(marker, "pid_r0"))
                                 .read())
                os.kill(killed_pid, signal.SIGKILL)  # the chaosblade moment
                # TOCTOU: the worker can advance past `seen` (and
                # checkpoint) before the SIGKILL lands — the worker is dead
                # NOW, so the file holds the final authoritative step
                try:
                    killed_at = int(open(progress).read())
                except (OSError, ValueError):
                    killed_at = seen
                logger.info("pod-kill: SIGKILL worker pid=%d at step %d",
                            killed_pid, killed_at)
        except (OSError, ValueError):
            pass
        time.sleep(0.05)
    try:
        out, _ = cli.communicate(
            timeout=max(5.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        cli.kill()
        out, _ = cli.communicate()

    report: Dict = {"scenario": "pod-kill", "killed_pid": killed_pid,
                    "killed_at_step": killed_at, "cli_rc": cli.returncode}
    report["completed"] = os.path.exists(os.path.join(marker, "done"))
    report["restarts"] = sum(
        1 for f in os.listdir(marker) if f.startswith("start_r")) - 1
    resume_file = os.path.join(marker, "start_r1")
    report["resume_step"] = (int(open(resume_file).read())
                             if os.path.exists(resume_file) else -1)
    # torn-checkpoint check: the committed latest must load completely and
    # carry self-consistent contents
    ck = FlashCheckpointer(ckpt_dir, job_name=f"{job}-verify")
    state = ck.load_checkpoint({"w": np.zeros((64, 64), np.float32),
                                "step": np.zeros((), np.int64)})
    ck.close()
    report["ckpt_intact"] = bool(
        state is not None
        and int(state["step"]) == total_steps - 1
        and np.all(np.asarray(state["w"]) == float(int(state["step"]))))
    # goodput: steps not lost to the fault / total useful steps (zero
    # lost when the resume point is past the killed step)
    if report["resume_step"] >= 0 and killed_at >= 0:
        lost = max(0, killed_at - report["resume_step"] + 1)
        report["goodput"] = round(1.0 - lost / total_steps, 3)
    report["ok"] = bool(
        report["completed"] and report["restarts"] == 1
        and 0 < report["resume_step"] <= killed_at + 1
        and report["ckpt_intact"] and cli.returncode == 0)
    if report["ok"]:
        import shutil

        shutil.rmtree(work, ignore_errors=True)
    else:
        report["cli_tail"] = out[-2000:]
        report["workdir"] = work  # kept for debugging
    return report


# ----------------------------------------------------------------- straggler


def straggler(n_nodes: int = 4, slow_node: int = 3,
              slow_factor: float = 5.0) -> Dict:
    """A CPU-stressed node steps far slower than its peers.

    Mirrors the report's chaosblade CPU-load experiment: the network-check
    sweep must name the straggler (so `--exclude-straggler` can drop it)
    and the diagnosis chain must flag it from runtime step cadence too."""
    from .common import messages as msg
    from .diagnosis.manager import (
        CheckStragglerOperator,
        DiagnosisDataManager,
        InferenceChain,
    )
    from .master.rendezvous import NetworkCheckRendezvousManager

    # 1) pre-flight: pairwise network-check sweep
    rdzv = NetworkCheckRendezvousManager()
    rdzv.update_rdzv_params(n_nodes, n_nodes, waiting_timeout=0.0)
    for nid in range(n_nodes):
        rdzv.join_rendezvous(nid, nid, 1)
    for nid in range(n_nodes):
        elapsed = slow_factor if nid == slow_node else 1.0
        rdzv.report_network_check_result(nid, True, elapsed)
    stragglers, _ = rdzv.get_straggler(threshold=2.0)

    # 2) runtime: step cadence diagnosis
    data = DiagnosisDataManager()
    now = time.time()
    for nid in range(n_nodes):
        period = 1.0 * (slow_factor if nid == slow_node else 1.0)
        for k in range(8):
            data.store_report(msg.DiagnosisReport(
                node_id=nid, payload_type="step", content=str(k),
                timestamp=now - (8 - k) * period))
    chain = InferenceChain([CheckStragglerOperator(ratio=3.0,
                                                   min_reports=6)])
    flagged = [c.node_id for c in chain.run(data)
               if c.name == "straggler"]

    report = {"scenario": "straggler", "expected": slow_node,
              "network_check_stragglers": stragglers,
              "runtime_stragglers": flagged}
    report["ok"] = (stragglers == [slow_node] and flagged == [slow_node])
    return report


# --------------------------------------------------------- network partition


def network_partition(heartbeat_timeout: float = 1.5,
                      wait: float = 3.0) -> Dict:
    """A node's control-plane link drops: heartbeats stop arriving.

    The master's heartbeat monitor must declare the node dead and relaunch
    it through the scaler (reference: network-break chaosblade experiment —
    the pod is replaced even though the process may still be running)."""
    from .common.constants import NodeEventType, NodeStatus
    from .common.global_context import get_context
    from .common.node import Node, NodeEvent
    from .master.job_manager import LocalJobManager

    ctx = get_context()
    old_timeout = ctx.node_heartbeat_timeout
    ctx.node_heartbeat_timeout = heartbeat_timeout
    try:
        jm = LocalJobManager(max_relaunch_count=3)
        for nid in range(2):
            node = jm.register_node("worker", nid, rank_index=nid)
            node.update_status(NodeStatus.RUNNING)
            node.heartbeat_time = time.time()
        t0 = time.monotonic()
        relaunched = []
        # node 1 goes silent; node 0 keeps beating — the master's dead-node
        # sweep (master.py run loop) is replayed here
        while time.monotonic() - t0 < wait and not relaunched:
            jm.get_node(0).heartbeat_time = time.time()
            for n in jm.get_dead_nodes():
                relaunched.append(n.id)
                dead = Node(n.type, n.id, rank_index=n.rank_index)
                dead.status = NodeStatus.FAILED
                dead.exit_reason = "Hang"
                jm.process_event(NodeEvent(NodeEventType.MODIFIED, dead))
            time.sleep(0.1)
        n1 = jm.get_node(1)
        report = {"scenario": "network-partition",
                  "dead_detected": relaunched,
                  "node1_relaunch_count": n1.relaunch_count}
        report["ok"] = (relaunched == [1] and n1.relaunch_count == 1)
        return report
    finally:
        ctx.node_heartbeat_timeout = old_timeout


# ------------------------------------------------------------------ preempt


_PREEMPT_WORKER = r"""
import json, os, sys, time
import numpy as np

from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)
from dlrover_wuqiong_tpu.telemetry import get_ledger

(ckpt_dir, marker_dir, total_steps, dt, interval, flash, with_model,
 fused) = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), float(sys.argv[4]),
    int(sys.argv[5]), sys.argv[6] == "1", sys.argv[7] == "1",
    int(sys.argv[8]))
ctx = init_elastic()
restart = ctx.world.restart_count
# the downtime split comes from the GOODPUT LEDGER, not ad-hoc timers:
# compile / restore_* / productive / rework are credited by the same
# call sites production uses (telemetry/ledger.py); the drill only adds
# cache counters the ledger does not model
led = get_ledger()
led.start()
extra = {"restart": restart, "cache_warm": False,
         "step_hits": 0, "step_misses": 0}
ledger_path = os.path.join(marker_dir, f"ledger_r{restart}.json")


def dump_ledger():
    snap = dict(led.snapshot(), **extra)
    tmp = ledger_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f)
    os.replace(tmp, ledger_path)  # a SIGKILL mid-write must not tear it


if with_model:
    # the re-mesh cost under measurement: rebuild + compile the REAL
    # train step through the persistent cache (auto/compile_cache.py) —
    # a warm restart deserializes from disk instead of recompiling
    import dataclasses
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.auto.compile_cache import counters
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                              use_flash_attention=False, remat=False)
    h0, m0 = counters.snapshot()
    with led.window("compile"):
        res = auto_accelerate(GPT(cfg), optimizer=optax.adam(1e-2),
                              devices=jax.devices(),
                              strategy=[("fsdp", {})])
        # batch sized by the inherited device count: under pytest the
        # worker sees the conftest's 8-device XLA_FLAGS and fsdp needs
        # B % n == 0
        bs = max(4, len(jax.devices()))
        data = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (bs, 33)).astype(np.int32)
        hb = {"input_ids": data[:, :-1], "labels": data[:, 1:]}
        if fused > 1:
            # the re-mesh cost a FUSED worker pays: K changes the HLO,
            # so this is its own cache entry (auto/compile_cache.py)
            from dlrover_wuqiong_tpu.data.elastic_dataset import (
                stack_batches)
            fb = res.place_fused_batch(stack_batches([hb] * fused))
            st, m = res.fused_train_step(fused)(res.state, fb)
        else:
            b = res.place_batch(dict(hb))
            st, m = res.train_step(res.state, b)
        float(m["loss"])  # force the compile + first dispatch
    h1, m1 = counters.snapshot()
    extra.update(cache_warm=res.cache_warm, step_hits=h1 - h0,
                 step_misses=m1 - m0)
ckpt = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"])
template = {"w": np.zeros((8, 8), np.float32),
            "step": np.zeros((), np.int64)}
# restore_* tiers are credited INSIDE engine.load (the sanctioned
# verified-restore route) — nothing to time here
state = ckpt.load_checkpoint(template)
start = int(state["step"]) + 1 if state is not None else 0
extra["start_step"] = start
# steps a PRIOR generation already executed past the restore point are
# REWORK, not productive: the shared step log knows the global high-water
prev_max = -1
try:
    with open(os.path.join(marker_dir, "steps.log")) as f:
        for ln in f:
            prev_max = max(prev_max, int(ln.split()[1]))
except (OSError, ValueError, IndexError):
    pass
dump_ledger()
with open(os.path.join(marker_dir, f"pid_r{restart}"), "w") as f:
    f.write(str(os.getpid()))
log = open(os.path.join(marker_dir, "steps.log"), "a")
step = start - 1
s = start
while s < total_steps:
    # one fused K-step dispatch: the host observes NOTHING until the
    # boundary — staging, disk saves and step reports all fire there
    # (fused=1 degenerates to the per-step loop)
    k_eff = min(fused - s % fused, total_steps - s)
    n_rework = max(0, min(s + k_eff, prev_max + 1) - s)
    if n_rework:
        with led.window("rework"):
            time.sleep(dt * n_rework)
    if k_eff - n_rework:
        with led.window("productive"):
            time.sleep(dt * (k_eff - n_rework))
    step = s + k_eff - 1
    # counted BEFORE staged: a kill between the two re-executes a block
    # (counted twice), never leaves a staged step in no count
    for i in range(k_eff):
        log.write(f"{time.time()} {s + i} {restart}\n")
    log.flush()
    sd = {"w": np.full((8, 8), float(step), np.float32),
          "step": np.int64(step)}
    if flash:
        # stage every BOUNDARY to shm (~free); the agent's
        # save-on-failure persists the last staged boundary when the
        # worker is killed — loss per kill is bounded by K, not interval
        ckpt.save_checkpoint(step, sd, storage_type=StorageType.MEMORY)
    if any((s + i) % interval == 0 for i in range(k_eff)) or \
        step == total_steps - 1:
        ckpt.save_checkpoint(step, sd, storage_type=StorageType.DISK)
    ctx.report_step(step)
    dump_ledger()  # boundary-cadence: the kill sees the latest split
    s += k_eff
ok = ckpt.wait_latest_checkpoint(60)
dump_ledger()
with open(os.path.join(marker_dir, "done"), "w") as f:
    f.write(f"{ok} {step}")
"""


def _read_last_step(steps_log: str) -> int:
    """Newest executed step in a drill worker's shared steps.log."""
    try:
        with open(steps_log) as f:
            lines = f.read().splitlines()
        return int(lines[-1].split()[1]) if lines else -1
    except (OSError, ValueError, IndexError):
        return -1


def preempt(total_steps: int = 600, dt: float = 0.1,
            ckpt_interval: int = 50, kills: int = 2, seed: int = 0,
            flash: bool = True, target: float = 0.95,
            timeout: float = 420.0, model: bool = False,
            cache_dir: str = "", compile_cache: bool = True,
            fused_steps: int = 1, kill_at_steps=None,
            relaunch_always: bool = False) -> Dict:
    """Randomized preemption drill against the goodput north star.

    N SIGKILLs land at seeded-random times over the run; goodput is
    computed from STEP ACCOUNTING against wall clock:

        goodput = total_steps * dt / wall_clock_seconds

    — re-executed steps, restart latency, and resume overhead all count
    as lost time, exactly like the reference's production goodput metric
    (README.md:55-56: 69% -> 95% at GLM-65B scale).  `ckpt_interval` is
    the lever the reference tuned (flash ckpt let them drop 250 -> 10
    steps, docs/blogs/flash_checkpoint.md:40); `flash=True` additionally
    stages EVERY step to shm, so the agent's save-on-failure persists
    the last step and the loss per kill becomes interval-INDEPENDENT.

    `model=True` makes every worker generation rebuild + compile the
    REAL train step, so the report's downtime split shows what each
    restart paid: `compile_s` (re-mesh XLA cost — near zero when the
    persistent cache serves it), `restore_s` (checkpoint load, summed
    over the ledger's restore tiers), and `rework_s` (re-executed
    steps).  Every number comes from per-generation GOODPUT LEDGER
    snapshots (telemetry/ledger.py) written at fusion boundaries — the
    same attribution the live runtime exports — not drill-local timers.  `compile_cache=False` runs the
    cold-compile control (DWT_COMPILE_CACHE=0); `cache_dir` pins the
    cache location (fresh dir → first generation cold, restarts warm).

    `fused_steps=K > 1` runs the worker as the fused K-step driver
    (trainer/train_step.py): the host observes only fusion BOUNDARIES, so
    shm staging, disk saves and preemption recovery all quantize to K —
    the drill proves the boundary-only elastic contract still meets the
    goodput target (loss per kill bounded by K + restart latency, not by
    the disk interval).

    `kill_at_steps=[s1, s2, ...]` replaces the seeded wall-clock schedule
    with STEP-triggered kills: each SIGKILL lands once the worker's
    shared step log crosses the threshold.  Two runs (e.g. adaptive vs
    static cadence in `preempt_adaptive`) then take faults at identical
    step positions, so their goodput difference isolates the cadence
    policy from restart-latency jitter.

    `relaunch_always=True` disables the master's repeated-error-class
    cutoff for the drill: a SIGKILL burst classifies as `host_oom`
    (exit_code=137 is ambiguous), and three consecutive kills would
    otherwise stop relaunching — but a drill kill IS the preemption
    storm the cutoff's TRANSIENT_CLASSES carve-out exists for.
    """
    import random

    extra_env = {}
    if relaunch_always:
        extra_env["DWT_CTX_RELAUNCH_ALWAYS"] = "1"
    if model:
        extra_env["DWT_COMPILE_CACHE"] = "1" if compile_cache else "0"
        if cache_dir:
            extra_env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    t_start = time.monotonic()
    cli, work, ckpt_dir, marker, job = _launch_standalone(
        "preempt", _PREEMPT_WORKER,
        [total_steps, dt, ckpt_interval, "1" if flash else "0",
         "1" if model else "0", max(1, fused_steps)],
        max_restarts=kills + 1, extra_env=extra_env)

    # kill schedule: seeded wall-clock times over the productive middle,
    # or explicit step thresholds when kill_at_steps pins the positions
    ideal = total_steps * dt
    steps_log = os.path.join(marker, "steps.log")
    if kill_at_steps is not None:
        schedule = [("step", int(s)) for s in sorted(kill_at_steps)]
        kills = len(schedule)
    else:
        rng = random.Random(seed)
        schedule = [("time", t) for t in
                    sorted(rng.uniform(0.15, 0.75) * ideal
                           for _ in range(kills))]
    killed = []
    for mode, when in schedule:
        if mode == "time":
            delay = t_start + when - time.monotonic()
            if delay > 0:
                time.sleep(delay)
        # wait out worker startup/restart: a kill scheduled before the
        # (re)launched worker wrote its pid must land, not be skipped.
        # Step-triggered kills additionally wait for the step log to
        # cross the threshold (rework included).
        pid = None
        wait_pid = time.monotonic() + (
            60.0 if mode == "time"
            else max(30.0, t_start + timeout * 0.75 - time.monotonic()))
        while time.monotonic() < wait_pid and cli.poll() is None:
            if mode == "step" and _read_last_step(steps_log) < when:
                time.sleep(0.05)
                continue
            pids = sorted((f for f in os.listdir(marker)
                           if f.startswith("pid_r")),
                          key=lambda s: int(s[5:]))
            if pids:
                try:
                    cand = int(open(os.path.join(marker, pids[-1])).read())
                    # a freshly-killed worker lingers as a zombie that
                    # still answers signal 0 — only a NEW pid counts
                    if cand not in {k["pid"] for k in killed}:
                        os.kill(cand, 0)  # alive?
                        pid = cand
                        break
                except (OSError, ValueError):
                    pass
            time.sleep(0.1)
        if pid is None:
            break
        try:
            os.kill(pid, signal.SIGKILL)
            killed.append({"t": round(time.monotonic() - t_start, 1),
                           "at_step": _read_last_step(steps_log),
                           "pid": pid})
        except OSError:
            pass
    try:
        out, _ = cli.communicate(
            timeout=max(5.0, t_start + timeout - time.monotonic()))
    except subprocess.TimeoutExpired:
        cli.kill()
        out, _ = cli.communicate()
    wall = time.monotonic() - t_start

    executed = 0
    try:
        with open(os.path.join(marker, "steps.log")) as f:
            executed = sum(1 for _ in f)
    except OSError:
        pass
    report: Dict = {
        "scenario": "preempt", "total_steps": total_steps, "dt": dt,
        "ckpt_interval": ckpt_interval, "flash": flash,
        "fused_steps": max(1, fused_steps),
        "kills": killed, "cli_rc": cli.returncode,
        "wall_s": round(wall, 1), "ideal_s": round(ideal, 1),
        "executed_steps": executed,
        "wasted_steps": max(0, executed - total_steps),
    }
    report["completed"] = os.path.exists(os.path.join(marker, "done"))
    # downtime decomposition (one GOODPUT LEDGER snapshot per worker
    # generation, telemetry/ledger.py): what each restart actually paid —
    # re-mesh compile, per-tier checkpoint restore, and re-executed work
    # — credited by the same production call sites, not drill timers.
    ledgers = []
    for name in os.listdir(marker):
        if not name.startswith("ledger_r") or name.endswith(".tmp"):
            continue
        try:
            with open(os.path.join(marker, name)) as f:
                ledgers.append(json.load(f))
        except (OSError, ValueError):
            pass
    ledgers.sort(key=lambda t: t.get("restart", 0))
    restarts_l = [t for t in ledgers if t.get("restart", 0) > 0]

    def led_s(snap, state):
        return float(snap.get("states", {}).get(state, 0.0))

    restore_states = ("restore_shm", "restore_replica", "restore_storage")
    report["downtime"] = {
        "compile_s": round(sum(led_s(t, "compile")
                               for t in restarts_l), 3),
        "compile_s_first": (round(led_s(ledgers[0], "compile"), 3)
                            if ledgers else 0.0),
        "restore_s": round(sum(led_s(t, st) for t in restarts_l
                               for st in restore_states), 3),
        "rework_s": round(sum(led_s(t, "rework") for t in ledgers), 3),
        "warm_restarts": sum(1 for t in restarts_l
                             if t.get("cache_warm")),
        "restarts": len(restarts_l),
    }
    # job-level ledger aggregate (sum of per-generation cumulative
    # snapshots — generations are disjoint processes, so summing is exact)
    agg: Dict[str, float] = {}
    for t in ledgers:
        for k, v in t.get("states", {}).items():
            agg[k] = agg.get(k, 0.0) + float(v)
    report["ledger"] = {
        "states": {k: round(v, 3) for k, v in sorted(agg.items())},
        "wall_s": round(sum(float(t.get("wall_s", 0.0))
                            for t in ledgers), 3),
        "generations": len(ledgers),
    }
    # goodput from STEP ACCOUNTING (useful/executed — re-executed steps
    # are the fault's waste); wall-clock goodput reported alongside (it
    # additionally charges restart latency and per-step staging, both of
    # which are fixed costs a toy-sized step exaggerates)
    report["goodput"] = (round(total_steps / executed, 4)
                         if executed >= total_steps else 0.0)
    report["goodput_wall"] = round(ideal / wall, 4) if wall > 0 else 0.0
    report["ok"] = bool(report["completed"] and cli.returncode == 0
                        and len(killed) == kills
                        and report["goodput"] >= target)
    if report["ok"]:
        import shutil

        shutil.rmtree(work, ignore_errors=True)
    else:
        report["cli_tail"] = out[-2000:]
        report["workdir"] = work
    return report


def preempt_table(total_steps: int = 600, dt: float = 0.1,
                  kills: int = 2, seed: int = 0,
                  out_dir: str = "") -> Dict:
    """The interval-vs-goodput curve (README): disk-only cadence at
    several intervals vs flash per-step staging, then two REAL-compile
    rows (model=True) contrasting warm vs cold restart compile cost —
    the downtime split makes the warm-pool win visible per-component,
    not just in aggregate goodput.

    The curve is also the adaptive-policy engine's OFFLINE PRIOR
    (brain/policy.py load_prior calibrates step time + checkpoint cost
    from it): rows persist atomically to `out_dir/policy/
    preempt_table.json` (default `$DWT_CKPT_DIR` or the system tmp dir)
    and the report carries `table_path` for `--policy-prior`."""
    rows = []
    # (interval, flash, model, compile_cache)
    grid = [(200, False, False, True), (50, False, False, True),
            (10, False, False, True), (50, True, False, True),
            (50, True, True, True), (50, True, True, False)]
    for interval, flash, model, compile_cache in grid:
        cache = (tempfile.mkdtemp(prefix="dwt-warmtbl-")
                 if model and compile_cache else "")
        r = preempt(total_steps=total_steps, dt=dt,
                    ckpt_interval=interval, kills=kills, seed=seed,
                    flash=flash, target=0.0, model=model,
                    cache_dir=cache, compile_cache=compile_cache)
        row = {"interval": interval, "flash": flash,
               "goodput": r["goodput"],
               "wasted_steps": r["wasted_steps"],
               "kills_landed": len(r["kills"]),
               "completed": r["completed"]}
        if model:
            row["compile_cache"] = compile_cache
            row["downtime"] = r["downtime"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if cache:
            import shutil

            shutil.rmtree(cache, ignore_errors=True)
    # a row where a scheduled kill never landed is NOT a valid curve
    # point — its goodput would be inflated silently
    report = {"scenario": "preempt-table", "rows": rows,
              "ok": all(r["completed"] and r["kills_landed"] == kills
                        for r in rows)}
    base = out_dir or os.getenv("DWT_CKPT_DIR", "") or tempfile.gettempdir()
    path = os.path.join(base, "policy", "preempt_table.json")
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"dt": dt, "total_steps": total_steps,
                       "kills": kills, "rows": rows}, f)
        os.replace(tmp, path)  # a crashed writer never tears the prior
        report["table_path"] = path
    except OSError:
        logger.warning("preempt-table: persisting %s failed", path,
                       exc_info=True)
        report["table_path"] = ""
    return report


def preempt_fused(total_steps: int = 300, dt: float = 0.05,
                  kills: int = 2, seed: int = 3,
                  fused_steps: int = 5) -> Dict:
    """Preemption drill with the fused K-step driver: elastic hooks
    (shm staging, disk saves, recovery) fire at fusion boundaries ONLY,
    and the goodput north star must still hold — the boundary
    quantization loses at most K-1 steps per kill, which flash staging
    keeps well inside the >=0.95 target at K=5."""
    r = preempt(total_steps=total_steps, dt=dt, ckpt_interval=50,
                kills=kills, seed=seed, flash=True, target=0.95,
                fused_steps=fused_steps)
    r["scenario"] = "preempt-fused"
    return r


def preempt_warm(total_steps: int = 120, dt: float = 0.05,
                 kills: int = 1, seed: int = 1,
                 timeout: float = 420.0) -> Dict:
    """Warm-restart proof: identical preemption drills, one compiling
    through the persistent cache (fresh dir — generation 0 cold, every
    restart served from disk), one with the cache disabled (every
    generation recompiles).  The headline number is `compile_s_saved`:
    the per-re-mesh compile time the warm path reclaims, which is
    exactly what the goodput accounting charges as dead time."""
    cache = tempfile.mkdtemp(prefix="dwt-warmdrill-")
    try:
        warm = preempt(total_steps=total_steps, dt=dt, ckpt_interval=20,
                       kills=kills, seed=seed, flash=True, target=0.0,
                       timeout=timeout, model=True, cache_dir=cache,
                       compile_cache=True)
        cold = preempt(total_steps=total_steps, dt=dt, ckpt_interval=20,
                       kills=kills, seed=seed, flash=True, target=0.0,
                       timeout=timeout, model=True,
                       compile_cache=False)
    finally:
        import shutil

        shutil.rmtree(cache, ignore_errors=True)
    saved = round(cold["downtime"]["compile_s"]
                  - warm["downtime"]["compile_s"], 3)
    report = {
        "scenario": "preempt-warm",
        "warm": {k: warm[k] for k in ("downtime", "goodput",
                                      "goodput_wall", "completed")},
        "cold": {k: cold[k] for k in ("downtime", "goodput",
                                      "goodput_wall", "completed")},
        "compile_s_saved": saved,
        "kills_landed": min(len(warm["kills"]), len(cold["kills"])),
    }
    report["ok"] = bool(
        warm["completed"] and cold["completed"]
        and len(warm["kills"]) == kills and len(cold["kills"]) == kills
        and warm["downtime"]["warm_restarts"]
        == warm["downtime"]["restarts"] > 0
        and cold["downtime"]["warm_restarts"] == 0
        and saved > 0)
    return report


# ---------------------------------------------------------- preempt adaptive


_ADAPTIVE_WORKER = r"""
import dataclasses, json, os, sys, time
import numpy as np

from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)
from dlrover_wuqiong_tpu.telemetry import get_ledger

(ckpt_dir, marker_dir, total_steps, dt, poll_steps, interval0) = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), float(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]))
ctx = init_elastic()
restart = ctx.world.restart_count
led = get_ledger()
led.start()
extra = {"restart": restart, "start_hits": 0, "start_misses": 0,
         "kchange_hits": 0, "kchange_misses": 0, "kchanges": [],
         "decisions": []}
ledger_path = os.path.join(marker_dir, f"ledger_r{restart}.json")


def dump_ledger():
    snap = dict(led.snapshot(), **extra)
    tmp = ledger_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f)
    os.replace(tmp, ledger_path)


# real model through the persistent compile cache, same build the
# warm-pool child replays (optax.adamw(3e-4), nano GPT, fsdp, abstract
# [8, 32] batch): the drill pre-warms the pool, so EVERY generation's
# startup compile and every policy fused-K switch must be cache HITS
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import optax
from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.auto.compile_cache import counters
from dlrover_wuqiong_tpu.auto.warm_pool import WarmPool, WarmSpec, model_spec
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                          use_flash_attention=False, remat=False)
model = GPT(cfg)
h0, m0 = counters.snapshot()
with led.window("compile"):
    res = auto_accelerate(model, optimizer=optax.adamw(3e-4),
                          devices=jax.devices(), strategy=[("fsdp", {})],
                          materialize=False)
    bsh = res.batch_sharding_fn(2, None, 0)
    ab = {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                            sharding=bsh),
          "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                         sharding=bsh)}
    res.train_step.lower(res.state, ab).compile()
h1, m1 = counters.snapshot()
extra.update(start_hits=h1 - h0, start_misses=m1 - m0)
pool = WarmPool(os.environ["JAX_COMPILATION_CACHE_DIR"])
knobs = {"interval": interval0, "cur_k": 1, "pending_k": None,
         "last_id": 0}


def spec_at(k):
    return WarmSpec(n_devices=len(jax.devices()),
                    strategy=[["fsdp", {}]], model=model_spec(model),
                    batch_shape=[8, 32], platform="cpu", fused_steps=k)


def switch_k(k):
    # fused-K cutover contract (trainer._prewarm_fused_k): only when the
    # pool holds a READY entry at the new K — otherwise kick a warm
    # compile and stay at the current K until a later boundary
    if pool._ready_entry_for(spec_at(k).spec_key()) is None:
        pool.warm_async(spec_at(k))
        return False
    hh0, mm0 = counters.snapshot()
    with led.window("compile"):
        bshk = res.batch_sharding_fn(3, None, 1)
        abk = {"input_ids": jax.ShapeDtypeStruct((k, 8, 32), jnp.int32,
                                                 sharding=bshk),
               "labels": jax.ShapeDtypeStruct((k, 8, 32), jnp.int32,
                                              sharding=bshk)}
        res.fused_train_step(k).lower(res.state, abk).compile()
    hh1, mm1 = counters.snapshot()
    extra["kchange_hits"] += hh1 - hh0
    extra["kchange_misses"] += mm1 - mm0
    extra["kchanges"].append({"k": k, "hits": hh1 - hh0,
                              "misses": mm1 - mm0})
    return True


dlog = open(os.path.join(marker_dir, "decisions.log"), "a")


def poll_policy():
    try:
        d = ctx.mc.get_policy_decision()
    except Exception:  # master outage: next boundary retries
        return
    if d.decision_id <= knobs["last_id"]:
        return
    knobs["last_id"] = d.decision_id
    seen = {"id": d.decision_id, "interval": d.ckpt_interval_steps,
            "fused": d.fused_steps, "replicas": d.replica_count,
            "route": d.recovery_route, "tier": d.preferred_tier,
            "restart": restart}
    extra["decisions"].append(seen)
    dlog.write(json.dumps(seen) + "\n")
    dlog.flush()
    if d.ckpt_interval_steps > 0:
        knobs["interval"] = d.ckpt_interval_steps
    if d.fused_steps > 1 and d.fused_steps != knobs["cur_k"]:
        knobs["pending_k"] = d.fused_steps
    elif d.fused_steps == 1:
        knobs["cur_k"] = 1
        knobs["pending_k"] = None


ckpt = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"])
template = {"w": np.zeros((8, 8), np.float32),
            "step": np.zeros((), np.int64)}
state = ckpt.load_checkpoint(template)
start = int(state["step"]) + 1 if state is not None else 0
extra["start_step"] = start
prev_max = -1
try:
    with open(os.path.join(marker_dir, "steps.log")) as f:
        for ln in f:
            prev_max = max(prev_max, int(ln.split()[1]))
except (OSError, ValueError, IndexError):
    pass
poll_policy()  # a restarted generation adopts the live cadence at once
dump_ledger()
with open(os.path.join(marker_dir, f"pid_r{restart}"), "w") as f:
    f.write(str(os.getpid()))
log = open(os.path.join(marker_dir, "steps.log"), "a")
step = start - 1
s = start
while s < total_steps:
    if knobs["pending_k"] is not None and switch_k(knobs["pending_k"]):
        knobs["cur_k"] = knobs["pending_k"]
        knobs["pending_k"] = None
    k = knobs["cur_k"]
    k_eff = min(k - s % k, total_steps - s)
    n_rework = max(0, min(s + k_eff, prev_max + 1) - s)
    if n_rework:
        with led.window("rework"):
            time.sleep(dt * n_rework)
    if k_eff - n_rework:
        with led.window("productive"):
            time.sleep(dt * (k_eff - n_rework))
    step = s + k_eff - 1
    if any((s + i) % knobs["interval"] == 0 for i in range(k_eff)) or \
            step == total_steps - 1:
        sd = {"w": np.full((8, 8), float(step), np.float32),
              "step": np.int64(step)}
        ckpt.save_checkpoint(step, sd, storage_type=StorageType.DISK)
    for i in range(k_eff):
        log.write(f"{time.time()} {s + i} {restart}\n")
    log.flush()
    ctx.report_step(step)
    if any((s + i) % poll_steps == 0 for i in range(k_eff)):
        poll_policy()
    dump_ledger()
    s += k_eff
ok = ckpt.wait_latest_checkpoint(60)
dump_ledger()
with open(os.path.join(marker_dir, "done"), "w") as f:
    f.write(f"{ok} {step}")
"""


def _ledger_goodput(states: Dict) -> float:
    """Goodput from the GOODPUT LEDGER's own attribution (productive vs
    re-executed work), not drill timers: generations are disjoint
    processes, so summed cumulative snapshots divide exactly."""
    productive = float(states.get("productive", 0.0))
    rework = float(states.get("rework", 0.0))
    total = productive + rework
    return round(productive / total, 4) if total > 0 else 0.0


def preempt_adaptive(total_steps: int = 600, dt: float = 0.05,
                     kill_at_steps=(260, 330, 390),
                     static_interval: int = 200, margin: float = 0.08,
                     floor: float = 0.7, policy_prior: str = "",
                     timeout: float = 420.0) -> Dict:
    """Closed-loop acceptance drill: adaptive policy vs static cadence.

    The failure regime shifts mid-run — quiet, then a kill burst at
    fixed STEP positions, then quiet again (the 1%/hr → 10%/hr → 1%/hr
    pattern scaled to drill time).  Two runs take the identical fault
    schedule:

    - **baseline**: `preempt()` at the static `static_interval` cadence;
    - **adaptive**: the real stack with a SEPARATE journaled master
      running the policy engine (`--policy`), seeded from a
      preempt-table prior (`--policy-prior`); each worker SIGKILL feeds
      the EWMA preemption-rate estimator through the agent's
      NodeFailure report, and the worker adopts the re-tuned cadence /
      fused-K at fusion boundaries.

    Invariants:

    - adaptive goodput beats baseline by >= `margin` (and clears
      `floor`) on BOTH metrics — the gated one is ledger-derived
      (productive vs rework, the runtime's own attribution) with step
      accounting as a cross-check: the burst collapses the Young–Daly interval,
      so re-executed work shrinks while the static run keeps losing up
      to `static_interval` steps per kill;
    - the decision history TIGHTENS under the burst (min interval below
      the first quiet-regime decision) and raises protection (replica
      ring + warm route);
    - fused-K switches NEVER pay a cold compile: every generation's
      startup and every K cutover is served by the pre-warmed pool
      (compile-cache miss counters stay zero);
    - the master is SIGKILLed mid-run after the burst and restarted on
      the same journal: the decision history served afterwards preserves
      the pre-kill prefix, and the full history is reconstructable from
      the journal files alone (offline `MasterJournal.load`).
    """
    from .common.comm import addr_connectable, find_free_port

    kill_at_steps = sorted(int(s) for s in kill_at_steps)
    kills = len(kill_at_steps)
    report: Dict = {"scenario": "preempt-adaptive",
                    "kill_at_steps": kill_at_steps,
                    "static_interval": static_interval, "margin": margin}

    # ---- static-cadence baseline on the identical fault schedule
    baseline = preempt(total_steps=total_steps, dt=dt,
                       ckpt_interval=static_interval, flash=False,
                       target=0.0, timeout=timeout,
                       kill_at_steps=kill_at_steps, relaunch_always=True)
    report["baseline"] = {k: baseline.get(k) for k in
                          ("goodput", "goodput_wall", "executed_steps",
                           "completed", "cli_rc")}
    report["baseline"]["goodput_ledger"] = _ledger_goodput(
        baseline.get("ledger", {}).get("states", {}))
    report["baseline_kills_landed"] = len(baseline.get("kills", []))

    # ---- pre-warm the pool at K=1 and the quiet-regime ladder K so the
    # adaptive worker's startup and fused-K cutovers are cache hits
    import dataclasses as _dc

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from .auto.warm_pool import WarmPool, WarmSpec, model_spec
    from .models.gpt import GPT, GPTConfig

    cache = tempfile.mkdtemp(prefix="dwt-adaptive-cache-")
    mspec = model_spec(GPT(_dc.replace(
        GPTConfig.nano(), dtype=jnp.float32, use_flash_attention=False,
        remat=False)))
    n_dev = len(jax.devices())
    pool = WarmPool(cache)
    for k in (1, 4):
        pool.warm_async(WarmSpec(
            n_devices=n_dev, strategy=[["fsdp", {}]], model=mspec,
            batch_shape=[8, 32], platform="cpu", fused_steps=k))
    if not pool.wait(timeout=300):
        report.update(ok=False, error="warm-pool prewarm failed",
                      pool=pool.status())
        return report

    # ---- adaptive run: journaled master with the policy engine
    work = tempfile.mkdtemp(prefix="dwt-chaos-adaptive-")
    marker = os.path.join(work, "markers")
    journal_dir = os.path.join(work, "journal")
    os.makedirs(marker)
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(_ADAPTIVE_WORKER)
    prior = policy_prior
    if not prior:
        # drill-scale prior: the same shape preempt_table persists, with
        # regime thresholds sized for a ~minute-long run (config block —
        # brain/policy.py load_prior).  Curve rows calibrate C≈0.1s.
        prior = os.path.join(work, "prior.json")
        with open(prior, "w") as f:
            json.dump({
                "dt": dt, "kills": kills,
                "rows": [{"interval": 10, "goodput": 0.78},
                         {"interval": 200, "goodput": 0.97}],
                "config": {"tau_s": 20.0, "min_interval_steps": 10,
                           "max_interval_steps": static_interval,
                           "replica_mtbf_s": 60.0, "warm_mtbf_s": 300.0,
                           "hysteresis": 0.2,
                           "fused_ladder": [[4, 300.0]]},
            }, f)
    global _launch_seq
    _launch_seq += 1
    job = f"adaptive{os.getpid()}n{_launch_seq}"
    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    env = dict(
        os.environ, DWT_JOB_NAME=job, JAX_PLATFORMS="cpu",
        DWT_SOCKET_DIR=os.path.join(work, "sockets"),
        # the kill burst is a preemption storm, not a crash loop: keep
        # relaunching through 3 consecutive SIGKILLs (same as baseline)
        DWT_CTX_RELAUNCH_ALWAYS="1",
        DWT_COMPILE_CACHE="1", JAX_COMPILATION_CACHE_DIR=cache,
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))

    def spawn_master():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
             f"--port={port}", "--min_nodes=1", "--max_nodes=1",
             f"--journal-dir={journal_dir}", "--poll-interval=0.25",
             "--policy", f"--policy-prior={prior}"],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    t_start = time.monotonic()
    master = spawn_master()
    cli = None
    out = ""
    tightened = protected = prefix_ok = False
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.1)
        if not addr_connectable(addr):
            report.update(ok=False, error="master never came up")
            return report
        cli_env = dict(env, DWT_MASTER_ADDR=addr)
        cli = subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.run",
             "--nnodes=1", "--nproc_per_node=1",
             f"--max_restarts={kills + 1}", script,
             os.path.join(work, "ckpt"), marker, str(total_steps),
             str(dt), "10", str(static_interval)],
            env=cli_env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

        # step-triggered kill burst, identical to the baseline schedule
        steps_log = os.path.join(marker, "steps.log")
        killed = []
        for threshold in kill_at_steps:
            pid = None
            wait_pid = time.monotonic() + max(
                30.0, t_start + timeout * 0.75 - time.monotonic())
            while time.monotonic() < wait_pid and cli.poll() is None:
                if _read_last_step(steps_log) < threshold:
                    time.sleep(0.05)
                    continue
                pids = sorted((f for f in os.listdir(marker)
                               if f.startswith("pid_r")),
                              key=lambda s: int(s[5:]))
                if pids:
                    try:
                        cand = int(open(os.path.join(
                            marker, pids[-1])).read())
                        if cand not in {p["pid"] for p in killed}:
                            os.kill(cand, 0)
                            pid = cand
                            break
                    except (OSError, ValueError):
                        pass
                time.sleep(0.1)
            if pid is None:
                break
            try:
                os.kill(pid, signal.SIGKILL)
                killed.append({"t": round(time.monotonic() - t_start, 1),
                               "at_step": _read_last_step(steps_log),
                               "pid": pid})
            except OSError:
                pass
        report["kills"] = killed

        # ---- SIGKILL the master after the burst; pre-kill history must
        # survive the journal replay as an identical prefix
        from .agent.master_client import MasterClient

        mc = MasterClient(addr, node_id=9999)
        history_before: list = []
        h_deadline = time.monotonic() + 30.0
        while time.monotonic() < h_deadline and not history_before:
            try:
                history_before = mc.get_policy_history()
            except Exception:  # noqa: BLE001
                pass
            if not history_before:
                time.sleep(0.25)
        master.kill()  # SIGKILL — replay must come from the journal
        master.wait(timeout=10)
        time.sleep(1.0)
        master = spawn_master()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.05)

        try:
            out, _ = cli.communicate(
                timeout=max(10.0, t_start + timeout - time.monotonic()))
        except subprocess.TimeoutExpired:
            cli.kill()
            out, _ = cli.communicate()

        history_after: list = []
        try:
            history_after = mc.get_policy_history()
        except Exception:  # noqa: BLE001
            pass

        # ------------------------------------------------------ invariants
        report["cli_rc"] = cli.returncode
        report["completed"] = os.path.exists(os.path.join(marker, "done"))
        report["worker_generations"] = sum(
            1 for f in os.listdir(marker) if f.startswith("pid_r"))
        executed = 0
        try:
            with open(steps_log) as f:
                executed = sum(1 for _ in f)
        except OSError:
            pass
        report["executed_steps"] = executed
        adaptive_goodput = (round(total_steps / executed, 4)
                            if executed >= total_steps else 0.0)
        report["goodput"] = adaptive_goodput

        ledgers = []
        for name in os.listdir(marker):
            if not name.startswith("ledger_r") or name.endswith(".tmp"):
                continue
            try:
                with open(os.path.join(marker, name)) as f:
                    ledgers.append(json.load(f))
            except (OSError, ValueError):
                pass
        ledgers.sort(key=lambda t: t.get("restart", 0))
        agg: Dict[str, float] = {}
        for t in ledgers:
            for k, v in t.get("states", {}).items():
                agg[k] = agg.get(k, 0.0) + float(v)
        report["ledger"] = {
            "states": {k: round(v, 3) for k, v in sorted(agg.items())},
            "generations": len(ledgers)}
        report["goodput_ledger"] = _ledger_goodput(agg)
        report["warm"] = {
            "start_misses": sum(t.get("start_misses", 0)
                                for t in ledgers),
            "start_hits": sum(t.get("start_hits", 0) for t in ledgers),
            "kchange_misses": sum(t.get("kchange_misses", 0)
                                  for t in ledgers),
            "kchange_hits": sum(t.get("kchange_hits", 0)
                                for t in ledgers),
            "kchanges": [c for t in ledgers
                         for c in t.get("kchanges", [])]}

        decisions = []
        try:
            with open(os.path.join(marker, "decisions.log")) as f:
                for ln in f:
                    decisions.append(json.loads(ln))
        except (OSError, ValueError):
            pass
        report["decisions_applied"] = decisions
        intervals = [d["interval"] for d in decisions if d["interval"] > 0]
        tightened = bool(len(intervals) >= 2
                         and min(intervals[1:]) < intervals[0])
        protected = any(d.get("replicas", 0) >= 2
                        and d.get("route") == "warm" for d in decisions)

        def _did(d):
            if isinstance(d, dict):
                return int(d.get("decision_id", 0))
            return int(getattr(d, "decision_id", 0) or 0)

        ids_before = [_did(d) for d in history_before]
        ids_after = [_did(d) for d in history_after]
        report["history"] = {"before_kill": ids_before,
                             "after_replay": ids_after}
        prefix_ok = bool(ids_before
                         and ids_after[:len(ids_before)] == ids_before)
        return report
    finally:
        if master.poll() is None:
            master.terminate()
            try:
                master.wait(timeout=10)
            except subprocess.TimeoutExpired:
                master.kill()
        if cli is not None and cli.poll() is None:
            cli.kill()
        # decision log reconstructable from the JOURNAL ALONE: load the
        # snapshot + frames offline (master stopped) and compare ids
        journal_ids: list = []
        try:
            from .master.journal import MasterJournal

            snap, entries = MasterJournal(journal_dir, fsync=False).load()
            rebuilt = list((snap or {}).get("policy") or [])
            rebuilt += [e["data"]["decision"] for e in entries
                        if e.get("kind") == "policy"]
            journal_ids = sorted({
                int(d["decision_id"] if isinstance(d, dict)
                    else d.decision_id) for d in rebuilt})
        except Exception:  # noqa: BLE001
            logger.warning("journal reconstruction failed", exc_info=True)
        report["journal_decision_ids"] = journal_ids
        ids_after = report.get("history", {}).get("after_replay", [])
        report["journal_matches_history"] = bool(
            ids_after and journal_ids
            and set(ids_after).issubset(set(journal_ids)))
        baseline_ok = bool(
            report["baseline"]["completed"]
            and report["baseline"]["cli_rc"] == 0
            and report["baseline_kills_landed"] == kills)
        report["ok"] = bool(
            baseline_ok
            and report.get("completed") and report.get("cli_rc") == 0
            and len(report.get("kills", [])) == kills
            # the gated metric is LEDGER-derived (the runtime's own
            # attribution), with step accounting as a cross-check
            and report.get("goodput_ledger", 0.0)
            >= report["baseline"]["goodput_ledger"] + margin
            and report.get("goodput", 0.0)
            >= report["baseline"]["goodput"] + margin
            and report.get("goodput", 0.0) >= floor
            and len(report.get("decisions_applied", [])) >= 2
            and tightened and protected
            and report.get("warm", {}).get("kchange_hits", 0) >= 1
            and report.get("warm", {}).get("kchange_misses", 1) == 0
            and report.get("warm", {}).get("start_misses", 1) == 0
            and prefix_ok and report["journal_matches_history"])
        report["adaptation"] = {"tightened": tightened,
                                "protected": protected,
                                "history_prefix_preserved": prefix_ok}
        if report["ok"]:
            import shutil

            shutil.rmtree(work, ignore_errors=True)
            shutil.rmtree(cache, ignore_errors=True)
        else:
            report["cli_tail"] = (out or "")[-3000:]
            report["workdir"] = work


# ------------------------------------------------------------- ckpt corrupt


_CKPT_CORRUPT_SAVER = r"""
import os, sys
import numpy as np

from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)

ckpt_dir = sys.argv[1]
ck = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"],
                       standalone=True)
ck.save_checkpoint(2, {"w": np.full((16, 16), 2.0, np.float32),
                       "step": np.int64(2)},
                   storage_type=StorageType.DISK)
assert ck.wait_latest_checkpoint(60)
# arm the crash: the NEXT persist hard-exits right after the shard file
# write, before meta/manifest — the SIGKILL-mid-persist moment
os.environ["DWT_CKPT_CRASH_POINT"] = "after-bin"
ck.save_checkpoint(4, {"w": np.full((16, 16), 4.0, np.float32),
                       "step": np.int64(4)},
                   storage_type=StorageType.DISK)
ck.wait_latest_checkpoint(60)  # unreachable: the saver dies mid-persist
"""


def ckpt_corrupt(timeout: float = 180.0) -> Dict:
    """Checkpoint trust-boundary drill: the full corruption fault matrix.

    Runs a live flash-checkpoint job (engine + in-process async saver +
    replica ring), commits generations {2, 4, 6}, snapshots the exact
    expected state, then injects each fault and asserts three invariants
    per case: (1) zero silent restores — the corruption is DETECTED (it
    appears in the restore report's fallbacks, or the torn generation is
    invisible by construction); (2) the restore selects the best healthy
    tier and the resumed state is BIT-IDENTICAL to the uncorrupted
    baseline for the step it claims; (3) after a degraded restore the
    recovered state is re-staged into shm / re-replicated (self-heal),
    so the next load takes the fast tier again.

    Faults: flipped byte in shm; flipped byte in storage; truncated
    shard file; missing manifest; stale-generation-only; corrupt replica
    blob (falls through to storage); SIGKILL mid-persist (subprocess
    saver hard-killed between shard write and manifest publish — restore
    falls back to generation N-1 and the doctor flags the torn dir).

    The drill also proves the telemetry contract: a degraded restore
    must reconstruct as ONE trace tree (`ckpt:restore` root + per-tier
    children) from a flight-recorder dump alone, and the goodput ledger
    must carry nonzero `restore_replica`/`restore_storage` credits.
    """
    import shutil

    import numpy as np

    from .checkpoint.checkpointer import FlashCheckpointer, StorageType
    from .checkpoint.ckpt_saver import AsyncCheckpointSaver
    from .checkpoint.integrity import QUARANTINE_DIR
    from .checkpoint.replica import CkptReplicaManager, ReplicaServer

    work = tempfile.mkdtemp(prefix="dwt-chaos-ckptcorrupt-")
    os.environ.setdefault("DWT_SOCKET_DIR", "/tmp/dwt/sockets")
    global _launch_seq
    _launch_seq += 1
    job = f"ckc{os.getpid()}n{_launch_seq}"
    ckpt_dir = os.path.join(work, "ckpt")
    cases = []
    report: Dict = {"scenario": "ckpt-corrupt", "cases": cases}

    def expected(step):
        return {"w": np.full((16, 16), float(step), np.float32),
                "step": np.int64(step)}

    def resume_step(w):
        # one deterministic "training step" — bit-identical resume means
        # this produces byte-equal results from restored vs. baseline
        import jax
        import jax.numpy as jnp

        return np.asarray(jax.jit(
            lambda x: x * jnp.float32(1.0001) + jnp.float32(1.0))(
                jnp.asarray(w)))

    def check(name, restored, rep, want_step, want_tier, extra_ok=True):
        exp = expected(want_step)
        identical = bool(
            restored is not None
            and np.array_equal(np.asarray(restored["w"]), exp["w"])
            and int(restored["step"]) == want_step
            and np.array_equal(resume_step(restored["w"]),
                               resume_step(exp["w"])))
        case = {"fault": name, "tier": rep.get("tier"),
                "step": rep.get("step"),
                "fallbacks": rep.get("fallbacks", []),
                "healed": rep.get("healed", False),
                "bit_identical": identical,
                "ok": bool(identical and rep.get("tier") == want_tier
                           and rep.get("step") == want_step and extra_ok)}
        cases.append(case)
        return case["ok"]

    AsyncCheckpointSaver.reset()
    srv = ReplicaServer()
    srv.start()
    template = {"w": np.zeros((16, 16), np.float32), "step": np.int64(0)}
    mgr = None
    ck = None
    try:
        addr = f"127.0.0.1:{srv.port}"
        # rank 1 is the REMOTE peer holding our backups (rank 0 itself
        # has no server entry: the ring walk refuses to ship a segment
        # back to its creator's own address)
        mgr = CkptReplicaManager(rank=0, peers={1: addr},
                                 job_name=job, replica_count=1)
        ck = FlashCheckpointer(ckpt_dir, job_name=job, standalone=True,
                               replica_fetch=mgr.restore)
        for s in (2, 4, 6):
            ck.save_checkpoint(s, expected(s),
                               storage_type=StorageType.DISK)
            assert ck.wait_latest_checkpoint(60), f"commit of step {s}"
        mgr.backup()  # peer now holds the verified step-6 segment

        shm = ck.engine._shm_handler  # noqa: SLF001 — drill injects faults

        def flip_shm():
            buf = shm._buf.buf  # noqa: SLF001
            buf[1 << 20] = (buf[1 << 20] + 1) % 256

        # --- 1) flipped byte in shm, valid replica -> replica tier serves
        flip_shm()
        restored = ck.load_checkpoint(template)
        rep = ck.last_restore_report
        ok1 = check("shm-flip->replica", restored, rep, 6, "replica",
                    extra_ok=any(f["tier"] == "shm"
                                 for f in rep["fallbacks"]))
        # self-heal: the fetched segment re-verifies, next load is shm
        restored = ck.load_checkpoint(template)
        ok1 = ok1 and ck.last_restore_report["tier"] == "shm"
        cases[-1]["ok"] = ok1

        # --- 2) flipped byte in shm AND in the replica blob -> storage
        flip_shm()
        with srv._lock:  # noqa: SLF001 — corrupt the held backup
            step6, blob = srv._store[0]
            bad = bytearray(blob)
            bad[1 << 20] ^= 0xFF
            srv._store[0] = (step6, bytes(bad))
        restored = ck.load_checkpoint(template)
        rep = ck.last_restore_report
        check("shm+replica-flip->storage", restored, rep, 6, "storage",
              extra_ok=(any(f["tier"] == "shm" for f in rep["fallbacks"])
                        and rep["healed"]))

        # --- 3) flipped byte in the newest storage generation
        shm.mark_empty()
        import glob as _glob

        bin6 = _glob.glob(os.path.join(
            ckpt_dir, "checkpoint-6", "shards_rank*.bin"))[0]
        raw = bytearray(open(bin6, "rb").read())
        raw[64] ^= 0x01
        open(bin6, "wb").write(raw)
        restored = ck.load_checkpoint(template)
        rep = ck.last_restore_report
        qdir = os.path.join(ckpt_dir, QUARANTINE_DIR)
        check("storage-flip->older-gen", restored, rep, 4, "storage",
              extra_ok=(any(f.get("step") == 6 and f.get("quarantined")
                            for f in rep["fallbacks"])
                        and os.path.isdir(qdir)))

        # --- 4) truncated shard file in the (now newest) generation
        shm.mark_empty()
        bin4 = _glob.glob(os.path.join(
            ckpt_dir, "checkpoint-4", "shards_rank*.bin"))[0]
        with open(bin4, "rb+") as f:
            f.truncate(100)
        restored = ck.load_checkpoint(template)
        rep = ck.last_restore_report
        check("truncated-leaf->older-gen", restored, rep, 2, "storage",
              extra_ok=any(f.get("reason") == "truncated-shard-file"
                           for f in rep["fallbacks"]))

        # --- 5) missing manifest on a committed generation
        shm.mark_empty()
        # rebuild a fresh committed gen 8, then rip its manifest out
        ck.save_checkpoint(8, expected(8), storage_type=StorageType.DISK)
        assert ck.wait_latest_checkpoint(60)
        shm.mark_empty()
        os.remove(os.path.join(ckpt_dir, "checkpoint-8", "manifest.json"))
        restored = ck.load_checkpoint(template)
        rep = ck.last_restore_report
        check("missing-manifest->older-gen", restored, rep, 2, "storage",
              extra_ok=any(f.get("reason") == "missing-manifest"
                           for f in rep["fallbacks"]))

        # --- 6) stale generation only: tracker names a vanished gen,
        # only an OLDER committed generation survives on storage
        shm.mark_empty()
        shutil.rmtree(os.path.join(ckpt_dir, "checkpoint-2"))
        ck.save_checkpoint(1, expected(1), storage_type=StorageType.DISK)
        # wait on the generation's OWN manifest: the tracker still says 2
        # (repointed by the earlier quarantine), so the step-agnostic
        # wait_latest_checkpoint would return before the persist lands
        manifest1 = os.path.join(ckpt_dir, "checkpoint-1", "manifest.json")
        deadline = time.monotonic() + 60
        while not os.path.exists(manifest1) and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        assert os.path.exists(manifest1), "step-1 persist never committed"
        shm.mark_empty()
        from .common.constants import CheckpointConstant

        with open(os.path.join(ckpt_dir,  # graftlint: disable=commit-order,atomic-publish -- drill forges a stale tracker on purpose
                               CheckpointConstant.TRACKER_FILE), "w") as f:
            f.write("2")  # retention ate checkpoint-2; tracker is stale
        restored = ck.load_checkpoint(template)
        rep = ck.last_restore_report
        check("stale-generation-only", restored, rep, 1, "storage",
              extra_ok=any(f.get("reason") == "missing-generation"
                           for f in rep["fallbacks"]))
    finally:
        if ck is not None:
            try:
                ck.close()
            except Exception:  # noqa: BLE001
                pass
        AsyncCheckpointSaver.reset()
        if mgr is not None:
            mgr.close()
        srv.stop()

    # flight recorder: every restore above recorded a `ckpt:restore`
    # span with per-tier children (telemetry/spans.py via engine.load).
    # Flush the ring next to the checkpoints and prove a DEGRADED
    # restore reconstructs as one trace tree from the dump alone —
    # root + >1 distinct tier children sharing its trace_id/span_id.
    from .telemetry import get_ledger, get_recorder, load_flight_dumps

    get_recorder().flush(ckpt_dir, "drill")
    dumps = load_flight_dumps(ckpt_dir)
    spans = [e["data"] for d in dumps for e in d.get("events", [])
             if e.get("kind") == "span"]
    roots = [s for s in spans if s.get("name") == "ckpt:restore"]
    trace_trees = 0
    for root in roots:
        tiers = {s["name"] for s in spans
                 if s.get("trace_id") == root.get("trace_id")
                 and s.get("parent_span") == root.get("span_id")
                 and s.get("name", "").startswith("ckpt:restore:")}
        if len(tiers) > 1 and root.get("attrs", {}).get("fallbacks", 0):
            trace_trees += 1
    led_states = get_ledger().snapshot()["states"]
    report["flight"] = {
        "dumps": len(dumps), "restore_spans": len(roots),
        "degraded_trace_trees": trace_trees,
        "ledger": {k: round(led_states.get(k, 0.0), 4)
                   for k in ("restore_shm", "restore_replica",
                             "restore_storage")},
    }
    flight_ok = bool(dumps and trace_trees > 0
                     and led_states.get("restore_replica", 0.0) > 0
                     and led_states.get("restore_storage", 0.0) > 0)

    # --- 7) SIGKILL mid-persist (subprocess saver, crash between shard
    # write and manifest publish) -> restore serves generation N-1
    sub_work = os.path.join(work, "midpersist")
    os.makedirs(sub_work)
    sub_ckpt = os.path.join(sub_work, "ckpt")
    _launch_seq += 1
    sub_job = f"ckm{os.getpid()}n{_launch_seq}"
    env = dict(os.environ, DWT_JOB_NAME=sub_job, JAX_PLATFORMS="cpu",
               DWT_SOCKET_DIR=os.path.join(sub_work, "sockets"),
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))) + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    script = os.path.join(sub_work, "saver.py")
    with open(script, "w") as f:
        f.write(_CKPT_CORRUPT_SAVER)
    proc = subprocess.run([sys.executable, script, sub_ckpt], env=env,
                          cwd=sub_work, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          timeout=timeout)
    AsyncCheckpointSaver.reset()
    _launch_seq += 1
    verify_job = f"ckv{os.getpid()}n{_launch_seq}"
    ck2 = FlashCheckpointer(sub_ckpt, job_name=verify_job,
                            standalone=True)
    try:
        restored = ck2.load_checkpoint(
            {"w": np.zeros((16, 16), np.float32), "step": np.int64(0)})
        rep = ck2.last_restore_report
        # the dead saver's shm segment must have been reaped on startup
        # (stale-segment sweeper) — its creator pid is gone
        swept = not os.path.exists(f"/dev/shm/{sub_job}_ckpt_shm_0")
        torn_dir = os.path.join(sub_ckpt, "checkpoint-4")
        torn_detectable = (os.path.isdir(torn_dir) and not os.path.exists(
            os.path.join(torn_dir, "manifest.json")))
        identical = bool(
            restored is not None
            and np.array_equal(np.asarray(restored["w"]),
                               np.full((16, 16), 2.0, np.float32))
            and int(restored["step"]) == 2)
        cases.append({
            "fault": "sigkill-mid-persist", "tier": rep.get("tier"),
            "step": rep.get("step"), "saver_rc": proc.returncode,
            "bit_identical": identical, "swept_stale_shm": swept,
            "torn_gen_detectable": torn_detectable,
            "ok": bool(proc.returncode == 137 and identical
                       and rep.get("step") == 2 and swept
                       and torn_detectable)})
    finally:
        ck2.close()
        AsyncCheckpointSaver.reset()

    # the doctor must independently flag the torn generation
    import json as _json

    doctor = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "ckpt_doctor.py"),
         sub_ckpt], stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, timeout=60)
    try:
        verdict = _json.loads(doctor.stdout.strip().splitlines()[-1])
        bad = [g for g in verdict["ckpt_doctor"]["generations"]
               if not g["ok"]]
        report["doctor"] = {"rc": doctor.returncode,
                            "flagged_steps": [g["step"] for g in bad]}
        doctor_ok = doctor.returncode == 1 and any(
            g["step"] == 4 for g in bad)
    except (ValueError, KeyError, IndexError):
        report["doctor"] = {"rc": doctor.returncode, "parse": "failed"}
        doctor_ok = False

    report["silent_restores"] = sum(
        1 for c in cases if not c.get("bit_identical"))
    report["ok"] = bool(all(c["ok"] for c in cases) and doctor_ok
                        and flight_ok and len(cases) == 7)
    if report["ok"]:
        shutil.rmtree(work, ignore_errors=True)
    else:
        report["workdir"] = work
        if proc.stdout:
            report["saver_tail"] = proc.stdout[-1500:]
    return report


# -------------------------------------------------------------- master kill


_MASTER_KILL_WORKER = r"""
import json, os, sys, time

from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
from dlrover_wuqiong_tpu.telemetry import get_ledger

(_ckpt_dir, marker_dir, dataset_size, batch, minibatches, dt) = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), float(sys.argv[6]))
ctx = init_elastic()
restart = ctx.world.restart_count
# the outage's cost surfaces in the GOODPUT LEDGER: master_client
# credits `degraded` for every second a verb burned blocked on the dead
# master, while training time through the outage stays `productive`
led = get_ledger()
led.start()
with open(os.path.join(marker_dir, f"start_r{restart}"), "w") as f:
    f.write(str(os.getpid()))
# dynamic sharding straight off the master: every fetched range and every
# completed range is logged so the drill can prove the journal replayed
# EXACTLY (no range lost, none handed out twice across the restart)
sc = ctx.sharding_client("chaos-mk", batch_size=batch,
                         dataset_size=dataset_size,
                         num_minibatches_per_shard=minibatches)
log = open(os.path.join(marker_dir, "shards.log"), "a")
step = 0
while True:
    task = sc.fetch_shard(wait=True, timeout=120.0)
    if task is None:
        break
    log.write(f"fetch {time.time():.3f} {task.task_id} "
              f"{task.shard.start} {task.shard.end}\n")
    log.flush()
    for i in range((task.shard.end - task.shard.start) // batch):
        with led.window("productive"):
            time.sleep(dt)  # one training step
        step += 1
        # per-step heartbeat: CRITICAL during the drill — these are the
        # frames that must buffer (not block, not crash) while the master
        # is dead, then drain after reconnect
        ctx.mc.report_heart_beat(step)
        log.write(f"step {time.time():.3f} {step}\n")
        log.flush()
    sc.report_shard_done(task.task_id)
    log.write(f"done {time.time():.3f} {task.task_id} "
              f"{task.shard.start} {task.shard.end}\n")
    log.flush()
stats = ctx.mc.degraded_stats()
with open(os.path.join(marker_dir, "done"), "w") as f:
    json.dump({"steps": step, "stats": stats,
               "ledger": led.snapshot()}, f)
# flight dump carries this worker's ledger + events into the incident
# timeline the drill gates on (telemetry/timeline.py): flush BEFORE
# exit so the offline assembly sees the same artifacts the live
# TimelineQuery does
from dlrover_wuqiong_tpu.telemetry import get_recorder
get_recorder().flush(_ckpt_dir, "drill-end")
"""


def master_kill(dataset_size: int = 576, batch: int = 4,
                minibatches: int = 24, dt: float = 0.08,
                outage_s: float = 1.5, target: float = 0.5,
                timeout: float = 240.0) -> Dict:
    """SIGKILL the job MASTER mid-run; restart it on the same journal.

    The reference's headline claim — no single process is fatal — applied
    to the master itself: the drill runs the real stack with the master as
    a SEPARATE process journaling every control-plane mutation
    (master/journal.py), hard-kills it while the worker is mid-shard, and
    restarts it on the same journal + port.  Invariants:

    - the worker NEVER crashes or restarts (exit clean, one generation);
    - dataset ranges tile exactly: none lost, none double-trained —
      journal replay reconstructed splitter cursors + in-flight tasks;
    - training steps land INSIDE the outage window (elastic hooks do not
      block on the dead master — heartbeats buffer in degraded mode);
    - the heartbeat buffer fully drains after reconnect, and the client
      observed the fencing-epoch bump + re-registered;
    - the worker's GOODPUT LEDGER shows the split: `degraded` (seconds
      burned blocked on the dead master) is nonzero AND `productive`
      kept accruing through the outage (telemetry/ledger.py);
    - wall-clock goodput (ideal step time / span) stays over `target`.
    """
    from .common.comm import addr_connectable, find_free_port

    work = tempfile.mkdtemp(prefix="dwt-chaos-masterkill-")
    marker = os.path.join(work, "markers")
    journal_dir = os.path.join(work, "journal")
    os.makedirs(marker)
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(_MASTER_KILL_WORKER)
    global _launch_seq
    _launch_seq += 1
    job = f"masterkill{os.getpid()}n{_launch_seq}"
    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    env = dict(
        os.environ, DWT_JOB_NAME=job, JAX_PLATFORMS="cpu",
        DWT_SOCKET_DIR=os.path.join(work, "sockets"),
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))

    def spawn_master():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
             f"--port={port}", "--min_nodes=1", "--max_nodes=1",
             f"--journal-dir={journal_dir}", "--poll-interval=0.5"],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    report: Dict = {"scenario": "master-kill", "outage_s": outage_s}
    master = spawn_master()
    cli = None
    out = ""
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.1)
        if not addr_connectable(addr):
            report.update(ok=False, error="master never came up")
            return report
        cli_env = dict(env, DWT_MASTER_ADDR=addr)
        cli = subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.run",
             "--nnodes=1", "--nproc_per_node=1", "--max_restarts=2",
             script, os.path.join(work, "ckpt"), marker,
             str(dataset_size), str(batch), str(minibatches), str(dt)],
            env=cli_env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

        # kill the master just after a mid-run shard fetch: the worker is
        # then provably mid-shard through the outage window
        shards_log = os.path.join(marker, "shards.log")
        kill_after_fetches = 2
        kill_t = restart_t = -1.0
        deadline = time.monotonic() + timeout / 2
        while time.monotonic() < deadline and cli.poll() is None:
            try:
                with open(shards_log) as f:
                    fetches = sum(1 for ln in f if ln.startswith("fetch "))
                if fetches >= kill_after_fetches:
                    break
            except OSError:
                pass
            time.sleep(0.05)
        else:
            report.update(ok=False, error="worker never reached the kill "
                                          "point", cli_rc=cli.poll())
            return report
        time.sleep(dt * 2)  # be safely inside the shard's step loop
        master.kill()  # SIGKILL — no snapshot, no goodbye
        master.wait(timeout=10)
        kill_t = time.time()
        logger.info("master-kill: SIGKILLed master pid=%d", master.pid)
        time.sleep(outage_s)
        master = spawn_master()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.05)
        # kill_t/restart_t stay WALL clock: they bracket step timestamps
        # the worker logs with time.time() in another process
        restart_t = time.time()
        report["measured_outage_s"] = round(restart_t - kill_t, 2)
        # restart-the-world baseline NET of the drill's deliberate idle
        # window: process spawn + jax import + journal replay until the
        # replacement answers.  `chaos master-failover` asserts its
        # promotion gap beats this number measured in the SAME
        # environment (never a hardcoded threshold).
        report["restart_gap_s"] = round(restart_t - kill_t - outage_s, 2)

        try:
            out, _ = cli.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            cli.kill()
            out, _ = cli.communicate()

        # ------------------------------------------------------ invariants
        report["cli_rc"] = cli.returncode
        report["worker_generations"] = sum(
            1 for f in os.listdir(marker) if f.startswith("start_r"))
        done_path = os.path.join(marker, "done")
        report["completed"] = os.path.exists(done_path)
        stats: Dict = {}
        worker_ledger: Dict = {}
        if report["completed"]:
            with open(done_path) as f:
                payload = json.load(f)
            stats = payload.get("stats", {})
            report["degraded"] = stats
            worker_ledger = payload.get("ledger", {})
        led_states = worker_ledger.get("states", {})
        # the ledger is the drill's downtime split: blocked-on-dead-master
        # seconds land in `degraded`, steps through the outage stay
        # `productive` (master_client._account_degraded)
        report["ledger"] = {
            "degraded_s": round(float(led_states.get("degraded", 0.0)), 3),
            "productive_s": round(
                float(led_states.get("productive", 0.0)), 3),
            "goodput_fraction": round(
                float(worker_ledger.get("goodput_fraction", 0.0)), 4),
        }
        fetched, completed, steps = [], [], []
        try:
            with open(shards_log) as f:
                for ln in f:
                    parts = ln.split()
                    if parts[0] == "fetch":
                        fetched.append((int(parts[3]), int(parts[4])))
                    elif parts[0] == "done":
                        completed.append((int(parts[3]), int(parts[4])))
                    elif parts[0] == "step":
                        steps.append(float(parts[1]))
        except OSError:
            pass
        # exact tiling: completed ranges cover [0, dataset_size) once
        covered = sorted(completed)
        tiles_ok = (sum(e - s for s, e in covered) == dataset_size
                    and all(covered[i][1] == covered[i + 1][0]
                            for i in range(len(covered) - 1))
                    and bool(covered) and covered[0][0] == 0
                    and covered[-1][1] == dataset_size)
        report["shards_completed"] = len(completed)
        report["shards_fetched"] = len(fetched)
        report["no_shard_lost_or_double"] = bool(
            tiles_ok and len(fetched) == len(completed))
        report["steps_in_outage"] = sum(
            1 for t in steps if kill_t <= t <= restart_t)
        total_steps = dataset_size // batch
        if steps:
            span = max(steps) - min(steps) + dt
            report["goodput_wall"] = round(total_steps * dt / span, 3)
        else:
            report["goodput_wall"] = 0.0
        report["heartbeats_buffered"] = stats.get("buffered_total", 0)
        report["buffer_drained"] = (stats.get("pending", 1) == 0
                                    and stats.get("dropped_total", 1) == 0)
        report["epoch_bumped"] = 2 in stats.get("epochs_seen", [])
        report["reregistered"] = stats.get("reregistrations", 0) >= 1

        # ------------------------------------------- incident timeline gate
        # The drill's observability claim (telemetry/timeline.py): the live
        # TimelineQuery against the RESTARTED master byte-equals the offline
        # assembly from the same disk artifacts, every journaled event
        # appears exactly once in (epoch, seq) order across the fencing
        # bump, and the narrative's degraded attribution agrees with the
        # worker's own ledger.
        from .agent.master_client import MasterClient
        from .telemetry import timeline as tl

        ckpt_dir = os.path.join(work, "ckpt")
        mc = MasterClient(addr, node_id=-1)
        try:
            live = mc.get_timeline(ckpt_dir=ckpt_dir)
            # the restarted master must be running the group-commit
            # journal (the default): the drill's exactly-once claims
            # below hold UNDER batched fsync, not just per-frame
            js = mc.get_journal_stats()
            report["journal_group_commit"] = {
                "enabled": js.enabled, "group_commit": js.group_commit,
                "max_frames": js.max_frames,
                "batch_mean": round(js.batch_mean, 2),
                "durable_seq": js.durable_seq}
        finally:
            mc.close()
        offline = tl.assemble_incident(journal_dir=journal_dir,
                                       ckpt_dir=ckpt_dir)
        report["timeline_events"] = live.events
        report["timeline_byte_equal"] = (
            live.content == tl.incident_json(offline))
        jkeys = [(e["epoch"], e["seq"]) for e in offline["events"]
                 if e["source"] == "journal" and e["kind"] != "flush"]
        report["timeline_causal"] = (
            jkeys == sorted(jkeys) and len(jkeys) == len(set(jkeys))
            and len(jkeys) == offline["counts"]["journal_events"])
        report["timeline_epochs"] = offline["counts"]["epochs"]
        narr = offline["narrative"]
        deg_lost = sum(float(i.get("lost_s", 0.0))
                       for i in narr["incidents"]
                       if i.get("attributed_state") == "degraded")
        report["timeline_degraded_s"] = round(deg_lost, 3)
        report["timeline_attribution_ok"] = abs(
            deg_lost - report["ledger"]["degraded_s"]) <= 0.05
        # the offline CLI on the same artifacts must hash to the live bytes
        tools_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        p = subprocess.run(
            [sys.executable, os.path.join(tools_dir, "incident_report.py"),
             "--journal", journal_dir, "--flight", ckpt_dir],
            capture_output=True, text=True, env=env, timeout=120)
        try:
            cli_line = json.loads(p.stdout)
        except ValueError:
            cli_line = {}
        report["incident_report_rc"] = p.returncode
        report["incident_report_sha_match"] = bool(
            p.returncode == 0
            and cli_line.get("timeline_sha256")
            == tl.incident_sha256(live.content))

        report["ok"] = bool(
            report["completed"] and cli.returncode == 0
            and report["worker_generations"] == 1
            and report["no_shard_lost_or_double"]
            and report["steps_in_outage"] > 0
            and report["heartbeats_buffered"] > 0
            and report["buffer_drained"]
            and report["epoch_bumped"] and report["reregistered"]
            and report["ledger"]["degraded_s"] > 0
            and report["ledger"]["productive_s"] > 0
            and report["goodput_wall"] >= target
            and report["timeline_byte_equal"]
            and report["timeline_causal"]
            and report["timeline_epochs"] == [1, 2]
            and report["timeline_attribution_ok"]
            and report["incident_report_sha_match"]
            and report["journal_group_commit"]["enabled"]
            and report["journal_group_commit"]["group_commit"])
        return report
    finally:
        if master.poll() is None:
            master.terminate()
            try:
                master.wait(timeout=10)
            except subprocess.TimeoutExpired:
                master.kill()
        if cli is not None and cli.poll() is None:
            cli.kill()
        if report.get("ok"):
            import shutil

            shutil.rmtree(work, ignore_errors=True)
        else:
            report["cli_tail"] = (out or "")[-2000:]
            report["workdir"] = work


# ---------------------------------------------------------- master failover


def master_failover(dataset_size: int = 576, batch: int = 4,
                    minibatches: int = 24, dt: float = 0.08,
                    lease_ttl: float = 1.0, target: float = 0.5,
                    timeout: float = 300.0) -> Dict:
    """SIGKILL the PRIMARY master; a warm standby takes over, fenced.

    The master-kill drill's gap — the fleet buffering until something
    restarts the process — is the cost ISSUE 20 removes: here a standby
    (`--standby-of`) tails the primary's journal over the fetch_journal
    verb, the primary heartbeats a leadership lease into that same
    journal, and on lease expiry the standby journals a ``failover``
    frame and promotes with an epoch strictly above anything the corpse
    could issue.  Invariants:

    - the worker NEVER restarts (one generation) and its endpoint list
      ("primary,standby") fails over with at least one rotation;
    - dataset ranges tile exactly across the takeover — the standby's
      mirrored journal reconstructed cursors + in-flight tasks, and
      idem-keyed retries stay exactly-once under the NEW epoch;
    - buffered verbs drain to the new leader (pending=0, dropped=0) and
      the client observed the promoted epoch (old+2) + re-registered;
    - the promotion gap (SIGKILL → standby serving as leader, lease-ttl
      detection included) beats the restart-the-world baseline measured
      in THIS environment: reviving the corpse and timing spawn→serving
      (the same quantity master-kill reports as ``restart_gap_s``) plus
      the SAME lease-ttl detection floor — no supervisor restarts a
      master it has not yet declared dead.  Never a hardcoded number;
    - the revived corpse self-fences via its ``--peer`` probe: read
      verbs answer, mutating verbs bounce with NotLeaderError;
    - the live incident timeline from the PROMOTED master byte-equals
      the offline assembly over BOTH journal dirs merged in (epoch,
      seq) order, with the takeover narrated as incident kind
      ``failover``.
    """
    from .common.comm import (RpcClient, RpcError, addr_connectable,
                              find_free_port)
    from .common import messages as msg

    work = tempfile.mkdtemp(prefix="dwt-chaos-failover-")
    marker = os.path.join(work, "markers")
    jd_primary = os.path.join(work, "journal-primary")
    jd_standby = os.path.join(work, "journal-standby")
    os.makedirs(marker)
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(_MASTER_KILL_WORKER)
    global _launch_seq
    _launch_seq += 1
    job = f"failover{os.getpid()}n{_launch_seq}"
    port_p, port_sb = find_free_port(), find_free_port()
    addr_p = f"127.0.0.1:{port_p}"
    addr_sb = f"127.0.0.1:{port_sb}"
    env = dict(
        os.environ, DWT_JOB_NAME=job, JAX_PLATFORMS="cpu",
        DWT_SOCKET_DIR=os.path.join(work, "sockets"),
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))

    def spawn_primary():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
             f"--port={port_p}", "--min_nodes=1", "--max_nodes=1",
             f"--journal-dir={jd_primary}", "--poll-interval=0.5",
             f"--lease-ttl={lease_ttl}", f"--peer={addr_sb}"],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    def spawn_standby():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
             f"--port={port_sb}", "--min_nodes=1", "--max_nodes=1",
             f"--journal-dir={jd_standby}", "--poll-interval=0.5",
             f"--lease-ttl={lease_ttl}", f"--standby-of={addr_p}"],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    def _probe(addr, timeout_s=2.0):
        """One JournalStatsQuery, None on any failure."""
        client = RpcClient(addr, node_id=-4, node_type="probe",
                           timeout=timeout_s, retries=1,
                           base_delay_s=0.02, max_delay_s=0.05)
        try:
            return client.get(msg.JournalStatsQuery())
        except RpcError:
            return None
        finally:
            client.close()

    report: Dict = {"scenario": "master-failover", "lease_ttl": lease_ttl}
    primary = spawn_primary()
    standby = cli = corpse = None
    out = ""
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr_p):
            time.sleep(0.1)
        if not addr_connectable(addr_p):
            report.update(ok=False, error="primary never came up")
            return report
        standby = spawn_standby()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr_sb):
            time.sleep(0.1)
        if not addr_connectable(addr_sb):
            report.update(ok=False, error="standby never came up")
            return report
        # gate the kill on the mirror actually flowing: the primary's
        # shipping gauges go live on the standby's first fetch
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            s = _probe(addr_p)
            if s is not None and s.standby_lag_frames >= 0:
                break
            time.sleep(0.1)
        else:
            report.update(ok=False, error="standby never fetched")
            return report

        cli_env = dict(env, DWT_MASTER_ADDR=f"{addr_p},{addr_sb}")
        cli = subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.run",
             "--nnodes=1", "--nproc_per_node=1", "--max_restarts=2",
             script, os.path.join(work, "ckpt"), marker,
             str(dataset_size), str(batch), str(minibatches), str(dt)],
            env=cli_env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

        # kill just after a mid-run shard fetch (same point as master-kill)
        shards_log = os.path.join(marker, "shards.log")
        deadline = time.monotonic() + timeout / 2
        while time.monotonic() < deadline and cli.poll() is None:
            try:
                with open(shards_log) as f:
                    fetches = sum(1 for ln in f if ln.startswith("fetch "))
                if fetches >= 2:
                    break
            except OSError:
                pass
            time.sleep(0.05)
        else:
            report.update(ok=False, error="worker never reached the kill "
                                          "point", cli_rc=cli.poll())
            return report
        time.sleep(dt * 2)
        pre = _probe(addr_p)
        report["pre_kill"] = {
            "durable_seq": getattr(pre, "durable_seq", -1),
            "shipped_seq": getattr(pre, "shipped_seq", -1),
            "standby_lag_frames": getattr(pre, "standby_lag_frames", -2)}
        primary.kill()  # SIGKILL — no snapshot, no goodbye
        primary.wait(timeout=10)
        kill_t = time.time()
        logger.info("master-failover: SIGKILLed primary pid=%d",
                    primary.pid)

        # promotion gap: SIGKILL → the standby answering as leader
        promoted_t = -1.0
        deadline = time.monotonic() + lease_ttl * 10 + 60.0
        while time.monotonic() < deadline:
            s = _probe(addr_sb, timeout_s=1.0)
            if s is not None and s.is_leader:
                promoted_t = time.time()
                report["promoted_epoch"] = s.epoch
                break
            time.sleep(0.05)
        if promoted_t < 0:
            report.update(ok=False, error="standby never promoted")
            return report
        report["promotion_gap_s"] = round(promoted_t - kill_t, 2)

        try:
            out, _ = cli.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            cli.kill()
            out, _ = cli.communicate()

        # ------------------------------------------------------ invariants
        report["cli_rc"] = cli.returncode
        report["worker_generations"] = sum(
            1 for f in os.listdir(marker) if f.startswith("start_r"))
        done_path = os.path.join(marker, "done")
        report["completed"] = os.path.exists(done_path)
        stats: Dict = {}
        worker_ledger: Dict = {}
        if report["completed"]:
            with open(done_path) as f:
                payload = json.load(f)
            stats = payload.get("stats", {})
            report["degraded"] = stats
            worker_ledger = payload.get("ledger", {})
        led_states = worker_ledger.get("states", {})
        report["ledger"] = {
            "degraded_s": round(float(led_states.get("degraded", 0.0)), 3),
            "productive_s": round(
                float(led_states.get("productive", 0.0)), 3),
        }
        fetched, completed, steps = [], [], []
        try:
            with open(shards_log) as f:
                for ln in f:
                    parts = ln.split()
                    if parts[0] == "fetch":
                        fetched.append((int(parts[3]), int(parts[4])))
                    elif parts[0] == "done":
                        completed.append((int(parts[3]), int(parts[4])))
                    elif parts[0] == "step":
                        steps.append(float(parts[1]))
        except OSError:
            pass
        covered = sorted(completed)
        tiles_ok = (sum(e - s for s, e in covered) == dataset_size
                    and all(covered[i][1] == covered[i + 1][0]
                            for i in range(len(covered) - 1))
                    and bool(covered) and covered[0][0] == 0
                    and covered[-1][1] == dataset_size)
        report["shards_completed"] = len(completed)
        report["no_shard_lost_or_double"] = bool(
            tiles_ok and len(fetched) == len(completed))
        total_steps = dataset_size // batch
        if steps:
            span = max(steps) - min(steps) + dt
            report["goodput_wall"] = round(total_steps * dt / span, 3)
        else:
            report["goodput_wall"] = 0.0
        report["heartbeats_buffered"] = stats.get("buffered_total", 0)
        report["buffer_drained"] = (stats.get("pending", 1) == 0
                                    and stats.get("dropped_total", 1) == 0)
        report["client_failovers"] = stats.get("failovers", 0)
        promoted_epoch = report.get("promoted_epoch", -1)
        report["epoch_fenced"] = promoted_epoch in stats.get(
            "epochs_seen", [])
        report["reregistered"] = stats.get("reregistrations", 0) >= 1

        # ------------------------------------- restart-the-world baseline
        # revive the corpse on its own journal: spawn→serving is exactly
        # the restart_gap_s master-kill measures, in the SAME environment.
        # The full restart-the-world cost ADDS the detection floor: no
        # supervisor restarts a master it has not yet declared dead, and
        # the cheapest honest declaration is the same lease ttl of
        # silence the standby itself waited out — so the comparison puts
        # the identical detection term on both sides and lets the
        # MEASURED mechanics (promote-in-place vs spawn+import+replay)
        # decide, never a hardcoded number.
        spawn_t = time.monotonic()
        corpse = spawn_primary()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and not addr_connectable(addr_p):
            time.sleep(0.05)
        if not addr_connectable(addr_p):
            report.update(ok=False, error="corpse never came back")
            return report
        report["restart_gap_s"] = round(time.monotonic() - spawn_t, 2)
        report["restart_the_world_s"] = round(
            report["restart_gap_s"] + lease_ttl, 2)
        report["promotion_beats_restart"] = bool(
            report["promotion_gap_s"] < report["restart_the_world_s"])

        # ------------------------------------------------ split-brain gate
        cs = _probe(addr_p)
        report["corpse_fenced"] = bool(
            cs is not None and not cs.is_leader
            and cs.epoch < promoted_epoch)
        corpse_cli = RpcClient(addr_p, node_id=-4, node_type="probe",
                               timeout=2.0, retries=1)
        try:
            read_ok = not corpse_cli.get(
                msg.KVStoreGetRequest(key="chaos-fo")).found
            try:
                corpse_cli.report(msg.KVStoreSetRequest(
                    key="chaos-fo", value=b"split"))
                mutation_refused = False
            except RpcError as e:
                mutation_refused = "NotLeaderError" in str(e)
        finally:
            corpse_cli.close()
        report["corpse_read_ok"] = bool(read_ok)
        report["corpse_mutation_refused"] = bool(mutation_refused)

        # ---------------------------------------- incident timeline gate
        # live (promoted standby, BOTH dirs) vs offline over the same
        # ordered dir list — byte-equal, exactly-once (epoch, seq), and
        # the takeover narrated as kind="failover"
        from .agent.master_client import MasterClient
        from .telemetry import timeline as tl

        ckpt_dir = os.path.join(work, "ckpt")
        mc = MasterClient(addr_sb, node_id=-1)
        try:
            live = mc.get_timeline(ckpt_dir=ckpt_dir,
                                   journal_dirs=[jd_standby, jd_primary])
        finally:
            mc.close()
        offline = tl.assemble_incident(journal_dir=jd_standby,
                                       ckpt_dir=ckpt_dir,
                                       journal_dirs=[jd_primary])
        report["timeline_byte_equal"] = (
            live.content == tl.incident_json(offline))
        jkeys = [(e["epoch"], e["seq"]) for e in offline["events"]
                 if e["source"] == "journal" and e["kind"] != "flush"]
        report["timeline_causal"] = (
            jkeys == sorted(jkeys) and len(jkeys) == len(set(jkeys))
            and len(jkeys) == offline["counts"]["journal_events"])
        kinds = [i["kind"] for i in offline["narrative"]["incidents"]]
        report["timeline_failover_incident"] = "failover" in kinds

        report["ok"] = bool(
            report["completed"] and cli.returncode == 0
            and report["worker_generations"] == 1
            and report["no_shard_lost_or_double"]
            and report["heartbeats_buffered"] > 0
            and report["buffer_drained"]
            and report["client_failovers"] >= 1
            and report["epoch_fenced"] and report["reregistered"]
            and report["ledger"]["degraded_s"] > 0
            and report["ledger"]["productive_s"] > 0
            and report["goodput_wall"] >= target
            and report["pre_kill"]["standby_lag_frames"] >= 0
            and report["promotion_beats_restart"]
            and report["corpse_fenced"]
            and report["corpse_read_ok"]
            and report["corpse_mutation_refused"]
            and report["timeline_byte_equal"]
            and report["timeline_causal"]
            and report["timeline_failover_incident"])
        return report
    finally:
        for proc in (corpse, standby):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        if primary.poll() is None:
            primary.kill()
        if cli is not None and cli.poll() is None:
            cli.kill()
        if report.get("ok"):
            import shutil

            shutil.rmtree(work, ignore_errors=True)
        else:
            report["cli_tail"] = (out or "")[-2000:]
            report["workdir"] = work


_HOT_SWAP_WORKER = r"""
import json, os, sys, time

import numpy as np

import jax
import jax.numpy as jnp

(ckpt_dir, marker_dir, rank_s, nodes_s, steps_s, kfuse_s, dt_s) = \
    sys.argv[1:8]
rank, n_nodes = int(rank_s), int(nodes_s)
total_steps, K, dt = int(steps_s), int(kfuse_s), float(dt_s)
addr = os.environ["DWT_MASTER_ADDR"]

from dlrover_wuqiong_tpu.agent.master_client import MasterClient
from dlrover_wuqiong_tpu.checkpoint.replica import (CkptReplicaManager,
                                                    ReplicaServer)
from dlrover_wuqiong_tpu.checkpoint.shm_handler import SharedMemoryHandler
from dlrover_wuqiong_tpu.telemetry import get_ledger, get_recorder
from dlrover_wuqiong_tpu.trainer.hotswap import HotSwapParticipant

log = open(os.path.join(marker_dir, f"log_r{rank}"), "a")


def emit(line):
    log.write(line + "\n")
    log.flush()


mc = MasterClient(addr, node_id=rank)
mc.register_node(rank)
led = get_ledger()
led.start()

# replica ring: one server per node, addresses exchanged via the KV store
server = ReplicaServer(host="127.0.0.1")
server.start()
mc.kv_store_set(f"hsw/replica/{rank}", f"127.0.0.1:{server.port}".encode())
peers = {}
while len(peers) < n_nodes:
    for r in range(n_nodes):
        if r not in peers:
            v = mc.kv_store_get(f"hsw/replica/{r}")
            if v:
                peers[r] = v.decode()
    time.sleep(0.05)
job = os.environ["DWT_JOB_NAME"] + f"r{rank}"
shm = SharedMemoryHandler(0, job)
rep = CkptReplicaManager(rank=rank, peers=peers, job_name=job,
                         replica_count=1, lock_timeout=0.2)

mc.join_rendezvous(rank, 1, node_ip="127.0.0.1", free_port=server.port)
while True:
    st = mc.get_comm_world()
    if st.complete and len(st.world) >= n_nodes:
        break
    time.sleep(0.05)
emit(f"world {time.time():.3f} {st.rdzv_round}")

# deterministic per-shard "training": the update is ELEMENTWISE, so
# stepping the shards separately bit-equals stepping their concatenation
# — the drill's golden degraded-mesh run relies on this
DIM = 16


def shard_init(r):
    return (np.arange(DIM, dtype=np.float32) + 1.0) * np.float32(
        0.1 * (r + 1))


traces = {"n": 0}


def _step(w, s):
    traces["n"] += 1  # trace-time side effect: counts XLA compiles
    g = w * jnp.float32(0.01) + jnp.float32(1e-4) * s.astype(jnp.float32)
    return w - jnp.float32(0.1) * g


stepfn = jax.jit(_step)
# warm-pool analog: compile BOTH mesh geometries up front — cutover onto
# the degraded (full-vector) executable must never pay a cold compile
stepfn(jnp.zeros((DIM,), jnp.float32), jnp.int32(0)).block_until_ready()
stepfn(jnp.zeros((n_nodes * DIM,), jnp.float32),
       jnp.int32(0)).block_until_ready()
warm = traces["n"]

w = jnp.asarray(shard_init(rank))
cur = {"w": w, "step": 0}
hist = {}


def cutover_cb(hydrated, st):
    if hydrated is None:
        return False
    dstep, flat, extra = hydrated
    dstep = int(dstep)
    own = hist.get(dstep)
    if own is None:
        # survivor paused BEHIND the victim's last stage: roll the own
        # shard forward to the merge step (shard-local update — exact)
        if dstep < cur["step"]:
            return False
        wtmp, s = cur["w"], cur["step"]
        while s < dstep:
            wtmp = stepfn(wtmp, jnp.int32(s))
            s += 1
        own = np.asarray(wtmp)
    parts = {rank: np.asarray(own, np.float32),
             int(st.dead_rank): np.asarray(flat["w"], np.float32)}
    full = np.concatenate([parts[r] for r in sorted(parts)])
    cur["resume"] = (dstep, jnp.asarray(full))
    return True


hs = HotSwapParticipant(mc, node_id=rank, replica_manager=rep,
                        cutover_cb=cutover_cb, ledger=led)

mode = "duo"
step = 0
swap_seen = -1.0
while True:
    if cur.get("resume") is not None:
        dstep, wfull = cur.pop("resume")
        step, w, mode = dstep, wfull, "solo"
        emit(f"cutover {time.time():.3f} {dstep} {traces['n']}")
        if swap_seen > 0:
            emit(f"recover {time.time():.3f} "
                 f"{time.monotonic() - swap_seen:.3f}")
    if step >= total_steps:
        break
    for _ in range(K):  # one fused window; boundary work below only
        with led.window("productive"):
            w = stepfn(w, jnp.int32(step))
            time.sleep(dt)
        step += 1
    cur["w"], cur["step"] = w, step
    if mode == "duo":
        arr = np.asarray(w)
        hist[step] = arr.copy()
        shm.save_state_dict({"w": arr}, step=step)
        rep.backup()
        emit(f"stage {time.time():.3f} {step}")
    else:
        loss = float(jnp.mean(w * w))
        emit(f"loss {time.time():.3f} {step} {loss.hex()}")
    mc.report_heart_beat(step)
    ph = hs.poll()  # fusion boundary: the ONLY place swap work happens
    if ph is not None and swap_seen < 0:
        swap_seen = time.monotonic()
        emit(f"swapseen {time.time():.3f} {step} {ph}")
    while hs.mid_ladder:  # park at this boundary until the ladder ends
        time.sleep(0.25)
        hs.poll()

with open(os.path.join(marker_dir, f"done_r{rank}"), "w") as f:
    json.dump({"rank": rank, "steps": step, "mode": mode,
               "warm_traces": warm, "final_traces": traces["n"],
               "fence_epoch": hs.fence_epoch,
               "ledger": led.snapshot()}, f)
get_recorder().flush(ckpt_dir, "drill-end")
"""


_HOT_SWAP_GOLDEN = r"""
import json, sys

import numpy as np

import jax
import jax.numpy as jnp

total_steps, fused_k, cut_step, n_nodes = map(int, sys.argv[1:5])
dim = 16
full = np.concatenate([(np.arange(dim, dtype=np.float32) + 1.0)
                       * np.float32(0.1 * (r + 1))
                       for r in range(n_nodes)])


@jax.jit
def step(w, s):
    g = w * jnp.float32(0.01) + jnp.float32(1e-4) * s.astype(jnp.float32)
    return w - jnp.float32(0.1) * g


w = jnp.asarray(full)
out = {}
for s in range(total_steps):
    w = step(w, jnp.int32(s))
    if (s + 1) % fused_k == 0 and (s + 1) > cut_step:
        out[str(s + 1)] = float(jnp.mean(w * w)).hex()
print(json.dumps(out))
"""


def hot_swap(total_steps: int = 64, fused_k: int = 4, dt: float = 0.02,
             kill_stage: int = 12, outage_s: float = 0.5,
             timeout: float = 240.0) -> Dict:
    """SIGKILL one worker of N mid-train; survivors absorb IN PLACE.

    The tentpole's proof drill: a 2-node world trains a sharded state
    with per-boundary shm staging + ring replication, the drill
    hard-kills one worker and reports the failure (as the agent
    supervisor would), and the master — whose adaptive policy route says
    "hotswap" — drives the journaled mesh-transition ladder
    (master/mesh_transition.py) instead of restarting the world.  The
    MASTER is then SIGKILLed mid-transition and restarted on the same
    journal.  Invariants:

    - the survivor NEVER restarts (one process, exit 0) and finishes
      the run in "solo" mode on the degraded mesh;
    - hydration is replica-tier: the dead rank's staged shard came from
      its ring holder digest-verified (trainer/hotswap.py), and the
      post-cutover loss trajectory is BIT-IDENTICAL to an uninterrupted
      run of the merged state on the degraded mesh (golden computed
      in-process with the same jitted step);
    - zero cold compiles after the warm-up: the degraded-mesh executable
      was pre-compiled (warm-pool analog), so the survivor's XLA trace
      count never moves after cutover;
    - the master crash mid-transition REPLAYS to the same transition
      (same tid, phase no earlier than last observed) and the ladder
      completes to "done" with the world rewritten minus the dead node;
    - the journal narrates the swap as ONE mesh_transition incident
      (telemetry/timeline.py) and the live TimelineQuery byte-equals
      the offline assembly + the incident_report CLI's sha;
    - transition time credits the ledger's restore_replica/rework
      states (nonzero), and recovery lands in seconds.
    """
    from .common.comm import addr_connectable, find_free_port

    phases_order = ["propose", "fence", "hydrate", "cutover", "release",
                    "done"]
    work = tempfile.mkdtemp(prefix="dwt-chaos-hotswap-")
    marker = os.path.join(work, "markers")
    journal_dir = os.path.join(work, "journal")
    ckpt_dir = os.path.join(work, "ckpt")
    os.makedirs(marker)
    os.makedirs(ckpt_dir)
    script = os.path.join(work, "worker.py")
    with open(script, "w") as f:
        f.write(_HOT_SWAP_WORKER)
    global _launch_seq
    _launch_seq += 1
    job = f"hotswap{os.getpid()}n{_launch_seq}"
    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    env = dict(
        os.environ, DWT_JOB_NAME=job, JAX_PLATFORMS="cpu",
        DWT_SOCKET_DIR=os.path.join(work, "sockets"),
        DWT_MASTER_ADDR=addr,
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))

    def spawn_master():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
             f"--port={port}", "--min_nodes=2", "--max_nodes=2",
             f"--journal-dir={journal_dir}", "--poll-interval=0.5"],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    def spawn_worker(r):
        return subprocess.Popen(
            [sys.executable, script, ckpt_dir, marker, str(r), "2",
             str(total_steps), str(fused_k), str(dt)],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    def staged(r):
        try:
            with open(os.path.join(marker, f"log_r{r}")) as f:
                return max((int(ln.split()[2]) for ln in f
                            if ln.startswith("stage ")), default=-1)
        except (OSError, ValueError):
            return -1

    report: Dict = {"scenario": "hot-swap", "outage_s": outage_s}
    master = spawn_master()
    workers: Dict[int, subprocess.Popen] = {}
    out = ""
    from .agent.master_client import MasterClient
    mc = None
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.1)
        if not addr_connectable(addr):
            report.update(ok=False, error="master never came up")
            return report
        # the adaptive route that arms in-place takeover (brain/plugins)
        mc = MasterClient(addr, node_id=-1)
        from .common import messages as msg
        mc.report_policy_decision(msg.PolicyDecision(
            decision_id=1, recovery_route="hotswap",
            preferred_tier="replica", reason="chaos hot-swap drill"))
        workers = {r: spawn_worker(r) for r in (0, 1)}

        # kill the victim once BOTH ranks have staged + replicated past
        # the kill point — the ring then provably holds its shard
        deadline = time.monotonic() + timeout / 2
        while time.monotonic() < deadline:
            if min(staged(0), staged(1)) >= kill_stage:
                break
            if any(p.poll() is not None for p in workers.values()):
                report.update(ok=False, error="worker died before kill",
                              rcs={r: p.poll()
                                   for r, p in workers.items()})
                return report
            time.sleep(0.05)
        else:
            report.update(ok=False,
                          error="workers never reached the kill point")
            return report
        workers[1].kill()  # SIGKILL — the pod is simply gone
        workers[1].wait(timeout=10)
        t_kill = time.monotonic()
        # the agent supervisor's job: report the node-level death
        vic = MasterClient(addr, node_id=1)
        try:
            vic.report_failure("SIGKILL", level="node")
        finally:
            vic.close()

        # catch the transition mid-ladder, then SIGKILL the master too
        observed = ""
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                ts = mc.get_mesh_transition()
            except Exception:  # noqa: BLE001 — keep polling
                time.sleep(0.03)
                continue
            if ts.transition_id == 1 and ts.phase in phases_order[:4]:
                observed = ts.phase
                break
            time.sleep(0.03)
        report["phase_at_master_kill"] = observed
        if not observed:
            report.update(ok=False, error="transition never observed")
            return report
        mc.close()
        mc = None
        master.kill()  # SIGKILL mid-transition — no snapshot, no goodbye
        master.wait(timeout=10)
        time.sleep(outage_s)
        master = spawn_master()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.05)
        mc = MasterClient(addr, node_id=-1)
        ts = mc.get_mesh_transition()
        report["phase_after_replay"] = ts.phase
        # replay lands on the SAME transition, no earlier than observed
        # (an ack in flight at kill time may have advanced it one rung)
        report["replay_same_transition"] = bool(
            ts.transition_id == 1 and ts.phase in phases_order
            and phases_order.index(ts.phase)
            >= phases_order.index(observed))

        # survivor finishes the run solo
        done_path = os.path.join(marker, "done_r0")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and not os.path.exists(done_path):
            if workers[0].poll() is not None:
                break
            time.sleep(0.1)
        try:
            out, _ = workers[0].communicate(timeout=30)
        except subprocess.TimeoutExpired:
            workers[0].kill()
            out, _ = workers[0].communicate()
        report["survivor_rc"] = workers[0].returncode
        report["completed"] = os.path.exists(done_path)
        if not report["completed"]:
            report.update(ok=False, error="survivor never finished")
            return report
        with open(done_path) as f:
            done = json.load(f)
        report["survivor_mode"] = done.get("mode")
        report["fence_epoch"] = done.get("fence_epoch")
        # zero cold compiles: the trace counter never moved after the
        # two warm-up compiles (duo + degraded geometries)
        report["cold_compiles_after_warm"] = (
            int(done.get("final_traces", -1))
            - int(done.get("warm_traces", 0)))
        led_states = (done.get("ledger") or {}).get("states", {})
        report["ledger"] = {
            "restore_replica_s": round(
                float(led_states.get("restore_replica", 0.0)), 4),
            "rework_s": round(float(led_states.get("rework", 0.0)), 4),
            "productive_s": round(
                float(led_states.get("productive", 0.0)), 3),
        }

        # survivor log: cutover step + recovery wall + solo losses
        cut_step, recover_s, losses = -1, -1.0, {}
        with open(os.path.join(marker, "log_r0")) as f:
            for ln in f:
                parts = ln.split()
                if parts[0] == "cutover":
                    cut_step = int(parts[2])
                elif parts[0] == "recover":
                    recover_s = float(parts[2])
                elif parts[0] == "loss":
                    losses[int(parts[2])] = parts[3]
        report["cutover_step"] = cut_step
        report["recovery_s"] = round(recover_s, 3)
        report["solo_boundaries"] = len(losses)

        # golden: the UNINTERRUPTED degraded-mesh run — the merged full
        # vector stepped by the same jitted fn from step 0 (elementwise
        # update: separate shards ≡ concatenation, see worker script).
        # Computed in a JAX_PLATFORMS=cpu subprocess: the drill process
        # may sit on a real TPU backend, and bit-identity needs the same
        # XLA:CPU executable the worker compiled.
        golden_py = os.path.join(work, "golden.py")
        with open(golden_py, "w") as f:
            f.write(_HOT_SWAP_GOLDEN)
        p = subprocess.run(
            [sys.executable, golden_py, str(total_steps), str(fused_k),
             str(cut_step), "2"],
            capture_output=True, text=True, env=env, timeout=120)
        try:
            golden = json.loads(p.stdout)
        except ValueError:
            golden = None
        report["loss_bit_identical"] = bool(
            losses and cut_step > 0
            and {str(k): v for k, v in losses.items()} == golden)

        # ------------------------------------------- incident timeline gate
        from .telemetry import timeline as tl

        live = mc.get_timeline(ckpt_dir=ckpt_dir)
        offline = tl.assemble_incident(journal_dir=journal_dir,
                                       ckpt_dir=ckpt_dir)
        report["timeline_byte_equal"] = (
            live.content == tl.incident_json(offline))
        narr = offline["narrative"]
        swaps = [i for i in narr["incidents"]
                 if i["kind"] == "mesh_transition"]
        report["mesh_incidents"] = len(swaps)
        inc = swaps[0] if swaps else {}
        report["incident_phase"] = inc.get("phase")
        swap_lost = float(inc.get("lost_s", 0.0))
        want_lost = (report["ledger"]["restore_replica_s"]
                     + report["ledger"]["rework_s"])
        report["timeline_attribution_ok"] = (
            abs(swap_lost - want_lost) <= 0.05)
        tools_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        p = subprocess.run(
            [sys.executable,
             os.path.join(tools_dir, "incident_report.py"),
             "--journal", journal_dir, "--flight", ckpt_dir],
            capture_output=True, text=True, env=env, timeout=120)
        try:
            cli_line = json.loads(p.stdout)
        except ValueError:
            cli_line = {}
        report["incident_report_sha_match"] = bool(
            p.returncode == 0
            and cli_line.get("timeline_sha256")
            == tl.incident_sha256(live.content))

        # journal-level exactly-once: ONE propose, phase frames a strict
        # ladder prefix ending "done" — replay re-advanced nothing
        proposes, phase_frames = 0, []
        with open(os.path.join(journal_dir, "journal.frames"), "rb") as f:
            for line in f.read().split(b"\n"):
                if not line.strip():
                    continue
                try:
                    frame = json.loads(line.decode("utf-8"))
                except ValueError:
                    break
                if frame.get("kind") != "mesh_transition":
                    continue
                data = frame.get("data") or {}
                ev = data.get("event")
                if ev == "propose":
                    proposes += 1
                elif ev == "phase":
                    phase_frames.append(str(data.get("phase", "")))
        report["journal_proposes"] = proposes
        report["journal_phases"] = phase_frames
        report["journal_ladder_ok"] = bool(
            proposes == 1
            and phase_frames == phases_order[1:])

        report["ok"] = bool(
            report["survivor_rc"] == 0
            and report["survivor_mode"] == "solo"
            and report["fence_epoch"] == 2
            and report["cold_compiles_after_warm"] == 0
            and report["ledger"]["restore_replica_s"] > 0
            and report["ledger"]["rework_s"] > 0
            and 0 < report["recovery_s"] <= 30.0
            and report["solo_boundaries"] > 0
            and report["loss_bit_identical"]
            and report["replay_same_transition"]
            and report["mesh_incidents"] == 1
            and report["incident_phase"] == "done"
            and report["timeline_byte_equal"]
            and report["timeline_attribution_ok"]
            and report["incident_report_sha_match"]
            and report["journal_ladder_ok"])
        return report
    finally:
        if mc is not None:
            mc.close()
        if master.poll() is None:
            master.terminate()
            try:
                master.wait(timeout=10)
            except subprocess.TimeoutExpired:
                master.kill()
        for p in workers.values():
            if p.poll() is None:
                p.kill()
        # SIGKILLed processes leak their POSIX shm segments (CLAUDE.md)
        from .checkpoint.shm_handler import SharedMemoryHandler
        for r in (0, 1):
            try:
                SharedMemoryHandler(0, f"{job}r{r}").unlink()
            except Exception:  # noqa: BLE001 — best-effort reap
                pass
        if report.get("ok"):
            import shutil

            shutil.rmtree(work, ignore_errors=True)
        else:
            report["cli_tail"] = (out or "")[-2000:]
            report["workdir"] = work


def serve_drain(n_requests: int = 8, max_new_tokens: int = 24,
                kill_after_done: int = 2, timeout: float = 300.0) -> Dict:
    """SIGKILL a decode WORKER mid-traffic; drain to a replacement.

    The serving subsystem's headline invariant: in-flight inference
    requests survive the death of the worker decoding them.  The drill
    runs a journaled standalone master, submits a batch of requests,
    starts a real `python -m dlrover_wuqiong_tpu.serving` worker,
    SIGKILLs it while some requests are done and others are mid-decode,
    reports the failure (the production attribution path is the
    heartbeat sweep; the drill reports explicitly, like the reference's
    chaosblade harness), starts a SECOND worker and drains.  Invariants:

    - zero dropped: every request gets a result with exactly
      `max_new_tokens` tokens despite the kill;
    - bit-identical: results equal an alone-decode of the same
      (weights, prompt, seed) on a fresh local engine with DIFFERENT
      batch geometry — re-admitted requests restart from the prompt
      (never a corrupt half-state) and the position-keyed sampler
      (serving/engine.py) makes the replay exact;
    - recovery is ATTRIBUTED: `requeued_total` > 0 in the serve summary
      and surfaces under the pinned `requeued` ledger counter;
    - one trace tree per request reconstructs from the flight dumps of
      BOTH worker generations (trace ids derive from request ids,
      serving/scheduler.request_trace_id) with admit + finish events.
    """
    from .agent.master_client import MasterClient
    from .common import messages as msg
    from .common.comm import addr_connectable, find_free_port
    from .serving.scheduler import request_trace_id
    from .telemetry.recorder import load_flight_dumps

    work = tempfile.mkdtemp(prefix="dwt-chaos-servedrain-")
    journal_dir = os.path.join(work, "journal")
    # ONE flight-dump dir shared by both worker generations: the trace
    # reconstruction must join spans across the kill
    ckpt_dir = os.path.join(work, "ckpt")
    os.makedirs(ckpt_dir)
    global _launch_seq
    _launch_seq += 1
    job = f"servedrain{os.getpid()}n{_launch_seq}"
    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    env = dict(
        os.environ, DWT_JOB_NAME=job, JAX_PLATFORMS="cpu",
        DWT_SOCKET_DIR=os.path.join(work, "sockets"),
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))

    def spawn_master():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
             f"--port={port}", "--min_nodes=1", "--max_nodes=1",
             f"--journal-dir={journal_dir}", "--poll-interval=0.5"],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    def spawn_worker(node_id: int):
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_wuqiong_tpu.serving",
             "--master", addr, "--node-id", str(node_id),
             "--slots", "2", "--max-len", "64", "--max-prompt-len", "8",
             "--fused-tokens", "2", "--stats-every", "1",
             "--model-seed", "0", "--ckpt-dir", ckpt_dir],
            env=env, cwd=work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    report: Dict = {"scenario": "serve-drain", "requests": n_requests,
                    "max_new_tokens": max_new_tokens}
    master = spawn_master()
    w1 = w2 = None
    cli = None
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not addr_connectable(addr):
            time.sleep(0.1)
        if not addr_connectable(addr):
            report.update(ok=False, error="master never came up")
            return report
        cli = MasterClient(addr, node_id=90, node_type="chaos")
        reqs = [msg.ServeRequest(
                    request_id=f"req-{i:02d}",
                    prompt=[1 + i, 7, 13, 2 + i][:3 + i % 2],
                    max_new_tokens=max_new_tokens, temperature=1.0,
                    seed=1000 + i, submitted_at=time.time())
                for i in range(n_requests)]
        report["accepted"] = cli.submit_serve_requests(reqs).accepted

        w1 = spawn_worker(1)
        # wait for MID-TRAFFIC: some requests done AND some leased (the
        # kill must land on held leases, or there is nothing to recover)
        deadline = time.monotonic() + timeout / 2
        done_at_kill = -1
        while time.monotonic() < deadline and w1.poll() is None:
            summ = cli.get_serve_summary()
            if summ.done_total >= kill_after_done and summ.leased > 0:
                done_at_kill = summ.done_total
                break
            time.sleep(0.05)
        report["done_at_kill"] = done_at_kill
        if not (0 <= done_at_kill < n_requests):
            report.update(ok=False, w1_rc=w1.poll(),
                          error="never reached mid-traffic kill point")
            return report
        w1.kill()  # SIGKILL — admitted requests die with their slots
        w1.wait(timeout=10)
        logger.info("serve-drain: SIGKILLed worker pid=%d at done=%d",
                    w1.pid, done_at_kill)
        failed_cli = MasterClient(addr, node_id=1,
                                  node_type="serve-worker")
        try:
            failed_cli.report_failure("chaos serve-drain SIGKILL",
                                      level="process")
        finally:
            failed_cli.close()

        w2 = spawn_worker(2)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cli.get_serve_summary().done_total >= n_requests:
                break
            time.sleep(0.1)
        resp = cli.get_serve_results([r.request_id for r in reqs])
        got = {r.request_id: [int(t) for t in r.tokens]
               for r in resp.results}
        summ = cli.get_serve_summary()
        report["results"] = len(got)
        report["requeued_total"] = summ.requeued_total
        report["requeued_counter"] = int(
            summ.counters.get("requeued", 0))
        report["zero_dropped"] = bool(
            len(got) == n_requests
            and all(len(t) == max_new_tokens for t in got.values()))

        # bit-identical replay: alone-decode on a fresh local engine
        # with DIFFERENT batch geometry (composition must not matter)
        import jax

        jax.config.update("jax_platforms", "cpu")
        from .models.gpt import GPT, GPTConfig
        from .serving import LocalServer, ServeSpec, ServingEngine

        cfg = GPTConfig.nano()
        params = GPT(cfg).init_params(jax.random.PRNGKey(0))
        srv = LocalServer(ServingEngine(cfg, params, ServeSpec(
            max_slots=3, max_len=64, max_prompt_len=8, fused_tokens=4)))
        for r in reqs:
            srv.submit(r.request_id, list(r.prompt),
                       max_new_tokens=r.max_new_tokens, seed=r.seed,
                       temperature=r.temperature)
        expected = srv.drain()
        mismatched = [rid for rid in expected
                      if got.get(rid) != expected[rid]]
        report["bit_identical"] = not mismatched
        if mismatched:
            report["mismatched"] = mismatched[:4]

        # one trace tree per request, reconstructed from flight dumps
        dumps = load_flight_dumps(ckpt_dir)
        report["flight_dumps"] = len(dumps)
        seen = set()  # (trace, span) — the ring re-flushes cumulatively
        names_by_trace: Dict = {}
        pids_by_trace: Dict = {}
        for d in dumps:
            for evt in d.get("events", []):
                if evt.get("kind") != "span":
                    continue
                rec = evt.get("data", {})
                key = (rec.get("trace_id", ""), rec.get("span_id", ""))
                if key in seen:
                    continue
                seen.add(key)
                tid = rec.get("trace_id", "")
                names_by_trace.setdefault(tid, set()).add(
                    rec.get("name", ""))
                pids_by_trace.setdefault(tid, set()).add(rec.get("pid"))
        trees_ok = True
        cross_generation = 0
        for r in reqs:
            tid = request_trace_id(r.request_id)
            if not {"serve:admit", "serve:finish"} <= \
                    names_by_trace.get(tid, set()):
                trees_ok = False
            if len(pids_by_trace.get(tid, set())) > 1:
                cross_generation += 1
        report["trace_trees_complete"] = trees_ok
        # requests admitted by gen-1 and re-admitted by gen-2 join one
        # tree with spans from two pids (informational: lease timing
        # decides whether a killed request was already admitted)
        report["trace_trees_cross_generation"] = cross_generation

        # ------------------------------------------- incident timeline gate
        # w2 re-flushes its flight ring on every stats push — freeze the
        # artifacts FIRST or live-vs-offline byte equality is a race
        w2.kill()
        w2.wait(timeout=10)
        from .telemetry import timeline as tl

        # the serve verbs above (journaled+idem submit/lease/result)
        # must have ridden the group-commit journal — batched fsync is
        # the default this drill now gates on, with the frames-per-sync
        # gauge surfaced as evidence
        js = cli.get_journal_stats()
        report["journal_group_commit"] = {
            "enabled": js.enabled, "group_commit": js.group_commit,
            "max_frames": js.max_frames,
            "batch_mean": round(js.batch_mean, 2),
            "durable_seq": js.durable_seq}

        live = cli.get_timeline(ckpt_dir=ckpt_dir)
        offline = tl.assemble_incident(journal_dir=journal_dir,
                                       ckpt_dir=ckpt_dir)
        report["timeline_events"] = live.events
        report["timeline_byte_equal"] = (
            live.content == tl.incident_json(offline))
        jkeys = [(e["epoch"], e["seq"]) for e in offline["events"]
                 if e["source"] == "journal"]
        report["timeline_causal"] = (
            jkeys == sorted(jkeys) and len(jkeys) == len(set(jkeys)))
        # exactly-once on the timeline itself: the serve_result journal
        # events' request ids tile the submitted set exactly once (the
        # requeue produced a second LEASE, never a second result), and
        # the batch submit journaled exactly one frame
        result_ids: list = []
        n_submit = 0
        for e in offline["events"]:
            if e["source"] != "journal":
                continue
            if e["kind"] == "serve_result":
                result_ids += list(e["data"].get("request_ids", []))
            elif e["kind"] == "serve_submit":
                n_submit += 1
        report["timeline_serve_exactly_once"] = (
            sorted(result_ids) == sorted(r.request_id for r in reqs)
            and n_submit == 1)
        # the offline CLI on the same artifacts must hash to the live bytes
        tools_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        p = subprocess.run(
            [sys.executable, os.path.join(tools_dir, "incident_report.py"),
             "--journal", journal_dir, "--flight", ckpt_dir],
            capture_output=True, text=True, env=env, timeout=120)
        try:
            cli_line = json.loads(p.stdout)
        except ValueError:
            cli_line = {}
        report["incident_report_rc"] = p.returncode
        report["incident_report_sha_match"] = bool(
            p.returncode == 0
            and cli_line.get("timeline_sha256")
            == tl.incident_sha256(live.content))

        report["ok"] = bool(
            report["zero_dropped"] and report["bit_identical"]
            and report["requeued_total"] > 0
            and report["requeued_counter"] > 0 and trees_ok
            and report["timeline_byte_equal"]
            and report["timeline_causal"]
            and report["timeline_serve_exactly_once"]
            and report["incident_report_sha_match"]
            and report["journal_group_commit"]["enabled"]
            and report["journal_group_commit"]["group_commit"])
        return report
    finally:
        tails = {}
        for name, p in (("w1", w1), ("w2", w2)):
            if p is None:
                continue
            if p.poll() is None:
                p.kill()
            try:
                out, _ = p.communicate(timeout=10)
            except (subprocess.TimeoutExpired, ValueError):
                out = ""
            tails[name] = (out or "")[-2000:]
        if cli is not None:
            cli.close()
        if master.poll() is None:
            master.terminate()
            try:
                master.wait(timeout=10)
            except subprocess.TimeoutExpired:
                master.kill()
        if report.get("ok"):
            import shutil

            shutil.rmtree(work, ignore_errors=True)
        else:
            report.update(workdir=work, **{f"{k}_tail": v
                                           for k, v in tails.items()})


def perf_regress() -> Dict:
    """Perf-regression sentinel drill (telemetry/perf.py) — jax-free.

    Three invariants, all on the REAL BaselineStore + RegressionSentinel
    (seeded synthetic windows, so the drill is hermetic and fast):

    1. QUIET: +-10% run-to-run noise around the baseline never fires —
       the MAD bound absorbs normal drift.
    2. THROTTLED: a sustained ~1.5x step-time slowdown whose extra wall
       sits in the collective category fires `perf-regression` after
       EXACTLY M consecutive beyond-bound windows, once per excursion,
       and attributes the moved category.
    3. KEY ISOLATION: another argument of `executable_key` (the fused
       width K) changes the executable key (a different executable is
       a new baseline, never a false regression), and the published
       store survives an atomic write + reload round-trip with
       identical stats.
    4. CUTOVER: after a K cutover the sentinel judges the new key
       against its OWN fresh baseline — step times that fired under
       the old key never fire post-cutover.
    """
    import random
    import shutil

    from .telemetry.perf import (BaselineStore, RegressionSentinel,
                                 executable_key)

    work = tempfile.mkdtemp(prefix="dwt-chaos-perfregress-")
    report: Dict = {"scenario": "perf-regress", "ok": False}
    try:
        m_consec = 3
        store = BaselineStore(
            path=os.path.join(work, "perf", "baseline.json"))
        sentinel = RegressionSentinel(store, m_consecutive=m_consec)
        key = executable_key("drill-fingerprint", 8, "cpu")
        rng = random.Random(1234)

        def window(v, coll_frac):
            cats = {"matmul": v * (1 - coll_frac),
                    "collective": v * coll_frac}
            beyond, event = sentinel.observe(key, v, cats, step=window.n)
            window.n += 8
            if not beyond:
                store.update(key, v, cats)
                store.publish()
            return event
        window.n = 0

        # 1) quiet phase: baseline forms, nothing fires
        quiet_events = [e for _ in range(16)
                        if (e := window(0.1 * (1 + 0.1 * (
                            rng.random() * 2 - 1)), 0.3)) is not None]
        # 2) throttled phase: +60% wall, all of it collective
        fired = []
        for i in range(2 * m_consec):
            e = window(0.16, 0.56)
            if e is not None:
                fired.append((i + 1, e))
        # 3) key isolation across a fused-K change + store round-trip
        flipped = executable_key("drill-fingerprint", 4, "cpu")
        # 4) cutover: the other width is a NEW executable key, so its
        #    windows land on a FRESH baseline — step times that would be
        #    deep beyond-bound under the OLD key (the throttled phase
        #    already fired on them) must never fire the sentinel after a
        #    cutover
        cutover_events = []
        n_cut = 0
        for i in range(4 * m_consec):
            beyond, event = sentinel.observe(
                flipped, 0.16, {"matmul": 0.112, "collective": 0.048},
                step=n_cut)
            n_cut += 8
            if event is not None:
                cutover_events.append(event)
            if not beyond:
                store.update(flipped, 0.16,
                             {"matmul": 0.112, "collective": 0.048})
                store.publish()
        reloaded = BaselineStore(
            path=os.path.join(work, "perf", "baseline.json"))
        report.update(
            quiet_events=len(quiet_events),
            fired_after_windows=fired[0][0] if fired else -1,
            fired_total=len(fired),
            fired_kind=fired[0][1]["kind"] if fired else "",
            attributed_category=fired[0][1]["category"] if fired else "",
            key_changed_on_k_change=flipped != key,
            cutover_windows=4 * m_consec,
            cutover_fired=len(cutover_events),
            cutover_baseline_n=int((store.stats(flipped) or
                                    {}).get("n", 0)),
            baseline_roundtrip=reloaded.stats(key) == store.stats(key)
            and store.stats(key) is not None,
        )
        report["ok"] = (
            not quiet_events
            and len(fired) == 1
            and fired[0][0] == m_consec
            and fired[0][1]["kind"] == "perf-regression"
            and fired[0][1]["category"] == "collective"
            and report["key_changed_on_k_change"]
            and not cutover_events
            and report["cutover_baseline_n"] > 0
            and report["baseline_roundtrip"])
        return report
    finally:
        if report.get("ok"):
            shutil.rmtree(work, ignore_errors=True)
        else:
            report["workdir"] = work


SCENARIOS = {"pod-kill": pod_kill, "straggler": straggler,
             "network-partition": network_partition,
             "preempt": preempt, "preempt-table": preempt_table,
             "preempt-warm": preempt_warm,
             "preempt-fused": preempt_fused,
             "preempt-adaptive": preempt_adaptive,
             "ckpt-corrupt": ckpt_corrupt,
             "master-kill": master_kill,
             "master-failover": master_failover,
             "hot-swap": hot_swap,
             "serve-drain": serve_drain,
             "perf-regress": perf_regress}


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    # --policy-prior PATH seeds preempt-adaptive from a persisted
    # preempt-table curve instead of the built-in drill-scale prior
    prior = ""
    filtered = []
    it = iter(argv)
    for a in it:
        if a == "--policy-prior":
            prior = next(it, "")
        elif a.startswith("--policy-prior="):
            prior = a.split("=", 1)[1]
        else:
            filtered.append(a)
    names = filtered or list(SCENARIOS)
    ok = True
    for name in names:
        fn = SCENARIOS.get(name)
        if fn is None:
            print(f"unknown scenario {name!r}; have {list(SCENARIOS)}",
                  file=sys.stderr)
            return 2
        report = (fn(policy_prior=prior)
                  if name == "preempt-adaptive" and prior else fn())
        print(json.dumps(report))
        ok = ok and report.get("ok", False)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
