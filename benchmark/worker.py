"""The process that holds the chip: builds the cell's Trainer, taps its
loop, and records what the metrics are made from.

Two ways in, one body (`train_process`):

- `drivers/trainer_inproc.py` calls it in the benchmark's own process;
- `drivers/elastic_cli.py` names THIS FILE as the training script of
  `python -m dlrover_wuqiong_tpu.run`, so the agent launches it (and
  launches it again after the kill): `python -u benchmark/worker.py
  <plan.json>`.

The cell runs `Trainer(model, TrainingArgs(...), data).train()` once —
the path users call, no callbacks, no private step loop.  The
benchmark's taps on it, all from outside:

- the seeded `data(step)` callable (`Tap`).  The Trainer calls it on its
  main thread once per optimizer step, before it dispatches that step;
  `trainer.state` is then the previous step's output, so waiting for it
  is a DEVICE-SYNCHRONISED instant: every earlier step is complete and
  nothing later is dispatched.  The window opens and closes on two such
  instants; between them the loop runs free.
- a `logging.Handler` on the Trainer's logger, which sees each `step N
  loss=...` record right after the loss readback (full-precision loss,
  and a device-progress stamp that costs the loop nothing);
- the goodput ledger's blocking host intervals and the compile-cache
  counters, read after `train()` returns.

Nothing here falls back to a CPU: `require_tpu` fails on any other
platform, and on a chip count other than the cell's.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()  # process start, as near as Python lets us

import glob  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRAINER_LOGGER = "dwt.trainer"  # common/log.py: get_logger("trainer")


class Events:
    """Stamps on `time.monotonic()` (one clock for every process of the
    machine), kept in memory and, for a worker under the agent, appended
    to a file the harness parent follows."""

    def __init__(self, path: str = "", gen: int = 0):
        self.items, self.path, self.gen = [], path, gen

    def add(self, ev: str, t: float | None = None, **fields) -> dict:
        rec = {"ev": ev, "t": time.monotonic() if t is None else t,
               "gen": self.gen, **fields}
        self.items.append(rec)
        if self.path:
            with open(self.path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        return rec


def require_tpu(chips: int) -> dict:
    """The device object of the result line — or no result at all."""
    import jax

    devs = jax.devices()  # raises where the backend cannot initialise
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found no TPU (default backend "
            f"{devs[0].platform!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); it measures on the "
            f"chip only")
    if len(devs) != chips:
        raise SystemExit(
            f"benchmark: this cell asks for {chips} chip(s) and JAX sees "
            f"{len(devs)}; the Trainer takes every device it sees")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class LossLog(logging.Handler):
    """Every `step N loss=X` record of the Trainer: (N, X, monotonic)."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.rows = []

    def emit(self, record):
        if isinstance(record.msg, str) and \
                record.msg.startswith("step %d loss=") and record.args:
            self.rows.append((int(record.args[0]), float(record.args[1]),
                              time.monotonic()))


class Tap:
    """`data(step)`: the seeded batch, and the window's bookkeeping.

    plan keys: mode ("window": warm up, open, measure, close | "hold":
    train to `hold_step` and wait to be killed | "resume": the window
    opened at the kill, close at `deadline`), warm_steps, seconds, trace
    (bool), untraced_steps, trace_steps, trace_dir, sync_every (0 =
    never), hold_step,
    deadline (absolute monotonic), may_hold_again (a resumed generation
    that had to compile turns into a "hold" one).
    """

    def __init__(self, inner, plan: dict, events: Events, losses: LossLog):
        self.inner, self.plan, self.ev, self.losses = (inner, plan, events,
                                                       losses)
        self.tr = None
        self.first = None
        self.phase = "warm" if plan["mode"] != "resume" else "window"
        self.t_open = self.s_open = None
        self.s_trace0 = None
        self._t_in = self._call_s = 0.0
        self.overhead_s = 0.0  # time spent in the tap itself; the Trainer
        # credits it to the ledger's data_stall, the reader takes it out
        self._main = threading.main_thread()

    def bind(self, trainer) -> None:
        self.tr = trainer

    # -- helpers

    def _sync(self) -> float:
        """Wait until every dispatched step is complete on the device."""
        import jax

        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(jax.tree.leaves(self.tr.state.step))
        return time.monotonic()

    def _counters(self) -> dict:
        """What the window's marks carry besides their time: the cache
        counters and the ledger's cumulative states, so that readers can
        take the window's own share (open -> close) of each."""
        from dlrover_wuqiong_tpu.auto.compile_cache import counters
        from dlrover_wuqiong_tpu.telemetry.ledger import get_ledger

        return {"cache_hits": counters.hits, "cache_misses": counters.misses,
                "ledger": get_ledger().snapshot()["states"],
                # the ledger books this call's data_stall only when the
                # call returns: leave this call's share out here too
                "tap_overhead_s": self.overhead_s - self._call_s}

    def _device_done_at(self, step: int) -> float:
        """When the device will have completed `step` steps, from the
        loss-readback stamps inside the window (the host runs ahead of
        the device by up to three logging boundaries)."""
        now = time.monotonic()
        rows = [r for r in self.losses.rows if r[2] >= (self.t_open or 0)]
        if len(rows) < 2:
            rows = self.losses.rows[-2:]  # early in the window
        if len(rows) < 2 or rows[-1][0] == rows[0][0]:
            return now
        per_step = (rows[-1][2] - rows[0][2]) / (rows[-1][0] - rows[0][0])
        return max(now, rows[-1][2] + (step - rows[-1][0]) * per_step)

    def _start_trace(self, step: int) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # device + host annotations only
        jax.profiler.start_trace(self.plan["trace_dir"],
                                 profiler_options=opts)
        self.s_trace0 = step
        self.ev.add("trace_start", step=step)
        self.phase = "trace"

    def _finish(self) -> None:
        self.phase = "done"
        # the way a job ends: the graceful-preemption flag, set through
        # the Trainer's public method; the step in flight still runs and
        # is not measured
        self.tr.request_stop()

    # -- the callable

    def __call__(self, step: int):
        import jax

        if threading.current_thread() is not self._main:
            raise RuntimeError(
                "benchmark tap: data(step) was called off the main thread "
                "(the fused batch stager?); the tap needs unfused dispatch "
                "— the auto fused-K decision was expected to be 1")
        p = self.plan
        self._t_in, self._call_s = time.monotonic(), 0.0
        if self.first is None:
            self.first = step
            self.ev.add("first_data", step=step)
        elif step == self.first + 1:
            self._sync()
            marks = self._counters()
            if p["mode"] == "resume" and marks["cache_misses"] > 0 \
                    and p.get("may_hold_again"):
                # this generation had to COMPILE (the cell's first run
                # in a checkout): it was set-up after all.  Train on to
                # the kill step and hold; the harness kills once more
                # and measures the generation after this one.
                if p["trace"]:
                    jax.profiler.stop_trace()
                self.ev.add("cold_resume", step=step, **marks)
                p["mode"], self.phase = "hold", "warm"
            else:
                self.ev.add("first_step_done", step=step, **marks)

        if self.phase == "warm":
            if p["mode"] == "hold" and step >= p["hold_step"]:
                self._hold(step)
            if p["mode"] == "window" and \
                    step >= self.first + p["warm_steps"]:
                self.t_open, self.s_open = self._sync(), step
                self._settle()
                self.ev.add("open", step=step, t_sync=self.t_open,
                            **self._counters())
                self.phase = "window"

        every = p.get("sync_every", 0)
        if every and self.phase in ("window", "trace"):
            # bracket the step interval that holds a save by two
            # device-synchronised instants
            if (step + 1) % every == 0:
                self.ev.add("pre_save", step=step, t_sync=self._sync())
            elif step % every == 0:
                self.ev.add("post_save", step=step, t_sync=self._sync())

        if self.phase == "window" and step > (self.s_open or -1):
            if p["mode"] == "resume":
                if p["trace"]:
                    if step >= self.first + p["trace_steps"]:
                        self._stop_trace(step)
                elif self._device_done_at(step) >= p["deadline"]:
                    self._close(step)
                    self._finish()
            elif p["trace"]:
                # a traced run needs no free-running window: a fixed short
                # stretch of steps (the host is ahead of the device by up
                # to three logging boundaries, so a clock would overrun)
                if step >= self.s_open + p["untraced_steps"]:
                    self._close(step)
                    self._start_trace(step)
            elif self._device_done_at(step) >= self.t_open + p["seconds"]:
                self._close(step)
                self._finish()
        elif self.phase == "trace":
            if step >= self.s_trace0 + p["trace_steps"]:
                self._stop_trace(step)
        self._settle()
        with jax.profiler.TraceAnnotation("bench.data"):
            return self.inner(step)

    def _settle(self) -> None:
        """Book the time spent in the tap so far in this call."""
        now = time.monotonic()
        self.overhead_s += now - self._t_in
        self._call_s += now - self._t_in
        self._t_in = now

    def _close(self, step: int) -> None:
        t_sync = self._sync()
        self._settle()
        self.ev.add("close", step=step, t_sync=t_sync, **self._counters())

    def _stop_trace(self, step: int) -> None:
        import jax

        t_sync = self._sync()
        self._settle()
        marks = self._counters()
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        self.ev.add("trace_stop", step=step, t_sync=t_sync,
                    stop_trace_s=time.monotonic() - t0, **marks)
        self._finish()

    def _hold(self, step: int) -> None:
        """Generation before a kill: every step so far is complete, the
        last logged loss has arrived; say so and wait to be killed."""
        self._sync()
        t_end = time.monotonic() + 10  # the record rides the pump thread
        while time.monotonic() < t_end and not any(
                r[0] == step for r in self.losses.rows):
            time.sleep(0.01)
        marks = self._counters()
        self.ev.add("hold", step=step, cache_hits=marks["cache_hits"],
                    cache_misses=marks["cache_misses"],
                    losses=[list(r) for r in self.losses.rows])
        while True:  # the harness kills this process group here
            time.sleep(0.05)


def init_check(cell: dict, model_mod, trainer, data, seed: int,
               events: "Events") -> dict:
    """The system's loss and gradient global norm at the seeded init on a
    few sequences against the plain reference, given the same
    parameters.  Outside the window.

    The system's side is ONE execution of the Trainer's own compiled
    step (the program the window then runs, kernels and sharding
    included) on a full-shape batch that repeats those few sequences:
    the mean loss and its gradient over the repeats are those of the few,
    and the step reports both (`loss`, `grad_norm`, the norm before
    clipping).  No second program of the system is compiled.  The step
    consumes the state it is given, so the state is drawn from the seed
    again afterwards."""
    import jax
    import numpy as np

    from benchmark.reference import loss_and_grad_norm

    spec = cell["config"]["correct"]
    n = int(spec["init_sequences"])
    if cell["global_batch"] % n:
        raise ValueError(f"global batch {cell['global_batch']} is no "
                         f"multiple of init_sequences {n}")
    few = {k: v[:n] for k, v in data(0).items()}
    tiled = {k: np.tile(v, (cell["global_batch"] // n, 1))
             for k, v in few.items()}
    used, metrics = trainer.res.fused_train_step(1)(
        trainer.state, trainer.res.place_batch(tiled))
    sys_loss, sys_norm = float(metrics["loss"]), float(metrics["grad_norm"])
    events.add("system_checked")
    for leaf in jax.tree.leaves(used):
        leaf.delete()
    trainer.state = model_mod.seeded_state(trainer, seed)
    ref_loss, ref_norm = loss_and_grad_norm(
        model_mod.reference_loss(cell["config"]), trainer.state.params,
        trainer.res.place_batch(dict(few)), precision="highest")
    loss_err = abs(sys_loss - ref_loss) / abs(ref_loss)
    norm_err = abs(sys_norm - ref_norm) / abs(ref_norm)
    ok = math.isfinite(sys_loss) and math.isfinite(sys_norm) and \
        loss_err <= spec["loss_rtol"] and norm_err <= spec["grad_norm_rtol"]
    return {"ok": bool(ok), "sequences": n, "loss": sys_loss,
            "ref_loss": ref_loss, "loss_rel_err": loss_err,
            "grad_norm": sys_norm, "ref_grad_norm": ref_norm,
            "grad_norm_rel_err": norm_err}


def train_process(cell: dict, plan: dict, run_dir: str,
                  events: Events) -> dict:
    """Build the cell's Trainer, run `train()` once under the tap, and
    return everything the metrics are made from (plain JSON types)."""
    device = require_tpu(cell["chips"])
    events.add("device_ready")
    import jax
    import numpy as np

    from benchmark import cells
    from benchmark.data import make_data
    from dlrover_wuqiong_tpu.auto.compile_cache import counters
    from dlrover_wuqiong_tpu.telemetry.ledger import get_ledger
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

    cfg, traffic = cell["config"], cell["traffic"]
    model_mod = cells.load_module("models", cfg["model_class"])
    targs = dict(traffic["training_args"])
    targs.update(
        output_dir=os.path.join(run_dir, "train"),
        global_batch_size=cell["global_batch"], seq_len=cell["seq_len"],
        strategy=[(n, dict(o)) for n, o in cfg["train"]["strategy"]],
        seed=plan["seed"])
    inner = make_data(cfg["vocab_size"], cell["global_batch"],
                      cell["seq_len"], plan["seed"], **traffic["data"])
    losses = LossLog()
    logging.getLogger(TRAINER_LOGGER).addHandler(losses)
    tap = Tap(inner, plan, events, losses)
    trainer = Trainer(model_mod.build(cfg), TrainingArgs(**targs), tap)
    tap.bind(trainer)
    events.add("trainer_built")

    check = None
    if plan.get("fresh", True):
        # a relaunched generation takes its state from the checkpoint
        trainer.state = model_mod.seeded_state(trainer, plan["seed"])
        events.add("seeded")
        check = init_check(cell, model_mod, trainer, inner, plan["seed"],
                           events)
        events.add("init_checked", ok=check["ok"])
    if plan["mode"] == "resume" and plan["trace"]:
        # trace generation 2 from train() entry (restore, first dispatch)
        tap._start_trace(-1)
        tap.phase = "window"
    events.add("train_enter", **tap._counters())
    out = trainer.train()
    events.add("train_exit", stopped_at=int(out["stopped_at"]))

    # ---- after the window: saves landed? ledger, counters, memory
    save_error = ""
    try:
        trainer.ckpt.wait_staging(120)
    except Exception as e:  # noqa: BLE001 — a failed drain is a failed save
        save_error = f"{type(e).__name__}: {e}"
    disk_every = int(targs.get("save_steps") or 0)
    want = [s for s in range(disk_every, int(out["stopped_at"]) + 1,
                             disk_every)] if disk_every else []
    want = [s for s in want if s > (tap.first or 0)]
    committed, deadline = [], time.monotonic() + 90
    while want and time.monotonic() < deadline:
        committed = trainer.ckpt.engine.committed_steps()
        if set(want) <= set(committed):
            break
        time.sleep(0.2)
    restore = dict(trainer.ckpt.last_restore_report or {})
    probes = {}
    if plan["trace"]:
        from dlrover_wuqiong_tpu.common.util import (
            measure_dispatch_overhead_s,
            measure_h2d_gbps,
        )

        probes = {"dispatch_overhead_s": measure_dispatch_overhead_s(
            force=True), "h2d_gbps": measure_h2d_gbps(force=True)}
    stats = [dict(d.memory_stats() or {}) for d in jax.local_devices()]
    # buffers at their peak plus the region the runtime reserves for the
    # programs' temporaries: `peak_bytes_in_use` alone leaves the step's
    # 8.8 GiB of activations out (PERF.md, PR 23)
    mem = [int(st.get("peak_bytes_in_use", 0))
           + int(st.get("peak_bytes_reserved", 0)) for st in stats]
    trainer.ckpt.close()
    logging.getLogger(TRAINER_LOGGER).removeHandler(losses)
    shutil.rmtree(os.path.join(run_dir, "train"), ignore_errors=True)

    trace = None
    if plan["trace"]:
        from benchmark import xtrace

        trace = xtrace.load(plan["trace_dir"])
        shutil.rmtree(plan["trace_dir"], ignore_errors=True)
    return {
        "device": device, "gen": events.gen, "events": events.items,
        "losses": [list(r) for r in losses.rows],
        "ledger": get_ledger().snapshot(),
        "counters": {"hits": counters.hits, "misses": counters.misses},
        "memory_peak_bytes": max(mem) if mem else 0,
        "memory_stats": stats[0] if stats else {},
        "first_data_step": tap.first, "stopped_at": int(out["stopped_at"]),
        "resumed_step": int(restore.get("step") or 0),
        "restore_tier": restore.get("tier", ""),
        "init_check": check, "save_error": save_error,
        "disk_saves_wanted": want,
        "disk_saves_committed": [s for s in want if s in set(committed)],
        "probes": probes, "trace": trace,
        "all_finite": bool(np.all(np.isfinite(
            [r[1] for r in losses.rows]))) if losses.rows else False,
        "t_proc0": T_PROC0,
    }


def shm_leftovers(job: str) -> list:
    return sorted(glob.glob(f"/dev/shm/*{job}*"))


def main() -> int:
    """Entry of a worker under the elastic agent: `worker.py plan.json`.
    The plan names the cell and the run directory; which generation this
    is comes from the agent's environment."""
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    from benchmark import cells
    from dlrover_wuqiong_tpu.common.constants import NodeEnv

    gen = int(os.getenv(NodeEnv.RESTART_COUNT, "0"))
    run_dir = plan["run_dir"]
    events = Events(os.path.join(run_dir, "events.jsonl"), gen)
    events.add("worker_start", pid=os.getpid(), pgid=os.getpgrp(),
               t_proc0=T_PROC0)
    cell = cells.load_cell(plan["workload"])
    plan = dict(plan, fresh=gen == 0)
    kill_file = os.path.join(run_dir, "kill.json")
    if not os.path.isfile(kill_file):
        # before the measured kill: train to the kill step and hold
        plan.update(mode="hold", trace=False,
                    hold_step=cell["traffic"]["fault"]["kill_step"])
    else:
        with open(kill_file) as f:
            t_kill = json.load(f)["t_kill"]
        plan.update(mode="resume", deadline=t_kill + plan["seconds"],
                    trace_dir=os.path.join(run_dir, "trace"),
                    hold_step=cell["traffic"]["fault"]["kill_step"],
                    may_hold_again=gen < plan["last_gen"])
    result = train_process(cell, plan, run_dir, events)
    trace = result.pop("trace")
    if trace is not None:
        with open(os.path.join(run_dir, "trace.json"), "w") as f:
            json.dump(trace, f)
    with open(os.path.join(run_dir, f"result_gen{gen}.json"), "w") as f:
        json.dump(result, f)
    events.add("worker_done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
