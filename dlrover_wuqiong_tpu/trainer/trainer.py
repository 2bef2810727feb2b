"""High-level Trainer — one object from model to trained checkpoint.

Parity: reference `atorch/atorch/trainer/atorch_trainer.py:136`
(`AtorchTrainer`, the HF-Trainer-style loop over auto_accelerate) and
`atorch_args.py` (TrainingArgs).

Composes the whole stack: `auto_accelerate` (strategy → compiled sharded
step), elastic context (rendezvous world + dynamic sharding when launched
by the agent), flash checkpoint (auto-resume + save cadence +
save-on-exit), the step profiler (always-on timing + windowed traces), lr
schedules, and periodic evaluation.

The hot loop runs the fused K-step driver by default
(`TrainingArgs.fused_steps=0` auto-tunes K from measured step time vs.
measured dispatch overhead): one dispatch and one metrics readback per K
optimizer steps, batches staged K-at-a-time by `FusedBatchStager` while
the current fusion executes.  Every elastic hook — logging, checkpoint
saves, shm staging, eval, master config polls, graceful SIGTERM
preemption, and the rollback resume — fires at fusion boundaries only;
K is clamped to divide the active cadences so those boundaries land
exactly where the unfused loop would have fired them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Callable, Dict, Iterable, Optional

import numpy as np

from ..common.log import get_logger
from ..telemetry import memory as tmemory
from ..telemetry import spans as tspans

logger = get_logger("trainer")

# the step's metrics the loop already handles by name; whatever else a
# step returns is a counted scalar the metrics pump passes on as it is
_STEP_SERIES = ("loss", "grad_norm", "losses", "grad_norms")


def _step_budget(mode: int) -> Dict[str, int]:
    """The compiled budget of the step program just dispatched at
    fusion width `mode` (`telemetry.perf.step_memory`), for
    `trainer:first_step`'s attrs: one `lower` walk over the leaves,
    answered by JAX's caches, once a width.  Telemetry never kills the
    run: a program the way back does not find again has no budget."""
    from ..telemetry.perf import step_memory

    try:
        return step_memory().get(mode, {})
    except Exception:  # noqa: BLE001 — see docstring
        logger.warning("no memory budget of the step at K=%d", mode,
                       exc_info=True)
        return {}


@dataclasses.dataclass
class TrainingArgs:
    """Parity: reference atorch_args.py — the knobs of the training loop."""

    output_dir: str = "/tmp/dwt-run"
    max_steps: int = 1000
    global_batch_size: int = 32
    seq_len: int = 1024
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    lr_schedule: str = "cosine"  # "cosine" | "linear" | "constant"
    min_lr_ratio: float = 0.1
    grad_accum_steps: int = 1
    strategy: Optional[list] = None          # auto_accelerate strategy
    logging_steps: int = 10
    save_steps: int = 200
    eval_steps: int = 0                      # 0 = no periodic eval
    max_eval_batches: int = 32
    seed: int = 0
    resume: bool = True                      # auto-resume from output_dir
    # "bf16" halves checkpoint bytes end to end (D2H staging, disk,
    # restore H2D) — lossy for f32 state (checkpointer docstring); for
    # restore-latency-critical deployments over slow host links
    ckpt_wire_dtype: Optional[str] = None

    def __post_init__(self):
        if self.ckpt_wire_dtype not in (None, "bf16"):
            # fail BEFORE Trainer runs param init + compile (CLAUDE.md:
            # bad knobs error at construction time, not minutes later)
            raise ValueError(
                f"unsupported ckpt_wire_dtype {self.ckpt_wire_dtype!r}; "
                f"use 'bf16' or None")
        if self.fused_steps < 0:
            raise ValueError(
                f"fused_steps must be >= 0 (0 = auto-tune), got "
                f"{self.fused_steps}")
        if self.perf_window_every < 0 or self.perf_regress_windows < 1 \
                or not 0.0 < self.perf_overhead_budget <= 1.0:
            raise ValueError(
                f"bad perf-observatory knobs: perf_window_every="
                f"{self.perf_window_every} (>= 0), perf_regress_windows="
                f"{self.perf_regress_windows} (>= 1), perf_overhead_budget="
                f"{self.perf_overhead_budget} (in (0, 1])")
        if self.tune_variants != 0:
            raise ValueError(
                f"tune_variants accepts 0 only (nothing tunes variants), "
                f"got {self.tune_variants}")
    profile_trace_dir: str = ""              # jax.profiler window target
    profile_start_step: int = -1
    profile_end_step: int = -1
    save_on_exit: bool = True
    tune_config_steps: int = 25              # poll master's paral config
    # every k steps (0 = off); applies dataloader batch size + ckpt cadence
    probe_interval: float = 30.0             # device-queue liveness probe
    # cadence for hang localization (0 = off; active only under the agent)
    # fused multi-step dispatch (trainer/train_step.py): 0 = auto-tune K
    # from measured step time vs. measured dispatch overhead (target <2%
    # overhead, clamped to a divisor of the active hook cadences so the
    # checkpoint cadence stays exactly reachable); 1 = unfused; K>1 =
    # explicit.  Elastic hooks (save/eval/logging/tune/preemption) fire
    # at fusion boundaries only.
    fused_steps: int = 0
    # SIGTERM (the agent's preemption signal, agent/elastic_agent.py)
    # finishes the in-flight fusion, saves, and exits cleanly instead of
    # dying mid-step
    graceful_preemption: bool = True
    # stage the train state to shm (save_to_memory) every N steps — at
    # fusion boundaries when fused — so the agent's save-on-failure
    # persists the last boundary; 0 = off
    flash_stage_steps: int = 0
    # poll the master's adaptive fault-tolerance decision (brain/policy.py)
    # every N steps — at fusion boundaries only; 0 = off.  Applies ckpt
    # cadence / restore-tier / replica knobs immediately; a fused-K change
    # first pre-compiles through the warm pool (K is part of the compile
    # cache key) and cuts over only once the entry is ready.
    policy_steps: int = 0
    # perf observatory (telemetry/perf.py): every Nth LOGGING boundary —
    # the boundary that already carries the one metrics readback — wraps
    # its fused dispatch in a StepProfiler window, folds the xplane op
    # split into a PerfSnapshot, and feeds the baseline store + regression
    # sentinel.  Windows self-limit to <perf_overhead_budget of wall and
    # never add a device readback.  0 = off.
    perf_window_every: int = 8
    perf_regress_windows: int = 3            # M consecutive beyond-MAD
    perf_overhead_budget: float = 0.01       # max profiling wall fraction
    # 0 only: benchmark/traffic/*.json pass the key; goes with it (ROADMAP D14)
    tune_variants: int = 0
    # overlap the logging boundary's host work (metrics readback, perf
    # window close, master reports) with the next fused dispatch via the
    # metrics pump thread; False = inline (sync).  User callbacks force
    # the inline path regardless: they are the loop's synchronous
    # surface (request_stop, config pushes) and must observe the
    # boundary before the next fusion dispatches.
    async_metrics: bool = True


class _MetricsPump:
    """Single background consumer for the logging boundary's host work.

    Overlap: the per-fusion metrics readback (`float(loss)`), the perf
    window close (xplane parse + baseline publish fsync), the master
    reports and the user callbacks move off the hot loop onto ONE daemon
    thread draining a bounded queue — the next fused dispatch overlaps
    the host work instead of serializing behind it.  Invariants:

    - ledger CREDITS stay on the main thread at fusion boundaries
      (CLAUDE.md telemetry rules): a job ships the snapshot dict taken
      at its boundary, never the live ledger;
    - `metrics` is an executable OUTPUT — donation-immune (CLAUDE.md),
      so reading it back after the next dispatch has donated the inputs
      is safe;
    - at most `maxsize` boundaries ride in flight (put() backpressures
      the main loop instead of queueing unbounded device values), and at
      most ONE open perf window (the trainer gates `maybe_open` on
      `windows_inflight() == 0` — jax traces can't nest);
    - a consume error leaves `windows_inflight` elevated on purpose: a
      half-closed window may still hold the profiler trace, and a stuck
      gate (no further windows) is safe where a nested trace is not;
    - the RpcClient serializes frames under its own lock, so master
      verbs from this thread never interleave with the main loop's;
    - joined from train()'s finally (conftest thread-leak guard).

    `enabled=False` (async_metrics off) consumes inline on the caller's
    thread — same code path, synchronous semantics.
    """

    def __init__(self, trainer: "Trainer", enabled: bool = True,
                 maxsize: int = 2):
        import queue
        import threading

        self._trainer = trainer
        self._lock = threading.Lock()
        self._last_loss = float("nan")
        self._windows_inflight = 0
        self._drained = 0
        self._errors = 0
        self._q: Any = None
        self._thread: Any = None
        if enabled:
            self._q = queue.Queue(maxsize=maxsize)
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="dwt-metrics-pump")
            self._thread.start()

    def submit(self, job: Dict[str, Any]) -> None:
        if job.get("pw") is not None:
            with self._lock:
                self._windows_inflight += 1
        if self._thread is None:
            # inline path: exceptions propagate — a raising user callback
            # must abort training exactly as the pre-pump loop did
            self._note_done(job, self._trainer._consume_boundary(job))
        else:
            self._q.put(job)

    def windows_inflight(self) -> int:
        with self._lock:
            return self._windows_inflight

    def last_loss(self) -> float:
        with self._lock:
            return self._last_loss

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"drained": self._drained, "errors": self._errors}

    def stop(self, timeout: float = 60.0) -> None:
        """Flush queued boundaries and join (train()'s finally)."""
        if self._thread is None:
            return
        self._q.put(None)
        self._thread.join(timeout)
        self._thread = None

    def _run(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            self._consume(job)

    def _consume(self, job: Dict[str, Any]) -> None:
        # async path only: the pump can't propagate across threads, so a
        # failed boundary is logged and counted, never fatal
        try:
            loss = self._trainer._consume_boundary(job)
        except Exception:  # noqa: BLE001 — see docstring
            logger.exception("metrics pump: boundary %s failed",
                             job.get("step"))
            with self._lock:
                self._errors += 1
            return
        self._note_done(job, loss)

    def _note_done(self, job: Dict[str, Any], loss: float) -> None:
        with self._lock:
            self._last_loss = loss
            self._drained += 1
            if job.get("pw") is not None:
                self._windows_inflight -= 1


class Trainer:
    """HF-style: Trainer(model, args, train_data[, eval_data]).train().

    `train_data` / `eval_data`: iterables yielding host batches — dicts of
    arrays shaped (global_batch, ...) — or callables `(step) -> batch`
    (useful for synthetic/streaming data).
    """

    def __init__(self, model, args: TrainingArgs,
                 train_data: Any, eval_data: Any = None,
                 optimizer=None, loss_fn: Optional[Callable] = None,
                 callbacks: Optional[list] = None):
        # construction is a large share of a restart (the plan, a sharded
        # state init, the checkpoint engine): its own span and children.
        # What the process did before it got here is `proc:boot`, which
        # ends on this span's start.
        with tspans.span("trainer:build") as rec:
            tspans.boot_span(beside=rec)
            self._build(model, args, train_data, eval_data, optimizer,
                        loss_fn, callbacks)
            # the state resident after `accelerate:init_state` and
            # `ckpt:open` (absent where the backend reads no memory)
            tmemory.note(rec, "hbm")

    def _build(self, model, args, train_data, eval_data, optimizer,
               loss_fn, callbacks):
        import optax

        self.model = model
        self.args = args
        self.train_data = train_data
        self.eval_data = eval_data
        self.callbacks = callbacks or []
        self._loss_fn = loss_fn

        # elastic context: no-op when not launched by the agent
        from .elastic import init_elastic

        self.ctx = init_elastic()
        # hot-swap participant (trainer/hotswap.py) — attached by the
        # agent/drill when a replica ring exists; polled at fusion
        # boundaries alongside the policy decision
        self.hotswap = None

        schedule = self._make_schedule(optax)
        self.optimizer = optimizer or optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(schedule, weight_decay=args.weight_decay))

        from ..auto.accelerate import auto_accelerate

        self.res = auto_accelerate(
            model, optimizer=self.optimizer, strategy=args.strategy,
            loss_fn=loss_fn, accum_steps=args.grad_accum_steps,
            seq_len=args.seq_len)
        self.state = self.res.state

        from ..checkpoint.checkpointer import FlashCheckpointer

        with tspans.span("ckpt:open"):
            self.ckpt = FlashCheckpointer(
                os.path.join(args.output_dir, "checkpoints"),
                job_name=os.getenv("DWT_JOB_NAME", "dwt"),
                wire_dtype=args.ckpt_wire_dtype)

        from ..utils.profiler import StepProfiler

        self.profiler = StepProfiler(
            trace_dir=args.profile_trace_dir or None,
            start_step=args.profile_start_step,
            end_step=args.profile_end_step)

        # perf observatory: in-train profiling windows + baseline store +
        # regression sentinel (telemetry/perf.py).  Registered as the
        # process singleton so flight-recorder dumps embed the latest
        # PerfSnapshot.  The baseline lives next to the checkpoints
        # ($ckpt_dir/perf/baseline.json) so it survives restarts with the
        # run, keyed by the full executable identity — a strategy / K /
        # backend change never pollutes another key's stats.
        self._perf = None
        if args.perf_window_every > 0:
            from ..telemetry.perf import PerfObservatory, set_observatory

            self._perf = PerfObservatory(
                ckpt_dir=os.path.join(args.output_dir, "checkpoints"),
                every=args.perf_window_every,
                m_consecutive=args.perf_regress_windows,
                overhead_budget=args.perf_overhead_budget,
                on_event=self._on_perf_event,
                job_name=os.getenv("DWT_JOB_NAME", "dwt"))
            set_observatory(self._perf)

        # master-tuned runtime config (batch size / ckpt cadence) — closes
        # the loop master → agent ParalConfigTuner → file → trainer.
        # Gated on the env path the agent's tuner exports: a standalone run
        # must not pick up a dead job's file at the shared default path.
        from ..agent.config_tuner import ParalConfigListener
        from ..common.constants import ConfigPath

        self._tune_listener = (
            ParalConfigListener()
            if args.tune_config_steps and os.getenv(ConfigPath.ENV_PARAL_CONFIG)
            else None)

        # adaptive-policy state: last decision id applied (master ids are
        # monotonic — replays/duplicates after a reconnect are skipped),
        # a fused-K change parked until its warm-pool entry is ready, and
        # the applied-decision log (tests + post-mortem)
        self._policy_last_id = 0
        self._policy_pending_k: Optional[int] = None
        self._warm_pool = None
        self.policy_applied: list = []

        # device-queue liveness probe → master hang localization
        self._prober = None
        if args.probe_interval > 0 and self.ctx.mc is not None:
            from ..diagnosis.probe import DeviceProber

            self._prober = DeviceProber(self.ctx.mc,
                                        interval=args.probe_interval)
            self._prober.start()

    # ------------------------------------------------------ paral-config

    def _batch_divisor(self) -> int:
        """A tuned batch size must divide the data-parallel axis product
        (batch-dim sharding) and the pipeline microbatch count."""
        import math

        mesh = self.res.mesh
        div = 1
        for ax in ("dp", "fsdp"):
            div *= mesh.shape.get(ax, 1)
        micro = getattr(self.res.model, "num_microbatches", 1)
        return div * micro // math.gcd(div, micro)

    def _apply_tuned_config(self, cfg: Dict) -> None:
        """Apply a master-pushed ParallelConfig between steps.

        Parity: reference elastic/dataloader.py:97-133 (batch size) +
        paral_config_tuner ckpt cadence.  Mesh-shape changes need a restart
        and are only logged here (the agent's restart path re-plans)."""
        bs = int(cfg.get("dataloader_batch_size") or 0)
        if bs > 0 and hasattr(self.train_data, "update_batch_size") and \
                bs != getattr(self.train_data, "batch_size", bs):
            div = self._batch_divisor()
            if bs % div:
                logger.warning(
                    "ignoring tuned batch size %d: not divisible by %d "
                    "(data-axis sharding x pipeline microbatches)", bs, div)
            else:
                self.train_data.update_batch_size(bs)
        ckpt_every = int(cfg.get("ckpt_interval_steps") or 0)
        if ckpt_every > 0 and ckpt_every != self.args.save_steps:
            logger.info("ckpt cadence %d -> %d steps",
                        self.args.save_steps, ckpt_every)
            self.args.save_steps = ckpt_every
        if cfg.get("mesh_shape"):
            logger.info("master proposes mesh %s (applies on next restart)",
                        cfg["mesh_shape"])

    # ------------------------------------------------- adaptive policy

    def _poll_mesh_transition(self) -> None:
        """Drive the hot-swap participant (trainer/hotswap.py) — fires
        only at fusion boundaries, on the policy-poll cadence.  The
        participant is attached by the agent/drill (it carries the
        replica ring + re-shard hooks the trainer doesn't own); without
        one this is a no-op."""
        hs = getattr(self, "hotswap", None)
        if hs is None:
            return
        try:
            hs.poll()
        except Exception:  # noqa: BLE001 — a broken participant must
            # degrade to classic restart-the-world, never kill the loop
            logger.exception("hot-swap poll failed")

    def _poll_policy(self) -> None:
        """Fetch the master's current PolicyDecision (polling verb — a
        dead master degrades to the last applied knobs, never an error)
        and apply it if it is new."""
        try:
            d = self.ctx.mc.get_policy_decision()
        except Exception:  # noqa: BLE001 — degraded mode keeps training
            return
        did = int(getattr(d, "decision_id", 0) or 0)
        if did <= self._policy_last_id:
            return
        self._policy_last_id = did
        self._apply_policy_decision(d)

    def _apply_policy_decision(self, d) -> None:
        """Apply one PolicyDecision's knobs.  Cadence/tier/replica apply
        immediately (next boundary / next backup / next load); a fused-K
        request is PARKED in _policy_pending_k — the loop cuts over only
        after _prewarm_fused_k confirms a ready warm-pool entry, because
        K changes the HLO and a cold mid-run compile would cost more than
        any cadence win."""
        applied: Dict[str, Any] = {"decision_id": d.decision_id}
        k_active = int(getattr(self, "_fused_k_active", 0) or 1)
        interval = int(getattr(d, "ckpt_interval_steps", 0) or 0)
        if interval > 0:
            if k_active > 1 and interval % k_active:
                # boundary-reachable: round UP to a fusion multiple so the
                # cadence the policy paid for is never silently skipped
                interval = ((interval + k_active - 1) // k_active) * k_active
            if interval != self.args.save_steps:
                logger.info("policy #%d: ckpt cadence %d -> %d steps",
                            d.decision_id, self.args.save_steps, interval)
                self.args.save_steps = interval
            applied["ckpt_interval_steps"] = interval
        tier = getattr(d, "preferred_tier", "") or ""
        if tier:
            try:
                self.ckpt.set_preferred_tier(tier)
                applied["preferred_tier"] = tier
            except ValueError as e:
                logger.warning("policy #%d: %s", d.decision_id, e)
        replicas = int(getattr(d, "replica_count", -1))
        if replicas >= 0:
            self.ckpt.set_replica_count(replicas)
            applied["replica_count"] = replicas
        k_req = int(getattr(d, "fused_steps", 0) or 0)
        if k_req > 0 and k_req != k_active:
            cad = self._hook_cadence()
            if k_req > 1 and cad and cad % k_req:
                logger.warning(
                    "policy #%d: fused_steps=%d does not divide the hook "
                    "cadence gcd %d — keeping K=%d", d.decision_id, k_req,
                    cad, k_active)
            elif getattr(self.res, "_fused_factory", None) is None \
                    and k_req > 1:
                logger.warning("policy #%d: no fused driver for this "
                               "strategy — keeping K=%d", d.decision_id,
                               k_active)
            else:
                self._policy_pending_k = k_req
                applied["fused_steps_requested"] = k_req
        self.policy_applied.append(applied)

    def _prewarm_fused_k(self, k: int) -> bool:
        """True when switching the fused driver to K may proceed: the
        K-wide executable of this run's published warm spec is ready in
        the pool — or can never be: no persistent cache, no spec
        published by THIS run (the dir is shared, a leftover file may be
        another job's), or a platform whose devices this process holds
        (`can_warm`: a warm child could not open them) — then the caller
        cuts over and compiles in place.  Otherwise kick an async warm
        compile and report False until a later boundary finds the entry
        ready."""
        cache_dir = getattr(self.res, "_cache_dir", None)
        if not cache_dir:
            return True
        from ..auto.warm_pool import (
            WarmPool,
            can_warm,
            load_current_spec,
            model_spec,
        )

        spec = load_current_spec(cache_dir)
        if spec is None or \
                spec.model != model_spec(self.res.model) or \
                spec.n_devices != self.res.mesh.size or \
                spec.batch_shape != [self.args.global_batch_size,
                                     self.args.seq_len]:
            return True
        if not can_warm(spec.platform):
            logger.info("policy fused_steps=%d: no warm child for platform "
                        "%r (this process holds its devices) — cutting "
                        "over, the step compiles in place", k, spec.platform)
            return True
        spec = dataclasses.replace(spec, fused_steps=k)
        if self._warm_pool is None:
            self._warm_pool = WarmPool(cache_dir)
        if self._warm_pool._ready_entry_for(spec.spec_key()) is not None:
            return True
        self._warm_pool.warm_async(spec)
        logger.info("policy fused_steps=%d: warming in the pool — cutover "
                    "deferred until the entry is ready", k)
        return False

    # ------------------------------------------------------------- schedule

    def _make_schedule(self, optax):
        a = self.args
        peak = a.learning_rate
        if a.lr_schedule == "constant":
            return optax.linear_schedule(0.0, peak, max(1, a.warmup_steps))
        decay_steps = max(1, a.max_steps - a.warmup_steps)
        if a.lr_schedule == "linear":
            decay = optax.linear_schedule(peak, peak * a.min_lr_ratio,
                                          decay_steps)
        else:
            decay = optax.cosine_decay_schedule(
                peak, decay_steps, alpha=a.min_lr_ratio)
        warmup = optax.linear_schedule(0.0, peak, max(1, a.warmup_steps))
        return optax.join_schedules([warmup, decay], [a.warmup_steps])

    # ----------------------------------------------------------------- data

    def _batch_at(self, source, step: int):
        if callable(source):
            return source(step)
        if not hasattr(self, "_iters"):
            self._iters = {}
        it = self._iters.get(id(source))
        if it is None:
            it = iter(source)
            self._iters[id(source)] = it
        try:
            return next(it)
        except StopIteration:
            it = iter(source)  # new epoch
            self._iters[id(source)] = it
            return next(it)

    # --------------------------------------------------- fused dispatch

    def request_stop(self):
        """Graceful stop at the next fusion boundary (preemption path)."""
        self._preempted = True

    def _on_sigterm(self, signum, frame):
        logger.info("SIGTERM: finishing the in-flight fusion, then "
                    "saving and exiting (graceful preemption)")
        self._preempted = True

    def _hook_cadence(self) -> int:
        """gcd of the active step cadences — K must divide it so every
        hook (logging/save/eval/tune) lands exactly on a fusion boundary,
        keeping the checkpoint cadence from the preempt-table goodput
        curve reachable."""
        import math

        a = self.args
        cad = 0
        for c in (a.logging_steps, a.save_steps,
                  a.eval_steps if self.eval_data is not None else 0,
                  a.tune_config_steps if self._tune_listener is not None
                  else 0,
                  a.policy_steps if self.ctx.mc is not None else 0,
                  a.flash_stage_steps):
            if c:
                cad = math.gcd(cad, int(c))
        return cad

    def _initial_fused_k(self):
        """args.fused_steps resolved: 1 (off), K (explicit), or None —
        auto-tune after measuring the first unfused steps."""
        a = self.args
        if a.fused_steps == 1:
            return 1
        if getattr(self.res, "_fused_factory", None) is None:
            # local_sgd: no fused driver.  Auto quietly runs unfused;
            # an explicit K>1 surfaces the strategy conflict.
            if a.fused_steps > 1:
                self.res.fused_train_step(a.fused_steps)  # raises
            logger.info("fused dispatch unavailable for this strategy; "
                        "running unfused")
            return 1
        if a.fused_steps > 1:
            return a.fused_steps
        return None  # auto

    def _dispatch_overhead_s(self) -> float:
        """Per-dispatch overhead estimate for the ledger's
        dispatch_overhead state — the cached backend probe (or the
        DWT_DISPATCH_OVERHEAD_S pin), never a readback on step outputs."""
        if not hasattr(self, "_disp_overhead"):
            from ..common.util import measure_dispatch_overhead_s

            self._disp_overhead = measure_dispatch_overhead_s()
        return self._disp_overhead

    def _autotune_fused_k(self, step_time_s: float) -> int:
        from .train_step import auto_fused_steps

        k = auto_fused_steps(step_time_s, cadence=self._hook_cadence())
        if k > 1:
            logger.info("fused_steps auto-tuned to %d "
                        "(measured step %.1fms)", k, step_time_s * 1e3)
        return k

    # ----------------------------------------------------- perf observatory

    def _strategy_fingerprint(self) -> str:
        """Strategy identity of the perf baseline key (K excluded)."""
        try:
            return repr((self.res.strategy.plan.describe(),
                         self.res.strategy_spec))
        except Exception:  # noqa: BLE001
            return repr(self.args.strategy)

    def _perf_key(self, fused_k: int) -> str:
        """Executable identity for the perf baseline — the same facts that
        key the compile cache (strategy fingerprint, fused-K, backend),
        so baseline stats never mix executables and a K cutover lands on
        a NEW key instead of firing the regression sentinel against the
        old width's baseline."""
        import jax

        from ..telemetry.perf import executable_key

        return executable_key(self._strategy_fingerprint(), int(fused_k),
                              jax.default_backend())

    def _on_perf_event(self, event: Dict) -> None:
        """Sentinel verdicts → master node-event stream (the same surface
        the checkpoint engine uses for ckpt-health).  Telemetry never
        kills the run."""
        import json as _json

        if self.ctx.mc is None:
            return
        try:
            self.ctx.mc.report_node_event(
                str(event.get("kind", "perf-regression")),
                _json.dumps(event, sort_keys=True), level="warning")
        except Exception:  # noqa: BLE001
            pass

    def _user_trace_active(self, s0: int, k_eff: int) -> bool:
        """True while the opt-in StepProfiler window overlaps this fusion —
        two jax.profiler traces can't nest, so perf windows yield."""
        a = self.args
        if not a.profile_trace_dir or a.profile_start_step < 0:
            return False
        return a.profile_start_step < s0 + k_eff and \
            s0 <= max(a.profile_end_step, a.profile_start_step)

    # ------------------------------------------------- boundary consumer

    def _consume_boundary(self, job: Dict[str, Any]) -> float:
        """One logging boundary's host work — runs on the metrics pump
        thread (inline when async_metrics=False).  The ONE readback per
        fusion lives here; that sync also flushes the fused block's
        device work into any open perf window's trace.  Reads trainer
        state but never writes it — results flow back through the pump's
        lock-guarded fields (and `_readback_mark`, which only this
        consumer touches once the loop runs)."""
        step = job["step"]
        with tspans.extract(job.get("trace")):
            with tspans.hot_span("pump:readback"):
                # metrics is an executable OUTPUT: donation-immune, safe
                # to read after the main thread has dispatched the next
                # fusion
                loss = float(job["metrics"]["loss"])
                t_read = time.monotonic()
                # whatever else the step counted (make_train_step: the
                # loss's `with_stats`) is a scalar output of the same
                # executable as the loss: there once the loss is
                counted = {k: float(v) for k, v in job["metrics"].items()
                           if k not in _STEP_SERIES}
            # the device has finished `step` and later steps are queued:
            # the devices' memory while the step runs, with no sync of
            # its own and nothing on the main thread
            hbm = tmemory.reading()
            if hbm:
                tspans.span_event("trainer:memory", {"step": step, **hbm})
            with tspans.hot_span("pump:report"):
                self._report_boundary(job, step, loss, t_read, counted)
        return loss

    def _report_boundary(self, job: Dict[str, Any], step: int, loss: float,
                         t_read: float,
                         counted: Optional[Dict[str, float]] = None) -> None:
        """What follows the readback at a logging boundary: perf-window
        close, the log line, master reports, callbacks.
        `counted` (the step's scalars beside loss and grad_norm, e.g. an
        MoE model's expert load) gets a log line of its own, rides in
        the callbacks' dict and is kept as one `trainer:step_metrics`
        span event."""
        counted = counted or {}
        snap = None
        pw = job.get("pw")
        if pw is not None:
            # the readback above synced the block, so the trace holds the
            # device work: fold the xplane op split + step time into a
            # PerfSnapshot, update the baseline, run the regression
            # sentinel, and ship it on the buffered latest-SENT-wins verb
            snap = self._perf.close(pw)
        # the readback returns when the device has finished `step`: the
        # rate between two of them is the device's, where the loop's own
        # clock only times dispatches that run ahead of it
        prev_step, prev_t = self._readback_mark
        self._readback_mark = (step, t_read)
        tps = (step - prev_step) * job["tokens_per_step"] / \
            max(t_read - prev_t, 1e-9)
        logger.info("step %d loss=%.4f tokens/s=%.0f", step, loss, tps)
        if counted:
            logger.info("step %d %s", step, " ".join(
                f"{k}={v:.4g}" for k, v in counted.items()))
            tspans.span_event("trainer:step_metrics",
                              {"step": step, **counted})
        self.ctx.report_step(step)
        self.ctx.report_loss(step, loss)
        if self.ctx.mc is not None:
            try:  # buffered verbs; telemetry never kills the run
                if snap:
                    self.ctx.mc.report_perf_snapshot(snap)
                self.ctx.mc.report_goodput_ledger(job["ledger"])
            except Exception:  # noqa: BLE001
                pass
        for cb in self.callbacks:
            cb(step, {"loss": loss, "tokens_per_sec": tps, **counted})

    # ---------------------------------------------------------------- train

    def train(self) -> Dict[str, float]:
        # every `trainer:iteration` and `ckpt:restore:*` hangs under
        # this span.  A fault ends it where it is caught (`end_span`),
        # so the flight dump written there holds the chain to its end.
        with contextlib.ExitStack() as scope:
            rec = scope.enter_context(tspans.span("trainer:train"))
            return self._train(rec, scope.close)

    def _train(self, span_rec: Dict[str, Any],
               end_span: Callable[[], None]) -> Dict[str, float]:
        import signal as _signal

        import jax

        from ..auto.compile_cache import seconds_between
        from ..telemetry.ledger import get_ledger
        from ..telemetry.perf import keep_step_executable
        from ..telemetry.recorder import get_recorder

        a = self.args
        led = get_ledger()
        led.start()
        start_step = 0
        restored_tier = ""
        # rollback rework ceiling: steps below this were trained before a
        # loss-spike rollback and are re-executed ("rework", not goodput)
        self._rework_until = -1
        if a.resume:
            from ..common.constants import NodeEnv

            # one-shot rollback ceiling injected by the agent after a
            # loss-spike diagnosis: resume from BEFORE the spike, not from
            # the latest commit (which can postdate onset)
            try:
                rb = int(os.getenv(NodeEnv.ROLLBACK_BEFORE_STEP, "-1"))
            except ValueError:  # empty/garbage env: resume normally,
                rb = -1        # don't wedge the restart loop
            restored = self.ckpt.load_checkpoint(
                self.state, before_step=rb if rb >= 0 else None)
            if restored is not None:
                self.state = restored
                start_step = int(np.asarray(
                    jax.tree.leaves(self.state.step)[0]))
                if rb >= 0:
                    self._rework_until = rb
                rep = self.ckpt.last_restore_report
                restored_tier = str(rep.get("tier", ""))
                logger.info("resumed from step %d (tier=%s%s)", start_step,
                            rep.get("tier", "?"),
                            ", degraded" if rep.get("fallbacks") else "")
                if rep.get("fallbacks") and self.ctx.mc is not None:
                    # checkpoint-health event: the master's event stream
                    # is where operators see that a tier was corrupt and
                    # which generation actually served the resume
                    self.ctx.mc.report_node_event(
                        "ckpt-health",
                        f"degraded resume: tier={rep.get('tier')} "
                        f"step={rep.get('step')} "
                        f"fallbacks={rep.get('fallbacks')}",
                        level="warning")

        span_rec["attrs"].update(start_step=start_step,
                                 restored_tier=restored_tier)
        # what the caller and a restore left, peaks included: whether a
        # process-wide peak predates the loop is read off this
        tmemory.note(span_rec, "hbm_at_entry")
        last_loss = float("nan")
        metrics = None
        self._preempted = False
        prev_sigterm = None
        if a.graceful_preemption:
            try:
                prev_sigterm = _signal.signal(_signal.SIGTERM,
                                              self._on_sigterm)
            except ValueError:  # not the main thread: leave the default
                prev_sigterm = None
        fused_k = self._initial_fused_k()
        stager = None
        step_time_s = 0.0
        step = start_step
        # (step, instant) of the last loss readback: tokens/s is device
        # progress from one readback to the next, taken on the pump
        self._readback_mark = (start_step, time.monotonic())
        # goodput ledger: the trainer owns productive / dispatch_overhead /
        # data_stall / compile / rework; the checkpoint engine credits
        # ckpt_stage/persist + restore tiers; master_client credits
        # degraded.  All accounting happens HERE at fusion boundaries from
        # host-side timers — never inside the jitted step, never via an
        # extra device readback.  Modes are fusion widths K: a K
        # cutover's first dispatch is a compile, not overhead.
        self._compiled_modes: set = set()
        # callbacks are synchronous user hooks (request_stop, config
        # pushes assert their effect on the NEXT fusion) — their presence
        # forces the inline path
        self._pump = _MetricsPump(
            self, enabled=a.async_metrics and not self.callbacks)
        try:
            while step < a.max_steps and not self._preempted:
                with tspans.hot_span("trainer:iteration"):
                    t_iter0 = time.monotonic()
                    if fused_k is None and step - start_step >= 2:
                        # two unfused steps measured (the first compiles):
                        # decide K, then fuse the rest of the run
                        fused_k = self._autotune_fused_k(step_time_s)
                    if self._policy_pending_k is not None and \
                            fused_k is not None:
                        # fusion-boundary K cutover: only once the warm pool
                        # holds a ready entry at the new K (never a cold
                        # compile mid-run); the stager rebuilds below at the
                        # new width, K=1 falls back to the unfused path
                        if self._policy_pending_k == fused_k:
                            self._policy_pending_k = None
                        elif self._prewarm_fused_k(self._policy_pending_k):
                            logger.info("policy: fused_steps %d -> %d at "
                                        "boundary %d", fused_k,
                                        self._policy_pending_k, step)
                            fused_k = self._policy_pending_k
                            self._policy_pending_k = None
                            stager = None
                    self._fused_k_active = fused_k or 0
                    if fused_k is not None and fused_k > 1 and stager is None:
                        from ..data.elastic_dataset import FusedBatchStager

                        stager = iter(FusedBatchStager(
                            lambda s: dict(self._batch_at(self.train_data, s)),
                            self.res.place_fused_batch, fused_k,
                            step, a.max_steps,
                            place_single=self.res.place_batch))
                    with tspans.hot_span("trainer:data"), \
                            led.window("data_stall"):
                        if stager is not None:
                            s0, k_eff, batch = next(stager)
                        else:
                            s0, k_eff = step, 1
                            batch = self.res.place_batch(
                                dict(self._batch_at(self.train_data, step)))
                    data_s = time.monotonic() - t_iter0
                    if self._tune_listener is not None and \
                            s0 % a.tune_config_steps == 0:
                        tuned = self._tune_listener.poll()
                        if tuned:
                            self._apply_tuned_config(tuned)
                    if a.policy_steps and self.ctx.mc is not None and \
                            s0 % a.policy_steps == 0:
                        with tspans.hot_span("trainer:policy_poll"):
                            self._poll_policy()
                            self._poll_mesh_transition()
                    pw = None
                    if self._perf is not None and a.logging_steps and \
                            (s0 + k_eff) % a.logging_steps == 0 and \
                            k_eff in self._compiled_modes and \
                            self._pump.windows_inflight() == 0 and \
                            not self._user_trace_active(s0, k_eff):
                        # perf window: only on a boundary that already carries
                        # the logging readback (that sync flushes the fused
                        # block's device work into the trace — zero NEW
                        # readbacks), never on the compile dispatch (compile
                        # wall is not a step-time baseline), never while the
                        # opt-in trace window is live or a pump-held window is
                        # still closing (jax traces can't nest).
                        # maybe_open applies the every-Nth cadence and the
                        # <1%-overhead self-limit.
                        self._perf.key = self._perf_key(k_eff)
                        pw = self._perf.maybe_open(s0, k_eff)
                    prof_before = self.profiler.last_profile
                    t_blk0 = time.monotonic()
                    # the span times the dispatch CALL, which returns
                    # before the device has run the step; the step
                    # annotation groups this dispatch's device work in a
                    # profiler trace
                    with tspans.hot_span("trainer:dispatch"), \
                            jax.profiler.StepTraceAnnotation(
                                "train", step_num=s0), \
                            self.profiler.step(s0):
                        if k_eff > 1:
                            self.state, metrics = self.res.fused_train_step(
                                k_eff)(self.state, batch)
                        else:
                            t0 = time.perf_counter()
                            self.state, metrics = self.res.train_step(
                                self.state, batch)
                            if fused_k is None:
                                # auto-tune measurement: sync so the timing is
                                # the real step, not the async dispatch
                                float(metrics["loss"])
                                step_time_s = time.perf_counter() - t0
                        if self.profiler.closes_at(s0):
                            # the opt-in trace window ends with this block:
                            # dispatch is async, so wait for the device or the
                            # trace holds only the block's first milliseconds
                            jax.block_until_ready(metrics)
                    blk_s = time.monotonic() - t_blk0
                    if k_eff not in self._compiled_modes:
                        # first dispatch at this fusion width
                        # traces+compiles
                        self._compiled_modes.add(k_eff)
                        led.account("compile", blk_s)
                        credited_blk = blk_s
                        # for a reader of the step's text or budget,
                        # afterwards (telemetry/perf.py): shapes and
                        # shardings only, no array is kept; ~2 ms at
                        # 1,740 leaves, once
                        keep_step_executable(
                            k_eff, self.res.fused_train_step(k_eff),
                            self.state, batch)
                        # once a width: the dispatch call, what JAX
                        # spent inside it getting programs ready, and
                        # what the program it dispatched was compiled
                        # to hold (a flight dump then has the budget)
                        tspans.past_span(
                            "trainer:first_step", t_blk0, t_blk0 + blk_s,
                            {"k": k_eff, "blk_s": blk_s,
                             **seconds_between(t_blk0, t_blk0 + blk_s),
                             **_step_budget(k_eff)})
                        if self.ctx.world.restart_count and \
                                len(self._compiled_modes) == 1:
                            # a restarted generation leaves its start-up
                            # chain beside the checkpoints: the worker's
                            # half of the restart's tree
                            # (tools/incident_report.py --restart-table)
                            get_recorder().flush(
                                self.ckpt.checkpoint_dir, "resumed")
                    else:
                        credited_blk = min(blk_s, self._dispatch_overhead_s())
                        led.account("dispatch_overhead", credited_blk)
                    if self.profiler.last_profile is not prof_before:
                        # a trace window just closed: surface slow collectives
                        self.ctx.report_op_profile(
                            self.profiler.last_profile.collective_evidence())
                    step = s0 + k_eff
                    hooks_excl_s = 0.0  # save/eval time: credited elsewhere
                    # (engine ledger states) or left to the other_s residual
                    # ---- boundary hooks: K divides every active cadence, so
                    # these fire exactly as in the unfused loop ----
                    if a.logging_steps and step % a.logging_steps == 0:
                        # the boundary's host work — the ONE readback per
                        # fusion, the perf-window close, the master reports
                        # and the callbacks — goes to the metrics pump so the
                        # next fused dispatch overlaps it instead of
                        # serializing behind the sync.  Ledger CREDITS stayed
                        # above on this thread; the pump only ships the
                        # snapshot dict taken here at the boundary.
                        # re-read the live batch size: the master may retune it
                        tokens_per_step = a.seq_len * getattr(
                            self.train_data, "batch_size", a.global_batch_size)
                        with tspans.hot_span("trainer:log_submit"):
                            self._pump.submit({
                                "step": step, "metrics": metrics, "pw": pw,
                                "tokens_per_step": tokens_per_step,
                                "ledger": led.snapshot(),
                                # the pump's spans hang under this one
                                "trace": tspans.current_trace(),
                            })
                        pw = None
                    saved = False
                    if a.save_steps and step % a.save_steps == 0:
                        t_h = time.monotonic()
                        with tspans.hot_span("trainer:save"):
                            self._save(step)
                        hooks_excl_s += time.monotonic() - t_h
                        saved = True
                    if a.flash_stage_steps and not saved and \
                            step % a.flash_stage_steps == 0:
                        # shm staging (save_to_memory): the agent's
                        # save-on-failure persists this boundary if the next
                        # fusion never completes
                        from ..checkpoint.checkpointer import StorageType

                        t_h = time.monotonic()
                        with tspans.hot_span("trainer:stage"):
                            self.ckpt.save_checkpoint(
                                step, self.state,
                                storage_type=StorageType.MEMORY)
                        hooks_excl_s += time.monotonic() - t_h
                    if a.eval_steps and self.eval_data is not None and \
                            step % a.eval_steps == 0:
                        t_h = time.monotonic()
                        with tspans.hot_span("trainer:eval"):
                            eval_loss = self.evaluate()
                        hooks_excl_s += time.monotonic() - t_h
                        logger.info("step %d eval_loss=%.4f", step, eval_loss)
                    # remainder of the iteration is the fused window itself:
                    # wall - data stall - credited dispatch/compile - hook time
                    # (saves are credited by the engine as ckpt_stage/persist;
                    # eval falls to the other_s residual by design)
                    window_s = max(0.0, (time.monotonic() - t_iter0) - data_s
                                   - credited_blk - hooks_excl_s)
                    led.account(
                        "rework" if s0 < self._rework_until else "productive",
                        window_s)
            if self._preempted and step < a.max_steps:
                logger.info("preempted at fusion boundary %d — saving and "
                            "exiting", step)
        except BaseException:
            # fault flight dump: ring buffer + ledger snapshot land next
            # to the checkpoints so post-mortem tooling finds them —
            # `trainer:train` among them, ended here
            span_rec["status"] = "error"
            span_rec["attrs"]["stopped_at"] = step
            end_span()
            get_recorder().flush(self.ckpt.checkpoint_dir, "fault")
            raise
        finally:
            # flush queued boundaries + join (thread-leak guard) BEFORE
            # the final cumulative ledger ship, so latest-wins ordering
            # holds at the master
            self._pump.stop()
            pump_loss = self._pump.last_loss()
            if pump_loss == pump_loss:
                last_loss = pump_loss
            if self._preempted:
                get_recorder().flush(self.ckpt.checkpoint_dir, "sigterm")
            if self.ctx.mc is not None:
                try:  # final cumulative snapshot (latest-wins at master)
                    self.ctx.mc.report_goodput_ledger(led.snapshot())
                except Exception:  # noqa: BLE001
                    pass
            if prev_sigterm is not None:
                try:
                    _signal.signal(_signal.SIGTERM, prev_sigterm)
                except ValueError:
                    pass
            if self._prober is not None:
                self._prober.stop()
            if a.save_on_exit:
                final = int(np.asarray(
                    jax.tree.leaves(self.state.step)[0]))
                if getattr(self, "_last_saved_step", -1) != final:
                    # don't re-stage a step the cadence save just staged:
                    # two concurrent saves of one step race on the same
                    # shard files
                    self._save(final)
                self.ckpt.wait_latest_checkpoint(600)
            self.profiler.close()
        if last_loss != last_loss and metrics is not None:
            last_loss = float(metrics["loss"])  # only short runs never log
        span_rec["attrs"]["stopped_at"] = step
        return {"final_step": a.max_steps, "final_loss": last_loss,
                "stopped_at": step}

    def _save(self, step: int):
        from ..checkpoint.checkpointer import StorageType

        # mesh/world shape + fused-K travel in the staging extras and land
        # in the committed generation's manifest (checkpoint/integrity.py)
        # — restore tooling can tell what world wrote a checkpoint
        mesh = getattr(self.res, "mesh", None)
        extra = {"mesh_shape": ({k: int(v) for k, v in
                                 dict(mesh.shape).items()}
                                if mesh is not None else {}),
                 "fused_steps": int(getattr(self, "_fused_k_active", 0)
                                    or self.args.fused_steps)}
        blocked = self.ckpt.save_checkpoint(
            step, self.state, storage_type=StorageType.DISK,
            extra_meta=extra)
        self._last_saved_step = step
        logger.info("checkpoint step %d staged (blocked %.3fs)", step,
                    blocked)

    # ----------------------------------------------------------------- eval

    def evaluate(self) -> float:
        """Mean loss over up to max_eval_batches of eval_data."""
        import jax

        if self.eval_data is None:
            raise ValueError("no eval_data")
        if not hasattr(self, "_eval_fn"):
            loss_fn = self.res.loss_fn

            @jax.jit
            def _eval(params, batch):
                return loss_fn(params, batch)

            self._eval_fn = _eval
        params = getattr(self.state, "params", None)
        if params is None:  # DiLoCo state: evaluate the synced outer params
            params = self.state.outer_params
        losses = []
        for i in range(self.args.max_eval_batches):
            try:
                batch = self.res.place_batch(
                    dict(self._batch_at(self.eval_data, i)))
            except StopIteration:  # pragma: no cover
                break
            losses.append(float(self._eval_fn(params, batch)))
        return float(np.mean(losses)) if losses else float("nan")
