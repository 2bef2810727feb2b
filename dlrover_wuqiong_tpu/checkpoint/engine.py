"""Checkpoint engine: training-process side of flash checkpointing.

Parity: reference `trainer/torch/flash_checkpoint/engine.py` (CheckpointEngine
ABC :136, `save_state_dict_to_memory` :297, `save_to_storage` :409) and
`full_ckpt_engine.py`.

The engine runs inside each training process.  `save_to_memory` snapshots the
sharded pytree ON DEVICE (jax.Arrays are immutable, so a device-to-device copy
at HBM bandwidth is a consistent point-in-time snapshot — milliseconds) and
returns; a drain thread then stages snapshot → shm (batched async D2H) off the
training path.  `save_to_storage` additionally enqueues an event for the
agent-side `AsyncCheckpointSaver`, which persists shm → storage.  In
standalone mode (no agent) the engine hosts the saver daemon in-process.

This is the TPU redesign of the reference's blocking tier: reference GPU→shm
memcpy rides PCIe (fast), so shm is its fast tier; on TPU the fast tier is
HBM itself and the D2H hop joins the async pipeline.  Training is blocked
only for the device copy; a crash mid-drain loses only the in-flight
checkpoint, exactly like a crash mid-memcpy in the reference.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from ..common.constants import CheckpointConstant
from ..common.log import get_logger
from ..common.multi_process import SharedLock, SharedQueue
from ..common.storage import CheckpointStorage, get_checkpoint_storage
from ..telemetry import memory as tmemory
from ..telemetry import spans as tspans
from ..telemetry.ledger import get_ledger
from .ckpt_saver import (
    AsyncCheckpointSaver,
    CheckpointEvent,
    read_last_step,
    shm_lock_name,
    step_dir,
)
from .integrity import (
    VerifyFailure,
    quarantine_step,
    read_manifest,
    verify_meta_bytes,
    verify_rank_bytes,
    verify_segment_entries,
)
from .shm_handler import SharedMemoryHandler, _np_dtype, flatten_state_dict

logger = get_logger("ckpt_engine")

# fallback reasons that mean CORRUPTION (reported to the master as
# checkpoint-health events) vs. benign tier misses (cold shm, a segment
# from another job/step, a single rank of a multi-process world)
_BENIGN_REASONS = ("stale", "foreign-segment", "step-mismatch",
                   "partial-local-coverage")


def _is_corruption(reason: str) -> bool:
    return bool(reason) and reason not in _BENIGN_REASONS


class CheckpointEngine:
    def __init__(self, checkpoint_dir: str, local_rank: int = 0,
                 job_name: str = "dwt", standalone: Optional[bool] = None,
                 storage: Optional[CheckpointStorage] = None,
                 local_shard_num: int = 1, node_rank: int = 0,
                 wire_dtype: Optional[str] = None,
                 replica_fetch=None):
        """`wire_dtype="bf16"`: f32 float leaves are cast to bf16 ON
        DEVICE during the snapshot — halving D2H staging, disk bytes, and
        restore H2D (restore upcasts on device).  NOT bit-exact for f32
        sources (16 mantissa bits dropped; bf16/int leaves round-trip
        exactly) — the exact-resume contract test pins both behaviors.
        The win is for transfer-bound links: restore bytes halve (r4
        verdict next #3)."""
        self.checkpoint_dir = checkpoint_dir
        self.local_rank = local_rank
        self.job_name = job_name
        if wire_dtype not in (None, "bf16"):
            raise ValueError(f"unsupported wire_dtype {wire_dtype!r}")
        self.wire_dtype = wire_dtype
        # gs://... checkpoint dirs resolve to the object-store backend
        self.storage = storage or get_checkpoint_storage(
            path_hint=checkpoint_dir)
        self._shm_handler = SharedMemoryHandler(local_rank, job_name)
        self._saver: Optional[AsyncCheckpointSaver] = None
        self._event_queue: Optional[SharedQueue] = None
        self._latest_step = -1
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_error: Optional[BaseException] = None
        # staging overlap (ISSUE 15): a save no longer waits out the PRIOR
        # drain on the training thread — the new drain thread joins its
        # predecessor first (the predecessor Thread object is passed as an
        # ARG, so the ordering is plain happens-before, no shared flag).
        # `_drain_lock` guards the cross-thread mutables below; the chain
        # is BOUNDED at depth 2 (one running + one queued): each queued
        # drain holds a full device snapshot, so deeper chains would
        # accumulate HBM copies until OOM — at the bound the save falls
        # back to the old blocking wait.
        self._drain_lock = threading.Lock()
        self._drain_pending = 0
        # seconds the drain chain spent waiting on predecessors, credited
        # to the ledger by the MAIN thread at the next save boundary
        # (ledger credits land at fusion boundaries, CLAUDE.md)
        self._chain_wait_s = 0.0
        self._snapshot_fn = None  # jitted tree-copy, cached across saves
        if standalone is None:
            # a worker launched by an elastic agent must attach to the agent's
            # saver queue, never host its own (socket-name collision)
            from ..common.constants import NodeEnv

            attached = os.getenv(NodeEnv.MASTER_ADDR) is not None
            standalone = (not attached
                          and AsyncCheckpointSaver.get_ckpt_saver() is None)
        if standalone:
            # host the async saver in-process (no separate agent)
            self._saver = AsyncCheckpointSaver.start_async_saving_ckpt(
                job_name, local_shard_num=local_shard_num,
                node_rank=node_rank, storage=self.storage)
            self._saver.register_path(checkpoint_dir)
            self._event_queue = self._saver._event_queue
        else:
            self._event_queue = SharedQueue(f"{job_name}-ckpt-events",
                                            master=False)
        # client side of the saver's per-segment lock: staging must not
        # overwrite the payload while the saver streams it to disk
        self._shm_lock = SharedLock(shm_lock_name(job_name, local_rank),
                                    master=False)
        # verified tiered restore (checkpoint/integrity.py): optional
        # callable that pulls this rank's segment from a peer replica
        # holder into local shm (agent wires CkptReplicaManager.restore);
        # tried when the local segment fails verification
        self.replica_fetch = replica_fetch
        # invoked with the restored step after a DEGRADED restore (a tier
        # other than local shm served it) — the agent hangs re-replication
        # here so the next failure doesn't pay the slow path again
        self.on_degraded_restore = None
        # report of the last load(): which tier/generation served, every
        # fallback taken and why, whether self-heal re-staged shm
        self.last_restore: Dict = {}
        # adaptive-policy restore hint (brain/policy.py): "" keeps the
        # default verified chain shm → replica → storage; "replica" skips
        # the local shm fast path (policy judged it likely stale/dead);
        # "storage" forces the authoritative read.  Every tier stays
        # digest-verified — the hint only SKIPS hot tiers, it never adds
        # an unverified path.
        self.preferred_tier = ""

    def _stage_locked(self, state: Any, step: int, extra: Dict):
        acquired = False
        try:
            acquired = self._shm_lock.acquire(
                timeout=CheckpointConstant.SAVE_TIMEOUT)
        except Exception:  # noqa: BLE001 — saver gone: stage unlocked
            acquired = False
        try:
            self._shm_handler.save_state_dict(state, step, extra)
        finally:
            if acquired:
                try:
                    self._shm_lock.release()
                except Exception:  # noqa: BLE001
                    pass

    # ------------------------------------------------------------------ save

    def _device_snapshot(self, state: Any) -> Any:
        """Point-in-time copy of a pytree: device leaves get a fresh device
        buffer at HBM bandwidth, host leaves a numpy copy.

        The copy decouples the checkpoint from buffer donation in the train
        step: the snapshot's buffers are never donated, so the drain thread
        can read them while training rolls forward.  The whole tree is copied
        in ONE jitted call — per-leaf `jnp.copy` pays the fixed dispatch
        cost once per leaf; a single dispatch pays it once.
        """
        import jax
        import jax.numpy as jnp

        def _wire(x):
            # bf16 wire staging: narrow f32 floats on DEVICE so the D2H
            # staging already moves half the bytes (engine docstring)
            if self.wire_dtype == "bf16" and \
                    getattr(x, "dtype", None) == jnp.float32:
                return x.astype(jnp.bfloat16)
            return jnp.copy(x)

        leaves = jax.tree.leaves(state)
        if not any(hasattr(x, "addressable_shards") for x in leaves):
            if self.wire_dtype == "bf16":
                return jax.tree.map(
                    lambda x: np.asarray(x).astype(jnp.bfloat16)
                    if np.asarray(x).dtype == np.float32
                    else np.copy(np.asarray(x)), state)
            return jax.tree.map(lambda x: np.copy(np.asarray(x)), state)
        if self._snapshot_fn is None:
            self._snapshot_fn = jax.jit(
                lambda t: jax.tree.map(_wire, t))
        snap = self._snapshot_fn(state)
        # await the smallest leaf: surfaces an allocation failure HERE (where
        # the caller can fall back) instead of asynchronously in the drain
        # thread; costs one scalar-sized readback
        small = min(jax.tree.leaves(snap), key=lambda x: x.size)
        np.asarray(small)
        return snap

    def _wait_drain(self, timeout: Optional[float] = None):
        t = self._drain_thread
        if t is not None and t.is_alive():
            t.join(timeout)
            if t.is_alive():
                raise TimeoutError(
                    f"checkpoint staging of step {self._latest_step} still "
                    f"in flight after {timeout}s")
        with self._drain_lock:
            err, self._drain_error = self._drain_error, None
        if err is not None:
            raise err

    def _take_drain_error(self) -> Optional[BaseException]:
        with self._drain_lock:
            err, self._drain_error = self._drain_error, None
        return err

    def _drain(self, prev: Optional[threading.Thread], snapshot: Any,
               step: int, extra: Dict, storage_path: Optional[str],
               trace: Optional[Dict] = None):
        """Background: wait out the predecessor staging (the segment must
        stay whole — one writer at a time), then snapshot → shm (batched
        async D2H), then hand off.  `trace` is the saving thread's span
        context: `ckpt:drain` hangs under its `ckpt:save`."""
        try:
            if prev is not None and prev.is_alive():
                t0 = time.monotonic()
                prev.join()
                waited = time.monotonic() - t0
                with self._drain_lock:
                    self._chain_wait_s += waited
            with tspans.extract(trace), \
                    tspans.span("ckpt:drain", {"step": step}):
                self._stage_locked(snapshot, step, extra)
            if storage_path is not None:
                self._event_queue.put(CheckpointEvent.save(step,
                                                           storage_path))
        except BaseException as e:  # noqa: BLE001 — surfaced on next save
            logger.exception("checkpoint drain of step %d failed", step)
            with self._drain_lock:
                self._drain_error = e
        finally:
            with self._drain_lock:
                self._drain_pending -= 1

    def _start_save(self, step: int, state: Any, extra_meta: Optional[Dict],
                    path: Optional[str],
                    storage_path: Optional[str]) -> float:
        with tspans.span("ckpt:save", {"step": step}):
            t0 = time.monotonic()
            # staging overlap: a prior drain still in flight no longer
            # blocks here — the new drain thread chains behind it.  Only
            # at the chain bound (one running + one queued snapshot in
            # HBM) does this save pay the old blocking wait.
            with self._drain_lock:
                pending = self._drain_pending
                chain_wait, self._chain_wait_s = self._chain_wait_s, 0.0
            if pending >= 2:
                self._wait_drain()  # bound the snapshot chain (HBM)
            err = self._take_drain_error()
            if err is not None:
                raise err
            # ledger split: time spent waiting out PRIOR stagings (here
            # or accumulated inside the drain chain) is persist stall;
            # everything after is this save's own stage cost
            t_persist = time.monotonic() - t0
            get_ledger().account("ckpt_persist", t_persist + chain_wait)
            extra = dict(extra_meta or {})
            # tag the segment with its checkpoint dir so a later process can't
            # restore a stale segment left over from an unrelated job run
            extra.setdefault("_ckpt_dir", path or self.checkpoint_dir)
            try:
                # a save's device-side copy is where a job that trains
                # fits and a job that saves does not
                with tspans.span("ckpt:snapshot") as snap_rec:
                    tmemory.note(snap_rec, "hbm_before")
                    snapshot = self._device_snapshot(state)
                    tmemory.note(snap_rec, "hbm_after")
            except Exception as e:  # noqa: BLE001
                # state too big to double-buffer in HBM (e.g. GPT-2 xl +
                # AdamW on a 16GB chip): fall back to synchronous staging
                # straight from the live buffers — slower blocking save,
                # but correct
                from ..common.util import is_oom_error

                if not is_oom_error(e):
                    raise
                logger.warning("device snapshot does not fit HBM; staging "
                               "synchronously (%s)", type(e).__name__)
                # the sync path writes the segment from THIS thread: any
                # chained drain must land first (one writer at a time)
                self._wait_drain()
                self._stage_locked(state, step, extra)
                self._latest_step = step
                if storage_path is not None:
                    self._event_queue.put(CheckpointEvent.save(step,
                                                               storage_path))
                blocked = time.monotonic() - t0
                get_ledger().account("ckpt_stage",
                                     max(0.0, blocked - t_persist))
                return blocked
            self._latest_step = step
            prev = self._drain_thread
            with self._drain_lock:
                self._drain_pending += 1
            self._drain_thread = threading.Thread(
                target=self._drain, args=(prev, snapshot, step, extra,
                                          storage_path,
                                          tspans.current_trace()),
                daemon=True, name="dwt-ckpt-drain")
            self._drain_thread.start()
            blocked = time.monotonic() - t0
            get_ledger().account("ckpt_stage", max(0.0, blocked - t_persist))
            self._record_blocking_metric(blocked)
            return blocked

    def _report_ckpt_health(self, tier: str, reason: str):
        """Checkpoint-health event: local metric + master node event.

        The master's event stream is where operators see corruption —
        a quarantined generation on one node of a large job would
        otherwise only exist in that node's logs."""
        try:
            from ..master.metrics import get_registry

            get_registry().inc(
                "dwt_ckpt_integrity_events",
                labels={"job": self.job_name, "tier": tier},
                help="checkpoint verification failures/degraded restores")
            from ..trainer import elastic as _elastic

            ctx = getattr(_elastic, "_context", None)
            if ctx is not None and ctx.mc is not None:
                ctx.mc.report_node_event(
                    "ckpt-health", f"{tier}: {reason}", level="warning")
        except Exception:  # noqa: BLE001 — health reporting must never
            pass           # break a restore

    def _record_blocking_metric(self, blocked: float):
        """Local registry + forward to the master (whose /metrics endpoint
        is the one operators scrape — the worker's registry is per-process
        and unexported)."""
        try:
            from ..master.metrics import get_registry

            get_registry().observe("dwt_ckpt_seconds", blocked,
                                   {"job": self.job_name,
                                    "kind": "blocking"},
                                   help="checkpoint stage timings")
            from ..trainer import elastic as _elastic

            ctx = getattr(_elastic, "_context", None)
            if ctx is not None and ctx.mc is not None:
                ctx.mc.report_custom_metric(
                    {"dwt_ckpt_blocking_seconds": blocked})
        except Exception:  # noqa: BLE001 — metrics must never break saves
            pass

    def save_to_memory(self, step: int, state: Any,
                       extra_meta: Optional[Dict] = None,
                       path: Optional[str] = None) -> float:
        """Snapshot on device + async stage into shm; returns blocking s."""
        return self._start_save(step, state, extra_meta, path, None)

    def save_to_storage(self, step: int, state: Any,
                        path: Optional[str] = None,
                        extra_meta: Optional[Dict] = None) -> float:
        """Snapshot + async stage + hand off to the async saver."""
        path = path or self.checkpoint_dir
        if self._saver is not None:
            self._saver.register_path(path)
        return self._start_save(step, state, extra_meta, path, path)

    def wait_staging(self, timeout: Optional[float] = None):
        """Block until the in-flight snapshot→shm staging (if any) lands."""
        self._wait_drain(timeout)

    def wait_saving_latest(self, timeout: float = 600.0) -> bool:
        """Block until the latest staged step is committed (for tests/exit).

        Keeps the bool contract: staging timeouts/errors → False, not raise.
        """
        deadline = time.monotonic() + timeout
        try:
            self._wait_drain(timeout)
        except (TimeoutError, Exception):  # noqa: BLE001
            logger.warning("staging did not complete within %ss", timeout,
                           exc_info=True)
            return False
        while time.monotonic() < deadline:
            if read_last_step(self.checkpoint_dir,
                              self.storage) >= self._latest_step:
                return True
            time.sleep(0.1)
        return False

    # ------------------------------------------------------------------ load

    def load(self, path: Optional[str] = None,
             step: Optional[int] = None) -> Optional[Dict[str, np.ndarray]]:
        """Verified tiered restore → flat {name: np.ndarray}.

        Walks shm segment → peer replica fetch → storage generations
        (newest committed first), digest-verifying each tier BEFORE any
        bytes are assembled or reach ``device_put`` — a flipped byte, torn
        persist, or truncated shard can never be silently restored.  A
        storage generation that fails verification is QUARANTINED to the
        ``.quarantine/`` sidecar (evidence, not deletion) and the walk
        continues to the next-older commit.  After a degraded restore
        (any tier but local shm) the recovered state is re-staged into
        shm (self-heal) so the next failure takes the fast path again.
        ``self.last_restore`` reports which tier/generation served and
        every fallback taken.  Names containing ``#shardN`` are assembled
        into full global arrays.

        Telemetry: the walk opens a ``ckpt:restore`` span with one child
        per tier attempted, and each tier's wall time is credited to its
        own ledger state (restore_shm / restore_replica / restore_storage)
        — a degraded restore shows exactly where the time went.
        """
        with tspans.span("ckpt:restore",
                         {"step": -1 if step is None else step}) as rec:
            result = self._load_tiered(path, step)
            rec["attrs"]["tier"] = self.last_restore.get("tier", "none")
            rec["attrs"]["fallbacks"] = len(
                self.last_restore.get("fallbacks", []))
            return result

    def _load_tiered(self, path: Optional[str],
                     step: Optional[int]) -> Optional[Dict[str, np.ndarray]]:
        led = get_ledger()
        self._wait_drain()  # an in-flight staging must land before reading
        path = path or self.checkpoint_dir
        report: Dict = {"tier": "none", "step": -1, "fallbacks": [],
                        "healed": False}
        preferred = self.preferred_tier
        if preferred:
            report["preferred"] = preferred
        self.last_restore = report

        stale_shm = None  # verified shm OLDER than the storage tracker:
        # kept as a candidate in case the newer storage gens are corrupt
        flat, shm_step, reason = None, -1, None
        if preferred not in ("replica", "storage"):
            with tspans.span("ckpt:restore:shm"), \
                    led.window("restore_shm"):
                flat, shm_step, reason = self._load_verified_shm(path, step)
        if flat is not None:
            if step is not None or shm_step >= read_last_step(
                    path, self.storage):
                report.update(tier="shm", step=shm_step)
                return flat
            stale_shm = (shm_step, flat)
            reason = "stale"
        if reason:
            report["fallbacks"].append({"tier": "shm", "reason": reason})
            if _is_corruption(reason):
                self._report_ckpt_health("shm", reason)

        # replica tier: pull my segment from a peer holder into shm
        # (replica.py digest-checks the blob before it touches the
        # segment), then re-verify end to end
        if stale_shm is None and self.replica_fetch is not None and \
                preferred != "storage":
            with tspans.span("ckpt:restore:replica"), \
                    led.window("restore_replica"):
                try:
                    fetched = self.replica_fetch()
                except Exception:  # noqa: BLE001 — replica is best-effort
                    logger.exception("replica fetch failed")
                    fetched = None
                if fetched is not None:
                    flat, shm_step, reason = self._load_verified_shm(
                        path, step)
                else:
                    flat, shm_step, reason = None, -1, None
            if fetched is not None:
                if flat is not None and (
                        step is not None or shm_step >= read_last_step(
                            path, self.storage)):
                    report.update(tier="replica", step=shm_step)
                    self._finish_degraded(flat, shm_step, path, report,
                                          restage=False)
                    return flat
                if flat is not None:
                    stale_shm = (shm_step, flat)
                    reason = "stale"
                if reason:
                    report["fallbacks"].append({"tier": "replica",
                                                "reason": reason})
                    if _is_corruption(reason):
                        self._report_ckpt_health("replica", reason)

        with tspans.span("ckpt:restore:storage"), \
                led.window("restore_storage"):
            flat = self.load_from_storage(path, step, _report=report)
        if flat is not None:
            if stale_shm is not None and stale_shm[0] > report["step"]:
                # every storage gen newer than the stale shm was corrupt:
                # the verified shm staging is now the best copy there is
                report.update(tier="shm", step=stale_shm[0])
                return stale_shm[1]
            # multi-process world (local shm legitimately holds only this
            # process's shards): restaging the ASSEMBLED global state
            # would blow local shm up to full-model size — skip the heal,
            # the next save re-stages the right shards
            restage = not any(f.get("reason") == "partial-local-coverage"
                              for f in report["fallbacks"])
            self._finish_degraded(flat, report["step"], path, report,
                                  restage=restage)
            return flat
        if stale_shm is not None:
            report.update(tier="shm", step=stale_shm[0])
            return stale_shm[1]
        return None

    def _load_verified_shm(self, path: str, step: Optional[int]
                           ) -> tuple:
        """(flat, step, reason) — flat None unless the local segment is
        present, tagged for `path`, digest-verified, step-matched, and
        fully covering.  `reason` explains a None (None reason = simply
        no segment staged)."""
        state = self._shm_handler.segment_state()
        if state in ("absent", "empty"):
            return None, -1, None
        if state == "torn":
            return None, -1, "torn-header"
        loaded = self._shm_handler.load_state_dict()
        if loaded is None:  # raced a concurrent invalidation
            return None, -1, None
        shm_step, flat, metas, extra = loaded
        if extra.get("_ckpt_dir") != path:
            # no tag (legacy/foreign segment) must NOT pass the guard
            return None, -1, "foreign-segment"
        if step is not None and shm_step != step:
            return None, -1, "step-mismatch"
        header = self._shm_handler.load_header() or {}
        ok, why = verify_segment_entries(metas, flat,
                                         header.get("algo", ""))
        if not ok:
            logger.error("shm segment for step %d fails verification "
                         "(%s) — falling back", shm_step, why)
            return None, -1, why
        entries = [dict(m.to_dict(), array=flat[m.name]) for m in metas]
        if not self._full_coverage(entries):
            # multi-process world: local shm holds only THIS process's
            # shards — assembling would fill peer shards with garbage
            # (and each process would restore different values).
            # Storage has every rank's shards.
            return None, -1, "partial-local-coverage"
        return self._assemble(entries), shm_step, None

    def _finish_degraded(self, flat: Dict, step: int, path: str,
                         report: Dict, restage: bool):
        """Self-heal after a degraded restore: re-stage the recovered
        state into shm (so the NEXT failure reads the fast tier) and let
        the wiring re-replicate it to peers."""
        if restage:
            try:
                self._stage_locked(flat, step, {"_ckpt_dir": path})
                ok, why = self._shm_handler.verify()
                report["healed"] = bool(ok)
                if not ok:
                    logger.warning("self-heal restage failed "
                                   "verification: %s", why)
            except Exception:  # noqa: BLE001 — healing must not break restore
                logger.exception("self-heal restage failed")
        else:
            report["healed"] = True  # replica fetch already filled shm
        self._latest_step = max(self._latest_step, step)
        if self.on_degraded_restore is not None:
            try:
                self.on_degraded_restore(step)
            except Exception:  # noqa: BLE001
                logger.exception("on_degraded_restore hook failed")
        logger.warning(
            "DEGRADED restore: tier=%s step=%d fallbacks=%s healed=%s",
            report["tier"], step, report["fallbacks"], report["healed"])
        self._report_ckpt_health(
            "degraded-restore",
            f"tier={report['tier']} step={step} "
            f"fallbacks={len(report['fallbacks'])}")

    @staticmethod
    def _full_coverage(entries) -> bool:
        """True iff every sharded tensor's shards tile its global shape."""
        import math

        vol: Dict[str, int] = {}
        glob: Dict[str, tuple] = {}
        for e in entries:
            name = e["name"]
            base = name.split("#shard")[0]
            if "#shard" not in name:
                continue  # whole tensor present
            glob[base] = tuple(e["global_shape"])
            v = 1
            for s, t in e["index"]:
                v *= max(0, t - s)
            vol[base] = vol.get(base, 0) + v
        return all(vol.get(b, 0) >= math.prod(gs) for b, gs in glob.items())

    def load_from_storage(self, path: Optional[str] = None,
                          step: Optional[int] = None,
                          _report: Optional[Dict] = None
                          ) -> Optional[Dict[str, np.ndarray]]:
        """Verified walk over committed generations, newest first.

        Explicit `step`: that generation only — a verification failure
        quarantines it and returns None (the caller asked for THOSE
        bytes; substituting another step silently would be worse than
        failing).  `step=None`: newest-first over every committed
        generation, quarantining failures and falling back until one
        verifies.  `_report` (engine-internal) collects tier/fallbacks.
        """
        path = path or self.checkpoint_dir
        report = _report if _report is not None else {
            "tier": "none", "step": -1, "fallbacks": [], "healed": False}
        if _report is None:
            self.last_restore = report
        if step is not None:
            candidates = [step]
        else:
            tracker = read_last_step(path, self.storage)
            candidates = sorted(
                set(self.committed_steps(path))
                | ({tracker} if tracker >= 0 else set()),
                reverse=True)
        for s in candidates:
            flat, failure = self._read_verified_step(path, s)
            if flat is not None:
                report.update(tier="storage", step=s)
                if step is None and s != candidates[0]:
                    logger.warning(
                        "restored OLDER generation %d (newest committed "
                        "was %d) — newer generations failed verification",
                        s, candidates[0])
                if step is None and report["fallbacks"] and \
                        read_last_step(path, self.storage) > s:
                    # the tracker's target was just quarantined: repoint
                    # it at the generation that actually verified, so
                    # later loads (and freshness comparisons against the
                    # healed shm staging) converge instead of re-walking
                    self.storage.write(str(s), os.path.join(
                        path, CheckpointConstant.TRACKER_FILE))
                return flat
            if failure is None:
                continue  # nothing (or an in-progress persist) there
            # verification failed: quarantine the evidence, walk on
            qdir = quarantine_step(self.storage, path, s, failure)
            report["fallbacks"].append(
                {"tier": "storage", "step": s, "reason": failure,
                 "quarantined": qdir})
            self._report_ckpt_health("storage", f"step {s}: {failure}")
        return None

    def _read_verified_step(self, path: str, step: int) -> tuple:
        """(flat, failure_reason): digest-verified read of one generation.

        (None, None) = generation absent / not yet committed (benign);
        (None, reason) = bytes present but fail the trust boundary.
        """
        sdir = step_dir(path, step)
        manifest = read_manifest(self.storage, sdir)
        if manifest is None:
            if not self.storage.exists(sdir):
                if read_last_step(path, self.storage) == step:
                    # the tracker names a generation that no longer
                    # exists at all — data loss, not an in-flight save
                    return None, "missing-generation"
                return None, None
            marker = os.path.join(sdir, CheckpointConstant.COMMIT_MARKER)
            tracker_step = read_last_step(path, self.storage)
            if self.storage.exists(marker) or tracker_step == step:
                # committed (or tracker-published) without a manifest:
                # a torn/ripped-out manifest, or a pre-trust-boundary
                # writer — unverifiable either way
                return None, "missing-manifest"
            return None, None  # persist still in flight — not ours to touch
        if int(manifest.get("step", -1)) != step:
            return None, "manifest-step-mismatch"
        algo = manifest.get("algo", "")
        entries = []
        for rank_s, entry in manifest["ranks"].items():
            rank = int(rank_s)
            meta_raw = self.storage.read(
                os.path.join(sdir, f"meta_rank{rank}.json"))
            raw = self.storage.read(
                os.path.join(sdir, f"shards_rank{rank}.bin"))
            if meta_raw is None or raw is None:
                return None, "missing-shard-file"
            meta_raw = (meta_raw.encode() if isinstance(meta_raw, str)
                        else bytes(meta_raw))
            raw = bytes(raw)
            try:
                meta = verify_meta_bytes(meta_raw, entry, algo, rank)
                verify_rank_bytes(raw, entry, algo, rank)
            except VerifyFailure as e:
                logger.error("step %d rank %d fails verification: %s",
                             step, rank, e)
                return None, e.reason
            for t in meta["tensors"]:
                arr = np.frombuffer(
                    raw, dtype=_np_dtype(t["dtype"]),
                    count=int(np.prod(t["shape"])) if t["shape"] else 1,
                    offset=t["file_offset"]).reshape(t["shape"])
                entries.append(dict(t, array=arr))
        if not self._full_coverage(entries):
            # partial step (a rank's shards never landed): assembling
            # would fill the holes with uninitialized memory
            logger.error("step %d on storage is missing shards — refusing "
                         "to assemble a partial checkpoint", step)
            return None, "partial-coverage"
        return self._assemble(entries), None

    @staticmethod
    def _assemble(entries) -> Dict[str, np.ndarray]:
        """Merge `name#shardN` pieces into global arrays by their indices."""
        out: Dict[str, np.ndarray] = {}
        partial: Dict[str, np.ndarray] = {}
        for e in entries:
            name = e["name"]
            base = name.split("#shard")[0]
            if "#shard" not in name:
                out[base] = e["array"]
                continue
            if base not in partial:
                partial[base] = np.empty(e["global_shape"],
                                         dtype=e["array"].dtype)
            slices = tuple(slice(s, t) for s, t in e["index"])
            partial[base][slices] = e["array"]
        out.update(partial)
        return out

    def latest_step(self) -> int:
        return max(self._latest_step,
                   read_last_step(self.checkpoint_dir, self.storage))

    def committed_steps(self, path: Optional[str] = None) -> list:
        """Sorted steps on storage bearing the commit marker.

        Loss-spike rollback needs to pick a step BEFORE the spike, not just
        the tracker's latest — the latest commit can postdate spike onset.
        The marker (written by `commit_checkpoint` only after EVERY shard's
        done-file landed) is required: a non-empty done-dir alone can be a
        partial set whose assembly would be silent garbage.
        """
        from ..common.constants import CheckpointConstant

        path = path or self.checkpoint_dir
        prefix = CheckpointConstant.CKPT_NAME_PREFIX
        steps = []
        for name in self.storage.listdir(path):
            if not name.startswith(prefix):
                continue
            try:
                step = int(name[len(prefix):])
            except ValueError:
                continue
            marker = os.path.join(path, name,
                                  CheckpointConstant.COMMIT_MARKER)
            if self.storage.exists(marker):
                steps.append(step)
        return sorted(steps)

    def demote_steps_after(self, step: int,
                           path: Optional[str] = None) -> None:
        """Point the tracker at `step` and delete NEWER step dirs.

        Rollback durability: once a spike rollback resumes from `step`,
        the post-spike commits are a poisoned lineage — if they survived,
        any later crash (before the rolled-back run commits fresh) would
        resume from them and silently undo the rollback.
        """
        from ..common.constants import CheckpointConstant

        path = path or self.checkpoint_dir
        for s in self.committed_steps(path):
            if s > step:
                logger.warning("rollback: discarding post-spike "
                               "checkpoint step %d", s)
                self.storage.safe_remove(step_dir(path, s))
        self.storage.write(str(step), os.path.join(
            path, CheckpointConstant.TRACKER_FILE))
        self._latest_step = min(self._latest_step, step)
        # the shm staging may still hold the newest (post-spike) state —
        # a later plain load() would prefer it over the demoted tracker
        header = self._shm_handler.load_header()
        if header and header.get("step", 0) > step:
            self._shm_handler.mark_empty()

    def close(self):
        try:
            self._wait_drain(timeout=600)
        except BaseException:  # noqa: BLE001 — teardown must proceed
            logger.exception("pending checkpoint drain failed during close")
        self._shm_handler.close()
        self._shm_lock.close()
        if self._event_queue is not None and self._saver is None:
            self._event_queue.close()


def restore_pytree(template: Any, flat: Dict[str, np.ndarray]) -> Any:
    """Rebuild a pytree (matching `template`'s structure/shardings) from the
    flat name→array dict returned by `CheckpointEngine.load`.

    Leaves of `template` that are `jax.Array`s (or ShapeDtypeStruct with a
    .sharding) get `jax.device_put(value, sharding)` so each process only
    materializes its addressable shards.
    """
    import jax

    flat_template = flatten_state_dict(template)
    leaves_by_name = {}
    put_names, put_values, put_shardings = [], [], []
    cast_after: Dict[str, Any] = {}
    for name, leaf in flat_template.items():
        if name not in flat:
            raise KeyError(f"checkpoint missing tensor {name!r}")
        value = flat[name]
        sharding = getattr(leaf, "sharding", None)
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and value.dtype != dtype:
            if (sharding is not None
                    and getattr(sharding, "memory_kind", None)
                    in (None, "device")
                    and value.dtype.itemsize < np.dtype(dtype).itemsize):
                # (pinned_host targets upcast on the HOST instead — an
                # astype on a host-kind array would need host compute)
                # NARROWER on the wire than in the template (bf16 wire
                # staging): ship the stored bytes and upcast ON DEVICE —
                # an eager host astype would double the H2D bytes, the
                # very thing wire staging halves (restore is
                # transfer-bound over slow host links)
                cast_after[name] = dtype
            else:
                value = value.astype(dtype)
        if sharding is not None:
            put_names.append(name)
            put_values.append(value)
            put_shardings.append(sharding)
        else:
            leaves_by_name[name] = value
    # ONE batched device_put for all leaves: per-leaf puts serialize a
    # host round-trip each; the batched form overlaps the transfers.
    #
    # DEAD END, do not retry without a measurement: packing single-device
    # leaves into one host buffer per dtype and splitting on device.
    # Eager per-leaf slices each compile a tiny executable (one per
    # distinct shape); a fused jit splitter compiles once, but that
    # compile lands inside the cold-restore window it was meant to
    # shorten.  The simple batched path stays.
    placed_list = list(jax.device_put(put_values, put_shardings))
    for name, placed in zip(put_names, placed_list):
        if name in cast_after:
            placed = placed.astype(cast_after[name])
        leaves_by_name[name] = placed
    # rebuild in template order
    treedef = jax.tree_util.tree_structure(template)
    ordered = [leaves_by_name[name] for name in flat_template]
    return jax.tree_util.tree_unflatten(treedef, ordered)
