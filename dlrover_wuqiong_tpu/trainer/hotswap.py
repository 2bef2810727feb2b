"""Worker-side hot-swap participant: survivor phase work + acks.

Parity axis: the reference's worker-side recovery
(`dlrover/python/elastic_agent/torch/training.py` restart paths) tears
the whole process group down and rebuilds it through a fresh rendezvous
— every survivor pays a restart even though only one rank died.  The
TPU redesign keeps the survivors ALIVE: they pause at a fusion
boundary, absorb the dead rank's shards from ring replicas, and resume
on a pre-compiled degraded-mesh executable — no teardown, no storage
round trip, no cold compile.

Counterpart of `master/mesh_transition.py` — the master owns the
journaled phase ladder, a survivor owns the work each phase names:

- **propose**: nothing to compute — being asked at all means the caller
  is parked at a FUSION BOUNDARY (poll() only ever runs there), so the
  ack simply confirms the pause.
- **fence**: adopt the bumped fencing epoch — after this ack the
  survivor will not dispatch into the old world again.
- **hydrate**: pull the dead rank's staged shards from its ring-replica
  holders (checkpoint/replica.py fetch_peer — digest-verified BEFORE the
  bytes are decoded; an unverifiable ring is a nack, never a silent
  skip).  Wall time credits the ledger's ``restore_replica`` state.
- **cutover**: hand the hydrated shards to the caller's re-shard hook
  (the degraded-mesh executable is pre-compiled via the warm pool —
  CLAUDE.md: a mesh change is a new compile-cache key, so cutover must
  never pay a cold compile mid-incident).  Wall time credits ``rework``
  — the swap re-derives state that a restart would have replayed.
- **release**: master-side only (world rewrite); the survivor polls
  until the transition leaves the ladder, then resumes under the new
  world/round.

The cutover hook owns device placement of the hydrated bytes
(checkpoint/engine.py restore_pytree is the sanctioned route).

Acks ride ``report_mesh_transition_phase`` (CRITICAL + idem — the
master journals each ack before answering); the state poll rides the
POLLING class (fail fast — a dead master degrades to "keep training on
the old world", and the master's own transition timeout aborts the
ladder if survivors stay unreachable).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from ..common import messages as msg
from ..common.log import get_logger

logger = get_logger("hotswap")


class HotSwapParticipant:
    """Drives one survivor through the transition ladder.

    Call ``poll()`` at fusion boundaries only.  Returns the phase that
    was acknowledged this call (or ``"done"``/``"aborted"`` once the
    tracked transition leaves the ladder), ``None`` when idle.
    """

    def __init__(self, mc, node_id: int,
                 replica_manager=None,
                 hydrate_cb: Optional[Callable] = None,
                 cutover_cb: Optional[Callable] = None,
                 fence_cb: Optional[Callable] = None,
                 ledger=None):
        self.mc = mc
        self.node_id = int(node_id)
        self.replica = replica_manager
        self.hydrate_cb = hydrate_cb
        self.cutover_cb = cutover_cb
        self.fence_cb = fence_cb
        self.ledger = ledger
        self.fence_epoch = 0
        #: (step, flat_state, extra) of the dead rank after hydrate
        self.hydrated: Optional[Tuple[int, Dict, Dict]] = None
        self._acked: set = set()       # (tid, phase) pairs already acked
        self._tracking = 0             # tid we are mid-ladder on

    @property
    def mid_ladder(self) -> bool:
        """True while a tracked transition is still on the ladder — the
        caller should stay parked at its fusion boundary and keep
        polling until this clears."""
        return bool(self._tracking)

    # ----------------------------------------------------------------- poll

    def poll(self) -> Optional[str]:
        try:
            st = self.mc.get_mesh_transition()
        except Exception:  # noqa: BLE001 — POLLING class: next boundary
            # retries; the master's timeout is the ladder's backstop
            return None
        tid = int(getattr(st, "transition_id", 0) or 0)
        phase = getattr(st, "phase", "") or ""
        if self._tracking and (tid != self._tracking
                               or phase in ("done", "aborted")):
            # the transition we were working left the ladder
            finished = phase if tid == self._tracking else "done"
            logger.info("hot-swap transition %d finished: %s",
                        self._tracking, finished)
            self._tracking = 0
            return finished
        if tid == 0 or phase in ("done", "aborted", "release"):
            return None
        if self.node_id not in (st.survivors or []):
            return None
        if (tid, phase) in self._acked:
            return None
        self._tracking = tid
        ok, detail = True, ""
        if phase == "fence":
            self.fence_epoch = int(st.fence_epoch)
            if self.fence_cb is not None:
                try:
                    self.fence_cb(self.fence_epoch)
                except Exception as e:  # noqa: BLE001 — a fence hook
                    # failure must nack, not crash the boundary
                    ok, detail = False, f"fence hook failed: {e}"
        elif phase == "hydrate":
            ok, detail = self._hydrate(st)
        elif phase == "cutover":
            ok, detail = self._cutover(st)
        elif phase == "propose":
            detail = "paused at fusion boundary"
        try:
            resp = self.mc.report_mesh_transition_phase(
                tid, phase, ok=ok, detail=detail)
        except Exception:  # noqa: BLE001 — the idem key makes a later
            # retry of this ack at-most-once; drop and re-poll
            return None
        if getattr(resp, "success", True):
            self._acked.add((tid, phase))
        logger.info("hot-swap %d: acked phase %s ok=%s %s", tid, phase,
                    ok, detail)
        return phase

    # ---------------------------------------------------------------- phases

    def _hydrate(self, st: msg.MeshTransitionState) -> Tuple[bool, str]:
        from contextlib import nullcontext

        from ..checkpoint.shm_handler import blob_state_dict

        win = (self.ledger.window("restore_replica")
               if self.ledger is not None else nullcontext())
        with win:
            if self.hydrate_cb is not None:
                try:
                    self.hydrated = self.hydrate_cb(st)
                except Exception as e:  # noqa: BLE001 — nack with cause
                    return False, f"hydrate hook failed: {e}"
                if self.hydrated is None:
                    return False, "hydrate hook returned nothing"
                return True, f"step {self.hydrated[0]}"
            if self.replica is None:
                return False, "no replica ring attached"
            fetched = self.replica.fetch_peer(int(st.dead_rank))
            if fetched is None:
                return False, (f"no verified replica of rank "
                               f"{st.dead_rank} reachable")
            step, blob = fetched
            parsed = blob_state_dict(blob)  # blob already digest-verified
            if parsed is None:
                return False, "verified blob failed to decode"
            pstep, flat, extra = parsed
            self.hydrated = (pstep, flat, extra)
            return True, f"step {step}"

    def _cutover(self, st: msg.MeshTransitionState) -> Tuple[bool, str]:
        from contextlib import nullcontext

        win = (self.ledger.window("rework")
               if self.ledger is not None else nullcontext())
        with win:
            if self.cutover_cb is None:
                # nothing to re-shard (caller only wanted the fence +
                # hydrate choreography) — confirm
                return True, "no cutover hook"
            try:
                out = self.cutover_cb(self.hydrated, st)
            except Exception as e:  # noqa: BLE001 — nack with cause
                return False, f"cutover failed: {e}"
            if out is False:
                return False, "cutover hook declined"
            return True, f"resharded onto {len(st.survivors)}-node mesh"
