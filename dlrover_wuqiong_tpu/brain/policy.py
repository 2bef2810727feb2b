"""Adaptive fault-tolerance policy engine: telemetry → Brain → knobs.

Parity axis: reference `dlrover/go/brain/pkg/optimizer` picks *resource*
plans from observed usage; this module is the fault-tolerance analogue
the reference never built — Chameleon (PAPERS.md) argues the protection
policy must be (re)selected from the MEASURED failure regime, and
PHOENIX shows the recovery route (hot tier vs cold storage) is itself a
policy decision.  The repo has every mechanism (tiered verified restore,
warm-pool re-mesh, fused-K boundaries, replica ring, journaled master)
and every sensor (goodput ledger, restore-tier latencies, journal
node-fail events); this closes the loop.

Four knobs per decision (common/messages.py PolicyDecision):

- **checkpoint cadence** — Young–Daly optimum ``sqrt(2·C·MTBF)`` where C
  is the per-checkpoint cost and MTBF comes from an exponentially
  decaying preemption-rate estimator over observed node-fail events.
- **fused-K** — dispatch-overhead amortization is rework exposure: a
  kill mid-window replays up to K-1 steps, so K steps down as MTBF does.
- **replica count** — the peer-replica ring only pays when node loss is
  likely inside a checkpoint window.
- **recovery route / preferred restore tier** — keep the warm pool hot
  (and prefer the replica tier) in a high-failure regime; cold re-mesh +
  storage restore is fine when failures are rare.

The engine is seeded offline from the ``chaos preempt-table``
goodput-vs-cadence curve (``policy/preempt_table.json``) which
calibrates step time and checkpoint cost, then adapts online.  All knob
math lives in registered brain algorithms (plugins.py) so the selection
is inspectable by name, like every other Brain decision.

Durability contract: the engine itself is deliberately STATELESS across
master restarts — every emitted decision is journaled by the master
(kind ``"policy"``) before becoming visible, so the decision log is
reconstructable from the journal alone; the rate estimator re-learns
from post-restart events (journal timestamps are not replayable onto a
monotonic clock).
"""

from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..common import messages as msg
from ..common.log import get_logger
from .plugins import get_algorithm

logger = get_logger("brain_policy")


# ---------------------------------------------------------------- estimator


class PreemptionRateEstimator:
    """Exponentially decaying event-rate estimator (events/sec → MTBF).

    An EWMA over point events: each recorded failure adds 1 to a weight
    that decays as ``exp(-dt/tau)``; the instantaneous rate is
    ``weight/tau``.  Runs on an injectable clock (``time.monotonic`` by
    default — durations, not timestamps) so tests drive it
    deterministically.
    """

    def __init__(self, tau_s: float = 60.0, clock=time.monotonic):
        self.tau_s = float(tau_s)
        self._clock = clock
        self._weight = 0.0
        self._last = self._clock()
        self.events = 0

    def _decay_to(self, now: float):
        dt = max(0.0, now - self._last)
        if dt:
            self._weight *= math.exp(-dt / self.tau_s)
            self._last = now

    def record(self, now: Optional[float] = None):
        now = self._clock() if now is None else now
        self._decay_to(now)
        self._weight += 1.0
        self.events += 1

    def rate_per_s(self, now: Optional[float] = None) -> float:
        now = self._clock() if now is None else now
        self._decay_to(now)
        return self._weight / self.tau_s

    def mtbf_s(self, now: Optional[float] = None) -> float:
        r = self.rate_per_s(now)
        return (1.0 / r) if r > 0 else float("inf")


# ------------------------------------------------------------------- prior


def load_prior(path: str) -> Dict[str, float]:
    """Calibrate (step_time_s, ckpt_cost_s) from a persisted preempt-table.

    The ``chaos preempt-table`` drill persists ``{"dt", "rows": [...]}``
    (policy/preempt_table.json).  Checkpoint cost falls out of the curve:
    with goodput loss modeled as ``1 - g ≈ base + C/(I·dt)``, two rows at
    intervals I1 < I2 give ``C = dt·(g2 - g1)/(1/I1 - 1/I2)``.
    An optional ``"config"`` dict carries PolicyConfig field overrides
    (regime thresholds are deployment-scale facts the curve alone cannot
    supply — a 30s drill and a week-long run need different tau).
    Returns {} when the file is missing/unusable — callers keep defaults.
    """
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    out: Dict = {}
    if isinstance(data.get("config"), dict):
        out["config"] = data["config"]
    dt = data.get("dt")
    if isinstance(dt, (int, float)) and dt > 0:
        out["step_time_s"] = float(dt)
    rows = data.get("rows")
    if not isinstance(rows, list):
        return out
    pts: List[Tuple[float, float]] = []
    for r in rows:
        if not isinstance(r, dict):
            continue
        interval = r.get("ckpt_interval", r.get("interval"))
        good = r.get("goodput", r.get("goodput_wall"))
        if isinstance(interval, (int, float)) and interval > 0 and \
                isinstance(good, (int, float)):
            pts.append((float(interval), float(good)))
    if len(pts) >= 2:
        pts.sort()
        (i1, g1), (i2, g2) = pts[0], pts[-1]
        step = out.get("step_time_s", 0.05)
        denom = (1.0 / i1) - (1.0 / i2)
        if denom > 0:
            c = step * (g2 - g1) / denom
            if 1e-4 <= c <= 60.0:
                out["ckpt_cost_s"] = c
    return out


# -------------------------------------------------------------------- config


@dataclass
class PolicyConfig:
    """Bounds + calibration for the knob algorithms.

    Defaults are sized for the chaos drills (dt≈0.05s steps): at a rare
    1/hr failure rate Young–Daly lands near the table's 200-step sweet
    spot; at a 10s MTBF burst it collapses to ~10-20 steps.
    """

    min_interval_steps: int = 5
    max_interval_steps: int = 500
    step_time_s: float = 0.05
    ckpt_cost_s: float = 0.1
    tau_s: float = 60.0
    # (K, MTBF floor seconds) descending: first floor the MTBF clears wins
    fused_ladder: Tuple[Tuple[int, float], ...] = ((4, 600.0), (2, 120.0))
    replica_mtbf_s: float = 120.0
    warm_mtbf_s: float = 600.0
    max_replicas: int = 2
    # relative cadence change below this is noise — don't thrash the knob
    hysteresis: float = 0.25
    prior_path: str = ""
    extra: Dict[str, float] = field(default_factory=dict)

    def algo_cfg(self, mtbf_s: float, replica_count: int) -> Dict:
        return {
            "mtbf_s": mtbf_s,
            "step_time_s": self.step_time_s,
            "ckpt_cost_s": self.ckpt_cost_s,
            "min_interval_steps": self.min_interval_steps,
            "max_interval_steps": self.max_interval_steps,
            "fused_ladder": self.fused_ladder,
            "replica_mtbf_s": self.replica_mtbf_s,
            "warm_mtbf_s": self.warm_mtbf_s,
            "max_replicas": self.max_replicas,
            "replica_count": replica_count,
        }


# -------------------------------------------------------------------- engine


class PolicyEngine:
    """Closed-loop decision maker the master ticks from its run loop.

    Inputs: failure events (``record_failure``, fed from the NodeFailure
    path the journal already records) and the job-level ledger summary
    (``observe_goodput``).  Output: ``maybe_decide`` returns a
    PolicyDecision only when the proposed knobs differ materially from
    the last emitted ones (hysteresis on cadence, exact on the discrete
    knobs) — the MASTER owns journaling + decision_id assignment.
    """

    def __init__(self, config: Optional[PolicyConfig] = None,
                 prior_path: str = "", clock=time.monotonic):
        self.cfg = config or PolicyConfig()
        path = prior_path or self.cfg.prior_path or \
            os.getenv("DWT_POLICY_PRIOR", "")
        if path:
            prior = load_prior(path)
            if prior:
                self.cfg.step_time_s = prior.get(
                    "step_time_s", self.cfg.step_time_s)
                self.cfg.ckpt_cost_s = prior.get(
                    "ckpt_cost_s", self.cfg.ckpt_cost_s)
                for k, v in (prior.get("config") or {}).items():
                    if k == "fused_ladder":
                        try:
                            self.cfg.fused_ladder = tuple(
                                (int(a), float(b)) for a, b in v)
                        except (TypeError, ValueError):
                            pass
                    elif k in ("step_time_s", "ckpt_cost_s"):
                        pass  # calibration comes from the curve, not here
                    elif hasattr(self.cfg, k) and isinstance(
                            getattr(self.cfg, k), (int, float)) and \
                            isinstance(v, (int, float)):
                        setattr(self.cfg, k,
                                type(getattr(self.cfg, k))(v))
                logger.info("policy prior loaded from %s: %s", path, prior)
            else:
                logger.warning("policy prior unusable: %s", path)
        self.estimator = PreemptionRateEstimator(self.cfg.tau_s, clock)
        self._clock = clock
        self._last_summary: Dict = {}
        self._last_emitted: Optional[msg.PolicyDecision] = None
        self._last_perf: Optional[Dict] = None
        self._perf_before: Optional[Dict] = None
        self._perf_after: Optional[Dict] = None

    # ------------------------------------------------------------- inputs

    def record_failure(self, now: Optional[float] = None):
        self.estimator.record(now)

    def observe_goodput(self, summary: Dict):
        """Latest job-level ledger aggregation (reason-text context; the
        knob math keys off the failure regime, not the fraction)."""
        if isinstance(summary, dict):
            self._last_summary = summary

    def observe_perf(self, summary: Dict):
        """Latest job-level perf aggregation (telemetry/perf.py via the
        master's PerfSummary) — the MEASURED before/after for decision-
        effect attribution (ROADMAP 5b): the summary observed before a
        decision is frozen as its "before" side, and subsequent
        observations become the "after", exposed by decision_effect().
        """
        if not isinstance(summary, dict):
            return
        self._last_perf = summary
        if self._last_emitted is not None and self._perf_before is not None:
            self._perf_after = summary

    def decision_effect(self) -> Dict:
        """Measured perf around the last emitted decision:
        ``{"decision_id", "before", "after"}`` (empty dict until both
        sides exist).  Pure read — attribution lives with the operator
        (tools/policy_report.py), not in the knob math."""
        if self._last_emitted is None or self._perf_before is None \
                or self._perf_after is None:
            return {}
        return {"decision_id": self._last_emitted.decision_id,
                "before": dict(self._perf_before),
                "after": dict(self._perf_after)}

    # ------------------------------------------------------------ decisions

    def propose(self, now: Optional[float] = None) -> msg.PolicyDecision:
        """Pure knob evaluation at `now` — no hysteresis, no side effects."""
        mtbf = self.estimator.mtbf_s(now)
        rate_hr = self.estimator.rate_per_s(now) * 3600.0
        replica = get_algorithm("optimize_job_replica_count")(
            [], [], self.cfg.algo_cfg(mtbf, 1))
        cfg = self.cfg.algo_cfg(mtbf, replica)
        interval = get_algorithm("optimize_job_ckpt_interval")([], [], cfg)
        fused = get_algorithm("optimize_job_fused_steps")([], [], cfg)
        route, tier = get_algorithm("optimize_job_recovery_route")(
            [], [], cfg)
        # cadence at a fusion-boundary multiple so the trainer never has
        # to shave the save hook off a mid-window step
        if fused > 1:
            interval = max(fused, (interval // fused) * fused)
        goodput = self._last_summary.get("goodput_fraction")
        reason = (
            f"mtbf={mtbf:.1f}s rate={rate_hr:.2f}/hr "
            f"C={self.cfg.ckpt_cost_s:.3f}s step={self.cfg.step_time_s:.3f}s"
            + (f" goodput={goodput:.3f}"
               if isinstance(goodput, float) else ""))
        return msg.PolicyDecision(
            ckpt_interval_steps=int(interval),
            replica_count=int(replica),
            fused_steps=int(fused),
            recovery_route=route,
            preferred_tier=tier,
            preempt_rate_per_hr=rate_hr,
            reason=reason,
            issued_at=time.time(),
        )

    def _materially_different(self, d: msg.PolicyDecision) -> bool:
        last = self._last_emitted
        if last is None:
            return True
        if (d.replica_count != last.replica_count
                or d.fused_steps != last.fused_steps
                or d.recovery_route != last.recovery_route
                or d.preferred_tier != last.preferred_tier):
            return True
        prev = max(1, last.ckpt_interval_steps)
        return abs(d.ckpt_interval_steps - prev) / prev > \
            self.cfg.hysteresis

    def maybe_decide(self, now: Optional[float] = None
                     ) -> Optional[msg.PolicyDecision]:
        d = self.propose(now)
        if not self._materially_different(d):
            return None
        self._note_decision_perf()
        self._last_emitted = d
        return d

    def note_emitted(self, d: msg.PolicyDecision):
        """Sync hysteresis baseline to an externally admitted decision."""
        if d is not self._last_emitted:
            self._note_decision_perf()
        self._last_emitted = d

    def _note_decision_perf(self):
        """Freeze the latest perf observation as the new decision's
        "before" side; the "after" fills on the next observe_perf."""
        self._perf_before = self._last_perf
        self._perf_after = None
