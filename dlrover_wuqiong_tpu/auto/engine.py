"""Strategy search engine: candidate generation + dry-run scoring.

Parity: reference `atorch/atorch/auto/engine/` (`executor.py` candidate
strategy generation, `strategy.py`, `sg_algo/` scoring) and the dry-runner
(`auto/dry_runner/dry_runner.py`) — the service that makes `auto_accelerate`
"auto" when no strategy is given.

TPU redesign: a candidate is a MeshPlan + flags; scoring compiles the real
train step for each candidate (XLA is the ground truth) and ranks by the
compiled executable's cost analysis (FLOPs / bytes-accessed / peak memory
against the device's roofline) or, when `measure=True` and devices are
real, by timing one executed step.  The search space is small and discrete,
so exhaustive scoring beats surrogate search; the BO helper (`bo.py`) is
for the continuous knobs (e.g. learning rates) layered on top.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..common.log import get_logger
from ..parallel.mesh import MeshPlan

logger = get_logger("auto_engine")


@dataclasses.dataclass
class Candidate:
    plan: MeshPlan
    remat: bool = False
    remat_policy: str = "full"       # ops/remat.py policy when remat is on
    pp_schedule: str = "gpipe"       # | "interleaved" (virtual stages)
    pp_virtual_stages: int = 1
    score: float = math.inf          # lower is better (estimated step s)
    peak_bytes: int = 0
    feasible: bool = True
    reason: str = ""

    def strategy(self) -> List[Tuple[str, Dict]]:
        out: List[Tuple[str, Dict]] = []
        if self.plan.tp > 1:
            out.append(("tensor_parallel", {"size": self.plan.tp}))
        if self.plan.sp > 1:
            out.append(("sequence_parallel", {"size": self.plan.sp}))
        if self.plan.pp > 1:
            pp_cfg: Dict = {"size": self.plan.pp}
            if self.pp_schedule != "gpipe":
                pp_cfg["schedule"] = self.pp_schedule
                pp_cfg["virtual_stages"] = self.pp_virtual_stages
            out.append(("pipeline_parallel", pp_cfg))
        if self.plan.ep > 1:
            out.append(("expert_parallel", {"size": self.plan.ep}))
        if self.plan.dp > 1:
            out.append(("data_parallel", {"size": self.plan.dp}))
        out.append(("fsdp", {"size": self.plan.fsdp}))
        ckpt: Dict = {"enabled": self.remat}
        if self.remat and self.remat_policy != "full":
            ckpt["policy"] = self.remat_policy
        out.append(("checkpoint", ckpt))
        return out


def _divisors_pow2(n: int, cap: int) -> List[int]:
    return [d for d in (1, 2, 4, 8, 16, 32) if d <= min(n, cap)
            and n % d == 0]


def generate_candidates(num_devices: int, n_head: int = 0,
                        n_layer: int = 0, num_experts: int = 0,
                        max_tp: int = 8, max_pp: int = 4,
                        with_remat: bool = True) -> List[Candidate]:
    """Enumerate valid mesh plans (parity executor.py candidate gen).

    Divisibility constraints prune the space: heads % tp, layers % pp,
    experts % ep, and the device count must factor exactly.
    """
    out: List[Candidate] = []
    for tp in _divisors_pow2(num_devices, max_tp):
        if n_head and n_head % tp:
            continue
        for pp in _divisors_pow2(num_devices // tp, max_pp):
            if n_layer and n_layer % pp:
                continue
            for ep in _divisors_pow2(num_devices // (tp * pp),
                                     num_experts or 1):
                if num_experts and num_experts % ep:
                    continue
                remaining = num_devices // (tp * pp * ep)
                plan = MeshPlan(tp=tp, pp=pp, ep=ep, fsdp=remaining)
                # remat variants: off, full recompute, and the selective
                # "dots" policy (save matmul outputs) — the compile-and-
                # score pass ranks the memory/time trade for real
                variants = ([(False, "full"), (True, "full"),
                             (True, "dots")] if with_remat
                            else [(False, "full")])
                for remat, policy in variants:
                    out.append(Candidate(plan=plan, remat=remat,
                                         remat_policy=policy))
                    if pp > 1 and n_layer and n_layer % (pp * 2) == 0:
                        # interleaved virtual stages shrink the bubble
                        # from (pp-1)/(M+pp-1) to (pp-1)/(2M+pp-1)
                        out.append(Candidate(plan=plan, remat=remat,
                                             remat_policy=policy,
                                             pp_schedule="interleaved",
                                             pp_virtual_stages=2))
    return out


# ------------------------------------------------------------------ scoring


def _device_roofline(device) -> Tuple[float, float]:
    """(peak_flops, hbm_bytes_per_s) for the scoring model.

    A device kind that is not in the table is an error, never a default:
    a made-up roofline ranks candidates by fiction.  The "cpu" row is
    nominal — the CPU mesh only has to rank candidates relative to each
    other (tests); it is not a statement about any host."""
    kind = device.device_kind.lower()
    table = {
        "tpu v5 lite": (197e12, 819e9), "tpu v5e": (197e12, 819e9),
        "tpu v5": (459e12, 1228e9), "tpu v5p": (459e12, 2765e9),
        "tpu v4": (275e12, 1228e9),
        "tpu v6 lite": (918e12, 1640e9), "tpu v6e": (918e12, 1640e9),
        "cpu": (1e12, 100e9),
    }
    if kind not in table:
        raise ValueError(
            f"no roofline for device kind {device.device_kind!r}; known: "
            f"{sorted(table)} — add its published peaks, do not guess")
    return table[kind]


def score_candidate(cand: Candidate, model, optimizer, sample_batch: Dict,
                    devices: Sequence, measure: bool = False,
                    hbm_per_device: Optional[int] = None) -> Candidate:
    """Compile the candidate's real train step; rank by roofline estimate.

    Parity: `run_dryrun_task` (auto/accelerate.py:118 → dry_runner.py) —
    the strategy is validated by actually building it; infeasible
    combinations (OOM, divisibility) come back marked rather than raised.
    """
    import jax

    from ..telemetry.memory import compiled_memory
    from .accelerate import auto_accelerate

    try:
        res = auto_accelerate(model, optimizer=optimizer,
                              strategy=cand.strategy(), devices=devices)
        batch = res.place_batch(dict(sample_batch))
        compiled = res.train_step.lower(res.state, batch).compile()
    except Exception as e:  # noqa: BLE001 — infeasible candidate
        cand.feasible = False
        cand.reason = repr(e)[:200]
        return cand

    try:
        costs = compiled.cost_analysis()
        if isinstance(costs, list):
            costs = costs[0] if costs else {}
    except Exception:  # noqa: BLE001
        costs = {}
    peak = compiled_memory(compiled).get("live_bytes", 0)
    cand.peak_bytes = peak
    limit = hbm_per_device
    if limit and peak > limit:
        cand.feasible = False
        cand.reason = f"peak {peak >> 30}GiB exceeds HBM"
        return cand

    if measure:
        t0 = time.perf_counter()
        state, m = compiled(res.state, batch)
        jax.tree.map(lambda x: x.block_until_ready(), m)
        t0 = time.perf_counter()
        state, m = compiled(state, batch)
        float(jax.tree.leaves(m)[0])
        cand.score = time.perf_counter() - t0
        return cand

    flops = float(costs.get("flops", 0.0))
    bytes_accessed = float(costs.get("bytes accessed", 0.0))
    peak_flops, bw = _device_roofline(devices[0])
    per_dev_flops = flops  # cost analysis is already per-program(device)
    cand.score = max(per_dev_flops / peak_flops, bytes_accessed / bw)
    if cand.plan.pp > 1:
        # roofline counts compute, not idle ticks — fold in the schedule's
        # fill/drain bubble (this is what lets an interleaved candidate
        # beat its gpipe twin without measure=True)
        from ..parallel.pipeline import (
            default_pp_microbatches,
            schedule_ticks,
        )

        m = default_pp_microbatches(1, cand.plan.pp)
        _, bubble = schedule_ticks(cand.pp_schedule, m, cand.plan.pp,
                                   cand.pp_virtual_stages)
        cand.score = cand.score / max(1e-9, 1.0 - bubble)
    if cand.score == 0:
        cand.score = math.inf
    return cand


def search_strategy(model, optimizer, sample_batch: Dict,
                    devices: Sequence, n_head: int = 0, n_layer: int = 0,
                    num_experts: int = 0, measure: bool = False,
                    hbm_per_device: Optional[int] = None,
                    top_k: int = 1) -> List[Candidate]:
    """Score every candidate; returns the top_k feasible, best first.

    Parity: the engine's strategy loop (executor.py:278) without the gRPC
    service hop — the search runs in-process.
    """
    cands = generate_candidates(len(devices), n_head=n_head,
                                n_layer=n_layer, num_experts=num_experts)
    logger.info("strategy search: %d candidates over %d devices",
                len(cands), len(devices))
    for c in cands:
        score_candidate(c, model, optimizer, sample_batch, devices,
                        measure=measure, hbm_per_device=hbm_per_device)
        sched = ("" if c.plan.pp <= 1 or c.pp_schedule == "gpipe"
                 else f" {c.pp_schedule}v{c.pp_virtual_stages}")
        logger.info("  %s%s remat=%s → %s", c.plan.describe(), sched,
                    c.remat_policy if c.remat else "off",
                    f"score={c.score:.4g}" if c.feasible
                    else f"infeasible ({c.reason[:60]})")
    feasible = [c for c in cands if c.feasible]
    feasible.sort(key=lambda c: c.score)
    return feasible[:top_k]
