"""Device-mesh planning: the TPU analogue of atorch's parallel-group engine.

Parity: reference `atorch/atorch/distributed/distributed.py`
(`create_parallel_group` :323, `get_pg_ranks` :291 — NCCL groups per parallel
dim) and `auto/opt_lib/shard_planners/dim_planner.py` (DimPlanner, auto sizing
of {tensor, pipe, data} dims).

TPU redesign: parallel "groups" are axes of one `jax.sharding.Mesh`.  Axis
order follows the hardware: innermost axes (tp/sp) ride ICI with the highest
bandwidth; outer axes (dp over DCN for multi-slice) tolerate lower bandwidth.
All axes always exist (size-1 axes are free) so PartitionSpecs are stable
across plans.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common.log import get_logger

logger = get_logger("mesh")

# canonical axis order: outer (slow/DCN) → inner (fast/ICI)
AXIS_ORDER = ("dp", "pp", "fsdp", "ep", "sp", "tp")


@dataclass
class MeshPlan:
    """Sizes of every parallel dim; product must equal device count."""

    dp: int = 1    # pure data parallel (replicated params)
    pp: int = 1    # pipeline stages
    fsdp: int = 1  # data parallel with sharded params/opt-state (ZeRO-3)
    ep: int = 1    # expert parallel
    sp: int = 1    # sequence/context parallel
    tp: int = 1    # tensor parallel

    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @property
    def num_devices(self) -> int:
        return math.prod(self.sizes().values())

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes over which the batch is split."""
        return ("dp", "fsdp")

    def validate(self, num_devices: int):
        if self.num_devices != num_devices:
            raise ValueError(
                f"mesh plan {self.sizes()} needs {self.num_devices} devices, "
                f"have {num_devices}")

    def describe(self) -> str:
        return "x".join(f"{a}{n}" for a, n in self.sizes().items() if n > 1) \
            or "single"


def build_mesh(plan: MeshPlan,
               devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
    """Build the global mesh. Multi-host: `devices` defaults to
    `jax.devices()` (all processes' devices — requires jax.distributed)."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    plan.validate(len(devices))
    shape = tuple(plan.sizes()[a] for a in AXIS_ORDER)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def auto_plan(num_devices: int, num_params: Optional[int] = None,
              hbm_per_device: int = 16 << 30,
              seq_len: int = 0, num_experts: int = 0,
              max_tp: int = 8) -> MeshPlan:
    """Heuristic dim planner (parity: DimPlanner dim_planner.py:238).

    Strategy: fit first (enough combined HBM for params+opt+activations),
    then throughput — prefer pure DP/FSDP (no per-layer collectives), add TP
    only when a single chip cannot hold a layer's working set, SP for very
    long sequences, EP sized to expert count.
    """
    plan = MeshPlan()
    remaining = num_devices

    if num_params:
        # bytes/param: bf16 params + f32 master+m+v ≈ 14; activations extra
        state_bytes = num_params * 14
        min_shards = max(1, math.ceil(state_bytes / (hbm_per_device * 0.7)))
        # TP when even sharded state per device is huge (very large models)
        if num_params > 30e9 and remaining >= 4:
            plan.tp = min(max_tp, _largest_pow2_leq(min(remaining, max_tp)))
            remaining //= plan.tp
    if seq_len >= 32768 and remaining >= 2:
        plan.sp = min(_largest_pow2_leq(remaining), max(2, seq_len // 32768))
        plan.sp = _largest_pow2_leq(plan.sp)
        remaining //= plan.sp
    if num_experts and remaining >= 2:
        plan.ep = min(_largest_pow2_leq(remaining), num_experts)
        remaining //= plan.ep
    # everything else: FSDP (sharded state costs nothing on TPU; allgather
    # weights overlap with compute under XLA latency hiding)
    plan.fsdp = remaining
    plan.validate(num_devices)
    if num_params:
        # enforce the fit: state must shard across enough devices.  sp/ep
        # don't shard the optimizer state, so only tp*fsdp counts.  Before
        # giving up, reclaim sp/ep devices for fsdp — fitting beats the
        # nice-to-have axes.
        while plan.tp * plan.fsdp < min_shards and (plan.sp > 1
                                                    or plan.ep > 1):
            if plan.sp > 1:
                plan.sp //= 2
            else:
                plan.ep //= 2
            plan.fsdp *= 2
            logger.info("reclaimed a device axis for state fit: %s",
                        plan.describe())
        if plan.tp * plan.fsdp < min_shards:
            raise ValueError(
                f"model state (~{num_params * 14 / 1e9:.0f} GB) does not fit: "
                f"needs ≥{min_shards} state shards but plan "
                f"{plan.describe()} provides {plan.tp * plan.fsdp} "
                f"(devices with ≥{hbm_per_device >> 30} GiB HBM)")
    logger.info("auto mesh plan for %d devices: %s", num_devices,
                plan.describe())
    return plan


def detect_hbm_per_device(devices: Optional[Sequence] = None) -> int:
    """Per-device accelerator memory, from the runtime when available."""
    from ..telemetry.memory import device_memory

    try:
        import jax

        first = device_memory((devices or jax.devices())[:1])
        if first and first[0]["bytes_limit"] > 0:
            return first[0]["bytes_limit"]
    except Exception:  # noqa: BLE001 — CPU/older runtimes have no stats
        pass
    return 16 << 30


def _largest_pow2_leq(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def hybrid_slice_plan(num_slices: int, devices_per_slice: int,
                      tp: int = 1, sp: int = 1) -> MeshPlan:
    """Multi-slice (DCN-connected) plan: dp over slices, fsdp/tp within
    a slice so heavy collectives stay on ICI (SURVEY.md §2.5 TPU row)."""
    inner = devices_per_slice // (tp * sp)
    return MeshPlan(dp=num_slices, fsdp=inner, tp=tp, sp=sp)


def context_mesh(mesh):
    """The mesh a NESTED shard_map must target.

    Inside another shard_map (manual axes active), jax requires the inner
    shard_map's mesh to be the context AbstractMesh — whose already-manual
    axes are marked — not the original all-Auto concrete mesh.  Outside
    any manual context the concrete mesh passes through unchanged.  Used
    by parallel/long_context.py (ring/Ulysses inside the pipeline) and
    parallel/pipeline.py (pipeline inside the DiLoCo dp body).
    """
    from jax.sharding import get_abstract_mesh

    ctx = get_abstract_mesh()
    if ctx is not None and getattr(ctx, "axis_names", None) and \
            any("manual" in str(t).lower() for t in
                getattr(ctx, "axis_types", ())):
        return ctx
    return mesh


def in_manual_context() -> bool:
    """True when tracing inside a shard_map with manual axes."""
    return context_mesh(None) is not None
