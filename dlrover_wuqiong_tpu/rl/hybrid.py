"""Hybrid RLHF engine: separate train and decode meshes with weight sync.

Parity: reference `atorch/atorch/rl/ds_hybrid_engine/hybrid_engine.py:1-378`
(+ `ds_hook.py`) — DeepSpeed-hybrid keeps TRAINING sharded for throughput
(ZeRO partitions) but runs GENERATION on an inference-friendly layout,
gathering/re-partitioning the actor weights between the two phases each
iteration.

TPU redesign: both layouts are just NamedShardings over two meshes built
from the SAME devices —

- train mesh: fsdp-major (or any auto_accelerate plan): maximizes update
  throughput and state sharding;
- decode mesh: tp x dp — parameters sharded over tp ONLY (so the KV-cache
  decode scan runs without per-step fsdp all-gathers) and the batch over
  dp.

The "weight sync" of the reference's gather+scatter hooks collapses to one
resharding `jax.device_put(actor_params, decode_shardings)` — XLA emits
the all-gather/all-to-all pattern between the two placements.  Sync
latency is measured per call (`last_sync_s`).
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common.log import get_logger
from ..parallel.mesh import MeshPlan, build_mesh
from ..parallel.sharding import ShardingPlanner

logger = get_logger("rl_hybrid")


class HybridEngine:
    """Two placements of the actor over one device set + timed sync."""

    def __init__(self, devices, train_plan: Optional[MeshPlan] = None,
                 decode_tp: int = 1):
        devices = list(devices)
        n = len(devices)
        if decode_tp < 1 or n % decode_tp:
            raise ValueError(f"decode_tp={decode_tp} must be >= 1 and "
                             f"divide the {n} devices")
        self.train_mesh = build_mesh(train_plan or MeshPlan(fsdp=n),
                                     devices)
        self.train_planner = ShardingPlanner(self.train_mesh)
        self.decode_mesh = build_mesh(
            MeshPlan(tp=decode_tp, dp=n // decode_tp), devices)
        self.decode_planner = ShardingPlanner(self.decode_mesh)
        self._decode_sh = None
        self.last_sync_s = 0.0

    def place_train(self, params: Any) -> Any:
        return self.train_planner.shard_params(params)

    def sync_to_decode(self, actor_params: Any) -> Any:
        """Reshard trained actor weights onto the decode placement.

        The reference hybrid engine's ds_hook gather/scatter round-trip;
        here one device_put between shardings, timed for the README
        sync-latency number."""
        if self._decode_sh is None:
            self._decode_sh = self.decode_planner.param_shardings(
                actor_params)
        from ..common.util import sync_tree

        t0 = time.perf_counter()
        placed = jax.device_put(actor_params, self._decode_sh)
        # all-leaf readback, not a single-leaf probe (a lower bound —
        # other leaves may still be in flight).  The first
        # call also compiles the sync reduction — steady-state
        # last_sync_s is the second call onward.
        sync_tree(placed)
        self.last_sync_s = time.perf_counter() - t0
        return placed

    def place_prompts(self, prompts: jax.Array) -> jax.Array:
        """Batch over the decode mesh's dp axis."""
        return jax.device_put(
            prompts, NamedSharding(self.decode_mesh, P("dp")))

    def place_batch_train(self, x: jax.Array) -> jax.Array:
        """Batch over the train mesh's data axes (for the PPO update)."""
        return jax.device_put(x, self.train_planner.batch_sharding(
            x.ndim, None, 0))
