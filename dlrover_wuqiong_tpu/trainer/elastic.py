"""Worker-process side of elastic training: world init + trainer wrapper.

Parity: reference `dlrover/trainer/torch/elastic/trainer.py` (ElasticTrainer
:181 — fixed global batch via grad-accum under changing world size) and the
worker-side env contract consumed from the agent.

TPU redesign: `init_elastic()` reads the agent-injected env, initializes
`jax.distributed` when the world spans hosts, and returns an `ElasticContext`
that the training script uses for mesh construction, step reporting, and
dynamic-sharding dataloaders.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

from ..agent.master_client import MasterClient
from ..common.constants import NodeEnv
from ..common.log import get_logger

logger = get_logger("elastic_trainer")


@dataclass
class WorldInfo:
    process_id: int = 0
    num_processes: int = 1
    coordinator_addr: str = ""
    node_id: int = 0
    node_rank: int = 0
    restart_count: int = 0


def get_world_info() -> WorldInfo:
    return WorldInfo(
        process_id=int(os.getenv(NodeEnv.PROCESS_ID, "0")),
        num_processes=int(os.getenv(NodeEnv.NUM_PROCESSES, "1")),
        coordinator_addr=os.getenv(NodeEnv.COORDINATOR_ADDR, ""),
        node_id=int(os.getenv(NodeEnv.NODE_ID, "0")),
        node_rank=int(os.getenv(NodeEnv.NODE_RANK, "0")),
        restart_count=int(os.getenv(NodeEnv.RESTART_COUNT, "0")),
    )


class ElasticContext:
    """Per-worker handle to the elastic world + master services."""

    def __init__(self, world: WorldInfo,
                 master_client: Optional[MasterClient]):
        self.world = world
        self.mc = master_client
        self._step_report_interval = 15.0
        self._last_report = 0.0
        self._warm_pool = None

    @property
    def is_distributed(self) -> bool:
        return self.world.num_processes > 1

    @property
    def process_id(self) -> int:
        return self.world.process_id

    def report_step(self, step: int, force: bool = False):
        """Throttled global-step reporting feeding the SpeedMonitor."""
        if self.mc is None:
            return
        now = time.time()
        if force or now - self._last_report > self._step_report_interval:
            try:
                self.mc.report_global_step(step)
                self._last_report = now
            except Exception:  # noqa: BLE001
                logger.debug("step report failed", exc_info=True)

    def report_loss(self, step: int, loss: float):
        """Feed the master's loss-spike detector (diagnosis/loss_spike.py).

        Reported at the trainer's logging cadence — the detector works on
        a trailing window of samples, not every step."""
        if self.mc is None:
            return
        try:
            import json as _json

            self.mc.report_diagnosis(
                "loss", _json.dumps({"step": step, "loss": float(loss)}))
        except Exception:  # noqa: BLE001
            logger.debug("loss report failed", exc_info=True)

    def report_op_profile(self, evidence: str):
        """Push top-slow-collective evidence (utils/xplane.py) to the
        master's diagnosis chain — xpu_timer parity for hang localization."""
        if self.mc is None or not evidence:
            return
        try:
            self.mc.report_diagnosis("op_profile", evidence)
        except Exception:  # noqa: BLE001
            logger.debug("op profile report failed", exc_info=True)

    def sharding_client(self, dataset_name: str, batch_size: int,
                        dataset_size: int, **kwargs):
        from ..agent.sharding_client import IndexShardingClient

        if self.mc is None:
            return None
        return IndexShardingClient(self.mc, dataset_name, batch_size,
                                   dataset_size, **kwargs)

    def enable_warm_restarts(self, result, global_batch: int,
                             seq_len: int, model=None,
                             fused_steps: Optional[int] = None):
        """Publish this world's compile spec and start warming the worlds
        one failure away (auto/warm_pool.py).

        `result` is the AccelerateResult driving training; `global_batch`
        and `seq_len` pin the abstract batch the degraded compile must
        match (the framework holds the GLOBAL batch fixed across world
        changes — GradientAccumulator below).  Returns the WarmPool, or
        None when the model/strategy cannot be replayed in a warm child
        (non-registry model, callable-bearing strategy) — warming is an
        optimization, never a requirement.
        """
        import jax

        from ..auto.compile_cache import resolve_cache_dir
        from ..auto.warm_pool import (
            WarmPool,
            WarmSpec,
            model_spec,
            publish_current_spec,
        )

        if getattr(result, "strategy_spec", None) is None:
            logger.info("warm restarts unavailable: strategy is not "
                        "replayable in a warm child")
            return None
        mspec = model_spec(model if model is not None else result.model)
        if mspec is None:
            logger.info("warm restarts unavailable: model not in the "
                        "warm-pool registry (gpt/llama)")
            return None
        cache_dir = resolve_cache_dir()
        if fused_steps is None:
            # default to the K the result runs with (the trainer's
            # auto-tuned K when fusion is on) — a warm entry at the wrong
            # K is a cache miss for the restarted worker
            fused_steps = getattr(result, "fused_steps", 1)
        spec = WarmSpec(
            n_devices=len(jax.devices()),
            strategy=result.strategy_spec, model=mspec,
            batch_shape=[int(global_batch), int(seq_len)],
            accum_steps=result.strategy.accum_steps,
            platform=jax.default_backend(),
            fused_steps=max(1, int(fused_steps)))
        publish_current_spec(cache_dir, spec)
        if self._warm_pool is None:
            self._warm_pool = WarmPool(cache_dir)
        local = int(os.getenv(NodeEnv.LOCAL_DEVICE_COUNT, "0")) or \
            max(1, len(jax.local_devices()))
        self._warm_pool.warm_degraded(
            spec, num_nodes=self.world.num_processes,
            devices_per_node=local)
        return self._warm_pool


_context: Optional[ElasticContext] = None


def init_elastic(connect_master: bool = True) -> ElasticContext:
    """Initialize the JAX world from the agent's env contract.

    Call once at the top of the training script (before creating arrays).
    """
    global _context
    if _context is not None:
        return _context
    world = get_world_info()
    # warm restarts: compile through the persistent cache from the first
    # trace — a relaunched worker on a known topology then deserializes
    # its train step from disk instead of recompiling
    from ..auto.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    if world.num_processes > 1 and world.coordinator_addr:
        import jax

        logger.info("jax.distributed.initialize(coord=%s, n=%d, id=%d)",
                    world.coordinator_addr, world.num_processes,
                    world.process_id)
        from ..telemetry import spans as tspans

        with tspans.backend_attach("distributed_initialize"):
            jax.distributed.initialize(
                coordinator_address=world.coordinator_addr,
                num_processes=world.num_processes,
                process_id=world.process_id)
    mc = None
    master_addr = os.getenv(NodeEnv.MASTER_ADDR, "")
    if connect_master and master_addr:
        mc = MasterClient(master_addr, world.node_id)
    _context = ElasticContext(world, mc)
    return _context


def reset_elastic_context():
    global _context
    if _context is not None and _context.mc is not None:
        _context.mc.close()
    if _context is not None and _context._warm_pool is not None:
        _context._warm_pool.stop()
    _context = None


class GradientAccumulator:
    """Keep the global batch fixed as world size changes.

    Parity: reference ElasticTrainer/GradientState (trainer.py:53-181): with
    `global_batch_size` fixed, each process accumulates
    `global_batch_size / (num_processes * per_step_batch)` micro-steps before
    applying the update.  In JAX this folds into the train step as a
    `lax.scan` over micro-batches (compiler-friendly, no Python loop).
    """

    def __init__(self, global_batch_size: int, micro_batch_size: int,
                 num_processes: int):
        denom = micro_batch_size * max(1, num_processes)
        self.accum_steps = max(1, global_batch_size // denom)
        self.global_batch_size = global_batch_size
        self.micro_batch_size = micro_batch_size

    def __repr__(self):
        return (f"GradientAccumulator(accum={self.accum_steps}, "
                f"global={self.global_batch_size})")
