"""TPU node health-check workload: matmul + collective benchmark.

Parity: reference `dlrover/trainer/torch/node_check/nvidia_gpu.py` (matmul
`utils.py:269`, `bm_allgather` :178) + `NodeCheckElasticAgent`
(training.py:864-1092).  GPU XID checks become TPU chip probes: a large bf16
matmul exercises the MXU; an all-gather over the local mesh (and, cross-host,
over ICI/DCN via jax.distributed) exercises the interconnect.  Results are
reported to the master's NetworkCheckRendezvousManager, which runs the 2-round
pairwise sweep to isolate the faulty node and flag stragglers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Tuple

from ..common.constants import RendezvousName
from ..common.log import get_logger

logger = get_logger("node_check")


def matmul_benchmark(size: int = 2048, rounds: int = 8) -> float:
    """Time a chain of bf16 matmuls on the local accelerator (MXU probe)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (size, size), dtype=jnp.bfloat16)

    @jax.jit
    def chain(x):
        def body(carry, _):
            y = carry @ carry
            # renormalize so values stay finite
            y = y / (jnp.sqrt(jnp.float32(size)).astype(jnp.bfloat16))
            return y, ()
        out, _ = jax.lax.scan(body, x, None, length=rounds)
        return out

    chain(x).block_until_ready()  # warmup/compile
    t0 = time.monotonic()
    chain(x).block_until_ready()
    return time.monotonic() - t0


def allgather_benchmark(nbytes: int = 1 << 24) -> float:
    """Time an all-gather across all visible devices (ICI probe)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    if n == 1:
        # single chip: time a HBM round-trip instead
        x = jnp.ones((nbytes // 4,), jnp.float32)
        y = jax.device_put(x)
        t0 = time.monotonic()
        jax.device_get(y)
        return time.monotonic() - t0
    mesh = Mesh(np.array(devices), ("x",))
    per = nbytes // 4 // n * n
    x = jax.device_put(
        jnp.ones((per,), jnp.float32),
        NamedSharding(mesh, P("x")))

    @jax.jit
    def gather(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None)))

    gather(x).block_until_ready()
    t0 = time.monotonic()
    gather(x).block_until_ready()
    return time.monotonic() - t0


def run_check_workload(matmul_size: int = 2048) -> Tuple[bool, float]:
    """Returns (healthy, elapsed_seconds)."""
    if os.getenv("DWT_MOCK_NODE_CHECK_FAIL") == "1":
        # fault-injection hook (parity: node_check/utils.py:169 mock_error)
        return False, 0.0
    try:
        t_matmul = matmul_benchmark(matmul_size)
        t_comm = allgather_benchmark()
        elapsed = t_matmul + t_comm
        logger.info("node check ok: matmul=%.3fs comm=%.3fs", t_matmul,
                    t_comm)
        return True, elapsed
    except Exception:  # noqa: BLE001 — any chip/runtime error = unhealthy
        logger.exception("node check workload failed")
        return False, 0.0


def run_check_child(timeout: float = 300.0) -> Tuple[bool, float]:
    """`run_check_workload` in a short-lived child process.

    The agent never touches JAX: an accelerator belongs to one process
    at a time, and a parent that ran the probe itself would still hold
    the chips when it launches the worker.  The child opens the devices,
    prints its verdict as one JSON line and exits — the devices are free
    again before this returns.  A child that dies or hangs is an
    unhealthy node."""
    from ..telemetry import spans as tspans

    try:
        # the child's life under one span of the agent's; of what lies
        # in it the child reports its backend's start (`attach_s`)
        with tspans.span("agent:node_check") as rec:
            proc = subprocess.run(
                [sys.executable, "-m",
                 "dlrover_wuqiong_tpu.agent.node_check"],
                capture_output=True, text=True, timeout=timeout)
            out = json.loads(proc.stdout.strip().splitlines()[-1])
            rec["attrs"].update(out)
        logger.info("node check child: healthy=%s elapsed=%.3fs "
                    "attach=%.3fs", out["healthy"], out["elapsed"],
                    out.get("attach_s", 0.0))
        return bool(out["healthy"]), float(out["elapsed"])
    except (subprocess.TimeoutExpired, ValueError, IndexError, KeyError):
        logger.exception("node check child failed")
        return False, 0.0


def run_network_check(agent, rounds: int = 2,
                      timeout: float = 300.0) -> bool:
    """Drive `rounds` sweeps of the pairwise check through the master.

    Parity: reference NodeCheckElasticAgent.run (:905) + node_health_check
    (:1073).
    """
    for r in range(rounds):
        outcome = agent.rendezvous(name=RendezvousName.NETWORK_CHECK)
        healthy, elapsed = run_check_child(timeout)
        agent.mc.report_network_check_result(healthy, elapsed)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            success, reason = agent.mc.network_check_success()
            if success:
                break
            if reason == "Node failure":
                break
            time.sleep(0.5)
    success, _ = agent.mc.network_check_success()
    if not success:
        stragglers = agent.mc.get_stragglers()
        if stragglers:
            logger.warning("stragglers detected: %s", stragglers)
    return success


if __name__ == "__main__":
    import jax

    from ..telemetry import spans as tspans

    # the child's first touch of the devices, apart from the probes
    with tspans.backend_attach("devices") as _attach:
        jax.devices()
    _healthy, _elapsed = run_check_workload()
    print(json.dumps({"healthy": _healthy, "elapsed": _elapsed,
                      "attach_s": _attach["dur_s"] if _attach else 0.0}),
          flush=True)
