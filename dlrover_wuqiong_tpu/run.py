"""`dwt-run` — elastic launcher CLI (dlrover-run equivalent).

Parity: reference `dlrover/trainer/torch/elastic_run.py` (main :391, run :342,
`_launch_dlrover_local_master` :237, `_elastic_config_from_args` :295) — a
torchrun-superset that (a) spawns a local master when none is reachable
(standalone), (b) optionally runs the node health-check, then (c) starts the
elastic agent supervising the training script.

Usage:
    python -m dlrover_wuqiong_tpu.run --standalone --nproc_per_node=1 train.py
    python -m dlrover_wuqiong_tpu.run --nnodes=2:4 --network-check train.py
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
from typing import List, Optional, Tuple

from .agent.elastic_agent import ElasticLaunchConfig, launch_agent
from .common.comm import addr_connectable
from .common.constants import NodeEnv
from .common.log import get_logger
from .master.master import JobMaster

logger = get_logger("run")


def parse_nnodes(value: str) -> Tuple[int, int]:
    if ":" in value:
        lo, hi = value.split(":")
        return int(lo), int(hi)
    n = int(value)
    return n, n


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser("dwt-run",
                                description="TPU elastic training launcher")
    p.add_argument("--nnodes", default="1",
                   help="N or MIN:MAX elastic node range")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.getenv(NodeEnv.LOCAL_DEVICE_COUNT, "1")))
    p.add_argument("--standalone", action="store_true",
                   help="run a local in-process master")
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--network-check", action="store_true", dest="network_check")
    p.add_argument("--node_unit", type=int, default=1)
    p.add_argument("--rdzv_timeout", type=float, default=600.0)
    p.add_argument("--log_dir", default="")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _launch_local_master(min_nodes: int, max_nodes: int,
                         node_unit: int) -> JobMaster:
    """Parity: reference `_launch_dlrover_local_master` :237 (in-process here —
    the master is pure Python; a thread keeps standalone single-process)."""
    master = JobMaster(port=0, min_nodes=min_nodes, max_nodes=max_nodes,
                       node_unit=node_unit)
    master.prepare()
    t = threading.Thread(target=master.run, daemon=True,
                         name="dwt-local-master")
    t.start()
    return master


def run(args: argparse.Namespace) -> int:
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    master_addr = os.getenv(NodeEnv.MASTER_ADDR, "")
    local_master = None
    use_standalone = args.standalone or not master_addr
    if use_standalone:
        from .telemetry import spans as tspans

        # the local master's start and the wait until it listens
        with tspans.span("cli:master"):
            local_master = _launch_local_master(min_nodes, max_nodes,
                                                args.node_unit)
        master_addr = local_master.addr
        os.environ[NodeEnv.MASTER_ADDR] = master_addr
        logger.info("standalone: local master at %s", master_addr)
    elif not addr_connectable(master_addr):
        logger.error("master %s not reachable", master_addr)
        return 2

    config = ElasticLaunchConfig(
        min_nodes=min_nodes, max_nodes=max_nodes,
        nproc_per_node=args.nproc_per_node,
        max_restarts=args.max_restarts,
        network_check=args.network_check,
        node_unit=args.node_unit,
        rdzv_timeout=args.rdzv_timeout,
        log_dir=args.log_dir)

    entrypoint = [sys.executable, "-u", args.training_script]
    entrypoint += [a for a in args.training_script_args if a != "--"]

    node_id = int(os.getenv(NodeEnv.NODE_ID, "0"))
    node_rank = int(os.getenv(NodeEnv.NODE_RANK, "0"))
    try:
        return launch_agent(config, entrypoint, master_addr, node_id,
                            node_rank)
    finally:
        if local_master is not None:
            local_master.stop()


def main(argv: Optional[List[str]] = None) -> int:
    return run(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
