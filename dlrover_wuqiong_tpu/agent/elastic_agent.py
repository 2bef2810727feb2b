"""Elastic training agent: node-level supervisor of JAX worker processes.

Parity: reference `dlrover/python/elastic_agent/torch/training.py`
(`ElasticTrainingAgent` :362, `_invoke_run` :580, `_assign_worker_ranks` :484,
`_restart_workers` :704, `launch_agent` :734, `MasterRendezvousHandler` :179).

TPU redesign: instead of torch-elastic WorkerSpecs + NCCL process groups, the
agent forms a `jax.distributed` world from the master rendezvous — rank-0's
ip:port becomes the coordinator — then launches ONE worker process per host
(the JAX/TPU model: a process owns all local chips) with the world contract in
env vars.  Elasticity is restart-the-world: on failure or membership change the
agent persists the staged flash checkpoint, kills workers, re-joins rendezvous
and relaunches with the new world (goodput comes from detection + restore
speed, SURVEY.md §7 hard-part (a)).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..checkpoint.ckpt_saver import AsyncCheckpointSaver
from ..common.comm import find_free_port
from ..common.constants import JobConstant, NodeEnv, RendezvousName
from ..common.log import get_logger
from .master_client import MasterClient

logger = get_logger("elastic_agent")


@dataclass
class ElasticLaunchConfig:
    """Parity: reference ElasticLaunchConfig (training.py:117) +
    auto_configure_params (:153)."""

    min_nodes: int = 1
    max_nodes: int = 1
    nproc_per_node: int = 1
    max_restarts: int = 3
    network_check: bool = False
    node_unit: int = 1
    rdzv_timeout: float = 600.0
    monitor_interval: float = 1.0
    log_dir: str = ""

    def auto_configure_params(self):
        self.network_check = self.network_check or (
            os.getenv("DWT_NETWORK_CHECK", "") == "1")
        if self.max_nodes >= 4 and os.getenv(
                "DWT_NETWORK_CHECK", "auto") == "auto":
            self.network_check = True


class WorkerContext:
    """One launched training process + its world assignment."""

    def __init__(self, proc: subprocess.Popen, process_id: int,
                 num_processes: int, restart_count: int,
                 log_path: str = ""):
        self.proc = proc
        self.process_id = process_id
        self.num_processes = num_processes
        self.restart_count = restart_count
        self.log_path = log_path  # captures stderr for error classification


class RendezvousOutcome:
    def __init__(self, rdzv_round: int, process_id: int, num_processes: int,
                 coordinator_addr: str, local_world_size: int):
        self.rdzv_round = rdzv_round
        self.process_id = process_id
        self.num_processes = num_processes
        self.coordinator_addr = coordinator_addr
        self.local_world_size = local_world_size


class ElasticAgent:
    def __init__(self, config: ElasticLaunchConfig, master_client: MasterClient,
                 node_id: int, node_rank: int,
                 entrypoint: Optional[List[str]] = None,
                 worker_env: Optional[Dict[str, str]] = None):
        self.config = config
        self.mc = master_client
        self.node_id = node_id
        self.node_rank = node_rank
        self.entrypoint = entrypoint or []
        self.worker_env = worker_env or {}
        self._worker: Optional[WorkerContext] = None
        self._restart_count = 0
        self._rollback_before = -1  # loss-spike resume ceiling (one-shot)
        self._stopped = threading.Event()
        self._saver: Optional[AsyncCheckpointSaver] = None
        self._heartbeat_thread: Optional[threading.Thread] = None
        self._last_restart_ts = 0.0
        self._replica_server = None
        self._replica_manager = None
        self._warm_pool = None
        self._warm_generation = 0  # invalidates stale warm threads
        self._policy_seen = 0  # last adaptive-policy decision id applied
        # last rendezvous round this agent ran in, PER rendezvous name
        # (network-check and elastic-training managers count independently):
        # a re-join after failure must wait for a NEWER round — accepting
        # the stale completed world hands out a dead coordinator and the
        # restarted workers split across two worlds (deadlock until the jax
        # distributed init timeout)
        self._last_rdzv_round: Dict[str, int] = {}

    # ------------------------------------------------------------- rendezvous

    def rendezvous(self,
                   name: str = RendezvousName.ELASTIC_TRAINING
                   ) -> RendezvousOutcome:
        """Join + poll until the master forms the world.

        Parity: reference MasterRendezvousHandler.next_rendezvous (:250).
        """
        from ..telemetry import spans as tspans

        with tspans.span(f"rdzv:{name}:join", {"node": self.node_id}) as rec:
            out = self._rendezvous_poll(name, rec)
        return out

    def _rendezvous_poll(self, name: str, span_rec) -> RendezvousOutcome:
        free_port = find_free_port()
        self.mc.join_rendezvous(
            self.node_rank, self.config.nproc_per_node, rdzv_name=name,
            node_ip=os.getenv("DWT_NODE_IP", "127.0.0.1"),
            free_port=free_port)
        deadline = time.monotonic() + self.config.rdzv_timeout
        while time.monotonic() < deadline:
            state = self.mc.get_comm_world(rdzv_name=name)
            if state.complete and state.rdzv_round <= \
                    self._last_rdzv_round.get(name, -1):
                # stale world from before our re-join — wait for the next
                time.sleep(0.5)
                continue
            if state.complete:
                my_rank = None
                total_procs = 0
                ranks = sorted(int(r) for r in state.world)
                for rank in ranks:
                    nid, lws, ip, port = state.world[str(rank)]
                    if nid == self.node_id:
                        my_rank = rank
                    total_procs += 1
                if my_rank is None:
                    # we were not included (e.g. over max_nodes) — rejoin
                    time.sleep(1.0)
                    self.mc.join_rendezvous(
                        self.node_rank, self.config.nproc_per_node,
                        rdzv_name=name,
                        node_ip=os.getenv("DWT_NODE_IP", "127.0.0.1"),
                        free_port=free_port)
                    continue
                self._last_rdzv_round[name] = state.rdzv_round
                span_rec["attrs"]["round"] = state.rdzv_round
                span_rec["attrs"]["world"] = total_procs
                return RendezvousOutcome(
                    state.rdzv_round, my_rank, total_procs,
                    state.coordinator_addr, self.config.nproc_per_node)
            time.sleep(0.5)
        raise TimeoutError(f"rendezvous {name} did not complete")

    # ------------------------------------------------------------- lifecycle

    def _start_saver(self):
        if self._saver is None:
            self._saver = AsyncCheckpointSaver.start_async_saving_ckpt(
                job_name=os.getenv(NodeEnv.JOB_NAME, "dwt"),
                local_shard_num=1, node_rank=self.node_rank)
            self._saver.metric_hook = lambda kind, s: \
                self.mc.report_custom_metric(
                    {f"dwt_ckpt_{kind}_seconds": s})

    def _setup_replication(self, outcome: RendezvousOutcome):
        """Ring replication of staged checkpoints over agent TCP (DCN).

        Parity: flash_checkpoint/replica.py backup/gather — peer addresses
        rendezvous through the master KV store; a replacement node restores
        its staged segment from a peer before touching storage.
        """
        from ..common.global_context import get_context
        from ..checkpoint.replica import CkptReplicaManager, ReplicaServer

        replicas = get_context().checkpoint_replica
        if replicas <= 0:
            return
        job = os.getenv(NodeEnv.JOB_NAME, "dwt")
        if self._replica_server is None:
            self._replica_server = ReplicaServer()
            self._replica_server.start()
        my_ip = os.getenv("DWT_NODE_IP", "127.0.0.1")
        my_addr = f"{my_ip}:{self._replica_server.port}"
        rdzv = outcome.rdzv_round
        self.mc.kv_store_set(f"replica/{rdzv}/{outcome.process_id}",
                             my_addr.encode())
        peers = {}
        keys = [f"replica/{rdzv}/{r}" for r in range(outcome.num_processes)]
        try:
            self.mc.kv_store_wait(keys, timeout=60.0)
            vals = self.mc.kv_store_multi_get(keys) or []
            for r, v in enumerate(vals):
                if v:
                    peers[r] = v.decode() if isinstance(v, bytes) else v
        except TimeoutError as e:
            # replication is best-effort: run with the peers that showed up
            logger.warning("replica peer rendezvous incomplete: %s", e)
        self._replica_manager = CkptReplicaManager(
            rank=outcome.process_id, peers=peers, job_name=job,
            replica_count=replicas,
            # holder corruption must reach the master's event stream —
            # the agent is the process that owns the mc here
            health_hook=lambda reason: self.mc.report_node_event(
                "ckpt-health", f"replica: {reason}", level="warning"))
        if not self._replica_manager.has_local_segment():
            # replacement node (or first boot after a node swap): the staged
            # checkpoint exists only on a peer — pull it into local shm so
            # the worker restores in-memory instead of re-reading storage.
            # Gating on the MISSING local segment (not restart counts, which
            # reset with the agent process) also guarantees we never
            # clobber a newer local segment with a peer's older copy.
            restored = self._replica_manager.restore()
            if restored is not None:
                logger.info("replica restore: staged step %d recovered "
                            "from a peer", restored)

    def _launch_worker(self, outcome: RendezvousOutcome) -> WorkerContext:
        env = dict(os.environ)
        env.update(self.worker_env)
        # make this framework importable in the worker regardless of its cwd
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        pythonpath = env.get("PYTHONPATH", "")
        if pkg_root not in pythonpath.split(os.pathsep):
            env["PYTHONPATH"] = (f"{pkg_root}{os.pathsep}{pythonpath}"
                                 if pythonpath else pkg_root)
        env.update({
            NodeEnv.MASTER_ADDR: self.mc.master_addr,
            NodeEnv.NODE_ID: str(self.node_id),
            NodeEnv.NODE_RANK: str(self.node_rank),
            NodeEnv.COORDINATOR_ADDR: outcome.coordinator_addr,
            NodeEnv.PROCESS_ID: str(outcome.process_id),
            NodeEnv.NUM_PROCESSES: str(outcome.num_processes),
            NodeEnv.LOCAL_DEVICE_COUNT: str(outcome.local_world_size),
            NodeEnv.RESTART_COUNT: str(self._restart_count),
        })
        env.setdefault("DWT_PROC_ROLE", "trainer")
        # one compile-cache dir across worker generations and warm
        # children: the restarted worker must read what the pool wrote
        from ..auto.compile_cache import CACHE_DIR_ENV, resolve_cache_dir

        env.setdefault(CACHE_DIR_ENV, resolve_cache_dir())
        if self._rollback_before >= 0:
            # one-shot: the relaunched worker resumes from the newest
            # committed ckpt BEFORE the spike step, then the ceiling clears
            env[NodeEnv.ROLLBACK_BEFORE_STEP] = str(self._rollback_before)
            self._rollback_before = -1
        stdout = None
        if self.config.log_dir:
            os.makedirs(self.config.log_dir, exist_ok=True)
            prune_prefix = f"worker_{self.node_rank}_"
            log_path = os.path.join(
                self.config.log_dir,
                f"{prune_prefix}r{self._restart_count}.log")
            stdout = open(log_path, "ab")
            stderr = subprocess.STDOUT
        else:
            # stderr always lands in a file: its tail (the traceback) is
            # what the master's error catalogue classifies on failure
            import tempfile

            log_dir = os.path.join(tempfile.gettempdir(), "dwt-worker-logs")
            os.makedirs(log_dir, exist_ok=True)
            prune_prefix = f"worker_{os.getpid()}_{self.node_rank}_"
            log_path = os.path.join(
                log_dir, f"{prune_prefix}r{self._restart_count}.stderr")
            stderr = open(log_path, "ab")
        # trace context crosses the process boundary via env: the worker's
        # spans (its `proc:boot`, restore tiers, rpc verbs) parent under
        # this span of the agent's, so a restart is one tree
        from ..telemetry import spans as tspans

        with tspans.span("agent:launch_worker",
                         {"restart_count": self._restart_count}) as rec, \
                tspans.env_context() as span_env:
            env.update(span_env)
            proc = subprocess.Popen(
                self.entrypoint, env=env, stdout=stdout, stderr=stderr,
                start_new_session=True)
            rec["attrs"]["worker_pid"] = proc.pid
        # the child holds its own dups — close the parent copies, or the
        # agent leaks one fd per restart over a long elastic job
        for fh in (stdout, stderr):
            if hasattr(fh, "close"):
                fh.close()
        self._prune_worker_logs(os.path.dirname(log_path), prune_prefix,
                                keep=5)
        logger.info("launched worker pid=%d process_id=%d/%d coord=%s "
                    "(log %s)", proc.pid, outcome.process_id,
                    outcome.num_processes, outcome.coordinator_addr,
                    log_path)
        return WorkerContext(proc, outcome.process_id,
                             outcome.num_processes, self._restart_count,
                             log_path=log_path)

    def _prune_worker_logs(self, log_dir: str, prefix: str, keep: int = 5):
        """Cap this agent's per-restart worker logs (oldest deleted).

        `prefix` comes from the launch site so it always matches the active
        naming scheme (config.log_dir files have no pid component — a
        hardcoded pid prefix silently never pruned them).  Ordered by
        mtime, NOT filename — lexicographic sort would rank r10 before r2
        and delete the newest logs once restarts hit 10."""
        try:
            mine = sorted(
                (f for f in os.listdir(log_dir) if f.startswith(prefix)),
                key=lambda f: os.path.getmtime(os.path.join(log_dir, f)))
            for stale in mine[:-keep]:
                os.unlink(os.path.join(log_dir, stale))
        except OSError:
            pass

    def _worker_log_tail(self, max_bytes: int = 4000) -> str:
        """Last bytes of the failed worker's captured output — the
        traceback the master's error catalogue classifies."""
        path = getattr(self._worker, "log_path", "")
        if not path:
            return ""
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - max_bytes))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def _stop_worker(self, timeout: float = 30.0):
        """Stop the worker and wait until its whole process GROUP is
        gone (the worker leads its own session): an accelerator stays
        held until the last process that opened it has exited, and a
        worker that died on its own (`os._exit`, a signal) can leave
        children behind — the next generation must not be launched onto
        devices the old one still holds."""
        if self._worker is None:
            return
        from ..telemetry import spans as tspans

        with tspans.span("agent:stop_worker"):
            self._stop_worker_group(self._worker.proc, timeout)
        self._worker = None

    @staticmethod
    def _stop_worker_group(proc: subprocess.Popen, timeout: float):
        pgid = proc.pid

        def _signal_group(sig) -> bool:
            try:
                os.killpg(pgid, sig)
                return True
            except ProcessLookupError:
                return False  # nobody left in the group

        deadline = time.monotonic() + timeout
        alive = _signal_group(signal.SIGTERM)
        while alive and time.monotonic() < deadline:
            proc.poll()  # reap the leader, or its zombie keeps the group
            time.sleep(0.05)
            alive = _signal_group(0)
        if alive:
            _signal_group(signal.SIGKILL)
            proc.wait(timeout=10)
            while _signal_group(0) and \
                    time.monotonic() < deadline + 10:
                time.sleep(0.05)

    def _start_heartbeat(self):
        def _loop():
            while not self._stopped.wait(JobConstant.HEARTBEAT_INTERVAL_SECS):
                try:
                    resp = self.mc.report_heart_beat_full()
                    if resp.action == "restart":
                        # capture the ceiling BEFORE the worker-liveness
                        # check: the master clears it one-shot, and it must
                        # not be lost to a restart-in-progress race
                        if resp.rollback_before_step >= 0:
                            # loss-spike rollback: the relaunched worker must
                            # resume from a ckpt BEFORE the spike (ADVICE r4
                            # — the latest commit may postdate spike onset)
                            self._rollback_before = resp.rollback_before_step
                        if self._worker is not None:
                            logger.info("master requested worker restart"
                                        " (rollback_before=%d)",
                                        resp.rollback_before_step)
                            self._stop_worker()
                except Exception:  # noqa: BLE001
                    logger.warning("heartbeat failed", exc_info=True)
                try:
                    self._apply_policy_knobs()
                except Exception:  # noqa: BLE001 — knob pickup is
                    pass           # best-effort, never kills the heartbeat

        self._heartbeat_thread = threading.Thread(
            target=_loop, daemon=True, name="dwt-agent-heartbeat")
        self._heartbeat_thread.start()

    def _apply_policy_knobs(self):
        """Heartbeat-cadence pickup of the agent-owned policy knob: the
        replica ring fan-out (the trainer owns cadence/fused-K/tier —
        it applies them at fusion boundaries).  Decision ids are
        monotonic, so a replayed master re-serves the same decision and
        the dedup keeps this idempotent."""
        if self._replica_manager is None:
            return
        d = self.mc.get_policy_decision()
        did = int(getattr(d, "decision_id", 0) or 0)
        if did <= self._policy_seen:
            return
        self._policy_seen = did
        if int(getattr(d, "replica_count", -1)) >= 0:
            self._replica_manager.set_replica_count(d.replica_count)

    # --------------------------------------------------------------- run loop

    def _flush_flight(self, reason: str):
        """Dump the flight-recorder ring next to the checkpoints (best
        effort — the saver's latest persist path is the anchor)."""
        from ..telemetry.recorder import get_recorder

        path = (getattr(self._saver, "_latest_path", "") or
                os.getenv("DWT_CKPT_DIR", ""))
        if path:
            get_recorder().flush(path, reason)

    def run(self) -> int:
        """Supervisor loop. Parity: reference `_invoke_run` (:580)."""
        from ..telemetry import spans as tspans

        tspans.set_process_role("agent")
        self._start_saver()
        self._start_heartbeat()
        from .config_tuner import ParalConfigTuner

        self._config_tuner = ParalConfigTuner(self.mc)
        self._config_tuner.start()
        self.mc.register_node(self.node_rank,
                              accelerator_num=self.config.nproc_per_node)
        try:
            while not self._stopped.is_set():
                # one span a turn of the loop: a worker generation, from
                # its rendezvous to what its exit set off
                with tspans.span(
                        "agent:generation",
                        {"restart_count": self._restart_count}) as rec:
                    code = self._run_generation(rec["attrs"])
                if code is not None:
                    return code
            return 1
        finally:
            if self._restart_count:
                # the restarts as whole trees (the fault's own dump was
                # written before the agent had dealt with it)
                self._flush_flight("agent-exit")

    def _run_generation(self, attrs: Dict) -> Optional[int]:
        """One turn of the supervisor loop.  The code `run` returns, or
        None to go round again."""
        from ..telemetry import spans as tspans

        outcome = self.rendezvous()
        if self._saver is not None:
            # commit must wait for EVERY rank's done-file — tell the saver
            # the current world size (reference ckpt_saver.py:863).  Ranks
            # are re-assigned each rendezvous (compacted on scale-down),
            # so the saver's committer/global-rank identity must follow.
            # Routed through the event queue: applies on the saver thread,
            # never racing an in-flight save.
            from ..checkpoint.ckpt_saver import CheckpointEvent

            self._saver._event_queue.put(CheckpointEvent.update_world(
                outcome.num_processes, outcome.process_id))
        try:
            with tspans.span("agent:replication_setup"):
                self._setup_replication(outcome)
            if self._replica_manager is not None:
                self._saver.post_save_hook = \
                    lambda step: self._replica_manager.backup()
        except Exception:  # noqa: BLE001 — replication is best-effort
            logger.exception("checkpoint replication setup failed")
        self._worker = self._launch_worker(outcome)
        self._kick_warm_pool(outcome)
        exit_code = attrs["exit_code"] = self._monitor_worker()
        if exit_code == 0:
            logger.info("worker succeeded")
            return 0
        if exit_code is None:
            # membership change → restart workers into a new world
            logger.info("membership change — restarting worker")
            self._stop_worker()
            return None
        # failure path
        logger.warning("worker failed with exit code %s", exit_code)
        self._flush_flight("worker-fault")
        if self._saver is not None:
            try:
                with tspans.span("agent:failure_save"):
                    self._saver.save_shm_to_storage()
            except Exception:  # noqa: BLE001
                logger.exception("failure-save failed")
        # normalize Python's negative signal codes to shell style
        # (-9 → 137) so the master's error catalogue can classify
        # signal deaths (SIGKILL=OOM-kill, SIGTERM=preemption)
        report_code = 128 - exit_code if exit_code < 0 else exit_code
        error_data = f"exit_code={report_code}"
        tail = self._worker_log_tail()
        if tail:
            error_data += "\n" + tail
            # stderr is captured to a file now — echo the tail so local
            # runs still show the traceback on the console
            logger.error("worker stderr tail:\n%s", tail[-1500:])
        resp = self.mc.report_failure(error_data,
                                      restart_count=self._restart_count)
        if resp is not None and not getattr(resp, "success", True):
            # master's error catalogue says restarts can't fix this
            # class (e.g. user-code error) — stop burning restarts
            logger.error("master: %s — not restarting",
                         getattr(resp, "reason", ""))
            return exit_code
        self._restart_count += 1
        if self._restart_count > self.config.max_restarts:
            logger.error("max restarts (%d) exhausted",
                         self.config.max_restarts)
            return exit_code
        self._stop_worker()
        return None

    def _kick_warm_pool(self, outcome: RendezvousOutcome,
                        spec_wait_s: float = 120.0):
        """Speculatively compile the post-failure meshes while the world
        is healthy (auto/warm_pool.py).

        The worker publishes its compile spec (model + strategy + batch)
        once its own auto_accelerate runs; a daemon thread here waits for
        a spec matching THIS world, then launches warm children for the
        degraded worlds (N−1 nodes).  The agent owns the lifecycle: it
        survives worker death, so warming keeps running right through the
        window where it matters.  DWT_WARM_POOL=0 disables.
        """
        if os.getenv("DWT_WARM_POOL", "1") == "0":
            return
        if outcome.num_processes <= 1:
            return  # no degraded world below a single node
        self._warm_generation += 1
        generation = self._warm_generation
        world_devices = outcome.num_processes * outcome.local_world_size

        def _wait_and_warm():
            from ..auto.compile_cache import resolve_cache_dir
            from ..auto.warm_pool import WarmPool, load_current_spec

            cache_dir = resolve_cache_dir()
            deadline = time.monotonic() + spec_wait_s
            while time.monotonic() < deadline and not self._stopped.is_set() \
                    and generation == self._warm_generation:
                spec = load_current_spec(cache_dir)
                # only a spec from THIS world: a stale file from the
                # previous (larger) world would warm the wrong meshes
                if spec is not None and \
                        spec.n_devices == world_devices:
                    if self._warm_pool is None:
                        self._warm_pool = WarmPool(cache_dir)
                    procs = self._warm_pool.warm_degraded(
                        spec, num_nodes=outcome.num_processes,
                        devices_per_node=outcome.local_world_size)
                    if procs:
                        logger.info(
                            "warm pool: %d degraded-mesh compiles "
                            "launched for world of %d", len(procs),
                            world_devices)
                    return
                time.sleep(2.0)

        threading.Thread(target=_wait_and_warm, daemon=True,
                         name="dwt-warm-pool").start()

    def _monitor_worker(self) -> Optional[int]:
        """Wait for worker exit or membership change.

        Returns exit code, or None when a re-rendezvous is needed.
        """
        from ..telemetry import spans as tspans

        proc = self._worker.proc
        while not self._stopped.is_set():
            code = proc.poll()
            if code is not None:
                # the poll SAW the exit here; the exit itself lies up to
                # one interval back (bounded by it, not measured)
                tspans.span_event("agent:worker_exit", {
                    "exit_code": code,
                    "poll_interval_s": self.config.monitor_interval})
                return code
            if self._membership_changed():
                return None
            time.sleep(self.config.monitor_interval)
        return proc.poll() if proc.poll() is not None else 1

    def _membership_changed(self) -> bool:
        """Parity: reference `_membership_changed` :711 (debounced)."""
        now = time.time()
        if now - self._last_restart_ts < JobConstant.RESTART_DEBOUNCE_SECS:
            return False
        try:
            waiting = self.mc.num_nodes_waiting()
        except Exception:  # noqa: BLE001
            return False
        if waiting > 0:
            self._last_restart_ts = now
            return True
        return False

    def stop(self):
        self._stopped.set()
        self._warm_generation += 1
        if self._warm_pool is not None:
            self._warm_pool.stop()
            self._warm_pool = None
        self._stop_worker()
        tuner = getattr(self, "_config_tuner", None)
        if tuner is not None:
            tuner.stop()
        if self._saver is not None:
            AsyncCheckpointSaver.reset()
            self._saver = None


def launch_agent(config: ElasticLaunchConfig, entrypoint: List[str],
                 master_addr: str, node_id: int, node_rank: int) -> int:
    """Parity: reference launch_agent (training.py:734)."""
    config.auto_configure_params()
    mc = MasterClient(master_addr, node_id)
    agent = ElasticAgent(config, mc, node_id, node_rank, entrypoint)
    if config.network_check:
        from .node_check import run_network_check
        ok = run_network_check(agent)
        if not ok:
            logger.error("node failed network check")
            return 3
    try:
        return agent.run()
    finally:
        agent.stop()
