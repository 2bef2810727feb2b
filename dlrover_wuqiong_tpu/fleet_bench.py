"""Synthetic-fleet RPC benchmark: spawned master + hundreds of clients.

Parity: the reference has no control-plane load harness — masters are
sized by running real jobs (`dlrover/python/master/dist_master.py:86`
composes managers with no benchmark hook; `master/servicer.py` RPC
handlers are exercised only by live agents).  Redesign: on TPU slices a
single journaled master fronts hundreds of hosts, so its RPC ceiling is
a first-class perf surface — this module is the proof harness for the
group-commit control plane (master/journal.py): one master SUBPROCESS
(the real ``python -m
dlrover_wuqiong_tpu.master`` entry, journal enabled) is hammered by
hundreds of threaded `MasterClient`s spread over several worker
PROCESSES — client processes, not threads, because a single python
process tops out near 4k rpc/s on the GIL and would measure itself, not
the master.  The workload mixes the three verb classes exactly as a
real fleet does (agent/master_client.py):

  journaled  kv_store_set / kv_store_add — durable frame before the ack
  buffered   goodput-ledger / custom-metric reports — never journaled
  polling    waiting-num / journal-stats gets — read-only

Two phases, same machinery: ``--group-commit-max-frames=1`` (the
historical per-frame-fsync baseline) vs the group-commit default.  The
headline evidence is journaled-verb throughput ratio + `rpc_p99_ms` +
`journal_batch_mean` (frames per fsync), reported as ADD-ONLY keys in
bench.py's single-line JSON and streamed per-round by
``tools/perf_probe.py rpc``.

CPU-only by construction: nothing here touches an accelerator (client
procs never import jax — verified by test_fleet_bench), so the numbers
do not depend on any device link.
"""

from __future__ import annotations

import math
import multiprocessing as mp
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

VERB_CLASSES = ("journaled", "buffered", "polling")

#: workers sleep until this shared wall-clock instant so every client
#: thread measures the SAME window (spawn/import skew stays outside it)
_START_LEAD_S = 6.0


def _client_thread(addr: str, node_id: int, start_at: float,
                   duration_s: float, out: Dict):
    """One synthetic agent: a mixed verb cycle until the deadline.

    Latencies are wall milliseconds per completed RPC, bucketed by verb
    class.  The mix is journaled-HEAVY (4 journaled : 1 buffered :
    1 polling) — an elastic fleet's hot verbs (task results, kv
    barriers, serve submissions) are the journaled ones, and they are
    what per-frame fsync convoys.  RPC failures (a timed-out frame
    behind a convoyed journal) are COUNTED, not fatal: baseline stalls
    are evidence, not a bench crash.
    """
    from .agent.master_client import MasterClient
    from .common.comm import RpcError

    cli = MasterClient(addr, node_id, outage_grace_s=30.0)
    lat: Dict[str, List[float]] = {c: [] for c in VERB_CLASSES}
    done_in_window: Dict[str, int] = {c: 0 for c in VERB_CLASSES}
    errors = 0
    key = f"fleet-{node_id}"
    ledger = {"states": {"productive": 1.0}, "wall_s": 1.0,
              "other_s": 0.0, "goodput_fraction": 1.0}
    now = time.time()
    if start_at > now:
        time.sleep(start_at - now)
    deadline = time.monotonic() + duration_s
    step = 0

    def timed(cls, fn, *args):
        nonlocal errors
        t0 = time.perf_counter()
        try:
            fn(*args)
        except RpcError:  # includes MasterUnreachableError
            errors += 1
            return
        lat[cls].append((time.perf_counter() - t0) * 1e3)
        # throughput counts only IN-WINDOW completions — a per-frame
        # baseline stalling RPCs for seconds must not bank the late tail
        # as window throughput (latency keeps the tail for p99)
        if time.monotonic() <= deadline:
            done_in_window[cls] += 1

    try:
        while time.monotonic() < deadline:
            step += 1
            timed("journaled", cli.kv_store_set, key, b"x%d" % step)
            timed("journaled", cli.kv_store_add, "fleet-counter", 1)
            timed("journaled", cli.kv_store_set, key + "b", b"y%d" % step)
            timed("journaled", cli.kv_store_add, f"fc{node_id % 8}", 1)
            timed("buffered", cli.report_goodput_ledger, ledger)
            timed("polling", cli.num_nodes_waiting)
    finally:
        cli.close()
    out[node_id] = {"lat": lat, "done": done_in_window, "errors": errors}


def _fleet_worker(addr: str, proc_idx: int, threads: int, start_at: float,
                  duration_s: float, conn):
    """Spawn target (module-level: picklable): one client process."""
    results: Dict[int, Dict[str, List[float]]] = {}
    ts = []
    for t in range(threads):
        node_id = 1000 + proc_idx * threads + t
        th = threading.Thread(
            target=_client_thread,
            args=(addr, node_id, start_at, duration_s, results),
            daemon=True)
        th.start()
        ts.append(th)
    for th in ts:
        th.join(duration_s + _START_LEAD_S + 60.0)
    merged: Dict = {c: [] for c in VERB_CLASSES}
    merged["done"] = {c: 0 for c in VERB_CLASSES}
    merged["errors"] = 0
    for got in results.values():
        for c in VERB_CLASSES:
            merged[c] += got["lat"][c]
            merged["done"][c] += got["done"][c]
        merged["errors"] += got["errors"]
    conn.send(merged)
    conn.close()


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(math.ceil(q * len(sorted_vals))) - 1)
    return sorted_vals[max(0, idx)]


class FleetMaster:
    """A journal-enabled master subprocess for benchmark phases.

    Context manager: spawns ``python -m dlrover_wuqiong_tpu.master`` with
    the group-commit knob under test, waits until connectable, and
    SIGTERMs it on exit.  ``journal_stats()`` polls the read-only gauge
    verb from the parent process.
    """

    def __init__(self, group_commit: bool,
                 max_frames: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 fsync_floor_ms: float = 0.0,
                 standby: bool = False):
        self.group_commit = group_commit
        self.max_frames = 1 if not group_commit else (max_frames or 256)
        self.max_wait_ms = max_wait_ms
        self.fsync_floor_ms = fsync_floor_ms
        # attach a warm standby (master/standby.py) tailing this master's
        # journal with NO lease (pure mirror, never promotes): the bench
        # phase proving shipping stays off the commit path (ISSUE 20)
        self.standby = standby
        self.standby_addr = ""
        self.addr = ""
        self._proc: Optional[subprocess.Popen] = None
        self._standby_proc: Optional[subprocess.Popen] = None
        self._work = ""

    def __enter__(self) -> "FleetMaster":
        from .common.comm import addr_connectable, find_free_port

        self._work = tempfile.mkdtemp(prefix="dwt-fleet-")
        port = find_free_port()
        self.addr = f"127.0.0.1:{port}"
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            # steady-state commit throughput: keep compaction (which
            # fences the queue) out of the measured window
            DWT_CTX_JOURNAL_SNAPSHOT_EVERY="100000000",
            # slow-storage emulation (journal.py): local NVMe fsyncs in
            # ~0.1ms, production masters journal to PD-class disks
            DWT_JOURNAL_FSYNC_FLOOR_MS=str(int(self.fsync_floor_ms)),
            PYTHONPATH=repo_root + os.pathsep +
            os.environ.get("PYTHONPATH", ""))
        args = [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
                f"--port={port}", "--min_nodes=1", "--max_nodes=1",
                f"--journal-dir={os.path.join(self._work, 'journal')}",
                "--poll-interval=1.0",
                f"--group-commit-max-frames={self.max_frames}"]
        if self.max_wait_ms is not None:
            args.append(f"--group-commit-max-wait-ms={self.max_wait_ms}")
        self._proc = subprocess.Popen(
            args, env=env, cwd=self._work, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and \
                not addr_connectable(self.addr):
            if self._proc.poll() is not None:
                raise RuntimeError(
                    "fleet master died on startup: "
                    + (self._proc.stdout.read() or "")[-2000:])
            time.sleep(0.1)
        if not addr_connectable(self.addr):
            raise RuntimeError("fleet master never came up")
        if self.standby:
            sb_port = find_free_port()
            self.standby_addr = f"127.0.0.1:{sb_port}"
            # a mirror does not need failover-grade 50ms polls: 0.2s
            # keeps lag to ~one pull of frames while the tailer's wakeup
            # + fetch cost stays off the same (possibly single) CPU the
            # measured master is on — the retention gauge compares
            # THROUGHPUT, and scheduler steal would masquerade as
            # shipping cost
            sb_env = dict(env, DWT_STANDBY_POLL_S="0.2")
            self._standby_proc = subprocess.Popen(
                [sys.executable, "-m", "dlrover_wuqiong_tpu.master",
                 f"--port={sb_port}", "--min_nodes=1", "--max_nodes=1",
                 f"--journal-dir={os.path.join(self._work, 'jrnl-sb')}",
                 "--poll-interval=1.0", f"--standby-of={self.addr}"],
                env=sb_env, cwd=self._work, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            # gate the phase on the mirror actually flowing: the
            # primary's lag gauge goes live on the standby's first fetch
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if self._standby_proc.poll() is not None:
                    raise RuntimeError(
                        "fleet standby died on startup: "
                        + (self._standby_proc.stdout.read() or "")[-2000:])
                if self.journal_stats()["standby_lag_frames"] >= 0:
                    break
                time.sleep(0.1)
            else:
                raise RuntimeError("fleet standby never fetched")
        return self

    def journal_stats(self) -> Dict:
        from .agent.master_client import MasterClient

        cli = MasterClient(self.addr, node_id=-2, outage_grace_s=10.0)
        try:
            st = cli.get_journal_stats()
            return {"enabled": st.enabled, "group_commit": st.group_commit,
                    "max_frames": st.max_frames,
                    "max_wait_ms": st.max_wait_ms,
                    "fsync_floor_ms": st.fsync_floor_ms,
                    "batches": st.batches, "frames": st.frames,
                    "batch_mean": round(st.batch_mean, 2),
                    "batch_max": st.batch_max,
                    "durable_seq": st.durable_seq, "epoch": st.epoch,
                    "shipped_seq": st.shipped_seq,
                    "standby_lag_frames": st.standby_lag_frames}
        finally:
            cli.close()

    def __exit__(self, *exc):
        for proc in (self._standby_proc, self._proc):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=10.0)
        return False


def run_fleet(addr: str, clients: int = 200, procs: int = 8,
              duration_s: float = 2.0) -> Dict:
    """Hammer `addr` with `clients` threads across `procs` processes.

    Returns per-class counts/rates/latency tails plus the aggregate
    ``rpc_per_s`` / ``rpc_p99_ms`` over one shared measurement window.
    """
    threads = max(1, math.ceil(clients / procs))
    ctx = mp.get_context("spawn")  # never fork a jax-initialized parent
    start_at = time.time() + _START_LEAD_S  # graftlint: disable=wall-clock-duration -- cross-process start barrier: spawn'd workers sleep until this shared wall-clock instant
    pipes, workers = [], []
    for p in range(procs):
        rx, tx = ctx.Pipe(duplex=False)
        w = ctx.Process(target=_fleet_worker,
                        args=(addr, p, threads, start_at, duration_s, tx),
                        daemon=True)
        w.start()
        tx.close()
        pipes.append(rx)
        workers.append(w)
    merged: Dict = {c: [] for c in VERB_CLASSES}
    done: Dict[str, int] = {c: 0 for c in VERB_CLASSES}
    errors = 0
    for rx in pipes:
        got = rx.recv()
        for c in VERB_CLASSES:
            merged[c] += got[c]
            done[c] += got["done"][c]
        errors += got["errors"]
    for w in workers:
        w.join(timeout=30.0)
        if w.is_alive():
            w.terminate()
    report: Dict = {"clients": procs * threads, "procs": procs,
                    "duration_s": duration_s}
    all_lat: List[float] = []
    for c in VERB_CLASSES:
        vals = sorted(merged[c])
        all_lat += vals
        report[c] = {
            "count": done[c],
            "rpc_per_s": round(done[c] / duration_s, 1),
            "p50_ms": round(_percentile(vals, 0.50), 3),
            "p99_ms": round(_percentile(vals, 0.99), 3),
        }
    all_lat.sort()
    report["rpc_total"] = sum(done.values())
    report["rpc_errors"] = errors
    report["rpc_per_s"] = round(sum(done.values()) / duration_s, 1)
    report["rpc_p99_ms"] = round(_percentile(all_lat, 0.99), 3)
    return report


#: bench phases, interleaved per round: per-frame-fsync baseline,
#: group-commit default, and group commit with a warm standby attached
#: (journal shipping must stay OFF the commit path — ISSUE 20)
_MODES = ("perframe", "grouped", "standby")


def fleet_bench(clients: int = 200, procs: int = 8,
                duration_s: float = 2.0, rounds: int = 2,
                fsync_floor_ms: float = 3.0) -> Dict:
    """A/B the per-frame-fsync baseline vs group commit, INTERLEAVED.

    Phases alternate per round (the same same-session interleave
    discipline as the kernel A/B probes — host load drifts), counts
    accumulate across rounds, and each phase gets a FRESH master so
    batch gauges attribute cleanly.  The headline ratio is
    journaled-verb throughput: grouped / per-frame.  The third phase
    re-runs the grouped shape with a warm STANDBY tailing the journal
    (no lease — pure mirror): acks gate on the local durable watermark
    only, so ``standby_retention`` must stay near 1.0 (shipping that
    re-serialized group commit would crater it) and the phase's journal
    gauges carry the shipped-seq/lag evidence.

    ``fsync_floor_ms`` pads each journal sync to the PRODUCTION storage
    regime (network-attached PD-class disks: 1-5ms per sync; this host's
    local NVMe fsyncs in ~0.1ms, which no real master journal rides).
    All phases pay the SAME floor per sync — group commit amortizes it,
    per-frame eats it per RPC — and the floor used is reported in every
    phase's journal gauges.  Pass 0 to measure bare local-disk fsync.
    """
    acc: Dict[str, Dict] = {}
    for mode in _MODES:
        acc[mode] = {c: {"count": 0} for c in VERB_CLASSES}
        acc[mode]["lat"] = {c: [] for c in VERB_CLASSES}
        acc[mode]["seconds"] = 0.0
        acc[mode]["errors"] = 0
        acc[mode]["journal"] = {}
    for _ in range(max(1, rounds)):
        for mode in _MODES:
            with FleetMaster(group_commit=(mode != "perframe"),
                             fsync_floor_ms=fsync_floor_ms,
                             standby=(mode == "standby")) as fm:
                got = run_fleet(fm.addr, clients=clients, procs=procs,
                                duration_s=duration_s)
                acc[mode]["seconds"] += duration_s
                for c in VERB_CLASSES:
                    acc[mode][c]["count"] += got[c]["count"]
                    acc[mode]["lat"][c].append(
                        (got[c]["p50_ms"], got[c]["p99_ms"]))
                acc[mode]["rpc_p99_ms"] = got["rpc_p99_ms"]
                acc[mode]["errors"] += got["rpc_errors"]
                acc[mode]["journal"] = fm.journal_stats()
    out: Dict = {"clients": clients, "procs": procs, "rounds": rounds,
                 "fsync_floor_ms": fsync_floor_ms}
    for mode in _MODES:
        secs = acc[mode]["seconds"] or 1.0
        summ = {"rpc_p99_ms": acc[mode]["rpc_p99_ms"],
                "rpc_errors": acc[mode]["errors"],
                "journal": acc[mode]["journal"]}
        total = 0
        for c in VERB_CLASSES:
            n = acc[mode][c]["count"]
            total += n
            tails = acc[mode]["lat"][c]
            summ[c] = {"rpc_per_s": round(n / secs, 1),
                       "p99_ms": round(max(t[1] for t in tails), 3)}
        summ["rpc_per_s"] = round(total / secs, 1)
        out[mode] = summ
    base = out["perframe"]["journaled"]["rpc_per_s"]
    grouped = out["grouped"]["journaled"]["rpc_per_s"]
    shipped = out["standby"]["journaled"]["rpc_per_s"]
    out["journaled_speedup"] = round(grouped / base, 2) if base else 0.0
    # the ISSUE 20 acceptance gauge: journaled rpc/s retained with a
    # standby attached (>= 0.9 of no-standby proves shipping is async)
    out["standby_retention"] = (round(shipped / grouped, 3)
                                if grouped else 0.0)
    out["standby_lag_frames"] = out["standby"]["journal"].get(
        "standby_lag_frames", -1)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m dlrover_wuqiong_tpu.fleet_bench`` — one JSON line.

    Runs in its own light process on purpose: the spawn'd client workers
    re-import THIS module's ``__main__``, which never touches jax — a
    heavy caller (bench.py) shells out here instead of spawning from its
    own jax-loaded interpreter.
    """
    import argparse
    import json

    p = argparse.ArgumentParser(
        prog="python -m dlrover_wuqiong_tpu.fleet_bench",
        description="synthetic-fleet control-plane RPC benchmark")
    p.add_argument("--clients", type=int, default=200)
    p.add_argument("--procs", type=int, default=8)
    p.add_argument("--duration-s", type=float, default=3.0)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--fsync-floor-ms", type=float, default=3.0,
                   help="per-sync storage-latency emulation (0 = bare "
                        "local fsync; default 3ms = PD-class disk)")
    args = p.parse_args(argv)
    out = fleet_bench(clients=args.clients, procs=args.procs,
                      duration_s=args.duration_s, rounds=args.rounds,
                      fsync_floor_ms=args.fsync_floor_ms)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
