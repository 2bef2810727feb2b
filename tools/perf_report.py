"""Perf-observatory report: ONE JSON line for the driver/operator.

Three sources, one schema family (telemetry/perf.py PERF_SNAPSHOT_KEYS):

    python tools/perf_report.py [--addr HOST:PORT]    # live master RPC
    python tools/perf_report.py --flight CKPT_DIR     # offline dumps
    python tools/perf_report.py --baseline CKPT_DIR   # baseline store

Live mode pulls the master's per-node latest PerfSnapshot aggregation
(each node's BUFFERED latest-SENT-wins PerfSnapshotReport —
master/master.py perf_summary) plus the job-level regression/retrace
totals.  The address defaults to DWT_MASTER_ADDR.

Offline ``--flight`` reads the flight-recorder dumps under
$CKPT_DIR/flight/ (written on fault/SIGTERM/drill flush): each dump
embeds the process's latest PerfSnapshot, and only the LATEST per
(role, pid) counts — snapshots are cumulative like the goodput ledger.

Offline ``--baseline`` reads the versioned perf-baseline store at
$CKPT_DIR/perf/baseline.json (atomic tmp+rename publishes, robust
median+MAD per executable key) and reports the rolling stats the
regression sentinel judges against.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _trim(snap: dict) -> dict:
    return {k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in sorted(snap.items())}


def _from_master(addr: str) -> dict:
    from dlrover_wuqiong_tpu.agent.master_client import MasterClient

    mc = MasterClient(addr, node_id=-1)
    try:
        s = mc.get_perf_summary()
    finally:
        mc.close()
    return {
        "source": "master", "addr": addr, "nodes": s.nodes,
        "regressions": s.regressions, "retraces": s.retraces,
        "tuned_variants": {nid: str(snap.get("tuned_variant", ""))
                           for nid, snap in sorted(s.snapshots.items())},
        "snapshots": {nid: _trim(snap)
                      for nid, snap in sorted(s.snapshots.items())},
    }


def _from_flight(ckpt_dir: str) -> dict:
    from dlrover_wuqiong_tpu.telemetry import load_flight_dumps

    if not os.path.isdir(ckpt_dir):
        raise FileNotFoundError(
            f"--flight: {ckpt_dir!r} is not a directory")
    dumps = load_flight_dumps(ckpt_dir)
    if not dumps:
        raise FileNotFoundError(
            f"--flight: no flight-recorder dumps under "
            f"{os.path.join(ckpt_dir, 'flight')!r}")
    latest = {}
    for d in dumps:
        if d.get("perf"):
            latest[(d.get("role"), d.get("pid"))] = d["perf"]
    snaps = {f"{role}:{pid}": _trim(snap)
             for (role, pid), snap in sorted(latest.items(),
                                             key=lambda kv: str(kv[0]))}
    return {
        "source": "flight", "ckpt_dir": ckpt_dir, "dumps": len(dumps),
        "nodes": len(snaps),
        "regressions": sum(int(s.get("regressions", 0))
                           for s in latest.values()),
        "retraces": sum(int(s.get("retraces", 0))
                        for s in latest.values()),
        "snapshots": snaps,
    }


def _from_baseline(path: str) -> dict:
    import json

    # accept the checkpoint dir (store lives at perf/baseline.json under
    # it) or a direct path to the json
    cand = path if os.path.isfile(path) else os.path.join(
        path, "perf", "baseline.json")
    if not os.path.isfile(cand):
        raise FileNotFoundError(
            f"--baseline: no baseline store at {cand!r}")
    with open(cand, "r", encoding="utf-8") as f:
        data = json.load(f)
    from dlrover_wuqiong_tpu.telemetry.perf import BaselineStore

    st = BaselineStore(path=cand)
    keys = {}
    for key in sorted(data.get("keys", {})):
        stats = st.stats(key) or {}
        keys[key] = {
            "n": int(stats.get("n", 0)),
            "median_s": round(float(stats.get("median", 0.0)), 6),
            "mad_s": round(float(stats.get("mad", 0.0)), 6),
            "categories": {c: round(m, 6) for c, m in
                           sorted(st.category_medians(key).items())},
        }
    return {"source": "baseline", "path": cand,
            "schema": int(data.get("schema", 0)), "keys": keys}


def main(argv=None) -> int:
    from dlrover_wuqiong_tpu.common.report_cli import run_report

    def _offline(v):
        if v.get("--baseline"):
            return _from_baseline(v["--baseline"])
        if v.get("--flight"):
            return _from_flight(v["--flight"])
        return None

    return run_report(
        argv, __doc__,
        offline=_offline,
        live=lambda addr, v: _from_master(addr),
        no_addr_error="no master address: pass --addr, set "
                      "DWT_MASTER_ADDR, or use --flight/--baseline "
                      "CKPT_DIR",
        value_flags=("--flight", "--baseline"))


if __name__ == "__main__":
    sys.exit(main())
