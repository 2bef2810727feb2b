"""Warm-pool state probe: ONE JSON line for the driver to snapshot.

Reads only the compile-cache directory's JSON sidecars (no JAX import —
runs in milliseconds, safe from cron/CI):

    python tools/warm_report.py [cache_dir]
    python tools/warm_report.py --cache-dir DIR

cache_dir defaults to JAX_COMPILATION_CACHE_DIR, else the framework default
(/tmp/dwt-compile-cache-<user>).  Fields:

- warm_meshes: ready warm-pool entries (mesh, device count, compile_s,
  whether the XLA entry already existed when the pool child compiled)
- warm_device_counts: {n_devices: ready entries} — what the master's
  WarmMeshPolicy sees
- serve: framework-key serve accounting across process generations
  (warm_hits = auto_accelerate calls whose exact topology a prior
  process had compiled; pool_hits = serves that found a ready pool
  entry for their key)
- cache_entries / cache_dir_bytes: the XLA layer's footprint
- inflight: warm children still compiling (stale markers expire in 10
  min — see auto/warm_pool.py)

Runs under the shared report-CLI contract (common/report_cli.py): -h to
stderr rc=0, failures are one ``{"error": ...}`` line rc=1 — this tool
has no live-master mode, the cache dir itself is the source.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _report(cache_dir: str) -> dict:
    from dlrover_wuqiong_tpu.auto.compile_cache import (
        cache_dir_bytes,
        pool_dir,
        registry_entries,
        serve_stats,
    )
    from dlrover_wuqiong_tpu.auto.warm_pool import (
        WarmPool,
        warm_device_counts,
    )

    report = {
        "cache_dir": cache_dir,
        "exists": os.path.isdir(cache_dir),
        "warm_meshes": [],
        "warm_device_counts": {},
        "serve": {"serves": 0, "warm_hits": 0, "cold_misses": 0,
                  "pool_hits": 0},
        "framework_keys": 0,
        "cache_entries": 0,
        "cache_dir_bytes": 0,
        "inflight": 0,
    }
    if report["exists"]:
        pool = WarmPool(cache_dir)
        status = pool.status()
        report["warm_meshes"] = [
            {k: e.get(k) for k in ("mesh", "n_devices", "compile_s",
                                   "platform", "already_cached")}
            for e in status["entries"] if e.get("ready")]
        report["warm_device_counts"] = {
            str(k): v for k, v in warm_device_counts(cache_dir).items()}
        report["inflight"] = status["inflight"]
        report["serve"] = serve_stats(cache_dir)
        report["framework_keys"] = len(registry_entries(cache_dir))
        try:
            report["cache_entries"] = sum(
                1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
        except OSError:
            pass
        report["cache_dir_bytes"] = cache_dir_bytes(cache_dir)
        # referenced so a refactor that drops the helper fails HERE, in
        # the tool that documents it, not silently in the master
        assert pool_dir(cache_dir)
    return report


def main(argv=None) -> int:
    argv = list(argv) if argv is not None else sys.argv[1:]
    from dlrover_wuqiong_tpu.common.report_cli import run_report

    def _offline(vals):
        from dlrover_wuqiong_tpu.auto.compile_cache import (
            resolve_cache_dir)

        # the historical positional form (`warm_report.py DIR`) keeps
        # working alongside the flag (tests/test_warm_pool.py drives it)
        positional = [a for a in argv if not a.startswith("-")]
        cache_dir = (vals.get("--cache-dir")
                     or (positional[0] if positional
                         else resolve_cache_dir()))
        return _report(cache_dir)

    def _no_live(addr, vals):
        # unreachable: _offline always returns a report
        raise RuntimeError("warm_report has no live-master mode")

    return run_report(
        argv, __doc__,
        offline=_offline,
        live=_no_live,
        no_addr_error="warm_report reads the cache dir, not the master",
        value_flags=("--cache-dir",))


if __name__ == "__main__":
    sys.exit(main())
