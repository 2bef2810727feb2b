"""Perf observatory: always-on in-train profiling windows, a versioned
perf-baseline store, and a regression sentinel wired into the policy loop.

Parity: reference `atorch/dev/xpu_timer/common/manager.cc` (always-on
kernel/collective timing exported to Prometheus) and the Brain-side
anomaly intent of `dlrover/python/master/stats/reporter.py` — but the
reference detects *hangs*, not *slow*: a job that silently loses 15%
throughput (a retrace storm, a degraded remat choice after a
re-mesh) passes every liveness check it has.

TPU redesign: per-op host hooks (LD_PRELOAD shims) don't exist on TPU,
so the observatory samples instead of intercepting — every N fusion
boundaries the trainer wraps ONE fused dispatch in a `StepProfiler`
window (utils/profiler.py) and this module folds the xplane op-category
split (utils/xplane.py) plus host step-time into a `PerfSnapshot` dict:

- windows are SELF-LIMITING: the measured profiling overhead (trace
  start/stop + xplane parse, host-side only — zero new device readbacks)
  is ledger-credited to the ``profile`` state and the next window is
  skipped until that overhead amortizes below ``overhead_budget`` (1%)
  of wall;
- snapshots are keyed by the FULL executable identity — strategy
  fingerprint, fused-K and backend — because each of those changes
  the HLO, and comparing step times across different executables is how
  perf dashboards lie;
- the baseline store (``$ckpt_dir/perf/baseline.json``) keeps ROBUST
  rolling stats per executable key (median + MAD — step times drift
  run to run, and means/stddevs would both chase outliers),
  published atomic tmp+rename like the preempt table;
- the regression sentinel fires a ``perf-regression`` event only after
  M CONSECUTIVE windows beyond the MAD bound (one slow window is
  weather, M in a row is climate), attributing the op category
  that moved; windows beyond the bound are NOT folded into the baseline
  (a sustained regression must not become the new normal);
- a compile/retrace observatory snapshots the persistent-cache counters
  (auto/compile_cache.py) per window: cache misses GROWING in steady
  state mean something is retracing the step — itself a ``retrace``
  event, because a retrace storm is a perf regression whose step time
  may look fine between compiles.

The sentinel/baseline math is deliberately jax-free (plain floats) so
`__graft_entry__.py`'s perf smoke and the chaos ``perf-regress`` drill
exercise the exact firing logic without a backend.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..common.log import get_logger

logger = get_logger("perf")

PERF_SCHEMA = 1

# ADD-ONLY (tests/test_perf.py pins it): consumers — flight dumps, the
# PerfSnapshotReport verb, tools/perf_report.py — key into this dict, so
# fields extend, never rename.
PERF_SNAPSHOT_KEYS = (
    "schema", "key", "step", "fused_k", "step_time_s",
    "baseline_median_s", "baseline_mad_s", "baseline_n", "categories",
    "overhead_s", "overhead_frac", "windows", "skipped",
    "cache_hits", "cache_misses", "retraces", "regressions",
    "last_event", "captured_at", "tuned_variant",
)

# ADD-ONLY: the perf-regression / retrace event envelope (node-event
# message payloads + incident timeline rows embed it verbatim).
PERF_EVENT_KEYS = (
    "kind", "key", "step", "step_time_s", "baseline_median_s",
    "baseline_mad_s", "deviation", "consecutive", "category",
    "category_delta_s",
)

# MAD → sigma for a normal distribution; the bound math quotes
# deviations in sigma-equivalents so thresholds read like z-scores.
_MAD_SIGMA = 1.4826


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _mad(xs: List[float], med: Optional[float] = None) -> float:
    if not xs:
        return 0.0
    m = _median(xs) if med is None else med
    return _median([abs(x - m) for x in xs])


def executable_key(strategy_fingerprint: str, fused_steps: int,
                   backend: str) -> str:
    """Digest of the full executable identity a step time belongs to:
    step times of different HLO must never share a baseline row."""
    blob = json.dumps({
        "strategy": str(strategy_fingerprint),
        "fused": int(fused_steps),
        "backend": str(backend),
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


class BaselineStore:
    """Rolling per-executable-key window stats at
    ``$ckpt_dir/perf/baseline.json`` (versioned, atomic tmp+rename like
    the preempt table — a crashed writer never tears the baseline).

    With an empty path the store is memory-only (drills, tests, jobs
    without a checkpoint dir)."""

    SCHEMA = 1

    def __init__(self, path: str = "", max_samples: int = 64):
        self.path = path
        self.max_samples = max_samples
        self._data: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------- state
    def _load(self) -> Dict[str, Any]:
        if self._data is not None:
            return self._data
        data: Dict[str, Any] = {"schema": self.SCHEMA, "keys": {}}
        if self.path and os.path.isfile(self.path):
            try:
                with open(self.path) as f:
                    raw = json.load(f)
                if isinstance(raw, dict) and isinstance(
                        raw.get("keys"), dict):
                    data["keys"] = raw["keys"]
            except (OSError, ValueError):
                # a torn/corrupt baseline is re-learned, never fatal
                logger.warning("unreadable perf baseline %s — starting "
                               "fresh", self.path, exc_info=True)
        self._data = data
        return data

    def _row(self, key: str) -> Dict[str, Any]:
        keys = self._load()["keys"]
        row = keys.get(key)
        if not isinstance(row, dict) or "step_s" not in row:
            row = {"step_s": [], "categories": {}}
            keys[key] = row
        return row

    # ----------------------------------------------------------- updates
    def update(self, key: str, step_time_s: float,
               categories: Optional[Dict[str, float]] = None) -> None:
        row = self._row(key)
        row["step_s"].append(float(step_time_s))
        del row["step_s"][:-self.max_samples]
        for cat, sec in (categories or {}).items():
            xs = row["categories"].setdefault(str(cat), [])
            xs.append(float(sec))
            del xs[:-self.max_samples]

    def stats(self, key: str) -> Optional[Dict[str, float]]:
        xs = self._row(key)["step_s"]
        if not xs:
            return None
        med = _median(xs)
        return {"median": med, "mad": _mad(xs, med), "n": len(xs)}

    def category_medians(self, key: str) -> Dict[str, float]:
        return {cat: _median(xs)
                for cat, xs in self._row(key)["categories"].items() if xs}

    # ----------------------------------------------------------- publish
    def publish(self) -> bool:
        """Atomic write-tmp-then-rename (fsync'd) — same durability shape
        as checkpoint markers and the preempt table."""
        if not self.path:
            return False
        data = self._load()
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
            return True
        except OSError:
            logger.warning("perf baseline publish to %s failed", self.path,
                           exc_info=True)
            return False


class RegressionSentinel:
    """M-consecutive-windows-beyond-the-MAD-bound detector (per key).

    The bound is ``median + max(nsig * 1.4826 * MAD, min_rel * median)``:
    the MAD term tracks the key's OBSERVED drift, the relative floor
    keeps a suspiciously quiet baseline (MAD≈0) from firing on ordinary
    run-to-run noise."""

    def __init__(self, store: BaselineStore, m_consecutive: int = 3,
                 nsig: float = 3.0, min_rel: float = 0.08,
                 min_baseline: int = 5):
        self.store = store
        self.m_consecutive = max(1, m_consecutive)
        self.nsig = nsig
        self.min_rel = min_rel
        self.min_baseline = max(1, min_baseline)
        self._streak: Dict[str, int] = {}

    def observe(self, key: str, step_time_s: float,
                categories: Optional[Dict[str, float]] = None,
                step: int = -1) -> Tuple[bool, Optional[Dict]]:
        """(beyond_bound, fired_event). Fires exactly once per excursion,
        on the M-th consecutive beyond-bound window."""
        stats = self.store.stats(key)
        if stats is None or stats["n"] < self.min_baseline:
            self._streak[key] = 0
            return False, None
        med, mad = stats["median"], stats["mad"]
        bound = med + max(self.nsig * _MAD_SIGMA * mad,
                          self.min_rel * med)
        if step_time_s <= bound:
            self._streak[key] = 0
            return False, None
        streak = self._streak.get(key, 0) + 1
        self._streak[key] = streak
        if streak != self.m_consecutive:
            return True, None
        cat, delta = self._attribute(key, categories)
        sigma = max(_MAD_SIGMA * mad, 1e-12)
        return True, {
            "kind": "perf-regression",
            "key": key,
            "step": step,
            "step_time_s": step_time_s,
            "baseline_median_s": med,
            "baseline_mad_s": mad,
            "deviation": (step_time_s - med) / sigma,
            "consecutive": streak,
            "category": cat,
            "category_delta_s": delta,
        }

    def _attribute(self, key: str,
                   categories: Optional[Dict[str, float]]
                   ) -> Tuple[str, float]:
        """The op category whose device time grew most vs its baseline
        median — 'what moved', not just 'something is slow'."""
        if not categories:
            return "", 0.0
        base = self.store.category_medians(key)
        best, best_delta = "", 0.0
        for cat, sec in categories.items():
            delta = float(sec) - base.get(cat, 0.0)
            if delta > best_delta:
                best, best_delta = cat, delta
        if not best:  # no category grew (host-side slowdown): largest wins
            best = max(categories, key=lambda c: categories[c])
            best_delta = 0.0
        return best, best_delta


class _Window:
    """One open profiling window (StepProfiler trace around one fused
    dispatch). Created by PerfObservatory.maybe_open, closed by .close."""

    def __init__(self, prof, ctx, span_ctx, step: int, fused_k: int,
                 tdir: str, open_cost_s: float, t_run0: float,
                 key: str = ""):
        self.prof = prof
        self.ctx = ctx
        self.span_ctx = span_ctx
        self.step = step
        self.fused_k = max(1, fused_k)
        self.tdir = tdir
        self.open_cost_s = open_cost_s
        self.t_run0 = t_run0
        # executable key CAPTURED at open time: `close` may run on the
        # trainer's metrics-pump thread while the main loop re-keys the
        # observatory for a fused-K cutover — the window must fold into
        # the baseline row of the executable it measured, not whichever
        # key is current when the pump drains it
        self.key = key


class PerfObservatory:
    """Window scheduler + snapshot folder + sentinel/retrace wiring.

    The trainer calls ``maybe_open(step, fused_k)`` at each eligible
    fusion boundary (one that already carries a host readback — the
    window must contain a sync so the trace holds the device work it
    claims to time, and reusing the existing one keeps the
    blocking-readback budget at ZERO new readbacks) and ``close(win)``
    right after that readback."""

    def __init__(self, key: str = "", ckpt_dir: str = "",
                 every: int = 8, m_consecutive: int = 3,
                 overhead_budget: float = 0.01,
                 nsig: float = 3.0, min_rel: float = 0.08,
                 min_baseline: int = 5, max_samples: int = 64,
                 registry=None, on_event: Optional[Callable] = None,
                 job_name: str = "dwt"):
        path = (os.path.join(ckpt_dir, "perf", "baseline.json")
                if ckpt_dir else "")
        self.store = BaselineStore(path, max_samples=max_samples)
        self.sentinel = RegressionSentinel(
            self.store, m_consecutive=m_consecutive, nsig=nsig,
            min_rel=min_rel, min_baseline=min_baseline)
        self.key = key
        self.every = max(1, every)
        self.overhead_budget = overhead_budget
        self.on_event = on_event
        self._job = job_name
        self._reg = registry
        self._t_start = time.monotonic()
        # counters shared between the trainer's main loop (maybe_open)
        # and its metrics-pump thread (close): one lock guards them all.
        # Blocking work — the baseline publish's fsync, the profiler
        # trace teardown — stays OUTSIDE the lock (graftlint
        # blocking-under-lock); store/sentinel internals need no lock of
        # their own because `close` runs on exactly one thread at a time
        # (the pump is a single consumer; without a pump it is the main
        # loop itself).
        self._lock = threading.Lock()
        self._overhead_s = 0.0
        self._eligible = 0
        self._windows = 0
        self._skipped = 0
        self._retraces = 0
        self._regressions = 0
        self._last_event: Optional[Dict] = None
        self._cache_seen: Optional[Tuple[int, int]] = None
        self._snapshot: Optional[Dict] = None

    # ----------------------------------------------------------- helpers
    def _registry(self):
        if self._reg is None:
            from ..master.metrics import get_registry

            self._reg = get_registry()
        return self._reg

    def overhead_fraction(self) -> float:
        wall = max(time.monotonic() - self._t_start, 1e-9)
        with self._lock:
            overhead = self._overhead_s
        return overhead / wall

    def snapshot(self) -> Optional[Dict]:
        with self._lock:
            return self._snapshot

    # ----------------------------------------------------------- windows
    def maybe_open(self, step: int, fused_k: int) -> Optional[_Window]:
        """Open a window on every ``every``-th eligible boundary, unless
        the self-limiter says profiling already costs ≥ budget of wall."""
        with self._lock:
            self._eligible += 1
            eligible = self._eligible
            windows = self._windows
        if (eligible - 1) % self.every:
            return None
        if windows and self.overhead_fraction() >= self.overhead_budget:
            with self._lock:
                self._skipped += 1
            return None
        from ..utils.profiler import StepProfiler

        from .spans import span

        t0 = time.monotonic()
        tdir = tempfile.mkdtemp(prefix="dwt-perf-win-")
        span_ctx = span("perf:window", {"step": step, "key": self.key,
                                        "fused_k": fused_k})
        span_ctx.__enter__()
        prof = StepProfiler(trace_dir=tdir, start_step=step, end_step=step,
                            device_only=True)
        ctx = prof.step(step)
        try:
            ctx.__enter__()
        except Exception:  # noqa: BLE001 — observability must not kill train
            span_ctx.__exit__(None, None, None)
            shutil.rmtree(tdir, ignore_errors=True)
            logger.warning("perf window open failed", exc_info=True)
            return None
        return _Window(prof, ctx, span_ctx, step, fused_k, tdir,
                       open_cost_s=time.monotonic() - t0,
                       t_run0=time.monotonic(), key=self.key)

    def close(self, win: _Window) -> Optional[Dict]:
        """Fold the window into a PerfSnapshot; returns the snapshot.

        Call AFTER the boundary's existing host readback: the measured
        step time then covers dispatch + device completion, and the
        trace holds the device work."""
        t_run = time.monotonic() - win.t_run0
        t1 = time.monotonic()
        try:
            win.ctx.__exit__(None, None, None)
            win.prof.close()
        except Exception:  # noqa: BLE001 — observability must not kill train
            logger.warning("perf window close failed", exc_info=True)
        win.span_ctx.__exit__(None, None, None)
        overhead = win.open_cost_s + (time.monotonic() - t1)
        shutil.rmtree(win.tdir, ignore_errors=True)
        with self._lock:
            self._overhead_s += overhead
            self._windows += 1
        self._credit_overhead(overhead)

        key = win.key or self.key
        step_s = t_run / win.fused_k
        prof = win.prof.last_profile
        cats = ({k: float(v) for k, v in prof.categories.items()}
                if prof is not None else {})
        beyond, event = self.sentinel.observe(key, step_s, cats,
                                              step=win.step)
        if not beyond:
            # beyond-bound windows stay OUT of the baseline: a sustained
            # regression must not median its way into normal
            self.store.update(key, step_s, cats)
            self.store.publish()
        if event is not None:
            with self._lock:
                self._regressions += 1
            self._fire(event)
        self._observe_compile_counters(win.step)
        return self._fold_snapshot(win, key, step_s, cats)

    def _credit_overhead(self, seconds: float) -> None:
        try:
            from .ledger import get_ledger

            get_ledger().account("profile", seconds)
        except Exception:  # noqa: BLE001 — telemetry must never break train
            pass

    def _fire(self, event: Dict) -> None:
        with self._lock:
            self._last_event = event
        counter = {"perf-regression": "dwt_perf_regression_events",
                   "retrace": "dwt_perf_retrace_events"}.get(event["kind"])
        if counter:
            try:
                self._registry().inc(
                    counter, labels={"job": self._job},
                    help="perf observatory events by kind")
            except Exception:  # noqa: BLE001
                pass
        try:
            from .recorder import get_recorder

            get_recorder().record("perf_event", event["kind"], dict(event))
        except Exception:  # noqa: BLE001
            pass
        if self.on_event is not None:
            try:
                self.on_event(event)
            except Exception:  # noqa: BLE001 — callbacks must not kill train
                logger.warning("perf on_event callback failed",
                               exc_info=True)

    def _observe_compile_counters(self, step: int) -> None:
        """Retrace observatory: cache misses growing in steady state mean
        the step is retracing — an event even when step time looks fine."""
        try:
            from ..auto.compile_cache import counters
        except Exception:  # noqa: BLE001
            return
        now = counters.snapshot()
        with self._lock:
            prev, self._cache_seen = self._cache_seen, now
        if prev is None:
            return  # first window: compiles before it are expected
        miss_delta = now[1] - prev[1]
        if miss_delta > 0:
            with self._lock:
                self._retraces += miss_delta
            self._fire({
                "kind": "retrace", "key": self.key, "step": step,
                "step_time_s": 0.0, "baseline_median_s": 0.0,
                "baseline_mad_s": 0.0, "deviation": 0.0,
                "consecutive": miss_delta, "category": "compile",
                "category_delta_s": 0.0,
            })

    def _fold_snapshot(self, win: _Window, key: str, step_s: float,
                       cats: Dict[str, float]) -> Dict:
        stats = self.store.stats(key) or {"median": 0.0, "mad": 0.0,
                                          "n": 0}
        overhead_frac = self.overhead_fraction()
        with self._lock:
            hits, misses = self._cache_seen or (0, 0)
            snap = {
                "schema": PERF_SCHEMA,
                "key": key,
                "step": win.step,
                "fused_k": win.fused_k,
                "step_time_s": step_s,
                "baseline_median_s": stats["median"],
                "baseline_mad_s": stats["mad"],
                "baseline_n": int(stats["n"]),
                "categories": {k: round(v, 6)
                               for k, v in sorted(cats.items())},
                "overhead_s": round(self._overhead_s, 6),
                "overhead_frac": round(overhead_frac, 6),
                "windows": self._windows,
                "skipped": self._skipped,
                "cache_hits": int(hits),
                "cache_misses": int(misses),
                "retraces": self._retraces,
                "regressions": self._regressions,
                "last_event": self._last_event,
                # wall stamp: persisted into flight dumps and compared
                # across processes by the latest-SENT-wins verb (never
                # duration math)
                "captured_at": time.time(),
                # wire surface (schema.lock.json PERF_SNAPSHOT_KEYS):
                # an older master still reads the key; nothing tunes
                "tuned_variant": "",
            }
            self._snapshot = snap
        return snap


# ------------------------------------------------------------- singleton

_observatory: Optional[PerfObservatory] = None


def set_observatory(obs: Optional[PerfObservatory]) -> None:
    global _observatory
    _observatory = obs


def get_observatory() -> Optional[PerfObservatory]:
    return _observatory


def reset_observatory() -> None:
    set_observatory(None)


def latest_snapshot() -> Optional[Dict]:
    """The flight recorder's embed hook (telemetry/recorder.py flush)."""
    obs = get_observatory()
    return obs.snapshot() if obs is not None else None


# the compiled step programs this process runs, by mode (fused K),
# for a reader that wants their text afterwards
# (analysis/hlo_scopes.py turns it into instruction -> scope).  What the
# loop keeps is the way back to the executable, never the executable
# and never an array: `jitted.lower(...).compile()` on the shapes,
# dtypes and shardings of a dispatch is answered by JAX's in-memory
# caches, nothing is traced, lowered or compiled again.  A struct names
# its sharding only where the array was committed to it — jit keys an
# uncommitted argument as unspecified, and a struct that pinned it
# would miss the cache and compile.  `lower` walks every leaf in Python
# (11 ms at GPT-2 124M's 450 leaves, 51–67 ms at XL's 1,740; PERF.md,
# PR 24), so it runs when someone asks, off the loop.
_step_executables: Dict[Any, Callable[[], Any]] = {}


def keep_step_executable(mode: Any, jitted: Any, state: Any,
                         batch: Any) -> None:
    import jax

    # on the loop only (aval, sharding) per leaf, ~1 us each; building
    # the structs costs ten times that and waits for whoever asks
    leaves, treedef = jax.tree.flatten((state, batch))
    like = [(jax.typeof(x),
             x.sharding if getattr(x, "committed", False) else None)
            for x in leaves]

    def find():
        args = treedef.unflatten(
            jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type,
                                 sharding=s) for a, s in like)
        return jitted.lower(*args).compile()

    _step_executables[mode] = functools.cache(find)


def step_executables() -> Dict[Any, Any]:
    """{mode: jax.stages.Compiled} of every step program kept so far."""
    return {mode: find() for mode, find in list(_step_executables.items())}


def step_memory() -> Dict[Any, Dict[str, int]]:
    """{mode: the compiled budget in bytes (`telemetry.memory.
    compiled_memory`: argument, output, alias, temp, generated code,
    live)} of every step program kept so far — what the step the loop
    dispatched was compiled to hold, per device.  Asked for off the
    loop, like `step_executables()`, whose finds it reuses."""
    from .memory import compiled_memory

    budgets = {mode: compiled_memory(compiled)
               for mode, compiled in step_executables().items()}
    return {mode: b for mode, b in budgets.items() if b}
