"""Level-1 compile reuse: persistent XLA cache + framework cache keys.

Parity: no single reference file — the reference hides recompile cost
behind PyTorch eager + NCCL re-init; on TPU every re-mesh re-traces and
re-compiles `train_step` under XLA, which the goodput accounting in
chaos.py charges as pure dead time.  PHOENIX-style hot-swap recovery
(PAPERS.md) needs the post-failure warm-up near zero, so restarts must
hit a *disk* cache instead of the compiler.

Two layers, deliberately separate:

- The XLA layer is JAX's persistent compilation cache
  (`jax_compilation_cache_dir`): keyed on the serialized HLO + compile
  options + backend, it is exact but opaque.  `enable_persistent_cache`
  points it at a directory that survives worker restarts, drops the
  size/time floors so CPU-mesh tests exercise the same path as 8B runs,
  and installs monitoring listeners so hit/miss/saved-seconds are
  observable in-process (`counters`).

- The framework layer is `train_step_cache_key`: a stable digest of
  everything the *trace* depends on — mesh axis sizes, the resolved
  strategy context, the final (post-override) model config, donation
  and the fused-step count.  The traced program is a function of those
  arguments alone (nothing under ops/, models/, parallel/ reads the
  environment inside a function: graftlint env-at-trace), so the key
  needs nothing else.  XLA's own key cannot be computed without
  tracing; this one can, so the warm pool (auto/warm_pool.py) and the
  master's scale planner can reason about "is this mesh already
  compiled?" before any worker exists.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ..common.log import get_logger

logger = get_logger("compile_cache")

# one registry sidecar + one pool directory per cache dir
_REGISTRY_SUBDIR = "framework-keys"
_POOL_SUBDIR = "warm-pool"
_SERVE_LOG = "serve.log"


@dataclasses.dataclass
class CacheCounters:
    """In-process XLA persistent-cache counters (monitoring listeners)."""

    hits: int = 0
    misses: int = 0
    time_saved_s: float = 0.0

    def snapshot(self) -> Tuple[int, int]:
        return self.hits, self.misses


counters = CacheCounters()

# what JAX spent getting each program ready, one record per
# `jax.monitoring` duration event: {"name", "fun_name", "t_mono" (start),
# "dur_s"}.  `jax:trace` (Python -> jaxpr), `jax:lower` (jaxpr -> MLIR),
# `jax:backend_compile` (XLA compile OR the load out of the persistent
# cache, whichever served it) and, inside the latter, `jax:cache_load`
# (the retrieval alone).  The first dispatch of the step is made of
# these; a set-up reader sums the ones with the step's `fun_name`.  A
# ring of its own (drop-oldest): eager helpers emit thousands of them
# (a set-up of the 124M model writes 8,400, Kimi's 15,300, XL's more
# than the 16,384 this ring held before PR 49, whose readers of set-up
# then read low in silence) and must not push a control-plane span out
# of telemetry/spans.py's buffer.
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax:trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax:lower",
    "/jax/core/compile/backend_compile_duration": "jax:backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax:cache_load",
}
durations: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=65536)
_unnamed = threading.local()
_listeners_installed = False
_enabled_dir: Optional[str] = None


def seconds_between(t0: float, t1: float) -> Dict[str, float]:
    """Seconds the `durations` records (every function's) cover inside
    `t0` .. `t1` on `time.monotonic()`, by kind: `trace_s`, `lower_s`,
    `backend_compile_s`, `cache_load_s` — what a first dispatch is made
    of.  Covered, not summed: a function traced inside another's trace
    has a record of its own, which lies within the outer one."""
    spans: Dict[str, list] = {short: [] for short in
                              _DURATION_EVENTS.values()}
    for rec in reversed(list(durations)):
        end = rec["t_mono"] + rec["dur_s"]
        if end < t0:
            break  # appended as they end: the rest ended earlier still
        if rec["t_mono"] >= t0 and end <= t1:
            spans[rec["name"]].append((rec["t_mono"], end))
    out = {}
    for short, ivals in spans.items():
        covered, reach = 0.0, t0
        for lo, hi in sorted(ivals):
            covered += max(0.0, hi - max(lo, reach))
            reach = max(reach, hi)
        out[short.removeprefix("jax:") + "_s"] = covered
    return out


CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir() -> str:
    """THE compile-cache location, for every process of a job.

    `JAX_COMPILATION_CACHE_DIR` when set — JAX itself reads that variable
    at import, so nothing here has to (or may) point it elsewhere —
    otherwise `<checkout>/.jax_cache`.  The path is part of XLA's cache
    key, so it is always a FIXED path: never a temp name, pid or time.
    Agent, worker generations, warm-pool children and the master's scale
    planner all resolve through here, so they agree without passing the
    directory around.
    """
    return os.environ.get(CACHE_DIR_ENV) or _CHECKOUT_CACHE_DIR


def _install_listeners() -> None:
    global _listeners_installed
    if _listeners_installed:
        return
    # private, but the only event surface jax 0.9 has for cache hits; an
    # ImportError here must surface — restart assertions (chip_smoke.py's
    # elastic phase, the warm-pool e2e) read these counters
    from jax._src import monitoring

    def _export(name: str, value: float = 1.0):
        # mirror into the shared Prometheus registry so /metrics and the
        # perf observatory's compile/retrace watcher see the same stream
        # the in-process counters do (lazy import: this module stays
        # importable without the master package at module level)
        try:
            from ..master.metrics import get_registry

            get_registry().inc(
                name, value,
                help="XLA persistent compile cache (auto/compile_cache)")
        except Exception:  # noqa: BLE001 — telemetry never breaks compiles
            pass

    def _on_event(name: str, **kw):
        if name.endswith("/cache_hits"):
            counters.hits += 1
            _export("dwt_compile_cache_hits")
        elif name.endswith("/cache_misses"):
            counters.misses += 1
            _export("dwt_compile_cache_misses")

    def _on_duration(name: str, secs: float, **kw):
        if name.endswith("/compile_time_saved_sec") and secs > 0:
            counters.time_saved_s += secs
            _export("dwt_compile_cache_time_saved_seconds", secs)
        short = _DURATION_EVENTS.get(name)
        if short is None:
            return
        rec = {"name": short, "fun_name": str(kw.get("fun_name", "")),
               "t_mono": time.monotonic() - secs, "dur_s": secs}
        # the retrieval event carries no name and fires inside the
        # compile it served, on the compiling thread: it is that
        # function's.  Any thread compiles (loop, pump, eval, drain), so
        # the unnamed record waits in a thread-local and nobody walks
        # the shared ring
        if short == "jax:cache_load":
            _unnamed.load = rec
        elif short == "jax:backend_compile":
            load = _unnamed.__dict__.pop("load", None)
            if load is not None:
                load["fun_name"] = rec["fun_name"]
        durations.append(rec)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _listeners_installed = True


def enable_persistent_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache at `resolve_cache_dir()`.

    Idempotent; returns the active dir, or None when disabled
    (DWT_COMPILE_CACHE=0).  JAX's own config already holds
    `JAX_COMPILATION_CACHE_DIR` when the process started with it; the
    config is written only when it does not hold the resolved dir (the
    variable was unset, or a test moved it after import), and then JAX's
    cache singleton — which binds its dir on first use — is reset.  The
    min-time and min-size floors are dropped so the sub-second CPU-mesh
    compiles the tests exercise take the same persist path as
    multi-minute TPU ones.
    """
    global _enabled_dir
    if os.getenv("DWT_COMPILE_CACHE", "1") == "0":
        # the variable that places the cache is JAX's own and switches
        # JAX's cache on by itself (the agent exports it to every
        # worker): "disabled" has to be said to JAX, not just skipped
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
        return None
    cache_dir = resolve_cache_dir()
    if _enabled_dir == cache_dir:
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    import jax

    if jax.config.jax_compilation_cache_dir != cache_dir:
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _install_listeners()
    _enabled_dir = cache_dir
    logger.info("persistent compile cache at %s", cache_dir)
    return cache_dir


def active_cache_dir() -> Optional[str]:
    return _enabled_dir


# ------------------------------------------------------------ framework key


def canonicalize(obj: Any) -> Any:
    """JSON-stable form of strategy/config values.

    Handles the payloads that actually appear in resolved strategies and
    model configs: dataclasses, dtypes/types, jax Meshes (→ axis sizes),
    callables (→ qualname — head_loss etc. key on identity-by-name), and
    containers.  Unknown objects fall back to repr.
    """
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (list, tuple)):
        return [canonicalize(v) for v in obj]
    if isinstance(obj, dict):
        return {str(k): canonicalize(v) for k, v in sorted(obj.items(),
                                                           key=lambda kv:
                                                           str(kv[0]))}
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: canonicalize(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, type):  # jnp.bfloat16 etc.
        return getattr(obj, "__name__", str(obj))
    shape = getattr(obj, "shape", None)
    axis_names = getattr(obj, "axis_names", None)
    if axis_names is not None and shape is not None:
        # jax Mesh / AbstractMesh: only axis sizes matter for the trace
        try:
            return {"mesh_axes": {str(a): int(s)
                                  for a, s in zip(axis_names, shape)}}
        except Exception:  # noqa: BLE001
            pass
    if callable(obj):
        return getattr(obj, "__qualname__", repr(obj))
    if hasattr(obj, "dtype") and hasattr(obj, "name"):  # np.dtype-like
        return str(obj)
    return repr(obj)


def train_step_cache_key(plan_sizes: Dict[str, int],
                         resolved_strategy: Any,
                         model_config: Any,
                         donate: bool,
                         accum_steps: int,
                         backend: Optional[str] = None,
                         extra: Optional[Dict] = None,
                         fused_steps: int = 1) -> str:
    """Digest of everything the train-step trace depends on.

    Same config → same key; changed mesh shape, strategy, model config,
    donation or fused-step count K → different
    key (tests/test_warm_pool.py pins the invalidation matrix).
    `fused_steps` changes the HLO (the K-step scan wraps the whole step,
    trainer/train_step.py) so K=1 and K=8 are distinct compiles.
    """
    import jax

    payload = {
        "mesh": {str(k): int(v) for k, v in dict(plan_sizes).items()},
        "strategy": canonicalize(resolved_strategy),
        "model": canonicalize(model_config),
        "donate": bool(donate),
        "accum": int(accum_steps),
        "fused": int(fused_steps),
        "backend": backend or jax.default_backend(),
        "jax": jax.__version__,
    }
    if extra:
        payload["extra"] = canonicalize(extra)
    blob = json.dumps(payload, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# -------------------------------------------------------- registry sidecar


def registry_dir(cache_dir: str) -> str:
    return os.path.join(cache_dir, _REGISTRY_SUBDIR)


def pool_dir(cache_dir: str) -> str:
    return os.path.join(cache_dir, _POOL_SUBDIR)


def note_train_step_served(cache_dir: Optional[str], key: str,
                           meta: Optional[Dict] = None) -> bool:
    """Record that auto_accelerate served this key; returns True when the
    key was already registered (a prior process compiled this exact
    topology — the restart should hit the XLA disk cache).

    Also appends a line to the pool's serve log so `tools/warm_report.py`
    can aggregate hit/miss across process generations.  Appends of one
    small line are atomic enough for the log's accounting purpose.
    """
    if not cache_dir:
        return False
    reg = registry_dir(cache_dir)
    path = os.path.join(reg, f"{key}.json")
    warm = os.path.exists(path)
    entry: Dict[str, Any] = {}
    try:
        os.makedirs(reg, exist_ok=True)
        if warm:
            try:
                with open(path) as f:
                    entry = json.load(f)
            except (OSError, ValueError):
                entry = {}
        entry.setdefault("key", key)
        entry.setdefault("created", time.time())
        entry["serve_count"] = int(entry.get("serve_count", 0)) + 1
        entry["last_served"] = time.time()
        if meta:
            entry["meta"] = meta
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(entry, f)
        os.replace(tmp, path)
        pool = pool_dir(cache_dir)
        pool_entry = os.path.join(pool, f"{key}.json")
        os.makedirs(pool, exist_ok=True)
        with open(os.path.join(pool, _SERVE_LOG), "a") as f:
            f.write(json.dumps({
                "key": key, "warm": warm, "ts": time.time(),
                "pool_hit": os.path.exists(pool_entry)}) + "\n")
    except OSError:
        logger.debug("cache registry write failed", exc_info=True)
    return warm


def serve_stats(cache_dir: str) -> Dict[str, int]:
    """Aggregate the serve log: framework warm hits vs cold misses, and
    how many serves found a ready warm-pool entry."""
    stats = {"serves": 0, "warm_hits": 0, "cold_misses": 0, "pool_hits": 0}
    path = os.path.join(pool_dir(cache_dir), _SERVE_LOG)
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                stats["serves"] += 1
                if rec.get("warm"):
                    stats["warm_hits"] += 1
                else:
                    stats["cold_misses"] += 1
                if rec.get("pool_hit"):
                    stats["pool_hits"] += 1
    except OSError:
        pass
    return stats


def registry_entries(cache_dir: str) -> Dict[str, Dict]:
    out: Dict[str, Dict] = {}
    reg = registry_dir(cache_dir)
    try:
        names = os.listdir(reg)
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(reg, name)) as f:
                out[name[:-5]] = json.load(f)
        except (OSError, ValueError):
            continue
    return out


def cache_dir_bytes(cache_dir: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(cache_dir):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass
    return total


def evict_lru(cache_dir: str, max_bytes: int) -> int:
    """Drop oldest-accessed XLA entries until the dir fits; returns bytes
    freed.  JAX touches a sibling `-atime` marker on every hit, so LRU
    order comes from those markers, falling back to the entry's mtime."""
    entries = []
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return 0
    for name in names:
        if not name.endswith("-cache"):
            continue
        path = os.path.join(cache_dir, name)
        atime_path = path[:-len("-cache")] + "-atime"
        try:
            stamp = os.path.getmtime(
                atime_path if os.path.exists(atime_path) else path)
            entries.append((stamp, path, atime_path,
                            os.path.getsize(path)))
        except OSError:
            continue
    total = cache_dir_bytes(cache_dir)
    freed = 0
    for _stamp, path, atime_path, size in sorted(entries):
        if total - freed <= max_bytes:
            break
        try:
            os.unlink(path)
            freed += size
            if os.path.exists(atime_path):
                os.unlink(atime_path)
        except OSError:
            pass
    if freed:
        logger.info("evicted %d bytes from compile cache", freed)
    return freed
