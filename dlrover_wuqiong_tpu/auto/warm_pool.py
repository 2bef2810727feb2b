"""Level-2 compile reuse: AOT warm-pool for the meshes a failure creates.

Parity: no reference counterpart — the reference's restart cost is NCCL
re-init, ours is an XLA re-compile (minutes at 8B scale).  PHOENIX
(PAPERS.md) makes hot-swap recovery cheap by preparing the degraded
configuration BEFORE the failure; ElasWave treats reconfiguration cost
as a first-class optimization target.  This module applies both to the
compile path: while training runs healthy on N nodes, a spawned
background process pre-lowers and pre-compiles `train_step` for the
worlds `master/rendezvous.py` would re-form after a kill (N−1 nodes;
slices−1 for multi-slice), writing into the SAME persistent compilation
cache (auto/compile_cache.py) the restarted workers read.  A post-kill
re-mesh then deserializes its executable from disk instead of invoking
the compiler — recovery drops by roughly the full compile time.

Mechanics:

- `WarmSpec` is a JSON round-trippable description of one compile: the
  model (registry kind + config overrides), resolved-strategy input,
  device count, global batch shape, accum steps, and platform.  The
  training side publishes its own spec (`publish_current_spec`, called
  from auto_accelerate) so the agent — which knows topology but not the
  model — can derive degraded specs without importing user code.
- Warming runs in a SUBPROCESS (spawn-fresh interpreter: CLAUDE.md
  forbids forking JAX processes, and the child needs its own
  XLA_FLAGS/platform before backend init — same self-provisioning
  pattern as tools/scale_fit.py).  The child uses
  `auto_accelerate(materialize=False)`: nothing is allocated, only
  lowered and compiled, so an 8B warm costs compile time, not HBM.
- Pool state is a directory of small JSONs under
  `<cache_dir>/warm-pool/` — readable by the master's scale policy
  (master/job_manager.py WarmMeshPolicy) and `tools/warm_report.py`
  without touching JAX.

Batch semantics: the default `batch_policy="fixed_global"` keeps the
global batch constant across world sizes — the framework's elasticity
contract (trainer/elastic.py GradientAccumulator holds the global batch
fixed, reference ElasticTrainer parity).  `"per_device"` scales the
batch with the device count instead; degraded specs that would need a
fractional batch are skipped rather than warmed wrong.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from ..common.log import get_logger
from .compile_cache import (
    CACHE_DIR_ENV,
    pool_dir,
    resolve_cache_dir,
)

logger = get_logger("warm_pool")

_INFLIGHT_TTL_S = 600.0  # a stale .inflight marker older than this is dead
_CURRENT_SPEC = "current_spec.json"

# model registry: WarmSpec round-trips configs for these kinds; anything
# else cannot be rebuilt in the warm child and is skipped (logged)
_MODEL_KINDS = ("gpt", "llama")


@dataclasses.dataclass
class WarmSpec:
    """One speculative compile, fully described by JSON-able fields."""

    n_devices: int
    strategy: List  # [[name, cfg], ...] as given to auto_accelerate
    model: Dict     # {"kind": "gpt"|"llama", "config": {overrides}}
    batch_shape: List[int]  # global [batch, seq] (int32 LM batch)
    accum_steps: int = 1
    platform: str = "cpu"   # jax platform the child must compile for
    batch_policy: str = "fixed_global"  # | "per_device"
    # K of the fused multi-step driver the worker runs (1 = plain step).
    # K changes the HLO (trainer/train_step.py), so a warm entry compiled
    # at the wrong K is a cache MISS for the restarted worker — the spec
    # must carry it.
    fused_steps: int = 1
    # ADD-ONLY: when set, this spec warms the SERVING executables (admit
    # + fused decode window) instead of a train step — a dict of
    # serving.ServeSpec fields (slot count / max_len / fused_tokens /
    # quant are all in the serving compile-cache key, so a replacement
    # decode worker after `chaos serve-drain` finds its programs warm).
    serve: Optional[Dict] = None

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, blob: str) -> "WarmSpec":
        return cls(**json.loads(blob))

    def spec_key(self) -> str:
        """Identity for dedup/inflight marking (NOT the train-step cache
        key — that needs strategy resolution and is computed in-child)."""
        return hashlib.sha256(self.to_json().encode()).hexdigest()[:16]


def model_spec(model) -> Optional[Dict]:
    """Serialize a model into registry form, or None when the model (or a
    non-JSON config override) cannot be rebuilt in the warm child."""
    cfg = getattr(model, "config", None)
    kind = {"GPT": "gpt", "Llama": "llama"}.get(type(model).__name__)
    if kind is None or not dataclasses.is_dataclass(cfg):
        return None
    try:
        defaults = type(cfg)()
    except TypeError:
        return None
    out: Dict[str, Any] = {}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if f.name == "mesh":
            continue  # set by auto_accelerate; the child re-derives it
        if v == getattr(defaults, f.name):
            continue
        if f.name == "dtype":
            out["dtype"] = getattr(v, "__name__", str(v))
        elif isinstance(v, (bool, int, float, str)) or v is None:
            out[f.name] = v
        elif isinstance(v, (tuple, list)):
            out[f.name] = list(v)
        else:
            logger.debug("model config field %s=%r not JSON-able; "
                         "cannot warm", f.name, v)
            return None
    return {"kind": kind, "config": out}


def build_model(spec_model: Dict):
    """Rebuild the model in the warm child (inverse of model_spec)."""
    import jax.numpy as jnp

    kind = spec_model["kind"]
    if kind == "gpt":
        from ..models.gpt import GPT, GPTConfig

        cfg_cls, model_cls = GPTConfig, GPT
    elif kind == "llama":
        from ..models.llama import Llama, LlamaConfig

        cfg_cls, model_cls = LlamaConfig, Llama
    else:
        raise ValueError(f"unknown model kind {kind!r}; "
                         f"registry: {_MODEL_KINDS}")
    overrides = dict(spec_model.get("config", {}))
    dtype_name = overrides.pop("dtype", None)
    # tuple-typed fields arrive as lists from JSON
    cfg = cfg_cls(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in overrides.items()})
    if dtype_name:
        cfg = dataclasses.replace(
            cfg, dtype={"bfloat16": jnp.bfloat16,
                        "float32": jnp.float32,
                        "float16": jnp.float16}[dtype_name])
    return model_cls(cfg)


def can_warm(platform: str) -> bool:
    """Whether a warm child may be started for `platform`.

    A warm child is a second process that initialises the platform's
    backend.  An accelerator's devices belong to one process at a time,
    and every caller here is (or supervises) the process training on
    them, so only the CPU platform — which any number of processes may
    open — can be warmed out of process.  Compiling for a described,
    unattached topology is the way to lift this (ROADMAP)."""
    return platform == "cpu"


# ------------------------------------------------------- degraded worlds


def degraded_specs(spec: WarmSpec, num_nodes: int,
                   devices_per_node: int) -> List[WarmSpec]:
    """The worlds rendezvous would re-form after one failure.

    N−1 nodes for the node-kill case; slices−1 for a multi-slice plan
    (whole-slice preemption is the dominant TPU failure domain).  The
    current world itself is NOT in the list — it is warm by virtue of
    running.
    """
    out: List[WarmSpec] = []

    def _scaled(n_dev: int, strategy: List) -> Optional[WarmSpec]:
        if n_dev < 1:
            return None
        batch = list(spec.batch_shape)
        if spec.batch_policy == "per_device" and batch:
            scaled = batch[0] * n_dev
            if scaled % spec.n_devices:
                logger.info("skip warm for %d devices: global batch %d "
                            "does not scale integrally", n_dev, batch[0])
                return None
            batch[0] = scaled // spec.n_devices
        return dataclasses.replace(spec, n_devices=n_dev,
                                   strategy=strategy,
                                   batch_shape=batch)

    multi_slice = next((cfg for name, cfg in
                        (s if isinstance(s, (list, tuple)) else (s, {})
                         for s in spec.strategy)
                        if name == "multi_slice"), None)
    if multi_slice:
        slices = int(multi_slice.get("slices", 2))
        per = int(multi_slice.get("devices_per_slice")
                  or spec.n_devices // slices)
        if slices > 2:
            degraded_cfg = dict(multi_slice, slices=slices - 1,
                                devices_per_slice=per)
            strategy = [["multi_slice", degraded_cfg]
                        if (s[0] if isinstance(s, (list, tuple)) else s)
                        == "multi_slice" else list(s)
                        for s in spec.strategy]
            got = _scaled((slices - 1) * per, strategy)
            if got:
                out.append(got)
        elif slices == 2:
            # losing a slice of 2 leaves a single-slice world: multi_slice
            # no longer applies — fall back to plain fsdp over the slice
            strategy = [list(s) for s in spec.strategy
                        if (s[0] if isinstance(s, (list, tuple)) else s)
                        != "multi_slice"]
            strategy.append(["fsdp", {}])
            got = _scaled(per, strategy)
            if got:
                out.append(got)
        return out

    if num_nodes > 1:
        got = _scaled((num_nodes - 1) * devices_per_node,
                      [list(s) if isinstance(s, (list, tuple)) else [s, {}]
                       for s in spec.strategy])
        if got:
            out.append(got)
    return out


# ------------------------------------------------------------- pool (parent)


class WarmPool:
    """Parent-side handle: launch warm children, read pool state."""

    def __init__(self, cache_dir: Optional[str] = None):
        self.cache_dir = cache_dir or resolve_cache_dir()
        self.pool = pool_dir(self.cache_dir)
        os.makedirs(self.pool, exist_ok=True)
        self._children: List[subprocess.Popen] = []

    # -------------------------------------------------------- launching

    def _publish(self, path: str, content: str) -> None:
        """Atomic write of a pool control file (write-tmp + rename)."""
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(content)
        os.replace(tmp, path)

    def warm_async(self, spec: WarmSpec) -> Optional[subprocess.Popen]:
        """Launch one background compile; None when deduped (already
        ready, or a live inflight marker exists)."""
        if not can_warm(spec.platform):
            logger.info("no warm child for platform %r: its devices are "
                        "held by the training process — the step compiles "
                        "in place", spec.platform)
            return None
        skey = spec.spec_key()
        if self._ready_entry_for(skey) is not None:
            return None
        inflight = os.path.join(self.pool, f"{skey}.inflight")
        try:
            if os.path.exists(inflight) and \
                    time.time() - os.path.getmtime(inflight) \
                    < _INFLIGHT_TTL_S:
                return None
            spec_path = os.path.join(self.pool, f"{skey}.spec.json")
            # both files are read by other processes (the compile child
            # re-derives its platform from the spec; concurrent warmers
            # dedupe on the inflight marker) — publish atomically so a
            # crash mid-write never leaves a torn spec or a marker whose
            # mtime lies about a write still in progress
            self._publish(spec_path, spec.to_json())
            self._publish(inflight, str(os.getpid()))
        except OSError:
            logger.warning("warm pool dir not writable", exc_info=True)
            return None
        env = dict(os.environ)
        env[CACHE_DIR_ENV] = self.cache_dir
        # the child re-derives platform/XLA_FLAGS from the spec before
        # touching the backend
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        pythonpath = env.get("PYTHONPATH", "")
        if pkg_root not in pythonpath.split(os.pathsep):
            env["PYTHONPATH"] = (f"{pkg_root}{os.pathsep}{pythonpath}"
                                 if pythonpath else pkg_root)
        log_path = os.path.join(self.pool, f"{skey}.log")
        with open(log_path, "ab") as log_f:
            proc = subprocess.Popen(
                [sys.executable, "-m",
                 "dlrover_wuqiong_tpu.auto.warm_pool", spec_path],
                env=env, stdout=log_f, stderr=subprocess.STDOUT,
                start_new_session=True)
        self._children.append(proc)
        logger.info("warming mesh for %d devices (spec %s, pid %d)",
                    spec.n_devices, skey, proc.pid)
        return proc

    def warm_degraded(self, spec: WarmSpec, num_nodes: int,
                      devices_per_node: int) -> List[subprocess.Popen]:
        """Speculatively warm every world one failure away."""
        procs = []
        for degraded in degraded_specs(spec, num_nodes, devices_per_node):
            p = self.warm_async(degraded)
            if p is not None:
                procs.append(p)
        return procs

    def wait(self, timeout: float = 600.0) -> bool:
        """Block until launched children exit; True when all succeeded."""
        deadline = time.monotonic() + timeout
        ok = True
        for proc in self._children:
            remaining = max(0.1, deadline - time.monotonic())
            try:
                ok = (proc.wait(timeout=remaining) == 0) and ok
            except subprocess.TimeoutExpired:
                ok = False
        return ok

    def stop(self):
        for proc in self._children:
            if proc.poll() is None:
                proc.terminate()
        self._children.clear()

    # ---------------------------------------------------------- reading

    def _entries(self) -> List[Dict]:
        out = []
        try:
            names = os.listdir(self.pool)
        except OSError:
            return out
        for name in names:
            if not name.endswith(".json") or name.endswith(".spec.json") \
                    or name == _CURRENT_SPEC:
                continue
            try:
                with open(os.path.join(self.pool, name)) as f:
                    out.append(json.load(f))
            except (OSError, ValueError):
                continue
        return out

    def status(self) -> Dict:
        entries = self._entries()
        return {
            "cache_dir": self.cache_dir,
            "entries": entries,
            "warm_device_counts": sorted({e["n_devices"] for e in entries
                                          if e.get("ready")}),
            "inflight": sum(1 for n in os.listdir(self.pool)
                            if n.endswith(".inflight"))
            if os.path.isdir(self.pool) else 0,
        }

    def _ready_entry_for(self, spec_key: str) -> Optional[Dict]:
        for e in self._entries():
            if e.get("spec_key") == spec_key and e.get("ready"):
                return e
        return None

    def is_warm(self, n_devices: int, platform: Optional[str] = None
                ) -> bool:
        for e in self._entries():
            if e.get("ready") and e.get("n_devices") == n_devices and \
                    (platform is None or e.get("platform") == platform):
                return True
        return False


def warm_device_counts(cache_dir: str) -> Dict[int, int]:
    """{n_devices: ready entry count} — JAX-free read for the master's
    scale policy and the report tool."""
    counts: Dict[int, int] = {}
    pool = pool_dir(cache_dir)
    try:
        names = os.listdir(pool)
    except OSError:
        return counts
    for name in names:
        if not name.endswith(".json") or name.endswith(".spec.json") \
                or name == _CURRENT_SPEC:
            continue
        try:
            with open(os.path.join(pool, name)) as f:
                e = json.load(f)
        except (OSError, ValueError):
            continue
        if e.get("ready"):
            n = int(e.get("n_devices", 0))
            counts[n] = counts.get(n, 0) + 1
    return counts


# ------------------------------------------------- current-spec publishing


def publish_current_spec(cache_dir: str, spec: WarmSpec) -> None:
    """Training side: record what THIS world compiled, so the agent (which
    knows topology but not the model) can warm the degraded worlds."""
    pool = pool_dir(cache_dir)
    try:
        os.makedirs(pool, exist_ok=True)
        tmp = os.path.join(pool, f".{_CURRENT_SPEC}.{os.getpid()}")
        with open(tmp, "w") as f:
            f.write(spec.to_json())
        os.replace(tmp, os.path.join(pool, _CURRENT_SPEC))
    except OSError:
        logger.debug("current-spec publish failed", exc_info=True)


def load_current_spec(cache_dir: str) -> Optional[WarmSpec]:
    try:
        with open(os.path.join(pool_dir(cache_dir), _CURRENT_SPEC)) as f:
            return WarmSpec.from_json(f.read())
    except (OSError, ValueError, TypeError):
        return None


# ------------------------------------------------------------- child main


def _child_main(spec_path: str) -> int:
    """Compile the spec's train step into the shared persistent cache.

    Self-provisioning (tools/scale_fit.py pattern): the CPU platform and
    its virtual device count are fixed BEFORE the backend initializes.
    """
    with open(spec_path) as f:
        spec = WarmSpec.from_json(f.read())
    if not can_warm(spec.platform):
        raise RuntimeError(
            f"warm child refused for platform {spec.platform!r}: the "
            f"training process holds its devices")
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags)
    os.environ["XLA_FLAGS"] = (
        flags +
        f" --xla_force_host_platform_device_count={spec.n_devices}"
    ).strip()
    import jax

    from .compile_cache import (
        counters,
        enable_persistent_cache,
        train_step_cache_key,
    )

    cache_dir = enable_persistent_cache()
    pool = pool_dir(cache_dir)
    skey = spec.spec_key()
    inflight = os.path.join(pool, f"{skey}.inflight")
    t0 = time.monotonic()  # duration math; entry "ts" stays wall-clock
    from ..telemetry import spans as tspans

    tspans.set_process_role("warm-pool")
    try:
        import jax.numpy as jnp
        import optax

        from .accelerate import auto_accelerate

        if getattr(spec, "serve", None):
            # serving warm: materialized on purpose — the engine's admit
            # and decode programs must actually RUN once to land in the
            # persistent cache, and a decode-mesh model is small next to
            # a training world (no optimizer state, no activations)
            from ..serving.engine import ServeSpec, ServingEngine

            model = build_model(spec.model)
            sspec = ServeSpec(**spec.serve)
            params = model.init_params(jax.random.PRNGKey(0))
            eng = ServingEngine(model.config, params, sspec,
                                cache_dir=cache_dir)
            with tspans.span("warm:serve", {"spec": skey,
                                            "slots": sspec.max_slots}):
                eng.admit(0, [1], 0)
                eng.decode_window()
                eng.retire(0)
            entry = {
                "spec_key": skey,
                "cache_key": eng.cache_key,
                "n_devices": spec.n_devices,
                "serve": dict(spec.serve),
                "platform": spec.platform,
                "compile_s": round(time.monotonic() - t0, 2),
                "ready": True,
                "ts": time.time(),
            }
            tmp = os.path.join(pool, f".{eng.cache_key}.{os.getpid()}")
            with open(tmp, "w") as f:
                json.dump(entry, f)
            os.replace(tmp, os.path.join(pool, f"{eng.cache_key}.json"))
            print(json.dumps(entry), flush=True)
            return 0

        model = build_model(spec.model)
        devices = jax.devices()[:spec.n_devices]
        if len(devices) < spec.n_devices:
            raise RuntimeError(
                f"warm child has {len(devices)} devices, spec needs "
                f"{spec.n_devices}")
        strategy = [tuple(s) if isinstance(s, list) else s
                    for s in spec.strategy]
        fused = max(1, int(getattr(spec, "fused_steps", 1)))
        res = auto_accelerate(model, optimizer=optax.adamw(3e-4),
                              strategy=strategy, devices=devices,
                              accum_steps=spec.accum_steps,
                              materialize=False, fused_steps=fused)
        shape = tuple(spec.batch_shape)
        batch_axis = 0
        if spec.accum_steps > 1:
            shape = (spec.accum_steps,) + shape
            batch_axis += 1
        if fused > 1:
            # the fused driver scans K pre-staged batches: leading fused
            # axis before the (optional) microbatch axis
            shape = (fused,) + shape
            batch_axis += 1
        bsh = res.batch_sharding_fn(len(shape), None, batch_axis)
        ab = {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32,
                                                sharding=bsh),
              "labels": jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=bsh)}
        h0, m0 = counters.snapshot()
        with tspans.span("warm:hydrate", {"spec": skey,
                                          "n_devices": spec.n_devices}):
            res.train_step.lower(res.state, ab).compile()
        h1, m1 = counters.snapshot()
        entry = {
            "spec_key": skey,
            "cache_key": res.cache_key,
            "n_devices": spec.n_devices,
            "mesh": res.strategy.plan.describe(),
            "platform": spec.platform,
            "fused_steps": fused,
            "compile_s": round(time.monotonic() - t0, 2),
            "already_cached": (h1 - h0) > 0 and (m1 - m0) == 0,
            "ready": True,
            "ts": time.time(),
        }
        tmp = os.path.join(pool, f".{res.cache_key}.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(entry, f)
        os.replace(tmp, os.path.join(pool, f"{res.cache_key}.json"))
        print(json.dumps(entry), flush=True)
        return 0
    except Exception as e:  # noqa: BLE001 — report, don't crash callers
        print(json.dumps({"spec_key": skey, "ready": False,
                          "error": repr(e)[:500]}), flush=True)
        return 1
    finally:
        try:
            os.unlink(inflight)
        except OSError:
            pass


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv[1]))
