"""Warm re-mesh subsystem: cache-key invalidation, degraded-world specs,
the master's warm-mesh scale policy, and the kill→re-mesh e2e where the
degraded mesh's train_step is served from the warm pool.

Tier-1 fast paths run on the virtual CPU mesh (conftest: 8 devices); the
e2e pieces spawn fresh interpreters because the persistent compilation
cache only proves itself ACROSS processes — in-process jit caching would
mask everything.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from dlrover_wuqiong_tpu.auto.compile_cache import (
    train_step_cache_key,
)
from dlrover_wuqiong_tpu.auto.warm_pool import (
    WarmPool,
    WarmSpec,
    build_model,
    degraded_specs,
    model_spec,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _key(**over):
    base = dict(
        plan_sizes={"dp": 1, "pp": 1, "fsdp": 8, "ep": 1, "sp": 1,
                    "tp": 1},
        resolved_strategy={"extra": {}, "amp": None, "remat": None,
                           "flash_attention": None},
        model_config={"n_layer": 2, "n_embd": 128},
        donate=True,
        accum_steps=1,
        backend="cpu",
    )
    base.update(over)
    return train_step_cache_key(**base)


class TestCacheKeyInvalidation:
    """Same config → same key; any trace-relevant change → new key."""

    def test_same_config_same_key(self):
        assert _key() == _key()

    def test_mesh_shape_changes_key(self):
        assert _key() != _key(plan_sizes={"dp": 1, "pp": 1, "fsdp": 4,
                                          "ep": 1, "sp": 1, "tp": 2})

    def test_strategy_changes_key(self):
        assert _key() != _key(resolved_strategy={
            "extra": {"remat_policy": "dots"}, "amp": None,
            "remat": True, "flash_attention": None})

    def test_model_config_changes_key(self):
        assert _key() != _key(model_config={"n_layer": 4, "n_embd": 128})

    def test_donate_changes_key(self):
        assert _key() != _key(donate=False)

    def test_accum_changes_key(self):
        assert _key() != _key(accum_steps=4)

    def test_fused_steps_changes_key(self):
        # the K-step scan wraps the whole step (trainer/train_step.py):
        # K=1 and K=8 are different HLO, so different compiles
        assert _key() != _key(fused_steps=8)
        assert _key(fused_steps=8) == _key(fused_steps=8)

    def test_backend_changes_key(self):
        assert _key() != _key(backend="tpu")

    def test_callable_payload_is_stable(self):
        # head_loss-style callables key on qualname, not object identity
        def head_loss(p, h, y):
            return 0.0

        k1 = _key(resolved_strategy={"extra": {"pp_head_loss": head_loss}})
        k2 = _key(resolved_strategy={"extra": {"pp_head_loss": head_loss}})
        assert k1 == k2


_RESOLVER_PROBE = r"""
import json, os, sys
import jax
updates = []
_orig = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _orig(k, v))[1]
from dlrover_wuqiong_tpu.auto.compile_cache import (
    enable_persistent_cache, pool_dir, registry_dir, resolve_cache_dir)
from dlrover_wuqiong_tpu.auto.warm_pool import WarmPool
from dlrover_wuqiong_tpu.master.job_manager import WarmMeshPolicy
active = enable_persistent_cache()
print(json.dumps({
    "resolved": resolve_cache_dir(), "active": active,
    "jax": jax.config.jax_compilation_cache_dir,
    "dir_updates": updates.count("jax_compilation_cache_dir"),
    "pool": WarmPool().pool, "policy": WarmMeshPolicy().cache_dir,
    "pool_dir": pool_dir(active), "registry_dir": registry_dir(active)}))
"""


class TestCacheDirResolver:
    """ONE resolver places the cache for every process of a job:
    JAX_COMPILATION_CACHE_DIR when set — and then no code points JAX
    anywhere else — otherwise the fixed <checkout>/.jax_cache."""

    def _probe(self, env_dir):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", _RESOLVER_PROBE],
                              env=env, cwd="/", capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    @pytest.mark.parametrize("placed", [True, False])
    def test_every_caller_agrees(self, tmp_path, placed):
        want = str(tmp_path / "cc") if placed else \
            os.path.join(REPO, ".jax_cache")
        got = self._probe(want if placed else "")
        assert got["resolved"] == got["active"] == got["jax"] == want
        assert got["policy"] == want  # master's scale planner
        assert got["pool"] == os.path.join(want, "warm-pool") \
            == got["pool_dir"]      # agent / trainer warm pools
        assert got["registry_dir"] == os.path.join(want, "framework-keys")
        # placed from outside: JAX read the variable itself, nothing
        # here may write the directory; unset: exactly one write
        assert got["dir_updates"] == (0 if placed else 1)

    def test_agent_exports_the_resolved_dir_to_workers(self, monkeypatch,
                                                       tmp_path):
        from dlrover_wuqiong_tpu.auto.compile_cache import (
            CACHE_DIR_ENV,
            resolve_cache_dir,
        )

        monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
        assert resolve_cache_dir() == os.path.join(REPO, ".jax_cache")
        monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path))
        assert resolve_cache_dir() == str(tmp_path)
        assert WarmPool().cache_dir == str(tmp_path)

    def test_disabled_cache_is_disabled_in_jax_too(self, tmp_path):
        """DWT_COMPILE_CACHE=0 must win over the placement variable —
        which switches JAX's own cache on all by itself."""
        env = dict(os.environ, JAX_PLATFORMS="cpu", DWT_COMPILE_CACHE="0",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        code = ("import jax, jax.numpy as jnp\n"
                "from dlrover_wuqiong_tpu.auto.compile_cache import "
                "enable_persistent_cache\n"
                "assert enable_persistent_cache() is None\n"
                "jax.jit(lambda x: x * 2)(jnp.ones(4)).block_until_ready()")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert not os.path.exists(tmp_path / "cc") or \
            not os.listdir(tmp_path / "cc")


def test_no_warm_child_for_a_platform_whose_devices_are_held(tmp_path):
    """One process per chip: a warm child would have to open devices the
    training process holds — none is started, and one line says so."""
    pool = WarmPool(str(tmp_path))
    spec = WarmSpec(n_devices=1, strategy=[["fsdp", {}]],
                    model={"kind": "gpt", "config": {"n_layer": 2}},
                    batch_shape=[8, 32], platform="tpu")
    assert pool.warm_async(spec) is None
    assert not [n for n in os.listdir(pool.pool)
                if n.endswith((".inflight", ".spec.json"))]


class TestAutoAccelerateKey:
    """The key as computed by the real resolve path."""

    def _build(self, n_dev, **kw):
        import optax

        from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
        from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  use_flash_attention=False, remat=False)
        return auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                               devices=jax.devices()[:n_dev],
                               materialize=False,
                               **kw)

    def test_same_build_same_key_and_registry_warms(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        r1 = self._build(8, strategy=[("fsdp", {})])
        r2 = self._build(8, strategy=[("fsdp", {})])
        assert r1.cache_key == r2.cache_key
        assert not r1.cache_warm  # first serve of this topology
        assert r2.cache_warm      # registry remembers the first
        assert r1.strategy_spec == [["fsdp", {}]]

    def test_mesh_changes_key(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        r8 = self._build(8, strategy=[("fsdp", {})])
        r4 = self._build(4, strategy=[("fsdp", {})])
        assert r8.cache_key != r4.cache_key

    def test_auto_path_spells_out_plan(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        r = self._build(8)  # no strategy → auto_plan
        assert ["fsdp", {"size": 8}] in r.strategy_spec


class TestWarmSpecs:
    def _spec(self, n=8, strategy=None, policy="fixed_global"):
        return WarmSpec(
            n_devices=n, strategy=strategy or [["fsdp", {}]],
            model={"kind": "gpt", "config": {"n_layer": 2}},
            batch_shape=[8, 32], batch_policy=policy)

    def test_node_kill_degrades_world(self):
        out = degraded_specs(self._spec(8), num_nodes=2,
                             devices_per_node=4)
        assert [s.n_devices for s in out] == [4]
        # fixed global batch: the elasticity contract keeps B constant
        assert out[0].batch_shape == [8, 32]

    def test_single_node_has_no_degraded_world(self):
        assert degraded_specs(self._spec(8), 1, 8) == []

    def test_per_device_batch_scales(self):
        out = degraded_specs(self._spec(8, policy="per_device"),
                             num_nodes=2, devices_per_node=4)
        assert out[0].batch_shape == [4, 32]

    def test_multi_slice_degrades_to_fewer_slices(self):
        spec = self._spec(12, strategy=[["multi_slice", {"slices": 3}]])
        out = degraded_specs(spec, num_nodes=3, devices_per_node=4)
        assert len(out) == 1
        assert out[0].n_devices == 8
        assert out[0].strategy[0][1]["slices"] == 2

    def test_two_slices_fall_back_to_fsdp(self):
        spec = self._spec(8, strategy=[["multi_slice", {"slices": 2}]])
        out = degraded_specs(spec, num_nodes=2, devices_per_node=4)
        assert len(out) == 1
        assert out[0].n_devices == 4
        names = [s[0] for s in out[0].strategy]
        assert "multi_slice" not in names and "fsdp" in names

    def test_model_spec_round_trip(self):
        from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  remat=False)
        ms = model_spec(GPT(cfg))
        assert ms["kind"] == "gpt"
        rebuilt = build_model(ms)
        assert rebuilt.config == cfg

    def test_spec_json_round_trip(self):
        spec = self._spec()
        assert WarmSpec.from_json(spec.to_json()) == spec
        assert spec.spec_key() == WarmSpec.from_json(
            spec.to_json()).spec_key()

    def test_spec_left_by_another_version_is_not_this_runs(self, tmp_path):
        """The pool dir outlives a process: a published spec with a field
        this version does not have reads as no spec (compile in place),
        never as a crash at a fusion boundary."""
        from dlrover_wuqiong_tpu.auto.compile_cache import pool_dir
        from dlrover_wuqiong_tpu.auto.warm_pool import (
            load_current_spec,
            publish_current_spec,
        )

        publish_current_spec(str(tmp_path), self._spec())
        assert load_current_spec(str(tmp_path)) == self._spec()
        path = os.path.join(pool_dir(str(tmp_path)), "current_spec.json")
        blob = json.load(open(path))
        blob["a_field_of_another_version"] = {"X": "1"}
        json.dump(blob, open(path, "w"))
        assert load_current_spec(str(tmp_path)) is None

    def test_fused_steps_rides_spec_and_degradation(self):
        # K changes the HLO: a degraded-world warm compile at the wrong
        # K would be a cache miss for the restarted fused worker
        spec = dataclasses.replace(self._spec(8), fused_steps=4)
        assert WarmSpec.from_json(spec.to_json()).fused_steps == 4
        assert spec.spec_key() != self._spec(8).spec_key()
        out = degraded_specs(spec, num_nodes=2, devices_per_node=4)
        assert out and out[0].fused_steps == 4


def _fake_pool_entry(cache_dir, n_devices, key="k"):
    pool = os.path.join(str(cache_dir), "warm-pool")
    os.makedirs(pool, exist_ok=True)
    with open(os.path.join(pool, f"{key}{n_devices}.json"), "w") as f:
        json.dump({"spec_key": f"s{n_devices}", "cache_key":
                   f"{key}{n_devices}", "n_devices": n_devices,
                   "ready": True, "platform": "cpu"}, f)


class TestWarmMeshPolicy:
    def test_policy_reads_pool_state(self, tmp_path):
        from dlrover_wuqiong_tpu.master.job_manager import WarmMeshPolicy

        _fake_pool_entry(tmp_path, 4)
        policy = WarmMeshPolicy(cache_dir=str(tmp_path),
                                devices_per_node_fn=lambda: 2)
        assert policy.is_warm_world(2)       # 2 nodes x 2 devices = 4
        assert not policy.is_warm_world(3)
        assert policy.preferred_world_size([1, 2, 3]) == 2

    def test_rendezvous_forms_warm_world_without_grace_wait(self,
                                                            tmp_path):
        """The scale-plan path: min reached, below max — normally the
        manager holds a straggler grace window open; with the degraded
        world warm it forms immediately (waiting is pure downtime when
        the restart is near-free)."""
        from dlrover_wuqiong_tpu.master.job_manager import WarmMeshPolicy
        from dlrover_wuqiong_tpu.master.rendezvous import (
            ElasticTrainingRendezvousManager,
        )

        def _join(rdzv, n):
            for nid in range(n):
                rdzv.join_rendezvous(nid, nid, 1)

        # control: no policy → the 1h grace window keeps the world open
        rdzv = ElasticTrainingRendezvousManager()
        rdzv.update_rdzv_params(2, 4, waiting_timeout=3600.0)
        _join(rdzv, 3)
        _round, _g, world = rdzv.get_comm_world(0)
        assert world == {}

        # warm 3-node world → formed despite the grace window
        _fake_pool_entry(tmp_path, 3)
        rdzv2 = ElasticTrainingRendezvousManager()
        rdzv2.update_rdzv_params(2, 4, waiting_timeout=3600.0)
        rdzv2.set_world_size_policy(WarmMeshPolicy(
            cache_dir=str(tmp_path), devices_per_node_fn=lambda: 1))
        _join(rdzv2, 3)
        _round, _g, world = rdzv2.get_comm_world(0)
        assert len(world) == 3

    def test_cold_pool_keeps_grace_window(self, tmp_path):
        from dlrover_wuqiong_tpu.master.job_manager import WarmMeshPolicy
        from dlrover_wuqiong_tpu.master.rendezvous import (
            ElasticTrainingRendezvousManager,
        )

        rdzv = ElasticTrainingRendezvousManager()
        rdzv.update_rdzv_params(2, 4, waiting_timeout=3600.0)
        rdzv.set_world_size_policy(WarmMeshPolicy(
            cache_dir=str(tmp_path), devices_per_node_fn=lambda: 1))
        for nid in range(3):
            rdzv.join_rendezvous(nid, nid, 1)
        _round, _g, world = rdzv.get_comm_world(0)
        assert world == {}  # nothing warm → still waiting on stragglers


# --------------------------------------------------------------- e2e


_RESTART_WORKER = r"""
import json, os, sys, time
n_dev = int(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={n_dev}")
import jax
jax.config.update("jax_platforms", "cpu")
import dataclasses
import jax.numpy as jnp
import optax
from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.auto.compile_cache import counters
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                          use_flash_attention=False, remat=False)
res = auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                      strategy=[("fsdp", {})], devices=jax.devices(),
                      materialize=False)
bsh = res.batch_sharding_fn(2, None, 0)
ab = {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=bsh),
      "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=bsh)}
h0, m0 = counters.snapshot()
t0 = time.time()
res.train_step.lower(res.state, ab).compile()
print(json.dumps({
    "cache_key": res.cache_key, "cache_warm": res.cache_warm,
    "step_hits": counters.hits - h0, "step_misses": counters.misses - m0,
    "compile_s": round(time.time() - t0, 3)}))
"""


def _run_restart_worker(tmp_path, cache_dir, n_dev):
    script = tmp_path / "restart_worker.py"
    script.write_text(_RESTART_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(script), str(n_dev)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_kill_remesh_served_from_warm_pool(tmp_path):
    """The acceptance e2e: while an 8-device world 'trains', the warm
    pool pre-compiles the 4-device degraded mesh in a background child;
    the post-kill re-meshed worker (fresh interpreter, 4 devices — what
    the agent relaunches after a node dies) then gets its train_step
    FROM THE POOL: framework key warm, XLA cache hit, zero fresh
    compiles in the step window.  A cold-control worker on an empty
    cache pays the full compile."""
    warm_cache = tmp_path / "warm-cache"
    cold_cache = tmp_path / "cold-cache"
    spec = WarmSpec(
        n_devices=4, strategy=[["fsdp", {}]],
        model={"kind": "gpt",
               "config": {"vocab_size": 512, "n_layer": 2, "n_head": 2,
                          "n_embd": 128, "block_size": 128,
                          "dtype": "float32", "remat": False,
                          "use_flash_attention": False}},
        batch_shape=[8, 32], platform="cpu")

    pool = WarmPool(str(warm_cache))
    assert pool.warm_async(spec) is not None
    assert pool.wait(timeout=240), "warm child failed"
    assert pool.is_warm(4)
    # dedup: an already-warm spec does not respawn
    assert pool.warm_async(spec) is None

    warm = _run_restart_worker(tmp_path, warm_cache, 4)
    cold = _run_restart_worker(tmp_path, cold_cache, 4)

    # the pool child and the restarted worker derived the SAME framework
    # key — the spec replay is faithful to the real build
    entry = [e for e in pool.status()["entries"] if e.get("ready")][0]
    assert entry["cache_key"] == warm["cache_key"]

    assert warm["cache_warm"], warm
    assert warm["step_hits"] >= 1 and warm["step_misses"] == 0, warm
    assert not cold["cache_warm"], cold
    assert cold["step_misses"] >= 1, cold
    assert warm["compile_s"] < cold["compile_s"], (warm, cold)

    # serve accounting: the warm worker's serve recorded a pool hit
    from dlrover_wuqiong_tpu.auto.compile_cache import serve_stats

    stats = serve_stats(str(warm_cache))
    assert stats["pool_hits"] >= 1 and stats["warm_hits"] >= 1, stats


@pytest.mark.slow  # tier-2: ~33s two-drill A/B; warm-pool serving is
# tier-1 via test_kill_remesh_served_from_warm_pool
def test_preempt_drill_reports_compile_saved(tmp_path):
    """chaos preempt with model=True: warm run (persistent cache) vs
    cold control — the downtime split shows a NONZERO compile_s saved
    on the restart, and the warm restart was served from cache."""
    from dlrover_wuqiong_tpu.chaos import preempt_warm

    r = preempt_warm(total_steps=100, dt=0.05, kills=1, seed=1)
    assert r["ok"], r
    assert r["compile_s_saved"] > 0, r
    assert r["warm"]["downtime"]["warm_restarts"] \
        == r["warm"]["downtime"]["restarts"] > 0, r
    assert r["cold"]["downtime"]["warm_restarts"] == 0, r


def test_warm_report_tool(tmp_path):
    """tools/warm_report.py: one line of JSON, parseable, with the pool
    and serve fields the driver snapshots."""
    _fake_pool_entry(tmp_path, 4)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "warm_report.py"),
         str(tmp_path)], capture_output=True, text=True, timeout=60,
        cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1
    report = json.loads(lines[0])
    assert report["warm_device_counts"] == {"4": 1}
    assert report["warm_meshes"][0]["n_devices"] == 4
    assert "serve" in report and "cache_dir_bytes" in report
