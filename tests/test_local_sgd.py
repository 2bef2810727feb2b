"""Local SGD / DiLoCo tests (reference atorch/local_sgd parity).

Runs on the virtual 8-device CPU mesh: dp=2 replica groups x fsdp=4.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.parallel.local_sgd import (
    DiLoCoState,
    LocalSGDConfig,
    _reduce_delta,
)


def _setup(sync_every=4, reduce="mean"):
    cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                              use_flash_attention=False, remat=False)
    res = auto_accelerate(
        GPT(cfg),
        optimizer=optax.adam(1e-2),
        strategy=[("local_sgd", {"sync_every": sync_every,
                                 "outer_lr": 0.7, "reduce": reduce}),
                  ("data_parallel", {"size": 2}),
                  ("fsdp", {})],
        devices=jax.devices())
    data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                              cfg.vocab_size)
    batch = res.place_batch({"input_ids": data[:, :-1],
                             "labels": data[:, 1:]})
    return res, batch


def _group_params(state, g):
    return jax.tree.map(lambda x: np.asarray(x[g]), state.inner_params)


class TestDiLoCo:
    def test_groups_diverge_then_sync(self):
        res, batch = _setup(sync_every=4)
        state = res.state
        assert isinstance(state, DiLoCoState)
        # inner steps 1-3: groups see different batch shards → diverge
        for _ in range(3):
            state, m = res.train_step(state, batch)
        g0 = _group_params(state, 0)
        g1 = _group_params(state, 1)
        diffs = [np.abs(a - b).max()
                 for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1))]
        assert max(diffs) > 0, "replica groups did not diverge"
        # step 4 is the sync step: groups re-align on the outer params
        state, m = res.train_step(state, batch)
        g0 = _group_params(state, 0)
        g1 = _group_params(state, 1)
        outer = jax.tree.map(np.asarray, state.outer_params)
        for a, b, w in zip(jax.tree.leaves(g0), jax.tree.leaves(g1),
                           jax.tree.leaves(outer)):
            np.testing.assert_allclose(a, b, atol=1e-6)
            np.testing.assert_allclose(a, w, atol=1e-6)

    def test_loss_decreases_across_rounds(self):
        res, batch = _setup(sync_every=2)
        state = res.state
        losses = []
        for _ in range(12):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert int(state.step) == 12

    def test_requires_dp_axis(self):
        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  use_flash_attention=False, remat=False)
        with pytest.raises(ValueError, match="dp axis"):
            auto_accelerate(GPT(cfg),
                            strategy=[("local_sgd", {}), ("fsdp", {})],
                            devices=jax.devices())


class TestReduceMethods:
    def test_gta_gates_disagreement(self):
        """Components with opposite signs across replicas are zeroed."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        cfg = LocalSGDConfig(reduce="gta", gta_threshold=0.0)

        def body(d):
            return _reduce_delta({"x": d}, cfg)["x"]

        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       axis_names={"dp"}, check_vma=False)
        # replica 0: [+1, +1]; replica 1: [-1, +1] → first comp gated off
        d = jnp.array([[1.0, 1.0], [-1.0, 1.0]])
        out = np.asarray(fn(d))
        np.testing.assert_allclose(out[0], [0.0, 1.0], atol=1e-6)

    def test_mean_reduce(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        cfg = LocalSGDConfig(reduce="mean")

        def body(d):
            return _reduce_delta({"x": d}, cfg)["x"]

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       axis_names={"dp"}, check_vma=False)
        d = jnp.array([[2.0], [4.0]])
        np.testing.assert_allclose(np.asarray(fn(d)), [[3.0], [3.0]])


class TestDiLoCoGradAccum:
    """local_sgd x grad_accum (round-3 rejection, now closed): gradients
    accumulate inside each replica group's inner step, so the composition
    is purely local and must match a single big-batch inner step."""

    def _setup(self, accum):
        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  use_flash_attention=False, remat=False)
        strat = [("local_sgd", {"sync_every": 2, "outer_lr": 0.7}),
                 ("data_parallel", {"size": 2}), ("fsdp", {})]
        if accum > 1:
            strat.append(("grad_accum", {"steps": accum}))
        res = auto_accelerate(GPT(cfg), optimizer=optax.sgd(1e-2),
                              strategy=strat, devices=jax.devices(),
                              rng=jax.random.PRNGKey(11))
        return cfg, res

    def test_accum_matches_big_batch_inner_step(self):
        cfg, res1 = self._setup(accum=1)
        _, res2 = self._setup(accum=2)
        data = np.asarray(jax.random.randint(
            jax.random.PRNGKey(0), (16, 33), 0, cfg.vocab_size))
        full = {"input_ids": data[:, :-1], "labels": data[:, 1:]}
        # microbatch split: dp group g sees full rows [8g, 8g+8); under
        # accum it must see the same rows across its two microbatches, and
        # each microbatch's dim 1 keeps the (dp, fsdp)-divisible layout
        def _split(v):
            out = np.zeros((2, 8) + v.shape[1:], v.dtype)
            for g in range(2):
                for mb in range(2):
                    out[mb, g * 4:(g + 1) * 4] = \
                        v[g * 8 + mb * 4:g * 8 + (mb + 1) * 4]
            return out

        micro = {k: _split(v) for k, v in full.items()}
        b1 = res1.place_batch(full)
        b2 = res2.place_batch(micro)
        s1, m1 = res1.train_step(res1.state, b1)
        s2, m2 = res2.train_step(res2.state, b2)
        # same rng → same init; sgd inner → grads average linearly, so the
        # accumulated step must match the big-batch step (CE normalizes per
        # microbatch; equal-size microbatches keep the mean identical)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(
                jax.tree.map(np.asarray, s1.inner_params)),
                jax.tree.leaves(jax.tree.map(np.asarray, s2.inner_params))):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)

    def test_accum_sync_round_still_aligns_groups(self):
        cfg, res = self._setup(accum=2)
        data = jax.random.randint(jax.random.PRNGKey(1), (2, 8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[..., :-1],
                                 "labels": data[..., 1:]})
        state = res.state
        for _ in range(2):  # sync_every=2 → second step syncs
            state, m = res.train_step(state, batch)
        g0 = jax.tree.map(lambda x: np.asarray(x[0]), state.inner_params)
        g1 = jax.tree.map(lambda x: np.asarray(x[1]), state.inner_params)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(a, b, atol=1e-6)
        assert np.isfinite(float(m["loss"]))


class TestDiLoCoStableBF16:
    """local_sgd x stable_bf16 (round-4 rejection, closed): bf16 inner
    params with Kahan/master precision, the outer sync re-anchoring the
    comp state (optimizers/bf16_stable.py reset_compensation)."""

    def _run(self, strategy, steps=8, lr=3e-3):
        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  use_flash_attention=False, remat=False)
        res = auto_accelerate(GPT(cfg), optimizer=optax.adam(lr),
                              strategy=strategy, devices=jax.devices(),
                              rng=jax.random.PRNGKey(5))
        data = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state, losses = res.state, []
        for _ in range(steps):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        return state, losses

    BASE = [("local_sgd", {"sync_every": 4, "outer_lr": 0.7}),
            ("data_parallel", {"size": 2}), ("fsdp", {})]

    @pytest.mark.parametrize("master", [False, True])
    def test_trajectory_matches_f32(self, master):
        s32, l32 = self._run(self.BASE)
        sb, lb = self._run(self.BASE + [("stable_bf16",
                                         {"master": master})])
        # inner params became bf16
        assert all(l.dtype == jnp.bfloat16
                   for l in jax.tree.leaves(sb.inner_params))
        # loss trajectory tracks f32 within bf16 tolerance, incl. ACROSS
        # the sync step at 4 (comp-state re-anchor correctness)
        np.testing.assert_allclose(lb, l32, rtol=0.05)

    def test_sync_still_aligns_groups_bf16(self):
        sb, _ = self._run(self.BASE + [("stable_bf16", {"master": True})])
        g0 = jax.tree.map(lambda x: np.asarray(x[0], np.float32),
                          sb.inner_params)
        g1 = jax.tree.map(lambda x: np.asarray(x[1], np.float32),
                          sb.inner_params)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(a, b, atol=1e-6)


class TestDiLoCoOptimizerOffload:
    """local_sgd x optimizer_offload (round-4 rejection, closed): stacked
    inner moments live in pinned_host between steps."""

    def _setup(self, offload):
        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  use_flash_attention=False, remat=False)
        strat = [("local_sgd", {"sync_every": 2, "outer_lr": 0.7}),
                 ("data_parallel", {"size": 2}), ("fsdp", {})]
        if offload:
            strat.append(("optimizer_offload", {}))
        res = auto_accelerate(GPT(cfg), optimizer=optax.adam(1e-2),
                              strategy=strat, devices=jax.devices(),
                              rng=jax.random.PRNGKey(7))
        data = jax.random.randint(jax.random.PRNGKey(2), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        return res, batch

    def test_moments_in_pinned_host_and_trajectory_identical(self):
        res_d, batch = self._setup(offload=False)
        res_h, _ = self._setup(offload=True)
        # param-shaped moments stack to ndim >= 2; the stacked count
        # scalar is (dp,) and legitimately stays on device
        kinds = {l.sharding.memory_kind
                 for l in jax.tree.leaves(res_h.state.inner_opt_state)
                 if l.ndim > 1}
        assert kinds == {"pinned_host"}, kinds
        sd, sh = res_d.state, res_h.state
        for _ in range(5):  # crosses the sync at step 2 and 4
            sd, md = res_d.train_step(sd, batch)
            sh, mh = res_h.train_step(sh, batch)
            np.testing.assert_allclose(float(md["loss"]),
                                       float(mh["loss"]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(sd.inner_params),
                        jax.tree.leaves(sh.inner_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
