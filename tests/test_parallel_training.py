"""Parallel-training tests on the virtual 8-device CPU mesh.

Covers: mesh planning, sharding rules, flash-attention numerics,
auto_accelerate end-to-end training (loss decreases) under several strategies
— the reference's auto_accelerate/strategy tests
(atorch/tests/common_tests) translated to GSPMD.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from version_gates import shard_index_set
from jax.sharding import PartitionSpec as P

from dlrover_wuqiong_tpu.auto.accelerate import (
    auto_accelerate,
    resolve_strategy,
)
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
from dlrover_wuqiong_tpu.ops.flash_attention import (
    _attention_reference,
    flash_attention,
    mha,
)
from dlrover_wuqiong_tpu.parallel.mesh import (
    MeshPlan,
    auto_plan,
    build_mesh,
    hybrid_slice_plan,
)
from dlrover_wuqiong_tpu.parallel.sharding import (
    ShardingPlanner,
    TRANSFORMER_RULES,
    spec_for_path,
)


class TestMeshPlan:
    def test_build_mesh_8(self):
        plan = MeshPlan(dp=2, fsdp=2, tp=2)
        mesh = build_mesh(plan)
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2
        assert mesh.devices.size == 8

    def test_validate_rejects_mismatch(self):
        with pytest.raises(ValueError):
            build_mesh(MeshPlan(dp=3))

    def test_auto_plan_small_model(self):
        plan = auto_plan(8, num_params=10_000_000)
        assert plan.num_devices == 8
        assert plan.tp == 1  # no TP for small models

    def test_auto_plan_huge_model_uses_tp(self):
        # 70B fits on 64 v5p-class chips (95 GiB HBM) and engages TP
        plan = auto_plan(64, num_params=70_000_000_000,
                         hbm_per_device=95 << 30)
        assert plan.tp > 1

    def test_auto_plan_rejects_state_that_cannot_fit(self):
        # 70B state (~980 GB) cannot fit 8 x 16 GiB chips: planner must say
        # so instead of emitting a plan that OOMs at runtime
        with pytest.raises(ValueError, match="does not fit"):
            auto_plan(8, num_params=70_000_000_000)

    def test_hybrid_slice_plan(self):
        plan = hybrid_slice_plan(num_slices=2, devices_per_slice=4, tp=2)
        assert plan.dp == 2 and plan.fsdp == 2 and plan.tp == 2


class TestShardingRules:
    def test_attention_specs(self):
        assert spec_for_path("h_0/attn/c_attn/kernel",
                             TRANSFORMER_RULES) == P("fsdp", "tp")
        assert spec_for_path("h_0/attn/c_proj/kernel",
                             TRANSFORMER_RULES) == P("tp", "fsdp")
        assert spec_for_path("layers_3/attention/q_proj/kernel",
                             TRANSFORMER_RULES) == P("fsdp", "tp")
        assert spec_for_path("wte/embedding",
                             TRANSFORMER_RULES) == P("tp", "fsdp")
        assert spec_for_path("h_0/ln_1/scale", TRANSFORMER_RULES) == P()

    def test_planner_shards_params(self):
        mesh = build_mesh(MeshPlan(fsdp=4, tp=2))
        model = GPT(GPTConfig.nano())
        params = model.init_params(jax.random.PRNGKey(0))
        planner = ShardingPlanner(mesh)
        sharded = planner.shard_params(params)
        k = sharded["h_0"]["attn"]["c_attn"]["kernel"]
        # sharded over both fsdp and tp → 8 distinct shards
        assert len(shard_index_set(k)) == 8
        # layernorm scales replicated
        ln = sharded["h_0"]["ln_1"]["scale"]
        assert len(shard_index_set(ln)) == 1


class TestFlashAttention:
    def test_matches_reference(self):
        key = jax.random.PRNGKey(1)
        q, k, v = (jax.random.normal(k_, (2, 4, 64, 32), jnp.float32)
                   for k_ in jax.random.split(key, 3))
        out = flash_attention(q, k, v, True, None)
        ref = _attention_reference(q, k, v, True, 1.0 / np.sqrt(32))
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_grad_matches_reference(self):
        key = jax.random.PRNGKey(2)
        q, k, v = (jax.random.normal(k_, (1, 2, 32, 16), jnp.float32)
                   for k_ in jax.random.split(key, 3))

        def f_fa(q, k, v):
            return flash_attention(q, k, v, True, None).sum()

        def f_ref(q, k, v):
            return _attention_reference(q, k, v, True,
                                        1.0 / np.sqrt(16)).sum()

        g_fa = jax.grad(f_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(a, b, atol=2e-4)

    def test_pallas_kernel_interpret_mode(self):
        """Run the actual pallas kernel in interpreter mode on CPU."""
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _fa_forward_pallas,
        )
        key = jax.random.PRNGKey(3)
        q, k, v = (jax.random.normal(k_, (2, 128, 128), jnp.float32)
                   for k_ in jax.random.split(key, 3))
        o, _ = _fa_forward_pallas(q, k, v, causal=True,
                                     sm_scale=1.0 / np.sqrt(128),
                                     block_q=64, block_k=64, interpret=True)
        ref = _attention_reference(q[None], k[None], v[None], True,
                                   1.0 / np.sqrt(128))[0]
        np.testing.assert_allclose(o, ref, atol=2e-5)

    def test_pallas_kernel_causal_sq_ne_sk(self):
        """Bottom-right-aligned causal mask when sq != sk (decode append)."""
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _fa_forward_pallas,
        )
        key = jax.random.PRNGKey(4)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, 64, 128), jnp.float32)
        k = jax.random.normal(kk, (2, 256, 128), jnp.float32)
        v = jax.random.normal(kv, (2, 256, 128), jnp.float32)
        o, _ = _fa_forward_pallas(q, k, v, causal=True,
                                     sm_scale=1.0 / np.sqrt(128),
                                     block_q=64, block_k=64, interpret=True)
        ref = _attention_reference(q[None], k[None], v[None], True,
                                   1.0 / np.sqrt(128))[0]
        np.testing.assert_allclose(o, ref, atol=2e-5)

    def test_pallas_kernel_padded_head_dim(self):
        """d=64 (GPT-2 heads) rides the kernel via zero-padding to 128."""
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _fa_forward_pallas,
            _pad_head_dim,
        )
        key = jax.random.PRNGKey(5)
        q, k, v = (jax.random.normal(k_, (2, 128, 64), jnp.float32)
                   for k_ in jax.random.split(key, 3))
        qp, kp, vp = (_pad_head_dim(x, 128) for x in (q, k, v))
        o, _ = _fa_forward_pallas(qp, kp, vp, causal=True,
                                     sm_scale=1.0 / np.sqrt(64),
                                     block_q=64, block_k=64, interpret=True)
        ref = _attention_reference(q[None], k[None], v[None], True,
                                   1.0 / np.sqrt(64))[0]
        np.testing.assert_allclose(o[:, :, :64], ref, atol=2e-5)

    @pytest.mark.parametrize("causal,sq,sk", [(True, 128, 128),
                                              (False, 128, 128),
                                              (True, 64, 256)])
    def test_pallas_backward_kernel(self, causal, sq, sk):
        """dq/dk/dv kernels vs autodiff-of-reference, interpret mode."""
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _fa_backward_pallas,
            _fa_forward_pallas,
        )
        key = jax.random.PRNGKey(6)
        kq, kk, kv, kg = jax.random.split(key, 4)
        scale = 1.0 / np.sqrt(128)
        q = jax.random.normal(kq, (2, sq, 128), jnp.float32)
        k = jax.random.normal(kk, (2, sk, 128), jnp.float32)
        v = jax.random.normal(kv, (2, sk, 128), jnp.float32)
        g = jax.random.normal(kg, (2, sq, 128), jnp.float32)

        o, lse = _fa_forward_pallas(q, k, v, causal, scale, 64, 64,
                                    interpret=True)
        dq, dk, dv = _fa_backward_pallas(q, k, v, o, lse, g, causal, scale,
                                         64, 64, interpret=True)

        def ref_loss(q, k, v):
            out = _attention_reference(q[None], k[None], v[None], causal,
                                       scale)[0]
            return (out * g).sum()

        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(dq, rq, atol=5e-4)
        np.testing.assert_allclose(dk, rk, atol=5e-4)
        np.testing.assert_allclose(dv, rv, atol=5e-4)


def _toy_batch(key, accum, batch, seq, vocab):
    data = jax.random.randint(key, (accum, batch, seq + 1), 0, vocab) \
        if accum > 1 else jax.random.randint(key, (batch, seq + 1), 0, vocab)
    return {"input_ids": data[..., :-1], "labels": data[..., 1:]}


class TestAutoAccelerate:
    def _train(self, strategy, model=None, accum=1, steps=8):
        model = model or GPT(GPTConfig.nano())
        res = auto_accelerate(
            model, optimizer=optax.adamw(1e-2), strategy=strategy,
            accum_steps=accum)
        key = jax.random.PRNGKey(0)
        batch = _toy_batch(key, accum, 8, 32, 16)
        batch = res.place_batch(batch)
        state = res.state
        losses = []
        for _ in range(steps):
            state, metrics = res.train_step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses

    def test_fsdp_training_loss_decreases(self):
        losses = self._train([("fsdp", {})])
        assert losses[-1] < losses[0]

    def test_tp_fsdp_training(self):
        losses = self._train([("tensor_parallel", {"size": 2}),
                              ("fsdp", {})])
        assert losses[-1] < losses[0]

    def test_dp_tp_matches_fsdp_numerics(self):
        l1 = self._train([("fsdp", {})], steps=4)
        l2 = self._train([("tensor_parallel", {"size": 4}),
                          ("data_parallel", {})], steps=4)
        # the model computes in bf16 (GPTConfig.nano default) and tp=4
        # splits the contraction axis: per-shard partial sums round at
        # shard boundaries before the cross-shard reduce, so the two
        # shardings are different bf16 rounding schedules, and adamw's
        # rsqrt amplifies the gap step over step (measured 3.7% at step
        # 1 → 10.3% at step 4 on XLA:CPU).  rtol covers that
        # compounding; the parity claim that survives bf16 is that both
        # runs optimize the same trajectory shape.
        np.testing.assert_allclose(l1, l2, rtol=0.15)
        assert l1[-1] < l1[0] and l2[-1] < l2[0]
        assert all(b < a for a, b in zip(l1, l1[1:]))  # monotone descent

    def test_grad_accum(self):
        losses = self._train([("fsdp", {}), ("grad_accum", {"steps": 2})],
                             accum=2)
        assert losses[-1] < losses[0]

    def test_strategy_flags_reach_model_config(self):
        model = GPT(GPTConfig.nano())
        assert model.config.dtype == jnp.bfloat16
        res = auto_accelerate(
            model, optimizer=optax.adamw(1e-2),
            strategy=[("fsdp", {}), ("half", {"enabled": False}),
                      ("checkpoint", {"enabled": False})])
        # the result carries the rebuilt model with the overridden flags
        assert res.model.config.dtype == jnp.float32
        assert res.model.config.remat is False

    def test_adafactor_opt_state_shards(self):
        """Factored states mirror the param treedef with reduced leaf shapes:
        they must NOT inherit param shardings (regression test)."""
        model = GPT(GPTConfig.nano())
        res = auto_accelerate(
            model, optimizer=optax.adafactor(1e-3),
            strategy=[("fsdp", {}), ("tensor_parallel", {"size": 2})])
        batch = _toy_batch(jax.random.PRNGKey(0), 1, 4, 32, 16)
        state, m = res.train_step(res.state, res.place_batch(batch))
        assert np.isfinite(float(m["loss"]))

    def test_unknown_strategy_rejected(self):
        with pytest.raises(ValueError, match="unknown optimization strategy"):
            auto_accelerate(GPT(GPTConfig.nano()),
                            strategy=[("fsdppp", {})])

    def test_llama_model_trains(self):
        model = Llama(LlamaConfig.nano())
        res = auto_accelerate(model, optimizer=optax.adamw(1e-2),
                              strategy=[("fsdp", {}),
                                        ("tensor_parallel", {"size": 2})])
        key = jax.random.PRNGKey(1)
        batch = _toy_batch(key, 1, 4, 64, 16)
        batch = res.place_batch(batch)
        state = res.state
        losses = []
        for _ in range(6):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_unknown_strategy_raises(self):
        with pytest.raises(ValueError, match="unknown optimization"):
            resolve_strategy([("warp_drive", {})], 8)


class TestShardedByConstructionInit:
    """Sharded-by-construction init (parity: reference meta-device init,
    atorch/utils/meta_model_utils.py + fsdp_init_util.py): auto_accelerate
    must never materialize the full unsharded train-state tree — params and
    optimizer moments are jit-initialized straight into their shards."""

    def _per_device_bytes(self, state):
        per_dev = {}
        for leaf in jax.tree.leaves(state):
            for sh in leaf.addressable_shards:
                per_dev[sh.device] = per_dev.get(sh.device, 0) + \
                    sh.data.nbytes
        return per_dev

    def test_fsdp_state_is_partitioned_not_replicated(self):
        cfg = GPTConfig(vocab_size=2048, n_layer=2, n_head=4, n_embd=256,
                        block_size=128)
        res = auto_accelerate(GPT(cfg), optimizer=optax.adamw(1e-3),
                              strategy=[("fsdp", {})])
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(res.state))
        per_dev = self._per_device_bytes(res.state)
        assert len(per_dev) == 8
        # fully replicated would be ~total per device; sharded-by-
        # construction must land near total/8 (+ replicated scalars/biases)
        worst = max(per_dev.values())
        assert worst < total * 0.25, (
            f"device holds {worst} of {total} bytes — state is (near-)"
            "replicated, not sharded by construction")
        # optimizer moments follow the param shardings
        mu = res.state.opt_state[0].mu["wte"]["embedding"]
        p = res.state.params["wte"]["embedding"]
        assert mu.sharding == p.sharding
        assert not p.sharding.is_fully_replicated

    def test_jit_init_matches_eager_init(self):
        cfg = GPTConfig.nano()
        model = GPT(cfg)
        rng = jax.random.PRNGKey(7)
        res = auto_accelerate(model, optimizer=optax.sgd(1e-2),
                              strategy=[("fsdp", {})], rng=rng)
        eager = model.init_params(rng)
        # same PRNG stream (partitionable threefry), tiny tolerance for
        # jit-fusion rounding (~3e-8 measured on the initializer scaling)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            eager, dict(res.state.params))

    def test_tp_fsdp_composed_init_shards_both_axes(self):
        cfg = GPTConfig(vocab_size=1024, n_layer=2, n_head=4, n_embd=256,
                        block_size=128)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adamw(1e-3),
            strategy=[("tensor_parallel", {"size": 2}), ("fsdp", {})])
        p = res.state.params["h_0"]["mlp"]["c_fc"]["kernel"]
        assert not p.sharding.is_fully_replicated
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(res.state))
        worst = max(self._per_device_bytes(res.state).values())
        assert worst < total * 0.3


class TestSelectiveRematPolicies:
    """("checkpoint", {policy}) strategy — selective activation
    checkpointing + host offload (parity: reference
    selective_offloading_checkpoint.py / activation_checkpointing.py).
    Every policy must train to the SAME loss and gradients; only what is
    saved vs recomputed vs offloaded differs."""

    POLICIES = ["full", "dots", "offload_dots", "save_names",
                "offload_names"]

    def _loss_and_grads(self, strategy):
        cfg = GPTConfig.nano()
        model = GPT(cfg)
        rng = jax.random.PRNGKey(3)
        res = auto_accelerate(model, optimizer=optax.sgd(1e-2),
                              strategy=strategy, rng=rng)
        data = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        # jit the whole loss+grad: eager op-by-op dispatch of the sharded
        # remat'd model issues collectives one at a time, which can abort
        # XLA:CPU's collective rendezvous under pytest process state
        loss, grads = jax.jit(
            lambda p: (res.loss_fn(p, batch),
                       jax.grad(lambda q: res.loss_fn(q, batch))(p)))(
            dict(res.state.params))
        return float(loss), jax.device_get(grads)

    # tier-2: ~34s three-policy gradient sweep; policy plumbing is
    # tier-1 via test_policy_threads_into_model_config and remat
    # correctness via the jaxpr-engine remat-noop gate
    @pytest.mark.slow
    def test_policies_match_no_remat_gradients(self):
        base_loss, base_grads = self._loss_and_grads(
            [("fsdp", {}), ("checkpoint", {"enabled": False})])
        for policy in self.POLICIES:
            loss, grads = self._loss_and_grads(
                [("fsdp", {}), ("checkpoint", {"policy": policy})])
            assert abs(loss - base_loss) < 1e-4, policy
            # bf16 compute: recompute-vs-saved changes fusion order, so
            # grads wobble at bf16 ulp scale (~1e-3 abs at these magnitudes)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=5e-2, atol=2e-3, err_msg=policy),
                grads, base_grads)

    def test_bad_policy_rejected_at_resolve_time(self):
        with pytest.raises(ValueError, match="remat policy"):
            auto_accelerate(GPT(GPTConfig.nano()),
                            strategy=[("checkpoint", {"policy": "bogus"})])

    def test_policy_threads_into_model_config(self):
        res = auto_accelerate(
            GPT(GPTConfig.nano()),
            strategy=[("fsdp", {}), ("checkpoint", {"policy": "dots"})])
        assert res.model.config.remat is True
        assert res.model.config.remat_policy == "dots"


class TestStreamedAttention:
    """Blockwise-scan fallback (_use_streamed): O(s*block) temps on any
    backend — the memory-faithful stand-in for the Pallas kernels used by
    the 8B AOT fit proof (tests/test_scale_8b.py)."""

    @pytest.mark.parametrize("causal,sq,sk", [(True, 256, 256),
                                              (False, 256, 256),
                                              (True, 128, 384)])
    def test_streamed_matches_reference(self, causal, sq, sk):
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _reference_with_lse,
            _streamed_with_lse,
        )
        key = jax.random.PRNGKey(11)
        kq, kk, kv = jax.random.split(key, 3)
        scale = 1.0 / np.sqrt(32)
        q = jax.random.normal(kq, (2, 3, sq, 32), jnp.float32)
        k = jax.random.normal(kk, (2, 3, sk, 32), jnp.float32)
        v = jax.random.normal(kv, (2, 3, sk, 32), jnp.float32)
        o_s, lse_s = _streamed_with_lse(q, k, v, causal, scale, 128)
        o_r, lse_r = _reference_with_lse(q, k, v, causal, scale)
        np.testing.assert_allclose(o_s, o_r, atol=2e-5)
        np.testing.assert_allclose(lse_s, lse_r, atol=2e-5)

    @pytest.mark.parametrize("sq,sk", [(2048, 2048), (1024, 4096)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_streamed_grads_match_dense_path(self, causal, sq, sk):
        """End-to-end through flash_attention's custom VJP: a shape over
        the 2048^2 threshold takes the streamed path, and must give the
        grads of the dense reference."""
        from dlrover_wuqiong_tpu.ops import flash_attention as fa

        assert fa._use_streamed(sq, sk)
        key = jax.random.PRNGKey(12)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 1, sq, 16), jnp.float32)
        k = jax.random.normal(kk, (1, 1, sk, 16), jnp.float32)
        v = jax.random.normal(kv, (1, 1, sk, 16), jnp.float32)

        def loss(q, k, v):
            return (fa.flash_attention(q, k, v, causal=causal) ** 2).sum()

        def dense(q, k, v):
            return (fa._attention_reference(q, k, v, causal, 0.25) ** 2).sum()

        g_str = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_str, g_dense):
            np.testing.assert_allclose(a, b, atol=3e-4)

    def test_streamed_lse_cotangent(self):
        """flash_attention_with_lse differentiates through BOTH outputs on
        the streamed path (the ring-attention building block)."""
        from dlrover_wuqiong_tpu.ops import flash_attention as fa

        key = jax.random.PRNGKey(13)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 1, 2048, 16), jnp.float32)
        k = jax.random.normal(kk, (1, 1, 2048, 16), jnp.float32)
        v = jax.random.normal(kv, (1, 1, 2048, 16), jnp.float32)

        def loss(q, k, v):
            o, lse = fa.flash_attention_with_lse(q, k, v, causal=True)
            return (o ** 2).sum() + (lse ** 2).sum()

        def dense(q, k, v):
            o, lse = fa._reference_with_lse(q, k, v, True, 0.25)
            return (o ** 2).sum() + (lse ** 2).sum()

        g_str = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_str, g_dense):
            np.testing.assert_allclose(a, b, atol=3e-4)

    def test_streamed_fully_masked_rows_sq_gt_sk(self):
        """causal with sq > sk: rows that see NO keys must return 0 with
        lse=-inf (matching the dense reference), not uniform attention
        (the m_new == NEG_INF exp(0) pitfall)."""
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _reference_with_lse,
            _streamed_with_lse,
        )
        key = jax.random.PRNGKey(14)
        kq, kk, kv = jax.random.split(key, 3)
        scale = 1.0 / np.sqrt(16)
        q = jax.random.normal(kq, (1, 2, 128, 16), jnp.float32)
        k = jax.random.normal(kk, (1, 2, 64, 16), jnp.float32)
        v = jax.random.normal(kv, (1, 2, 64, 16), jnp.float32)
        o_s, lse_s = _streamed_with_lse(q, k, v, True, scale, 32)
        o_r, lse_r = _reference_with_lse(q, k, v, True, scale)
        np.testing.assert_allclose(o_s, o_r, atol=2e-5)
        np.testing.assert_allclose(lse_s, lse_r, atol=2e-5)
        # the first sq-sk rows are fully masked
        assert np.all(np.asarray(o_s[:, :, :63]) == 0.0)
        assert np.all(np.isneginf(np.asarray(lse_s[:, :, :63])))

    @pytest.mark.parametrize("causal,sq,sk", [(True, 128, 128),
                                              (False, 128, 128),
                                              (True, 64, 128),
                                              (True, 128, 64)])
    def test_fused_single_block_backward(self, causal, sq, sk):
        """num_q == num_kv == 1 rides the fused dq+dk+dv kernel — must
        match autodiff-of-reference exactly like the split path."""
        from dlrover_wuqiong_tpu.ops.flash_attention import (
            _fa_backward_pallas,
            _fa_forward_pallas,
        )
        key = jax.random.PRNGKey(8)
        kq, kk, kv, kg = jax.random.split(key, 4)
        scale = 1.0 / np.sqrt(128)
        q = jax.random.normal(kq, (2, sq, 128), jnp.float32)
        k = jax.random.normal(kk, (2, sk, 128), jnp.float32)
        v = jax.random.normal(kv, (2, sk, 128), jnp.float32)
        g = jax.random.normal(kg, (2, sq, 128), jnp.float32)
        o, lse = _fa_forward_pallas(q, k, v, causal, scale, sq, sk,
                                    interpret=True)
        dq, dk, dv = _fa_backward_pallas(q, k, v, o, lse, g, causal,
                                         scale, sq, sk, interpret=True)

        def ref_loss(q, k, v):
            # _reference_with_lse (not _attention_reference): the naive
            # softmax turns a row with NO visible keys (sq > sk) into
            # NaN and poisons its grads via 0*NaN; the lse variant
            # defines out = 0 for empty rows, matching the kernels
            from dlrover_wuqiong_tpu.ops.flash_attention import (
                _reference_with_lse,
            )

            out, _ = _reference_with_lse(q[None], k[None], v[None],
                                         causal, scale)
            return (out[0] * g).sum()

        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(dq, rq, atol=3e-4)
        np.testing.assert_allclose(dk, rk, atol=3e-4)
        np.testing.assert_allclose(dv, rv, atol=3e-4)


class TestMultiSliceStrategy:
    """Resolve-time contract of the multi_slice (DCN) strategy."""

    def test_plan_shape(self):
        from dlrover_wuqiong_tpu.auto.accelerate import resolve_strategy

        ctx = resolve_strategy(
            [("multi_slice", {"slices": 2, "tp": 2})], 8)
        p = ctx.plan
        assert (p.dp, p.fsdp, p.tp) == (2, 2, 2), p

    def test_uneven_slices_rejected(self):
        from dlrover_wuqiong_tpu.auto.accelerate import resolve_strategy

        with pytest.raises(ValueError, match="devices/slice"):
            resolve_strategy(
                [("multi_slice", {"slices": 3})], 8)

    def test_tp_must_divide_slice(self):
        from dlrover_wuqiong_tpu.auto.accelerate import resolve_strategy

        with pytest.raises(ValueError, match="divide the"):
            resolve_strategy(
                [("multi_slice", {"slices": 2, "tp": 3})], 8)
