"""Ring attention + Ulysses SP vs full attention on the virtual 8-device mesh.

Mirrors the reference's distributed-attention tests (atorch
modules/distributed_transformer) translated to shard_map/ppermute.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from dlrover_wuqiong_tpu.ops.flash_attention import _attention_reference
from dlrover_wuqiong_tpu.parallel.long_context import (
    _chunk_attention,
    _merge_partials,
    ring_attention,
    ulysses_attention,
)
from dlrover_wuqiong_tpu.parallel.mesh import MeshPlan, build_mesh


@pytest.fixture(scope="module")
def sp_mesh():
    return build_mesh(MeshPlan(sp=4, fsdp=2))


def _qkv(key, b=2, h=4, s=128, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, h, s, d), jnp.float32),
            jax.random.normal(kk, (b, h, s, d), jnp.float32),
            jax.random.normal(kv, (b, h, s, d), jnp.float32))


class TestMergePartials:
    def test_merge_two_halves_equals_full(self):
        q, k, v = _qkv(jax.random.PRNGKey(0), s=64)
        o_full, _ = _chunk_attention(q, k, v, False, None)
        o1, l1 = _chunk_attention(q, k[:, :, :32], v[:, :, :32], False,
                                     None)
        o2, l2 = _chunk_attention(q, k[:, :, 32:], v[:, :, 32:], False,
                                     None)
        o, _ = _merge_partials(o1, l1, o2, l2)
        np.testing.assert_allclose(o, o_full, atol=1e-5)

    def test_merge_with_empty_partial(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), s=32)
        o1, l1 = _chunk_attention(q, k, v, False, None)
        o0 = jnp.zeros_like(o1)
        l0 = jnp.full(l1.shape, -jnp.inf)
        o, lse = _merge_partials(o1, l1, o0, l0)
        np.testing.assert_allclose(o, o1, atol=1e-6)
        np.testing.assert_allclose(lse, l1, atol=1e-6)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, sp_mesh, causal):
        q, k, v = _qkv(jax.random.PRNGKey(2))
        ref = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(16))
        out = ring_attention(q, k, v, sp_mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_grads_match(self, sp_mesh):
        q, k, v = _qkv(jax.random.PRNGKey(3), s=64)

        def f_ring(q, k, v):
            return (ring_attention(q, k, v, sp_mesh, causal=True) ** 2).sum()

        def f_ref(q, k, v):
            return (_attention_reference(q, k, v, True,
                                         1.0 / np.sqrt(16)) ** 2).sum()

        gr = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_sp1_mesh_falls_through(self):
        mesh = build_mesh(MeshPlan(fsdp=8))
        q, k, v = _qkv(jax.random.PRNGKey(4), s=64)
        out = ring_attention(q, k, v, mesh, causal=True)
        ref = _attention_reference(q, k, v, True, 1.0 / np.sqrt(16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, sp_mesh, causal):
        q, k, v = _qkv(jax.random.PRNGKey(5))
        ref = _attention_reference(q, k, v, causal, 1.0 / np.sqrt(16))
        out = ulysses_attention(q, k, v, sp_mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_grads_match(self, sp_mesh):
        q, k, v = _qkv(jax.random.PRNGKey(6), s=64)

        def f_uly(q, k, v):
            return (ulysses_attention(q, k, v, sp_mesh,
                                      causal=True) ** 2).sum()

        def f_ref(q, k, v):
            return (_attention_reference(q, k, v, True,
                                         1.0 / np.sqrt(16)) ** 2).sum()

        gu = jax.grad(f_uly, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gu, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_heads_not_divisible_rejected(self, sp_mesh):
        q, k, v = _qkv(jax.random.PRNGKey(7), h=3)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, sp_mesh)


class TestSequenceParallelTraining:
    """auto_accelerate with sequence_parallel trains end-to-end and matches
    the pure-FSDP numerics (the reference's SP promise: same model, sharded
    sequence)."""

    @pytest.mark.parametrize("impl", ["ulysses", "ring"])
    def test_sp_training_matches_fsdp(self, impl):
        import optax

        from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
        from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

        def train(strategy, steps=4):
            model = GPT(GPTConfig(vocab_size=512, n_layer=2, n_head=4,
                                  n_embd=64, block_size=128,
                                  dtype=jnp.float32))
            res = auto_accelerate(model, optimizer=optax.adamw(1e-2),
                                  strategy=strategy)
            data = jax.random.randint(jax.random.PRNGKey(0), (8, 129), 0, 512)
            batch = res.place_batch({"input_ids": data[:, :-1],
                                     "labels": data[:, 1:]}, seq_axis=1)
            state, losses = res.state, []
            for _ in range(steps):
                state, m = res.train_step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        base = train([("fsdp", {})])
        sp = train([("sequence_parallel", {"size": 4, "impl": impl}),
                    ("fsdp", {})])
        np.testing.assert_allclose(sp, base, rtol=2e-2)
