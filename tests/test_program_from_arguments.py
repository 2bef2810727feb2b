"""The traced program is a function of its arguments.

What `ops/flash_attention.py` traces — the pack width, the grid, the
backward's form, the streamed fallback — is decided from shapes alone;
the three framework keys and the warm spec hold arguments alone.  The
process environment is in none of it: the five names that once were
(`RETIRED`) change nothing when set.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import flash_attention as fa


def _pallas_calls(jaxpr):
    """[(kernel name, grid)] of every pallas_call under `jaxpr`."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        tuple(eqn.params["grid_mapping"].grid)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_calls(sub))
    return out


def _attention_grad_jaxpr(b, h, t, d, dtype=jnp.bfloat16):
    x = jax.ShapeDtypeStruct((b, h, t, d), dtype)
    return jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)


# ------------------------------------------- (A) shape -> program decisions


@pytest.mark.parametrize("bh,pack", [
    (288, 8), (100, 4), (80, 8), (24, 8), (12, 4), (6, 2), (7, 1), (1, 1)])
def test_pack_is_the_largest_of_8_4_2_1_dividing_the_heads(bh, pack):
    assert fa._fit_pack(bh) == pack


@pytest.mark.parametrize("causal", [True, False])  # ring steps are not
@pytest.mark.parametrize("sq,sk,block,fused", [
    (1024, 1024, 1024, True),    # GPT-2's context: one block each way
    (512, 512, 1024, True),      # a block is capped at the sequence
    (256, 256, 256, True),
    (256, 512, 512, True),       # one block each way, sq != sk
    (1024, 1024, 512, False),    # 2 x 2
    (2048, 2048, 1024, False),
    (1024, 2048, 1024, False),   # one query block, two key blocks
    (2048, 1024, 1024, False),
])
def test_backward_is_fused_iff_one_block_each_way(sq, sk, block, fused,
                                                  causal):
    q = jax.ShapeDtypeStruct((2, sq, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((2, sk, 64), jnp.float32)
    lse = jax.ShapeDtypeStruct((2, 1, sq), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, o, l, g: fa._fa_backward_pallas(
            q, k, v, o, l, g, causal, 0.125, block, block, True))(
        q, k, k, q, lse, q)
    names = sorted(name for name, _ in _pallas_calls(jaxpr.jaxpr))
    assert names == (["dwt_fa_bwd_fused"] if fused
                     else ["dwt_fa_bwd_dkv", "dwt_fa_bwd_dq"])


@pytest.mark.parametrize("sq,sk,streamed", [
    (2048, 2048, True), (1024, 4096, True),
    (2048, 2047, False), (1024, 1024, False)])
def test_streamed_fallback_switches_at_2048_squared(sq, sk, streamed):
    assert fa._use_streamed(sq, sk) is streamed


@pytest.mark.parametrize("b,h,t,d,pack,grid,fused,tiles", [
    (24, 12, 1024, 64, 8, (1, 1), True, (3, 4)),      # gpt2_124m.steady
    (4, 25, 1024, 64, 4, (1, 1), True, (3, 4)),       # gpt2_xl, per chip
    (5, 16, 4096, 128, 8, (4, 4), False, (36, 64)),   # olmoe_1b_7b.steady
])
def test_the_cells_attention_plans(monkeypatch, b, h, t, d, pack, grid,
                                   fused, tiles):
    """PERF.md section 5's prose, pinned: what each benchmark cell's
    attention traces to on the chip, from its shape alone."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    calls = _pallas_calls(_attention_grad_jaxpr(b, h, t, d).jaxpr)
    groups = b * h // pack
    want = [("dwt_fa_fwd", (groups,) + grid)]
    want += [("dwt_fa_bwd_fused", (groups,))] if fused else \
        [("dwt_fa_bwd_dq", (groups,) + grid),
         ("dwt_fa_bwd_dkv", (groups,) + grid)]
    assert sorted(calls) == sorted(want)
    assert fa._fit_pack(b * h) == pack
    assert fa.causal_tile_count(t, t) == tiles


# ----------------------------------- (B) the environment is in none of it

RETIRED = [("DWT_FA_PACK", "4"), ("DWT_FA_NO_FUSED", "1"),
           ("DWT_FA_STREAMED", "1"), ("DWT_FP8_DENSE", "1"),
           ("DWT_REMAT_POLICY", "dots")]


def _programs_and_keys(monkeypatch):
    from dlrover_wuqiong_tpu.auto.compile_cache import train_step_cache_key
    from dlrover_wuqiong_tpu.auto.warm_pool import WarmSpec
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
    from dlrover_wuqiong_tpu.serving.engine import (
        ServeSpec,
        serve_step_cache_key,
    )
    from dlrover_wuqiong_tpu.telemetry.perf import executable_key

    cfg = dataclasses.replace(GPTConfig.nano(), remat=True, fp8=False,
                              use_flash_attention=False)
    model = GPT(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    out = {
        # the reference path a CPU run takes, and the kernels' path
        "attention_cpu": str(_attention_grad_jaxpr(1, 8, 256, 64)),
        "model": str(jax.make_jaxpr(jax.grad(
            lambda p: model.apply(p, ids).astype(jnp.float32).sum()))(
            params)),
        "train_key": train_step_cache_key(
            {"fsdp": 8}, {"remat": True}, {"n_layer": 2}, True, 1,
            backend="cpu"),
        "executable_key": executable_key("fp", 8, "cpu"),
        "serve_key": serve_step_cache_key({"n_layer": 2}, ServeSpec(),
                                          backend="cpu"),
        "spec_key": WarmSpec(
            n_devices=8, strategy=[["fsdp", {}]],
            model={"kind": "gpt", "config": {"n_layer": 2}},
            batch_shape=[8, 32]).spec_key(),
    }
    with monkeypatch.context() as m:
        m.setattr(fa, "_on_tpu", lambda: True)
        out["attention_tpu"] = str(_attention_grad_jaxpr(1, 8, 256, 64))
    return out


@pytest.mark.parametrize("name,value", RETIRED)
def test_a_retired_variable_changes_no_program_and_no_key(monkeypatch, name,
                                                          value):
    monkeypatch.delenv(name, raising=False)
    unset = _programs_and_keys(monkeypatch)
    monkeypatch.setenv(name, value)
    assert _programs_and_keys(monkeypatch) == unset


# -------------------------- (C) fused, split and reference backward agree


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("sq,sk", [
    (128, 128), (256, 256), (128, 256), (256, 128)])
def test_fused_and_split_backward_agree_with_the_reference(sq, sk, d):
    """The same arrays through both forms of the backward — one block
    each way, and the blocks halved — and through `jax.grad` of the plain
    reference (interpret mode)."""
    keys = jax.random.split(jax.random.PRNGKey(sq + sk + d), 4)
    q, g = (jax.random.normal(kx, (2, sq, d), jnp.float32)
            for kx in keys[:2])
    k, v = (jax.random.normal(kx, (2, sk, d), jnp.float32)
            for kx in keys[2:])
    scale = d ** -0.5

    def loss(q, k, v):
        o, _ = fa._reference_with_lse(q[None], k[None], v[None], True, scale)
        return (o[0] * g).sum()

    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for block_q, block_k in ((sq, sk), (sq // 2, sk // 2)):
        o, lse = fa._fa_forward_pallas(q, k, v, True, scale, block_q,
                                       block_k, interpret=True)
        got = fa._fa_backward_pallas(q, k, v, o, lse, g, True, scale,
                                     block_q, block_k, interpret=True)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=5e-4)


# ------------------------------------------- (D) fused K and TrainingArgs


@pytest.fixture(scope="module")
def built():
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                              use_flash_attention=False, remat=False)
    return auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                           strategy=[("fsdp", {})], materialize=False,
                           seq_len=32)


def test_fused_train_step_of_one_is_train_step(built):
    assert built.fused_train_step(1) is built.train_step
    assert built.fused_train_step(0) is built.train_step
    assert built._fused_cache == {}


def test_fused_train_step_is_cached_by_k(built):
    four = built.fused_train_step(4)
    assert four is not built.train_step
    assert built.fused_train_step(4) is four
    assert built.fused_train_step(2) is not four
    assert sorted(built._fused_cache) == [2, 4]


def test_tune_variants_accepts_zero_only():
    from dlrover_wuqiong_tpu.trainer.trainer import TrainingArgs

    assert TrainingArgs(tune_variants=0).tune_variants == 0
    for bad in (1, -1, 3):
        with pytest.raises(ValueError, match="tune_variants"):
            TrainingArgs(tune_variants=bad)


def test_training_args_has_33_fields_and_no_tuner_knob():
    from dlrover_wuqiong_tpu.trainer.trainer import TrainingArgs

    names = [f.name for f in dataclasses.fields(TrainingArgs)]
    assert len(names) == 33
    assert [n for n in names if n.startswith("tune_")] == \
        ["tune_config_steps", "tune_variants"]
