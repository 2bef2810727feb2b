"""`bailing_hybrid` through `models/bailing_hybrid.py`: KDA mixers (a delta
rule whose decay is a number a key CHANNEL) beside gated latent
attention, a leading dense SwiGLU and expert layers routed under a group
limit — against the plain reference
(`benchmark/reference_bailing_hybrid.py`) at a nano size on the CPU,
float32 on both sides; the recurrence's routes against each other and
against the decay-a-head path; the share tests (heads of both mixers,
experts); the group limit; the parameter counts at the published widths;
the counters; the sharding rules; what is refused.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_bailing_hybrid as ref
from dlrover_wuqiong_tpu.models import moe
from dlrover_wuqiong_tpu.models.bailing_hybrid import (
    BailingHybrid,
    BailingHybridConfig,
)
from dlrover_wuqiong_tpu.models.kda import KDAConfig, KDAMixer
from dlrover_wuqiong_tpu.models.latent_attention import (
    LatentAttention,
    LatentAttentionConfig,
)
from dlrover_wuqiong_tpu.models.llama import rope_freqs
from dlrover_wuqiong_tpu.ops import delta_rule as dr
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 48  # three chunks of 16


def nano(**over):
    """Three layers, one of each kind: 0 KDA + dense, 1 KDA + experts,
    2 latent + experts; experts 4-7 of 16 held."""
    return BailingHybridConfig.nano(**{**dict(
        num_layers=3, dtype=jnp.float32, remat=False,
        use_flash_attention=False, experts_held=4, first_expert=4), **over})


def reference_loss(cfg, **control):
    return functools.partial(
        ref.loss, n_layer=cfg.num_layers, group_size=cfg.layer_group_size,
        first_dense=cfg.first_dense_layers, heads=cfg.num_heads,
        lower_bound=cfg.kda_lower_bound, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, theta=cfg.rope_theta, top_k=cfg.top_k,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        routed_scaling=cfg.routed_scaling, first_expert=cfg.first_expert,
        eps=cfg.rms_eps, **control)


def with_opinions(params, seed, scale=0.1):
    """Every leaf off its draw, so that no scale is 1 and no term is
    symmetric by accident."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(next(keys), a.shape), params)


def batch_of(seed, rows=2):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0, 256)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


# ------------------------------------------------- model against reference

@pytest.fixture(scope="module")
def both_sides():
    """(leaf names, the model's loss and gradient, the reference's, the
    parameters, the step's counters) at nano size, every block
    recomputed."""
    cfg = nano(remat=True)
    model = BailingHybrid(cfg)
    params = with_opinions(
        jax.jit(model.init_params)(jax.random.PRNGKey(1)), 2)
    batch = batch_of(3)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            make_lm_loss(model.apply).with_stats, has_aux=True))(
                params, batch)
        want = jax.jit(jax.value_and_grad(reference_loss(cfg)))(
            params, batch)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    return names, (loss, grads), want, params, stats


# 2 KDA mixers x 11 + 1 latent x 6 + 3 x 2 norms + dense 3 + 2 x 8 expert
# layer leaves + table, head, norm
N_LEAVES = 22 + 6 + 6 + 3 + 16 + 3


def test_the_loss_is_the_references(both_sides):
    names, (loss, _), (ref_loss, _), *_ = both_sides
    assert len(names) == N_LEAVES
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)


@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_every_leafs_gradient_is_the_references(both_sides, leaf):
    """Leaf by leaf (the norm over 650M entries that the chip compares
    would average a wrong leaf away)."""
    names, (_, grads), (_, ref_grads), *_ = both_sides
    got = jax.tree.leaves(grads)[leaf]
    want = jax.tree.leaves(ref_grads)[leaf]
    if "selection_bias" in names[leaf]:
        assert not np.any(got) and not np.any(want)  # it chooses only
        return
    np.testing.assert_allclose(
        got, want, rtol=1e-3, atol=1e-4 * float(jnp.abs(want).max()),
        err_msg=names[leaf])


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_equation_is_another_loss(both_sides, wrong):
    """Each control the reference names moves the loss by more than the
    two sides differ: the reference would tell it from the model."""
    _, _, (right, _), params, _ = both_sides
    with jax.default_matmul_precision("highest"):
        off = float(jax.jit(reference_loss(nano(), wrong=wrong))(
            params, batch_of(3)))
    assert abs(off - float(right)) > 2e-5 * float(right), (wrong, off)


# ------------------------------------------------ the recurrence's routes

def _operands(seed, b=2, t=128, h=3, dk=16, dv=24, bound=-5.0, sharp=3.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = bound * jax.nn.sigmoid(sharp * jax.random.normal(ks[3],
                                                         (b, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _out_and_grads(fn, operands):
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape))), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*operands)
    return (o, *grads)


@pytest.mark.parametrize("t,chunk,bound", [
    (128, 64, -5.0), (64, 16, -5.0), (96, 32, -0.3)])
def test_the_chunked_channel_form_is_the_sequential_recurrence(
        t, chunk, bound):
    """Output and all five cotangents (q, k, v, the decay, beta), decays
    from e^-5 a step (the published lower bound) to nearly none."""
    operands = _operands(t + chunk, t=t, bound=bound)
    assert dr.delta_route(t, chunk, 3, 16, 24, channel_decay=True) \
        == "chunked"
    want = _out_and_grads(dr.gated_delta_rule_sequential, operands)
    got = _out_and_grads(functools.partial(
        dr.gated_delta_rule, chunk=chunk, dtype=jnp.float32), operands)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()),
            err_msg=name)


@pytest.mark.parametrize("route", ("sequential", "chunked"))
def test_a_decay_alike_in_every_channel_is_the_decay_a_head(route):
    """With alpha the same in every channel of a head the channel form IS
    `ops/delta_rule.py`'s recurrence: each route against the scalar
    path's, output and cotangents (the decay's summed over a head's
    channels)."""
    q, k, v, g, beta = _operands(7, sharp=1.0)
    g_head = g[..., 0]
    alike = jnp.broadcast_to(g_head[..., None], g.shape)
    fn = dr.gated_delta_rule_sequential if route == "sequential" else \
        functools.partial(dr.gated_delta_rule, chunk=64, dtype=jnp.float32)
    want = _out_and_grads(fn, (q, k, v, g_head, beta))
    got = _out_and_grads(fn, (q, k, v, alike, beta))
    got = (*got[:4], got[4].sum(-1), got[5])
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()),
            err_msg=name)


@pytest.mark.parametrize("floor", (-5.0, dr.CHANNEL_DECAY_FLOOR))
def test_the_sub_blocks_hold_at_the_lower_bound_on_every_step(floor):
    """g = -5 (the published bound; -8, the form's own) in every channel
    of every step: e^{-b} over a chunk of 64 would be e^320; the
    sub-blocks' exponents stay within 8 x 8 = 64 of 0, so nothing is inf
    or nan, forward or backward, and the result is the sequential
    recurrence's.  (The products' operands keep float32's exponent in
    bfloat16; the CPU has no bfloat16 product to try it with.)"""
    q, k, v, g, beta = _operands(11)
    g = jnp.full_like(g, floor)
    got = _out_and_grads(functools.partial(
        dr.gated_delta_rule, chunk=64, dtype=jnp.float32),
        (q, k, v, g, beta))
    assert all(bool(jnp.isfinite(a).all()) for a in got)
    want = _out_and_grads(dr.gated_delta_rule_sequential, (q, k, v, g, beta))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("t,chunk,on_tpu,want", [
    (8192, 64, True, ("kernel", 4)),  # the cell's: the channel pair
    (8192, 64, False, "chunked"), (16, 64, True, "sequential"),
    (100, 64, False, "sequential"), (96, 24, True, "sequential")],
    indirect=["on_tpu"])
def test_the_route_of_a_decay_a_channel(t, chunk, on_tpu, want):
    """`delta_route` answers for the channel form from the call's shapes:
    whole chunks of whole sub-blocks are chunked — on one TPU device the
    channel pair's, by the plan the decay-a-head pair has at the same
    shapes — anything else sequential."""
    assert dr.delta_route(t, chunk, 16, 128, 128, channel_decay=True) \
        == want
    if want != "sequential" and on_tpu:
        assert dr.delta_route(t, chunk, 16, 128, 128) == ("kernel", 4)


# ----------------------------------------------------------- share tests

def _kda_heads(params, cfg, lo, hi):
    """The leaves of heads lo..hi-1 of a KDA mixer's tree."""
    n, dk, dv = cfg.num_heads, cfg.key_dim, cfg.value_dim
    cut_k, cut_v = slice(lo * dk, hi * dk), slice(lo * dv, hi * dv)
    conv = params["conv_kernel"]

    def cols(name, cut):
        return {"kernel": params[name]["kernel"][:, cut]}

    return {
        "q_proj": cols("q_proj", cut_k), "k_proj": cols("k_proj", cut_k),
        "v_proj": cols("v_proj", cut_v), "f_proj": cols("f_proj", cut_k),
        "b_proj": cols("b_proj", slice(lo, hi)),
        "g_proj": cols("g_proj", slice(lo, hi)),
        "o_proj": {"kernel": params["o_proj"]["kernel"][cut_v]},
        "conv_kernel": jnp.concatenate([
            conv[:, :n * dk][:, cut_k], conv[:, n * dk:2 * n * dk][:, cut_k],
            conv[:, 2 * n * dk:][:, cut_v]], axis=1),
        "A_log": params["A_log"][lo:hi], "dt_bias": params["dt_bias"][cut_k],
        "gate_norm": params["gate_norm"]}


def test_the_halves_of_a_kda_mixers_heads_add_up_to_the_mixer():
    """Heads 0-1 and heads 2-3 add up to the four-head mixer: the state,
    both L2 norms, the three gates and the output norm are per head, the
    convolution and the decay per channel, and `Wo`'s partial sums add.
    And the reference's mixer on a share is the model's."""
    cfg = KDAConfig(hidden_size=40, num_heads=4, key_dim=8, value_dim=8,
                    chunk_size=16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 40))
    whole = KDAMixer(cfg)
    params = with_opinions(
        jax.jit(whole.init)(jax.random.PRNGKey(1), x)["params"], 5)
    half = jax.jit(KDAMixer(dataclasses.replace(cfg, num_heads=2)).apply)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(whole.apply)({"params": params}, x)
        parts = [half({"params": _kda_heads(params, cfg, lo, lo + 2)}, x)
                 for lo in (0, 2)]
        np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-4,
                                   atol=1e-5)
        assert float(jnp.abs(parts[1]).max()) > 0.1 * float(
            jnp.abs(want).max())  # neither share is nothing
        held = jax.jit(functools.partial(
            ref.kda, heads=2, lower_bound=cfg.lower_bound, eps=cfg.eps))(
                x, _kda_heads(params, cfg, 2, 4))
        np.testing.assert_allclose(parts[1], held, rtol=1e-4, atol=1e-5)


def _latent_heads(params, cfg, lo, hi):
    qk, kv = cfg.qk_head_dim, cfg.qk_nope_head_dim + cfg.v_head_dim
    dv = cfg.v_head_dim
    return {
        "q_proj": {"kernel": params["q_proj"]["kernel"][:, lo * qk:hi * qk]},
        "kv_a_proj": params["kv_a_proj"], "kv_a_norm": params["kv_a_norm"],
        "kv_b_proj": {
            "kernel": params["kv_b_proj"]["kernel"][:, lo * kv:hi * kv]},
        "g_proj": {"kernel": params["g_proj"]["kernel"][:, lo:hi]},
        "o_proj": {"kernel": params["o_proj"]["kernel"][lo * dv:hi * dv]}}


def test_the_halves_of_gated_latent_attentions_heads_add_up_to_the_layer():
    """`num_heads` is the heads HELD: a head has its own columns of Wq,
    Wkv_b and the gate and its own rows of Wo, while Wkv_a, the latent's
    norm and the one rotated key are computed alike on both halves.  The
    gate is `LlamaConfig.attn_gate`'s form, counted as Laguna's is; with
    the gate off the layer has no `g_proj` (Kimi's and Xing's tree)."""
    cfg = LatentAttentionConfig(
        hidden_size=40, num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, kv_lora_rank=24, attn_gate=True, rms_eps=1e-6,
        dtype=jnp.float32, use_flash_attention=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 40))
    cos, sin = rope_freqs(8, SEQ, 6e6, None)
    whole = LatentAttention(cfg)
    params = with_opinions(
        jax.jit(whole.init)(jax.random.PRNGKey(1), x, cos, sin)["params"],
        5, 0.3)
    half = jax.jit(LatentAttention(
        dataclasses.replace(cfg, num_heads=2)).apply)
    with jax.default_matmul_precision("highest"):
        want, sown = jax.jit(functools.partial(
            whole.apply, mutable=["intermediates"]))(
                {"params": params}, x, cos, sin)
        parts = [half({"params": _latent_heads(params, cfg, lo, lo + 2)},
                      x, cos, sin) for lo in (0, 2)]
        np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-4,
                                   atol=1e-5)
        held = ref.latent(x, _latent_heads(params, cfg, 2, 4), heads=2,
                          nope=16, rope=8, theta=6e6, eps=1e-6)
        np.testing.assert_allclose(parts[1], held, rtol=1e-4, atol=1e-5)
        ungated = ref.latent(x, params, heads=4, nope=16, rope=8, theta=6e6,
                             eps=1e-6, wrong="attn_gate")
    assert float(jnp.abs(ungated - want).max()) > 1e-2
    gate = jax.nn.sigmoid(x @ params["g_proj"]["kernel"])
    mean, = jax.tree.leaves(sown["intermediates"]["attn_gate_mean"])
    np.testing.assert_allclose(mean, gate.mean(), rtol=1e-5)
    assert cfg.attention_params() == sum(
        a.size for a in jax.tree.leaves(params))
    plain = dataclasses.replace(cfg, attn_gate=False)
    assert "g_proj" not in jax.eval_shape(
        LatentAttention(plain).init, jax.random.PRNGKey(1), x, cos,
        sin)["params"]
    assert cfg.attention_params() - plain.attention_params() == 40 * 4


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Every share of the experts (four of eight: 32 experts in 4 groups
    of 8, 2 kept, 4 a token — the cell's router at a sixteenth of its
    width, where 64 chips hold 8 of 512 each), the shared expert counted
    once, add up to the uncut reference's layer — under the group limit,
    which every share applies alike."""
    hidden, width, n_exp, held = 24, 16, 32, 8
    base = moe.MoEConfig(
        num_experts=n_exp, top_k=4, impl="grouped", dtype=jnp.float32,
        aux_loss="none", score_func="sigmoid", selection_bias=True,
        routed_scaling=2.5, n_group=4, topk_group=2, shared_width=width)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, hidden))
    params = with_opinions(jax.jit(moe.MoEMLP(hidden, width, base).init)(
        jax.random.PRNGKey(1), x)["params"], 3, 0.3)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(
            x.reshape(-1, hidden), params, top_k=4, n_group=4, topk_group=2,
            routed_scaling=2.5, first_expert=0).reshape(x.shape)
        shared = ref._swiglu(
            x, params["shared_gate_proj"]["kernel"],
            params["shared_up_proj"]["kernel"],
            params["shared_down_proj"]["kernel"])
        total = shared
        for first in range(0, n_exp, held):
            share = {**params, **{
                name: params[name][first:first + held] for name in
                ("experts_w_in", "experts_w_gate", "experts_w_down")}}
            layer = moe.MoEMLP(hidden, width, dataclasses.replace(
                base, experts_held=held, first_expert=first))
            part, _ = jax.jit(functools.partial(
                layer.apply, mutable=["intermediates"]))(
                    {"params": share}, x)
            total = total + (part - shared)  # the shared expert once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# -------------------------------------------------------- the group limit

def _scores(seed, tokens=96, experts=64):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.nn.sigmoid(jax.random.normal(k1, (tokens, experts))),
            0.3 * jax.random.normal(k2, (experts,)))


def test_no_group_limit_is_the_parents_choice_bit_for_bit():
    """`n_group` = `topk_group` = 1 (the default): the choice and the
    gates are `top_k` of `probs + bias` and `probs` at the chosen, as
    before the limit existed, and the lowered program holds nothing
    new."""
    probs, bias = _scores(0)
    route = functools.partial(moe.route_top_k, top_k=8, bias=bias,
                              floor=False, scaling=2.5)
    gates, experts = route(probs)
    _, want = jax.lax.top_k(probs + bias, 8)
    np.testing.assert_array_equal(experts, want)
    picked = jnp.take_along_axis(probs, want, axis=-1)
    np.testing.assert_array_equal(
        gates, picked / (picked.sum(-1, keepdims=True) + 1e-20) * 2.5)
    explicit = functools.partial(route, n_group=1, topk_group=1)
    assert jax.jit(route).lower(probs).as_text() == \
        jax.jit(explicit).lower(probs).as_text()
    cfg = moe.MoEConfig(impl="grouped")
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    assert "n_group" not in cfg.grouped_only_fields()
    assert moe.MoEConfig(impl="grouped", n_group=8, topk_group=4
                         ).grouped_only_fields() == {"n_group": 8,
                                                     "topk_group": 4}


@pytest.mark.parametrize("seed", range(4))
def test_the_limited_choice_is_the_references_three_steps(seed):
    """8 of 64 experts from the 4 best of 8 groups (a group's score the
    sum of its two largest `probs + bias`): the reference finds them one
    entry at a time; and `group_limit_binds` counts the tokens whose
    choice an unlimited top-k would have made differently."""
    probs, bias = _scores(10 + seed)
    _, experts = moe.route_top_k(probs, 8, bias=bias, floor=False,
                                 n_group=8, topk_group=4)
    member = ref.chosen_experts(probs + bias, top_k=8, n_group=8,
                                topk_group=4)
    got = (experts[..., None] == jnp.arange(64)).any(-2)
    np.testing.assert_array_equal(got, member)
    groups = np.asarray(experts) // 8
    assert all(len(set(row)) <= 4 for row in groups)
    _, free = jax.lax.top_k(probs + bias, 8)
    differs = (np.sort(np.asarray(free), -1)
               != np.sort(np.asarray(experts), -1)).any(-1)
    assert 0 < differs.sum() < len(differs)
    assert int(moe.group_limit_binds(probs, bias, experts, 8, 4)) \
        == differs.sum()


def test_a_group_limit_that_does_not_fit_is_refused():
    probs, _ = _scores(1)
    for n_group, topk_group in ((7, 2), (8, 9), (8, 0), (64, 4)):
        with pytest.raises(ValueError, match="groups"):
            moe.route_top_k(probs, 8, n_group=n_group, topk_group=topk_group)
    with pytest.raises(ValueError, match="impl='grouped'"):
        moe.MoEMLP(8, 8, moe.MoEConfig(n_group=2, topk_group=1)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


# ------------------------------------------------------- parameter counts

def test_num_params_is_the_cells_count_and_the_published_models():
    """Shapes only: 648,853,344 at the cell's sizes (layers 0-6 with one
    leading dense layer, sixteen of thirty-two heads, eight of 512
    experts, an eighth of the vocabulary) and 124,050,077,152 uncut, by
    `num_params` and by the tree."""
    def tree_size(cfg):
        shapes = jax.eval_shape(BailingHybrid(cfg).init_params,
                                jax.random.PRNGKey(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    whole = BailingHybridConfig()
    assert whole.num_params() == 124_050_077_152
    # the tree at two periods of the uncut model (both leading dense
    # layers, two latent layers, every width and all 512 experts): what
    # `num_params` adds up a layer is what `init_params` draws
    two_periods = dataclasses.replace(whole, num_layers=12)
    assert two_periods.num_params() == tree_size(two_periods)
    assert whole.num_params() - two_periods.num_params() == 30 * (
        whole.moe_ffn_params() + 2 * 2560) \
        + 25 * whole.linear_config().num_params() \
        + 5 * whole.attention_config().attention_params()
    assert [i for i in range(42) if whole.mixer_kind(i) == "attention"] \
        == [5, 11, 17, 23, 29, 35, 41]
    cell = BailingHybridConfig(
        num_layers=7, first_dense_layers=1, num_heads=16, experts_held=8,
        vocab_size=19648)
    assert cell.num_params() == tree_size(cell) == 648_853_344
    assert cell.linear_config().num_params() == 26_323_088
    assert cell.attention_config().attention_params() == 16_720_384
    assert cell.dense_config().ffn_params() == 47_185_920
    held_16 = dataclasses.replace(cell, experts_held=16)
    assert held_16.num_params() * 16 > 14.4e9  # 14.91 GB: over the rung
    all_heads = dataclasses.replace(cell, num_heads=32)
    assert all_heads.num_params() == 822_036_416


def test_num_params_is_the_tree_at_nano_size(both_sides):
    params = both_sides[3]
    assert nano().num_params() == sum(
        a.size for a in jax.tree.leaves(params))


# ------------------------------------------------------------ the counters

def test_the_counters_ride_the_steps_metrics(both_sides):
    """`make_lm_loss.with_stats` hands out the delta rule's lanes and
    gates as it does for a decay a head, the KDA mixers' own two (the
    share of decay channels at the floor, the output gate's mean), the
    latent layer's gate mean and the group limit's share of tokens."""
    cfg, stats, batch = nano(), both_sides[4], batch_of(4)
    lanes = 2 * (cfg.linear_key_dim + cfg.linear_value_dim)  # two layers
    assert float(stats["delta_lanes_run"]) == \
        float(stats["delta_lanes_model"]) == lanes
    assert 0.0 < float(stats["delta_alpha_mean"]) < 1.0
    assert 0.0 < float(stats["delta_beta_mean"]) < 1.0
    assert 0.0 < float(stats["kda_decay_floor_share"]) < 1.0
    assert 0.0 < float(stats["kda_gate_mean"]) < 1.0
    assert 0.0 < float(stats["attn_gate_mean"]) < 1.0
    assert 0.0 < float(stats["moe_group_limit_binds"]) < 1.0
    assert float(stats["moe_rows_held"]) + float(stats["moe_rows_absent"]) \
        == 2 * 2 * SEQ * cfg.top_k

    # the floor's share and the gate's mean are the first layer's leaves'
    one = nano(num_layers=1)
    model = BailingHybrid(one)
    params = with_opinions(
        jax.jit(model.init_params)(jax.random.PRNGKey(1)), 7)
    _, stats = jax.jit(make_lm_loss(model.apply).with_stats)(params, batch)
    p = params["layers_0"]
    x = params["embed_tokens"]["embedding"][batch["input_ids"]]
    h = ref._rms_norm(x, p["input_norm"], one.rms_eps)
    p = p["linear_attention"]
    f = (h @ p["f_proj"]["kernel"] + p["dt_bias"]).reshape(2, SEQ, 4, 16)
    g = -5.0 * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    np.testing.assert_allclose(stats["kda_decay_floor_share"],
                               (g <= -4.95).mean(), rtol=1e-5)
    np.testing.assert_allclose(stats["delta_alpha_mean"], jnp.exp(g).mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(
        stats["kda_gate_mean"],
        jax.nn.sigmoid(h @ p["g_proj"]["kernel"]).mean(), rtol=1e-5)
    assert "moe_group_limit_binds" not in stats  # the one layer is dense


# ----------------------------------------------------------- the sharding

def test_sharding_rules_name_every_parameter():
    from jax.sharding import PartitionSpec as P

    from dlrover_wuqiong_tpu.parallel.sharding import (
        MOE_RULES,
        TRANSFORMER_RULES,
        path_of,
        spec_for_path,
    )

    rules = MOE_RULES + TRANSFORMER_RULES
    params = jax.eval_shape(BailingHybrid(nano()).init_params,
                            jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for path in paths:
        assert any(re.match(pat, path, re.IGNORECASE)
                   for pat, _ in rules), path
    la, at = "layers_0/linear_attention", "layers_2/attention"  # nano's
    want = {
        f"{la}/q_proj/kernel": P("fsdp", "tp"),
        f"{la}/k_proj/kernel": P("fsdp", "tp"),
        f"{la}/v_proj/kernel": P("fsdp", "tp"),
        f"{la}/f_proj/kernel": P("fsdp", "tp"),
        f"{la}/g_proj/kernel": P("fsdp", "tp"),
        f"{la}/b_proj/kernel": P("fsdp", None),
        f"{la}/o_proj/kernel": P("tp", "fsdp"),
        f"{la}/conv_kernel": P(), f"{la}/A_log": P(), f"{la}/dt_bias": P(),
        f"{la}/gate_norm/scale": P(),
        f"{at}/q_proj/kernel": P("fsdp", "tp"),
        f"{at}/kv_a_proj/kernel": P("fsdp", None),
        f"{at}/kv_b_proj/kernel": P("fsdp", "tp"),
        f"{at}/g_proj/kernel": P("fsdp", "tp"),
        f"{at}/o_proj/kernel": P("tp", "fsdp"),
        f"{at}/kv_a_norm/scale": P(),
        "layers_0/feed_forward/gate_proj/kernel": P("fsdp", "tp"),
        "layers_1/feed_forward/selection_bias": P(),
        "layers_1/feed_forward/router/kernel": P("fsdp", None),
        "lm_head/kernel": P("fsdp", "tp"), "norm/scale": P()}
    assert set(want) <= set(paths)
    for path, spec in want.items():
        assert spec_for_path(path, rules) == spec, path
    assert re.match(BailingHybrid.untrained_params[0],
                    "layers_1/feed_forward/selection_bias")


# --------------------------------------------------------- what is refused

@pytest.mark.parametrize("over,match", [
    (dict(swiglu_limits=(0, 0, 4.0)), "clamp"),
    (dict(mtp_layers=1, mtp_loss_weight=0.3), "multi-token"),
    (dict(kda_lower_bound=-11.0), "lower_bound")])
def test_what_the_stack_does_not_compute_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(BailingHybrid(nano(**over)).init_params,
                       jax.random.PRNGKey(0))


def test_the_published_mtp_weight_of_zero_builds_nothing():
    """`mtp_loss_scaling_factor` 0 as published: the module would add
    nothing to the loss or to any gradient, and the tree has none."""
    params = jax.eval_shape(BailingHybrid(nano(mtp_layers=1)).init_params,
                            jax.random.PRNGKey(0))
    assert not any(name.startswith("mtp") for name in params)


def test_a_mesh_of_several_devices_is_refused_and_one_device_steps():
    """Through `auto_accelerate`, as every configuration: on two devices
    the stack says what it cannot run; on one an optimizer step under
    `fsdp` runs with every block rematerialised, the selection bias left
    alone by the optimizer and moved by its rule."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    model = BailingHybrid(nano(remat=True, bias_update_rate=0.05))
    with pytest.raises(ValueError, match="one device"):
        auto_accelerate(model, strategy=[("fsdp", {})],
                        devices=jax.devices()[:2],
                        optimizer=optax.adamw(1e-3), seq_len=SEQ)
    res = auto_accelerate(model, strategy=[("fsdp", {})],
                          devices=jax.devices()[:1],
                          optimizer=optax.adamw(1e-3), seq_len=SEQ)
    before = np.asarray(
        res.state.params["layers_1"]["feed_forward"]["selection_bias"])
    batch = {k: np.asarray(v) for k, v in batch_of(5, rows=4).items()}
    state, metrics = res.train_step(res.state, res.place_batch(batch))
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1
    assert 0.0 < float(metrics["moe_group_limit_binds"]) < 1.0
    after = np.asarray(
        state.params["layers_1"]["feed_forward"]["selection_bias"])
    assert 0 < np.abs(after - before).max() <= 0.05 + 1e-6
