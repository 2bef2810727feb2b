"""Laguna (`models/laguna.py`: full layers of few heads rotated in part
under YaRN beside windowed layers of more heads rotated whole, a sigmoid
gate a head on every attention's output, a leading dense SwiGLU, softmax
top-k experts scaled and a shared expert, of which a chip may hold a
share) against its plain reference (`benchmark/reference_laguna.py`) in
float32 at a small size: loss, logits and per-leaf gradients over the
published pattern with a window that bites; the reference with one
equation wrong, each told apart; what the new fields of `LlamaConfig` do
(`attn_gate`, tables narrower than the head) off the TPU and on the direct kernels in
interpret mode at a window shorter than their block; the shares' parts
of one expert layer against the uncut reference layer; the counters; the
benchmark configuration's parameter count.
"""

import dataclasses
import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_laguna as ref
from dlrover_wuqiong_tpu.models import attention as attn_mod
from dlrover_wuqiong_tpu.models.laguna import (
    FULL,
    SLIDING,
    Laguna,
    LagunaConfig,
)
from dlrover_wuqiong_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    apply_rope,
    rope_freqs,
)
from dlrover_wuqiong_tpu.models.moe import MoEConfig, MoEMLP
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.ops import head_gate
from dlrover_wuqiong_tpu.ops import rope
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 48  # two windows of 24: the window bites in three layers of five


def _nano(**over):
    return LagunaConfig.nano(**{
        **dict(dtype=jnp.float32, remat=False), **over})


def _rope_parameters(cfg: LagunaConfig) -> dict:
    """`rope_parameters` as config.json writes it, from the program's
    config."""
    yarn = cfg.full_rope_scaling
    return {
        FULL: {"rope_theta": cfg.full_rope_theta, "rope_type": "yarn",
               "factor": yarn.factor,
               "original_max_position_embeddings":
               yarn.original_max_position_embeddings,
               "beta_slow": yarn.beta_slow, "beta_fast": yarn.beta_fast,
               "attention_factor": 0.1 * math.log(yarn.factor) + 1.0,
               "partial_rotary_factor": cfg.full_rotary_factor},
        SLIDING: {"rope_type": "default",
                  "rope_theta": cfg.sliding_rope_theta,
                  "partial_rotary_factor": cfg.sliding_rotary_factor}}


def _sizes(cfg: LagunaConfig, **over):
    return {**dict(
        layer_types=cfg.layer_types, mlp_layer_types=cfg.mlp_layer_types,
        rope_parameters=_rope_parameters(cfg), window=cfg.sliding_window,
        n_kv_head=cfg.num_kv_heads, head_dim=cfg.head_dim, top_k=cfg.top_k,
        routed_scaling=cfg.routed_scaling, first_expert=cfg.first_expert,
        eps=cfg.rms_eps), **over}


def _batch(seed=0, batch=2, vocab=256):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                             vocab)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _params(cfg, seed=0):
    params = Laguna(cfg).init_params(jax.random.PRNGKey(seed), seq=SEQ)
    # expert matrices drawn at 0.02 would leave the experts' part of the
    # stream, and its gradients, too small to test; a gate's product at
    # lecun's width leaves every gate near a half: both wider

    def wider(path, leaf):
        names = [p.key for p in path]
        if names[-1].startswith("experts_w"):
            return leaf * 10.0
        return leaf * 4.0 if "g_proj" in names else leaf
    return jax.tree_util.tree_map_with_path(wider, params)


CASES = {
    "whole": dict(),
    "share": dict(experts_held=2, first_expert=4),
    "aux": dict(aux_loss_weight=0.01),
    "remat": dict(remat=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_match_the_reference(case):
    cfg = _nano(**CASES[case])
    assert cfg.top_k < cfg.num_experts and cfg.sliding_window < SEQ
    assert cfg.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert cfg.num_heads_per_layer == (6, 8, 8, 8, 6)
    assert cfg.rotary_dim(FULL) * 2 == cfg.rotary_dim(SLIDING) == 16
    model, params, batch = Laguna(cfg), _params(cfg), _batch()
    loss_fn = make_lm_loss(model.apply)
    got, got_g = jax.value_and_grad(loss_fn)(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(functools.partial(
            ref.loss, aux_weight=cfg.aux_loss_weight, **_sizes(cfg)))(
                params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    assert len(flat) == len(jax.tree.leaves(want_g))
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=str(path))


def test_the_logits_match_the_reference():
    cfg = _nano()
    params, batch = _params(cfg), _batch(seed=1)
    got = Laguna(cfg).apply({"params": params}, batch["input_ids"])
    with jax.default_matmul_precision("highest"):
        x, w_head, _ = ref.forward(params, batch["input_ids"], **_sizes(cfg))
        want = x @ w_head
    np.testing.assert_allclose(got, want, atol=2e-5
                               * float(jnp.abs(want).max()))


@pytest.mark.parametrize("wrong", [
    dict(wrong=("gate",)),             # the output gate dropped
    dict(wrong=("window",)),           # the window dropped from the mask
    dict(wrong=("rotary",)),           # a full layer's whole head rotated
    dict(wrong=("yarn",)),             # a full layer's tables unscaled
    dict(wrong=("yarn_width",)),       # the ramp over the head size
    dict(wrong=("shared",)),           # the shared expert dropped
    dict(window=16),                   # another window
    dict(top_k=2),
    dict(routed_scaling=1.0),
], ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_the_reference_with_one_term_wrong_is_told_apart(wrong):
    """The comparison above is tight enough to tell the model from its
    neighbours: the same reference with one equation changed."""
    cfg = _nano()
    params, batch = _params(cfg), _batch()
    got = make_lm_loss(Laguna(cfg).apply)(params, batch)
    with jax.default_matmul_precision("highest"):
        same = ref.loss(params, batch, **_sizes(cfg))
        other = ref.loss(params, batch, **_sizes(cfg, **wrong))
    assert abs(float(got) - float(same)) < 1e-5 * abs(float(got))
    assert abs(float(got) - float(other)) > 1e-4 * abs(float(got))


def test_one_head_count_for_every_layer_is_another_model():
    """The head count is the layer's: a full layer's projections are 6
    heads wide and a sliding layer's 8, and a model of one count for
    every layer does not take the tree."""
    cfg = _nano()
    params = _params(cfg)
    for layer, heads in enumerate(cfg.num_heads_per_layer):
        attention = params[f"layers_{layer}"]["attention"]
        assert attention["q_proj"]["kernel"].shape == (64, heads * 16)
        assert attention["o_proj"]["kernel"].shape == (heads * 16, 64)
        assert attention["g_proj"]["kernel"].shape == (64, heads)
        assert attention["k_proj"]["kernel"].shape == (64, 2 * 16)
    one_count = _nano(num_heads_per_layer=(8,) * 5)
    assert one_count.num_params() != cfg.num_params()
    with pytest.raises(Exception, match="q_proj|shape"):
        Laguna(one_count).apply({"params": params}, _batch()["input_ids"])
    with pytest.raises(ValueError, match="one entry a layer"):
        Laguna(_nano(num_heads_per_layer=(8,) * 4)).init_params(
            jax.random.PRNGKey(0))


def _sliced_rope(x, cos, sin):
    """x (b, s, h, d): the first 2 * half features cut out, rotated by
    halves and joined to the rest."""
    half = cos.shape[-1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s,
                            x[..., 2 * half:]], -1)


@pytest.mark.parametrize("rotary", [8, 16, 24, 32])
@pytest.mark.parametrize("flat", [False, True], ids=["heads", "rows"])
def test_a_head_rotated_in_part_passes_the_rest(rotary, flat):
    """`apply_rope(..., head_dim=d)` on both layouts against the cut and
    the join, forward and cotangent; the whole head is the old call."""
    b, s, h, d = 2, 12, 3, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    cos, sin = rope_freqs(rotary, s, 100.0)
    want, pull = jax.vjp(lambda x: _sliced_rope(x, cos, sin), x)
    shape = (b, s, h * d) if flat else x.shape
    got, pull_got = jax.vjp(
        lambda x: apply_rope(x, cos, sin, head_dim=d), x.reshape(shape))
    np.testing.assert_allclose(got.reshape(x.shape), want, atol=1e-6)
    if rotary < d:
        np.testing.assert_array_equal(got.reshape(x.shape)[..., rotary:],
                                      x[..., rotary:])
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    np.testing.assert_allclose(pull_got(g.reshape(shape))[0].reshape(x.shape),
                               pull(g)[0], atol=1e-6)
    if rotary == d:
        np.testing.assert_array_equal(
            got, apply_rope(x.reshape(shape), cos, sin))


def test_yarns_ramp_is_computed_over_the_rotary_width():
    """At the published sizes: 64 rotated features of 128, theta
    500,000, factor 64 over 4,096 positions, beta 64 and 1 — the ramp
    runs between pairs 5 and 16 of 32, where the head size would put it
    between 11 and 32 of 64; the tables carry 0.1 ln 64 + 1."""
    cfg = LagunaConfig()
    yarn = cfg.full_rope_scaling
    assert cfg.rotary_dim(FULL) == 64 and cfg.rotary_dim(SLIDING) == 128
    assert yarn.ramp_ends(64, cfg.full_rope_theta) == (5, 16)
    assert yarn.ramp_ends(128, cfg.full_rope_theta) == (11, 32)
    assert yarn.table_mscale == pytest.approx(1.4158883083359672, rel=1e-15)
    assert yarn.softmax_mscale == 1.0
    (cos, sin), (cos_s, sin_s) = cfg.rope_tables(96)
    assert cos.shape == (96, 32) and cos_s.shape == (96, 64)
    rope = _rope_parameters(cfg)
    inv, factor = ref.inv_freq(rope[FULL], 64)
    t = jnp.arange(96, dtype=jnp.float32)[:, None]
    np.testing.assert_allclose(cos, jnp.cos(t * inv) * factor, atol=1e-6)
    np.testing.assert_allclose(sin, jnp.sin(t * inv) * factor, atol=1e-6)
    plain = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    wide, _ = ref.inv_freq(rope[FULL], 64, ramp_width=128)
    assert float(jnp.abs(wide / inv - 1).max()) > 0.5
    inv_s, one = ref.inv_freq(rope[SLIDING], 128)
    assert one == 1.0
    np.testing.assert_allclose(cos_s, jnp.cos(t * inv_s), atol=1e-6)


def _gated(heads, window=0, flash=True, kv=2, d=16):
    return LlamaConfig(hidden_size=64, num_heads=heads, num_kv_heads=kv,
                       attn_head_dim=d, dtype=jnp.float32,
                       attn_window=window, attn_gate=True,
                       use_flash_attention=flash)


def _attention_params(layer, x, cos, sin, seed=1):
    params = layer.init(jax.random.PRNGKey(seed), x, cos, sin)["params"]
    return {**params, "g_proj": {"kernel": params["g_proj"]["kernel"] * 4.0}}


@pytest.mark.parametrize("flash", [True, False])
@pytest.mark.parametrize("heads,window,rotary", [(6, 0, 8), (8, 10, 16)])
def test_a_gated_attention_layer_is_the_references(heads, window, rotary,
                                                   flash):
    """`LlamaConfig.attn_gate` and tables `rotary` / 2 wide through
    `LlamaAttention`'s routes off the TPU: the reference's attention,
    and not the ungated one's."""
    cfg = _gated(heads, window, flash)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    cos, sin = rope_freqs(rotary, 32, 100.0)
    layer = LlamaAttention(cfg)
    params = _attention_params(layer, x, cos, sin)
    assert params["g_proj"]["kernel"].shape == (64, heads)
    assert cfg.attention_params() == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    got, upd = layer.apply({"params": params}, x, cos, sin,
                           mutable=["intermediates"])
    inv = 1.0 / 100.0 ** (jnp.arange(0, rotary, 2) / rotary)
    sizes = dict(n_kv_head=2, head_dim=16, inv=inv, factor=1.0,
                 window=window or None)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, params, gate=True, **sizes)
        ungated = ref.attention(x, params, gate=False, **sizes)
        gates = jax.nn.sigmoid(x @ params["g_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want - ungated).max()) > 1e-2
    assert float(gates.std()) > 0.1  # no gate sits at its half
    mean, = upd["intermediates"]["attn_gate_mean"]
    assert float(mean) == pytest.approx(float(gates.mean()), rel=1e-5)
    # without the field there is no leaf and no counter
    bare = dataclasses.replace(cfg, attn_gate=False)
    assert "g_proj" not in LlamaAttention(bare).init(
        jax.random.PRNGKey(1), x, cos, sin)["params"]


@pytest.fixture
def direct(on_tpu, monkeypatch):
    """tests/test_flash_attention_grouped.py's: the direct entry as the
    chip runs it, its kernels interpreted, at blocks of 64 — and the
    rotation's and the gate's, which one TPU device takes beside them."""
    monkeypatch.setattr(rope, "_rope_kernels", functools.partial(
        rope._rope_kernels, interpret=True))
    monkeypatch.setattr(head_gate, "_gate_kernels", functools.partial(
        head_gate._gate_kernels, interpret=True))
    for name in ("_projected_forward", "_projected_backward"):
        monkeypatch.setattr(fa, name, functools.partial(
            lambda kernel, *a, **kw: kernel(*a, **{**kw, "interpret": True}),
            getattr(fa, name)))
    monkeypatch.setattr(fa, "_PROJECTED_BLOCK", 64)
    monkeypatch.setattr(fa, "_CAUSAL_TILE", 32)


@pytest.mark.parametrize("heads,window,rotary", [
    (6, 0, 64),      # a full layer: groups of 3, half the head rotated
                     # (`dwt_rope` passes the rest inside the kernel)
    (8, 32, 128),    # a sliding layer: groups of 4, a window of HALF a block
    (8, 20, 128),    # and one under a tile that is no multiple of anything
])
def test_the_direct_kernels_run_both_kinds_of_layer(direct, heads, window,
                                                    rotary):
    """Heads of 128 over 2 kv heads go direct: the kernels index k and v
    at their own width (groups of 3 and of 4), a window SHORTER than the
    kernels' block runs the windowed kernels on two blocks a query block
    (`_window_plan`: d_max = 1), the gate multiplies what they wrote —
    output and every gradient against the plain reference."""
    t, d = 256, 128
    cfg = _gated(heads, window, kv=2, d=d)
    assert attn_mod.goes_direct(cfg, heads, d, t)
    assert fa.kv_route(heads, 2, d) == ("indexed", heads // 2)
    if window:
        plan = fa._window_plan(window, t // 64, t // 64, 64, 64, 0)
        assert plan["steps"] == 2 and plan["whole"] is None
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 64))
    cos, sin = rope_freqs(rotary, t, 10000.0)
    layer = LlamaAttention(cfg)
    params = _attention_params(layer, x, cos, sin)
    w = jax.random.normal(jax.random.PRNGKey(2), (1, t, 64))

    def run(params, x):
        return (layer.apply({"params": params}, x, cos, sin) * w).sum()

    text = str(jax.make_jaxpr(run)(params, x))
    assert ("dwt_fa_win_fwd" in text) == bool(window)
    # q's rotation and k's are the kernel's, half a head as the whole
    assert rope.rope_route(heads * d, d, None, rotary) == "kernel"
    assert text.count("name=dwt_rope") == 2
    # the gate multiplies on the slabs the attention kernels wrote
    assert head_gate.gate_route(heads * d, d) == "kernel"
    assert len(re.findall(r"name=dwt_gate\b", text)) == 1
    inv = 1.0 / 10000.0 ** (jnp.arange(0, rotary, 2) / rotary)

    def plain(params, x):
        return (ref.attention(
            x, params, n_kv_head=2, head_dim=d, inv=inv, factor=1.0,
            window=window or None, gate=True) * w).sum()

    got, (got_p, got_x) = jax.value_and_grad(run, argnums=(0, 1))(params, x)
    with jax.default_matmul_precision("highest"):
        want, (want_p, want_x) = jax.value_and_grad(
            plain, argnums=(0, 1))(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-4, abs=1e-3)
    np.testing.assert_allclose(got_x, want_x, atol=1e-3
                               * float(jnp.abs(want_x).max()))
    for (path, g), want_g in zip(
            jax.tree_util.tree_flatten_with_path(got_p)[0],
            jax.tree.leaves(want_p)):
        np.testing.assert_allclose(
            g, want_g, atol=1e-3 * float(jnp.abs(want_g).max()),
            err_msg=str(path))


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_shares_parts_add_up_to_the_uncut_layer(request, route):
    """Eight chips with two of the sixteen SwiGLU experts each: the
    routed parts all eight give, and the shared expert — which every
    chip computes alike — counted ONCE, are the uncut reference layer's,
    on the route every CPU run takes and on the `dwt_gmm` kernels a
    share runs on one TPU device (interpret mode)."""
    whole = MoEConfig(num_experts=16, top_k=3, impl="grouped",
                      aux_loss="none", dtype=jnp.float32, shared_width=24,
                      routed_scaling=2.5, norm_topk_prob=True)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    params = MoEMLP(hidden=32, ffn=24, moe=whole).init(
        jax.random.PRNGKey(0), u)["params"]
    params = {k: v * 10.0 if k.startswith("experts_w") else v
              for k, v in params.items()}
    sizes = dict(top_k=3, routed_scaling=2.5)
    flat = u.reshape(64, 32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(flat, params, first_expert=0,
                                   shared=True, **sizes)
        shared = want - ref.expert_layer(flat, params, first_expert=0,
                                         shared=False, **sizes)[0]
    assert float(jnp.abs(shared).max()) > 1e-2
    if route == "kernel":
        request.getfixturevalue("held_rows_interpreted")
    routed, rows = 0.0, 0
    for first in range(0, 16, 2):
        moe = dataclasses.replace(whole, experts_held=2, first_expert=first)
        part = {k: v[first:first + 2] if k.startswith("experts_w") else v
                for k, v in params.items()}
        assert gm.gmm_route((192, 32), (2, 32, 24), 16) == route
        y, upd = MoEMLP(hidden=32, ffn=24, moe=moe).apply(
            {"params": part}, u, mutable=["intermediates"])
        with jax.default_matmul_precision("highest"):
            one, _ = ref.expert_layer(flat, part, first_expert=first,
                                      shared=True, **sizes)
        np.testing.assert_allclose(y.reshape(64, 32), one, atol=2e-5
                                   * float(jnp.abs(want).max()))
        routed = routed + (y.reshape(64, 32) - shared)
        rows += int(upd["intermediates"]["moe_rows_held"][0])
    assert rows == 64 * 3  # every assignment is held by one chip
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               rtol=0, atol=3e-5
                               * float(jnp.abs(want).max()))


def test_the_step_counts_the_windows_pairs_and_the_gates():
    """Three sliding layers of five sow the pairs their window keeps and
    the pairs their kernels' tiles hold; every layer sows its mean gate;
    a full layer sows no pairs."""
    cfg = _nano()
    model, params, batch = Laguna(cfg), _params(cfg), _batch()
    _, stats = make_lm_loss(model.apply).with_stats(params, batch)
    kept = 24 * SEQ - 24 * 23 // 2
    assert float(stats["attn_pairs_kept"]) == 3 * 2 * 8 * kept
    done, square = fa.causal_tile_count(SEQ, SEQ, window=24)
    assert float(stats["attn_pairs_computed"]) \
        == 3 * 2 * 8 * done * SEQ * SEQ // square
    assert float(stats["attn_tiles_window"]) == 3 * 2 * 8 * done
    assert 0.3 < float(stats["attn_gate_mean"]) < 0.7
    assert attn_mod.window_pairs(cfg.attention_config(0), 2, 6, SEQ) is None
    # at the benchmark cell's shape: tiles of 512 on a band of 512 hold
    # twice what the window keeps
    kept, held = attn_mod.window_pairs(
        LagunaConfig().attention_config(1), 1, 64, 16384)
    assert kept == 64 * (512 * 16384 - 512 * 511 // 2)
    assert held == 64 * fa.causal_tile_count(16384, 16384, window=512)[0] \
        * 512 * 512
    assert fa.causal_tile_count(16384, 16384, window=512)[0] == 16 * 4 - 1
    assert kept / held == pytest.approx(0.50, abs=0.01)


def test_a_model_without_gates_or_windows_counts_neither():
    cfg = _nano(attn_gate=False, layer_types=(FULL,) * 5)
    _, stats = make_lm_loss(Laguna(cfg).apply).with_stats(
        Laguna(cfg).init_params(jax.random.PRNGKey(0), seq=SEQ), _batch())
    assert not [k for k in stats if k.startswith("attn_")]


def test_the_sharding_rules_bind_every_leaf():
    from jax.sharding import PartitionSpec as P

    from dlrover_wuqiong_tpu.parallel.sharding import (
        MOE_RULES,
        TRANSFORMER_RULES,
        path_of,
        spec_for_path,
    )

    rules = list(MOE_RULES) + list(TRANSFORMER_RULES)
    shapes = jax.eval_shape(Laguna(_nano()).init_params,
                            jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    for path in paths:
        assert any(re.match(rule, path, re.IGNORECASE)
                   for rule, _ in rules), path
    attn = "layers_1/attention/"
    assert spec_for_path(attn + "g_proj/kernel", rules) == P("fsdp", "tp")
    assert spec_for_path(attn + "q_proj/kernel", rules) == P("fsdp", "tp")
    assert spec_for_path(attn + "o_proj/kernel", rules) == P("tp", "fsdp")
    assert spec_for_path("layers_0/feed_forward/gate_proj/kernel",
                         rules) == P("fsdp", "tp")
    assert spec_for_path("layers_1/feed_forward/shared_down_proj/kernel",
                         rules) == P("tp", "fsdp")


def test_the_published_models_parameter_count():
    """40 layers whole: 33.44B, the published 33.4B — which a gate an
    ELEMENT (a 2048 x heads*128 product a layer) would miss by 0.63B."""
    cfg = LagunaConfig()
    assert cfg.num_params() == 33_442_596_864
    per_head = sum(2048 * h for h in cfg.num_heads_per_layer)
    assert per_head == 2400 * 2048
    assert cfg.num_params() - per_head + 128 * per_head == 34_066_827_264


def test_the_cells_parameter_count_is_the_files():
    """`init_params` at the benchmark configuration's sizes (shapes
    only) holds the count the file writes out."""
    from benchmark.models import laguna as model_class

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_xs_2_33b_a3b.json")) as f:
        config = json.load(f)
    model = model_class.build(config)
    shapes = jax.eval_shape(functools.partial(model.init_params, seq=8),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == model.config.num_params() == config["share"]["parameters"]
    layer = shapes["layers_1"]
    assert layer["feed_forward"]["experts_w_gate"].shape == (32, 2048, 512)
    assert layer["feed_forward"]["router"]["kernel"].shape == (2048, 256)
    assert layer["feed_forward"]["shared_up_proj"]["kernel"].shape \
        == (2048, 512)
    assert layer["attention"]["q_proj"]["kernel"].shape == (2048, 64 * 128)
    assert layer["attention"]["g_proj"]["kernel"].shape == (2048, 64)
    assert shapes["layers_0"]["attention"]["q_proj"]["kernel"].shape \
        == (2048, 48 * 128)
    assert shapes["layers_4"]["attention"]["g_proj"]["kernel"].shape \
        == (2048, 48)
    assert shapes["layers_0"]["feed_forward"]["up_proj"]["kernel"].shape \
        == (2048, 8192)
    assert shapes["lm_head"]["kernel"].shape == (2048, 12544)
