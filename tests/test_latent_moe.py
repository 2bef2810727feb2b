"""The latent-attention MoE stack (`models/latent_moe.py`: latent
attention whose q and k are wider than its v, a leading dense SwiGLU
layer, sigmoid-routed SwiGLU experts beside a SwiGLU shared expert, of
which a chip may hold a share) against its plain reference
(`benchmark/reference_kimi_vl.py`) in float32 at a small size: the
attention module's output and gradients, the stack's loss and per-leaf
gradients, the shares' parts of one expert layer against the uncut
reference layer (the shared expert counted once), what the module
refuses and counts, and the benchmark configuration's parameter count.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_kimi_vl as ref
from dlrover_wuqiong_tpu.models import attention as attn_mod
from dlrover_wuqiong_tpu.models.latent_attention import (
    LatentAttention,
    LatentAttentionConfig,
)
from dlrover_wuqiong_tpu.models.latent_moe import LatentMoE, LatentMoEConfig
from dlrover_wuqiong_tpu.models.llama import LlamaConfig, rope_freqs
from dlrover_wuqiong_tpu.models.moe import MoEConfig, MoEMLP
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.parallel.sharding import (
    MOE_RULES,
    TRANSFORMER_RULES,
    path_of,
)
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 48


def _nano(**over):
    return LatentMoEConfig.nano(**{
        **dict(dtype=jnp.float32, remat=False), **over})


def _sizes(cfg: LatentMoEConfig, **over):
    return {**dict(
        n_layer=cfg.num_layers, first_dense=cfg.first_dense_layers,
        n_head=cfg.num_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, top_k=cfg.top_k,
        routed_scaling=cfg.routed_scaling, first_expert=cfg.first_expert,
        eps=cfg.rms_eps, theta=cfg.rope_theta), **over}


def _batch(seed=0, batch=2, vocab=256):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                             vocab)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _wider(params):
    """Expert matrices drawn at 0.02 would leave the experts' part of
    the stream, and its gradients, too small to test: unit-ish variance."""
    def wider(path, leaf):
        name = path[-1].key
        return leaf * 10.0 if name.startswith("experts_w") else leaf
    return jax.tree_util.tree_map_with_path(wider, params)


def _params(cfg, seed=0):
    return _wider(LatentMoE(cfg).init_params(jax.random.PRNGKey(seed),
                                             seq=SEQ))


def _attention(flash=True, **over):
    cfg = LatentAttentionConfig(**{**dict(
        hidden_size=64, num_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
        dtype=jnp.float32, use_flash_attention=flash), **over})
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 64))
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, 64, 800000.0)
    layer = LatentAttention(cfg)
    return cfg, layer, x, cos, sin


_REF_ATTN = dict(n_head=4, nope=16, rope=8, theta=800000.0, eps=1e-5)


@pytest.mark.parametrize("flash", [True, False])
def test_latent_attention_and_its_gradients_match_the_reference(flash):
    """`LatentAttention` on its two routes off the TPU (`attend` ->
    `mha`'s jnp path with two widths, and the plain-softmax fallback):
    the output, and the gradient of every leaf and of the input."""
    cfg, layer, x, cos, sin = _attention(flash)
    params = layer.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    assert set(params) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                           "o_proj"}
    assert params["q_proj"]["kernel"].shape == (64, 4 * 24)
    assert params["kv_a_proj"]["kernel"].shape == (64, 24 + 8)
    assert params["kv_b_proj"]["kernel"].shape == (24, 4 * 32)
    assert params["o_proj"]["kernel"].shape == (4 * 16, 64)
    count = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert count == cfg.attention_params()

    def run(p, x):
        return jnp.sum(jnp.sin(3.0 * layer.apply({"params": p}, x, cos,
                                                  sin)))

    def want_run(p, x):
        return jnp.sum(jnp.sin(3.0 * ref.attention(x, p, **_REF_ATTN)))

    got, got_g = jax.value_and_grad(run, argnums=(0, 1))(params, x)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(want_run, argnums=(0, 1))(params,
                                                                    x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree.leaves(want_g)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=str(path))


@pytest.mark.parametrize("wrong", [
    dict(scale=16 ** -0.5),        # 1/sqrt(nope) for 1/sqrt(nope + rope)
    dict(latent_norm=False),       # the latent's norm left out
    dict(rotate_key=False),        # the shared key part not rotated
])
def test_latent_attention_with_one_term_wrong_is_told_apart(wrong):
    """The reference's three wrong-equation controls each move the
    module's output: the comparison above tells them from the model."""
    _, layer, x, cos, sin = _attention()
    params = layer.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    params["kv_a_norm"]["scale"] = params["kv_a_norm"]["scale"] * 1.5
    got = layer.apply({"params": params}, x, cos, sin)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, params, **_REF_ATTN)
        other = ref.attention(x, params, **_REF_ATTN, **wrong)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want - other).max()) > 1e-3


def test_a_q_latent_is_built_not_refused():
    """`q_lora_rank` was refused until PR 51; it is the down-projection,
    its norm and the up-projection now (tests/test_xing4_0.py holds them
    to the reference), and `q_proj` is gone from such a layer."""
    _, layer, x, cos, sin = _attention(q_lora_rank=32)
    params = layer.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    assert "q_proj" not in params
    assert params["q_a_proj"]["kernel"].shape == (64, 32)
    assert params["q_a_norm"]["scale"].shape == (32,)
    assert params["q_b_proj"]["kernel"].shape == (32, 4 * 24)


def test_latent_attention_on_a_mesh_is_refused():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("dp", "sp"))
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 4, 24))
    cfg = LlamaConfig(hidden_size=64, num_heads=4, num_kv_heads=4,
                      dtype=jnp.float32, mesh=mesh, attn_impl="ulysses")
    with pytest.raises(ValueError, match="one device"):
        attn_mod.attend(q, q, q[..., :16], cfg)


CASES = {
    "whole": dict(),
    "share": dict(experts_held=2, first_expert=4),
    "two_dense": dict(first_dense_layers=2),
    "remat": dict(remat=True),
    "no_flash": dict(use_flash_attention=False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_match_the_reference(case):
    cfg = _nano(**CASES[case])
    assert cfg.top_k < cfg.num_experts
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim != cfg.v_head_dim
    model, params, batch = LatentMoE(cfg), _params(cfg), _batch()
    loss_fn = make_lm_loss(model.apply)
    got, got_g = jax.value_and_grad(loss_fn)(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(functools.partial(
            ref.loss, **_sizes(cfg)))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    assert len(flat) == len(jax.tree.leaves(want_g))
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        if path[-1].key == "selection_bias":  # enters through a top-k alone
            assert float(jnp.abs(g).max()) == float(jnp.abs(w).max()) == 0
            continue
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=str(path))


@pytest.mark.parametrize("wrong", [
    dict(scale=16 ** -0.5), dict(latent_norm=False),
    dict(rotate_key=False), dict(top_k=2), dict(routed_scaling=1.0),
    dict(theta=10000.0),
])
def test_the_reference_with_one_term_wrong_is_told_apart(wrong):
    """The comparison above is tight enough to tell the model from its
    neighbours: the same reference with one equation changed."""
    cfg = _nano()
    params, batch = _params(cfg), _batch()
    got = make_lm_loss(LatentMoE(cfg).apply)(params, batch)
    with jax.default_matmul_precision("highest"):
        other = ref.loss(params, batch, **_sizes(cfg, **wrong))
    assert abs(float(got) - float(other)) > 1e-4 * abs(float(got))


_LAYER = dict(num_experts=8, top_k=3, impl="grouped", expert_act="swiglu",
              aux_loss="none", score_func="sigmoid", selection_bias=True,
              routed_scaling=2.446, shared_width=48, dtype=jnp.float32)


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_shares_parts_add_up_to_the_uncut_layer(request, route):
    """Four chips with two of the eight SwiGLU experts each: their
    routed parts, and the SwiGLU shared expert that every chip computes
    alike counted ONCE, are the uncut reference layer's — on the route
    every CPU run takes and on the `dwt_gmm` kernels a share runs on one
    TPU device (interpret mode)."""
    whole = MoEConfig(**_LAYER)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    params = _wider(MoEMLP(hidden=32, ffn=24, moe=whole).init(
        jax.random.PRNGKey(0), u)["params"])
    assert set(params) == {
        "router", "selection_bias", "experts_w_in", "experts_w_gate",
        "experts_w_down", "shared_gate_proj", "shared_up_proj",
        "shared_down_proj"}
    kw = dict(top_k=3, routed_scaling=2.446)
    flat = u.reshape(64, 32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(flat, params, first_expert=0, **kw)
        shared = ref._swiglu(flat, *(params[f"shared_{n}_proj"]["kernel"]
                                     for n in ("gate", "up", "down")))
    assert float(jnp.abs(shared).max()) > 1e-2
    if route == "kernel":
        request.getfixturevalue("held_rows_interpreted")
    routed, rows = 0.0, 0
    for first in (0, 2, 4, 6):
        moe = dataclasses.replace(whole, experts_held=2, first_expert=first)
        part = {k: v[first:first + 2] if k.startswith("experts_w") else v
                for k, v in params.items()}
        assert gm.gmm_route((192, 32), (2, 32, 24), 8) == route
        y, upd = MoEMLP(hidden=32, ffn=24, moe=moe).apply(
            {"params": part}, u, mutable=["intermediates"])
        with jax.default_matmul_precision("highest"):
            one = ref.expert_layer(flat, part, first_expert=first, **kw)
        np.testing.assert_allclose(y.reshape(64, 32), one, atol=2e-5
                                   * float(jnp.abs(want).max()))
        routed = routed + (y.reshape(64, 32) - shared)
        rows += int(upd["intermediates"]["moe_rows_held"][0])
    assert rows == 64 * 3  # every assignment is held by one chip
    np.testing.assert_allclose(
        np.asarray(routed + shared), np.asarray(want), rtol=0,
        atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("act,leaves", [
    ("relu2", {"shared_up_proj", "shared_down_proj"}),
    ("swiglu", {"shared_gate_proj", "shared_up_proj", "shared_down_proj"}),
    ("reglu", {"shared_gate_proj", "shared_up_proj", "shared_down_proj"}),
])
def test_the_shared_expert_takes_the_routed_experts_form(act, leaves):
    """relu^2's two matrices where it was, a gated form's three."""
    moe = MoEConfig(**{**_LAYER, "expert_act": act})
    layer = MoEMLP(hidden=32, ffn=24, moe=moe)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    assert {k for k in params if k.startswith("shared")} == leaves
    counted = dataclasses.replace(
        LlamaConfig(hidden_size=32, intermediate_size=24), moe=moe)
    assert counted.ffn_params() == sum(
        int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    tokens = u.reshape(32, 32)
    up = tokens @ params["shared_up_proj"]["kernel"]
    if act == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        gate = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}[act]
        h = gate(tokens @ params["shared_gate_proj"]["kernel"]) * up
    without = {k: (jax.tree.map(jnp.zeros_like, v)
                   if k == "shared_down_proj" else v)
               for k, v in params.items()}
    got = layer.apply({"params": params}, u) \
        - layer.apply({"params": without}, u)
    np.testing.assert_allclose(
        got.reshape(32, 32), h @ params["shared_down_proj"]["kernel"],
        atol=2e-5)


def test_the_step_counts_the_latent_layers_lanes():
    """Every latent layer sows the lanes a score entry's two products
    run as the kernels block them and the lanes its widths ask: equal
    (nothing padded) at widths that are multiples of 8, as the published
    192 and 128 are; a width off the sublane is padded to the slab."""
    cfg = _nano()
    _, stats = make_lm_loss(LatentMoE(cfg).apply).with_stats(
        _params(cfg), _batch())
    assert float(stats["attn_lanes_model"]) == 3 * (16 + 8 + 16)
    assert float(stats["attn_lanes_run"]) == 3 * (16 + 8 + 16)
    assert not [k for k in stats if k.startswith("attn_tiles")]
    assert fa.kernel_lanes(192, 128) == 320
    assert fa.kernel_lanes(20, 12) == 256
    odd = _nano(qk_rope_head_dim=4, num_layers=1)
    _, stats = make_lm_loss(LatentMoE(odd).apply).with_stats(
        _params(odd), _batch())
    assert float(stats["attn_lanes_model"]) == 16 + 4 + 16
    assert float(stats["attn_lanes_run"]) == 128 + 16


def test_no_leaf_of_the_stack_falls_to_an_unnamed_default():
    """Every leaf of the stack is matched by one of
    `parallel/sharding.py`'s rules; the latent's three new leaves by
    rules of their own (column-parallel down- and up-projection, a
    replicated norm)."""
    import re

    from jax.sharding import PartitionSpec as P

    from dlrover_wuqiong_tpu.parallel.sharding import spec_for_path

    rules = list(MOE_RULES) + list(TRANSFORMER_RULES)
    shapes = jax.eval_shape(LatentMoE(_nano()).init_params,
                            jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert len(paths) == 43
    for path in paths:
        assert any(re.match(rule, path, re.IGNORECASE)
                   for rule, _ in rules), path
    attn = "layers_1/attention/"
    assert spec_for_path(attn + "kv_a_proj/kernel", rules) == P("fsdp", None)
    assert spec_for_path(attn + "kv_b_proj/kernel", rules) == P("fsdp", "tp")
    assert spec_for_path(attn + "kv_a_norm/scale", rules) == P()
    assert spec_for_path(attn + "q_proj/kernel", rules) == P("fsdp", "tp")
    assert spec_for_path(attn + "o_proj/kernel", rules) == P("tp", "fsdp")
    assert spec_for_path("layers_1/feed_forward/shared_gate_proj/kernel",
                         rules) == P("fsdp", "tp")
    assert spec_for_path("layers_1/feed_forward/selection_bias",
                         rules) == P()


def test_the_selection_bias_is_left_to_its_rule():
    assert LatentMoE.untrained_params == (
        r"(layers|mtp_\d+/block)_\d+/feed_forward/selection_bias",)
    cfg = _nano(bias_update_rate=0.01)
    _, stats = make_lm_loss(LatentMoE(cfg).apply).with_stats(
        _params(cfg), _batch())
    steps = stats["param_steps"]
    assert sorted(steps) == ["layers_1", "layers_2"]  # layer 0 is dense
    assert steps["layers_1"]["feed_forward"]["selection_bias"].shape == (8,)


def test_the_cells_parameter_count_is_the_files():
    """`init_params` at the benchmark configuration's sizes (shapes
    only) holds the count the file writes out."""
    from benchmark.models import kimi_vl as model_class

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi_vl_a3b.json")) as f:
        config = json.load(f)
    model = model_class.build(config)
    shapes = jax.eval_shape(functools.partial(model.init_params, seq=8),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == model.config.num_params() == config["share"]["parameters"]
    assert count == {5: 568_484_608, 6: 668_890_432}[
        config["num_hidden_layers"]]
    assert shapes["layers_0"]["feed_forward"]["gate_proj"]["kernel"].shape \
        == (2048, 11264)
    layer = shapes["layers_1"]
    assert layer["feed_forward"]["experts_w_gate"].shape == (8, 2048, 1408)
    assert layer["feed_forward"]["router"]["kernel"].shape == (2048, 64)
    assert layer["feed_forward"]["shared_up_proj"]["kernel"].shape \
        == (2048, 2816)
    assert layer["attention"]["q_proj"]["kernel"].shape == (2048, 3072)
    assert layer["attention"]["kv_a_proj"]["kernel"].shape == (2048, 576)
    assert layer["attention"]["kv_b_proj"]["kernel"].shape == (512, 4096)
    assert shapes["lm_head"]["kernel"].shape == (2048, 20480)
    # one whole layer with all 64 experts: a chip cannot hold two
    whole = dataclasses.replace(model.config, experts_held=0, num_layers=2)
    one = dataclasses.replace(whole, num_layers=1)
    assert whole.num_params() - one.num_params() == 584_847_936
