"""SmallThinker (`models/smallthinker.py`: windowed RoPE layers beside
global no-position ones, the router in front of the attention, ReGLU
experts of which a chip may hold a share) against its plain reference
(`benchmark/reference_smallthinker.py`) in float32 at a small size:
loss and per-leaf gradients over one period with a window that bites;
the shares' parts of one expert layer against the uncut reference layer,
on the plain route and on the share's `dwt_gmm` kernels; what the two
new fields of `MoEMLP` and the one of `LlamaAttention` do; and the
benchmark configuration's parameter count.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_smallthinker as ref
from dlrover_wuqiong_tpu.models import attention as attn_mod
from dlrover_wuqiong_tpu.models.llama import LlamaAttention, LlamaConfig
from dlrover_wuqiong_tpu.models.moe import MoEConfig, MoEMLP
from dlrover_wuqiong_tpu.models.smallthinker import (
    SmallThinker,
    SmallThinkerConfig,
)
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 48  # two windows of 24: the window bites in three layers of four


def _nano(**over):
    return SmallThinkerConfig.nano(**{
        **dict(dtype=jnp.float32, remat=False), **over})


def _sizes(cfg: SmallThinkerConfig, **over):
    return {**dict(
        rope_layout=cfg.rope_layout,
        sliding_window_layout=cfg.sliding_window_layout,
        window=cfg.sliding_window_size, n_head=cfg.num_heads,
        n_kv_head=cfg.num_kv_heads, top_k=cfg.top_k,
        first_expert=cfg.first_expert, eps=cfg.rms_eps,
        theta=cfg.rope_theta, aux_weight=cfg.aux_loss_weight), **over}


def _batch(seed=0, batch=2, vocab=256):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                             vocab)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _params(cfg, seed=0):
    params = SmallThinker(cfg).init_params(jax.random.PRNGKey(seed), seq=SEQ)
    # expert matrices drawn at 0.02 would leave the experts' part of the
    # stream, and its gradients, too small to test: unit-ish variance
    def wider(path, leaf):
        name = path[-1].key
        return leaf * 10.0 if name.startswith("experts_w") else leaf
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
    assert cfg.top_k < cfg.num_experts and cfg.sliding_window_size < SEQ
    assert cfg.rope_layout == cfg.sliding_window_layout == (0, 1, 1, 1)
    model, params, batch = SmallThinker(cfg), _params(cfg), _batch()
    loss_fn = make_lm_loss(model.apply)
    got, got_g = jax.value_and_grad(loss_fn)(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(functools.partial(
            ref.loss, **_sizes(cfg)))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    assert len(flat) == len(jax.tree.leaves(want_g))
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=str(path))


@pytest.mark.parametrize("wrong", [
    dict(sliding_window_layout=(0, 0, 0, 0)),      # the window dropped
    dict(rope_layout=(1, 1, 1, 1)),                # every layer rotated
    dict(window=16),                               # another window
    dict(top_k=2),
])
def test_the_reference_with_one_term_wrong_is_told_apart(wrong):
    """The comparison above is tight enough to tell the model from its
    neighbours: the same reference with one equation changed."""
    cfg = _nano()
    params, batch = _params(cfg), _batch()
    got = make_lm_loss(SmallThinker(cfg).apply)(params, batch)
    with jax.default_matmul_precision("highest"):
        other = ref.loss(params, batch, **_sizes(cfg, **wrong))
    assert abs(float(got) - float(other)) > 1e-4 * abs(float(got))


def test_the_router_reads_the_blocks_input_not_the_experts():
    """`MoEMLP(u, router_input=h)`: the choice and the gates follow h."""
    moe = MoEConfig(num_experts=8, top_k=3, impl="grouped",
                    expert_act="reglu", aux_loss="none",
                    dtype=jnp.float32)
    layer = MoEMLP(hidden=32, ffn=24, moe=moe)
    u, h = (jax.random.normal(jax.random.PRNGKey(i), (2, 12, 32))
            for i in (1, 2))
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    params = {k: v * 10.0 if k.startswith("experts_w") else v
              for k, v in params.items()}
    got = layer.apply({"params": params}, u, router_input=h)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(u.reshape(24, 32), h.reshape(24, 32),
                                   params, top_k=3, first_expert=0)
        swapped, _ = ref.expert_layer(u.reshape(24, 32), u.reshape(24, 32),
                                      params, top_k=3, first_expert=0)
    np.testing.assert_allclose(got.reshape(24, 32), want, atol=2e-5)
    assert float(jnp.abs(want - swapped).max()) > 1e-2
    # handed nothing, the router reads the experts' input as it always did
    np.testing.assert_allclose(
        layer.apply({"params": params}, u).reshape(24, 32), swapped,
        atol=2e-5)


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_shares_parts_add_up_to_the_uncut_layer(request, route):
    """Four chips with two of the eight ReGLU experts each: their parts
    of the result are the uncut reference layer's (no shared expert to
    count once), on the route every CPU run takes and on the `dwt_gmm`
    kernels a share runs on one TPU device (interpret mode)."""
    whole = MoEConfig(num_experts=8, top_k=3, impl="grouped",
                      expert_act="reglu", aux_loss="none",
                      dtype=jnp.float32)
    u, h = (jax.random.normal(jax.random.PRNGKey(i), (2, 32, 32))
            for i in (3, 4))
    params = MoEMLP(hidden=32, ffn=24, moe=whole).init(
        jax.random.PRNGKey(0), u)["params"]
    params = {k: v * 10.0 if k.startswith("experts_w") else v
              for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(u.reshape(64, 32), h.reshape(64, 32),
                                   params, top_k=3, first_expert=0)
    if route == "kernel":
        request.getfixturevalue("held_rows_interpreted")
    total, rows = 0.0, 0
    for first in (0, 2, 4, 6):
        moe = dataclasses.replace(whole, experts_held=2, first_expert=first)
        part = {k: v[first:first + 2] if k.startswith("experts_w") else v
                for k, v in params.items()}
        assert gm.gmm_route((192, 32), (2, 32, 24), 8) == route
        y, upd = MoEMLP(hidden=32, ffn=24, moe=moe).apply(
            {"params": part}, u, router_input=h, mutable=["intermediates"])
        with jax.default_matmul_precision("highest"):
            one, _ = ref.expert_layer(u.reshape(64, 32), h.reshape(64, 32),
                                      part, top_k=3, first_expert=first)
        np.testing.assert_allclose(y.reshape(64, 32), one, atol=2e-5
                                   * float(jnp.abs(want).max()))
        total = total + y.reshape(64, 32)
        rows += int(upd["intermediates"]["moe_rows_held"][0])
    assert rows == 64 * 3  # every assignment is held by one chip
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_a_reglu_shares_gradients_through_the_kernels_are_the_plain_routes(
        request):
    """Three matrices a group on the share's kernels: every leaf's
    gradient against the plain route's."""
    moe = MoEConfig(num_experts=8, top_k=3, impl="grouped",
                    expert_act="reglu", aux_loss="none", dtype=jnp.float32,
                    experts_held=2, first_expert=4)
    layer = MoEMLP(hidden=32, ffn=24, moe=moe)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    assert set(params) == {"router", "experts_w_in", "experts_w_gate",
                           "experts_w_down"}

    def run(p):
        return jnp.sum(jnp.sin(30.0 * layer.apply({"params": p}, u)))

    want, want_g = jax.value_and_grad(run)(params)
    request.getfixturevalue("held_rows_interpreted")
    text = str(jax.make_jaxpr(jax.grad(run))(params))
    assert "pallas_call" in text and "ragged_dot" not in text
    got, got_g = jax.value_and_grad(run)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5,
            atol=1e-6 * max(1.0, float(jnp.abs(w).max())), err_msg=str(path))


def test_the_capacity_path_refuses_reglu():
    layer = MoEMLP(hidden=32, ffn=24, moe=MoEConfig(
        num_experts=8, top_k=2, expert_act="reglu"))
    with pytest.raises(ValueError, match="expert_act"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))


@pytest.mark.parametrize("flash", [True, False])
def test_a_windowed_attention_layer_sees_its_window_only(flash):
    """`LlamaConfig.attn_window` through `LlamaAttention`'s routes off
    the TPU (`mha`'s jnp path, and the plain-softmax fallback): the
    reference's attention with the mask written out."""
    cfg = LlamaConfig(hidden_size=64, num_heads=4, num_kv_heads=2,
                      attn_head_dim=16, dtype=jnp.float32, attn_window=10,
                      rope=False, use_flash_attention=flash)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    layer = LlamaAttention(cfg)
    params = layer.init(jax.random.PRNGKey(1), x, None, None)["params"]
    got = layer.apply({"params": params}, x, None, None)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, params, n_head=4, n_kv_head=2, rope=False,
                             theta=0.0, window=10)
        full = ref.attention(x, params, n_head=4, n_kv_head=2, rope=False,
                             theta=0.0, window=None)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want - full).max()) > 1e-2


def test_the_step_counts_the_windowed_layers_tiles():
    """Three windowed layers of four sow what their kernels compute and
    what causal calls of their shape would (`causal_tile_count`); the
    global layer sows nothing, a model without a window nothing at all."""
    cfg = _nano()
    model, batch = SmallThinker(cfg), _batch()
    _, stats = make_lm_loss(model.apply).with_stats(_params(cfg), batch)
    one = 2 * cfg.num_heads * np.asarray([
        fa.causal_tile_count(SEQ, SEQ, window=24)[0],
        fa.causal_tile_count(SEQ, SEQ)[0]])
    assert float(stats["attn_tiles_window"]) == 3 * one[0]
    assert float(stats["attn_tiles_causal"]) == 3 * one[1]
    no_window = _nano(sliding_window_layout=(0, 0, 0, 0))
    _, stats = make_lm_loss(SmallThinker(no_window).apply).with_stats(
        _params(no_window), batch)
    assert not [k for k in stats if k.startswith("attn_tiles")]
    assert attn_mod.window_tiles(cfg.attention_config(0), 2, 4, SEQ) is None
    # at the benchmark cell's shape: skipping, not masking
    win, causal = attn_mod.window_tiles(
        SmallThinkerConfig().attention_config(1), 1, 28, 16384)
    assert (win, causal) == (28 * 252, 28 * 528)


def test_ring_attention_refuses_a_window_and_ulysses_passes_it_on():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("dp", "sp"))
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 4, 16))
    cfg = LlamaConfig(hidden_size=64, num_heads=4, num_kv_heads=4,
                      dtype=jnp.float32, attn_window=10, mesh=mesh,
                      attn_impl="ring")
    with pytest.raises(ValueError, match="window"):
        attn_mod.attend(q, q, q, cfg)
    got = attn_mod.attend(q, q, q, dataclasses.replace(
        cfg, attn_impl="ulysses"))
    want = attn_mod.attend(q, q, q, dataclasses.replace(
        cfg, attn_impl="flash", mesh=None))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_cells_parameter_count_is_the_files():
    """`init_params` at the benchmark configuration's sizes (shapes
    only) holds the count the file writes out."""
    from benchmark.models import smallthinker as model_class

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        config = json.load(f)
    model = model_class.build(config)
    shapes = jax.eval_shape(functools.partial(model.init_params, seq=8),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == model.config.num_params() == 559_290_880
    assert count == config["share"]["parameters"]
    layer = shapes["layers_1"]
    assert layer["feed_forward"]["experts_w_gate"].shape == (16, 2560, 768)
    assert layer["feed_forward"]["router"]["kernel"].shape == (2560, 64)
    assert layer["attention"]["k_proj"]["kernel"].shape == (2560, 512)
    assert shapes["lm_head"]["kernel"].shape == (2560, 18992)
