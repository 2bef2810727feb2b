"""The Xing4.0-shaped stack — `models/latent_moe.py` with four residual
lanes mixed by manifold-constrained hyper-connections, a q latent,
YaRN-scaled rotation, a share of the experts and a multi-token-
prediction module — against its plain reference
(`benchmark/reference_xing4_0.py`) in float32 at a small size: loss and
every gradient leaf, the reference's wrong-equation controls, YaRN's
tables against the closed form, the shares' parts of one block against
the uncut reference block, what one lane leaves untouched, the sharding
rules, the benchmark configuration's parameter count and what its
`build` refuses.
"""

import dataclasses
import functools
import json
import math
import os
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing4_0 as ref
from dlrover_wuqiong_tpu.models import hyper_connection as hc
from dlrover_wuqiong_tpu.models.latent_attention import (
    LatentAttention,
    LatentAttentionConfig,
)
from dlrover_wuqiong_tpu.models.latent_moe import (
    LatentMoE,
    LatentMoEBlock,
    LatentMoEConfig,
)
from dlrover_wuqiong_tpu.models.llama import RopeScaling, rope_freqs
from dlrover_wuqiong_tpu.parallel.sharding import (
    MOE_RULES,
    TRANSFORMER_RULES,
    path_of,
    spec_for_path,
)
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
# original positions of 16 put the ramp's ends inside nano's 4 pairs
YARN = dict(factor=64, original_max_position_embeddings=16, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
PUBLISHED_YARN = dict(YARN, original_max_position_embeddings=4096)


def _nano(**over):
    return LatentMoEConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, num_layers=2, residual_lanes=4,
        q_lora_rank=12,
        rope_scaling=RopeScaling(**YARN), top_k=2,
        experts_held=4, first_expert=2), **over})


def _sizes(cfg: LatentMoEConfig, **over):
    return {**dict(
        n_layer=cfg.num_layers, first_dense=cfg.first_dense_layers,
        n_head=cfg.num_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, top_k=cfg.top_k,
        routed_scaling=cfg.routed_scaling, first_expert=cfg.first_expert,
        eps=cfg.rms_eps, theta=cfg.rope_theta, yarn=YARN,
        lanes=cfg.residual_lanes, sinkhorn_iters=cfg.hc_sinkhorn_iters,
        hc_eps=cfg.hc_eps, res_clamp=cfg.hc_res_clamp, mtp=cfg.mtp_layers,
        mtp_weight=cfg.mtp_loss_weight), **over}


def _batch(seed=0, batch=2, vocab=256):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                             vocab)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _wider(params):
    """Expert matrices drawn at 0.02 would leave the experts' part of
    the stream too small to test (tests/test_latent_moe.py's), and gains
    of 0.01 on a Phi of zero no dynamic part of the coefficients at all:
    the experts and the gains near unit scale, Phi drawn."""
    def wider(path, leaf):
        name = path[-1].key
        if name.startswith("experts_w"):
            return leaf * 10.0
        if name == "phi":
            return 0.3 * jax.random.normal(jax.random.PRNGKey(
                zlib.crc32(jax.tree_util.keystr(path).encode())), leaf.shape)
        return leaf * 30.0 if name == "alpha" else leaf
    return jax.tree_util.tree_map_with_path(wider, params)


def _params(cfg, seed=0):
    return _wider(LatentMoE(cfg).init_params(jax.random.PRNGKey(seed),
                                             seq=SEQ))


# with an MTP module the trunk is the dense layer alone: the module's
# block is the expert layer, so every kind of block is in each case
_MTP = dict(mtp_layers=1, num_layers=1)
CASES = {
    "mtp_remat": dict(remat=True, **_MTP),
    "whole_no_flash": dict(experts_held=0, first_expert=0,
                           use_flash_attention=False, residual_lanes=2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_match_the_reference(case):
    cfg = _nano(**CASES[case])
    model, params, batch = LatentMoE(cfg), _params(cfg), _batch()
    loss_fn = make_lm_loss(model.apply)
    (got, stats), got_g = jax.jit(jax.value_and_grad(
        loss_fn.with_stats, has_aux=True))(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(functools.partial(
            ref.loss, **_sizes(cfg))))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert ("mtp_ce" in stats) == bool(cfg.mtp_layers)
    assert cfg.hc_sinkhorn_iters == 20
    assert 0 < float(stats["resmix_sinkhorn_err"]) < 0.1
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    assert len(flat) == len(jax.tree.leaves(want_g))
    # a doubly-stochastic H_res moves with A_res's row and column sums in
    # no way, so b_res's gradient is a difference of near-equal numbers:
    # held to the scale of the whole gradient, as every small leaf is
    scale = max(float(jnp.abs(w).max()) for w in jax.tree.leaves(want_g))
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        if path[-1].key == "selection_bias":  # enters through a top-k alone
            assert float(jnp.abs(g).max()) == float(jnp.abs(w).max()) == 0
            continue
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=1e-5 * float(jnp.abs(w).max()) + 1e-6 * scale,
            err_msg=str(path))
    moved = [path_of(p) for p, w in zip(
        (p for p, _ in flat), jax.tree.leaves(want_g))
        if float(jnp.abs(w).max()) > 1e-6 * scale]
    for part in ("attention_hc/phi", "feed_forward_hc/alpha", "q_a_proj",
                 "q_a_norm", "q_b_proj") + (
                     ("mtp_0/eh_proj", "mtp_0/hnorm", "mtp_0/enorm",
                      "mtp_0/block_0/attention_hc/phi")
                     if cfg.mtp_layers else ()):
        assert any(part in path for path in moved), part


@pytest.mark.parametrize("wrong", [
    dict(sinkhorn_iters=1),        # one round where the model has 20
    dict(post_factor=1.0),         # h_post without its factor 2
    dict(q_norm=False),            # the q latent's RMSNorm left out
    dict(scale_mscale=False),      # the softmax's scale without m^2
    dict(mtp_weight=0.1),
], ids=lambda w: next(iter(w)))
def test_the_reference_with_one_term_wrong_is_told_apart(wrong):
    """The comparison above is tight enough to tell the model from its
    neighbours: the same reference with one equation changed."""
    cfg, params, batch, got = _with_mtp()
    with jax.default_matmul_precision("highest"):
        other = jax.jit(functools.partial(
            ref.loss, **_sizes(cfg, **wrong)))(params, batch)
    assert abs(got - float(other)) > 1e-4 * abs(got)


@functools.lru_cache(maxsize=None)
def _with_mtp():
    """(config, parameters, batch, the program's loss — which the
    reference as it stands gives too: the test above) of the stack with
    its MTP module, a q latent norm off its unit scale."""
    cfg = _nano(**_MTP)
    params, batch = _params(cfg), _batch()
    params["layers_0"]["attention"]["q_a_norm"]["scale"] *= 1.5
    got = float(jax.jit(make_lm_loss(LatentMoE(cfg).apply))(params, batch))
    return cfg, params, batch, got


def test_one_lane_is_the_stack_as_it_stood():
    """`residual_lanes` 1 and the new fields at their defaults: no
    hyper-connection leaf, no `hc` scope in the lowered loss, no counter
    — the lowered text itself is pinned by tests/test_stack.py's digest,
    taken on the parent commit."""
    cfg = LatentMoEConfig.nano(dtype=jnp.float32)
    assert (cfg.residual_lanes, cfg.mtp_layers, cfg.q_lora_rank,
            cfg.rope_scaling) == (1, 0, None, None)
    model, batch = LatentMoE(cfg), _batch()
    params = model.init_params(jax.random.PRNGKey(0), seq=SEQ)
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    assert len(paths) == 43 and not any("_hc" in p or "q_a_" in p or
                                        "mtp" in p for p in paths)
    loss_fn = make_lm_loss(model.apply)
    text = jax.jit(loss_fn).lower(params, batch).as_text(debug_info=True)
    assert "/hc/" not in text and "attention/q_proj" in text
    _, stats = jax.eval_shape(loss_fn.with_stats, params, batch)
    assert "resmix_sinkhorn_err" not in stats and "mtp_ce" not in stats
    assert cfg.attention_config().attn_scale == 0.0


def test_yarn_at_factor_one_is_the_unscaled_table():
    cos, sin = rope_freqs(64, 128, 10000.0)
    one = RopeScaling(factor=1.0, mscale=1, mscale_all_dim=1)
    cos1, sin1 = rope_freqs(64, 128, 10000.0, one)
    np.testing.assert_allclose(cos1, cos, atol=1e-6)
    np.testing.assert_allclose(sin1, sin, atol=1e-6)
    assert one.softmax_mscale == one.table_mscale == 1.0


def test_yarn_at_the_published_factor_is_the_closed_form():
    """factor 64 over 4,096 original positions, beta 32 / 1, theta
    10,000, 64 rotated lanes: the ramp runs from pair 10 to pair 23; the
    fast pairs keep their frequency, the slow ones turn 64 times slower;
    the tables carry mscale / mscale_all_dim = 1 and the softmax m^2 with
    m = 0.1 ln 64 + 1."""
    scaling = RopeScaling(**PUBLISHED_YARN)
    assert scaling.ramp_ends(64, 10000.0) == (10, 23)
    assert scaling.softmax_mscale == pytest.approx(1.41589, abs=1e-5)
    assert scaling.table_mscale == 1.0
    pos = 4097
    cos, sin = rope_freqs(64, pos + 1, 10000.0, scaling)
    plain = [10000.0 ** (-2 * i / 64) for i in range(32)]
    want = []
    for i, inv in enumerate(plain):
        kept = 1.0 - min(max((i - 10) / 13, 0.0), 1.0)
        want.append(inv / 64 * (1 - kept) + inv * kept)
    assert want[:11] == plain[:11] and want[23:] == [
        inv / 64 for inv in plain[23:]]
    np.testing.assert_allclose(cos[pos], np.cos(pos * np.asarray(want)),
                               atol=2e-3)
    np.testing.assert_allclose(sin[pos], np.sin(pos * np.asarray(want)),
                               atol=2e-3)
    inv, table = ref.yarn_inv_freq(64, 10000.0, PUBLISHED_YARN)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert table == 1.0
    cfg = LatentMoEConfig(qk_nope_head_dim=128, qk_rope_head_dim=64,
                          rope_scaling=scaling)
    assert cfg.attention_config().attn_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)


def test_a_q_latent_has_its_three_leaves_and_its_count():
    cfg = LatentAttentionConfig(
        hidden_size=64, num_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24, q_lora_rank=12,
        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 64))
    cos, sin = rope_freqs(8, 64, 10000.0)
    params = LatentAttention(cfg).init(jax.random.PRNGKey(1), x, cos,
                                       sin)["params"]
    assert set(params) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                           "kv_a_norm", "kv_b_proj", "o_proj"}
    assert params["q_a_proj"]["kernel"].shape == (64, 12)
    assert params["q_b_proj"]["kernel"].shape == (12, 4 * 24)
    count = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert count == cfg.attention_params()


def _block_reference(x, p, dense, cfg):
    sizes = _sizes(cfg)
    how = {k: sizes[k] for k in (
        "n_head", "nope", "rope", "theta", "yarn", "eps", "top_k",
        "routed_scaling", "first_expert", "hc_eps", "res_clamp")}
    with jax.default_matmul_precision("highest"):
        out = jax.jit(functools.partial(
            ref.block, dense=dense, iters=cfg.hc_sinkhorn_iters, **how))(
                [x[:, i] for i in range(x.shape[1])], p)
    return jnp.stack(out, axis=1)


def test_the_shares_parts_add_up_to_the_uncut_block():
    """Two chips with four of the eight experts each: a block's output
    is C + h_post x (the share's routed part), C what every chip computes
    alike (the mixes, attention, the shared expert).  The shares'
    outputs less C — so that C is counted ONCE — are the uncut reference
    block's."""
    whole = _nano(experts_held=0, first_expert=0)
    block = LatentMoEBlock(whole, 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, SEQ, 64))
    cos, sin = rope_freqs(8, 64, whole.rope_theta, whole.rope_scaling)
    params = _wider(block.init(jax.random.PRNGKey(0), x, cos, sin)["params"])
    want = _block_reference(x, params, False, whole)

    def share(first, zeroed=False):
        cfg = dataclasses.replace(whole, experts_held=4, first_expert=first)
        ff = {k: (v[first:first + 4] * (0.0 if zeroed else 1.0))
              if k.startswith("experts_w") else v
              for k, v in params["feed_forward"].items()}
        part = {**params, "feed_forward": ff}
        out = jax.jit(LatentMoEBlock(cfg, 1).apply)({"params": part}, x,
                                                     cos, sin)
        np.testing.assert_allclose(
            out, _block_reference(x, part, False, cfg),
            atol=2e-5 * float(jnp.abs(want).max()))
        return out

    common = share(0, zeroed=True)
    assert float(jnp.abs(want - common).max()) > 1e-2  # the routed part
    total = share(0) + share(4) - common
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=5e-5 * float(jnp.abs(want).max()))


def test_several_lanes_on_a_mesh_are_refused():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("fsdp",))
    model = LatentMoE(_nano(mesh=mesh))
    with pytest.raises(ValueError, match="one device"):
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))


def test_no_leaf_of_the_stack_falls_to_an_unnamed_default():
    """Every leaf of the four-lane stack with a q latent and an MTP
    module is matched by one of `parallel/sharding.py`'s rules, the new
    ones by rules of their own: the q latent as the kv latent, Phi's
    hidden features over `fsdp`, the few gains and biases replicated,
    the joining product column-parallel; the selection bias of the
    module's block is left to its rule as the trunk's are."""
    from jax.sharding import PartitionSpec as P

    rules = list(MOE_RULES) + list(TRANSFORMER_RULES)
    cfg = _nano(mtp_layers=1)
    assert cfg.num_layers == 2
    shapes = jax.eval_shape(LatentMoE(cfg).init_params,
                            jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    for path in paths:
        assert any(re.match(rule, path, re.IGNORECASE)
                   for rule, _ in rules), path
    attn = "layers_1/attention/"
    assert spec_for_path(attn + "q_a_proj/kernel", rules) == P("fsdp", None)
    assert spec_for_path(attn + "q_b_proj/kernel", rules) == P("fsdp", "tp")
    assert spec_for_path(attn + "q_a_norm/scale", rules) == P()
    assert spec_for_path("layers_1/attention_hc/phi", rules) \
        == P(None, "fsdp", None)
    for leaf in ("alpha", "b_pre", "b_post", "b_res"):
        assert spec_for_path(f"layers_1/feed_forward_hc/{leaf}", rules) == P()
    assert spec_for_path("mtp_0/eh_proj/kernel", rules) == P("fsdp", "tp")
    assert spec_for_path("mtp_0/block_0/attention/q_b_proj/kernel",
                         rules) == P("fsdp", "tp")
    assert spec_for_path("mtp_0/hnorm/scale", rules) == P()
    untrained = [p for p in paths if any(
        re.search(rule, p) for rule in LatentMoE.untrained_params)]
    assert untrained == [
        "layers_1/feed_forward/selection_bias",
        "mtp_0/block_0/feed_forward/selection_bias"]
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == cfg.num_params()


def _file():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_0_29b_a4b.json")) as f:
        return json.load(f)


def test_the_cells_parameter_count_is_the_files():
    """`init_params` at the benchmark configuration's sizes (shapes
    only) holds the count the file writes out, at published widths; the
    MTP module the cut leaves out is 154.1M more, over the rung."""
    from benchmark.models import xing4_0 as model_class

    config = _file()
    model = model_class.build(config)
    shapes = jax.eval_shape(functools.partial(model.init_params, seq=8),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == model.config.num_params() \
        == config["share"]["parameters"] == 759_346_446
    assert shapes["layers_0"]["feed_forward"]["gate_proj"]["kernel"].shape \
        == (3584, 9216)
    layer = shapes["layers_1"]
    assert layer["feed_forward"]["experts_w_gate"].shape == (8, 3584, 1024)
    assert layer["feed_forward"]["router"]["kernel"].shape == (3584, 64)
    assert layer["feed_forward"]["shared_up_proj"]["kernel"].shape \
        == (3584, 1024)
    assert layer["attention"]["q_a_proj"]["kernel"].shape == (3584, 768)
    assert layer["attention"]["q_b_proj"]["kernel"].shape == (768, 6144)
    assert layer["attention"]["kv_a_proj"]["kernel"].shape == (3584, 576)
    assert layer["attention"]["kv_b_proj"]["kernel"].shape == (512, 8192)
    assert layer["attention"]["o_proj"]["kernel"].shape == (4096, 3584)
    assert layer["attention_hc"]["phi"].shape == (4, 3584, 24)
    assert shapes["lm_head"]["kernel"].shape == (3584, 16384)
    assert "mtp_0" not in shapes
    with_mtp = dataclasses.replace(model.config, mtp_layers=1)
    assert with_mtp.num_params() - count == 154_127_222
    assert with_mtp.num_params() * 16 > 14.4e9 > count * 16
    whole = dataclasses.replace(model.config, experts_held=0, num_layers=2)
    one = dataclasses.replace(whole, num_layers=1)
    assert whole.num_params() - one.num_params() \
        == config["share"]["whole_expert_layer_parameters"]
    assert model.config.hyper_config() == hc.HyperConnectionConfig()


@pytest.mark.parametrize("key,value,says", [
    ("n_group", 8, "group limit"), ("topk_group", 4, "group limit"),
    ("tie_word_embeddings", True, "untied"),
    ("attention_bias", True, "no bias"),
    ("moe_layer_freq", 2, "expert layer"),
    ("rope_scaling", {"type": "linear", "factor": 4}, "YaRN"),
    ("num_nextn_predict_layers", 2, "multi-token"),
])
def test_build_refuses_what_the_program_cannot_state(key, value, says):
    from benchmark.models import xing4_0 as model_class

    with pytest.raises(ValueError, match=says):
        model_class.build({**_file(), key: value})
