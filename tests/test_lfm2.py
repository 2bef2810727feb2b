"""`lfm2_moe` through `models/lfm2.py`: gated short-convolution mixers
beside grouped-query attention with a per-head QK norm, a leading dense
SwiGLU and sigmoid-routed expert layers without a shared expert, under a
tied head — against the plain reference
(`benchmark/reference_lfm2_moe.py`) at a nano size on the CPU, float32 on
both sides; the mixer's causality and its reach; rows that do not leak;
the share test; the vocabulary slice; the per-head QK norm beside the
whole-projection one; the normaliser's epsilon; the parameter counts at
the published widths; the counters; the sharding rules; what is refused.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lfm2_moe as ref
from dlrover_wuqiong_tpu.models import moe
from dlrover_wuqiong_tpu.models.lfm2 import (
    Lfm2,
    Lfm2Config,
    ShortConvMixer,
    gated_short_conv,
)
from dlrover_wuqiong_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    rope_freqs,
)
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 48


def nano(**over):
    """Three layers: 0 conv + dense, 1 attention + experts, 2 conv +
    experts; experts 4-7 of 16 held."""
    return Lfm2Config.nano(**{**dict(
        dtype=jnp.float32, remat=False, use_flash_attention=False,
        experts_held=4, first_expert=4), **over})


def reference_loss(cfg, **control):
    return functools.partial(
        ref.loss, layer_types=cfg.layer_types, n_dense=cfg.num_dense_layers,
        n_head=cfg.num_heads, n_kv=cfg.num_kv_heads, theta=cfg.rope_theta,
        top_k=cfg.top_k, routed_scaling=cfg.routed_scaling,
        first_expert=cfg.first_expert, eps=cfg.rms_eps, **control)


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
    model = Lfm2(cfg)
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


# 2 conv mixers x 3 + 1 attention x 6 + 3 x 2 norms + dense 3 + 2 x 5
# expert layer leaves + table, norm
N_LEAVES = 6 + 6 + 6 + 3 + 10 + 2


def test_the_loss_is_the_references(both_sides):
    names, (loss, _), (ref_loss, _), *_ = both_sides
    assert len(names) == N_LEAVES
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)


@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_every_leafs_gradient_is_the_references(both_sides, leaf):
    """Leaf by leaf (the norm over 470M entries that the chip compares
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
    assert abs(off - float(right)) > 5e-6 * float(right), (wrong, off)
    with pytest.raises(ValueError, match="one of"):
        reference_loss(nano(), wrong="nothing")(params, batch_of(3))


# --------------------------------------------------------------- the mixer

@pytest.fixture(scope="module")
def mixer():
    layer = ShortConvMixer(32, 3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 32))
    params = with_opinions(
        layer.init(jax.random.PRNGKey(1), x)["params"], 2, 0.3)
    return jax.jit(lambda p, x: layer.apply({"params": p}, x)), params, x


@pytest.mark.parametrize("t", (0, 17, SEQ - 1))
def test_a_step_moves_its_own_output_and_the_next_two(mixer, t):
    """Through the mixer alone a change at step t moves no output before
    t (it is causal) and none after t + 2 (three taps: nothing is carried
    further), and it does move t, t + 1 and t + 2."""
    apply, params, x = mixer
    moved = np.abs(np.asarray(
        apply(params, x.at[0, t].add(1.0)) - apply(params, x))).max(-1)
    reach = [s for s in (t, t + 1, t + 2) if s < SEQ]
    assert np.all(moved[0, reach] > 1e-4)
    assert not np.any(np.delete(moved[0], reach))
    assert not np.any(moved[1])  # the other row of the batch


def test_the_filters_last_tap_is_on_the_current_step():
    """[B | C | X] in that order, and kernel[taps - 1] on z[t]: with B =
    C = 1 and a filter (0, 0, 1) a channel the mixer's middle is X
    itself, with (1, 0, 0) X two steps back behind two zeros."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 4))
    bcx = jnp.concatenate([jnp.ones_like(x), jnp.ones_like(x), x], -1)
    now = jnp.zeros((3, 4)).at[2].set(1.0)
    np.testing.assert_array_equal(
        gated_short_conv(bcx, now, jnp.float32), x)
    back = gated_short_conv(bcx, now[::-1], jnp.float32)
    np.testing.assert_array_equal(back[:, 2:], x[:, :-2])
    assert not np.any(back[:, :2])
    # the gates: B on the way in, C on the way out
    b, c = 2.0 * jnp.ones_like(x), 3.0 * jnp.ones_like(x)
    np.testing.assert_allclose(gated_short_conv(
        jnp.concatenate([b, c, x], -1), now, jnp.float32), 6.0 * x)


def test_two_rows_of_a_batch_do_not_leak(both_sides):
    """Two sequences side by side in a batch: the first row's logits are
    those it has alone — the filter's zeros stand before EACH row's start
    (the expert layer is a token's own; the attention a row's)."""
    params = both_sides[3]
    model = Lfm2(nano())
    ids = batch_of(9)["input_ids"]
    both = jax.jit(model.apply)({"params": params}, ids)
    alone = jax.jit(model.apply)({"params": params}, ids[:1])
    np.testing.assert_allclose(both[:1], alone, rtol=2e-5, atol=2e-5)
    other = jax.jit(model.apply)(
        {"params": params}, ids.at[1].set((ids[1] + 1) % 256))
    np.testing.assert_allclose(other[:1], both[:1], rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- the shares

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Every share of the experts (eight shares of 8 of 64, the cell's
    router at its published width, 4 a token under the bias; no shared
    expert), the router and the bias counted once — every share computes
    them alike — add up to the uncut reference's layer."""
    hidden, width, n_exp, held = 24, 16, 64, 8
    base = moe.MoEConfig(
        num_experts=n_exp, top_k=4, impl="grouped", dtype=jnp.float32,
        aux_loss="none", score_func="sigmoid", selection_bias=True,
        gate_norm_eps=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, hidden))
    params = with_opinions(jax.jit(moe.MoEMLP(hidden, width, base).init)(
        jax.random.PRNGKey(1), x)["params"], 3, 0.3)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(
            x.reshape(-1, hidden), params, top_k=4, routed_scaling=1.0,
            first_expert=0).reshape(x.shape)
        total, rows = jnp.zeros_like(x), 0
        for first in range(0, n_exp, held):
            share = {**params, **{
                name: params[name][first:first + held] for name in
                ("experts_w_in", "experts_w_gate", "experts_w_down")}}
            layer = moe.MoEMLP(hidden, width, dataclasses.replace(
                base, experts_held=held, first_expert=first))
            part, sown = jax.jit(functools.partial(
                layer.apply, mutable=["intermediates"]))(
                    {"params": share}, x)
            total = total + part
            rows += int(sown["intermediates"]["moe_rows_held"][0])
    assert rows == 2 * SEQ * 4  # every assignment on exactly one share
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_a_slice_of_the_vocabulary_is_a_smaller_vocabulary(both_sides):
    """The first rows of the tied table, ids inside them: the sliced
    model's logits are the whole model's over the slice — lookup and head
    read the same rows — and its loss is the reference's over the
    slice."""
    params = both_sides[3]
    kept = 64
    sliced = {**params, "embed_tokens": {
        "embedding": params["embed_tokens"]["embedding"][:kept]}}
    ids = batch_of(5)["input_ids"] % kept
    whole = jax.jit(Lfm2(nano()).apply)({"params": params}, ids)
    small_model = Lfm2(nano(vocab_size=kept))
    small = jax.jit(small_model.apply)({"params": sliced}, ids)
    assert small.shape[-1] == kept
    np.testing.assert_allclose(small, whole[..., :kept], rtol=1e-5,
                               atol=1e-5)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}
    with jax.default_matmul_precision("highest"):
        got = jax.jit(make_lm_loss(small_model.apply))(sliced, batch)
        want = jax.jit(reference_loss(nano(vocab_size=kept)))(sliced, batch)
    assert abs(float(got) - float(want)) < 2e-6 * float(want)


# ---------------------------------------------------------- the QK norms

def _attention(**over):
    cfg = dataclasses.replace(
        LlamaConfig.nano(), dtype=jnp.float32, use_flash_attention=False,
        **over)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.hidden_size))
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    layer = LlamaAttention(cfg)
    return cfg, layer, (x, cos, sin)


def test_the_per_head_norm_is_not_the_whole_projections():
    """`qk_head_norm`: ONE (head size,) scale for q's heads and one for
    k's, the statistic over a head's lanes — against the reference's
    attention; `qk_norm` has a scale a feature and one statistic over all
    of them, and gives another output on the same q, k, v and o."""
    cfg, layer, args = _attention(qk_head_norm=True)
    params = with_opinions(
        layer.init(jax.random.PRNGKey(1), *args)["params"], 2)
    assert params["q_norm"]["scale"].shape == (cfg.head_dim,) \
        == params["k_norm"]["scale"].shape
    got = layer.apply({"params": params}, *args)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(
            args[0], params, n_head=cfg.num_heads, n_kv=cfg.num_kv_heads,
            theta=cfg.rope_theta, eps=cfg.rms_eps)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    whole_cfg, whole, _ = _attention(qk_norm=True)
    wide = {**params, "q_norm": {"scale": jnp.tile(
        params["q_norm"]["scale"], cfg.num_heads)}, "k_norm": {
            "scale": jnp.tile(params["k_norm"]["scale"], cfg.num_kv_heads)}}
    other = whole.apply({"params": wide}, *args)
    assert float(jnp.abs(other - got).max()) > 1e-3
    assert cfg.attention_params() == sum(
        a.size for a in jax.tree.leaves(params))
    assert whole_cfg.attention_params() - cfg.attention_params() == \
        (cfg.num_heads + cfg.num_kv_heads - 2) * cfg.head_dim
    with pytest.raises(ValueError, match="one norm"):
        _attention(qk_norm=True, qk_head_norm=True)[1].init(
            jax.random.PRNGKey(0), *args)


def test_the_per_head_norm_is_off_by_default_and_adds_nothing():
    """Default off: `LlamaAttention` lowers to the text it lowers to with
    the field never mentioned, with no `q_norm` in its tree (the whole
    models' lowered texts are pinned, untouched, by tests/test_stack.py's
    digests and tests/test_program_from_arguments.py's)."""
    assert LlamaConfig().qk_head_norm is False
    cfg, layer, args = _attention()
    params = layer.init(jax.random.PRNGKey(1), *args)["params"]
    assert set(params) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    text = jax.jit(layer.apply).lower({"params": params}, *args).as_text()
    explicit = LlamaAttention(dataclasses.replace(cfg, qk_head_norm=False))
    assert text == jax.jit(explicit.apply).lower(
        {"params": params}, *args).as_text()
    assert "rsqrt" not in text


# ---------------------------------------------------- the normaliser's eps

def test_the_gates_normaliser_is_the_configs_and_1e20_by_default():
    """`MoEConfig.gate_norm_eps`: 1e-20 (the published DeepSeek-V3 form)
    unless a config says otherwise; at LFM2's 1e-6 the chosen gates are
    `s / (sum + 1e-6)`, which float32 tells from `s / sum`; a router
    left at the default is called as it always was and lowers to the
    same text."""
    probs = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0),
                                             (96, 64)) - 9.0)  # tiny sums
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    route = functools.partial(moe.route_top_k, top_k=4, bias=bias,
                              floor=False)
    _, want = jax.lax.top_k(probs + bias, 4)
    picked = jnp.take_along_axis(probs, want, axis=-1)
    for eps in (1e-20, 1e-6):
        gates, experts = route(probs, eps=eps)
        np.testing.assert_array_equal(experts, want)
        np.testing.assert_array_equal(
            gates, picked / (picked.sum(-1, keepdims=True) + eps))
    assert float(jnp.abs(route(probs, eps=1e-6)[0]
                         - route(probs)[0]).max()) > 1e-4
    assert jax.jit(route).lower(probs).as_text() == jax.jit(
        functools.partial(route, eps=1e-20)).lower(probs).as_text()
    cfg = moe.MoEConfig(impl="grouped")
    assert cfg.gate_norm_eps == 1e-20
    assert "gate_norm_eps" not in cfg.grouped_only_fields()
    assert moe.MoEConfig(impl="grouped", gate_norm_eps=1e-6
                         ).grouped_only_fields() == {"gate_norm_eps": 1e-6}
    assert nano().moe_config().gate_norm_eps == 1e-6
    with pytest.raises(ValueError, match="impl='grouped'"):
        moe.MoEMLP(8, 8, moe.MoEConfig(gate_norm_eps=1e-6)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


# ------------------------------------------------------- parameter counts

def test_num_params_is_the_cells_count_and_the_published_models():
    """Shapes only: 469,285,248 at the cell's sizes (published blocks 1-5
    with one leading dense layer, eight of 64 experts, an eighth of the
    vocabulary) and 23,843,661,440 uncut, by `num_params` and by the
    tree."""
    def tree_size(cfg):
        shapes = jax.eval_shape(Lfm2(cfg).init_params,
                                jax.random.PRNGKey(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    whole = Lfm2Config()
    assert whole.num_params() == 23_843_661_440
    assert [i for i, kind in enumerate(whole.layer_types)
            if kind == "full_attention"] == list(range(2, 40, 4))
    # the tree at the first six blocks of the uncut model (both leading
    # dense layers, one whole period, all 64 experts): what `num_params`
    # adds up a layer is what `init_params` draws
    six = dataclasses.replace(whole, layer_types=whole.layer_types[:6])
    assert six.num_params() == tree_size(six)
    assert whole.num_params() - six.num_params() == 34 * (
        whole.moe_ffn_params() + 2 * 2048) + 25 * whole.conv_params() \
        + 9 * whole.attention_config().attention_params()
    cell = Lfm2Config(
        vocab_size=8192, layer_types=whole.layer_types[1:6],
        num_dense_layers=1, experts_held=8)
    assert cell.layer_types == ("conv", "full_attention", "conv", "conv",
                                "conv")
    assert cell.num_params() == tree_size(cell) == 469_285_248
    assert cell.conv_params() == 16_783_360
    assert cell.attention_config().attention_params() == 10_485_888
    assert cell.attention_config().ffn_params() == 72_351_744
    assert cell.moe_ffn_params() == 131_136 + 8 * 9_437_184
    assert cell.num_params() * 16 < 0.5 * 16e9 < 14.4e9


def test_num_params_is_the_tree_at_nano_size(both_sides):
    params = both_sides[3]
    assert nano().num_params() == sum(
        a.size for a in jax.tree.leaves(params))


# ------------------------------------------------------------ the counters

def test_the_counters_ride_the_steps_metrics(both_sides):
    """`make_lm_loss.with_stats` hands out which lines the gated
    convolutions ran (both mixers, the plain ones) beside the expert
    layers' counts; a model without such a mixer has neither."""
    cfg, stats = nano(), both_sides[4]
    assert float(stats["shortconv_calls"]) == 2.0 \
        == float(stats["shortconv_plain_calls"])
    assert float(stats["moe_rows_held"]) + float(stats["moe_rows_absent"]) \
        == 2 * 2 * SEQ * cfg.top_k
    assert "moe_group_limit_binds" not in stats
    only_attention = Lfm2(nano(layer_types=("full_attention",)))
    params = jax.jit(only_attention.init_params)(jax.random.PRNGKey(0))
    _, stats = jax.jit(make_lm_loss(only_attention.apply).with_stats)(
        params, batch_of(4))
    assert "shortconv_calls" not in stats


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
    params = jax.eval_shape(Lfm2(nano()).init_params, jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for path in paths:
        assert any(re.match(pat, path, re.IGNORECASE)
                   for pat, _ in rules), path
    sc, at = "layers_0/short_conv", "layers_1/attention"  # nano's
    want = {
        f"{sc}/in_proj/kernel": P("fsdp", "tp"),
        f"{sc}/out_proj/kernel": P("tp", "fsdp"),
        f"{sc}/conv_kernel": P(),
        f"{at}/q_proj/kernel": P("fsdp", "tp"),
        f"{at}/k_proj/kernel": P("fsdp", "tp"),
        f"{at}/o_proj/kernel": P("tp", "fsdp"),
        f"{at}/q_norm/scale": P(), f"{at}/k_norm/scale": P(),
        "layers_0/operator_norm/scale": P(), "layers_0/ffn_norm/scale": P(),
        "layers_0/feed_forward/gate_proj/kernel": P("fsdp", "tp"),
        "layers_0/feed_forward/down_proj/kernel": P("tp", "fsdp"),
        "layers_1/feed_forward/selection_bias": P(),
        "layers_1/feed_forward/router/kernel": P("fsdp", None),
        "layers_1/feed_forward/experts_w_in": P("ep", "fsdp", "tp"),
        "embed_tokens/embedding": spec_for_path("embed_tokens/embedding",
                                                rules),
        "norm/scale": P()}
    assert set(want) <= set(paths)
    for path, spec in want.items():
        assert spec_for_path(path, rules) == spec, path
    assert "lm_head" not in params  # the table is the head
    assert re.match(Lfm2.untrained_params[0],
                    "layers_1/feed_forward/selection_bias")


# --------------------------------------------------------- what is refused

def test_a_kind_the_stack_does_not_have_is_refused():
    with pytest.raises(ValueError, match="layer is one of"):
        jax.eval_shape(Lfm2(nano(layer_types=("conv", "mamba"))).init_params,
                       jax.random.PRNGKey(0))


def test_a_mesh_of_several_devices_is_refused_and_one_device_steps():
    """Through `auto_accelerate`, as every configuration: on two devices
    the stack says what it cannot run; on one an optimizer step under
    `fsdp` runs with every block rematerialised, the selection bias left
    alone by the optimizer and moved by its rule."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    model = Lfm2(nano(remat=True, bias_update_rate=0.05))
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
    assert float(metrics["shortconv_plain_calls"]) == 2.0
    after = np.asarray(
        state.params["layers_1"]["feed_forward"]["selection_bias"])
    assert 0 < np.abs(after - before).max() <= 0.05 + 1e-6
