"""`qwen3_next` through `models/qwen3_next.py`: periods of gated
delta-rule mixers whose value heads outnumber their key heads
(`models/gated_delta.py`) and one output-gated attention of heads rotated
in part (`models/llama.py`), an expert layer beside a sigmoid-gated
shared expert behind every mixer (`models/moe.py`), zero-centred norms,
an untied head — against the plain reference
(`benchmark/reference_qwen3_next.py`) at a nano size on the CPU, float32
on both sides: the loss and every leaf's gradient on the routes the CPU
takes (the chunked form, the scan over time), with and without the
balance term; every control the reference names; the parameter counts at
the published widths; the share test; the counters; the sharding rules;
what is refused; and that the extensions left the mixers, norms and
layers they extend bit for bit what they were.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_qwen3_next as ref
from dlrover_wuqiong_tpu.models import moe
from dlrover_wuqiong_tpu.models.gated_delta import (
    GatedDeltaConfig,
    GatedDeltaMixer,
)
from dlrover_wuqiong_tpu.models.llama import RMSNorm
from dlrover_wuqiong_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from dlrover_wuqiong_tpu.ops.delta_rule import delta_route
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 48  # three chunks of 16: the chunked route


def nano(**over):
    """One period (three delta-rule blocks, one attention block), experts
    4-7 of 16 held, 4 value heads over 2 key heads, 4 of 16 lanes
    rotated."""
    return Qwen3NextConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, experts_held=4, first_expert=4),
        **over})


def reference_loss(cfg, **control):
    return functools.partial(
        ref.loss, n_layer=cfg.num_layers,
        interval=cfg.full_attention_interval, n_head=cfg.num_heads,
        n_kv=cfg.num_kv_heads, theta=cfg.rope_theta,
        rotary=cfg.rotary_dim / cfg.head_dim,
        key_heads=cfg.linear_key_heads, value_heads=cfg.linear_value_heads,
        key_dim=cfg.linear_key_dim, value_dim=cfg.linear_value_dim,
        top_k=cfg.top_k, first_expert=cfg.first_expert, eps=cfg.rms_eps,
        aux_weight=cfg.router_aux_loss_weight, **control)


def with_opinions(params, seed, scale=0.1):
    """Every leaf off its draw, so that no zero-centred scale is 0, no
    plain one 1 and no term symmetric by accident."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(next(keys), a.shape), params)


def batch_of(seed, rows=2, seq=SEQ):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, 256)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


def _sides(cfg, batch):
    model = Qwen3Next(cfg)
    params = with_opinions(
        jax.jit(model.init_params)(jax.random.PRNGKey(1)), 2)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            make_lm_loss(model.apply).with_stats, has_aux=True))(
                params, batch)
        want, ref_grads = jax.jit(jax.value_and_grad(
            reference_loss(cfg)))(params, batch)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    return names, (loss, grads), (want, ref_grads), params, stats


# ------------------------------------------------- model against reference

@pytest.fixture(scope="module")
def chunked():
    """T = 48, three whole chunks of 16: `_chunked`; every block
    recomputed."""
    assert delta_route(SEQ, 16, 4, 8, 8) == "chunked"
    return _sides(nano(remat=True), batch_of(3))


@pytest.fixture(scope="module")
def sequential():
    """T = 40, no whole number of chunks: the scan over time."""
    assert delta_route(40, 16, 4, 8, 8) == "sequential"
    return _sides(nano(), batch_of(4, seq=40))


@pytest.fixture(scope="module")
def balanced():
    """The router's load-balancing term on (the cell's assumption)."""
    return _sides(nano(router_aux_loss_weight=0.01), batch_of(6))


# a delta block: 7 products, conv, A_log, dt_bias, the output norm = 11;
# the attention: 4 products + 2 head norms = 6; a block's 2 norms; router,
# 3 expert stacks, the shared expert's 3 and its gate = 8; table, final
# norm, head
N_LEAVES = 3 * (11 + 2 + 8) + (6 + 2 + 8) + 3


def _all_of_it_is_the_references(sides):
    names, (loss, grads), (want, ref_grads), *_ = sides
    assert len(names) == N_LEAVES
    assert abs(float(loss) - float(want)) < 3e-6 * abs(float(want))
    for name, got, ref_leaf in zip(names, jax.tree.leaves(grads),
                                   jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(ref_leaf).max()) > 0, name
        np.testing.assert_allclose(
            got, ref_leaf, rtol=1e-3,
            atol=1e-4 * float(jnp.abs(ref_leaf).max()), err_msg=name)


# ONE test a setting holds the loss and every leaf, so that one worker
# builds the setting's two compiled sides once (tests/test_keye.py)

def test_on_the_chunked_route_loss_and_every_leaf_are_the_references(
        chunked):
    _all_of_it_is_the_references(chunked)


def test_on_the_scan_over_time_loss_and_every_leaf_are_the_references(
        sequential):
    _all_of_it_is_the_references(sequential)


def test_with_the_balance_term_all_of_it_is_the_references(balanced):
    """And the term is a term: 0.01 x (a number near top_k = 3) over the
    loss without it."""
    _all_of_it_is_the_references(balanced)
    _, (loss, _), _, params, _ = balanced
    with jax.default_matmul_precision("highest"):
        bare = jax.jit(reference_loss(nano()))(params, batch_of(6))
    assert float(loss) - float(bare) == pytest.approx(0.03, abs=0.012)


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_equation_is_another_loss(chunked, wrong):
    """Each control the reference names moves the loss by far more than
    the two sides differ: the reference would tell it from the model."""
    _, (loss, _), _, params, _ = chunked
    with jax.default_matmul_precision("highest"):
        off = jax.jit(reference_loss(nano(), wrong=wrong))(params,
                                                           batch_of(3))
    assert abs(float(off) - float(loss)) > 1e-4 * float(loss), wrong


def test_an_unknown_control_is_refused():
    with pytest.raises(ValueError, match="one of"):
        reference_loss(nano(), wrong="nothing")({}, batch_of(3))


# ------------------------------------------------------------ the counters

def test_the_counters_ride_the_steps_metrics(chunked):
    stats = chunked[4]
    # three mixers: 4 value heads' q and k rows read for the 2 the model has
    assert float(stats["delta_qk_rows_run"]) == 3 * 4
    assert float(stats["delta_qk_rows_model"]) == 3 * 2
    assert float(stats["delta_lanes_run"]) == \
        float(stats["delta_lanes_model"]) == 3 * 16
    assert 0 < float(stats["delta_alpha_mean"]) < 1
    # no factor 2 on the write gate: a sigmoid's mean
    assert 0.3 < float(stats["delta_beta_mean"]) < 0.7
    assert 0.3 < float(stats["attn_gate_mean"]) < 0.7
    assert "attn_gate_kernel_share" not in stats  # no head-wise gate
    assert 0.3 < float(stats["moe_shared_gate_mean"]) < 0.7
    # heads of 16 off the TPU: nothing is padded
    assert float(stats["attn_lanes_run"]) == float(
        stats["attn_lanes_model"]) == 32
    assert float(stats["moe_rows_held"]) + float(
        stats["moe_rows_absent"]) == 4 * 2 * SEQ * 3
    assert float(stats["moe_dropped"]) == 0


# ------------------------------------------------------- parameter counts

def test_num_params_is_the_cells_count_and_the_published_models():
    """Shapes only: 625,667,136 at the cell's sizes (one period of
    twelve, 32 of 512 experts, an eighth of the vocabulary), 424,340,544
    with 16 held, and 79.67B uncut, by `num_params` and by the tree."""
    def tree_size(cfg):
        shapes = jax.eval_shape(Qwen3Next(cfg).init_params,
                                jax.random.PRNGKey(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    whole = Qwen3NextConfig()
    routed = 512 * 3_145_728
    assert routed == 1_610_612_736
    assert whole.num_params() == 36 * (37_918_912 + routed) \
        + 12 * (31_463_936 + routed) + 2 * 151_936 * 2048 + 2048 \
        == 79_674_391_296
    assert whole.layer_types.count("full_attention") == 12
    assert whole.layer_types[:4] == ("linear_attention",) * 3 \
        + ("full_attention",)
    assert whole.linear_config().num_params() == 33_718_464
    assert whole.attention_config().attention_params() == 27_263_488
    # router 1,048,576, shared expert 3,145,728, its gate 2,048
    assert whole.attention_config().ffn_params() - routed == 4_196_352
    cell = Qwen3NextConfig(vocab_size=18_992, num_layers=4, experts_held=32)
    assert cell.num_params() == tree_size(cell) == 625_667_136 \
        == 3 * 138_582_208 + 132_127_232 + 77_793_280
    assert dataclasses.replace(cell, experts_held=16).num_params() \
        == 424_340_544
    assert 0.25 * 16e9 < cell.num_params() * 16 < 14.4e9
    one = dataclasses.replace(whole, num_layers=4, num_experts=4, top_k=2)
    assert one.num_params() == tree_size(one)


def test_num_params_is_the_tree_at_nano_size():
    shapes = jax.eval_shape(Qwen3Next(nano()).init_params,
                            jax.random.PRNGKey(0))
    assert nano().num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


# -------------------------------------------------------------- the shares

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Every share of the experts (sixteen shares of 32 of 512, the
    cell's router at its published width, 10 a token renormalised), their
    ROUTED parts added and the gated shared expert — which every share
    computes alike — counted ONCE, add up to the uncut reference's
    layer."""
    hidden, width, n_exp, held = 24, 16, 512, 32
    base = moe.MoEConfig(
        num_experts=n_exp, top_k=10, impl="grouped", dtype=jnp.float32,
        aux_loss="none", score_func="softmax", norm_topk_prob=True,
        shared_width=width, shared_gate=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, hidden))
    params = with_opinions(jax.jit(moe.MoEMLP(hidden, width, base).init)(
        jax.random.PRNGKey(1), x)["params"], 3, 0.3)
    stacks = ("experts_w_in", "experts_w_gate", "experts_w_down")
    flat = x.reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(flat, params, top_k=10, first_expert=0)
        # the gated shared expert alone: a layer that holds no routed one
        nothing = {**params, **{n: params[n][:0] for n in stacks}}
        shared = ref.expert_layer(flat, nothing, top_k=10, first_expert=0)
        total, rows = jnp.zeros_like(flat), 0
        for first in range(0, n_exp, held):
            share = {**params, **{n: params[n][first:first + held]
                                  for n in stacks}}
            layer = moe.MoEMLP(hidden, width, dataclasses.replace(
                base, experts_held=held, first_expert=first))
            part, sown = jax.jit(functools.partial(
                layer.apply, mutable=["intermediates"]))(
                    {"params": share}, x)
            total = total + part.reshape(-1, hidden) - shared
            rows += int(sown["intermediates"]["moe_rows_held"][0])
    assert rows == 2 * SEQ * 10  # every assignment on exactly one share
    assert float(jnp.abs(shared).max()) > 0
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)


# ------------------------------------- what the extensions left as it was

def _text(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_at_equal_heads_the_mixer_is_bit_for_bit_what_it_was():
    """`num_key_heads` unset or equal to `num_heads`, the factor 2 on:
    the same parameters, the same lowered text (so the same numbers), the
    same five counters; and a grouped mixer is the equal-headed one on
    repeated q and k projections."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    was = GatedDeltaConfig(hidden_size=64, num_heads=4, key_dim=8,
                           value_dim=8, chunk_size=16, dtype=jnp.float32)
    now = dataclasses.replace(was, num_key_heads=4, neg_eigval=True)
    params = jax.jit(GatedDeltaMixer(was).init)(jax.random.PRNGKey(1), x)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, jax.eval_shape(GatedDeltaMixer(now).init,
                                  jax.random.PRNGKey(1), x))
    def run(cfg):
        return functools.partial(GatedDeltaMixer(cfg).apply,
                                 mutable=["intermediates"])

    assert _text(run(was), params, x) == _text(run(now), params, x)
    out, sown = jax.jit(run(was))(params, x)
    assert sown["intermediates"]["delta_stats"][0].shape == (5,)
    # value heads over key heads: the equal-headed mixer whose q and k
    # projections (and filters) are a key head's, repeated
    grouped = dataclasses.replace(was, num_key_heads=2)
    small = jax.jit(GatedDeltaMixer(grouped).init)(jax.random.PRNGKey(2), x)
    p = small["params"]
    assert p["q_proj"]["kernel"].shape == (64, 16)
    assert p["conv_kernel"].shape == (4, 2 * 16 + 32)

    def repeated(w, width=8):  # key head g -> value heads 2g, 2g + 1
        cut = w.reshape(*w.shape[:-1], 2, width)
        return jnp.repeat(cut, 2, axis=-2).reshape(*w.shape[:-1], 4 * width)

    conv = p["conv_kernel"]
    big = {**p, "q_proj": {"kernel": repeated(p["q_proj"]["kernel"])},
           "k_proj": {"kernel": repeated(p["k_proj"]["kernel"])},
           "conv_kernel": jnp.concatenate(
               [repeated(conv[:, :16]), repeated(conv[:, 16:32]),
                conv[:, 32:]], axis=-1)}
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(run(grouped))(small, x)
        want, _ = jax.jit(run(was))({"params": big}, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    stats = sown["intermediates"]["delta_stats"][0]
    assert stats.shape == (7,) and tuple(np.asarray(stats[2:4])) == (4, 2)


def test_the_write_gate_without_its_factor_is_half_of_it():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    cfg = GatedDeltaConfig(hidden_size=64, num_heads=2, key_dim=8,
                           value_dim=8, chunk_size=16, dtype=jnp.float32)
    params = jax.jit(GatedDeltaMixer(cfg).init)(jax.random.PRNGKey(1), x)

    def beta_sum(c):
        _, sown = GatedDeltaMixer(c).apply(params, x,
                                           mutable=["intermediates"])
        return float(sown["intermediates"]["delta_stats"][0][3])

    assert beta_sum(cfg) == pytest.approx(
        2 * beta_sum(dataclasses.replace(cfg, neg_eigval=False)), rel=1e-6)


def test_value_heads_no_multiple_of_the_key_heads_are_refused():
    cfg = GatedDeltaConfig(hidden_size=64, num_heads=4, num_key_heads=3,
                           key_dim=8, value_dim=8)
    with pytest.raises(ValueError, match="do not divide"):
        jax.eval_shape(GatedDeltaMixer(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16, 64)))


def test_the_plain_norm_is_what_it_was_and_the_zero_centred_one_adds_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    plain, centred = RMSNorm(1e-6, jnp.float32), RMSNorm(
        1e-6, jnp.float32, zero_centred=True)
    p = plain.init(jax.random.PRNGKey(1), x)
    z = centred.init(jax.random.PRNGKey(1), x)
    assert float(p["params"]["scale"].min()) == 1.0
    assert float(jnp.abs(z["params"]["scale"]).max()) == 0.0
    # at their draws the two are one function
    np.testing.assert_array_equal(plain.apply(p, x), centred.apply(z, x))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    np.testing.assert_allclose(
        centred.apply({"params": {"scale": w}}, x),
        plain.apply({"params": {"scale": 1.0 + w}}, x), rtol=1e-6)
    assert "add" not in _text(plain.apply, p, x).split("rsqrt")[-1]


def test_an_ungated_shared_expert_is_what_it_was_and_a_gate_needs_one():
    hidden, width = 24, 16
    base = moe.MoEConfig(num_experts=8, top_k=2, impl="grouped",
                         dtype=jnp.float32, aux_loss="none",
                         shared_width=width)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, hidden))
    layer = moe.MoEMLP(hidden, width, base)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    assert "shared_expert_gate" not in params["params"]
    gated = moe.MoEMLP(hidden, width,
                       dataclasses.replace(base, shared_gate=True))
    more = jax.jit(gated.init)(jax.random.PRNGKey(1), x)["params"]
    assert more["shared_expert_gate"]["kernel"].shape == (hidden, 1)
    # a gate whose logit is very large is the ungated layer
    wide = {**more, "shared_expert_gate": {
        "kernel": jnp.zeros((hidden, 1))}}
    half = gated.apply({"params": wide}, x)
    routed = moe.MoEMLP(hidden, width, dataclasses.replace(
        base, shared_width=0)).apply({"params": {
            k: v for k, v in more.items() if not k.startswith("shared")}}, x)
    whole = layer.apply({"params": {k: v for k, v in more.items()
                                    if k != "shared_expert_gate"}}, x)
    np.testing.assert_allclose(half - routed, 0.5 * (whole - routed),
                               rtol=1e-4, atol=1e-6)
    with pytest.raises(ValueError, match="there is none"):
        jax.eval_shape(moe.MoEMLP(hidden, width, dataclasses.replace(
            base, shared_width=0, shared_gate=True)).init,
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="impl='grouped'"):
        jax.eval_shape(moe.MoEMLP(hidden, width, dataclasses.replace(
            base, impl="capacity", shared_gate=True)).init,
            jax.random.PRNGKey(0), x)


def test_the_elementwise_gate_beside_another_gate_is_refused():
    from dlrover_wuqiong_tpu.models.llama import LlamaAttention, rope_freqs

    cfg = dataclasses.replace(nano().attention_config(), attn_gate=True)
    cos, sin = rope_freqs(4, 16, 1e4)
    with pytest.raises(ValueError, match="asked for both"):
        jax.eval_shape(LlamaAttention(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16, 64)), cos, sin)


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
    params = jax.eval_shape(Qwen3Next(nano()).init_params,
                            jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for path in paths:
        assert any(re.match(pat, path, re.IGNORECASE)
                   for pat, _ in rules), path
    lin, at, ff = ("layers_0/linear_attention", "layers_3/attention",
                   "layers_3/feed_forward")
    want = {
        f"{lin}/q_proj/kernel": P("fsdp", "tp"),
        f"{lin}/g_proj/kernel": P("fsdp", "tp"),
        f"{lin}/a_proj/kernel": P("fsdp", None),
        f"{lin}/o_proj/kernel": P("tp", "fsdp"),
        f"{lin}/conv_kernel": P(), f"{lin}/A_log": P(),
        f"{lin}/dt_bias": P(), f"{lin}/gate_norm_scale": P(),
        f"{at}/q_proj/kernel": P("fsdp", "tp"),
        f"{at}/o_proj/kernel": P("tp", "fsdp"),
        f"{at}/q_norm/scale": P(), f"{at}/k_norm/scale": P(),
        "layers_0/input_norm/scale": P(),
        f"{ff}/router/kernel": P("fsdp", None),
        f"{ff}/shared_expert_gate/kernel": P("fsdp", None),
        f"{ff}/shared_up_proj/kernel": P("fsdp", "tp"),
        f"{ff}/shared_down_proj/kernel": P("tp", "fsdp"),
        f"{ff}/experts_w_in": P("ep", "fsdp", "tp"),
        "lm_head/kernel": P("fsdp", "tp"), "norm/scale": P()}
    assert set(want) <= set(paths)
    for path, spec in want.items():
        assert spec_for_path(path, rules) == spec, path


# --------------------------------------------------------- what is refused

def test_a_share_on_several_devices_is_refused_and_one_device_steps():
    """Through `auto_accelerate`, as every configuration: on two devices
    a chip's share of the experts says what it cannot run; on one an
    optimizer step under `fsdp` runs with every block rematerialised and
    returns the counters."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    model = Qwen3Next(nano(remat=True, router_aux_loss_weight=0.01))
    with pytest.raises(ValueError, match="one device"):
        auto_accelerate(model, strategy=[("fsdp", {})],
                        devices=jax.devices()[:2],
                        optimizer=optax.adamw(1e-3), seq_len=SEQ)
    res = auto_accelerate(model, strategy=[("fsdp", {})],
                          devices=jax.devices()[:1],
                          optimizer=optax.adamw(1e-3), seq_len=SEQ)
    batch = {k: np.asarray(v) for k, v in batch_of(5, rows=4).items()}
    state, metrics = res.train_step(res.state, res.place_batch(batch))
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1
    assert float(metrics["delta_qk_rows_run"]) == 12
    assert 0 < float(metrics["moe_shared_gate_mean"]) < 1
