"""`olmo_hybrid` through `models/olmo_hybrid.py`: the reordered-norm block
of two sublayers, the gated delta-rule mixer and the QK-normed attention
without rotation — against the plain reference
(`benchmark/reference_olmo_hybrid.py`) at a nano size on the CPU, float32
on both sides; the parameter counts at the published widths; the share
of a mixer's heads; the counters; the sharding rules.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_olmo_hybrid as ref
from dlrover_wuqiong_tpu.models.gated_delta import (
    GatedDeltaConfig,
    GatedDeltaMixer,
)
from dlrover_wuqiong_tpu.models.olmo_hybrid import (
    OlmoHybrid,
    OlmoHybridConfig,
)
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 48  # three chunks of 16


def nano(**over):
    return OlmoHybridConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, use_flash_attention=False), **over})


def reference_loss(cfg, **control):
    return functools.partial(
        ref.loss, layer_types=cfg.layer_types, n_head=cfg.num_heads,
        linear_heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
        value_dim=cfg.linear_value_dim, eps=cfg.rms_eps, **control)


def with_opinions(params, seed):
    """Every leaf off its draw, so that no scale is 1 and no term is
    symmetric by accident."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape), params)


def batch_of(seed, rows=2):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ), 0, 256)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


@pytest.fixture(scope="module")
def both_sides():
    """(leaf names, the model's loss and gradient, the reference's), with
    and without recomputation, at nano size."""
    out = {}
    for remat in (False, True):
        cfg = nano(remat=remat)
        model = OlmoHybrid(cfg)
        params = with_opinions(model.init_params(jax.random.PRNGKey(1)), 2)
        batch = batch_of(3)
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(make_lm_loss(model.apply))(params, batch)
            want = jax.value_and_grad(reference_loss(cfg))(params, batch)
        names = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        out[remat] = (names, got, want)
    return out


# the leaves of the nano tree: 3 layers (16 + 11 + 16) + table, head, norm
N_LEAVES = 46


@pytest.mark.parametrize("remat", (False, True))
def test_the_loss_is_the_references(both_sides, remat):
    names, (loss, _), (ref_loss, _) = both_sides[remat]
    assert len(names) == N_LEAVES
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)


@pytest.mark.parametrize("leaf", range(N_LEAVES))
def test_every_leafs_gradient_is_the_references(both_sides, leaf):
    """Leaf by leaf (the norm over 800M entries that the chip compares
    would average a wrong leaf away)."""
    names, (_, grads), (_, ref_grads) = both_sides[True]
    got = jax.tree.leaves(grads)[leaf]
    want = jax.tree.leaves(ref_grads)[leaf]
    np.testing.assert_allclose(
        got, want, rtol=1e-3, atol=1e-4 * float(jnp.abs(want).max()),
        err_msg=names[leaf])


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_equation_is_another_loss(both_sides, wrong):
    """Each control the cell's file names moves the loss by far more than
    the two sides differ: the reference would tell it from the model."""
    cfg = nano()
    params = with_opinions(
        OlmoHybrid(cfg).init_params(jax.random.PRNGKey(1)), 2)
    with jax.default_matmul_precision("highest"):
        off = float(reference_loss(cfg, wrong=wrong)(params, batch_of(3)))
    right = float(both_sides[False][2][0])
    assert abs(off - right) > 1e-4 * right, (wrong, off, right)


def test_num_params_is_the_cells_count_and_the_published_models():
    """Shapes only: 795,736,986 at the cell's sizes (one period, fifteen
    of thirty linear heads, an eighth of the vocabulary) and
    7,430,870,688 uncut, by `num_params` and by the tree."""
    def tree_size(cfg):
        shapes = jax.eval_shape(OlmoHybrid(cfg).init_params,
                                jax.random.PRNGKey(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    whole = OlmoHybridConfig()
    assert whole.num_params() == tree_size(whole) == 7_430_870_688
    assert whole.linear_config().num_params() == 88_750_332
    cell = OlmoHybridConfig(
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        vocab_size=12544, linear_heads=15)
    assert cell.num_params() == tree_size(cell) == 795_736_986
    assert cell.linear_config().num_params() == 44_375_262
    llama = cell.attention_config()
    assert llama.attention_params() == 58_990_080
    assert llama.ffn_params() == 126_812_160
    held_all = OlmoHybridConfig(layer_types=cell.layer_types,
                                vocab_size=12544)
    assert held_all.num_params() == 928_862_196  # 14.86 GB: over the rung


def test_num_params_is_the_tree_at_nano_size():
    cfg = nano()
    params = OlmoHybrid(cfg).init_params(jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))


def test_a_layer_kind_the_stack_does_not_have_is_refused():
    cfg = nano(layer_types=("linear_attention", "mamba"))
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybrid(cfg).init_params(jax.random.PRNGKey(0))


# ---------------------------------------------------------------- the share

def _heads(params, cfg, lo, hi):
    """The leaves of a mixer that holds heads lo..hi-1 of `params`'."""
    dk, dv, n = cfg.key_dim, cfg.value_dim, cfg.num_heads
    cut_k = slice(lo * dk, hi * dk)
    cut_v = slice(lo * dv, hi * dv)
    conv = params["conv_kernel"]
    return {
        "q_proj": {"kernel": params["q_proj"]["kernel"][:, cut_k]},
        "k_proj": {"kernel": params["k_proj"]["kernel"][:, cut_k]},
        "v_proj": {"kernel": params["v_proj"]["kernel"][:, cut_v]},
        "g_proj": {"kernel": params["g_proj"]["kernel"][:, cut_v]},
        "a_proj": {"kernel": params["a_proj"]["kernel"][:, lo:hi]},
        "b_proj": {"kernel": params["b_proj"]["kernel"][:, lo:hi]},
        "o_proj": {"kernel": params["o_proj"]["kernel"][cut_v]},
        "conv_kernel": jnp.concatenate([
            conv[:, :n * dk][:, cut_k], conv[:, n * dk:2 * n * dk][:, cut_k],
            conv[:, 2 * n * dk:][:, cut_v]], axis=1),
        "A_log": params["A_log"][lo:hi], "dt_bias": params["dt_bias"][lo:hi],
        "gate_norm_scale": params["gate_norm_scale"]}


def test_the_shares_of_a_mixers_heads_add_up_to_the_mixer():
    """The share test: the outputs of a mixer that holds heads 0-1 and of
    one that holds heads 2-3 add up to the four-head mixer's, before the
    block's norm — the state, both L2 norms, both gates and the output
    norm are per head, the convolution per channel, and `Wo`'s partial
    sums add.  And the reference's mixer on a share is the model's."""
    cfg = GatedDeltaConfig(hidden_size=40, num_heads=4, key_dim=6,
                           value_dim=10, chunk_size=16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 40))
    whole = GatedDeltaMixer(cfg)
    params = with_opinions(
        whole.init(jax.random.PRNGKey(1), x)["params"], 5)
    half = GatedDeltaMixer(GatedDeltaConfig(
        **{**cfg.__dict__, "num_heads": 2}))
    with jax.default_matmul_precision("highest"):
        want = whole.apply({"params": params}, x)
        parts = [half.apply({"params": _heads(params, cfg, lo, lo + 2)}, x)
                 for lo in (0, 2)]
        np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-4,
                                   atol=1e-5)
        assert float(jnp.abs(parts[1]).max()) > 0.1 * float(
            jnp.abs(want).max())  # neither share is nothing
        held = ref.linear_attention(
            x, _heads(params, cfg, 2, 4), heads=2, key_dim=6, value_dim=10,
            eps=cfg.eps)
        np.testing.assert_allclose(parts[1], held, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ the counters

def test_the_mixers_counters_ride_the_steps_metrics():
    """`make_lm_loss.with_stats` hands out the lanes of the plan and the
    means of the two gates over heads, tokens and layers; a model without
    a linear layer hands out none."""
    cfg = nano()
    model = OlmoHybrid(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    batch = batch_of(4)
    _, stats = make_lm_loss(model.apply).with_stats(params, batch)
    lanes = 2 * (cfg.linear_key_dim + cfg.linear_value_dim)  # two layers
    assert float(stats["delta_lanes_run"]) == \
        float(stats["delta_lanes_model"]) == lanes
    assert 0.0 < float(stats["delta_alpha_mean"]) < 1.0
    assert 0.0 < float(stats["delta_beta_mean"]) < 2.0

    # the means are the gates': recomputed here from the first layer's
    # leaves on the embedded tokens
    one = nano(layer_types=("linear_attention",))
    model = OlmoHybrid(one)
    params = with_opinions(model.init_params(jax.random.PRNGKey(1)), 7)
    _, stats = make_lm_loss(model.apply).with_stats(params, batch)
    x = params["embed_tokens"]["embedding"][batch["input_ids"]]
    p = params["layers_0"]["linear_attention"]
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        x @ p["a_proj"]["kernel"] + p["dt_bias"]))
    beta = 2 * jax.nn.sigmoid(x @ p["b_proj"]["kernel"])
    np.testing.assert_allclose(stats["delta_alpha_mean"], alpha.mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(stats["delta_beta_mean"], beta.mean(),
                               rtol=1e-5)

    only_attention = nano(layer_types=("full_attention",))
    model = OlmoHybrid(only_attention)
    _, stats = make_lm_loss(model.apply).with_stats(
        model.init_params(jax.random.PRNGKey(1)), batch)
    assert not any(k.startswith("delta_") for k in stats)


# ----------------------------------------------------------- the sharding

def test_sharding_rules_name_every_parameter():
    """Every leaf of the tree is matched by a rule of its own kind (none
    falls through to `spec_for_path`'s replicated default by accident)."""
    from jax.sharding import PartitionSpec as P

    from dlrover_wuqiong_tpu.parallel.sharding import (
        TRANSFORMER_RULES,
        path_of,
        spec_for_path,
    )

    params = OlmoHybrid(nano()).init_params(jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for path in paths:
        assert any(re.match(pat, path, re.IGNORECASE)
                   for pat, _ in TRANSFORMER_RULES), path
    la = "layers_0/linear_attention"
    want = {
        "embed_tokens/embedding": P("tp", "fsdp"),
        f"{la}/q_proj/kernel": P("fsdp", "tp"),
        f"{la}/k_proj/kernel": P("fsdp", "tp"),
        f"{la}/v_proj/kernel": P("fsdp", "tp"),
        f"{la}/g_proj/kernel": P("fsdp", "tp"),
        f"{la}/a_proj/kernel": P("fsdp", None),
        f"{la}/b_proj/kernel": P("fsdp", None),
        f"{la}/o_proj/kernel": P("tp", "fsdp"),
        f"{la}/conv_kernel": P(), f"{la}/A_log": P(), f"{la}/dt_bias": P(),
        f"{la}/gate_norm_scale": P(),
        "layers_0/post_mixer_norm/scale": P(),
        "layers_0/post_feedforward_norm/scale": P(),
        "layers_0/feed_forward/gate_proj/kernel": P("fsdp", "tp"),
        "layers_0/feed_forward/down_proj/kernel": P("tp", "fsdp"),
        "layers_1/attention/q_proj/kernel": P("fsdp", "tp"),
        "layers_1/attention/q_norm/scale": P(),
        "layers_1/attention/o_proj/kernel": P("tp", "fsdp"),
        "lm_head/kernel": P("fsdp", "tp"), "norm/scale": P()}
    assert set(want) <= set(paths)
    for path, spec in want.items():
        assert spec_for_path(path, TRANSFORMER_RULES) == spec, path


def test_the_model_is_handed_its_mesh_and_steps_on_two_devices():
    """`auto_accelerate` hands the config its mesh as it does the other
    hybrids' and the chunked form is partitioned by GSPMD: an optimizer
    step under `fsdp` on two devices, every block rematerialised."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    res = auto_accelerate(OlmoHybrid(nano(remat=True)),
                          strategy=[("fsdp", {})], devices=jax.devices()[:2],
                          optimizer=optax.adamw(1e-3), seq_len=SEQ)
    assert res.model.config.mesh is res.mesh
    batch = {k: np.asarray(v) for k, v in batch_of(5, rows=4).items()}
    state, metrics = res.train_step(res.state, res.place_batch(batch))
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1
    assert float(metrics["delta_lanes_run"]) > 0


# ------------------------------------------- the convolution, lifted (PR 47)

def test_the_lifted_convolution_left_the_hybrids_parameter_trees_alone():
    """`models/mamba2.causal_conv_silu` is the convolution the Mamba-2
    mixer always ran, under the scope it always had: the two hybrids'
    parameter trees — names and shapes, what checkpoints,
    `parallel/sharding.py`'s rules and the scopes files bind to — are
    what they were."""
    from dlrover_wuqiong_tpu.models.granite_hybrid import (
        GraniteHybrid, GraniteHybridConfig)
    from dlrover_wuqiong_tpu.models.mamba2 import (
        Mamba2Config, Mamba2Mixer, causal_conv_silu)
    from dlrover_wuqiong_tpu.models.nemotron_h import (
        NemotronH, NemotronHConfig)

    def tree(model):
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        return {jax.tree_util.keystr(p): tuple(s.shape) for p, s in
                jax.tree_util.tree_flatten_with_path(shapes)[0]}

    granite = tree(GraniteHybrid(GraniteHybridConfig.nano()))
    mamba = {k.split("['mamba']")[1]: v for k, v in granite.items()
             if k.startswith("['layers_0']['mamba']")}
    assert mamba == {
        "['A_log']": (8,), "['D']": (8,), "['conv_bias']": (160,),
        "['conv_kernel']": (4, 160), "['dt_bias']": (8,),
        "['gate_norm_scale']": (128,), "['in_proj']['kernel']": (64, 296),
        "['out_proj']['kernel']": (128, 64)}
    nemotron = tree(NemotronH(NemotronHConfig.nano()))
    mixers = {k for k in nemotron if "conv_kernel" in k or "conv_bias" in k}
    assert mixers and all("['mixer']" in k or "['mamba']" in k
                          for k in mixers)

    # the same numbers as the lines it replaced, under the scope `conv`
    cfg = Mamba2Config(hidden_size=32, num_heads=4, head_dim=8, n_groups=1,
                       state_size=8, chunk_size=16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.conv_dim))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.conv_dim))
    bias = jax.random.normal(jax.random.PRNGKey(2), (cfg.conv_dim,))
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(sum(padded[:, j:j + 16] * kernel[j]
                           for j in range(4)) + bias)
    np.testing.assert_array_equal(
        causal_conv_silu(x, kernel, bias, jnp.float32), want)
    u = jnp.zeros((1, 16, 32))
    mixer = Mamba2Mixer(cfg)
    text = jax.jit(mixer.apply).lower(
        {"params": mixer.init(jax.random.PRNGKey(0), u)["params"]},
        u).as_text(debug_info=True)
    assert "Mamba2Mixer/conv/" in text


# ------------------- what PR 66's extensions left as it was (sha256, PR 65)

def _mixer_text(dtype):
    cfg = GatedDeltaConfig(hidden_size=48, num_heads=3, key_dim=8,
                           value_dim=24, chunk_size=16, dtype=dtype)
    mixer = GatedDeltaMixer(cfg)
    u = jax.ShapeDtypeStruct((2, 64, 48), jnp.float32)
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(1), jnp.zeros(u.shape))["params"])

    def loss(p, u):
        out, sown = mixer.apply({"params": p}, u, mutable=["intermediates"])
        return out.astype(jnp.float32).sum() \
            + sown["intermediates"]["delta_stats"][0].sum()

    return jax.jit(jax.value_and_grad(loss)).lower(params, u).as_text()


def _shared_expert_text():
    from dlrover_wuqiong_tpu.models import moe

    layer = moe.MoEMLP(24, 16, moe.MoEConfig(
        num_experts=8, top_k=2, impl="grouped", dtype=jnp.float32,
        aux_loss="none", shared_width=16))
    x = jax.ShapeDtypeStruct((2, 16, 24), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(1), jnp.zeros(x.shape))["params"])
    return jax.jit(jax.value_and_grad(
        lambda p, x: layer.apply({"params": p}, x).sum())).lower(
            params, x).as_text()


def _norm_text():
    from dlrover_wuqiong_tpu.models.llama import RMSNorm

    norm = RMSNorm(1e-6, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((3, 16), jnp.float32)
    params = jax.eval_shape(lambda: norm.init(jax.random.PRNGKey(1),
                                              jnp.zeros(x.shape)))
    return jax.jit(norm.apply).lower(params, x).as_text()


@pytest.mark.parametrize("text,sha", [
    (functools.partial(_mixer_text, jnp.float32),
     "405ce1e4fc078b09663d2e4b89c5d1d878c1ea6745761c7af8580c5f2c3d5804"),
    (functools.partial(_mixer_text, jnp.bfloat16),
     "0fff0fdb20933d197685fbb65efe481d06d87a4e31db0d2c1d3165aa684ed65a"),
    (_shared_expert_text,
     "90e1d46db83060a8156014e47d8cffe33f966b320c5d3baa77b935325ad1f3a6"),
    (_norm_text,
     "0817bbb873205170c72ffdd97176cd411d06937ea69ad0de4e7863214138d833"),
], ids=["mixer_f32", "mixer_bf16", "ungated_shared_expert", "plain_norm"])
def test_what_the_grouped_heads_and_gates_extend_lowers_to_the_parents_text(
        text, sha):
    """`GatedDeltaMixer` at equal heads (value and gradient, its five
    counters), an expert layer whose shared expert is ungated, and the
    plain `RMSNorm` lower on the CPU to the text they lowered to before
    `num_key_heads`, `neg_eigval`, `shared_gate` and `zero_centred`
    were there (the sha256 of that text, taken from the parent commit's
    checkout, PR 65's tree): bit for bit what they were."""
    import hashlib

    assert hashlib.sha256(text().encode()).hexdigest() == sha
