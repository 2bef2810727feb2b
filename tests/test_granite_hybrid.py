"""`granitemoehybrid` without experts through `models/granite_hybrid.py`:
the two-sublayer block, the four multipliers, the softmax's explicit
scale on all three attention routes, the tied head under a vocabulary
slice and the chunked scan at one group — each against the plain
reference (`benchmark/reference_granite_hybrid.py`) at a nano size on the
CPU, float32 on both sides.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_granite_hybrid as ref
from benchmark import reference_nemotron_h as ref_mamba
from benchmark.reference import loss_and_grad_norm
from dlrover_wuqiong_tpu.models import attention as dispatch
from dlrover_wuqiong_tpu.models.granite_hybrid import (
    GraniteHybrid,
    GraniteHybridConfig,
)
from dlrover_wuqiong_tpu.models.llama import LlamaAttention, LlamaConfig
from dlrover_wuqiong_tpu.ops import mosaic
from dlrover_wuqiong_tpu.ops.ssd import ssd_scan
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 64  # four chunks of 16


def nano(**over):
    return GraniteHybridConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, use_flash_attention=False), **over})


def reference_loss(cfg):
    return functools.partial(
        ref.loss, layer_types=cfg.layer_types, n_head=cfg.num_heads,
        n_kv_head=cfg.num_kv_heads, mamba_heads=cfg.mamba_heads,
        mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
        state=cfg.state_size,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, eps=cfg.rms_eps)


def with_opinions(params, seed):
    """Scales and skip terms off 1, and queries loud enough that the
    softmax's scale shows at a fresh draw."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def bump(path, a):
        if path[-1].key in ("scale", "gate_norm_scale", "D"):
            return a * (1 + 0.3 * jax.random.normal(next(keys), a.shape))
        if len(path) > 1 and path[-2].key == "q_proj":
            return a * 4.0
        return a
    return jax.tree_util.tree_map_with_path(bump, params)


def seeded(cfg, seed=3, batch=3):
    model = GraniteHybrid(cfg)
    params = with_opinions(model.init_params(jax.random.PRNGKey(seed)),
                           seed + 100)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, SEQ + 1),
                             0, cfg.vocab_size)
    return model, params, {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- the whole model

@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    cfg = nano(remat=remat)
    assert set(cfg.layer_types) == {"mamba", "attention"}
    model, params, batch = seeded(cfg)
    with jax.default_matmul_precision("highest"):
        sys_loss, sys_grads = jax.jit(jax.value_and_grad(
            make_lm_loss(model.apply)))(params, batch)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            reference_loss(cfg)))(params, batch)
    assert abs(float(sys_loss) - float(ref_loss)) / float(ref_loss) < 1e-5
    want = _leaves(ref_grads)
    assert set(want) == set(_leaves(sys_grads)) == set(_leaves(params))
    for path, got in _leaves(sys_grads).items():
        top = float(jnp.abs(want[path]).max())
        assert top > 0, path
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[path]), rtol=1e-4,
            atol=2e-5 * top, err_msg=path)


WRONG = {
    "residual_multiplier_dropped": dict(residual_multiplier=1.0),
    "softmax_scaled_by_rsqrt_d": dict(attention_multiplier=0.25),
    "logits_scaling_dropped": dict(logits_scaling=1.0),
    "embedding_multiplier_dropped": dict(embedding_multiplier=1.0),
    "skip_term_dropped": {},
}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_equation_is_outside_the_tolerance(wrong, monkeypatch):
    """Each of the five the cell's check is held to on the chip (PERF.md
    section 6, PR 33) moves the loss or the gradient's norm by more than
    float32's agreement (1e-5, 1e-4) at nano size as well."""
    from dlrover_wuqiong_tpu.models import mamba2

    cfg = nano()
    assert cfg.hidden_size // cfg.num_heads == 16  # 1/sqrt(16) = 0.25
    _, params, batch = seeded(cfg)
    if wrong == "skip_term_dropped":
        monkeypatch.setattr(
            mamba2, "ssd_scan",
            lambda x, dlt, a, b, c, d, f=mamba2.ssd_scan, **kw:
            f(x, dlt, a, b, c, 0 * d, **kw))
    model = GraniteHybrid(dataclasses.replace(cfg, **WRONG[wrong]))
    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(reference_loss(cfg), params,
                                            batch, precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss > 1e-5 or \
        abs(sys_norm - ref_norm) / ref_norm > 1e-4


def test_the_residual_multiplier_is_not_rounded_to_the_compute_dtype():
    """0.22 is no bfloat16 (0.2197265625): the branch is scaled and added
    in float32 and rounded once, where `x + 0.22 * branch` on bfloat16
    arrays would scale by 0.2197, 0.12% low in every one of the twenty
    branches."""
    from dlrover_wuqiong_tpu.models.granite_hybrid import _scaled

    assert float(jnp.bfloat16(0.22)) == 0.2197265625
    k = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k[0], (64, 256), jnp.bfloat16)
    branch = 8 * jax.random.normal(k[1], (64, 256), jnp.bfloat16)
    got = _scaled(branch, 0.22, plus=x)
    want = (x.astype(jnp.float32) + 0.22 * branch.astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(jnp.bfloat16),
                                             np.float32))
    # the scale a result really carries: its error regressed on the branch
    def scale_error(y):
        b = branch.astype(jnp.float32)
        return float(jnp.sum((y.astype(jnp.float32) - want) * b)
                     / jnp.sum(b * b))

    naive = x + 0.22 * branch
    assert scale_error(naive) < -1e-4  # towards 0.2197 - 0.22 = -2.7e-4
    assert abs(scale_error(got)) < 1e-5


def test_the_tied_table_takes_the_lookups_gradient_and_the_heads():
    """One leaf, two uses.  The head's part of the table's gradient is
    (softmax - onehot)^T norm(x) / logits_scaling over EVERY row of the
    vocabulary; the lookup's part, the rest, lands on the rows the input
    holds and on no other."""
    cfg = nano()
    model, params, batch = seeded(cfg)
    assert "lm_head" not in params
    seen = np.zeros(cfg.vocab_size, bool)
    seen[np.asarray(batch["input_ids"]).ravel()] = True
    assert seen.any() and not seen.all()
    grad = jax.grad(make_lm_loss(model.apply))(params, batch)[
        "embed_tokens"]["embedding"]
    logits, found = model.apply(
        {"params": params}, batch["input_ids"], mutable=["intermediates"],
        capture_intermediates=lambda m, _: m.name == "norm")
    hidden = found["intermediates"]["norm"]["__call__"][0]
    onehot = jax.nn.one_hot(batch["labels"], cfg.vocab_size)
    d_logits = (jax.nn.softmax(logits, -1) - onehot) / batch["labels"].size
    head = jnp.einsum("btv,bte->ve", d_logits, hidden) / cfg.logits_scaling
    lookup = np.asarray(grad - head)
    top = float(jnp.abs(grad).max())
    assert float(jnp.abs(head).min(axis=-1).max()) > 0
    assert (np.abs(head).max(-1) > 1e-3 * top).all()  # every row
    assert np.abs(lookup[~seen]).max() < 1e-5 * top
    assert (np.abs(lookup[seen]).max(-1) > 1e-3 * top).all()


# ------------------------------------------------- the parameter count

def test_num_params_is_the_cells_count_at_the_published_widths():
    from benchmark import cells

    cell = cells.load_cell("granite4_h_micro.steady")
    model = cells.load_module("models", "granite_hybrid").build(
        cell["config"])
    cfg = model.config
    h = cfg.hidden_size
    assert cfg.mamba_config().num_params() == 25_847_232
    assert cfg.attention_config().ffn_params() == 50_331_648
    assert cfg.attention_config().attention_params() == 10_485_760
    assert 25_847_232 + 50_331_648 + 2 * h == 76_182_976
    assert 10_485_760 + 50_331_648 + 2 * h == 60_821_504
    assert 9 * 76_182_976 + 60_821_504 == 746_468_288
    assert cfg.vocab_size * h + h == 25_692_160
    assert cfg.num_params() == 772_160_448
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 772_160_448
    mixer = shapes["layers_0"]["mamba"]
    assert mixer["in_proj"]["kernel"].shape == (2048, 8512)
    assert mixer["conv_kernel"].shape == (4, 4352)
    assert shapes["layers_5"]["attention"]["k_proj"]["kernel"].shape == \
        (2048, 512)
    assert shapes["embed_tokens"]["embedding"].shape == (12544, 2048)
    whole = dataclasses.replace(
        cfg, vocab_size=100352,
        layer_types=GraniteHybridConfig().layer_types)
    assert whole == dataclasses.replace(GraniteHybridConfig(),
                                        **{f: getattr(cfg, f) for f in (
                                            "dtype", "remat", "remat_policy",
                                            "use_flash_attention",
                                            "chunk_size")})
    assert whole.layer_types.count("attention") == 4
    assert whole.num_params() == 3_191_396_096  # the catalog's 3B


def test_num_params_is_the_tree_at_nano_size():
    cfg = nano()
    params = GraniteHybrid(cfg).init_params(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()


def test_a_layer_kind_the_stack_does_not_have_is_refused():
    cfg = nano(layer_types=("mamba", "moe"))
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybrid(cfg).init_params(jax.random.PRNGKey(0))


# ------------------------------------------------- sharding

def test_sharding_rules_name_every_parameter():
    """Every leaf of the tree is matched by a rule of its own kind (none
    falls through to `spec_for_path`'s replicated default by accident):
    the tied table by the embedding's rule, as the head's operand too."""
    from jax.sharding import PartitionSpec as P

    from dlrover_wuqiong_tpu.parallel.sharding import (
        TRANSFORMER_RULES,
        path_of,
        spec_for_path,
    )

    params = GraniteHybrid(nano()).init_params(jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for path in paths:
        assert any(re.match(pat, path, re.IGNORECASE)
                   for pat, _ in TRANSFORMER_RULES), path
    want = {
        "embed_tokens/embedding": P("tp", "fsdp"),
        "layers_0/mamba/in_proj/kernel": P("fsdp", "tp"),
        "layers_0/mamba/out_proj/kernel": P("tp", "fsdp"),
        "layers_0/mamba/conv_kernel": P(), "layers_0/mamba/D": P(),
        "layers_0/mamba/gate_norm_scale": P(),
        "layers_0/input_norm/scale": P(),
        "layers_0/post_mixer_norm/scale": P(),
        "layers_0/feed_forward/gate_proj/kernel": P("fsdp", "tp"),
        "layers_0/feed_forward/up_proj/kernel": P("fsdp", "tp"),
        "layers_0/feed_forward/down_proj/kernel": P("tp", "fsdp"),
        "layers_1/attention/q_proj/kernel": P("fsdp", "tp"),
        "layers_1/attention/k_proj/kernel": P("fsdp", "tp"),
        "layers_1/attention/o_proj/kernel": P("tp", "fsdp"),
        "norm/scale": P()}
    assert set(want) <= set(paths)
    for path, spec in want.items():
        assert spec_for_path(path, TRANSFORMER_RULES) == spec, path


# ------------------------------------------------- the scan at one group

@pytest.fixture(scope="module")
def scan_at_one_group():
    """Values and the gradients of a scalar of y, chunk 256 and
    sequential, 4 heads on ONE B, C pair, two chunks."""
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    b, t, h, p, n = 1, 512, 4, 8, 16
    # step sizes as the initialiser draws them (softplus(dt_bias) in
    # [0.001, 0.1]) times A in [-16, -1]: a chunk of 256 decays by up to
    # exp(-400), so the masked exponent and the carry both work
    args = (jax.random.normal(k[0], (b, t, h, p)),
            jnp.exp(jax.random.uniform(k[1], (b, t, h), minval=np.log(1e-3),
                                       maxval=np.log(0.1))),
            -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0),
            jax.random.normal(k[3], (b, t, 1, n)),
            jax.random.normal(k[4], (b, t, 1, n)),
            jax.random.normal(k[5], (h,)))
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, fn in (("chunked", functools.partial(ssd_scan, chunk=256)),
                         ("sequential", ref_mamba.recurrence)):
            def scalar(*a, fn=fn):
                return jnp.sum(jnp.sin(fn(*a)))
            out[name] = (fn(*args), jax.grad(
                scalar, argnums=tuple(range(6)))(*args))
    return out


def test_the_scan_at_one_group_and_chunk_256_is_the_recurrence(
        scan_at_one_group):
    got, want = (scan_at_one_group[k][0] for k in ("chunked", "sequential"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("i", range(6), ids=("x", "dlt", "A", "B", "C", "D"))
def test_the_scans_gradient_at_one_group_is_the_recurrences(
        scan_at_one_group, i):
    got = scan_at_one_group["chunked"][1][i]
    want = scan_at_one_group["sequential"][1][i]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


# ------------------------------------------------- the softmax's scale

def _masked_softmax(q, k, v, scale):
    """(b, t, h, d) each, float32, written out."""
    t = q.shape[1]
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), v)


@pytest.fixture(scope="module")
def qkv():
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    return tuple(jax.random.normal(key, (2, 32, 4, 16)) for key in k)


@pytest.mark.parametrize("scale", [0.0, 0.015625, 0.5])
@pytest.mark.parametrize("route", ["attend", "attend_projected"])
def test_the_dispatch_hands_the_kernels_the_configs_scale(qkv, route, scale):
    """0 is 1/sqrt(head size), anything else the scale itself, on the
    cut-to-heads route and on the projections' layout (off the TPU both
    end in `mha`'s reference)."""
    cfg = LlamaConfig(attn_scale=scale)
    q, k, v = qkv
    if route == "attend":
        got = dispatch.attend(q, k, v, cfg)
    else:
        flat = tuple(a.reshape(2, 32, 64) for a in qkv)
        got = dispatch.attend_projected(flat, 4, cfg).reshape(q.shape)
    want = _masked_softmax(q, k, v, scale or 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    other = _masked_softmax(q, k, v, 0.1)
    assert float(jnp.abs(other - want).max()) > 1e-3


@pytest.mark.parametrize("flash", [False, True])
@pytest.mark.parametrize("scale", [0.0, 0.015625])
def test_llama_attention_scales_the_scores_by_attn_scale(flash, scale):
    """`LlamaAttention` end to end, the `jnp` fallback and the dispatch:
    the reference's masked softmax at the explicit scale."""
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=48, num_layers=1, num_heads=4,
        num_kv_heads=2, attn_head_dim=16, rope=False, dtype=jnp.float32,
        use_flash_attention=flash, attn_scale=scale, max_seq_len=32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 48))
    layer = LlamaAttention(cfg)
    params = layer.init(jax.random.PRNGKey(0), x, None, None)["params"]
    params["q_proj"]["kernel"] = params["q_proj"]["kernel"] * 4.0
    got = layer.apply({"params": params}, x, None, None)
    want = ref.attention(x, params, n_head=4, n_kv_head=2,
                         scale=scale or 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    if scale:
        by_rsqrt = ref.attention(x, params, n_head=4, n_kv_head=2,
                                 scale=0.25)
        assert float(jnp.abs(by_rsqrt - want).max()) > 1e-2


def test_the_kernel_entries_are_handed_none_at_the_default(monkeypatch):
    """At `attn_scale` = 0 every entry of `ops/flash_attention.py` is
    called as the parent called it (`sm_scale` None: those functions are
    untouched), so every model that does not set the field traces the
    program it traced; with the field set, the value arrives on the
    direct route, the transposed one and the shard_map's."""
    from dlrover_wuqiong_tpu.parallel import long_context

    seen = []

    windows = []

    def spy(name, result):
        def fn(*args, sm_scale="absent", **kw):
            seen.append((name, sm_scale))
            windows.append(kw.get("window"))
            return result(*args)
        return fn

    monkeypatch.setattr(dispatch, "mha", spy("mha", lambda q, k, v: q))
    monkeypatch.setattr(
        dispatch, "flash_attention_projected",
        lambda proj, n_head, causal, sm_scale="absent", window=None:
        seen.append(("projected", sm_scale)) or windows.append(window)
        or proj[0])
    monkeypatch.setattr(long_context, "sharded_flash_attention",
                        spy("sharded", lambda q, k, v, mesh: q))
    monkeypatch.setattr(long_context, "ring_attention",
                        spy("ring", lambda q, k, v, mesh: q))
    q = jnp.zeros((1, 1024, 2, 64))
    flat = (q.reshape(1, 1024, 128),) * 3

    class FourChips:
        size = 4

    for scale, want in ((0.0, None), (0.015625, 0.015625)):
        seen.clear()
        cfg = LlamaConfig(attn_scale=scale)
        dispatch.attend(q, q, q, cfg)
        with monkeypatch.context() as m:
            m.setattr(dispatch, "goes_direct", lambda *a: True)
            dispatch.attend_projected(flat, 2, cfg)
            m.setattr(mosaic, "on_tpu", lambda: True)
            dispatch.attend(q, q, q, dataclasses.replace(
                cfg, mesh=FourChips()))
            dispatch.attend(q, q, q, dataclasses.replace(
                cfg, mesh=FourChips(), attn_impl="ring"))
        assert seen == [("mha", want), ("projected", want),
                        ("sharded", want), ("ring", want)]
    # and no window where `attn_window` is 0 (PR 37's field, read beside)
    assert windows == [None] * 8
