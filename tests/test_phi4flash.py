"""`models/phi4flash.py` (a decoder-hybrid-decoder: Mamba-1 mixers,
differential attention under a window or none, one full layer whose keys
and values the cross-decoder reads, gated memory units on the last
scan's output) against its plain reference
(`benchmark/reference_phi4flash.py`) at nano size in float32: logits,
loss and every leaf's gradient; what is handed on beside x takes the
SUMMED cotangents of its readers; each wrong equation the cell's check is
held to on the chip falls outside float32's agreement here too; the
parameter count; what is refused."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_phi4flash as ref
from benchmark.reference import loss_and_grad_norm
from dlrover_wuqiong_tpu.models import phi4flash
from dlrover_wuqiong_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 40  # five windows of 8; no whole number of the scan's chunks


LAYERS = (0, 1, 6, 7, 8, 9, 10, 11)


def nano(**over):
    """Eight of 12 published layers: 0-1 a Mamba / window pair of the
    self-decoder, 6 hands on m, 7 hands on (K, V), 8-11 TWO GMU / cross
    pairs: two readers of each."""
    return Phi4FlashConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, num_layers_published=12,
        layer_ids=LAYERS), **over})


def reference_loss(cfg, **over):
    return functools.partial(ref.loss, **{**dict(
        layer_ids=cfg.layers, n_published=cfg.num_layers_published,
        mb_per_layer=cfg.mb_per_layer, n_head=cfg.num_heads,
        n_kv_head=cfg.num_kv_heads, window=cfg.sliding_window,
        state=cfg.mamba_state_size, eps=cfg.norm_eps), **over})


def with_opinions(params, seed):
    """Scales, biases, skip terms and lambdas off their draw, so that no
    term is a no-op at a fresh draw."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))

    def bump(path, a):
        name = path[-1].key
        if name in ("scale", "subln_scale", "D"):
            return a * (1 + 0.3 * jax.random.normal(next(keys), a.shape))
        if name == "bias" or name.startswith("lambda_"):
            return a + 0.3 * jax.random.normal(next(keys), a.shape)
        return a
    return jax.tree_util.tree_map_with_path(bump, params)


def seeded(cfg, seed=3, batch=2):
    model = Phi4Flash(cfg)
    params = with_opinions(model.init_params(jax.random.PRNGKey(seed)),
                           seed + 100)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, SEQ + 1),
                             0, cfg.vocab_size)
    return model, params, {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- the whole model

def test_the_kinds_follow_the_published_index():
    cfg = Phi4FlashConfig()
    kinds = [cfg.kind(i) for i in range(32)]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert (cfg.memory_layer, cfg.kv_layer) == (16, 17)
    assert [round(cfg.lambda_init(i), 4) for i in (1, 17, 19)] == \
        [0.3555, 0.7963, 0.7980]


def test_logits_match_the_reference():
    cfg = nano()
    model, params, batch = seeded(cfg)
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, batch["input_ids"])
        want = ref.forward(params, batch["input_ids"],
                           **reference_loss(cfg).keywords)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    cfg = nano(remat=remat)
    assert {cfg.kind(i) for i in cfg.layers} == set(phi4flash.KINDS)
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
        # a lambda vector's gradient is ONE scalar's, d loss / d lam — a
        # sum over the layer's whole output that the sub-norm all but
        # cancels (the norm takes a common scale out again): float32
        # leaves it three digits
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want[path]),
            rtol=2e-3 if "lambda_" in path else 2e-4, atol=2e-5 * top,
            err_msg=path)


@functools.lru_cache(maxsize=None)
def _whole_gradient():
    cfg = nano(remat=True)
    model, params, batch = seeded(cfg)
    return cfg, params, batch, jax.grad(make_lm_loss(model.apply))(params,
                                                                   batch)


@pytest.mark.parametrize("reader", [9, 11, 8, 10])
def test_what_is_handed_on_takes_every_readers_cotangent(reader):
    """TWO cross layers read layer 7's (K, V) and two GMU layers layer
    6's m.  Without one reader (the cut that drops it) the gradient of
    the HANDING layer's own leaves — Wqkv's k and v columns, layer 6's
    scan leaves — changes: each reader's cotangent flows back, under
    remat too (the whole-model test holds the sum to the reference)."""
    cfg, params, batch, g_all = _whole_gradient()
    cut = dataclasses.replace(cfg, layer_ids=tuple(
        i for i in cfg.layers if i != reader))
    place = cfg.layers.index(reader)
    kept = {f"layers_{j - (j > place)}": params[f"layers_{j}"]
            for j in range(len(cfg.layers)) if j != place}
    cut_params = {**{k: v for k, v in params.items()
                     if not k.startswith("layers_")}, **kept}
    g_cut = jax.grad(make_lm_loss(Phi4Flash(cut).apply))(cut_params, batch)
    source = "attention" if reader % 2 else "mamba"
    at = cfg.layers.index(cfg.kv_layer if reader % 2 else cfg.memory_layer)
    whole, less = (_leaves(g[f"layers_{at}"][source])
                   for g in (g_all, g_cut))
    leaf = "['qkv_proj']['kernel']" if reader % 2 else "['A_log']"
    if reader % 2:  # the k and v columns of Wqkv
        cols = cfg.num_heads * cfg.head_dim
        whole, less = ({leaf: g[leaf][:, cols:]} for g in (whole, less))
    diff = float(jnp.abs(whole[leaf] - less[leaf]).max())
    assert diff > 1e-3 * float(jnp.abs(whole[leaf]).max())


@functools.lru_cache(maxsize=None)
def _two_scalars():
    """(cfg, params, batch, the system's (loss, gradient norm)), held to
    the reference's within float32's agreement once."""
    cfg = nano()
    model, params, batch = seeded(cfg)
    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch,
                                            precision="highest")
    good = loss_and_grad_norm(reference_loss(cfg), params, batch,
                              precision="highest")
    assert abs(sys_loss - good[0]) / good[0] < 1e-5
    assert abs(sys_norm - good[1]) / good[1] < 1e-4
    return cfg, params, batch, (sys_loss, sys_norm)


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_equation_is_outside_the_tolerance(wrong):
    """Each of the five the cell's check is held to on the chip (PERF.md
    section 6, PR 72) moves the loss or the gradient's norm by more than
    float32's agreement (1e-5, 1e-4) at nano size as well."""
    cfg, params, batch, (sys_loss, sys_norm) = _two_scalars()
    bad = loss_and_grad_norm(reference_loss(cfg, wrong=(wrong,)), params,
                             batch, precision="highest")
    assert abs(sys_loss - bad[0]) / bad[0] > 1e-3 or \
        abs(sys_norm - bad[1]) / bad[1] > 1e-3


def test_the_window_is_the_windowed_layers_alone():
    """A key further back than the window moves a `window` layer's
    output nowhere and a `full` layer's everywhere behind it."""
    cfg = nano()
    _, params, _ = seeded(cfg)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, cfg.hidden_size))
    moved = u.at[:, 0].add(1.0)
    for layer, sees in ((1, False), (7, True)):
        att = phi4flash.DiffAttention(cfg, layer)
        p = params[f"layers_{cfg.layers.index(layer)}"]["attention"]
        a, _ = att.apply({"params": p}, u)
        b, _ = att.apply({"params": p}, moved)
        far = float(jnp.abs(a[:, cfg.sliding_window:]
                            - b[:, cfg.sliding_window:]).max())
        assert (far > 1e-4) == sees, (layer, far)


# ------------------------------------------------- counts and counters

def test_num_params_is_the_cells_count_at_the_published_widths():
    cell = Phi4FlashConfig(vocab_size=25008,
                           layer_ids=(0, 1, 16, 17, 18, 19))
    assert cell.num_params() == 697_094_272
    assert Phi4FlashConfig().num_params() == 3_852_562_944
    assert cell.mixer_params("mamba") == 41_241_600
    assert cell.mixer_params("full") == cell.mixer_params("window") \
        == 19_668_864
    assert cell.mixer_params("gmu") == 26_214_400
    assert cell.mixer_params("cross") == 13_112_704
    shapes = jax.eval_shape(Phi4Flash(cell).init_params,
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in
               jax.tree.leaves(shapes)) == 697_094_272


def test_num_params_is_the_tree_at_nano_size():
    model = Phi4Flash(nano())
    params = model.init_params(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        model.config.num_params()


def test_the_step_carries_the_layers_counters():
    from dlrover_wuqiong_tpu.models.sown import collect

    cfg = nano()
    model, params, batch = seeded(cfg)
    _, state = model.apply({"params": params}, batch["input_ids"],
                           mutable=["intermediates"])
    _, aux = collect(state["intermediates"], batch,
                     jnp.zeros((), jnp.float32))
    lam0 = [cfg.lambda_init(i) for i in cfg.layers if i % 2]
    assert min(lam0) - 1.5 < float(aux["attn_diff_lambda_mean"]) \
        < max(lam0) + 1.5
    assert -0.3 < float(aux["gmu_gate_mean"]) < 0.6
    # the windowed layer: no more tiles than its causal call would run
    assert float(aux["attn_tiles_window"]) <= float(aux["attn_tiles_causal"])
    assert float(aux["attn_lanes_run"]) == float(aux["attn_lanes_model"]) \
        == 4 * 3 * cfg.head_dim  # four attention layers


# ------------------------------------------------- what is refused

def test_a_reader_without_its_source_is_refused():
    for ids in ((0, 1, 7, 8), (0, 1, 6, 9)):
        cfg = nano(layer_ids=ids)
        with pytest.raises(ValueError, match="hands|handed"):
            Phi4Flash(cfg).init_params(jax.random.PRNGKey(0))


def test_a_mesh_of_several_devices_is_refused():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    with pytest.raises(ValueError, match="one device"):
        Phi4Flash(nano(mesh=mesh)).init_params(jax.random.PRNGKey(0))


def test_a_pipeline_split_is_refused_by_name():
    """A stage boundary behind the memory layer would have to carry x,
    m, K and V: `parallel/pipeline.py` refuses the model, naming them."""
    from dlrover_wuqiong_tpu.parallel.pipeline import PipelinedLM

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match=r"m, k, v"):
        PipelinedLM(Phi4Flash(nano()), mesh, num_microbatches=2)
