"""`SDAR` through `models/sdar.py`: block-diffusion training of a
Qwen3-MoE trunk — a clean and a noised copy of every sequence under a
static block mask (`ops/block_attention.py`), the per-head QK norm,
position ids a copy, softmax top-k experts without a shared one, an
untied head on the noised copy alone, a loss on the masked positions
weighed by 1 / t through `models/sown.objective` — against the plain
reference (`benchmark/reference_sdar_moe.py`) at a nano size on the CPU,
float32 on both sides: the two draws bit for bit, the loss and every
leaf's gradient at three block lengths, with the kernels interpreted;
the controls; the objective's registration; the counters; the parameter
counts at the published widths; the share test; the sharding rules; what
is refused."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sdar_moe as ref
from dlrover_wuqiong_tpu.models import moe, sdar, sown
from dlrover_wuqiong_tpu.models.gpt import (
    cross_entropy_loss,
    weighted_cross_entropy,
)
from dlrover_wuqiong_tpu.models.sdar import SDAR, SDARConfig
from dlrover_wuqiong_tpu.ops import block_attention as ba
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 48


def nano(**over):
    """Two layers, blocks of 4, experts 4-7 of 16 held."""
    return SDARConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, experts_held=4, first_expert=4,
        noise_seed=11), **over})


def reference_loss(cfg, **control):
    return functools.partial(
        ref.loss, n_layer=cfg.num_layers, n_head=cfg.num_heads,
        n_kv=cfg.num_kv_heads, theta=cfg.rope_theta, top_k=cfg.top_k,
        first_expert=cfg.first_expert, eps=cfg.rms_eps,
        noise_seed=cfg.noise_seed, block_length=cfg.block_length,
        noise_eps=cfg.noise_eps, mask_id=cfg.mask_id,
        aux_weight=cfg.router_aux_loss_weight, **control)


def with_opinions(params, seed, scale=0.1):
    """Every leaf off its draw, so that no scale is 1 and no term is
    symmetric by accident."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(next(keys), a.shape), params)


def batch_of(seed, rows=2, seq=SEQ):
    """Ids below MASK (255), as the traffic's lie below the slice's last
    row; labels as the harness hands them, which the objective ignores."""
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, 255)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


def _params(cfg):
    return with_opinions(
        jax.jit(SDAR(cfg).init_params)(jax.random.PRNGKey(1)), 2)


def _sides(cfg, batch):
    model, params = SDAR(cfg), _params(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            make_lm_loss(model.apply).with_stats, has_aux=True))(
                params, batch)
        want, ref_grads = jax.jit(jax.value_and_grad(
            reference_loss(cfg)))(params, batch)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    return names, (loss, grads), (want, ref_grads), params, stats


# attention 4 products + 2 head norms, 2 block norms, router and 3 expert
# stacks, a layer; table, final norm, head
N_LEAVES = 2 * (6 + 2 + 4) + 3


def _all_of_it_is_the_references(sides):
    names, (loss, grads), (want, ref_grads), *_ = sides
    assert len(names) == N_LEAVES
    assert abs(float(loss) - float(want)) < 3e-6 * abs(float(want))
    for name, got, exp in zip(names, jax.tree.leaves(grads),
                              jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(exp).max()) > 0, name
        np.testing.assert_allclose(
            got, exp, rtol=1e-3, atol=1e-4 * float(jnp.abs(exp).max()),
            err_msg=name)


# ------------------------------------------------- model against reference

@pytest.fixture(scope="module")
def both_sides():
    """Blocks of 4 over 48 tokens, every block recomputed, the balance
    term on (the cell's assumption)."""
    return _sides(nano(remat=True, router_aux_loss_weight=0.01),
                  batch_of(3))


@pytest.mark.parametrize("length,seq", [(8, 48), (2, 16), (16, 32)])
def test_at_other_block_lengths_all_of_it_is_the_references(length, seq):
    _all_of_it_is_the_references(_sides(nano(block_length=length),
                                        batch_of(4, seq=seq)))


def test_loss_and_every_leafs_gradient_are_the_references(both_sides):
    """Leaf by leaf (the norm over 646M entries that the chip compares
    would average a wrong leaf away; a failure names its leaf)."""
    _all_of_it_is_the_references(both_sides)
    *_, stats = both_sides
    # the weighted loss and the balance term, 0.01 x a number near top_k
    assert 0.0 < float(stats["diffusion_masked_share"]) < 1.0
    assert float(stats["moe_load_max_over_mean"]) >= 1.0


def test_each_control_is_another_number(both_sides):
    """Every equation the reference can get wrong moves the loss by far
    more than the two sides differ: the reference would tell each from
    the model."""
    _, (loss, _), _, params, _ = both_sides
    cfg = nano(router_aux_loss_weight=0.01)
    for wrong in ref.WRONG:
        with jax.default_matmul_precision("highest"):
            off = jax.jit(reference_loss(cfg, wrong=wrong))(
                params, batch_of(3))
        assert abs(float(off) - float(loss)) > 1e-3 * float(loss), wrong
    with pytest.raises(ValueError, match="one of"):
        reference_loss(cfg, wrong="shift")(params, batch_of(3))


@pytest.fixture
def kernels_interpreted(on_tpu, monkeypatch):
    """A layer's attention as one TPU device runs it — `dwt_fa_bd_*` and
    the rotation's `dwt_rope` beside them — interpreted: the forward at
    blocks of 16 cut into tiles of 8, the backward at blocks of 8."""
    from dlrover_wuqiong_tpu.ops import rope

    monkeypatch.setattr(rope, "_rope_kernels", functools.partial(
        rope._rope_kernels, interpret=True))
    monkeypatch.setattr(ba, "_STEPS", {"forward": (16, 2),
                                       "backward": (8, 2)})
    monkeypatch.setattr(ba, "TILE", 8)
    monkeypatch.setattr(ba, "_forward_jit", functools.partial(
        ba._forward, interpret=True))
    monkeypatch.setattr(ba, "_backward_jit", functools.partial(
        ba._backward, interpret=True))


def test_a_layer_through_the_kernels_is_the_layer_through_the_plain_lines(
        kernels_interpreted, monkeypatch):
    """Heads of 128 (a slab each, two to a kv head), a copy of three
    blocks: the layer takes `dwt_fa_bd_*`, its output and every gradient
    are the plain route's (the kernels' oracle, which the reference holds
    above), and the counters say which route ran."""
    from dlrover_wuqiong_tpu.models.llama import LlamaAttention, rope_freqs
    from dlrover_wuqiong_tpu.ops import mosaic

    cfg = nano(head_dim=128, num_heads=2, num_kv_heads=1).attention_config()
    assert ba.bd_route(SEQ, 4, 2, 1, 128) == "kernel"
    layer = LlamaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 2 * SEQ, 64))
    cos, sin = (jnp.concatenate([a, a]) for a in rope_freqs(128, SEQ, 1e6))
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def route():  # a new function a route: a trace is kept by identity
        def run(params, x):
            y, sowed = layer.apply({"params": params}, x, cos, sin,
                                   mutable=["intermediates"])
            return (y * w).sum(), sowed["intermediates"]["attn_bd"][0]
        return run

    with jax.default_matmul_precision("highest"):
        params = with_opinions(layer.init(
            jax.random.PRNGKey(1), x, cos, sin)["params"], 3)
        assert "dwt_fa_bd_fwd" in str(jax.make_jaxpr(route())(params, x))
        (got, counted), grads = jax.value_and_grad(
            route(), argnums=(0, 1), has_aux=True)(params, x)
        monkeypatch.setattr(mosaic, "on_tpu", lambda: False)
        assert "dwt_" not in str(jax.make_jaxpr(route())(params, x))
        (want, dense), ref_grads = jax.value_and_grad(
            route(), argnums=(0, 1), has_aux=True)(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(b).max()))
    run_, live, kept, computed = ba.bd_tile_count(SEQ, 4, "kernel")
    assert run_ == live  # no dead tile is walked
    heads_rows = 2 * 2  # heads x sequences
    assert [float(c) for c in counted] == [
        heads_rows * run_, heads_rows * live,
        heads_rows * (SEQ * SEQ + SEQ * 4), heads_rows * computed]
    assert float(dense[0]) == heads_rows * (2 * SEQ // 8) ** 2 \
        > float(counted[0])


# ----------------------------------------------------------------- the draw

def test_the_two_draws_are_one_bit_for_bit():
    """The program's vmapped draw and the reference's own lines, each
    COMPILED, as the step and the harness's check compile them (op by op
    the product and the sum of t = eps + (1 - eps) u round apart, where a
    compiler may contract them to one rounding: t's last bit)."""
    ids = batch_of(6, rows=5, seq=64)["input_ids"]
    for seed, length in ((0, 4), (20251006, 8), (7, 16)):
        t, m = jax.jit(sdar.draw_noise, static_argnums=(1, 2, 3))(
            ids, seed, length, 1e-3)
        t_ref, m_ref = jax.jit(ref.draw, static_argnums=(1, 2, 3))(
            ids, seed, length, 1e-3)
        # and uncompiled they are one too, and the same masks
        t_op, m_op = sdar.draw_noise(ids, seed, length, 1e-3)
        t_ref_op, m_ref_op = ref.draw(ids, seed, length, 1e-3)
        assert np.array_equal(np.asarray(t_op), np.asarray(t_ref_op))
        assert np.array_equal(np.asarray(m_op), np.asarray(m_ref_op))
        assert np.array_equal(np.asarray(m_op), np.asarray(m))
        assert t.shape == (5, 64 // length) and m.shape == (5, 64)
        assert np.array_equal(np.asarray(t), np.asarray(t_ref))
        assert np.array_equal(np.asarray(m), np.asarray(m_ref))
        assert float(t.min()) >= 1e-3 and float(t.max()) <= 1.0


def test_the_draw_is_a_function_of_the_sequence_and_the_seed_alone():
    """Repeats of a sequence in one batch draw alike (the harness's check
    tiles a few sequences over the batch), whatever lies beside them and
    wherever they lie; another sequence, another seed, another draw."""
    ids = batch_of(8, rows=3, seq=64)["input_ids"]
    tiled = jnp.concatenate([ids, ids[::-1]])
    t, m = sdar.draw_noise(tiled, 3, 4, 1e-3)
    for a, b in ((0, 5), (1, 4), (2, 3)):
        assert np.array_equal(m[a], m[b]) and np.array_equal(t[a], t[b])
    assert not np.array_equal(m[0], m[1])
    alone = sdar.draw_noise(ids[1:2], 3, 4, 1e-3)
    assert np.array_equal(alone[1][0], m[1])
    assert not np.array_equal(sdar.draw_noise(ids, 4, 4, 1e-3)[1], m[:3])
    # a token moved is another sequence: the hash reads the places too
    swapped = ids.at[0, :2].set(ids[0, :2][::-1])
    assert int(sdar.sequence_hash(swapped)[0]) != int(
        sdar.sequence_hash(ids)[0]) or int(ids[0, 0]) == int(ids[0, 1])


def test_the_schedule_masks_half_and_weighs_one_in_expectation():
    ids = jax.random.randint(jax.random.PRNGKey(0), (64, 512), 0, 255)
    t, m = sdar.draw_noise(ids, 1, 4, 1e-3)
    weights = m / jnp.repeat(t, 4, axis=1)
    assert float(m.mean()) == pytest.approx(0.5, abs=0.01)
    assert float(weights.mean()) == pytest.approx(1.0, abs=0.05)
    # one t a BLOCK: a block's tokens are masked under one probability
    assert t.shape == (64, 128)


# ------------------------------------------------------------ the objective

def test_the_weighted_cross_entropy_is_the_plain_lines_and_its_gradient():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 40))
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 40)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (2, 12)) \
        * (jax.random.uniform(jax.random.PRNGKey(3), (2, 12)) < 0.5)

    def plain(x):
        nll = -jnp.take_along_axis(jax.nn.log_softmax(x), targets[..., None],
                                   -1)[..., 0]
        return (nll * weights).sum() / weights.size

    got, grad = jax.value_and_grad(weighted_cross_entropy)(
        logits, targets, weights)
    want, want_grad = jax.value_and_grad(plain)(logits)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)
    # under unit weights it is the cross-entropy itself
    assert float(weighted_cross_entropy(
        logits, targets, jnp.ones((2, 12)))) == pytest.approx(
            float(cross_entropy_loss(logits, targets)), rel=1e-6)
    # the weights are data: no cotangent
    assert not np.any(np.asarray(jax.grad(
        weighted_cross_entropy, argnums=2)(logits, targets, weights)))
    # bfloat16 logits keep their dtype through the backward
    bf = jax.grad(weighted_cross_entropy)(logits.astype(jnp.bfloat16),
                                          targets, weights)
    assert bf.dtype == jnp.bfloat16


def test_the_objective_stands_where_the_cross_entropy_stood(both_sides):
    """`make_lm_loss` asks `sown.objective_of`: the model's sown targets
    and weights give the scalar, the batch's shifted labels none of it;
    a model that sows none keeps the next-token cross-entropy."""
    _, (loss, _), _, params, _ = both_sides
    cfg = nano(remat=True, router_aux_loss_weight=0.01)
    batch = batch_of(3)
    other = {**batch, "labels": jnp.zeros_like(batch["labels"])}
    with jax.default_matmul_precision("highest"):
        again = jax.jit(make_lm_loss(SDAR(cfg).apply))(params, other)
    assert float(again) == float(loss)
    logits = jnp.zeros((2, SEQ, 256))
    assert sown.objective_of({}, batch, logits) is None
    assert sown.objective_of({"layers_0": {"moe_aux": (jnp.ones(()),)}},
                             batch, logits) is None
    inter = {"diffusion_targets": (batch["input_ids"],),
             "diffusion_weights": (jnp.ones((2, SEQ)),)}
    assert float(sown.objective_of(inter, batch, logits)) == pytest.approx(
        np.log(256), rel=1e-6)
    key = f"{sdar.__name__}.diffusion_objective"
    assert key in sown._OBJECTIVES


def test_two_objectives_for_one_model_are_refused(monkeypatch):
    def second(intermediates, batch, logits):
        return jnp.zeros(())

    monkeypatch.setitem(sown._OBJECTIVES, "tests.second", second)
    inter = {"diffusion_targets": (jnp.zeros((1, 4), jnp.int32),),
             "diffusion_weights": (jnp.ones((1, 4)),)}
    with pytest.raises(ValueError, match="two objectives"):
        sown.objective_of(inter, {}, jnp.zeros((1, 4, 8)))


def test_the_counters_ride_the_steps_metrics(both_sides):
    *_, stats = both_sides
    layers, rows, heads = 2, 2, 4
    run, live, kept, computed = ba.bd_tile_count(SEQ, 4, "plain")
    for name, count in (("attn_bd_tiles_run", run),
                        ("attn_bd_tiles_live", live),
                        ("attn_bd_pairs_kept", kept),
                        ("attn_bd_pairs_computed", computed)):
        assert float(stats[name]) == layers * rows * heads * count, name
    _, m = sdar.draw_noise(batch_of(3)["input_ids"], 11, 4, 1e-3)
    assert float(stats["diffusion_masked_share"]) == pytest.approx(
        float(m.mean()))
    assert float(stats["diffusion_weight_mean"]) > 0
    # both copies are routed: 2 x SEQ positions a sequence, top-3
    assert float(stats["moe_rows_held"]) + float(stats["moe_rows_absent"]) \
        == layers * rows * 2 * SEQ * 3


def test_the_head_reads_the_noised_copy_alone():
    cfg = nano()
    model = SDAR(cfg)
    params = _params(cfg)
    logits, sowed = model.apply({"params": params},
                                batch_of(3)["input_ids"],
                                mutable=["intermediates"])
    assert logits.shape == (2, SEQ, 256)
    inter = sowed["intermediates"]
    assert inter["diffusion_targets"][0].shape == (2, SEQ)
    assert inter["diffusion_weights"][0].dtype == jnp.float32


# ------------------------------------------------------- parameter counts

def test_num_params_is_the_cells_count_and_the_published_models():
    """Shapes only: 645,623,296 at the cell's sizes (6 of 48 layers, 16
    of 128 experts, an eighth of the vocabulary), 550,984,960 and
    456,346,624 at the rungs not reached, 30.53B uncut, by `num_params`
    and by the tree."""
    def tree_size(cfg):
        shapes = jax.eval_shape(SDAR(cfg).init_params, jax.random.PRNGKey(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    whole = SDARConfig()
    assert whole.num_params() == 48 * 623_120_640 + 622_331_904 \
        == 30_532_122_624
    one = dataclasses.replace(whole, num_layers=1)
    assert one.num_params() == tree_size(one)
    cell = SDARConfig(vocab_size=18_992, num_layers=6, experts_held=16)
    assert cell.num_params() == tree_size(cell) == 645_623_296
    llama = cell.attention_config()
    assert llama.attention_params() == 18_874_624
    assert llama.ffn_params() == 262_144 + 16 * 4_718_592
    assert cell.num_params() * 16 < 0.65 * 16e9 < 14.4e9
    for depth, count in ((5, 550_984_960), (4, 456_346_624)):
        assert dataclasses.replace(cell, num_layers=depth).num_params() \
            == count == cell.num_params() - (6 - depth) * 94_638_336
    assert cell.mask_id == 18_991


def test_num_params_is_the_tree_at_nano_size():
    shapes = jax.eval_shape(SDAR(nano()).init_params, jax.random.PRNGKey(0))
    assert nano().num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


# -------------------------------------------------------------- the shares

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Every share of the experts (eight shares of 16 of 128, the cell's
    router at its published width, 8 a token renormalised; no shared
    expert) over BOTH copies' positions, the router counted once — every
    share computes it alike — add up to the uncut reference's layer."""
    hidden, width, n_exp, held = 24, 16, 128, 16
    base = moe.MoEConfig(
        num_experts=n_exp, top_k=8, impl="grouped", dtype=jnp.float32,
        aux_loss="none", score_func="softmax", norm_topk_prob=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 2 * SEQ, hidden))
    params = with_opinions(jax.jit(moe.MoEMLP(hidden, width, base).init)(
        jax.random.PRNGKey(1), x)["params"], 3, 0.3)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(x.reshape(-1, hidden), params, top_k=8,
                                   first_expert=0)
        total, rows = jnp.zeros_like(x), 0
        for first in range(0, n_exp, held):
            share = {**params, **{
                name: params[name][first:first + held] for name in
                ("experts_w_in", "experts_w_gate", "experts_w_down")}}
            layer = moe.MoEMLP(hidden, width, dataclasses.replace(
                base, experts_held=held, first_expert=first))
            part, sowed = jax.jit(functools.partial(
                layer.apply, mutable=["intermediates"]))(
                    {"params": share}, x)
            total = total + part
            rows += int(sowed["intermediates"]["moe_rows_held"][0])
    assert rows == 2 * 2 * SEQ * 8  # every assignment on exactly one share
    np.testing.assert_allclose(total, want.reshape(x.shape), rtol=1e-4,
                               atol=1e-5)


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
    params = jax.eval_shape(SDAR(nano()).init_params, jax.random.PRNGKey(0))
    paths = [path_of(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for path in paths:
        assert any(re.match(pat, path, re.IGNORECASE)
                   for pat, _ in rules), path
    at = "layers_0/attention"
    want = {
        f"{at}/q_proj/kernel": P("fsdp", "tp"),
        f"{at}/o_proj/kernel": P("tp", "fsdp"),
        f"{at}/q_norm/scale": P(), f"{at}/k_norm/scale": P(),
        "layers_0/input_norm/scale": P(),
        "layers_1/feed_forward/router/kernel": P("fsdp", None),
        "layers_1/feed_forward/experts_w_in": P("ep", "fsdp", "tp"),
        "lm_head/kernel": P("fsdp", "tp"), "norm/scale": P()}
    assert set(want) <= set(paths)
    for path, spec in want.items():
        assert spec_for_path(path, rules) == spec, path


# --------------------------------------------------------- what is refused

def test_a_mesh_of_several_devices_is_refused_and_one_device_steps():
    """Through `auto_accelerate`, as every configuration: on two devices
    the stack says what it cannot run; on one an optimizer step under
    `fsdp` runs with every block rematerialised and carries the
    diffusion's counters."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    model = SDAR(nano(remat=True))
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
    assert 0 < float(metrics["diffusion_masked_share"]) < 1
    assert float(metrics["attn_bd_tiles_run"]) > 0


@pytest.mark.parametrize("over,seq,match", [
    (dict(block_length=3), 48, "does not divide"),
    (dict(block_length=1024), 2048, "does not divide"),
    (dict(block_length=32), 48, "does not divide")])
def test_a_block_length_off_the_tile_or_the_sequence_is_refused(over, seq,
                                                                match):
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(SDAR(nano(**over)).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, seq), jnp.int32))


def test_the_mask_beside_a_window_or_a_gate_is_refused():
    from dlrover_wuqiong_tpu.models.llama import LlamaAttention, rope_freqs

    cfg = dataclasses.replace(nano().attention_config(), attn_window=8)
    x = jnp.zeros((1, 16, 64))
    cos, sin = rope_freqs(16, 16, 1e4)
    with pytest.raises(ValueError, match="a window, a gate"):
        jax.eval_shape(LlamaAttention(cfg).init, jax.random.PRNGKey(0), x,
                       cos, sin)


def test_the_trainer_and_the_mesh_code_name_no_model():
    """PR 68's rule holds with the fourth registration: the objective is
    asked through `models/sown.py` alone."""
    import pathlib

    root = pathlib.Path(sdar.__file__).resolve().parents[1]
    for folder in ("trainer", "parallel"):
        for path in (root / folder).glob("*.py"):
            text = path.read_text()
            assert "sdar" not in text.lower(), path
            assert "diffusion" not in text.lower(), path
