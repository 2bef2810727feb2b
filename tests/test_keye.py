"""`KeyeVL2`'s language model through `models/keye.py`: grouped-query
attention over a learned top-k choice of keys (`models/sparse_indexer.py`,
`ops/sparse_attention.py`) under a per-head QK norm and M-RoPE, softmax
top-k experts without a shared one, an untied head — against the plain
reference (`benchmark/reference_keye_vl2.py`) at a nano size on the CPU,
float32 on both sides: loss, cross-entropy, index KL and every leaf's
gradient, with T over `topk` (the choice binds) and at it (nothing is
chosen); the chosen set; which term reaches which leaves; M-RoPE under
three distinct streams; the parameter counts at the published widths;
the share test; the counters; the sharding rules; what is refused.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_keye_vl2 as ref
from dlrover_wuqiong_tpu.models import moe
from dlrover_wuqiong_tpu.models.keye import Keye, KeyeConfig
from dlrover_wuqiong_tpu.models.llama import mrope_tables, rope_freqs
from dlrover_wuqiong_tpu.ops import sparse_attention as sa
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 48


def nano(**over):
    """Two layers, top-16 of up to 48 keys, experts 4-7 of 16 held."""
    return KeyeConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, experts_held=4, first_expert=4),
        **over})


def reference_loss(cfg, **control):
    return functools.partial(
        ref.loss, n_layer=cfg.num_layers, n_head=cfg.num_heads,
        n_kv=cfg.num_kv_heads, topk=cfg.index_topk, theta=cfg.rope_theta,
        sections=cfg.mrope_sections, top_k=cfg.top_k,
        first_expert=cfg.first_expert, eps=cfg.rms_eps,
        index_loss_weight=cfg.index_loss_weight,
        aux_weight=cfg.router_aux_loss_weight, **control)


def with_opinions(params, seed, scale=0.1):
    """Every leaf off its draw, so that no scale is 1 and no term is
    symmetric by accident."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(next(keys), a.shape), params)


def batch_of(seed, rows=2, seq=SEQ):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, 256)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}


def distinct_positions(rows, seq, seed=9):
    """Three streams that differ from each other and between the rows."""
    return jax.random.randint(jax.random.PRNGKey(seed), (3, rows, seq), 0,
                              seq)


def _sides(cfg, batch, positions=None):
    model = Keye(cfg)
    params = with_opinions(
        jax.jit(model.init_params)(jax.random.PRNGKey(1)), 2)
    apply = model.apply if positions is None else functools.partial(
        model.apply, positions=positions)
    ref_batch = batch if positions is None else {**batch,
                                                 "positions": positions}
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            make_lm_loss(apply).with_stats, has_aux=True))(params, batch)
        def parts(p, b):
            total, *apart = reference_loss(cfg, parts=True)(p, b)
            return total, apart

        want, ref_grads = jax.jit(jax.value_and_grad(
            parts, has_aux=True))(params, ref_batch)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    return names, (loss, grads), (want, ref_grads), params, stats


# ------------------------------------------------- model against reference

@pytest.fixture(scope="module")
def both_sides():
    """T = 48 over topk = 16: the choice binds on two rows in three;
    every block recomputed."""
    return _sides(nano(remat=True), batch_of(3))


@pytest.fixture(scope="module")
def nothing_chosen():
    """T = topk = 16: every causal key is kept."""
    return _sides(nano(), batch_of(4, seq=16))


@pytest.fixture(scope="module")
def three_streams():
    return _sides(nano(), batch_of(5),
                  positions=distinct_positions(2, SEQ))


@pytest.fixture(scope="module")
def balanced():
    """The router's load-balancing term on (the cell's assumption)."""
    return _sides(nano(router_aux_loss_weight=0.01), batch_of(6))


# attention 4 products + 2 head norms + indexer 5, 2 block norms, router
# and 3 expert stacks, a layer; table, final norm, head
N_LEAVES = 2 * (11 + 2 + 4) + 3


def _terms_are_the_references(sides, balance_on=False):
    names, (loss, _), ((ref_loss, (ref_ce, ref_kl)), _), _, stats = sides
    assert len(names) == N_LEAVES
    for got, want in ((loss, ref_loss), (stats["ce"], ref_ce),
                      (stats["index_kl"], ref_kl)):
        assert abs(float(got) - float(want)) < 3e-6 * abs(float(want))
    assert float(ref_kl) > 1e-3  # a term, not a rounding
    # what is neither cross-entropy nor index term: the balance term,
    # 0.01 x (a number near top_k) where it is on, nothing where it is off
    rest = float(loss) - float(stats["ce"]) - 2 * float(stats["index_kl"])
    assert rest == pytest.approx(0.03 if balance_on else 0.0,
                                 abs=0.012 if balance_on else 2e-5)


def _leaf_is_the_references(sides, leaf):
    names, (_, grads), (_, ref_grads), *_ = sides
    got = jax.tree.leaves(grads)[leaf]
    want = jax.tree.leaves(ref_grads)[leaf]
    assert float(jnp.abs(want).max()) > 0, names[leaf]
    np.testing.assert_allclose(
        got, want, rtol=1e-3, atol=1e-4 * float(jnp.abs(want).max()),
        err_msg=names[leaf])


# ONE test a setting holds the three terms and every leaf, so that one
# worker builds the setting's two compiled sides once (a test a leaf
# builds them on every worker of the run: 26 s each, six times)

def test_loss_ce_index_kl_and_every_leafs_gradient_are_the_references(
        both_sides):
    """Leaf by leaf (the norm over 659M entries that the chip compares
    would average a wrong leaf away; a failure names its leaf)."""
    _terms_are_the_references(both_sides)
    for leaf in range(N_LEAVES):
        _leaf_is_the_references(both_sides, leaf)


def test_the_counters_ride_the_steps_metrics(both_sides):
    stats = both_sides[4]
    layers, rows = 2, 2
    assert float(stats["attn_sparse_kept"]) == layers * rows \
        * sa.kept_pairs(SEQ, 16)
    assert float(stats["attn_sparse_causal"]) == layers * rows * SEQ \
        * (SEQ + 1) // 2
    # one tile at this size: live, causal and run coincide
    assert float(stats["attn_sparse_live_tiles"]) \
        == float(stats["attn_sparse_tiles_causal"]) \
        == float(stats["attn_sparse_tiles_run"]) == layers * rows
    assert float(stats["index_kl"]) > 0
    assert sa.kept_pairs(16384, 2048) == 2048 * 2049 // 2 + 14336 * 2048
    assert round(100 * sa.kept_pairs(16384, 2048)
                 / (16384 * 16385 // 2), 1) == 23.4


def test_the_sections_order_is_unseen_by_text(both_sides):
    """Where the three streams coincide a permutation of the sections is
    the same rotation: the cell's text cannot tell it."""
    _, (loss, _), _, params, _ = both_sides
    with jax.default_matmul_precision("highest"):
        off = jax.jit(reference_loss(nano(), wrong="mrope_sections"))(
            params, batch_of(3))
    assert abs(float(off) - float(loss)) < 3e-6 * float(loss)


def test_where_nothing_is_chosen_all_of_it_is_the_references(
        nothing_chosen):
    _terms_are_the_references(nothing_chosen)
    for leaf in range(N_LEAVES):
        _leaf_is_the_references(nothing_chosen, leaf)


def test_with_the_balance_term_all_of_it_is_the_references(balanced):
    _terms_are_the_references(balanced, balance_on=True)
    for leaf in range(N_LEAVES):
        _leaf_is_the_references(balanced, leaf)


def test_under_three_streams_all_of_it_is_the_references_and_no_wrong_one(
        three_streams):
    """Under distinct streams (where the sections' order shows) the
    three terms and every leaf are the reference's, and each control the
    reference names moves the loss by more than the two sides differ:
    the reference would tell it from the model."""
    _terms_are_the_references(three_streams)
    for leaf in range(N_LEAVES):
        _leaf_is_the_references(three_streams, leaf)
    _, (loss, _), _, params, _ = three_streams
    batch = {**batch_of(5), "positions": distinct_positions(2, SEQ)}
    for wrong in ref.WRONG:
        with jax.default_matmul_precision("highest"):
            off = jax.jit(reference_loss(nano(), wrong=wrong))(params, batch)
        assert abs(float(off) - float(loss)) > 1e-4 * float(loss), wrong


# ------------------------------------------------------------ which leaves

def _is_indexer(name):
    return "indexer" in name


def test_the_cross_entropy_reaches_no_indexer_leaf_and_the_term_no_other():
    cfg = nano()
    model = Keye(cfg)
    params = with_opinions(
        jax.jit(model.init_params)(jax.random.PRNGKey(1)), 2)
    batch = batch_of(3)
    with_stats = make_lm_loss(model.apply).with_stats

    def ce_alone(p):
        return with_stats(p, batch)[1]["ce"]

    def term_alone(p):
        loss, stats = with_stats(p, batch)
        return loss - stats["ce"]

    for fn, reached in ((ce_alone, lambda n: not _is_indexer(n)),
                        (term_alone, _is_indexer)):
        grads = jax.jit(jax.grad(fn))(params)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            name = jax.tree_util.keystr(path)
            assert bool(np.any(np.asarray(g))) == reached(name), name


# ---------------------------------------------------------- the chosen set

def _scores(seed, rows=2, seq=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, seq, seq))


def test_the_chosen_set_is_the_references():
    scores = _scores(0)
    got = sa._plain_select(scores, 16)
    want = ref.chosen_keys(scores, 0, 16)
    assert np.array_equal(got, want)
    kept = np.asarray(got).sum(-1)
    assert np.array_equal(kept[0], np.minimum(np.arange(SEQ) + 1, 16))


def test_a_tie_goes_to_the_lower_key_in_both():
    """Rows of few distinct values: the threshold falls inside a run of
    equals, and only the lowest keys of the run are kept."""
    scores = jnp.round(_scores(1) * 2) / 2  # steps of a half: many ties
    got = np.asarray(sa._plain_select(scores, 16))
    assert np.array_equal(got, ref.chosen_keys(scores, 0, 16))
    row = np.asarray(scores[0, SEQ - 1])
    kept = got[0, SEQ - 1]
    thr = row[kept].min()
    ties = np.flatnonzero(row == thr)
    assert 0 < kept[ties].sum() < len(ties)  # the run is cut
    n = kept[ties].sum()
    assert kept[ties[:n]].all() and not kept[ties[n:]].any()


# ------------------------------------------------------------------ M-RoPE

def test_mrope_is_rope_where_the_streams_coincide():
    pos = jnp.broadcast_to(jnp.arange(SEQ), (3, 2, SEQ))
    cos, sin = mrope_tables(16, 1e4, (2, 2, 4), pos)
    want_cos, want_sin = rope_freqs(16, SEQ, 1e4)
    np.testing.assert_allclose(cos[1], want_cos, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(sin[0], want_sin, rtol=1e-6, atol=1e-6)


def test_mrope_takes_each_pair_from_its_own_stream():
    pos = distinct_positions(2, SEQ)
    cos, _ = mrope_tables(16, 1e4, (2, 2, 4), pos)
    inv = 1.0 / 1e4 ** (np.arange(0, 16, 2) / 16)
    for pair, stream in enumerate((0, 0, 1, 1, 2, 2, 2, 2)):
        np.testing.assert_allclose(
            cos[..., pair], np.cos(np.asarray(pos[stream]) * inv[pair]),
            rtol=1e-5, atol=1e-5)
    # the indexer's narrower head takes the sections in proportion
    half, _ = mrope_tables(8, 1e4, (2, 2, 4), pos)  # 4 pairs: 1, 1, 2
    inv = 1.0 / 1e4 ** (np.arange(0, 8, 2) / 8)
    for pair, stream in enumerate((0, 1, 2, 2)):
        np.testing.assert_allclose(
            half[..., pair], np.cos(np.asarray(pos[stream]) * inv[pair]),
            rtol=1e-5, atol=1e-5)


def test_sections_that_do_not_divide_the_pairs_are_refused():
    with pytest.raises(ValueError, match="in proportion"):
        mrope_tables(8, 1e4, (3, 3, 2), distinct_positions(1, 8))


# ------------------------------------------------------- parameter counts

def test_num_params_is_the_cells_count_and_the_published_models():
    """Shapes only: 659,190,016 at the cell's sizes (6 of 48 layers, 16
    of 128 experts, an eighth of the vocabulary), 562,290,560 at ISSUE
    62's depth 5 and 30.64B uncut, by `num_params` and by the tree."""
    def tree_size(cfg):
        shapes = jax.eval_shape(Keye(cfg).init_params, jax.random.PRNGKey(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    whole = KeyeConfig()
    assert whole.num_params() == 48 * 625_381_760 + 622_331_904 \
        == 30_640_656_384
    one = dataclasses.replace(whole, num_layers=1)
    assert one.num_params() == tree_size(one)
    cell = KeyeConfig(vocab_size=18_992, num_layers=5, experts_held=16)
    assert cell.num_params() == tree_size(cell) == 562_290_560
    llama = cell.attention_config()
    assert llama.attention_params() == 18_874_624 + 2_261_120
    assert llama.ffn_params() == 262_144 + 16 * 4_718_592
    assert cell.num_params() * 16 < 0.6 * 16e9 < 14.4e9
    taken = dataclasses.replace(cell, num_layers=6)  # the rung's depth
    assert taken.num_params() == tree_size(taken) == 659_190_016 \
        == cell.num_params() + 96_899_456


def test_num_params_is_the_tree_at_nano_size():
    shapes = jax.eval_shape(Keye(nano()).init_params, jax.random.PRNGKey(0))
    assert nano().num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


# -------------------------------------------------------------- the shares

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Every share of the experts (eight shares of 16 of 128, the cell's
    router at its published width, 8 a token renormalised; no shared
    expert), the router counted once — every share computes it alike —
    add up to the uncut reference's layer."""
    hidden, width, n_exp, held = 24, 16, 128, 16
    base = moe.MoEConfig(
        num_experts=n_exp, top_k=8, impl="grouped", dtype=jnp.float32,
        aux_loss="none", score_func="softmax", norm_topk_prob=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, hidden))
    params = with_opinions(jax.jit(moe.MoEMLP(hidden, width, base).init)(
        jax.random.PRNGKey(1), x)["params"], 3, 0.3)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(x.reshape(-1, hidden), params, top_k=8,
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
    assert rows == 2 * SEQ * 8  # every assignment on exactly one share
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


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
    params = jax.eval_shape(Keye(nano()).init_params, jax.random.PRNGKey(0))
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
        f"{at}/indexer/wq_idx/kernel": P("fsdp", "tp"),
        f"{at}/indexer/wk_idx/kernel": P("fsdp", None),
        f"{at}/indexer/w_proj/kernel": P("fsdp", None),
        f"{at}/indexer/k_norm/scale": P(),
        f"{at}/indexer/k_norm/bias": P(),
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
    `fsdp` runs with every block rematerialised and logs the two terms
    apart."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    model = Keye(nano(remat=True))
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
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["ce"]) + 2 * float(metrics["index_kl"]), rel=1e-5)


def test_the_choice_beside_a_window_or_a_gate_is_refused():
    from dlrover_wuqiong_tpu.models.llama import LlamaAttention

    cfg = dataclasses.replace(nano().attention_config(), attn_window=8)
    x = jnp.zeros((1, 16, 64))
    cos, sin = rope_freqs(16, 16, 1e4)
    with pytest.raises(ValueError, match="window or a gate"):
        jax.eval_shape(LlamaAttention(cfg).init, jax.random.PRNGKey(0), x,
                       cos, sin)
