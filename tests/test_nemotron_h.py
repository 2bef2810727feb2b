"""`nemotron_h` through `models/nemotron_h.py`: the chunked scan against
the sequential recurrence, the Mamba-2 mixer, the expert layer with its
sigmoid router, selection bias, scaled gates, relu^2 experts, shared
expert and a chip's share of the experts, and the whole stack — each
against the plain reference (`benchmark/reference_nemotron_h.py`) at a
nano size on the CPU, float32 on both sides.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_nemotron_h as ref
from benchmark.reference import loss_and_grad_norm
from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
from dlrover_wuqiong_tpu.models.mamba2 import Mamba2Config, Mamba2Mixer
from dlrover_wuqiong_tpu.models.moe import (
    MoEConfig, MoEMLP, collect_moe_stats)
from dlrover_wuqiong_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.ops.ssd import ssd_scan
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

SEQ = 64  # four chunks of 16


def nano(**over):
    return NemotronHConfig.nano(**{**dict(
        dtype=jnp.float32, remat=False, use_flash_attention=False), **over})


def reference_loss(cfg):
    return functools.partial(
        ref.loss, pattern=cfg.pattern, n_head=cfg.num_heads,
        n_kv_head=cfg.num_kv_heads, head_dim=cfg.head_dim,
        mamba_heads=cfg.mamba_heads, mamba_head_dim=cfg.mamba_head_dim,
        n_groups=cfg.n_groups, state=cfg.state_size, top_k=cfg.top_k,
        routed_scaling=cfg.routed_scaling, first_expert=cfg.first_expert,
        eps=cfg.rms_eps)


def with_opinions(params, seed):
    """Scales off 1, a selection bias that reorders the choice, a router
    and experts loud enough that a wrong gate or a missing norm shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))

    def bump(path, a):
        name = path[-1].key
        if name in ("scale", "gate_norm_scale", "D"):
            return a * (1 + 0.3 * jax.random.normal(next(keys), a.shape))
        if name == "selection_bias":
            return 0.1 * jax.random.normal(next(keys), a.shape)
        if name.startswith("experts_w"):
            return a * 8.0  # normal(0.02) leaves the layer mute
        if len(path) > 1 and path[-2].key == "router":
            return a * 6.0
        return a
    return jax.tree_util.tree_map_with_path(bump, params)


def seeded(cfg, seed=3, batch=3):
    model = NemotronH(cfg)
    params = with_opinions(model.init_params(jax.random.PRNGKey(seed)),
                           seed + 100)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, SEQ + 1),
                             0, cfg.vocab_size)
    return model, params, {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


# ------------------------------------------------- the chunked scan

def _scan_inputs(seed=0, b=2, t=SEQ, h=8, p=16, g=2, n=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, t, g, n)),
            jax.random.normal(k[4], (b, t, g, n)),
            jax.random.normal(k[5], (h,)))


NAMES = ("x", "dlt", "A", "B", "C", "D")


@pytest.fixture(scope="module")
def scan_both_ways():
    """Values and the gradients of a scalar of y, chunked and sequential.
    Float32 on both sides; the two differ in the ORDER of the sums (a
    chunk's products are summed by a matmul, the carried state once a
    chunk), so they agree to a few float32 roundings of sums of up to 64
    terms of mixed sign: 1e-5 of the largest entry."""
    args = _scan_inputs()
    with jax.default_matmul_precision("highest"):
        out = {}
        for name, fn in (("chunked", functools.partial(ssd_scan, chunk=16)),
                         ("sequential", ref.recurrence)):
            def scalar(*a, fn=fn):
                return jnp.sum(jnp.sin(fn(*a)))
            out[name] = (fn(*args), jax.grad(
                scalar, argnums=tuple(range(6)))(*args))
    return out


def test_chunked_scan_is_the_sequential_recurrence(scan_both_ways):
    got, want = scan_both_ways["chunked"][0], scan_both_ways["sequential"][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("i", range(6), ids=NAMES)
def test_chunked_scan_gradient_is_the_recurrences(scan_both_ways, i):
    got = scan_both_ways["chunked"][1][i]
    want = scan_both_ways["sequential"][1][i]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_scan_refuses_a_ragged_last_chunk():
    x, dlt, a, b, c, d = _scan_inputs(t=40)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd_scan(x, dlt, a, b, c, d, chunk=16)


def test_scan_keeps_decays_and_state_in_float32_under_bf16():
    """bf16 operands into the products, float32 decays: against the
    float32 scan the result is off by bf16's rounding of the operands
    (2^-8 relative each), not by a decay rounded to bf16, which at
    cumulative sums of -10 and below would lose whole chunks."""
    args = _scan_inputs()
    want = ssd_scan(*args, chunk=16)
    got = ssd_scan(*args, chunk=16, dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert 1e-4 < err < 2e-2, err


# ------------------------------------------------- the mixer

def test_mixer_matches_the_reference_and_counts_its_parameters(monkeypatch):
    cfg = Mamba2Config(hidden_size=48, num_heads=8, head_dim=16, n_groups=2,
                       state_size=8, chunk_size=16, dtype=jnp.float32)
    mixer = Mamba2Mixer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 48))
    params = with_opinions(mixer.init(jax.random.PRNGKey(1), u)["params"], 2)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    # the initialiser's settings: step sizes in [dt_min, dt_max], A in
    # [-16, -1]; nothing clamps them in the forward pass
    dt = jax.nn.softplus(params["dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1
    assert 0.0 <= float(params["A_log"].min()) \
        and float(params["A_log"].max()) <= np.log(16.0)
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": params}, u)
        want = ref.mamba_mixer(u, params, heads=8, head_dim=16, groups=2,
                               state=8, eps=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
    # the gate's norm is over each GROUP of d_inner / G features: one
    # norm over all of them is another layer
    monkeypatch.setattr(ref, "_rms_norm", lambda x, s, eps, f=ref._rms_norm:
                        f(x.reshape(*x.shape[:-2], 1, -1), s, eps
                          ).reshape(x.shape))
    ungrouped = ref.mamba_mixer(u, params, heads=8, head_dim=16, groups=2,
                                state=8, eps=1e-5)
    assert float(jnp.abs(ungrouped - got).max()) > 1e-2 * float(
        jnp.abs(got).max())


# ------------------------------------------------- the expert layer

def _moe(**over):
    return MoEConfig(**{**dict(
        num_experts=8, top_k=2, impl="grouped", dtype=jnp.float32,
        aux_loss="none", score_func="sigmoid", selection_bias=True,
        routed_scaling=2.5, expert_act="relu2", shared_width=48), **over})


def _expert_layer(moe, seed=0):
    layer = MoEMLP(hidden=32, ffn=24, moe=moe)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 48, 32))
    params = with_opinions(layer.init(jax.random.PRNGKey(seed + 1), x)[
        "params"], seed + 2)
    return layer, params, x


def _reference_layer(params, x, moe):
    return ref.expert_layer(
        x.reshape(-1, x.shape[-1]), params, top_k=moe.top_k,
        routed_scaling=moe.routed_scaling,
        first_expert=moe.first_expert).reshape(x.shape)


@pytest.mark.parametrize("held,first", [(0, 0), (2, 0), (2, 4), (3, 5)])
def test_expert_layer_matches_the_references_masked_form(held, first):
    moe = _moe(experts_held=held, first_expert=first)
    layer, params, x = _expert_layer(moe)
    assert "experts_w_gate" not in params  # relu2 has no gate matrix
    assert params["experts_w_in"].shape[0] == (held or 8)
    assert params["router"]["kernel"].shape == (32, 8)

    def both(p):
        with jax.default_matmul_precision("highest"):
            return (layer.apply({"params": p}, x), _reference_layer(p, x, moe))

    got, want = both(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
    # per leaf, the router's included; the bias has no gradient at all
    grads = [jax.grad(lambda p, i=i: jnp.sum(jnp.sin(both(p)[i])))(params)
             for i in (0, 1)]
    for path, g in jax.tree_util.tree_flatten_with_path(grads[0])[0]:
        w = functools.reduce(lambda t, k: t[k.key], path, grads[1])
        if path[-1].key == "selection_bias":
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=2e-5 * float(jnp.abs(w).max()), err_msg=str(path))


@pytest.mark.parametrize("crowded", [False, True])
def test_a_share_is_exact_whatever_the_routing_sends_it(crowded):
    """2 of 16 experts.  A routing that sends this chip EVERY assignment
    (`crowded`: the router prefers exactly the two held experts) is the
    reference's like an even one: nothing is dropped, and the work done
    is the same either way."""
    moe = _moe(num_experts=16, experts_held=2, first_expert=5)
    layer, params, x = _expert_layer(moe)
    if crowded:
        kernel = -jnp.abs(params["router"]["kernel"])
        params["router"]["kernel"] = kernel.at[:, 5:7].set(1.0)
        x = jnp.abs(x)  # every token's scores of experts 5 and 6 lead
    y, upd = layer.apply({"params": params}, x, mutable=["intermediates"])
    inter = upd["intermediates"]
    rows, every = int(inter["moe_rows_held"][0]), 2 * 48 * 2
    assert (rows == every) if crowded else (0 < rows <= every // 2)
    assert int(inter["moe_dropped"][0]) == 0
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(params, x, moe)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    g = jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x))))(params)
    w = jax.grad(lambda p: jnp.sum(jnp.sin(
        _reference_layer(p, x, moe))))(params)
    for name in ("experts_w_in", "experts_w_down"):
        np.testing.assert_allclose(
            np.asarray(g[name]), np.asarray(w[name]), rtol=1e-4,
            atol=2e-5 * float(jnp.abs(w[name]).max()))
    np.testing.assert_allclose(
        np.asarray(g["router"]["kernel"]), np.asarray(w["router"]["kernel"]),
        rtol=1e-4, atol=2e-5 * float(jnp.abs(w["router"]["kernel"]).max()))


def test_the_bias_changes_the_choice_and_not_the_gates():
    moe = _moe(shared_width=0, routed_scaling=1.0)
    layer, params, x = _expert_layer(moe)
    tokens = x.reshape(-1, 32)
    s = jax.nn.sigmoid(tokens @ params["router"]["kernel"])
    chosen = jax.lax.top_k(s + params["selection_bias"], 2)[1]
    by_score = jax.lax.top_k(s, 2)[1]
    assert (np.sort(chosen, -1) != np.sort(by_score, -1)).any()
    # hand-made: the gates are the scores at the chosen experts,
    # normalised, and the bias appears nowhere in them
    g = jnp.take_along_axis(s, chosen, -1)
    g = g / (g.sum(-1, keepdims=True) + 1e-20)
    want = jnp.zeros_like(tokens)
    for j in range(2):
        for e in range(8):
            y = jnp.square(jax.nn.relu(tokens @ params["experts_w_in"][e])) \
                @ params["experts_w_down"][e]
            want = want + jnp.where((chosen[:, j] == e)[:, None],
                                    g[:, j, None] * y, 0.0)
    got = layer.apply({"params": params}, x).reshape(-1, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_the_bias_rule_steps_each_expert_toward_the_even_load():
    """`bias_update_rate`: the layer sows rate x the relative shortfall
    of each expert's load, clipped to one rate, over ALL the router's
    experts, held here or not, and
    `collect_param_steps` hands it to that layer's own variable; the
    result and the counters are those of the layer without the rule."""
    from dlrover_wuqiong_tpu.models.moe import collect_param_steps

    moe = _moe(experts_held=2, first_expert=4)
    ruled = dataclasses.replace(moe, bias_update_rate=0.25)
    layer, params, x = _expert_layer(moe)
    tokens = x.reshape(-1, 32)
    s = jax.nn.sigmoid(tokens @ params["router"]["kernel"])
    chosen = jax.lax.top_k(s + params["selection_bias"], 2)[1]
    load = np.bincount(np.asarray(chosen).ravel(), minlength=8)
    assert load.max() > load.mean() > load.min()
    out, inter = MoEMLP(hidden=32, ffn=24, moe=ruled).apply(
        {"params": params}, x, mutable=["intermediates"])
    inter = {"layers_3": {"feed_forward": inter["intermediates"]}}
    steps = collect_param_steps(inter)
    assert list(steps) == ["layers_3"]
    np.testing.assert_allclose(
        np.asarray(steps["layers_3"]["feed_forward"]["selection_bias"]),
        0.25 * np.clip((load.mean() - load) / load.mean(), -1, 1),
        rtol=1e-6)
    plain, plain_inter = layer.apply({"params": params}, x,
                                     mutable=["intermediates"])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    assert collect_param_steps(plain_inter) == {}
    with pytest.raises(ValueError, match="selection bias"):
        MoEMLP(hidden=32, ffn=24, moe=dataclasses.replace(
            ruled, selection_bias=False)).init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_shares_parts_add_up_to_the_uncut_layer(request, route):
    """Four chips with two of the eight experts each: their parts of the
    result, the shared expert counted once, are the whole layer's — on
    the route every CPU run takes and on the `dwt_gmm` kernels a share
    runs on one TPU device (interpret mode, 192 rows in six tiles of
    32).  Each share counts the row tiles its products walk: the whole
    buffer on the plain route, on the kernels' the groups' visits."""
    whole = _moe()
    layer, params, x = _expert_layer(whole)
    want = _reference_layer(params, x, whole)
    _, upd = layer.apply({"params": params}, x, mutable=["intermediates"])
    for counter in ("moe_gmm_tiles", "moe_map_tiles", "moe_gather_rows",
                    "moe_combine_rows"):
        assert counter not in upd["intermediates"]
        assert f"{counter}_share" not in collect_moe_stats(
            upd["intermediates"])
    if route == "kernel":
        request.getfixturevalue("held_rows_interpreted")
    shared = jnp.square(jax.nn.relu(
        x @ params["shared_up_proj"]["kernel"])) \
        @ params["shared_down_proj"]["kernel"]
    total, rows = shared, 0
    for first in (0, 2, 4, 6):
        moe = _moe(experts_held=2, first_expert=first)
        part = {**params, **{
            k: params[k][first:first + 2]
            for k in ("experts_w_in", "experts_w_down")}}
        assert gm.gmm_route((192, 32), (2, 32, 24), 8) == route
        y, upd = MoEMLP(hidden=32, ffn=24, moe=moe).apply(
            {"params": part}, x, mutable=["intermediates"])
        inter = upd["intermediates"]
        total = total + (y - shared)
        held = int(inter["moe_rows_held"][0])
        assert held + int(inter["moe_rows_absent"][0]) == 2 * 48 * 2
        assert int(inter["moe_dropped"][0]) == 0
        assert inter["moe_tokens_per_expert"][0].shape == (2,)
        rows += held
        sizes = np.asarray(inter["moe_tokens_per_expert"][0])
        ends = np.cumsum(sizes)
        visits = sum(int((e - 1) // 32 - (e - n) // 32 + 1)
                     for e, n in zip(ends, sizes) if n)
        assert 0 < visits < 6
        walked, of = (int(v) for v in inter["moe_gmm_tiles"][0])
        # (the plain route counts in the kernels' own tile of 256 rows)
        assert (walked, of) == ((visits, 6) if route == "kernel" else (1, 1))
        assert float(collect_moe_stats(inter)["moe_gmm_tiles_share"]) \
            == pytest.approx(walked / of)
        # the passes between the products walk the tiles that hold a
        # held row: within one tile of the held rows' share
        walked, of = (int(v) for v in inter["moe_map_tiles"][0])
        assert (walked, of) == ((-(-held // 32), 6) if route == "kernel"
                                else (1, 1))
        assert float(collect_moe_stats(inter)["moe_map_tiles_share"]) \
            == pytest.approx(walked / of)
        # (192 rows are one turn of `dispatch`'s loop: every row)
        assert [int(v) for v in inter["moe_gather_rows"][0]] == [192, 192]
    assert rows == 2 * 48 * 2  # every assignment is held by one chip
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_a_shares_gradients_through_the_kernels_are_the_plain_routes(
        request):
    """Every leaf's gradient of a share's layer (2 of 8 experts): the
    kernel route (interpret mode, tiles of 32 rows) against the plain."""
    moe = _moe(experts_held=2, first_expert=4)
    layer, params, x = _expert_layer(moe)

    def run(p):
        return jnp.sum(jnp.sin(layer.apply({"params": p}, x)))

    want, want_g = jax.value_and_grad(run)(params)
    request.getfixturevalue("held_rows_interpreted")
    assert "pallas_call" in str(jax.make_jaxpr(jax.grad(run))(params))
    got, got_g = jax.value_and_grad(run)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5,
            atol=1e-6 * max(1.0, float(jnp.abs(w).max())), err_msg=str(path))


def test_a_share_runs_the_one_grouped_path_over_the_held_experts():
    """A share is the whole layer's path, not a second one: the same sort,
    the same inverse of it, the same row gathers (`dispatch`, `combine`)
    and `ragged_dot`, whose groups are the HELD experts alone (2 here,
    never the published 8) — an absent assignment has no group, and its
    place in the row buffer lies behind the held rows, where the sums
    over a token's k rows do not read."""
    moe = _moe(experts_held=2, first_expert=4)
    layer, params, x = _expert_layer(moe)
    text = str(jax.make_jaxpr(
        lambda p, x: layer.apply({"params": p}, x))(params, x))
    whole = str(jax.make_jaxpr(lambda p, x: MoEMLP(
        hidden=32, ffn=24, moe=_moe()).apply({"params": p}, x))(
            _expert_layer(_moe())[1], x))
    for traced in (text, whole):
        assert traced.count("ragged_dot_general[") == 2  # relu2: no gate
        # the assignments' sort (their numbers and their gates ride it)
        # and the inverse of its permutation
        assert len(re.findall(r"\bsort\[", traced)) == 2
        assert len(re.findall(r":i32\[192\] \w+:i32\[192\] \w+:f32\[192\] "
                              r"= sort\[", traced)) == 1
        assert traced.count("name=argsort") == 1
        assert traced.count("custom_vjp_call[") == 2
        assert traced.count("name=dispatch") == 1
        assert traced.count("name=combine") == 1
        # no row is scattered, and nothing is counted by a scatter: the
        # groups' sizes are a compare-and-sum (`expert_counts`)
        assert not re.findall(r" = scatter[-\w]*\[", traced)
        assert traced.count("mode=GatherScatterMode.PROMISE_IN_BOUNDS") == 2
    assert "f32[2,32,24]" in text and "f32[8,32,24]" not in text
    # group_sizes: the held experts' part of the ONE count of all 8
    assert re.search(r":i32\[2\] = slice\[limit_indices=\(6,\)", text)
    assert "f32[8,32,24]" in whole and "i32[8]" in whole


def test_a_share_keeps_what_no_group_wrote_out_of_every_gradient():
    """The places of the row buffer behind the held rows belong to no
    group; `lax.ragged_dot` writes zeros there on the CPU, the TPU's
    grouped kernels nothing at all.  Whatever they hold (a NaN here, put
    there by hand) reaches neither the result nor a gradient."""
    from dlrover_wuqiong_tpu.models import moe as moe_mod

    moe = _moe(experts_held=2, first_expert=4)
    layer, params, x = _expert_layer(moe)

    def run(p):
        return jnp.sum(jnp.sin(layer.apply({"params": p}, x)))

    want, want_g = jax.value_and_grad(run)(params)
    plain = jax.lax.ragged_dot

    def unwritten(lhs, rhs, group_sizes, **kw):
        rows = jnp.arange(lhs.shape[0])[:, None] < group_sizes.sum()
        return jnp.where(rows, plain(lhs, rhs, group_sizes, **kw), jnp.nan)

    moe_mod.jax.lax.ragged_dot = unwritten
    try:
        got, got_g = jax.value_and_grad(run)(params)
    finally:
        moe_mod.jax.lax.ragged_dot = plain
    assert np.isfinite(float(got)) and float(got) == float(want)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


def test_capacity_dispatch_refuses_every_grouped_only_field():
    for field, value in [("score_func", "sigmoid"), ("selection_bias", True),
                         ("routed_scaling", 2.5), ("expert_act", "relu2"),
                         ("shared_width", 16), ("experts_held", 2),
                         ("bias_update_rate", 0.001), ("aux_loss", "none")]:
        moe = MoEConfig(num_experts=4, top_k=2, impl="capacity",
                        dtype=jnp.float32, **{field: value})
        with pytest.raises(ValueError, match=field):
            MoEMLP(hidden=8, ffn=4, moe=moe).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


def test_new_fields_at_their_defaults_leave_olmoes_layer_as_it_was():
    """`experts_held` = all and every new field at its default: the
    layer's output, counters and sown loss are bit for bit those of the
    same fields spelled out, the parameter tree has the old names, and
    no new counter is sown."""
    cfg = MoEConfig(num_experts=8, top_k=2, impl="grouped", aux_loss="topk",
                    norm_topk_prob=False, z_loss_weight=0.001,
                    dtype=jnp.float32)
    assert (cfg.score_func, cfg.selection_bias, cfg.routed_scaling,
            cfg.expert_act, cfg.shared_width, cfg.experts_held,
            cfg.first_expert, cfg.held) == \
        ("softmax", False, 1.0, "swiglu", 0, 0, 0, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    layer = MoEMLP(hidden=32, ffn=24, moe=cfg)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"router", "experts_w_in", "experts_w_gate",
                           "experts_w_down"}
    y, upd = layer.apply({"params": params}, x, mutable=["intermediates"])
    spelled = MoEMLP(hidden=32, ffn=24, moe=dataclasses.replace(
        cfg, experts_held=8, first_expert=0, score_func="softmax",
        routed_scaling=1.0, expert_act="swiglu", shared_width=0,
        selection_bias=False))
    y2, upd2 = spelled.apply({"params": params}, x,
                             mutable=["intermediates"])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    assert set(upd["intermediates"]) == {
        "moe_aux_loss", "moe_tokens_per_expert", "moe_dropped"}
    for key, sown in upd["intermediates"].items():
        np.testing.assert_array_equal(
            np.asarray(sown[0]), np.asarray(upd2["intermediates"][key][0]))
    # and the formula, written out: un-normalised softmax gates, SwiGLU
    tok = x.reshape(-1, 32)
    probs = jax.nn.softmax(tok @ params["router"]["kernel"])
    gates, experts = jax.lax.top_k(probs, 2)
    want = jnp.zeros_like(tok)
    for j in range(2):
        for e in range(8):
            h = jax.nn.silu(tok @ params["experts_w_gate"][e]) * \
                (tok @ params["experts_w_in"][e])
            want = want + jnp.where(
                (experts[:, j] == e)[:, None],
                gates[:, j, None] * (h @ params["experts_w_down"][e]), 0.0)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 32)),
                               np.asarray(want), atol=1e-6)


def test_a_shared_expert_beside_swiglu_experts_is_a_swiglu():
    """Once refused (relu2 was the one form a shared expert had): it takes
    the routed experts' form, three matrices, and the count follows."""
    cfg = MoEConfig(num_experts=4, top_k=2, impl="grouped", shared_width=16,
                    dtype=jnp.float32)
    params = MoEMLP(hidden=32, ffn=24, moe=cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8, 32)))["params"]
    assert {k: v["kernel"].shape for k, v in params.items()
            if k.startswith("shared")} == {
        "shared_gate_proj": (32, 16), "shared_up_proj": (32, 16),
        "shared_down_proj": (16, 32)}
    llama = dataclasses.replace(LlamaConfig.nano(), hidden_size=32,
                                intermediate_size=24, moe=cfg)
    assert llama.ffn_params() == sum(
        a.size for a in jax.tree.leaves(params))


# ------------------------------------------------- attention's two fields

def test_attention_takes_an_explicit_head_size_and_no_rotation():
    cfg = dataclasses.replace(
        LlamaConfig.nano(), hidden_size=48, num_heads=4, num_kv_heads=2,
        attn_head_dim=32, rope=False, dtype=jnp.float32,
        use_flash_attention=False, num_layers=1, vocab_size=64)
    assert cfg.head_dim == 32 and LlamaConfig.nano().head_dim == 32
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    att = params["layers_0"]["attention"]
    assert att["q_proj"]["kernel"].shape == (48, 128)
    assert att["k_proj"]["kernel"].shape == (48, 64)
    assert att["o_proj"]["kernel"].shape == (128, 48)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 48))
    from dlrover_wuqiong_tpu.models.llama import LlamaAttention

    got = LlamaAttention(cfg).apply({"params": att}, x, None, None)
    want = ref.attention(x, att, n_head=4, n_kv_head=2, head_dim=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # with rotation the same parameters give another result
    from dlrover_wuqiong_tpu.models.llama import rope_freqs

    cos, sin = rope_freqs(32, 16, 10000.0)
    turned = LlamaAttention(dataclasses.replace(cfg, rope=True)).apply(
        {"params": att}, x, cos, sin)
    assert float(jnp.abs(turned - got).max()) > 1e-3


# ------------------------------------------------- the whole model

@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("held,first", [(0, 0), (2, 4)])
def test_loss_and_gradient_norm_match_the_reference(held, first, remat):
    cfg = nano(experts_held=held, first_expert=first, remat=remat)
    assert set(cfg.pattern) == {"M", "E", "*"}
    model, params, batch = seeded(cfg)
    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(reference_loss(cfg), params,
                                            batch, precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4


@pytest.mark.parametrize("wrong", ["softmax_router", "no_scaling",
                                   "bias_in_gates", "no_shared_expert",
                                   "decay_doubled", "no_skip_term",
                                   "conv_taps_reversed"])
def test_a_wrong_term_is_outside_the_tolerance(wrong, monkeypatch):
    """The check is tight: each variant a careless port would make moves
    the loss or the gradient norm by more than the tolerances above (the
    Mamba-2 ones are those read against the cell's limits on the chip:
    PERF.md section 6, PR 31)."""
    from dlrover_wuqiong_tpu.models import mamba2

    cfg = nano()
    model, params, batch = seeded(cfg)
    sys_params = params
    if wrong in ("decay_doubled", "no_skip_term"):
        monkeypatch.setattr(
            mamba2, "ssd_scan",
            lambda x, dlt, a, b, c, d, f=mamba2.ssd_scan, **kw:
            f(x, dlt, 2 * a, b, c, d, **kw) if wrong == "decay_doubled"
            else f(x, dlt, a, b, c, 0 * d, **kw))
    elif wrong == "conv_taps_reversed":
        sys_params = jax.tree_util.tree_map_with_path(
            lambda path, a: a[::-1] if "conv_kernel" in str(path[-1]) else a,
            params)
    if wrong == "softmax_router":
        monkeypatch.setattr(
            NemotronHConfig, "moe_config",
            lambda self, f=NemotronHConfig.moe_config:
            dataclasses.replace(f(self), score_func="softmax"))
    elif wrong == "no_scaling":
        model = NemotronH(dataclasses.replace(cfg, routed_scaling=1.0))
    elif wrong == "no_shared_expert":
        model = NemotronH(dataclasses.replace(cfg, shared_width=0))
    elif wrong == "bias_in_gates":
        from dlrover_wuqiong_tpu.models import moe

        monkeypatch.setattr(
            moe, "route_top_k",
            lambda probs, k, norm, bias=None, floor=True, scaling=1.0,
            f=moe.route_top_k: f(probs + bias, k, norm, None, floor,
                                 scaling))
    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            sys_params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(reference_loss(cfg), params,
                                            batch, precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss > 1e-5 or \
        abs(sys_norm - ref_norm) / ref_norm > 1e-4


# ------------------------------------------------- the parameter count

def test_num_params_is_the_cells_count_at_the_published_widths():
    from benchmark import cells

    cell = cells.load_cell("nemotron3_nano_30b_a3b.steady")
    model = cells.load_module("models", "nemotron_h").build(cell["config"])
    cfg = model.config
    assert cfg.mamba_config().num_params() + cfg.hidden_size == 38_744_896
    assert cfg.attention_config().attention_params() + cfg.hidden_size \
        == 23_399_040
    assert cfg.num_params() == 666_963_456
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 666_963_456
    ff = shapes["layers_1"]["feed_forward"]
    assert ff["router"]["kernel"].shape == (2688, 128)
    assert ff["selection_bias"].shape == (128,)
    assert ff["experts_w_in"].shape == (8, 2688, 1856)
    assert ff["shared_up_proj"]["kernel"].shape == (2688, 3712)
    whole = dataclasses.replace(cfg, experts_held=0, vocab_size=131072,
                                pattern=NemotronHConfig().pattern)
    assert 31.5e9 < whole.num_params() < 31.7e9  # the catalog's 31.6B


@pytest.mark.parametrize("held", [0, 2])
def test_num_params_is_the_tree_at_nano_size(held):
    cfg = nano(experts_held=held)
    params = NemotronH(cfg).init_params(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()


def test_llama_num_params_counts_by_head_size_and_the_expert_fields():
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=48, intermediate_size=24, num_layers=2,
        num_heads=4, num_kv_heads=2, attn_head_dim=32, max_seq_len=32,
        moe=_moe(experts_held=3, first_expert=1), dtype=jnp.float32,
        use_flash_attention=False)
    params = Llama(cfg).init_params(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()


# ------------------------------------------------- sharding and the optimizer

def test_sharding_rules_name_the_new_parameters():
    from jax.sharding import PartitionSpec as P

    from dlrover_wuqiong_tpu.parallel.sharding import (
        MOE_RULES,
        TRANSFORMER_RULES,
        spec_for_path,
    )

    rules = MOE_RULES + TRANSFORMER_RULES
    for path, want in [
            ("layers_0/mamba/in_proj/kernel", P("fsdp", "tp")),
            ("layers_0/mamba/out_proj/kernel", P("tp", "fsdp")),
            ("layers_0/mamba/conv_kernel", P()),
            ("layers_0/mamba/conv_bias", P()),
            ("layers_0/mamba/A_log", P()), ("layers_0/mamba/D", P()),
            ("layers_0/mamba/dt_bias", P()),
            ("layers_0/mamba/gate_norm_scale", P()),
            ("layers_0/norm/scale", P()),
            ("layers_1/feed_forward/selection_bias", P()),
            ("layers_1/feed_forward/router/kernel", P("fsdp", None)),
            ("layers_1/feed_forward/experts_w_in", P("ep", "fsdp", "tp")),
            ("layers_1/feed_forward/experts_w_down", P("ep", "tp", "fsdp")),
            ("layers_1/feed_forward/shared_up_proj/kernel", P("fsdp", "tp")),
            ("layers_1/feed_forward/shared_down_proj/kernel",
             P("tp", "fsdp")),
            ("layers_5/attention/q_proj/kernel", P("fsdp", "tp")),
            ("layers_5/attention/o_proj/kernel", P("tp", "fsdp"))]:
        assert spec_for_path(path, rules) == want, path


@pytest.mark.parametrize("rate", [0.0, 0.01])
def test_a_few_trainer_steps_count_the_share_and_leave_the_bias_alone(
        tmp_path, rate):
    """Falling loss through `Trainer.train()` on the 8-device test mesh
    under fsdp, `moe_rows_held` and `moe_rows_absent` in the
    `trainer:step_metrics` events, and a selection bias that neither the
    step nor weight decay has moved while every other leaf has; with the
    out-of-band rule on, the bias has moved, by 12 steps of one rate
    each at most."""
    from dlrover_wuqiong_tpu.telemetry import spans as tspans
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

    cfg = nano(experts_held=2, first_expert=2, remat=True,
               bias_update_rate=rate)
    _, _, batch = seeded(cfg, batch=8)
    batch = {k: np.asarray(v) for k, v in batch.items()}
    got = []
    args = TrainingArgs(
        output_dir=str(tmp_path), max_steps=12, global_batch_size=8,
        seq_len=SEQ, warmup_steps=1, learning_rate=3e-3, logging_steps=4,
        save_steps=0, fused_steps=1, perf_window_every=0,
        save_on_exit=False, resume=False, strategy=[("fsdp", {})])
    tr = Trainer(NemotronH(cfg), args, lambda step: dict(batch),
                 callbacks=[lambda step, m: got.append((step, m))])
    before = jax.tree.map(np.asarray, tr.state.params)
    tspans.clear_spans()
    try:
        tr.train()
    finally:
        tr.ckpt.close()
    assert [step for step, _ in got] == [4, 8, 12]
    assert got[-1][1]["loss"] < got[0][1]["loss"]
    events = [s["attrs"] for s in tspans.spans_snapshot()
              if s["name"] == "trainer:step_metrics"]
    assert len(events) == 3
    for a in events:
        assert a["moe_rows_held"] + a["moe_rows_absent"] == 8 * SEQ * 2 * 2
        assert a["moe_rows_held"] > 0 and a["moe_dropped"] == 0.0
        # off the TPU the grouped products walk the whole buffer
        assert a["moe_gmm_tiles_share"] == 1.0
        # and so do the elementwise passes between them, and the
        # gathers into expert order
        assert a["moe_map_tiles_share"] == 1.0
        assert a["moe_gather_rows_share"] == 1.0
        # and the sums by assignment index every assignment
        assert a["moe_combine_rows_share"] == 1.0
    after = jax.tree.map(np.asarray, tr.state.params)
    for path, old in jax.tree_util.tree_flatten_with_path(before)[0]:
        new = functools.reduce(lambda t, k: t[k.key], path, after)
        if path[-1].key == "selection_bias" and rate:
            moved = (new - old) / rate
            assert np.abs(moved).max() <= 12 + 1e-3 and (moved != 0).all()
            assert "param_steps" not in got[-1][1]
        elif path[-1].key == "selection_bias":
            assert old.any()
            np.testing.assert_array_equal(new, old)
        else:
            assert (new != old).any(), path
