"""The dropless path's two row movements (`models/moe.dispatch`,
`models/moe.combine`): a pair of transposes, each the other's backward
pass, both gathers through the sort and its inverse.  Held here, on the
CPU in float32, against the forms plain autodiff gives (`tokens[idx]` and
its scatter-add, `segment_sum` of a weighted copy), for every expert held
and for a chip's share of them.  A share's grouped products through the
kernel route of `ops/grouped_matmul.py` (interpret mode) are the plain
route's, and so is the gather into expert order that walks the held
rows' chunks alone there."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.models import moe
from dlrover_wuqiong_tpu.models.moe import (
    MoEConfig,
    MoEMLP,
    collect_moe_stats,
    combine,
    dispatch,
    gathered_rows,
    grouped_experts,
)
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm

T, K, D, F = 48, 6, 16, 12

# (experts the weights hold, first of them, experts the router names)
HOLDINGS = {"all_8": (8, 0, 8), "share_8_of_128_at_0": (8, 0, 128),
            "share_8_of_128_at_40": (8, 40, 128)}


def _routing(kind, held, first, num_experts):
    """experts (T, K), a token's K all different."""
    t = np.arange(T)[:, None]
    j = np.arange(K)[None, :]
    if kind == "even":
        experts = (t * K + j) % num_experts
    elif kind == "one_expert":  # every token's first choice is the same
        rest = (t * (K - 1) + j - 1) % (num_experts - 1)
        experts = np.where(j == 0, first + 1,
                           (first + 2 + rest) % num_experts)
    elif kind == "none_held":   # a share that receives no row
        experts = (first + held + (t * K + j) % (num_experts - held)) \
            % num_experts
    else:
        raise ValueError(kind)
    assert all(len(set(row)) == K for row in experts.tolist())
    return jnp.asarray(experts, jnp.int32)


CASES = [pytest.param(h, r, id=f"{h}-{r}")
         for h in HOLDINGS for r in ("even", "one_expert", "none_held")
         if not (h == "all_8" and r == "none_held")]


def _sorted(holding, routing):
    """What `grouped_experts` computes before it moves a row: (order,
    inv, held_rows, token_idx with the absent rows' index out of range,
    group_sizes)."""
    held, first, num_experts = HOLDINGS[holding]
    flat = _routing(routing, held, first, num_experts).reshape(-1) - first
    flat = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(flat)
    inv = jnp.argsort(order).reshape(T, K).T
    sizes = jnp.bincount(flat, length=held)
    held_rows = sizes.sum()
    if routing == "none_held":
        assert int(held_rows) == 0
    elif holding != "all_8":
        assert 0 < int(held_rows) < T * K
    token_idx = jnp.where(jnp.arange(T * K) < held_rows, order // K, T)
    return order, inv, held_rows, token_idx, sizes


def _draw(*shapes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32)
            for k, s in zip(keys, shapes)]


def _close(got, want):
    """To 1e-6 of the largest entry (float32 sums in another order)."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=1e-6 * max(1.0, np.abs(want).max(initial=0)))


@pytest.mark.parametrize("holding,routing", CASES)
def test_dispatch_is_the_plain_gather_and_its_scatter_add(holding, routing):
    order, inv, held_rows, token_idx, _ = _sorted(holding, routing)
    tokens, d_rows = _draw((T, D), (T * K, D))
    held = np.arange(T * K) < int(held_rows)

    def plain(x):
        return x.at[token_idx].get(mode="fill", fill_value=0)

    got, vjp = jax.vjp(lambda x: dispatch(x, order, inv, held_rows), tokens)
    want, plain_vjp = jax.vjp(plain, tokens)
    _close(got[held], want[held])
    _close(vjp(d_rows)[0], plain_vjp(d_rows)[0])


@pytest.mark.parametrize("holding,routing", CASES)
def test_combine_is_segment_sum_of_the_weighted_rows(holding, routing):
    order, inv, held_rows, token_idx, _ = _sorted(holding, routing)
    rows, gates, d_out = _draw((T * K, D), (T, K), (T, D), seed=1)
    held = np.arange(T * K) < int(held_rows)

    def plain(ys, g):
        return jax.ops.segment_sum(ys * g.reshape(-1)[order][:, None],
                                   token_idx, num_segments=T)

    got, vjp = jax.vjp(
        lambda ys, g: combine(ys, g, order, inv, held_rows), rows, gates)
    want, plain_vjp = jax.vjp(plain, rows, gates)
    _close(got, want)
    (d_rows, d_gates), (want_rows, want_gates) = vjp(d_out), plain_vjp(d_out)
    _close(d_gates, want_gates)
    # behind the held rows the plain form's gradient is zero and this
    # one's is the token's cotangent: `grouped_experts` masks every
    # product there, and the mask's transpose takes it out
    _close(d_rows[held], want_rows[held])
    assert np.isfinite(np.asarray(d_rows)).all()


@pytest.mark.parametrize("holding,routing", CASES)
def test_each_ones_backward_pass_is_the_other(holding, routing):
    order, inv, held_rows, _, _ = _sorted(holding, routing)
    tokens, rows, gates = _draw((T, D), (T * K, D), (T, K), seed=2)
    flat_gates = gates.reshape(-1)[order][:, None]

    _, vjp = jax.vjp(lambda x: dispatch(x, order, inv, held_rows), tokens)
    _close(vjp(rows)[0], combine(rows, None, order, inv, held_rows))
    _, vjp = jax.vjp(lambda ys: combine(ys, None, order, inv, held_rows),
                     rows)
    _close(vjp(tokens)[0], dispatch(tokens, order, inv, held_rows))
    _, vjp = jax.vjp(lambda ys: combine(ys, gates, order, inv, held_rows),
                     rows)
    _close(vjp(tokens)[0],
           dispatch(tokens, order, inv, held_rows) * flat_gates)
    # transposes: <dispatch(x), r> = <x, combine(r)> over the held rows
    held = (jnp.arange(T * K) < held_rows)[:, None]
    lhs = jnp.vdot(dispatch(tokens, order, inv, held_rows) * held, rows)
    rhs = jnp.vdot(tokens, combine(rows, None, order, inv, held_rows))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("holding,routing", CASES)
def test_what_lies_behind_the_held_rows_is_never_read(holding, routing):
    """No group writes the row buffer behind the held rows, and the TPU's
    grouped kernels leave there whatever was there: a NaN planted in
    those places of the rows, and of the rows' gradient, reaches no
    token's sum and no gate's gradient."""
    order, inv, held_rows, _, _ = _sorted(holding, routing)
    rows, gates, d_out = _draw((T * K, D), (T, K), (T, D), seed=3)
    tail = (jnp.arange(T * K) >= held_rows)[:, None]
    dirty = jnp.where(tail, jnp.nan, rows)
    clean = jnp.where(tail, 0.0, rows)

    def run(ys):
        out, vjp = jax.vjp(
            lambda g: combine(ys, g, order, inv, held_rows), gates)
        return out, vjp(d_out)[0]

    for got, want in zip(run(dirty), run(clean)):
        assert np.isfinite(np.asarray(got)).all()
        _close(got, want)
    _, vjp = jax.vjp(lambda x: dispatch(x, order, inv, held_rows), d_out)
    assert np.isfinite(np.asarray(vjp(dirty)[0])).all()
    _close(vjp(dirty)[0], vjp(clean)[0])


def _scatter_form(tokens, gates, experts, w_in, w_down, first, num_experts):
    """`grouped_experts` (relu^2) as it was before the rows moved by
    gathers: plain indexing and `segment_sum`, differentiated by JAX."""
    held = w_in.shape[0]
    flat = experts.reshape(-1) - first
    flat = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(flat)
    sizes = jnp.bincount(flat, length=held)
    held_row = jnp.arange(T * K) < sizes.sum()
    token_idx = jnp.where(held_row, order // K, T)
    xs = tokens.at[token_idx].get(mode="fill", fill_value=0)

    def grouped(lhs, rhs):
        return jnp.where(held_row[:, None],
                         jax.lax.ragged_dot(lhs, rhs, sizes), 0)

    ys = grouped(jnp.square(jax.nn.relu(grouped(xs, w_in))), w_down)
    flat_gates = gates.reshape(-1)[order]
    return jax.ops.segment_sum(ys * flat_gates[:, None], token_idx,
                               num_segments=T)


@pytest.mark.parametrize("holding,routing", CASES)
def test_the_expert_pass_keeps_its_value_and_every_gradient(holding,
                                                            routing):
    held, first, num_experts = HOLDINGS[holding]
    experts = _routing(routing, held, first, num_experts)
    tokens, gates, w_in, w_down = _draw(
        (T, D), (T, K), (held, D, F), (held, F, D), seed=4)
    w_in, w_down = 0.2 * w_in, 0.2 * w_down  # results of order one

    def new(*args):
        out, sizes = grouped_experts(args[0], args[1], experts, None,
                                     args[2], args[3], first_expert=first,
                                     num_experts=num_experts)
        return jnp.sum(jnp.sin(out)), (out, sizes)

    def old(*args):
        out = _scatter_form(args[0], args[1], experts, args[2], args[3],
                            first, num_experts)
        return jnp.sum(jnp.sin(out)), out

    args = (tokens, gates, w_in, w_down)
    (_, (got, sizes)), got_g = jax.value_and_grad(
        new, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    (_, want), want_g = jax.value_and_grad(
        old, argnums=(0, 1, 2, 3), has_aux=True)(*args)
    _close(got, want)
    for g, w in zip(got_g, want_g):
        _close(g, w)
    assert sizes.shape == (held,)
    if routing == "none_held":
        assert int(sizes.sum()) == 0 and not np.asarray(got).any()


CHUNK = 64  # `moe._GATHER_CHUNK` on the kernel route here: two row tiles

# an expert's form -> the gate's activation (None: relu^2, no gate matrix)
FORMS = {"relu2": None, "reglu": jax.nn.relu, "swiglu": jax.nn.silu}


def _on_the_kernel_route(monkeypatch, poison):
    """What a share takes on one TPU device, here: the layer's own route
    decision with the backend said to be the TPU and a row tile of 32
    (T*k = 288 rows: nine tiles, of which the held rows fill one or
    none) and a gather chunk of two tiles (four and a half of them: the
    last turn of an all-held buffer is moved back onto its end), every
    kernel in interpret mode.  `poison`: every place a kernel may leave
    unwritten is handed on as NaN — the rows of no group in a product,
    the tiles no grid step visits in a map, the row buffers behind the
    chunks `dispatch` gathers (of the tokens and of the cotangent) —
    forward and backward.  Returns the list the kernels' calls are noted
    in."""
    calls = []
    kernels, maps, gmm, rows_map = (gm._grouped_kernels, gm._rows_map_kernels,
                                    gm._gmm, gm._rows_map)

    def poisoned_gmm(lhs, rhs, sizes, **kw):
        out = gmm(lhs, rhs, sizes, **kw)
        behind = jnp.arange(out.shape[0]) >= sizes.sum()
        return jnp.where(behind[:, None], jnp.nan, out)

    def poisoned_map(held_rows, *buffers, fn, tile, **kw):
        calls.append(fn.__name__.lstrip("_"))
        outs = rows_map(held_rows, *buffers, fn=fn, tile=tile, **kw)
        unvisited = jnp.arange(outs[0].shape[0]) >= -(-held_rows // tile) * tile
        return [jnp.where(unvisited[:, None], jnp.nan, o) for o in outs]

    def noting_kernels(lhs, rhs, sizes):
        calls.append(lhs.shape)
        return kernels(lhs, rhs, sizes, interpret=True)

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    monkeypatch.setattr(gm, "_ROW_TILE", 32)
    monkeypatch.setattr(gm, "_grouped_kernels", noting_kernels)
    monkeypatch.setattr(gm, "_rows_map_kernels",
                        functools.partial(maps, interpret=True))
    monkeypatch.setattr(gm, "_unwritten_kernel", functools.partial(
        gm._unwritten_kernel, interpret=True))
    monkeypatch.setattr(moe, "_GATHER_CHUNK", CHUNK)
    if poison:
        monkeypatch.setattr(gm, "_gmm", poisoned_gmm)
        monkeypatch.setattr(gm, "_rows_map", poisoned_map)
        monkeypatch.setattr(gm, "_unwritten_kernel", lambda rows, like: (
            jnp.full((rows, like.shape[1]), jnp.nan, like.dtype)))
    return calls


@pytest.mark.parametrize("held", [0, 1, CHUNK, CHUNK + 1, T * K])
def test_the_chunked_dispatch_is_the_plain_gather_on_the_held_rows(
        monkeypatch, held):
    """`dispatch` on the kernel route against `tokens[order // k]`: the
    same rows, to the bit, wherever a row is held; behind the last turn
    of the loop the buffer is what it was (NaN here), and what
    `gathered_rows` counts is what was fetched; the backward pass reads
    the held rows alone on either route."""
    _on_the_kernel_route(monkeypatch, poison=True)
    order = jax.random.permutation(jax.random.PRNGKey(held), T * K)
    inv = jnp.argsort(order).reshape(T, K).T
    tokens, d_rows = _draw((T, D), (T * K, D), seed=6)
    held_rows = jnp.asarray(held, jnp.int32)

    def both(route):
        return jax.vjp(lambda x: dispatch(x, order, inv, held_rows, route),
                       tokens)

    (got, vjp), (want, plain_vjp) = both("kernel"), both("plain")
    fetched, of = (int(n) for n in gathered_rows(held_rows[None], T * K,
                                                 "kernel"))
    assert (fetched, of) == (min(-(-held // CHUNK) * CHUNK, T * K), T * K)
    got = np.asarray(got)
    np.testing.assert_array_equal(got[:fetched], np.asarray(want)[:fetched])
    assert held <= fetched and np.isnan(got[fetched:]).all()
    np.testing.assert_array_equal(np.asarray(vjp(d_rows)[0]),
                                  np.asarray(plain_vjp(d_rows)[0]))
    assert [int(n) for n in gathered_rows(held_rows[None], T * K, "plain")] \
        == [T * K, T * K]


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_a_share_counts_the_rows_its_dispatch_fetches(monkeypatch, route):
    """A layer that holds 2 of 8 experts sows `moe_gather_rows` beside
    its tile counts — the held rows rounded up to a turn of the loop on
    the kernel route, every row on the plain — and `collect_moe_stats`
    reduces it to `moe_gather_rows_share`; a whole layer sows none."""
    cfg = dict(num_experts=8, top_k=3, impl="grouped", aux_loss="none",
               expert_act="relu2", dtype=jnp.float32)
    x = _draw((2, 48, D), seed=7)[0]
    rows = 2 * 48 * 3
    if route == "kernel":
        _on_the_kernel_route(monkeypatch, poison=False)
    for held in (8, 2):
        layer = MoEMLP(hidden=D, ffn=F, moe=MoEConfig(
            **cfg, experts_held=held % 8))
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        out, upd = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
        assert np.isfinite(np.asarray(out)).all()
        inter, stats = upd["intermediates"], collect_moe_stats(
            upd["intermediates"])
        if held == 8:
            assert "moe_gather_rows" not in inter
            assert "moe_gather_rows_share" not in stats
            continue
        held_rows = int(inter["moe_rows_held"][0])
        assert 0 < held_rows < rows - CHUNK
        fetched = -(-held_rows // CHUNK) * CHUNK if route == "kernel" \
            else rows
        assert [int(n) for n in inter["moe_gather_rows"][0]] \
            == [fetched, rows]
        assert float(stats["moe_gather_rows_share"]) == pytest.approx(
            fetched / rows)


def _expert_pass(form, holding, routing, seed=5):
    """(a function of (tokens, gates, w_gate or None, w_in, w_down) ->
    loss, (out, sizes); its arguments; the layer's weights' shapes)."""
    held, first, num_experts = HOLDINGS[holding]
    experts = _routing(routing, held, first, num_experts)
    tokens, gates, w_gate, w_in, w_down = _draw(
        (T, D), (T, K), (held, D, F), (held, D, F), (held, F, D), seed=seed)
    gated = FORMS[form] is not None

    def run(tokens, gates, w_gate, w_in, w_down):
        out, sizes = grouped_experts(
            tokens, gates, experts, w_gate if gated else None, w_in, w_down,
            first_expert=first, num_experts=num_experts,
            gate_act=FORMS[form] or jax.nn.silu)
        return jnp.sum(jnp.sin(out)), (out, sizes)

    return run, (tokens, gates, 0.2 * w_gate, 0.2 * w_in, 0.2 * w_down)


@pytest.mark.parametrize("poison", [False, True], ids=["", "poisoned"])
@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("holding,routing", [
    c for c in CASES if c.values[0] != "all_8"])
def test_a_shares_pass_through_the_kernels_is_the_plain_routes(
        monkeypatch, holding, routing, form, poison):
    """`grouped_experts` on the route a share takes on one TPU device —
    the grouped products in `dwt_gmm` / `dwt_gmm_t` / `dwt_tgmm`, the
    activation, the sum of two first products' row gradients and the
    combine's backward pair in `dwt_rows_map_*`, the gathers into expert
    order over the held rows' chunks, here in interpret mode — against
    the route every CPU run takes: the output, `group_sizes` and every
    gradient, for relu^2, ReGLU and SwiGLU experts.  Poisoned, a NaN
    stands wherever a kernel or `dispatch`'s loop may leave a place
    unwritten, in every buffer the maps and the products read: the loss
    and every gradient are finite and the plain route's all the same."""
    run, args = _expert_pass(form, holding, routing)
    gated = FORMS[form] is not None
    both = jax.value_and_grad(run, argnums=(0, 1, 2, 3, 4), has_aux=True)
    weights = [a.shape for a in args[2 if gated else 3:]]
    num_experts = HOLDINGS[holding][2]
    assert gm.experts_route(T * K, weights, num_experts) == "plain"
    (want_loss, (want, want_sizes)), want_g = both(*args)
    calls = _on_the_kernel_route(monkeypatch, poison)
    assert gm.experts_route(T * K, weights, num_experts) == "kernel"
    (loss, (got, got_sizes)), got_g = both(*args)
    assert [c for c in calls if isinstance(c, tuple)] \
        == [(T * K, D), (T * K, F)]
    if poison:
        act = {"relu2": "relu2", "reglu": "gated_relu",
               "swiglu": "gated_silu"}[form]
        assert sorted(c for c in calls if isinstance(c, str)) == sorted(
            [act, f"{act}_bwd", "weigh"] + ["add"] * gated)
    assert np.isfinite(float(loss))
    # (a sum of T*D sines of either sign)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-6 * T * D)
    _close(got, want)
    np.testing.assert_array_equal(np.asarray(got_sizes),
                                  np.asarray(want_sizes))
    for g, w in zip(got_g, want_g):
        assert np.isfinite(np.asarray(g)).all()
        _close(g, w)


@pytest.mark.parametrize("form", FORMS)
def test_one_product_off_the_kernels_takes_the_whole_layer_off_them(
        monkeypatch, form):
    """ONE route a layer call: where the first products' blocks pass the
    VMEM bound and the last product's do not, `gmm_route` alone would
    send the last one to the kernels; the layer's route is "plain" and
    its program holds no Pallas call — no map leaves a tile unwritten
    that `lax.ragged_dot` then reads."""
    run, args = _expert_pass(form, "share_8_of_128_at_40", "even")
    plain = str(jax.make_jaxpr(jax.grad(lambda *a: run(*a)[0],
                                        argnums=(0, 1, 2, 3, 4)))(*args))
    calls = _on_the_kernel_route(monkeypatch, poison=True)
    monkeypatch.setattr(
        gm, "_vmem_bytes",
        lambda c, n: gm._VMEM_LIMIT + 1 if c == D else 0)
    assert gm.gmm_route((T * K, D), (8, D, F), 128) == "plain"
    assert gm.gmm_route((T * K, F), (8, F, D), 128) == "kernel"
    assert moe.layer_route(T * K, *args[2:], 128) == "plain"
    traced = str(jax.make_jaxpr(jax.grad(lambda *a: run(*a)[0],
                                         argnums=(0, 1, 2, 3, 4)))(*args))
    assert not calls and "pallas_call" not in traced
    assert traced == plain
