"""The dropless path's two row movements (`models/moe.dispatch`,
`models/moe.combine`): a pair of transposes, each the other's backward
pass, both gathers through the sort and its inverse.  Held here, on the
CPU in float32, against the forms plain autodiff gives (`tokens[idx]` and
its scatter-add, `segment_sum` of a weighted copy), for every expert held
and for a chip's share of them.  A share's grouped products through the
kernel route of `ops/grouped_matmul.py` (interpret mode) are the plain
route's, and so is the gather into expert order that walks the held
rows' chunks alone there."""


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
        lambda ys, g: combine(ys, g, g.reshape(-1)[order], order, inv,
                              held_rows), rows, gates)
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
    _close(vjp(rows)[0], combine(rows, None, None, order, inv, held_rows))
    _, vjp = jax.vjp(lambda ys: combine(ys, None, None, order, inv, held_rows),
                     rows)
    _close(vjp(tokens)[0], dispatch(tokens, order, inv, held_rows))
    _, vjp = jax.vjp(lambda ys: combine(ys, gates, flat_gates[:, 0], order,
                                        inv, held_rows), rows)
    _close(vjp(tokens)[0],
           dispatch(tokens, order, inv, held_rows) * flat_gates)
    # transposes: <dispatch(x), r> = <x, combine(r)> over the held rows
    held = (jnp.arange(T * K) < held_rows)[:, None]
    lhs = jnp.vdot(dispatch(tokens, order, inv, held_rows) * held, rows)
    rhs = jnp.vdot(tokens, combine(rows, None, None, order, inv, held_rows))
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
            lambda g: combine(ys, g, g.reshape(-1)[order], order, inv,
                              held_rows), gates)
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


# `moe._GATHER_CHUNK` and `moe._SUM_CHUNK` on the kernel route here: two
# row tiles
CHUNK = 64

# an expert's form -> the gate's activation (None: relu^2, no gate matrix)
FORMS = {"relu2": None, "reglu": jax.nn.relu, "swiglu": jax.nn.silu}


def _on_the_kernel_route(request, poison):
    """What a share takes on one TPU device, here (`held_rows_interpreted`
    of tests/conftest.py): the layer's own route decision with the
    backend said to be the TPU and a row tile of 32 (T*k = 288 rows:
    nine tiles, of which the held rows fill one or none), every kernel
    in interpret mode; and a gather chunk of two tiles (four and a half
    of them: the last turn of an all-held buffer is moved back onto its
    end).  `poison`: every place a kernel may leave
    unwritten is handed on as NaN — the rows of no group in a product,
    the tiles no grid step visits in a map, the row buffers behind the
    chunks `dispatch` gathers (of the tokens and of the cotangent) —
    forward and backward.  Returns the list the kernels' calls are noted
    in."""
    request.getfixturevalue("held_rows_interpreted")
    monkeypatch = request.getfixturevalue("monkeypatch")
    calls = []
    kernels, gmm, rows_map = gm._grouped_kernels, gm._gmm, gm._rows_map

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
        return kernels(lhs, rhs, sizes)

    monkeypatch.setattr(gm, "_grouped_kernels", noting_kernels)
    monkeypatch.setattr(moe, "_GATHER_CHUNK", CHUNK)
    monkeypatch.setattr(moe, "_SUM_CHUNK", CHUNK)
    if poison:
        monkeypatch.setattr(gm, "_gmm", poisoned_gmm)
        monkeypatch.setattr(gm, "_rows_map", poisoned_map)
        monkeypatch.setattr(gm, "_unwritten_kernel", lambda rows, like: (
            jnp.full((rows, like.shape[1]), jnp.nan, like.dtype)))
    return calls


@pytest.mark.parametrize("held", [0, 1, CHUNK, CHUNK + 1, T * K])
def test_the_chunked_dispatch_is_the_plain_gather_on_the_held_rows(
        request, held):
    """`dispatch` on the kernel route against `tokens[order // k]`: the
    same rows, to the bit, wherever a row is held; behind the last turn
    of the loop the buffer is what it was (NaN here), and what
    `gathered_rows` counts is what was fetched; the backward pass reads
    the held rows alone on either route."""
    _on_the_kernel_route(request, poison=True)
    order = jax.random.permutation(jax.random.PRNGKey(held), T * K)
    inv = jnp.argsort(order).reshape(T, K).T
    tokens, d_rows = _draw((T, D), (T * K, D), seed=6)
    held_rows = jnp.asarray(held, jnp.int32)

    def both(route):
        back = inv if route == "plain" else moe._held_by_token(
            order, jnp.ones(T * K), (inv < held_rows).T)
        return jax.vjp(lambda x: dispatch(x, order, back, held_rows, route),
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
def test_a_share_counts_the_rows_its_dispatch_fetches(request, route):
    """A layer that holds 2 of 8 experts sows `moe_gather_rows` beside
    its tile counts — the held rows rounded up to a turn of the loop on
    the kernel route, every row on the plain — and `moe_combine_rows`,
    the index entries of a sum by assignment; `collect_moe_stats`
    reduces them to `moe_gather_rows_share` and
    `moe_combine_rows_share`; a whole layer sows neither."""
    cfg = dict(num_experts=8, top_k=3, impl="grouped", aux_loss="none",
               expert_act="relu2", dtype=jnp.float32)
    x = _draw((2, 48, D), seed=7)[0]
    rows = 2 * 48 * 3
    if route == "kernel":
        _on_the_kernel_route(request, poison=False)
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
            for counter in ("moe_gather_rows", "moe_combine_rows"):
                assert counter not in inter
                assert f"{counter}_share" not in stats
            continue
        held_rows = int(inter["moe_rows_held"][0])
        assert 0 < held_rows < rows - CHUNK
        fetched = -(-held_rows // CHUNK) * CHUNK if route == "kernel" \
            else rows
        assert [int(n) for n in inter["moe_gather_rows"][0]] \
            == [fetched, rows]
        assert float(stats["moe_gather_rows_share"]) == pytest.approx(
            fetched / rows)
        # and the index entries a sum by assignment fetches: a chunk and
        # its halo a turn over the held rows and one a token on the
        # kernel route, every assignment on the plain
        indexed = -(-held_rows // CHUNK) * (CHUNK + moe._halo(3)) + 2 * 48 \
            if route == "kernel" else rows
        assert [int(n) for n in inter["moe_combine_rows"][0]] \
            == [indexed, rows]
        assert float(stats["moe_combine_rows_share"]) == pytest.approx(
            indexed / rows)
        assert (indexed < rows) == (route == "kernel")


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
        request, holding, routing, form, poison):
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
    calls = _on_the_kernel_route(request, poison)
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
        request, monkeypatch, form):
    """ONE route a layer call: where the first products' blocks pass the
    VMEM bound and the last product's do not, `gmm_route` alone would
    send the last one to the kernels; the layer's route is "plain" and
    its program holds no Pallas call — no map leaves a tile unwritten
    that `lax.ragged_dot` then reads."""
    run, args = _expert_pass(form, "share_8_of_128_at_40", "even")
    plain = str(jax.make_jaxpr(jax.grad(lambda *a: run(*a)[0],
                                        argnums=(0, 1, 2, 3, 4)))(*args))
    calls = _on_the_kernel_route(request, poison=True)
    monkeypatch.setattr(
        gm, "_vmem_bytes",
        lambda c, n: gm._VMEM_LIMIT + 1 if c == D else 0)
    assert gm.gmm_route((T * K, D), (8, D, F), 128) == "plain"
    assert gm.gmm_route((T * K, F), (8, F, D), 128) == "kernel"
    assert gm.experts_route(T * K, args[2:], 128) == "plain"
    traced = str(jax.make_jaxpr(jax.grad(lambda *a: run(*a)[0],
                                         argnums=(0, 1, 2, 3, 4)))(*args))
    assert not calls and "pallas_call" not in traced
    assert traced == plain


# ------- the bookkeeping (PR 45), against the `jax.numpy` lines it replaces

def _assignments(kind, k, num_experts, first=0, held=None, tokens=T):
    """experts (tokens, k), a token's k all different: drawn from all of
    them ("random"), from all but experts `first .. first + held - 1`
    ("absent": every assignment misses the share that holds those), or
    from all but expert `first` ("one_empty")."""
    held = held or num_experts
    names = np.arange(num_experts)
    if kind == "absent":
        names = np.setdiff1d(names, np.arange(first, first + held))
    elif kind == "one_empty":
        names = np.setdiff1d(names, [first])
    elif kind != "random":
        raise ValueError(kind)
    rng = np.random.default_rng(k * 1000 + num_experts)
    return jnp.asarray(np.stack([rng.choice(names, k, replace=False)
                                 for _ in range(tokens)]), jnp.int32)


def _held_number(experts, first, held):
    """The (T*k,) assignments as `grouped_experts` sorts them: a held
    expert's number among the held, `held` for an absent one."""
    flat = experts.reshape(-1) - first
    return jnp.where((flat >= 0) & (flat < held), flat, held)


def _parent_order(flat_expert, gates):
    order = jnp.argsort(flat_expert)
    return order, jax.lax.stop_gradient(gates).reshape(-1)[order]


def _parent_numbers(numbers, order, held_rows, k):
    inv = jnp.argsort(order).reshape(-1, k).T
    return jnp.where(inv < held_rows, numbers[inv], 0.0).T


def _parent_counts(experts, num_experts):
    return jnp.bincount(experts.reshape(-1), length=num_experts)


def _parent_route_top_k(probs, top_k, norm_topk_prob=True, bias=None,
                        floor=True, scaling=1.0):
    if bias is None:
        gates, experts = jax.lax.top_k(probs, top_k)
    else:
        _, experts = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias), top_k)
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        total = gates.sum(-1, keepdims=True)
        gates = gates / (jnp.maximum(total, 1e-9) if floor
                         else total + 1e-20)
    if scaling != 1.0:
        gates = gates * scaling
    return gates, experts


def _the_parents_lines(monkeypatch):
    """`models/moe.py` with the bookkeeping as the `jax.numpy` lines it
    was: `bincount`, `argsort` and a gather of the gates, a gather of
    the dots through `inv`, `take_along_axis`."""
    monkeypatch.setattr(moe, "expert_counts", _parent_counts)
    monkeypatch.setattr(moe, "_expert_order", _parent_order)
    monkeypatch.setattr(moe, "_numbers_by_assignment", _parent_numbers)
    monkeypatch.setattr(moe, "route_top_k", _parent_route_top_k)


def _same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", ["random", "absent", "one_empty"])
@pytest.mark.parametrize("k", [1, 6, 8])
@pytest.mark.parametrize("num_experts", [16, 64, 128])
def test_the_counts_are_bincounts(num_experts, k, kind):
    """`expert_counts` is `jnp.bincount` over all the experts, and the
    groups' sizes of a share are its held part — also where no
    assignment falls on a held expert, and where an expert has no row."""
    first, held = num_experts // 2, num_experts // 4
    experts = _assignments(kind, k, num_experts, first, held)
    got = moe.expert_counts(experts, num_experts)
    _same_bits(got, _parent_counts(experts, num_experts))
    assert int(got.sum()) == T * k
    flat = _held_number(experts, first, held)
    _same_bits(got[first:first + held], jnp.bincount(flat, length=held))
    if kind == "absent":
        assert not int(got[first:first + held].sum())
    if kind == "one_empty":
        assert not int(got[first])
    # and of a batch of sequences, as `MoEMLP` never hands it but may
    _same_bits(moe.expert_counts(experts.reshape(4, T // 4, k),
                                 num_experts), got)


# (experts held, first of them, experts the router names), assignments
SORTED = [pytest.param(h, kind, id=f"{h[0]}_of_{h[2]}_at_{h[1]}-{kind}")
          for h in [(16, 0, 16), (8, 0, 128), (8, 40, 128)]
          for kind in ("random", "absent", "one_empty")
          if not (kind == "absent" and h[0] == h[2])]


@pytest.mark.parametrize("k", [1, 6, 8])
@pytest.mark.parametrize("holding,kind", SORTED)
def test_one_sort_orders_the_assignments_and_their_gates(holding, kind, k):
    """`_expert_order` is `argsort` (stable) and the gather of the gates
    through it; the gates in expert order carry no gradient."""
    held, first, num_experts = holding
    experts = _assignments(kind, k, num_experts, first, held)
    flat = _held_number(experts, first, held)
    gates, = _draw((T, k), seed=8)
    _same_bits(moe._expert_order(flat, gates), _parent_order(flat, gates))
    d_gates = jax.grad(lambda g: moe._expert_order(flat, g)[1].sum())(gates)
    assert not np.asarray(d_gates).any()


@pytest.mark.parametrize("held", [0, 1, 100, T * K])
@pytest.mark.parametrize("k", [1, 6, 8])
def test_a_sort_on_the_order_takes_numbers_back_by_assignment(k, held):
    """`_numbers_by_assignment` is the gather through `inv` under its
    mask: what lies behind the held rows (NaN here) reaches nothing."""
    held = min(held, T * k)
    order = jax.random.permutation(jax.random.PRNGKey(k + held), T * k)
    numbers, = _draw((T * k,), seed=9)
    numbers = jnp.where(jnp.arange(T * k) < held, numbers, jnp.nan)
    held_rows = jnp.asarray(held, jnp.int32)
    got = moe._numbers_by_assignment(numbers, order, held_rows, k)
    _same_bits(got, _parent_numbers(numbers, order, held_rows, k))
    assert np.isfinite(np.asarray(got)).all()
    assert int((np.asarray(got) != 0).sum()) == held


@pytest.mark.parametrize("norm,floor,scaling", [
    (True, True, 1.0), (True, False, 2.5), (False, True, 1.0)])
@pytest.mark.parametrize("k", [1, 6, 8])
@pytest.mark.parametrize("biased", [False, True], ids=["", "biased"])
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_the_gates_are_a_select_of_the_scores(jitted, biased, k, norm, floor,
                                              scaling):
    """`route_top_k` reads the scores at the chosen experts by a select
    and a sum: the gates `top_k` hands back — under a selection bias
    `take_along_axis`'s — the same experts, and the same gradient to the
    scores, bit for bit; the bias gets none.  Under `jit` too: without
    its barrier the compiler merges the select's sum over E with the
    normaliser's over k, the k scores add up in another order and a
    third of the gates lose their last bit."""
    logits, bias, d_gates = _draw((T, 64), (64,), (T, k), seed=10)
    probs = jax.nn.sigmoid(logits)

    def run(fn):
        def both(probs, bias, d_gates):
            def routed(p, b):
                return fn(p, k, norm, b if biased else None, floor, scaling)

            gates, vjp, experts = jax.vjp(routed, probs, bias, has_aux=True)
            return gates, experts, vjp(d_gates)

        return (jax.jit(both) if jitted else both)(probs, bias, d_gates)

    got, want = run(moe.route_top_k), run(_parent_route_top_k)
    _same_bits(got, want)
    assert not np.asarray(got[2][1]).any()
    # the choice follows the biased scores, the gates do not
    assert biased == (np.asarray(got[1]) != np.asarray(
        jax.lax.top_k(probs, k)[1])).any()


ROUTED = [pytest.param(route, *case.values, id=f"{route}-{case.id}")
          for route in ("plain", "kernel") for case in SORTED
          if not (route == "kernel" and case.values[0][0] == 16)]


@pytest.mark.parametrize("k", [1, 6, 8])
@pytest.mark.parametrize("route,holding,kind", ROUTED)
def test_the_expert_pass_is_the_parents_bit_for_bit(request, monkeypatch,
                                                    route, holding, kind, k):
    """`grouped_experts` with the bookkeeping as dense vector ops against
    the same pass with the `jax.numpy` lines it had (`bincount`,
    `argsort` and two gathers of T*k numbers): the output, the groups'
    sizes and every gradient to the bit, on the route every CPU run
    takes and on a share's kernels (interpret mode) — also where no
    assignment reaches the share, and where an expert has no row."""
    held, first, num_experts = holding
    tokens = 64  # T*k a multiple of the kernels' row tile here, k = 1 too
    experts = _assignments(kind, k, num_experts, first, held, tokens)
    x, gates, w_gate, w_in, w_down = _draw(
        (tokens, D), (tokens, k), (held, D, F), (held, D, F), (held, F, D),
        seed=11)
    args = (x, gates, 0.2 * w_gate, 0.2 * w_in, 0.2 * w_down)

    def run(x, gates, w_gate, w_in, w_down):
        out, sizes = grouped_experts(x, gates, experts, w_gate, w_in, w_down,
                                     first_expert=first,
                                     num_experts=num_experts)
        return jnp.sum(jnp.sin(out)), (out, sizes)

    both = jax.value_and_grad(run, argnums=(0, 1, 2, 3, 4), has_aux=True)
    if route == "kernel":
        _on_the_kernel_route(request, poison=False)
    assert gm.experts_route(tokens * k, args[2:], num_experts) == route
    got = both(*args)
    _the_parents_lines(monkeypatch)
    want = both(*args)
    _same_bits(got, want)
    flat = _held_number(experts, first, held)
    _same_bits(got[0][1][1], jnp.bincount(flat, length=held))
    if kind == "absent":
        assert not np.asarray(got[0][1][0]).any()


# (experts held, first of them) of 16, a selection bias with its rule,
# the auxiliary term, k, the layer's route
LAYERS = [pytest.param(held, first, biased, aux, k, route,
                       id=f"{held}_at_{first}-{'biased-' * biased}{aux}"
                          f"-k{k}-{route}")
          for held, first in ((16, 0), (4, 8)) for biased in (False, True)
          for aux in ("topk", "none") for k in (1, 6, 8)
          for route in ("plain", "kernel")
          if not (route == "kernel" and held == 16)]


@pytest.mark.parametrize("held,first,biased,aux,k,route", LAYERS)
def test_the_layer_is_the_parents_bit_for_bit(request, monkeypatch, held,
                                              first, biased, aux, k, route):
    """`MoEMLP` counts its assignments ONCE (the auxiliary term, the
    bias's rule and the groups' sizes read that count) and routes by a
    select: against the layer with the parent's lines (three
    `bincount`s, `take_along_axis`, `argsort` and the gathers of
    numbers) the output, everything it sows and every gradient agree to
    the bit, a whole layer and a share, on both routes."""
    cfg = MoEConfig(
        num_experts=16, top_k=k, impl="grouped", aux_loss=aux,
        aux_loss_weight=0.01, dtype=jnp.float32, selection_bias=biased,
        bias_update_rate=0.05 * biased,
        score_func="sigmoid" if biased else "softmax",
        routed_scaling=2.5 if biased else 1.0, experts_held=held % 16,
        first_expert=first)
    layer = MoEMLP(hidden=D, ffn=F, moe=cfg)
    x, = _draw((2, 32, D), seed=12)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]

    def run(params, x):
        out, upd = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
        inter = upd["intermediates"]
        return (jnp.sum(jnp.sin(out)) + moe.collect_moe_aux_loss(inter),
                (out, inter))

    def both(params, x):
        # the plain route under `jit`, as a step runs it (what the
        # compiler fuses it adds up in its own order: `route_top_k`'s
        # barrier); the kernels in interpret mode stay eager, and so does
        # a sigmoid router under the "topk" term, which no model has:
        # there the CPU compiler contracts the term's gradient, load * c,
        # and its sum with the select's into one fused multiply-add, and
        # a few of the router's gradients keep the bit the parent's
        # product rounds away
        fn = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
        jitted = route == "plain" and not (biased and aux == "topk")
        return (jax.jit(fn) if jitted else fn)(params, x)

    if route == "kernel":
        _on_the_kernel_route(request, poison=False)
    got = both(params, x)
    inter = got[0][1][1]
    assert ("moe_aux_loss" in inter) == (aux == "topk")
    assert ("moe_selection_bias_step" in inter) == biased
    assert ("moe_rows_held" in inter) == (held < 16)
    assert gm.experts_route(64 * k, [params[f"experts_w_{w}"] for w in (
        "gate", "in", "down")], 16) == route
    if held < 16:
        assert int(inter["moe_rows_held"][0]) + int(
            inter["moe_rows_absent"][0]) == 64 * k
    _the_parents_lines(monkeypatch)
    _same_bits(got, both(params, x))


# ------- the sums by assignment over the held rows alone (PR 50), against
# ------- the gather through the sort's inverse

def _ways_back(experts, first, held, gates):
    """What `grouped_experts` hands `dispatch` and `combine` on either
    route: (order, the gates in expert order, held_rows, {route: the way
    back from rows to tokens})."""
    tokens, k = experts.shape
    flat = _held_number(experts, first, held)
    order, flat_gates = moe._expert_order(flat, gates)
    held_rows = (flat < held).sum().astype(jnp.int32)
    return order, flat_gates, held_rows, {
        "plain": jnp.argsort(order).reshape(tokens, k).T,
        "kernel": moe._held_by_token(order, flat_gates,
                                     flat.reshape(tokens, k) < held)}


# (experts held of 64, the share of the rows that is): none, a chip's 4
# and 18 of 64, every one
SHARES = {"none": 0, "6pct": 4, "28pct": 18, "all": 64}
SUMS = [pytest.param(share, k, id=f"{share}-k{k}")
        for share in SHARES for k in (1, 6, 8)]


@pytest.mark.parametrize("share,k", SUMS)
def test_the_sums_over_the_held_rows_are_the_gathers_by_assignment(
        request, share, k):
    """`combine` (with gates) and `dispatch`'s backward pass (without)
    on the kernel route — a loop over the held rows' chunks in
    assignment order, a token's rows added where they lie side by side,
    one gather of T entries — against the plain route's gather of all
    T*k rows through the inverse (`_by_assignment`): the sums, the rows'
    gradient over the held rows and the gates' gradient bit for bit,
    bfloat16 rows weighted and added in float32 and rounded once.  No
    row held, a chip's share (under one turn of the loop; several and a
    part of one), every row held; k = 1 (nothing to add), 6 and 8;
    tokens whose rows lie across a turn's edge; and NaN in every row
    behind the held ones, which nothing may read."""
    held, tokens = SHARES[share], 96
    rows = tokens * k
    _on_the_kernel_route(request, poison=True)
    experts = _assignments("absent" if not held else "random", k, 64, 0,
                           held or 8, tokens)
    gates, ys, d_ys, d_out = _draw((tokens, k), (rows, D), (rows, D),
                                   (tokens, D), seed=13)
    # gates that are powers of two, of either sign: a turn of the loop is
    # compiled as one program, and the CPU's compiler contracts its
    # product and sum into one fused multiply-add, which keeps the bits a
    # product of its own rounds away (the TPU has no such instruction)
    gates = jnp.sign(gates) * 2.0 ** jnp.round(jnp.abs(gates) * 2 - 2)
    order, flat_gates, held_rows, back = _ways_back(experts, 0, held, gates)
    n = int(held_rows)
    assert n == {"none": 0, "all": rows}.get(share, n)
    if share in ("6pct", "28pct"):
        assert 0.5 * held / 64 < n / rows < 1.5 * held / 64
    if (share, k) in (("28pct", 6), ("28pct", 8), ("all", 1)):
        assert n > CHUNK and n % CHUNK  # several turns and a part of one
    if (share, k) in (("28pct", 6), ("28pct", 8), ("all", 6)):
        # some token's rows lie on both sides of a turn's edge
        token = np.asarray(back["kernel"].token)
        assert any(token[e - 1] == token[e] for e in range(CHUNK, n, CHUNK))
    behind = (jnp.arange(rows) >= held_rows)[:, None]
    ys, d_ys = (jnp.where(behind, jnp.nan, x).astype(jnp.bfloat16)
                for x in (ys, d_ys))
    d_out = d_out.astype(jnp.bfloat16)
    x = jnp.zeros((tokens, D), jnp.bfloat16)

    def run(route):
        out, vjp = jax.vjp(
            lambda r, g: combine(r, g, flat_gates, order, back[route],
                                 held_rows, route), ys, gates)
        d_rows, d_gates = vjp(d_out)
        d_x, = jax.vjp(lambda t: dispatch(t, order, back[route], held_rows,
                                          route), x)[1](d_ys)
        return out, d_rows[:n], d_gates, d_x

    got, want = run("kernel"), run("plain")
    for leaf in got:
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    _same_bits(got, want)
    if not held:
        assert not any(np.asarray(leaf, np.float32).any() for leaf in got)
