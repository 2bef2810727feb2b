"""The grouped-matmul kernels of a chip's share of an expert layer
(`ops/grouped_matmul.py`: `dwt_gmm`, `dwt_gmm_t`, `dwt_tgmm`) in
interpret mode on the CPU, against `jax.lax.ragged_dot` and its
differentiation: groups that are empty, groups that share a row tile,
group sizes that sum to less than the buffer with the rows behind them
poisoned (NaN) on the way in, and the grid's row axis — the visits —
against a count by hand.  And the elementwise passes between a share's
products (`dwt_rows_map_*`: an activation, a sum of two row gradients,
the combine's backward pair) against the `jax.numpy` function each one
applies, values and gradients, at the edges of the held rows.  What the
described-`v5e` compiles cannot see
(results), as they see what this cannot (tiling, VMEM):
tests/test_tpu_compile.py.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.models import moe
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.ops import mosaic

TILE = 32
ROWS, C, N = 256, 64, 128

# group sizes over a 256-row buffer in tiles of 32
CASES = {
    "full_buffer_on_tile_borders": [64, 96, 32, 64],
    "full_buffer_groups_share_tiles": [50, 70, 41, 95],
    "a_share_of_the_buffer": [40, 25, 30, 17],
    "a_share_with_empty_groups": [40, 0, 70, 0],
    "first_and_last_group_empty": [0, 33, 31, 0],
    "three_groups_in_one_tile": [5, 9, 7, 60],
    "one_row": [0, 0, 1, 0],
    "no_row_at_all": [0, 0, 0, 0],
}


def _operands(sizes, dtype, seed=0):
    held = sum(sizes)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(ks[0], (ROWS, C), jnp.float32)
    rhs = jax.random.normal(ks[1], (len(sizes), C, N), jnp.float32) / 8
    d_out = jax.random.normal(ks[2], (ROWS, N), jnp.float32)
    behind = (jnp.arange(ROWS) >= held)[:, None]
    poisoned = [jnp.where(behind, jnp.nan, a).astype(dtype)
                for a in (lhs, d_out)]
    clean = [jnp.where(behind, 0, a).astype(dtype) for a in (lhs, d_out)]
    return clean, poisoned, rhs.astype(dtype), jnp.array(sizes, jnp.int32)


def _value_and_grads(product, lhs, rhs, d_out, held):
    """The held rows of the product and of the rows' gradient, and the
    weights' gradient: what the caller reads (`models/moe.py` masks the
    rest)."""
    out, vjp = jax.vjp(product, lhs, rhs)
    d_lhs, d_rhs = vjp(d_out.astype(out.dtype))
    assert out.dtype == lhs.dtype and d_lhs.dtype == lhs.dtype
    assert d_rhs.dtype == rhs.dtype and d_rhs.shape == rhs.shape
    return [np.asarray(a, np.float32)
            for a in (out[:held], d_lhs[:held], d_rhs)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_kernels_are_ragged_dot_on_the_held_rows(case, dtype, tol):
    """Value and both gradients; the kernels are handed NaNs behind the
    held rows (in the rows and in the cotangent) and `ragged_dot` zeros:
    nothing of them reaches a held row or the weights' gradient."""
    (lhs, d_out), (lhs_nan, d_out_nan), rhs, sizes = _operands(
        CASES[case], dtype)
    held = sum(CASES[case])
    want = _value_and_grads(
        lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs, d_out, held)
    got = _value_and_grads(
        lambda l, r: gm._grouped_kernels(l, r, sizes, tile=TILE,
                                         interpret=True),
        lhs_nan, rhs, d_out_nan, held)
    for name, g, w in zip(("out", "d_lhs", "d_rhs"), got, want):
        assert np.isfinite(g).all(), name
        scale = max(1.0, float(np.abs(w).max())) if w.size else 1.0
        assert np.abs(g - w).max(initial=0.0) <= tol * scale, name


@pytest.mark.parametrize("columns", [128, 256, 1024])
def test_column_tiles_change_no_result(columns):
    """The result's columns in tiles of 128 (two grid steps a visit; the
    weights' gradient in 1 x 2 tiles of C x N) or whole."""
    (lhs, d_out), _, rhs, sizes = _operands(
        CASES["full_buffer_groups_share_tiles"], jnp.float32)
    rhs = jnp.concatenate([rhs, rhs * 0.5], axis=-1)       # N = 256
    d_out = jnp.concatenate([d_out, -d_out], axis=-1)
    want = _value_and_grads(
        lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs, d_out, ROWS)
    got = _value_and_grads(
        lambda l, r: gm._grouped_kernels(l, r, sizes, tile=TILE,
                                         columns=columns, interpret=True),
        lhs, rhs, d_out, ROWS)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-5 * np.abs(w).max())


def _visits_by_hand(sizes, tile, empty_groups):
    visits, start = [], 0
    for g, n in enumerate(sizes):
        tiles = sorted({r // tile for r in range(start, start + n)})
        if not tiles and empty_groups:
            tiles = [None]
        visits += [(g, t) for t in tiles]
        start += n
    return visits


@pytest.mark.parametrize("empty_groups", [False, True])
@pytest.mark.parametrize("case", CASES)
def test_the_grid_visits_the_tiles_that_hold_a_held_row(case, empty_groups):
    """`group_visits`: the (group, row tile) pairs with a row in common,
    in order, and no other; a tile behind the held rows is in none; the
    distinct tiles are the first ceil(held / tile).  `dwt_tgmm`'s plan
    adds one visit a group of no rows (it writes that group's zeros)."""
    sizes = CASES[case]
    offsets, group, row_tile, n = gm.group_visits(
        jnp.array(sizes), ROWS, TILE, empty_groups)
    want = _visits_by_hand(sizes, TILE, empty_groups)
    assert int(n) == len(want)
    assert group.shape == row_tile.shape == (ROWS // TILE + len(sizes),)
    got = list(zip(np.asarray(group)[:int(n)], np.asarray(row_tile)[:int(n)]))
    for (g, t), (wg, wt) in zip(got, want):
        assert g == wg and (wt is None or t == wt)
    assert list(np.asarray(offsets)) == [0] + list(np.cumsum(sizes))
    held_tiles = -(-sum(sizes) // TILE)
    assert {t for (_, t), (_, wt) in zip(got, want) if wt is not None} \
        == set(range(held_tiles))
    # every index a grid step could read is a valid one
    assert 0 <= int(group.min()) and int(group.max()) < len(sizes)
    assert 0 <= int(row_tile.min()) and int(row_tile.max()) < ROWS // TILE


def test_row_tiles_counts_the_visits_or_the_whole_buffer():
    sizes = jnp.array([700, 0, 30, 500])  # 1230 rows of 4096, tiles of 256
    assert gm._ROW_TILE == 256
    walked, of = gm.row_tiles(sizes, 4096, "kernel")
    assert (int(walked), int(of)) == (3 + 1 + 3, 16)
    walked, of = gm.row_tiles(sizes, 4096, "plain")
    assert (int(walked), int(of)) == (16, 16)


def _mesh(size):
    return None if size is None else types.SimpleNamespace(size=size)


@pytest.mark.parametrize(
    "on_tpu,rows,held,named,mesh,route", [
        (True, 98304, 8, 128, None, "kernel"),   # the hybrid cell's share
        (True, 98304, 8, 128, 1, "kernel"),      # a mesh of one device
        (False, 98304, 8, 128, None, "plain"),   # off the TPU
        (True, 98304, 8, 128, 4, "plain"),       # a mesh of several devices
        (True, 163840, 64, 64, None, "plain"),   # a whole layer (OLMoE's)
        (True, 98304, 8, None, None, "plain"),   # nobody says how many
        (True, 98304 + 8, 8, 128, None, "plain"),  # the tile divides it not
        (True, 192, 4, 8, None, "plain"),        # nano sizes
    ], indirect=["on_tpu"])
def test_the_route_is_the_calls_shapes_mesh_and_backend(
        on_tpu, rows, held, named, mesh, route):
    """`gmm_route`: no knob, no environment variable, no model's name."""
    assert gm.gmm_route((rows, 2688), (held, 2688, 1856), named,
                        _mesh(mesh)) == route
    assert gm.gmm_route((rows, 1856), (held, 1856, 2688), named,
                        _mesh(mesh)) == route


@pytest.mark.parametrize("cell,rows,held,d,f,named,first", [
    # relu2 experts: no gate matrix
    ("nemotron3_nano_30b_a3b.steady", 98304, 8, 2688, 1856, 128, False),
    ("smallthinker_21b_a3b.steady", 196608, 16, 2560, 768, 64, True),
    ("kimi_vl_a3b.steady", 196608, 8, 2048, 1408, 64, True),
])
def test_the_share_cells_layers_take_the_kernels(on_tpu, cell, rows, held,
                                                 d, f, named, first):
    """`experts_route` at the three share cells' own T*k rows and weight
    shapes, on one TPU device: "kernel", whole layer."""
    shapes = [(held, d, f)] * (1 + first) + [(held, f, d)]
    assert gm.experts_route(rows, shapes, named) == "kernel"
    assert gm.experts_route(rows, shapes, held) == "plain"  # all held


def test_blocks_that_do_not_fit_vmem_keep_the_plain_route(on_tpu):
    assert gm.gmm_route((4096, 32768), (8, 32768, 1856), 128) == "plain"


def test_off_the_kernel_route_the_product_is_ragged_dot_word_for_word():
    """The plain route adds nothing to the program: the same jaxpr as
    the call it stands for (a whole layer's lowered step stays the
    parent's byte for byte)."""
    (lhs, _), _, rhs, sizes = _operands(CASES["a_share_of_the_buffer"],
                                        jnp.float32)
    ours = jax.make_jaxpr(
        lambda l, r, s: gm.grouped_matmul(l, r, s, "plain"))(lhs, rhs, sizes)
    theirs = jax.make_jaxpr(
        lambda l, r, s: jax.lax.ragged_dot(l, r, s))(lhs, rhs, sizes)
    assert str(ours) == str(theirs)


def test_a_program_traces_each_kernel_shape_once(monkeypatch):
    """Four layers, one trace of each kernel body: the wrappers sit
    behind `jax.jit` (a shape no other test of this file uses)."""
    (lhs, _), _, rhs, sizes = _operands(CASES["a_share_of_the_buffer"],
                                        jnp.float32)
    traced = {"gmm": 0, "tgmm": 0}
    bodies = {"gmm": gm._gmm_kernel, "tgmm": gm._tgmm_kernel}

    def counting(name):
        def body(*refs, **kw):
            traced[name] += 1
            return bodies[name](*refs, **kw)
        return body

    monkeypatch.setattr(gm, "_gmm_kernel", counting("gmm"))
    monkeypatch.setattr(gm, "_tgmm_kernel", counting("tgmm"))

    def four_layers(l, r, s):
        for _ in range(4):
            l = gm._grouped_kernels(l, r, s, tile=16, interpret=True)
            l = l[:, :C]
        return l.sum()

    jax.jit(jax.grad(four_layers, argnums=(0, 1))).lower(lhs, rhs, sizes)
    # the forward and the transposed-weight form, and one tgmm
    assert traced == {"gmm": 2, "tgmm": 1}


def test_the_kernel_route_refuses_rows_the_tile_does_not_divide():
    (lhs, _), _, rhs, sizes = _operands(CASES["a_share_of_the_buffer"],
                                        jnp.float32)
    with pytest.raises(ValueError, match="row tile"):
        gm._grouped_kernels(lhs[:250], rhs, sizes, tile=TILE, interpret=True)


# ------------------------------------------ the passes between products

# how many of the 256 rows are held: none, a whole number of 32-row
# tiles, one short of it, one over, a share that ends inside a tile, all
HELD = {"none": 0, "two_tiles": 64, "one_short": 63, "one_over": 65,
        "a_share": 100, "one_row": 1, "whole_buffer": ROWS}

# (the function of blocks, widths of its buffers; 1 = a per-row operand)
FORMS = {
    "relu2": (moe._activation(None), (N,)),
    "reglu": (moe._activation(jax.nn.relu), (N, N)),
    "swiglu": (moe._activation(jax.nn.silu), (N, N)),
    "sum_of_two": (gm._add, (C, C)),
    "combine_bwd_pair": (moe._weigh, (C, C, 1)),
}


def _buffers(widths, held, dtype, seed=0):
    """(clean, poisoned): zeros / NaNs behind the held rows; a per-row
    operand (the gates) is float32 whatever the buffers are."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(widths))
    behind = (jnp.arange(ROWS) >= held)[:, None]
    drawn = [jax.random.normal(k, (ROWS, w), jnp.float32)
             for k, w in zip(keys, widths)]
    return tuple([jnp.where(behind, fill, a).astype(
        jnp.float32 if a.shape[1] == 1 else dtype) for a in drawn]
        for fill in (0, jnp.nan))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("held", HELD)
@pytest.mark.parametrize("form", FORMS)
def test_a_map_is_its_function_on_the_held_rows(form, held, dtype, tol):
    """`rows_map`: every result and every buffer's gradient are the
    plain `jax.numpy` function's on the held rows, ZERO on the rows of
    the last visited tile that lie behind them — though the buffers and
    the cotangents are handed in with NaNs there — and whatever the
    interpreter left in the tiles no grid step visits."""
    fn, widths = FORMS[form]
    n_held = HELD[held]
    visited = -(-n_held // TILE) * TILE
    clean, dirty = _buffers(widths, n_held, dtype)
    mask = (jnp.arange(ROWS) < n_held)[:, None]

    def plain(*buffers):
        return tuple(jnp.where(mask, o, 0) for o in fn(*buffers))

    def ours(*buffers):
        return gm._rows_map_kernels(fn, jnp.int32(n_held), *buffers,
                                    tile=TILE, interpret=True)

    want, want_vjp = jax.vjp(plain, *clean)
    got, got_vjp = jax.vjp(ours, *dirty)
    d_clean, d_dirty = _buffers([w.shape[1] for w in want], n_held,
                                jnp.float32, seed=1)
    cots = [[d.astype(w.dtype) for d, w in zip(ds, want)]
            for ds in (d_clean, d_dirty)]
    pairs = list(zip(got, want)) + list(
        zip(got_vjp(tuple(cots[1])), want_vjp(tuple(cots[0]))))
    assert len(pairs) == len(want) + len(widths)
    for g, w in pairs:
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = (np.asarray(a, np.float32)[:visited] for a in (g, w))
        assert np.isfinite(g).all()
        assert not g[n_held:].any()
        scale = max(1.0, float(np.abs(w).max(initial=0.0)))
        assert np.abs(g - w).max(initial=0.0) <= tol * scale


def test_a_map_writes_its_result_over_the_buffer_it_is_told_to():
    """`alias`: the combine's backward pair writes the weighted cotangent
    over the gathered one; the results are the un-aliased call's."""
    fn, widths = FORMS["combine_bwd_pair"]
    clean, _ = _buffers(widths, 100, jnp.float32)
    run = [gm._rows_map_kernels(fn, jnp.int32(100), *clean, alias=alias,
                                tile=TILE, interpret=True)
           for alias in (None, (1, 0))]
    for a, b in zip(*run):
        np.testing.assert_array_equal(np.asarray(a)[:128], np.asarray(b)[:128])
    # operand 0 is the prefetched count of held rows
    assert "input_output_aliases=((2, 0),)" in str(jax.make_jaxpr(
        lambda *b: gm._rows_map_pallas(jnp.int32(100), *b, fn=fn, tile=TILE,
                                       interpret=True, alias=(1, 0)))(*clean))


@pytest.mark.parametrize("held", HELD)
def test_the_maps_grid_is_the_tiles_that_hold_a_held_row(held):
    """`map_tiles`: ceil(held / 256) of the buffer's tiles on the kernel
    route, all of them on the plain."""
    sizes = jnp.array([HELD[held] * 20, 0, HELD[held] * 12])
    rows = ROWS * 32
    walked, of = gm.map_tiles(sizes, rows, "kernel")
    assert (int(walked), int(of)) == (-(-HELD[held] * 32 // 256), 32)
    walked, of = gm.map_tiles(sizes, rows, "plain")
    assert (int(walked), int(of)) == (32, 32)


@pytest.mark.parametrize("d,f,route", [
    (2688, 1856, "kernel"),     # the hybrid cell's share
    (2560, 768, "kernel"),      # SmallThinker's
    (2048, 1408, "kernel"),     # the latent-attention MoE's SwiGLU experts
    (32768, 1856, "plain"),     # the first product's blocks pass VMEM
    (1856, 32768, "plain"),     # the last product's do
    (8192, 1024, "plain"),      # no product's, the maps' do
])
def test_a_layer_has_one_route(on_tpu, monkeypatch, d, f, route):
    """`experts_route`: "kernel" only where every product's `gmm_route`
    says so and the maps' blocks fit; one product over the VMEM bound
    makes the whole layer plain (its other product alone would not
    be)."""
    shapes = [(8, d, f), (8, d, f), (8, f, d)]
    assert gm.experts_route(98304, shapes, 128) == route
    assert gm.experts_route(98304, shapes[1:], 128) == route
    singly = {gm.gmm_route((98304, c), (e, c, n), 128) for e, c, n in shapes}
    assert singly == {"kernel"} if route == "kernel" or d == 8192 \
        else singly == {"kernel", "plain"}
    # and nothing but a share on one TPU device
    assert gm.experts_route(98304, shapes, 8) == "plain"
    assert gm.experts_route(98304, shapes, 128, _mesh(4)) == "plain"
    assert gm.experts_route(98304 + 8, shapes, 128) == "plain"
    monkeypatch.setattr(mosaic, "on_tpu", lambda: False)
    assert gm.experts_route(98304, shapes, 128) == "plain"


def test_a_route_nobody_knows_is_refused():
    (lhs, _), _, rhs, sizes = _operands(CASES["a_share_of_the_buffer"],
                                        jnp.float32)
    with pytest.raises(ValueError, match="no route"):
        gm.grouped_matmul(lhs, rhs, sizes, 128)


def test_a_pair_of_products_sums_its_row_gradients_in_a_map():
    """`grouped_matmul(lhs, (a, b), ...)`: both products and every
    gradient are two single calls'; the backward pass holds ONE
    `dwt_rows_map_add` and no `add_any` over the buffer."""
    (lhs, d_out), (lhs_nan, d_out_nan), rhs, sizes = _operands(
        CASES["a_share_of_the_buffer"], jnp.float32)
    held = sum(CASES["a_share_of_the_buffer"])
    pair = (rhs, rhs[::-1] * 0.5)

    def plain(l, a, b):
        return gm.grouped_matmul(l, (a, b), sizes, "plain")

    def ours(l, a, b):
        return gm._grouped_kernels(l, (a, b), sizes, tile=TILE,
                                   interpret=True)

    want, want_vjp = jax.vjp(plain, lhs, *pair)
    got, got_vjp = jax.vjp(ours, lhs_nan, *pair)
    want_g = want_vjp((d_out, -d_out))
    got_g = got_vjp((d_out_nan, -d_out_nan))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g[:held], w[:held], atol=2e-5)
    np.testing.assert_allclose(got_g[0][:held], want_g[0][:held], atol=1e-4)
    assert not np.asarray(got_g[0][held:-(-held // TILE) * TILE]).any()
    for g, w in zip(got_g[1:], want_g[1:]):
        np.testing.assert_allclose(g, w, atol=1e-4)
    text = str(jax.make_jaxpr(
        lambda l, a, b, d: jax.vjp(ours, l, a, b)[1]((d, d)))(
            lhs, *pair, d_out))
    assert text.count("dwt_rows_map_add") == 1 and "add_any" not in text


def test_a_program_traces_each_map_once(monkeypatch):
    """Four layers, one trace of the map's body a function and shape
    (forward, and its VJP), as the products' kernels."""
    fn, widths = FORMS["reglu"]
    clean, _ = _buffers(widths, 100, jnp.float32)
    traced = []
    body = gm._rows_map_kernel

    def counting(*refs, fn, **kw):
        traced.append(fn.__name__)
        return body(*refs, fn=fn, **kw)

    monkeypatch.setattr(gm, "_rows_map_kernel", counting)

    def four_layers(g, u):
        for _ in range(4):
            g, = gm._rows_map_kernels(fn, jnp.int32(100), g, u, tile=16,
                                      interpret=True)
        return g.sum()

    jax.jit(jax.grad(four_layers, argnums=(0, 1))).lower(*clean)
    assert sorted(traced) == ["gated_relu", "gated_relu_bwd"]


def test_a_map_refuses_rows_the_tile_does_not_divide():
    fn, widths = FORMS["relu2"]
    clean, _ = _buffers(widths, 100, jnp.float32)
    with pytest.raises(ValueError, match="row tile"):
        gm._rows_map_kernels(fn, jnp.int32(100), clean[0][:250], tile=TILE,
                             interpret=True)
