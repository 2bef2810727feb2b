"""The grouped-matmul kernels of a chip's share of an expert layer
(`ops/grouped_matmul.py`: `dwt_gmm`, `dwt_gmm_t`, `dwt_tgmm`) in
interpret mode on the CPU, against `jax.lax.ragged_dot` and its
differentiation: groups that are empty, groups that share a row tile,
group sizes that sum to less than the buffer with the rows behind them
poisoned (NaN) on the way in, and the grid's row axis — the visits —
against a count by hand.  What the described-`v5e` compiles cannot see
(results), as they see what this cannot (tiling, VMEM):
tests/test_tpu_compile.py.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import grouped_matmul as gm

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
    ])
def test_the_route_is_the_calls_shapes_mesh_and_backend(
        monkeypatch, on_tpu, rows, held, named, mesh, route):
    """`gmm_route`: no knob, no environment variable, no model's name."""
    monkeypatch.setattr(gm, "_on_tpu", lambda: on_tpu)
    assert gm.gmm_route((rows, 2688), (held, 2688, 1856), named,
                        _mesh(mesh)) == route
    assert gm.gmm_route((rows, 1856), (held, 1856, 2688), named,
                        _mesh(mesh)) == route


def test_blocks_that_do_not_fit_vmem_keep_the_plain_route(monkeypatch):
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    assert gm.gmm_route((4096, 32768), (8, 32768, 1856), 128) == "plain"


def test_off_the_kernel_route_the_product_is_ragged_dot_word_for_word():
    """The plain route adds nothing to the program: the same jaxpr as
    the call it stands for (a whole layer's lowered step stays the
    parent's byte for byte)."""
    (lhs, _), _, rhs, sizes = _operands(CASES["a_share_of_the_buffer"],
                                        jnp.float32)
    ours = jax.make_jaxpr(
        lambda l, r, s: gm.grouped_matmul(l, r, s, 128))(lhs, rhs, sizes)
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
