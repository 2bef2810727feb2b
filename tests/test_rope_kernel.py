"""`ops/rope.py`'s kernel `dwt_rope` in interpret mode (no chip): against
the written-out rotation, its VJP against the plain formula's, a
rotation and its inverse, and which calls `rope_route` hands to it —
whole heads, and heads whose first lanes turn while the rest pass.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from test_laguna import _sliced_rope
from test_program_from_arguments import _rope_written_out

from dlrover_wuqiong_tpu.models.llama import apply_rope, rope_freqs
from dlrover_wuqiong_tpu.ops import rope

T, TILE = 40, 16  # two whole row tiles and half of one


@pytest.fixture
def on_the_kernel_route(on_tpu, monkeypatch):
    """What one TPU device runs, here: the route's own decision with the
    backend said to be the TPU, a row tile T is no multiple of, the
    kernel in interpret mode; -> the calls the kernel took."""
    calls = []

    def kernels(x, cos, sin, head_dim=0):
        calls.append(x.shape)
        return rope._rope_kernels(x, cos, sin, head_dim, interpret=True)

    monkeypatch.setattr(rope, "_ROW_TILE", TILE)
    monkeypatch.setattr(rope, "rotate_rows", kernels)
    return calls


def _close(got, want, dtype):
    """Equal to a rounding of the last sum (the interpreter's CPU may
    contract a product and the sum where the formula's fusion does not)."""
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -22
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=ulp,
                               atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", ["heads", "flat"])
@pytest.mark.parametrize("h", [1, 4, 7])
@pytest.mark.parametrize("d", [64, 128])
def test_rotation_is_the_written_out_formula_on_either_route(
        on_the_kernel_route, d, h, layout, dtype):
    """`apply_rope` as one TPU device runs it, on (b, s, h, d) and on the
    projections' own (b, s, h*d): rows of a whole number of slabs go
    through `dwt_rope` (a head a slab at 128, two a slab at 64), a
    lone head of 64 too (padded to a slab), seven heads of 64 keep the
    formula — and both are the per-head rotation."""
    b = 2
    cos, sin = rope_freqs(d, T + 8, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(h + d), (b, T, h, d), dtype)
    got = apply_rope(x if layout == "heads" else x.reshape(b, T, h * d),
                     cos, sin)
    route = rope.rope_route(h * d, d)
    assert route == ("plain" if (h, d) == (7, 64) else "kernel")
    assert on_the_kernel_route == ([(b, T, h * d)] if route == "kernel"
                                   else [])
    assert got.dtype == dtype
    assert got.shape == (x.shape if layout == "heads" else (b, T, h * d))
    _close(got.reshape(x.shape), _rope_written_out(x, cos, sin), dtype)


# (head, rotated): every pair `rope_route` admits — a head's first half
# (Laguna's full layers: 64 of 128) or quarter, never under 32 lanes
WHOLE = [(64, 64), (128, 128)]
IN_PART = [(128, 64), (128, 32), (64, 32)]
MSCALE = 1.4158883  # YaRN's on Laguna's tables: a passed lane has none


def _scaled_tables(rotated, seq, theta=10000.0, mscale=MSCALE):
    cos, sin = rope_freqs(rotated, seq, theta)
    return cos * mscale, sin * mscale


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", ["heads", "flat"])
@pytest.mark.parametrize("h", [1, 4, 7])
@pytest.mark.parametrize("d,rotated", IN_PART)
def test_a_head_rotated_in_part_is_the_cut_and_the_join_on_either_route(
        on_the_kernel_route, d, rotated, h, layout, dtype):
    """Tables narrower than half the head, scaled as YaRN scales them:
    `dwt_rope` turns a head's first `rotated` lanes and passes the rest
    inside the kernel — bit for bit what they were, not times the
    tables' scale — wherever the rows are whole slabs (or a lone head of
    64); seven heads of 64 keep the formula.  Both are the slice, the
    rotation and the join, and the formula with `head_dim`."""
    b = 2
    cos, sin = _scaled_tables(rotated, T + 8)
    x = jax.random.normal(jax.random.PRNGKey(h + d), (b, T, h, d), dtype)
    shape = x.shape if layout == "heads" else (b, T, h * d)
    got = apply_rope(x.reshape(shape), cos, sin, head_dim=d)
    route = rope.rope_route(h * d, d, None, rotated)
    assert route == ("plain" if (h, d) == (7, 64) else "kernel")
    assert on_the_kernel_route == ([(b, T, h * d)] if route == "kernel"
                                   else [])
    assert got.dtype == dtype and got.shape == shape
    want = _sliced_rope(x.astype(jnp.float32), cos[:T], sin[:T])
    _close(got.reshape(x.shape), want.astype(dtype), dtype)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(x.shape)[..., rotated:], np.float32),
        np.asarray(x[..., rotated:], np.float32))
    # the plain route's own lines: a mesh of several devices
    plain = apply_rope(x.reshape(shape), cos, sin, mesh=_mesh(2), head_dim=d)
    assert len(on_the_kernel_route) == (route == "kernel")
    _close(got, plain, dtype)


@pytest.mark.parametrize("d,rotated", IN_PART)
def test_no_roll_wraps_a_passed_lane_into_a_kept_product(d, rotated):
    """The partner of a rotated lane is a rotated lane of the SAME head:
    NaNs in every passed lane leave every rotated lane finite and what it
    was.  (A passed lane's own partner is multiplied by zero — a finite
    one vanishes, as on the plain route.)"""
    cos, sin = _scaled_tables(rotated, T)
    x = jax.random.normal(jax.random.PRNGKey(d), (2, T, 4, d), jnp.float32)
    poisoned = x.at[..., rotated:].set(jnp.nan)
    got = rope._rope_kernels(poisoned.reshape(2, T, 4 * d), cos, sin, d,
                             tile=TILE, interpret=True).reshape(x.shape)
    want = _sliced_rope(x, cos, sin)
    _close(got[..., :rotated], want[..., :rotated], jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,rotated", WHOLE + IN_PART)
def test_backward_is_the_plain_formulas_gradient(on_the_kernel_route, d,
                                                 rotated, dtype):
    """The `custom_vjp` (the kernel again, the sine negated: a passed
    lane's cotangent passes) against JAX's own differentiation of the
    formula, off the TPU."""
    cos, sin = _scaled_tables(rotated, T, 500000.0,
                              1.0 if rotated == d else MSCALE)
    width = {} if rotated == d else {"head_dim": d}
    keys = jax.random.split(jax.random.PRNGKey(d), 2)
    x, d_out = (jax.random.normal(k, (2, T, 4 * d), dtype) for k in keys)
    got, = jax.vjp(lambda x: apply_rope(x, cos, sin, **width), x)[1](d_out)
    assert len(on_the_kernel_route) == 1 and got.dtype == dtype
    # a mesh of several devices outside a shard_map: the plain route
    want, = jax.vjp(lambda x: apply_rope(x, cos, sin, mesh=_mesh(2),
                                         **width), x)[1](d_out)
    assert len(on_the_kernel_route) == 1
    _close(got, want, dtype)
    if rotated < d:
        cut = (2, T, 4, d)
        np.testing.assert_array_equal(
            np.asarray(got.reshape(cut)[..., rotated:], np.float32),
            np.asarray(d_out.reshape(cut)[..., rotated:], np.float32))


@pytest.mark.parametrize("d,rotated", WHOLE + IN_PART)
def test_a_rotation_and_its_inverse_are_the_identity(d, rotated):
    cos, sin = rope_freqs(rotated, T, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(d), (2, T, 2 * d), jnp.float32)
    turn = functools.partial(rope._rope, half=rotated // 2, d=d, tile=TILE,
                             interpret=True)
    table = rope.rope_table(cos, sin, d)
    assert table.shape == (T, 128) and table.dtype == jnp.float32
    there = turn(x, table, inverse=False)
    turned = (jnp.arange(2 * d) % d < rotated)
    assert float(jnp.abs(there[:, 1:] - x[:, 1:])[..., turned].max()) > 0.5
    np.testing.assert_array_equal(there[..., ~turned], x[..., ~turned])
    np.testing.assert_allclose(turn(there, table, inverse=True), x,
                               atol=2e-6)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("fsdp",))


@pytest.mark.parametrize("on_tpu,lanes,d,devices,inside,want", [
    # the cells: SmallThinker's q and k, OLMoE's, latent attention's q part
    (True, 3584, 128, 0, False, "kernel"),
    (True, 512, 128, 1, False, "kernel"),
    (True, 2048, 128, 1, False, "kernel"),
    (True, 1024, 64, 1, False, "kernel"),
    # latent attention's ONE rotated key part: half a slab, padded
    (True, 64, 64, 1, False, "kernel"),
    # seven heads of 64, half a head of 128, a head size off the slab
    (True, 448, 64, 0, False, "plain"),
    (True, 64, 128, 0, False, "plain"),
    (True, 1024, 32, 0, False, "plain"),
    (True, 1024, 256, 0, False, "plain"),
    # off the TPU; on a mesh of several devices, but inside a shard_map
    (False, 3584, 128, 0, False, "plain"),
    (True, 3584, 128, 4, False, "plain"),
    (True, 3584, 128, 4, True, "kernel"),
    # Laguna's full layers, 64 of a head's 128 rotated: q's rows and k's
    (True, 6144, (128, 64), 0, False, "kernel"),
    (True, 1024, (128, 64), 1, False, "kernel"),
    (True, 6144, (128, 64), 4, True, "kernel"),
    (True, 1024, (128, 64), 4, True, "kernel"),
    (True, 6144, (128, 64), 4, False, "plain"),
    (False, 6144, (128, 64), 0, False, "plain"),
    (False, 1024, (128, 64), 0, False, "plain"),
    # what the same body serves: a quarter of 128, half of 64 (a lone
    # head of it padded), the whole head said aloud
    (True, 2048, (128, 32), 0, False, "kernel"),
    (True, 1024, (64, 32), 0, False, "kernel"),
    (True, 64, (64, 32), 0, False, "kernel"),
    (True, 2048, (128, 128), 0, False, "kernel"),
    # the pairs left plain: under 32 rotated lanes, a width no slab
    # divides by, more than the head, seven half-rotated heads of 64
    (True, 2048, (128, 16), 0, False, "plain"),
    (True, 1024, (64, 16), 0, False, "plain"),
    (True, 2048, (128, 96), 0, False, "plain"),
    (True, 2048, (128, 48), 0, False, "plain"),
    (True, 1024, (64, 128), 0, False, "plain"),
    (True, 448, (64, 32), 0, False, "plain"),
], indirect=["on_tpu"])
def test_which_calls_take_the_kernel(on_tpu, lanes, d, devices, inside, want):
    """`rope_route`: the static counter of the decision.  `d` is the
    head's width, or (the head's, the rotated part's)."""
    mesh = _mesh(devices) if devices else None
    d, *rotated = d if isinstance(d, tuple) else (d,)
    if not inside:
        assert rope.rope_route(lanes, d, mesh, *rotated) == want
        return
    seen = []

    def shard(x):
        seen.append(rope.rope_route(lanes, d, mesh, *rotated))
        return x

    jax.eval_shape(jax.shard_map(shard, mesh=mesh, in_specs=P("fsdp"),
                                 out_specs=P("fsdp")), jnp.zeros((8, 4)))
    assert seen == [want]
