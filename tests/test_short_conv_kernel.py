"""`ops/short_conv.py`: the mixers' short causal convolution + silu as
the Pallas pair `dwt_conv_fwd` / `dwt_conv_bwd`, held to the plain lines
of `models/mamba2.causal_conv_silu` in interpret mode on the CPU, and
`conv_route`'s answers at the cells' shapes.

What interpret mode cannot see (tiling, VMEM, the sublane rolls as
Mosaic lowers them) is tests/test_tpu_compile.py's: the pair compiled
for a described v5e at the cells' shapes and counted in the three
hybrids' steps.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dlrover_wuqiong_tpu.models import mamba2
from dlrover_wuqiong_tpu.ops import mosaic, short_conv

TAPS = 4


def _operands(b, t, channels, dtype, bias, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x, d_out = (jax.random.normal(k, (b, t, channels), dtype)
                for k in keys[:2])
    kernel = jax.random.uniform(keys[2], (TAPS, channels), jnp.float32,
                                -0.5, 0.5)
    bias = jax.random.uniform(keys[3], (channels,), jnp.float32,
                              -0.5, 0.5) if bias else None
    return x, kernel, bias, d_out


def _plain(x, kernel, bias):
    """The oracle: the plain lines in float32 on the SAME numbers (a
    bfloat16 x widened), where they round nothing."""
    return mamba2.causal_conv_silu(x.astype(jnp.float32), kernel, bias,
                                   jnp.float32)


def _close(got, want, rel):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() <= rel * np.abs(want).max(), (
        np.abs(got - want).max(), np.abs(want).max())


# what one rounding to the rows' dtype may move, against the largest value
_ROUNDING = {jnp.float32: 2e-6, jnp.bfloat16: 2 ** -8}


# (b, T, channels, rows a grid step, rows a chunk): two batch rows, so a
# row's start must read zeros and not the row before it; several grid
# steps AND several chunks a step, so both halos cross a block's edge and
# a chunk's; one step of one chunk; the three cells' widths (4,352 is 17
# pairs of lane tiles)
SHAPES = [
    pytest.param(2, 128, 256, 64, 32, id="two_rows_blocks_and_chunks"),
    pytest.param(1, 64, 128, 64, 64, id="one_block_one_chunk"),
    pytest.param(1, 96, 384, 32, 16, id="three_blocks_one_lane_tile"),
    pytest.param(2, 64, 2048, 32, 16, id="ling_2048"),
    pytest.param(1, 64, 4352, 32, 16, id="granite_4352"),
    pytest.param(1, 64, 6144, 32, 32, id="nemotron_6144"),
]


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,channels,rows,chunk", SHAPES)
def test_forward_is_the_plain_lines(b, t, channels, rows, chunk, dtype,
                                    bias):
    """y, rounded once at the write: within one rounding of the float32
    lines at either dtype (the plain lines in bfloat16 round every
    product and every sum)."""
    x, kernel, bias, _ = _operands(b, t, channels, dtype, bias)
    got = short_conv._conv_kernels(x, kernel, bias, dtype, rows=rows,
                                   chunk=chunk, interpret=True)
    assert got.dtype == dtype and got.shape == x.shape
    _close(got, _plain(x, kernel, bias), _ROUNDING[dtype])


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,channels,rows,chunk", SHAPES)
def test_gradients_are_the_plain_lines(b, t, channels, rows, chunk, dtype,
                                       bias):
    """dx (the rows' dtype, rounded once), and the filter's and the
    bias's gradients, float32 sums over batch and T that the sequential
    grid axes and the chunks' loop accumulate."""
    x, kernel, bias, d_out = _operands(b, t, channels, dtype, bias, seed=1)

    def kernels(x, kernel, bias):
        return short_conv._conv_kernels(x, kernel, bias, dtype, rows=rows,
                                        chunk=chunk, interpret=True)

    got = jax.vjp(kernels, x, kernel, bias)[1](d_out)
    want = jax.vjp(_plain, x, kernel, bias)[1](d_out.astype(jnp.float32))
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    _close(got[0], want[0], _ROUNDING[dtype])
    _close(got[1], want[1], 1e-5)
    if bias is None:
        assert got[2] is None
    else:
        assert got[2].dtype == jnp.float32
        _close(got[2], want[2], 1e-5)


def test_a_rows_start_reads_zeros_not_the_row_before():
    """Two batch rows and two grid steps a row: the second row's y is
    that row's alone, bit for bit, and the first row's dx hold nothing
    of the second row's dy."""
    x, kernel, bias, d_out = _operands(2, 64, 128, jnp.float32, True)

    def kernels(x):
        return short_conv._conv_kernels(x, kernel, bias, jnp.float32,
                                        rows=32, chunk=16, interpret=True)

    both, vjp = jax.vjp(kernels, x)
    second, _ = jax.vjp(kernels, x[1:])
    _, vjp_first = jax.vjp(kernels, x[:1])
    np.testing.assert_array_equal(both[1], second[0])
    np.testing.assert_array_equal(vjp(d_out)[0][0],
                                  vjp_first(d_out[:1])[0][0])


@pytest.mark.parametrize("lane,reads_in_place", [(256, True), (128, False)],
                         ids=["whole_blocks_in", "half_a_block_in"])
def test_a_slice_is_read_where_it_lies(monkeypatch, lane, reads_in_place):
    """`source`: x is lanes [lane, lane + 256) of a wider array (the
    Mamba-2 mixer's z | xBC | dt, a width that is no whole lane tile).
    The kernels read it there — the custom call's operand is the wide
    array, at whole channel blocks into it — and the answer and every
    gradient are those of the slice handed over alone; the wide array's
    cotangent arrives through the slice, nothing is added to it."""
    wide, kernel, bias, _ = _operands(2, 64, 256 + lane + 72, jnp.float32,
                                      True)
    kernel, bias = kernel[:, :256], bias[:256]
    seen, forward = [], short_conv._conv_fwd
    monkeypatch.setattr(short_conv, "_conv_fwd", lambda read, coef, **plan: (
        seen.append((read.shape, plan["first"])),
        forward(read, coef, **plan))[1])

    def sliced(wide, kernel, bias, source):
        x = wide[..., lane:lane + 256]
        return short_conv._conv_kernels(
            x, kernel, bias, jnp.float32, (wide, lane) if source else None,
            rows=32, chunk=16, interpret=True)

    in_place = functools.partial(sliced, source=True)
    copied = functools.partial(sliced, source=False)
    got, vjp = jax.vjp(in_place, wide, kernel, bias)
    want, vjp_copied = jax.vjp(copied, wide, kernel, bias)
    np.testing.assert_array_equal(got, want)
    for g, w in zip(vjp(got), vjp_copied(got)):
        np.testing.assert_array_equal(g, w)
    assert np.all(np.asarray(vjp(got)[0])[..., :lane] == 0)
    assert seen[0] == ((wide.shape, lane // 256) if reads_in_place
                       else ((2, 64, 256), 0))


@pytest.fixture
def kernel_route(on_tpu, monkeypatch):
    """What one TPU device traces at `causal_conv_silu`, here: the
    backend said to be the TPU, a row block that divides a short
    sequence, the pair in interpret mode."""
    monkeypatch.setattr(short_conv, "_ROW_BLOCK", 16)
    monkeypatch.setattr(short_conv, "_ROWS_A_STEP", 32)
    monkeypatch.setattr(short_conv, "_CHUNK", 16)
    monkeypatch.setattr(short_conv, "conv_silu_rows", functools.partial(
        short_conv._conv_kernels, interpret=True))


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_the_entry_takes_the_kernels_where_the_route_says(kernel_route,
                                                          monkeypatch, bias):
    """`causal_conv_silu` is still the one entry: on the kernel route it
    returns the pair's answer (three grid steps of two chunks here), on
    the plain route its own lines, and a mesh of several devices or
    ragged channels keep the plain lines whatever the backend."""
    x, kernel, bias, d_out = _operands(2, 96, 256, jnp.bfloat16, bias)
    assert short_conv.conv_route(96, 256, TAPS, jnp.bfloat16) == "kernel"
    assert short_conv._blocks(96, 256) == (32, 256)

    def entry():  # a new function a trace: JAX keeps a traced one's
        return lambda x, kernel, bias: mamba2.causal_conv_silu(
            x, kernel, bias, jnp.bfloat16)

    got, vjp = jax.vjp(entry(), x, kernel, bias)
    assert "dwt_conv_fwd" in str(jax.make_jaxpr(entry())(x, kernel, bias))
    with monkeypatch.context() as mp:
        mp.setattr(mosaic, "on_tpu", lambda: False)
        assert "dwt_conv" not in str(
            jax.make_jaxpr(entry())(x, kernel, bias))
        want, vjp_plain = jax.vjp(entry(), x, kernel, bias)
    _close(got, want, 2 ** -6)  # the plain lines round every product
    for g, w in zip(vjp(d_out), vjp_plain(d_out)):
        if w is not None:
            _close(g, w, 2 ** -6)
    ragged = x[..., :200]
    assert "dwt_conv" not in str(jax.make_jaxpr(entry())(
        ragged, kernel[:, :200], None if bias is None else bias[:200]))


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


# (t, channels): what `causal_conv_silu` is handed in the four cells
@pytest.mark.parametrize("cell,t,channels,want", [
    ("nemotron3_nano_30b_a3b", 8192, 6144, "kernel"),
    ("granite4_h_micro", 8192, 4352, "kernel"),
    ("ling3_0_flash_q_k_and_v", 8192, 2048, "kernel"),
    ("olmo_hybrid_7b_q_and_k", 8192, 1440, "plain"),
    ("olmo_hybrid_7b_v", 8192, 2880, "plain"),
    ("no_whole_row_block", 8192 + 128, 6144, "plain"),
    ("nemotrons_parameter_draw_on_one_chunk", 128, 6144, "plain"),
    ("granites_parameter_draw_on_one_chunk", 256, 4352, "plain"),
])
def test_route_at_the_cells_shapes(on_tpu, cell, t, channels, want):
    """The static counter: the route's answer on one TPU device at each
    call the four cells make, and for a T that is no whole row block."""
    assert short_conv.conv_route(t, channels, TAPS, jnp.bfloat16) == want
    assert short_conv.conv_route(t, channels, TAPS, jnp.bfloat16,
                                 _mesh(1)) == want


@pytest.mark.parametrize("on_tpu,mesh,want", [
    (False, None, "plain"),   # every CPU run
    (True, None, "kernel"),
    (True, 1, "kernel"),
    (True, 4, "plain"),       # GSPMD's to partition: no Mosaic call can be
], indirect=["on_tpu"], ids=["off", "device", "one", "mesh"])
def test_route_by_where_the_call_runs(on_tpu, mesh, want):
    mesh = mesh and _mesh(mesh)
    assert short_conv.conv_route(8192, 6144, TAPS, jnp.bfloat16,
                                 mesh) == want


@pytest.mark.parametrize("taps,dtype,want", [
    (4, jnp.float32, "kernel"),
    (2, jnp.bfloat16, "kernel"),
    (8, jnp.bfloat16, "plain"),    # taps and a bias are over one tile
    (1, jnp.bfloat16, "plain"),    # no convolution
    (4, jnp.float16, "plain"),
])
def test_route_by_taps_and_dtype(on_tpu, taps, dtype, want):
    assert short_conv.conv_route(8192, 2048, taps, dtype) == want


@pytest.mark.parametrize("t,channels,want", [
    (8192, 6144, (4096, 256)), (8192, 4352, (4096, 256)),
    (8192, 2048, (4096, 256)), (1536, 384, (1536, 128)),
    (512 * 9, 128, (1536, 128)), (512, 256, (512, 256)),
])
def test_a_grid_steps_block(t, channels, want):
    """Two lane tiles where the channels' tiles pair, and the most whole
    row blocks up to 4,096 rows that divide T."""
    assert short_conv._blocks(t, channels) == want
