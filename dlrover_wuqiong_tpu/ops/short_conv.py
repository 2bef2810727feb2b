"""The mixers' short causal depthwise convolution + silu in ONE pass.

    pre[t, c] = sum_s w[taps-1-s, c] x[t-s, c] (+ bias[c]),  s = 0 .. taps-1
    y = silu(pre)                         x[t] = 0 before a row's start

over rows laid out as the projections leave them, (b, T, channels): one
filter a channel, its LAST tap on the current step
(`models/mamba2.causal_conv_silu`, the ONE entry of the Mamba-2, the
gated delta-rule and the KDA mixers).

`dwt_conv_fwd` reads x once and writes y once; `dwt_conv_bwd` reads x
and dy once, REBUILDS pre, and writes dx once — the residuals of the
`jax.custom_vjp` are x and the coefficients, nothing a full
recomputation would not hold anyway.  With the forward recomputed that
is 2 + 2 + 3 = 7 passes over the array a layer; the compiler's fusions
of the shifted bfloat16 products moved 20 to 25 (PERF.md section 6,
PR 59).

A grid step is (a block of `_LANES_A_STEP` channels, a batch row, a
block of rows of T): all three axes independent in the forward, the
last two sequential in the backward, whose coefficient gradients are
one (8, lanes) float32 block a channel block that the batch and T axes
revisit.  The `taps - 1` rows BEFORE a block (and, in the backward,
AFTER it: dx[t] reads dpre[t + s], and dpre there needs x there) are a
second and a third view of the same arrays at a packed bfloat16 tile's
grain, 16 rows, clamped at the ends of T and zeroed there in the
kernel: no grid axis carries anything but that gradient block.  Inside
a step the block is worked `_CHUNK` rows at a time by a `fori_loop`
(one chunk's code whatever the block's height, and a chunk's float32
windows stay in vector registers: 64 rows ran the backward at 1.5x the
speed of 256; a chunk's halo is the block's own neighbouring rows, or
the views' at the block's two ends).  A shift of s rows is a sublane
roll of the float32 window (`pltpu.roll`, axis 0) and an aligned slice:
the window holds eight rows of halo, a float32 tile, on either side.
The six rolls of the backward are a fifth of its time and what keeps it
VPU-bound at two thirds of the chip's bandwidth; the forward runs at
four fifths.

Taps, their sum, the bias, silu and its derivative are float32 whatever
the rows hold; y is rounded ONCE, at the write.  The filter is NOT
rounded to `dtype` as the plain lines round it.  silu(p) = p/2 + p/2
tanh(p/2): the kernels are handed HALVED coefficients (exact) and work
on p/2, one transcendental and no division an element (a sigmoid's
division cost the forward a fifth of its time); the backward's sums
come out doubled and are halved outside.  The coefficients ride in one
(8, channels) float32 array, `taps` rows of filter and one of bias
(zeros without one; the kernel does not add them), and their gradient
leaves the backward in the same form.

Which calls take it is what a call can observe (`conv_route`): whole
128-lane tiles of channels, a T of whole row blocks, at most seven taps,
on one of `_SITES`.  Every other call keeps the plain lines of
`causal_conv_silu`, which are the route's other answer and the tests'
oracle.  An x that is a SLICE of a wider array (the Mamba-2 mixer's
xBC of z | xBC | dt) is read where it lies (`conv_silu_rows`' `source`):
the block index carries the offset, and no copy of the slice is made
for the custom call — two passes a phase, and at Nemotron 201 MB live.

What a v5e trace showed: PERF.md section 6, PR 59
(`tools/perf_probe.py conv`).

Parity: none — the reference's model zoo has no convolution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import (LANES, SUBLANES, _compiler_params, _out_struct,
                     _reckoned_vmem)

# by `tools/perf_probe.py conv` and its sweeps (PERF.md section 6, PR 59)
_ROW_BLOCK = 512     # T is whole ones: a parameter draw on a chunk is not
_ROWS_A_STEP = 4096  # rows a grid step at most: a bf16 block of 256 lanes 2 MB
_LANES_A_STEP = 256  # channels a grid step: two lane tiles where they pair
_CHUNK = 64          # rows a turn of a kernel's loop: its windows in registers
_COEF_ROWS = 8       # a float32 tile: the taps' rows, the bias's, zeros
_HALO = 8            # float32 rows of halo a window holds on a side
# a row at a time, and the rows BEFORE a row's start are zeros: a shard
# of a `shard_map` over a sequence axis would start mid-sequence, and no
# mixer has run inside one (as `ops/ssd.py`, `ops/delta_rule.py`)
_SITES = frozenset({"device"})


def conv_route(t: int, channels: int, taps: int, dtype, mesh=None) -> str:
    """Which route a convolution of (b, `t`, `channels`) rows takes:
    "kernel" (`dwt_conv_fwd` / `dwt_conv_bwd`) when the channels are
    whole 128-lane tiles, `t` a whole number of `_ROW_BLOCK`s, the taps
    and the bias fit one float32 tile of coefficients, `dtype` is
    bfloat16 or float32 and the call runs on one of `_SITES` (`mesh` is
    the model config's); else "plain", `causal_conv_silu`'s own lines.
    The static counter of the decision, with the compiled step's count
    of `dwt_conv_*` custom calls, as `ops/rope.rope_route` is of the
    rotation's."""
    shapes = channels % LANES == 0 and t % _ROW_BLOCK == 0 \
        and 1 < taps < _COEF_ROWS \
        and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
    if not shapes or mosaic.kernel_site(mesh) not in _SITES:
        return "plain"
    return "kernel"


def _blocks(t: int, channels: int) -> tuple:
    """(rows, lanes) of a grid step: two lane tiles where the channels'
    tiles pair, and the most whole `_ROW_BLOCK`s up to `_ROWS_A_STEP`
    that divide `t`."""
    lanes = _LANES_A_STEP if channels % _LANES_A_STEP == 0 else LANES
    blocks = t // _ROW_BLOCK
    most = max(m for m in range(1, _ROWS_A_STEP // _ROW_BLOCK + 1)
               if blocks % m == 0)
    return most * _ROW_BLOCK, lanes


def _coefficients(kernel, bias):
    """(taps, channels) and (channels,) or None -> (8, channels) float32."""
    rows = [kernel.astype(jnp.float32)]
    if bias is not None:
        rows.append(bias.astype(jnp.float32)[None])
    coef = jnp.concatenate(rows, axis=0)
    return jnp.pad(coef, ((0, _COEF_ROWS - coef.shape[0]), (0, 0)))


def _halo_rows(ref, edge, at, rows, chunk, *, after):
    """The float32 tile of rows before (or `after`) the chunk at row `at`
    of a block of `rows`: the block's own neighbours, or `edge`'s — the
    view's 16 rows, zeroed by the caller where T ends — at the block's
    end."""
    if after:
        inside = at + chunk < rows
        start = jnp.minimum(at + chunk, rows - SUBLANES)
    else:
        inside = at > 0
        start = jnp.maximum(at - SUBLANES, 0)
    own = ref[0, pl.ds(pl.multiple_of(start, SUBLANES), SUBLANES), :]
    tile = jnp.where(inside, own.astype(jnp.float32), edge)
    # a packed bfloat16 tile is 16 rows; the 8 that touch the chunk
    return tile[:_HALO] if after else tile[SUBLANES - _HALO:]


def _half_pre_activation(window, coef_ref, taps, has_bias):
    """The shifted float32 rows of `window` (eight of halo, then the
    rows asked for) and HALF their weighted sum — the coefficients come
    halved: ([x[t-s] for s], pre / 2)."""
    shifted = [window[_HALO:]] + [
        pltpu.roll(window, s, 0)[_HALO:] for s in range(1, taps)]
    half = shifted[0] * coef_ref[taps - 1:taps, :]
    for s in range(1, taps):
        half = half + shifted[s] * coef_ref[taps - 1 - s:taps - s, :]
    if has_bias:
        half = half + coef_ref[taps:taps + 1, :]
    return shifted, half


def _fwd_kernel(x_ref, before_ref, coef_ref, o_ref, *, taps, has_bias,
                chunk):
    rows = x_ref.shape[1]
    before = jnp.where(pl.program_id(2) > 0,
                       before_ref[0].astype(jnp.float32), 0.0)

    def one_chunk(r, _):
        at = pl.multiple_of(r * chunk, chunk)
        window = jnp.concatenate([
            _halo_rows(x_ref, before, at, rows, chunk, after=False),
            x_ref[0, pl.ds(at, chunk), :].astype(jnp.float32)], axis=0)
        _, half = _half_pre_activation(window, coef_ref, taps, has_bias)
        # silu(p) = p sigmoid(p) = p/2 + p/2 tanh(p/2)
        o_ref[0, pl.ds(at, chunk), :] = (
            half + half * jnp.tanh(half)).astype(o_ref.dtype)

    jax.lax.fori_loop(0, rows // chunk, one_chunk, None)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                coef_ref, dx_ref, dcoef_ref, *, taps, has_bias, chunk):
    rows = x_ref.shape[1]
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    before = jnp.where(first, 0.0, before_ref[0].astype(jnp.float32))
    after = jnp.where(last, 0.0, after_ref[0].astype(jnp.float32))
    dy_after = jnp.where(last, 0.0, dy_after_ref[0].astype(jnp.float32))

    @pl.when(jnp.logical_and(first, pl.program_id(1) == 0))
    def _():
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    def one_chunk(r, _):
        at = pl.multiple_of(r * chunk, chunk)
        # x over the chunk and a tile either side; pre and dpre over the
        # chunk and the tile after it, whose dpre the last rows' dx read
        window = jnp.concatenate([
            _halo_rows(x_ref, before, at, rows, chunk, after=False),
            x_ref[0, pl.ds(at, chunk), :].astype(jnp.float32),
            _halo_rows(x_ref, after, at, rows, chunk, after=True)], axis=0)
        d_out = jnp.concatenate([
            dy_ref[0, pl.ds(at, chunk), :].astype(jnp.float32),
            _halo_rows(dy_ref, dy_after, at, rows, chunk, after=True)],
            axis=0)
        shifted, half = _half_pre_activation(window, coef_ref, taps,
                                             has_bias)
        # silu'(p) = s (1 + p (1 - s)), s = sigmoid(p) = (1 + th) / 2:
        # TWICE dpre, the other half rides on the halved coefficients
        th = jnp.tanh(half)
        dpre = d_out * ((1.0 + th) * (1.0 + half * (1.0 - th)))
        dx = dpre[:chunk] * coef_ref[taps - 1:taps, :]
        for s in range(1, taps):
            dx = dx + pltpu.roll(dpre, chunk + _HALO - s, 0)[:chunk] \
                * coef_ref[taps - 1 - s:taps - s, :]
        dx_ref[0, pl.ds(at, chunk), :] = dx.astype(dx_ref.dtype)
        own = dpre[:chunk]
        for s in range(taps):
            dcoef_ref[taps - 1 - s:taps - s, :] += jnp.sum(
                own * shifted[s][:chunk], axis=0, keepdims=True)
        if has_bias:
            dcoef_ref[taps:taps + 1, :] += jnp.sum(own, axis=0,
                                                   keepdims=True)

    jax.lax.fori_loop(0, rows // chunk, one_chunk, None)


def _specs(t, rows, lanes, first=0):
    """The block of a grid step (channel block c, batch row b, row block
    i) of an array in which the convolution's channels start at channel
    block `first`, the 16 rows before it and after it (clamped into T),
    and the channel block's coefficients."""
    tiles, last = rows // SUBLANES, t // SUBLANES - 1
    block = pl.BlockSpec((1, rows, lanes), lambda c, b, i: (b, i, c + first))
    before = pl.BlockSpec((1, SUBLANES, lanes), lambda c, b, i: (
        b, jnp.maximum(i * tiles - 1, 0), c + first))
    after = pl.BlockSpec((1, SUBLANES, lanes), lambda c, b, i: (
        b, jnp.minimum((i + 1) * tiles, last), c + first))
    coef = pl.BlockSpec((_COEF_ROWS, lanes), lambda c, b, i: (0, c))
    return block, before, after, coef


def _fwd_pallas(x, coef, *, taps, has_bias, dtype, rows, chunk, first,
                interpret):
    """x (b, T, >= first lane blocks + channels): the convolution reads
    the coefficients' channels of it, from lane block `first` on."""
    (b, t, _), channels = x.shape, coef.shape[1]
    lanes = _blocks(t, channels)[1]
    block, before, _, coefs = _specs(t, rows, lanes, first)
    size, out = jnp.dtype(x.dtype).itemsize, jnp.dtype(dtype).itemsize
    # blocks in and out and the halo's, double-buffered, and a chunk's
    # float32 window, its shifted forms and their sum
    vmem = 2 * (rows + SUBLANES) * lanes * (size + out) \
        + (taps + 4) * (chunk + 2 * _HALO) * lanes * 4
    numbers = b * t * channels
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, has_bias=has_bias,
                          chunk=chunk),
        grid=(channels // lanes, b, t // rows),
        in_specs=[block, before, coefs],
        out_specs=_specs(t, rows, lanes)[0],
        out_shape=_out_struct((b, t, channels), dtype, x),
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel",
            vmem_limit=_reckoned_vmem(vmem)),
        cost_estimate=pl.CostEstimate(
            flops=(2 * taps + 4) * numbers, transcendentals=numbers,
            bytes_accessed=numbers * (size + out) + coef.size * 4),
        interpret=interpret,
        name="dwt_conv_fwd",
    )(x, x, 0.5 * coef)


def _bwd_pallas(x, coef, d_out, *, taps, has_bias, rows, chunk, first,
                interpret):
    b, t, channels = d_out.shape
    lanes = _blocks(t, channels)[1]
    block, before, after, coefs = _specs(t, rows, lanes, first)
    own, _, own_after, _ = _specs(t, rows, lanes)
    size, d_size = (jnp.dtype(a.dtype).itemsize for a in (x, d_out))
    vmem = 2 * (rows + 2 * SUBLANES) * lanes * (2 * size + d_size) \
        + (2 * taps + 8) * (chunk + 2 * _HALO) * lanes * 4
    numbers = b * t * channels
    dx, twice = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, has_bias=has_bias,
                          chunk=chunk),
        grid=(channels // lanes, b, t // rows),
        in_specs=[block, before, after, own, own_after, coefs],
        out_specs=[own, coefs],
        out_shape=[_out_struct(d_out.shape, x.dtype, x),
                   _out_struct(coef.shape, jnp.float32, x)],
        compiler_params=_compiler_params(
            "parallel", "arbitrary", "arbitrary",
            vmem_limit=_reckoned_vmem(vmem)),
        cost_estimate=pl.CostEstimate(
            flops=(6 * taps + 10) * numbers, transcendentals=numbers,
            bytes_accessed=numbers * (2 * size + d_size)
            + 2 * coef.size * 4),
        interpret=interpret,
        name="dwt_conv_bwd",
    )(x, x, x, d_out, d_out, 0.5 * coef)
    return dx, 0.5 * twice


# behind `jax.jit` a body is traced and lowered to Mosaic once a shape,
# not once a call (a layer's forward, its recomputation, Ling's three)
_STATIC = ("taps", "has_bias", "rows", "chunk", "first", "interpret")
_conv_fwd = jax.jit(_fwd_pallas, static_argnames=_STATIC + ("dtype",))
_conv_bwd = jax.jit(_bwd_pallas, static_argnames=_STATIC)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(x, read, coef, plan):
    """`read` is what the kernels read — x itself, or the wider array x
    is a slice of; x carries the cotangent, `read` none."""
    del x
    return _conv_fwd(read, coef, **dict(plan))


def _conv_vjp_fwd(x, read, coef, plan):
    return _conv(x, read, coef, plan), (read, coef)


def _conv_vjp_bwd(plan, kept, d_out):
    plan = {k: v for k, v in plan if k != "dtype"}
    dx, dcoef = _conv_bwd(*kept, d_out, **plan)
    return dx, None, dcoef


_conv.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def _conv_kernels(x, kernel, bias, dtype, source=None, rows=None,
                  chunk=None, interpret=False):
    """`conv_silu_rows` whatever the route says (tests reach the kernels
    in interpret mode through here, at a few rows a grid step and a
    chunk; the probe its rows a grid step)."""
    block, lanes = _blocks(*x.shape[1:])
    rows = rows or block
    wide, lane = source or (x, 0)
    if lane % lanes:  # no whole channel blocks into it: x's own copy
        wide, lane = x, 0
    plan = (("taps", kernel.shape[0]), ("has_bias", bias is not None),
            ("dtype", jnp.dtype(dtype)), ("rows", rows),
            ("chunk", chunk or min(rows, _CHUNK)), ("first", lane // lanes),
            ("interpret", interpret))
    return _conv(x, wide, _coefficients(kernel, bias), plan)


def conv_silu_rows(x, kernel, bias, dtype, source=None):
    """silu(causal depthwise convolution of x (b, T, channels) along T)
    by `kernel` (taps, channels), its last tap on the current step, and
    `bias` (channels,) or None, in `dtype`: the kernel route of
    `models/mamba2.causal_conv_silu`, for calls of which `conv_route`
    says "kernel".  Differentiable in x, the filter and the bias.

    `source` (rows, lane) says x is `rows[..., lane:lane + channels]`:
    the kernels then read x THERE, in the array it was sliced from (a
    custom call's operand is an array of its own, so the slice would be
    copied out first: two more passes a phase, and the copy live), and
    x itself is only where the cotangent goes."""
    return _conv_kernels(x, kernel, bias, dtype, source)
