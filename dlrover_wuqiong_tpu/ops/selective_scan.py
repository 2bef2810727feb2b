"""The selective scan of a Mamba-1 layer (Gu & Dao 2023, arXiv:2312.00752).

The recurrence, per channel d of D and state n of N (h_0 = 0):

    h_t[d, n] = exp(dt_t[d] * A[d, n]) * h_{t-1}[d, n]
                + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[d, n] * C_t[n] + D[d] * x_t[d]

A decay for every (channel, state) PAIR: no state-space dual, so
`ops/ssd.py`'s chunked form — ONE decay a head — cannot compute it (as
heads of P = 1, N = 1 it would be D * N heads of an L x L product each).
It is elementwise work on the vector units, D * N multiply-adds and
exponentials a token, sequential in t.  dt, A, every exp and h are
float32 whatever x, B and C arrive in; y leaves in float32.

Two routes compute it, chosen by `sscan_route` from what a call can
observe (its shapes, where it runs), never by a knob:

- "kernel": a pair of Pallas (Mosaic) kernels, `dwt_sscan_fwd` and
  `dwt_sscan_bwd`, behind one `jax.custom_vjp`.  A grid step is one
  (batch row, chunk of `_CHUNK` steps, block of `_BLOCK` channels); the
  chunks are walked in order (in reverse by the backward kernel) and the
  state of a block, (N, block) float32 — the states on sublanes, the
  channels on lanes — is carried through a chunk in registers and
  between chunks in a VMEM scratch.  The forward kernel keeps, for the
  backward, the state ENTERING every chunk, (b, T / chunk, N, D) float32;
  the backward kernel walks a chunk forward from it, keeping the chunk's
  states in VMEM, then backward: no array of T x D x N elements is an
  operand or a result of any op.  B and C reach the kernels spread over
  a lane tile, (b, T, N, 128) float32: a step's (N, 1) column, which
  every channel of a block multiplies, is then one aligned read, and
  their cotangents leave summed over a chunk's channel blocks in the
  same form, the 128 lanes' sum `jax.numpy`'s (the transpose of the
  spread, which JAX differentiates).
- "plain": `jax.numpy` — a `lax.scan` over the chunks of a
  rematerialised `lax.scan` over a chunk's steps, so that the backward
  pass keeps the chunk-boundary states and one chunk's steps; a sequence
  that is no whole number of chunks is padded with steps that change
  nothing (dt = 0).  Off the TPU, on a mesh of several devices (a Mosaic
  kernel cannot be partitioned by GSPMD; `_SITES`), at shapes the
  kernels do not take — and the tests' oracle.

`benchmark/`'s `kernel.sscan_roofline` counts the RECURRENCE's work from
shapes, whatever computes it.

Scopes (under the caller's): `sscan` around all of it; the kernels'
custom calls, forward, recomputed and backward, carry it.

Parity: none — the reference (atorch's modules and kernels) has no
state-space layer; this is the paper's recurrence.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import LANES, _compiler_params, _iota, _out_struct


# ------------------------------------------------------------ the route

# The geometry, measured on the chip at the cell's shape (1 x 8,192 x
# 5,120 x 16; `tools/perf_probe.py sscan`, PERF.md section 6, PR 72),
# forward | backward kernel, ms a call, by channels x steps a grid step:
#            64 steps        128             256
#   256   2.01 | 4.60    1.77 | 4.36    1.70 | 4.24
#   512   1.64 | 4.01    1.55 | 3.91    1.50 | 3.86
#  1024   1.48 | 4.35    1.44 | 4.29    1.43 | 4.27
_BLOCK = 512    # channels a grid step takes: its state is 8 registers
_CHUNK = 256    # steps a grid step takes
_ROWS = 8       # steps read and written at once: a float32 tile's rows
_VMEM_LIMIT = 64 * 1024 * 1024  # this kernel's own request of the compiler
_SITES = frozenset({"device"})  # never run inside a shard_map


def _vmem_bytes(n: int, block: int, chunk: int) -> int:
    """What the backward kernel (the larger) holds: a chunk's states,
    double-buffered blocks of x, dt, dy and the three gradients by row,
    B, C and their gradients spread over a lane tile."""
    return ((chunk + 1) * n * block * 4 + 2 * 7 * chunk * block * 4
            + 2 * 4 * chunk * n * LANES * 4)


def sscan_route(t: int, d: int, n: int, mesh=None,
                chunk: int = _CHUNK) -> Tuple[str, int]:
    """Which route `selective_scan` takes at these shapes: ("kernel",
    channels a grid step) where the call runs on one of `_SITES` (`mesh`
    is the mixer config's), the sequence is whole chunks of whole
    `_ROWS`-step tiles, the channels are whole blocks of whole lane
    tiles, the states whole sublane tiles, and a block fits VMEM; else
    ("plain", 0).  The static counter of the decision (with the compiled
    step's count of `dwt_sscan_*` custom calls), as `ops/ssd.scan_route`
    is of the dual form's."""
    if mosaic.kernel_site(mesh) not in _SITES:
        return "plain", 0
    block = min(_BLOCK, d)
    if t % chunk or chunk % _ROWS or d % block or block % LANES or n % 8:
        return "plain", 0
    if _vmem_bytes(n, block, chunk) > _VMEM_LIMIT:
        return "plain", 0
    return "kernel", block


# ------------------------------------------------------------ the scan

@jax.named_scope("sscan")
def selective_scan(x, dt, a, b_mat, c_mat, d_skip, mesh=None):
    """x (b, T, D); dt (b, T, D), the step sizes AFTER softplus; a
    (D, N), negative; b_mat, c_mat (b, T, N); d_skip (D,); `mesh` the
    mixer config's.  Returns y (b, T, D) in float32."""
    route, block = sscan_route(x.shape[1], x.shape[2], a.shape[1], mesh)
    if route == "kernel":
        y = _scan_kernels(x, dt, a, b_mat, c_mat, _CHUNK, block)
    else:
        y = _scan_plain(x, dt, a, b_mat, c_mat, _CHUNK)
    return y + d_skip.astype(jnp.float32) * x.astype(jnp.float32)


@jax.named_scope("sscan")
def selective_scan_plain(x, dt, a, b_mat, c_mat, d_skip, chunk: int = _CHUNK):
    """`selective_scan` on the plain route whatever the shapes and the
    site: the tests' oracle."""
    return _scan_plain(x, dt, a, b_mat, c_mat, chunk) \
        + d_skip.astype(jnp.float32) * x.astype(jnp.float32)


def _scan_plain(x, dt, a, b_mat, c_mat, chunk):
    bsz, t, d = x.shape
    n = a.shape[1]
    f32 = jnp.float32
    pad = -t % chunk
    # (chunks, L, b, ...): time leads, a padded step has dt = 0
    x, dt, b_mat, c_mat = (
        jnp.pad(v.astype(f32), ((0, 0), (0, pad), (0, 0))).reshape(
            bsz, -1, chunk, v.shape[-1]).transpose(1, 2, 0, 3)
        for v in (x, dt, b_mat, c_mat))
    a = a.astype(f32)

    def step(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def one_chunk(h, rows):
        return jax.lax.scan(step, h, rows)

    _, y = jax.lax.scan(one_chunk, jnp.zeros((bsz, d, n), f32),
                        (x, dt, b_mat, c_mat))
    return y.transpose(2, 0, 1, 3).reshape(bsz, t + pad, d)[:, :t]


# ------------------------------------------------------------ the kernels
#
# Layouts.  x, dt, y and their gradients: (b, T, D), a block (L, W) —
# the mixer's own layout.  A: transposed, (N, D), a block (N, W).  B, C
# and their gradients: (b, T, N, 128), a block (L, N, 128), a step's
# (N, 1) column on every lane.  The state: (N, W) float32, a channel a
# lane.  A step reads row t of x, dt and dy spread over the N sublanes
# and B_t, C_t spread over the W lanes; y_t, d(dt)_t and dx_t are sums
# over the sublanes, dB_t and dC_t over the lanes — those the caller's.

def _over_lanes(tile, width):
    """(N, 128), the same on every lane -> (N, width)."""
    return tile if width == LANES else jnp.concatenate(
        [tile] * (width // LANES), axis=1)


def _lane_tiles_sum(v):
    """(N, W) -> (N, 128): the sum of W's lane tiles."""
    out = v[:, :LANES]
    for g in range(1, v.shape[1] // LANES):
        out = out + v[:, g * LANES:(g + 1) * LANES]
    return out


def _set_row(tile, r, row):
    """tile (8, W) with row r set to row (1, W)."""
    return jnp.where(_iota((_ROWS, 1), 0) == r, row, tile)


def _sscan_fwd_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, y_ref, *rest,
                      save):
    st_ref = rest[0] if save else None
    h_scr, u_scr = rest[-2:]
    k, j = pl.program_id(1), pl.program_id(2)
    chunk, w = dt_ref.shape

    @pl.when(k == 0)
    def _first_chunk():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    if save:
        st_ref[...] = h_scr[j]
    u_scr[...] = dt_ref[...] * x_ref[...].astype(jnp.float32)
    at = at_ref[...]

    def tile(i, h):
        r0 = pl.multiple_of(i * _ROWS, _ROWS)
        dt8, u8 = dt_ref[pl.ds(r0, _ROWS), :], u_scr[pl.ds(r0, _ROWS), :]
        y8 = jnp.zeros((_ROWS, w), jnp.float32)
        for r in range(_ROWS):
            bb = _over_lanes(b_ref[r0 + r], w)
            cc = _over_lanes(c_ref[r0 + r], w)
            h = jnp.exp(dt8[r:r + 1] * at) * h + u8[r:r + 1] * bb
            y8 = _set_row(y8, r, jnp.sum(h * cc, axis=0, keepdims=True))
        y_ref[pl.ds(r0, _ROWS), :] = y8
        return h

    h_scr[j] = jax.lax.fori_loop(0, chunk // _ROWS, tile, h_scr[j])


def _sscan_bwd_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, st_ref, dy_ref,
                      dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                      g_scr, u_scr, h_scr):
    k, j = pl.program_id(1), pl.program_id(2)
    chunk, w = dt_ref.shape

    @pl.when(k == 0)
    def _last_chunk():
        g_scr[j] = jnp.zeros(g_scr.shape[1:], jnp.float32)
        da_ref[j] = jnp.zeros(da_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _first_block():  # dB and dC sum over a chunk's channel blocks
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    u_scr[...] = dt_ref[...] * x_ref[...].astype(jnp.float32)
    at = at_ref[...]

    # the chunk forward from the state that entered it: h_scr[t + 1] is
    # the state AFTER step t
    h_scr[0] = st_ref[...]

    def forward(i, h):
        r0 = pl.multiple_of(i * _ROWS, _ROWS)
        dt8, u8 = dt_ref[pl.ds(r0, _ROWS), :], u_scr[pl.ds(r0, _ROWS), :]
        for r in range(_ROWS):
            bb = _over_lanes(b_ref[r0 + r], w)
            h = jnp.exp(dt8[r:r + 1] * at) * h + u8[r:r + 1] * bb
            h_scr[r0 + r + 1] = h
        return h

    jax.lax.fori_loop(0, chunk // _ROWS, forward, st_ref[...])

    # and backward: g is the cotangent of the state AFTER the step at
    # hand, as the steps behind it left it
    def backward(i, carry):
        g, da = carry
        r0 = pl.multiple_of((chunk // _ROWS - 1 - i) * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        dt8, u8, dy8 = dt_ref[rows, :], u_scr[rows, :], dy_ref[rows, :]
        du8 = jnp.zeros((_ROWS, w), jnp.float32)
        ddt8 = jnp.zeros((_ROWS, w), jnp.float32)
        for r in reversed(range(_ROWS)):
            t = r0 + r
            dyb, dtb = dy8[r:r + 1], dt8[r:r + 1]
            g = g + dyb * _over_lanes(c_ref[t], w)
            dc_ref[t] += _lane_tiles_sum(dyb * h_scr[t + 1])
            db_ref[t] += _lane_tiles_sum(g * u8[r:r + 1])
            du8 = _set_row(du8, r, jnp.sum(
                g * _over_lanes(b_ref[t], w), axis=0, keepdims=True))
            g = g * jnp.exp(dtb * at)     # of the state BEFORE the step
            q = g * h_scr[t]              # of the decay, times the decay
            ddt8 = _set_row(ddt8, r, jnp.sum(q * at, axis=0, keepdims=True))
            da = da + q * dtb
        dx_ref[rows, :] = (du8 * dt8).astype(dx_ref.dtype)
        ddt_ref[rows, :] = ddt8 + du8 * x_ref[rows, :].astype(jnp.float32)
        return g, da

    g, da = jax.lax.fori_loop(
        0, chunk // _ROWS, backward,
        (g_scr[j], jnp.zeros(at.shape, jnp.float32)))
    g_scr[j] = g
    da_ref[j] += da


_PARAMS = _compiler_params("parallel", "arbitrary", "arbitrary",
                           vmem_limit=_VMEM_LIMIT)


def _specs(chunk, block, n, nb, at):
    """BlockSpecs by operand kind; `at(k)` is the chunk a grid step
    works on (the backward kernel walks them in reverse)."""
    return dict(
        row=pl.BlockSpec((None, chunk, block), lambda b, k, j: (b, at(k), j)),
        a=pl.BlockSpec((n, block), lambda b, k, j: (0, j)),
        bc=pl.BlockSpec((None, chunk, n, LANES),
                        lambda b, k, j: (b, at(k), 0, 0)),
        state=pl.BlockSpec((None, None, n, block),
                           lambda b, k, j: (b, at(k), 0, j)),
        # resident over a batch row's chunks and blocks: a sum over time
        da=pl.BlockSpec((None, nb, n, block), lambda b, k, j: (b, 0, 0, 0)))


def _sscan_forward_pallas(x, dt, at, bm, cm, *, chunk, block, save,
                          interpret):
    """y (b, T, D) float32 and, with `save`, the state ENTERING every
    chunk, (b, chunks, N, D) float32, for the backward kernel."""
    bsz, t, d = x.shape
    n = at.shape[0]
    c, nb = t // chunk, d // block
    sp = _specs(chunk, block, n, nb, lambda k: k)
    out_shape = [_out_struct((bsz, t, d), jnp.float32, x)]
    out_specs = [sp["row"]]
    if save:
        out_shape.append(_out_struct((bsz, c, n, d), jnp.float32, x))
        out_specs.append(sp["state"])
    out = pl.pallas_call(
        functools.partial(_sscan_fwd_kernel, save=save),
        grid=(bsz, c, nb),
        in_specs=[sp["row"], sp["row"], sp["a"], sp["bc"], sp["bc"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nb, n, block), jnp.float32),
                        pltpu.VMEM((chunk, block), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="dwt_sscan_fwd",
    )(x, dt, at, bm, cm)
    return tuple(out) if save else (out[0], None)


def _sscan_backward_pallas(x, dt, at, bm, cm, states, dy, *, chunk, block,
                           interpret):
    bsz, t, d = x.shape
    n = at.shape[0]
    c, nb = t // chunk, d // block
    sp = _specs(chunk, block, n, nb, lambda k: c - 1 - k)
    f32 = jnp.float32
    return pl.pallas_call(
        _sscan_bwd_kernel,
        grid=(bsz, c, nb),
        in_specs=[sp["row"], sp["row"], sp["a"], sp["bc"], sp["bc"],
                  sp["state"], sp["row"]],
        out_specs=[sp["row"], sp["row"], sp["bc"], sp["bc"], sp["da"]],
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _out_struct(dt.shape, f32, x),
                   _out_struct(bm.shape, f32, x),
                   _out_struct(cm.shape, f32, x),
                   _out_struct((bsz, nb, n, block), f32, x)],
        scratch_shapes=[pltpu.VMEM((nb, n, block), f32),
                        pltpu.VMEM((chunk, block), f32),
                        pltpu.VMEM((chunk + 1, n, block), f32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="dwt_sscan_bwd",
    )(x, dt, at, bm, cm, states, dy)


# A model's layers call the kernels with the same shapes and the same
# static plan: behind `jax.jit` a kernel body is traced and lowered to
# Mosaic once a step program, not once a layer (`ops/ssd.py`'s way).
_STATIC = ("chunk", "block", "interpret")
_forward = jax.jit(_sscan_forward_pallas, static_argnames=_STATIC + ("save",))
_backward = jax.jit(_sscan_backward_pallas, static_argnames=_STATIC)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunks(x, dt, at, bm, cm, plan):
    """The kernels' pair: (x (b, T, D), dt float32, A transposed (N, D),
    B and C (b, T, N, 128) float32) -> y (b, T, D) float32, without the
    D x term.  `plan`: the static arguments."""
    return _forward(x, dt, at, bm, cm, save=False, **dict(plan))[0]


def _chunks_fwd(x, dt, at, bm, cm, plan):
    y, states = _forward(x, dt, at, bm, cm, save=True, **dict(plan))
    return y, (x, dt, at, bm, cm, states)


def _chunks_bwd(plan, res, dy):
    x, dt, at, bm, cm, states = res
    dx, ddt, db, dc, da = _backward(x, dt, at, bm, cm, states, dy,
                                    **dict(plan))
    # (b, blocks, N, W) -> (N, D), the batch rows summed
    da = da.sum(0).transpose(1, 0, 2).reshape(at.shape)
    return dx, ddt, da, db, dc


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _spread(m):
    """(b, T, N) -> (b, T, N, 128) float32, a number on every lane; its
    transpose, which JAX writes, is the lanes' sum."""
    return jnp.broadcast_to(m.astype(jnp.float32)[..., None],
                            (*m.shape, LANES))


def _scan_kernels(x, dt, a, b_mat, c_mat, chunk, block, interpret=False):
    """The kernel route: `_chunks` on the operands in the kernels'
    layouts, small arrays in `jax.numpy`, differentiated by JAX."""
    plan = (("chunk", chunk), ("block", block), ("interpret", interpret))
    return _chunks(x, dt.astype(jnp.float32), a.astype(jnp.float32).T,
                   _spread(b_mat), _spread(c_mat), plan)
