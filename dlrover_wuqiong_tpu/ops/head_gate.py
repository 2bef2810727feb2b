"""A gate a head multiplied on the attention kernels' own rows in ONE pass.

    y'[.., t, head, :] = y[.., t, head, :] * g[.., t, head]

on rows laid out as the direct attention kernels write them and the
output projection reads them: (B, T, heads * d), the heads side by side,
d a whole number of 128-lane slabs.  g is (B, T, heads), float32 — one
number a head and position, 1/128 of y's size at d = 128.

Spread over a head's lanes in `jax.numpy` (`jnp.repeat(g, d, axis=-1)`),
g becomes a (B, T, heads, d) broadcast whose reshape to (B, T, heads * d)
is no bitcast in the chip's tiled layout — T sits on the sublanes of one
and the heads on those of the other — so the compiler writes the
broadcast, lays it out again and copies it, a float32 array of y's size
a layer and phase, before the multiply, and does the same to the
cotangent's sum a head.  Here nothing of y's size is made for g.

`dwt_gate` (one `pallas_call`) reads a block of y once and writes it
once.  A grid step is (a tile of `_ROW_TILE` positions, a batch row); its
block is the rows' whole width and the tile's (tile, heads) of g, worked
on a head at a time in ONE traced loop (`lax.fori_loop`: a head's lanes
start at a slab's edge, so a traced head indexes them): a head's column
of g is one lane of the block — the lane a mask keeps, summed out — and
is spread over the head's slabs in the vector registers.  The product is
float32 whatever the rows hold and rounded once — the arithmetic of the
`jax.numpy` line, bit for bit.

`dwt_gate_bwd` is the backward pass of the `jax.custom_vjp`, one pass
too: from blocks of the cotangent dy', y and g it writes dy = dy' * g a
head and dg = the sum over the head's lanes of dy' * y
(`mosaic._row_dot`, float32), the heads' sums gathered into the (tile,
heads) block they leave in.  It keeps y and g — under full
recomputation y is the recomputed forward's, so nothing new outlives the
forward pass.

Which calls take it is what a call can observe, never a knob
(`gate_route`): a head of whole slabs, rows of whole heads, on one of
`_SITES`.  Every other call keeps `models/llama.LlamaAttention`'s own
line, which is the plain route and the tests' oracle.

What a v5e trace showed: PERF.md section 6, PR 61
(`tools/perf_probe.py gate`).

Parity: none — the reference gates with torch ops on (B, T, heads, d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import mosaic
from .mosaic import (
    LANES,
    _compiler_params,
    _iota,
    _out_struct,
    _put,
    _reckoned_vmem,
    _row_dot,
)

# positions a grid step: three bf16 blocks of 8,192 lanes, twice, are
# 12 MB — under the compiler's own 16 MiB, so the call asks for nothing
# its neighbours' staged operands would have to make room for
_ROW_TILE = 128
_SITES = frozenset({"device", "manual"})  # a row at a time: a shard is one


def gate_route(lanes: int, d: int, mesh=None) -> str:
    """Which route the gate of rows `lanes` wide, heads of `d`, takes:
    "kernel" (`dwt_gate` and its backward) when a head is one or more
    whole 128-lane slabs, the rows are whole heads, and the call runs on
    one of `_SITES` (`mesh` is the model config's); else "plain",
    `models/llama.LlamaAttention`'s own line.  The static counter of the
    decision, with the compiled step's count of the pair's custom calls,
    as `ops/rope.rope_route` is of the rotation's."""
    if d % LANES or lanes % d \
            or mosaic.kernel_site(mesh) not in _SITES:
        return "plain"
    return "kernel"


def _head_lanes(head, d):
    """Head `head`'s lanes of a block's row: whole slabs from a slab's
    edge, so a traced `head` indexes them as a constant would."""
    return pl.ds(pl.multiple_of(head * d, LANES), d)


def _column(g, head_of, head):
    """Head `head`'s column of the tile's g, (tile, 1): the one lane the
    mask keeps, summed out — exact, and a traced `head` can ask it."""
    return jnp.where(head_of == head, g, 0.0).sum(-1, keepdims=True)


def _gate_kernel(y_ref, g_ref, o_ref):
    """One (position tile, batch row): every head of the block times its
    column of the tile's g.  ONE traced loop body whatever the heads: 64
    unrolled in Python cost seconds of every run's lowering."""
    g = g_ref[0]
    d = y_ref.shape[-1] // g.shape[-1]
    head_of = _iota(g.shape, 1)

    def gate(head, _):
        lanes = _head_lanes(head, d)
        o_ref[0, :, lanes] = (y_ref[0, :, lanes].astype(jnp.float32)
                              * _column(g, head_of, head)
                              ).astype(o_ref.dtype)

    jax.lax.fori_loop(0, g.shape[-1], gate, None)


def _gate_bwd_kernel(dy_ref, y_ref, g_ref, dx_ref, dg_ref):
    """One (position tile, batch row) of the backward pass: dy' times g a
    head, and dy' . y summed over the head's lanes into its column of the
    tile's dg."""
    g = g_ref[0]
    d = y_ref.shape[-1] // g.shape[-1]
    head_of = _iota(g.shape, 1)

    def gate(head, dg):
        lanes = _head_lanes(head, d)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        dx_ref[0, :, lanes] = (dy * _column(g, head_of, head)
                               ).astype(dx_ref.dtype)
        return _put(dg, head_of, head, _row_dot(dy, y_ref[0, :, lanes]))

    dg_ref[0] = jax.lax.fori_loop(0, g.shape[-1], gate, jnp.zeros_like(g))


def _blocks(y, g, tile):
    """The grid (ceil(T / tile), B) and its two kinds of block: y's rows
    and g's.  What a last tile reads behind T is never written."""
    b, t, lanes = y.shape
    return ((pl.cdiv(t, tile), b),
            pl.BlockSpec((1, tile, lanes), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, tile, g.shape[-1]), lambda i, j: (j, i, 0)))


def _held(y, passes, tile):
    # `passes` blocks of y's rows and two of g's (a slab wide in VMEM),
    # double-buffered, and the float32 temporaries of a head's slabs
    size = jnp.dtype(y.dtype).itemsize
    return 2 * (passes * tile * y.shape[-1] * size + 2 * tile * LANES * 4) \
        + 8 * tile * LANES * 4


def _gate_pallas(y, g, *, tile, interpret):
    """y (B, T, lanes) times g (B, T, heads), a head's lanes by its g."""
    grid, rows, gates = _blocks(y, g, tile)
    size = jnp.dtype(y.dtype).itemsize
    return pl.pallas_call(
        _gate_kernel,
        grid=grid,
        in_specs=[rows, gates],
        out_specs=rows,
        out_shape=_out_struct(y.shape, y.dtype, y),
        compiler_params=_compiler_params(
            "parallel", "arbitrary",
            vmem_limit=_reckoned_vmem(_held(y, 2, tile))),
        cost_estimate=pl.CostEstimate(
            flops=y.size, transcendentals=0,
            bytes_accessed=2 * y.size * size + g.size * 4),
        interpret=interpret,
        name="dwt_gate",
    )(y, g)


def _gate_bwd_pallas(d_out, y, g, *, tile, interpret):
    """(dy, dg) of `_gate_pallas` from its cotangent, y and g."""
    grid, rows, gates = _blocks(y, g, tile)
    size = jnp.dtype(y.dtype).itemsize
    return pl.pallas_call(
        _gate_bwd_kernel,
        grid=grid,
        in_specs=[rows, rows, gates],
        out_specs=[rows, gates],
        out_shape=[_out_struct(y.shape, y.dtype, y),
                   _out_struct(g.shape, g.dtype, g)],
        compiler_params=_compiler_params(
            "parallel", "arbitrary",
            vmem_limit=_reckoned_vmem(_held(y, 3, tile))),
        cost_estimate=pl.CostEstimate(
            flops=3 * y.size, transcendentals=0,
            bytes_accessed=3 * y.size * size + 2 * g.size * 4),
        interpret=interpret,
        name="dwt_gate_bwd",
    )(d_out, y, g)


# behind `jax.jit` a body is traced and lowered to Mosaic once a shape,
# not once a call (five layers, forward and recomputed)
_gate = jax.jit(_gate_pallas, static_argnames=("tile", "interpret"))
_gate_bwd = jax.jit(_gate_bwd_pallas, static_argnames=("tile", "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated(y, g, plan):
    return _gate(y, g, **dict(plan))


def _gated_fwd(y, g, plan):
    return _gated(y, g, plan), (y, g)


def _gated_bwd(plan, kept, d_out):
    return _gate_bwd(d_out, *kept, **dict(plan))


_gated.defvjp(_gated_fwd, _gated_bwd)


def _gate_kernels(y, g, tile=None, interpret=False):
    """`gate_rows` whatever the route says (tests reach the pair in
    interpret mode through here, the probe its tile)."""
    # all of a shorter sequence: a block's rows are a multiple of a
    # packed bfloat16 tile, 16, or the array's own
    plan = (("tile", min(y.shape[1], tile or _ROW_TILE)),
            ("interpret", interpret))
    return _gated(y, g.astype(jnp.float32), plan)


def gate_rows(y, g):
    """y (B, T, heads * d) times g (B, T, heads), each head's lanes by its
    own g, in y's dtype: the kernel route of `models/llama.
    LlamaAttention`'s output gate, for calls of which `gate_route` says
    "kernel".  Differentiable in both."""
    return _gate_kernels(y, g)
