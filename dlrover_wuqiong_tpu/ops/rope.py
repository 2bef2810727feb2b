"""Rotary position embedding over the projections' rows in ONE pass.

    y[.., t, head, :] = (x1 cos_t - x2 sin_t | x2 cos_t + x1 sin_t | x3)

for each head's rotated halves (x1 | x2) and whatever lies behind them
(x3: nothing, in most models), on rows laid out as the projections leave
them: (B, T, heads * d), the heads side by side.

`dwt_rope` (one `pallas_call`) reads a block of rows once and writes it
once.  A grid step is (a tile of `_ROW_TILE` positions, a batch row); its
block is the rows' whole width, worked on a 128-lane slab at a time.  The
block's positions pick their rows of ONE float32 table over one slab,
`[cos | sin]` a head (`rope_table`: (T, 128), a head's d at d = 128, two
heads' at d = 64; 8 MB at T = 16,384), the same for every head and every
batch row — the batch is the inner grid axis, so a tile of the table is
fetched once for all of it — and nothing of the rows' size is ever tiled
out in HBM.  A grid step turns its tile of the table into `[cos | cos]`
and `[-sin | sin]` (a roll by d/2 and two selects, once for all the
slabs).  Inside a slab the partner of lane i is lane i +- d/2 of the
SAME head: at d = 128 a head is a slab and both directions are the one
cyclic `pltpu.roll` by 64; at d = 64 two heads share a slab, and the
partner is the roll by 32 in a head's upper half and by 96 (= -32) in
its lower — neither wraps into a lane that is kept.  The sign rides on
the sine: two products and one sum an element, in float32 whatever the
rows hold, rounded once.

A head rotated IN PART (tables narrower than half the head: Laguna's
full layers turn the first 64 of 128 features) is the same pass.  The
table is `[cos | sin | 0 ..]` a head, and the tile's two forms become
`[cos | cos | 1 ..]` and `[-sin | sin | 0 ..]`: a lane's place in its
head is an iota % d, and the lanes behind the rotated width are SELECTED
to exactly 1 and exactly 0, not read from the table — under YaRN the
tables carry the row's attention factor, and a passed feature has none.
With the sine no longer a half BEHIND a cosine's partner but only ahead
of it, the two forms are built from both rolls of the table.  The
partner of a rotated lane is still lane +- half of the same head (the
two rolls of x, as at d = 64), a rotated lane, so no roll wraps a passed
lane's value into a kept product; a passed lane's own partner (another
passed lane, where the rotated width divides the head) is multiplied by
that zero: a finite one vanishes, as in the formula.  Which form is
traced is a Python branch on static widths: a whole head's body is what
it was before the branch, line for line.

The cotangent of a rotation by theta is the rotation by -theta: the
backward pass of the `jax.custom_vjp` is the SAME kernel with the sine
negated (in VMEM, a tile a grid step), and keeps nothing but the table;
a passed lane's cotangent passes.

Which calls take it is what a call can observe, never a knob
(`rope_route`): d of 64 or 128; a rotated width (the tables', read by
the caller) of the head's or of a part of it that divides a slab as a
head may — 64 or 32 of 128, 32 of 64; rows whose width is a whole number
of slabs — or ONE head of 64, half a slab, which is padded to one
(latent attention's shared key part: it then reads the table its q heads
read, and the formula's own tables are not built at all) — on one of
`_SITES`.  Every other call keeps the formula of
`models/llama.apply_rope`, which is the plain route and the tests'
oracle.

What a v5e trace showed: PERF.md section 6, PR 44 and PR 56
(`tools/perf_probe.py rope`).

Parity: none — the reference rotates with torch ops a head at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import LANES, _compiler_params, _out_struct, _reckoned_vmem

_ROW_TILE = 512  # positions a grid step: a bf16 block of 3,584 lanes is 3.5 MB
_SITES = frozenset({"device", "manual"})  # a row at a time: a shard is one


def rope_route(lanes: int, d: int, mesh=None, rotated: int = 0) -> str:
    """Which route a rotation of rows `lanes` wide, heads of `d` whose
    first `rotated` features turn (0: all of them; a call reads it off
    its tables' width), takes: "kernel" (`dwt_rope`) when a head is a
    slab or half of one (`mosaic.slab_heads` 1 or 2 of one slab), the
    rotated part is the head or a part of it that divides a slab as a
    head may (`mosaic.slab_heads`: 128, 64 or 32 lanes), the rows are a
    whole number of 128-lane slabs or one lone head (half a slab:
    padded), and the call runs on one of `_SITES` (`mesh` is the model
    config's); else "plain", `models/llama.apply_rope`'s own lines.  A
    head of TWO slabs is "plain" whatever turns: d = 256 with its first
    64 lanes rotated (`rope_route(4096, 256, mesh, 64)`: qwen3_next's
    attention) takes the formula — the kernel walks a row a slab at a
    time and a head's place in it is an iota % d within ONE slab
    (tests/test_kernel_site.py pins the two calls).  The
    static counter of the decision, with the compiled step's count of
    `dwt_rope` custom calls, as `ops/ssd.scan_route` is of the scan's."""
    heads = mosaic.slab_heads(d)
    a_slab = heads in (1, 2) and heads * d == LANES  # d of 128, or 64
    slabs = lanes % LANES == 0 or lanes == d  # whole, or a lone head
    part = rotated or d  # the table's pairs tile a head's first lanes
    turns = part <= d and mosaic.slab_heads(part) * part == LANES
    if not (a_slab and slabs and turns) \
            or mosaic.kernel_site(mesh) not in _SITES:
        return "plain"
    return "kernel"


def rope_table(cos, sin, d: int = 0):
    """`rope_freqs`' (T, half) cos and sin -> the kernel's float32 table
    over one 128-lane slab, (T, 128): `[cos | sin]` a head, two heads
    side by side at d = 64.  A head `d` wider than its 2 * half rotated
    features is `[cos | sin | 0 ..]`: the kernel reads nothing behind
    the sine."""
    head = jnp.concatenate([cos, sin], axis=-1).astype(jnp.float32)
    if d > head.shape[-1]:
        head = jnp.pad(head, ((0, 0), (0, d - head.shape[-1])))
    return jnp.tile(head, (1, LANES // head.shape[-1]))


def _rope_kernel(x_ref, table_ref, o_ref, *, half, d, inverse):
    """One (position tile, batch row): every slab of the block rotated
    by the tile's rows of the table — by their negative where `inverse`.
    Heads of `d` lanes turn their first 2 * half and pass the rest."""
    cos_sin = table_ref[...]
    sin_cos = pltpu.roll(cos_sin, half, 1)
    # which half of its head a lane lies in
    lane = jax.lax.broadcasted_iota(jnp.int32, cos_sin.shape, 1)
    upper = (lane // half) % 2 == 1
    # a lower half's sine lies half AHEAD of it: over whole heads that
    # is the roll by half again, behind a rotated part it is not
    sin_ahead = sin_cos if d == 2 * half else pltpu.roll(
        cos_sin, LANES - half, 1)
    c = jnp.where(upper, sin_cos, cos_sin)     # [cos | cos ..
    s = jnp.where(upper, cos_sin, -sin_ahead)  # [-sin | sin ..
    if d > 2 * half:  # .. | 1 ..] and .. | 0 ..]: the lanes that pass
        turns = lane % d < 2 * half
        c, s = jnp.where(turns, c, 1.0), jnp.where(turns, s, 0.0)
    if inverse:
        s = -s
    for slab in range(x_ref.shape[-1] // LANES):
        lanes = slice(slab * LANES, (slab + 1) * LANES)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        partner = pltpu.roll(x, half, 1)
        if 2 * half < LANES:  # two heads a slab, or a head's first part
            partner = jnp.where(upper, partner,
                                pltpu.roll(x, LANES - half, 1))
        o_ref[0, :, lanes] = (x * c + partner * s).astype(o_ref.dtype)


def _rope_pallas(x, table, *, half, d, inverse, tile, interpret):
    """x (B, T, lanes) rotated by the table (T, 128).  Grid: (ceil(T /
    tile), B); what a last tile reads behind T is never written."""
    b, t, lanes = x.shape
    size = jnp.dtype(x.dtype).itemsize
    rows = pl.BlockSpec((1, tile, lanes), lambda i, j: (j, i, 0))
    # blocks in and out and the table's tile, double-buffered, and the
    # float32 temporaries of the table's two forms and of a slab
    vmem = 2 * (2 * tile * lanes * size + tile * LANES * 4) \
        + 10 * tile * LANES * 4
    return pl.pallas_call(
        functools.partial(_rope_kernel, half=half, d=d, inverse=inverse),
        grid=(pl.cdiv(t, tile), b),
        in_specs=[rows, pl.BlockSpec((tile, LANES), lambda i, j: (i, 0))],
        out_specs=rows,
        out_shape=_out_struct(x.shape, x.dtype, x),
        compiler_params=_compiler_params(
            "parallel", "arbitrary", vmem_limit=_reckoned_vmem(vmem)),
        cost_estimate=pl.CostEstimate(
            flops=3 * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * size + t * LANES * 4),
        interpret=interpret,
        name="dwt_rope",
    )(x, table)


# behind `jax.jit` the body is traced and lowered to Mosaic once a shape,
# not once a call (three layers x q and k, forward and backward)
_rope = jax.jit(_rope_pallas,
                static_argnames=("half", "d", "inverse", "tile", "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rotated(x, table, plan):
    return _rope(x, table, inverse=False, **dict(plan))


def _rotated_fwd(x, table, plan):
    return _rotated(x, table, plan), table


def _rotated_bwd(plan, table, d_out):
    return _rope(d_out, table, inverse=True, **dict(plan)), None


_rotated.defvjp(_rotated_fwd, _rotated_bwd)


def _rope_kernels(x, cos, sin, head_dim=0, tile=None, interpret=False):
    """`rotate_rows` whatever the route says (tests reach the kernel in
    interpret mode through here, the probe its tile)."""
    t, lanes = x.shape[1:]
    half = cos.shape[-1]
    d = head_dim or 2 * half
    # all of a shorter sequence: a block's rows are a multiple of a
    # packed bfloat16 tile, 16, or the array's own
    plan = (("half", half), ("d", d), ("tile", min(t, tile or _ROW_TILE)),
            ("interpret", interpret))
    if lanes < LANES:  # a lone head of 64: an empty head beside it
        x = jnp.pad(x, ((0, 0), (0, 0), (0, LANES - lanes)))
    return _rotated(x, rope_table(cos[:t], sin[:t], d), plan)[..., :lanes]


def rotate_rows(x, cos, sin, head_dim=0):
    """x (B, T, heads * d) rotated head by head, position t by row t of
    `rope_freqs`' cos and sin ((T', half), T' >= T; a head's first
    2 * half features turn, those behind them, where `head_dim` d is
    wider, pass): the kernel route of `models/llama.apply_rope`, for
    calls of which `rope_route` says "kernel".  Differentiable in x; the
    tables are constants."""
    return _rope_kernels(x, cos, sin, head_dim)
