"""Block-diffusion attention: a clean and a noised copy of a sequence under
a static BLOCK mask that is no triangle and no band.

The 2T positions are `[clean ; noised]`; with blocks of `L` tokens,
b(i) = i // L inside a copy:

    K(clean i)  = { clean j : b(j) <= b(i) }                 block-causal
    K(noised i) = { clean j : b(j) <  b(i) } u { noised j : b(j) = b(i) }

(BD3-LMs' vectorised training, arXiv:2503.09573).  No clean query sees a
noised key; a noised query sees its own block both ways and no other
noised block.  `kept_mask_bd` is that rule as a dense boolean, the jnp
route's mask and every test's oracle; nothing on the kernel route ever
holds a (2T)^2 array of any type.

A sibling of `ops/flash_attention.py`, not a fourth branch inside its
kernels: it shares their helpers (`NEG_INF`, `LOG2E`, `mosaic`'s
products and output struct) and their arithmetic (q pre-scaled
by scale * log2 e in its own dtype, float32 scores and statistics, the
backward in transposed space so lse and delta stay (1, rows) rows), but
its GRID is another thing: the mask is arithmetic on positions, so the
plan (`bd_plan`) is a pure function of (T, L, block, tile) — a LIST of
the (query block, key block) pairs that hold a kept pair, each with the
static variant it runs under — handed to the kernels as a prefetched
scalar array.  A grid step is one entry of the list: the 240 dead tiles
under the diagonal of the noised quadrant (T = 8,192, tiles of 512) are
no grid step, are not fetched and cost nothing; 288 of 1,024 tiles run.
Inside a block the variants cut the work into `tile`-sided pieces as the
causal kernels do (`_work`): pieces below the staircase run unmasked,
the pieces it crosses under a mask of (row block - key block) between
two static bounds, pieces above it not at all.

Two kernels, every name under `dwt_fa_` so that every reader of the
attention kernels' time takes them: `dwt_fa_bd_fwd` (a part of a kv
head's group of query heads a step; the list ordered by query block) and
`dwt_fa_bd_bwd`, the backward as ONE sweep (the list ordered by KEY
block; p recomputed once, then dv, dk and dq from it, their sums whole in
VMEM: `ops/sparse_attention.py`'s form).  The layout is the projections'
own: q (b, 2T, H*d), k and v (b, 2T, KV*d), grouped heads indexed.

`bd_route` says which route a call takes from what it can observe, no
knob: the kernels on one TPU device at heads of whole 128-lane slabs, a
copy a whole number of blocks and L a power of two that divides the
tile, and one head's whole-length dq fits VMEM; the dense `jax.numpy`
lines (`_plain`) everywhere else — every CPU run, and the kernels'
oracle.  `bd_tile_count` is the counter of what a route computes, as
`causal_tile_count` is of the causal kernels.

Parity: none — the reference trains next-token models only.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .flash_attention import LOG2E, NEG_INF
from .mosaic import _compiler_params, _dot, _dot_c0, _dot_t, _out_struct

_SITES = frozenset({"device"})  # never run inside a shard_map
_VMEM_LIMIT = 100 * 1024 * 1024  # what every kernel here asks of 128 MiB
TILE = 512     # side of the score tiles a block on the staircase is cut
# into, and what a block length has to divide (`models/sdar.py` asks)
_GROUP_LANES = 1024  # the most lanes of q a step's query heads span
# (a step's preferred q rows and keys, the most query heads of a group it
# takes), where the copy is whole such blocks (else `TILE`).  Ms a call on
# the chip at the cell's shape, 1 x 2 x 8,192 x 32/4 x 128, by device time
# (`tools/perf_probe.py attn_bd`; PERF.md section 6, PR 71): forward (512,
# 4) 13.44, (1024, 1) 9.38, **(1024, 2) 8.25**, (1024, 4) 20.77, (2048, 1)
# 8.60; backward (512, 2) 17.98, (1024, 1) 17.08, **(1024, 2) 16.59**,
# (2048, 1) 35.53, and FOUR heads, whose sums pass `_VMEM_LIMIT`, with dk
# and dv summed outside: (512, 4) 17.09 + 0.52, (1024, 4) 35.81.  A step's
# instructions decide: four static variants of (1024 x 1024) unrolled for
# four heads, or of (2048 x 2048) for one, run at half (1024, 2)'s speed.
_STEPS = {"forward": (1024, 2), "backward": (1024, 2)}
_FAR = 1 << 30  # a bound no difference of blocks reaches

# the variants a grid step runs under: static programs, chosen by the plan
WHOLE, CLEAN, NOISED, SAME = range(4)
# (row block - key block) a kept pair of a crossed piece lies between
_BOUNDS = {CLEAN: (0, _FAR), NOISED: (1, _FAR), SAME: (0, 0)}


def kept_mask_bd(t: int, block_length: int):
    """(2t, 2t) bool: whether query i of `[clean ; noised]` sees key j."""
    pos = jnp.arange(2 * t)
    noised, blk = pos >= t, (pos % t) // block_length
    qn, kn, qb, kb = noised[:, None], noised[None, :], blk[:, None], blk[None]
    return jnp.where(kn, qn & (kb == qb), jnp.where(qn, kb < qb, kb <= qb))


def _fit(t: int, block_length: int, block: Optional[int],
         tile: Optional[int], which: str = "forward"):
    """(block, tile) a copy of `t` tokens runs at in the `which` kernels:
    their preferred block where it divides the copy, else the tile
    (`block`, `tile`: tests' and sweeps' own); None where no block of
    whole `block_length`s divides the copy."""
    tile = tile or TILE
    block = block or next(
        (b for b in (max(_STEPS[which][0], tile), tile) if t % b == 0), None)
    if block is None or t % block or block % tile or tile % block_length:
        return None
    return block, tile


@functools.lru_cache(maxsize=None)
def bd_plan(t: int, block: int, by_keys: bool = False) -> tuple:
    """The grid steps of a call over copies of `t` tokens in blocks of
    `block`, as (an int32 array of 5 x n, n): `[rows | cols | variant |
    first | last]`, n entries each — the query block, the key block
    (both counted over the 2t positions), the variant the step runs
    under, and whether it is the first or the last step of the block its
    OUTPUT rests on (the query block; with `by_keys` the key block, and
    the list is ordered by it).  A noised query block meets its own
    noised block FIRST: every row then has a live key from its first
    step on, and no softmax state ever rests on a masked row alone."""
    n = t // block
    if by_keys:
        # a clean key block: the clean queries at or behind it, then the
        # noised ones; a noised key block: its own queries alone
        steps = [(copy * n + q, j, WHOLE if q > j else (CLEAN, NOISED)[copy])
                 for j in range(n) for copy in (0, 1) for q in range(j, n)]
        steps += [(n + j, n + j, SAME) for j in range(n)]
        rests = 1
    else:
        steps = [(i, j, CLEAN if i == j else WHOLE)
                 for i in range(n) for j in range(i + 1)]
        for i in range(n):
            steps.append((n + i, n + i, SAME))
            steps += [(n + i, j, NOISED if i == j else WHOLE)
                      for j in range(i + 1)]
        rests = 0
    on = [s[rests] for s in steps]
    first = [int(k == 0 or on[k - 1] != on[k]) for k in range(len(on))]
    last = [int(k == len(on) - 1 or on[k + 1] != on[k])
            for k in range(len(on))]
    table = np.asarray([[s[0] for s in steps], [s[1] for s in steps],
                        [s[2] for s in steps], first, last], np.int32)
    return table.reshape(-1), len(steps)


def _pieces(variant: int, block: int, tile: int) -> dict:
    """{(q0, k0): crossed} of the `tile`-sided pieces of one block that
    hold a kept pair under `variant`; `crossed` pieces are masked."""
    n = block // tile
    if variant == WHOLE:
        return {(r * tile, c * tile): False
                for r in range(n) for c in range(n)}
    if variant == SAME:
        return {(r * tile, r * tile): True for r in range(n)}
    return {(r * tile, c * tile): c == r
            for r in range(n) for c in range(r + 1)}


def _work(variant: int, block: int, tile: int, by_keys: bool) -> list:
    """[(band lo, band hi, [(piece lo, piece hi, bounds | None)])]: bands
    of queries whose pieces are key ranges, or with `by_keys` bands of
    keys whose pieces are query ranges; neighbouring unmasked pieces are
    one.  A whole block is one band of one piece."""
    if variant == WHOLE:
        return [(0, block, [(0, block, None)])]
    tiles = _pieces(variant, block, tile)
    work = []
    for b0 in range(0, block, tile):
        runs = []
        for p0 in range(0, block, tile):
            crossed = tiles.get((p0, b0) if by_keys else (b0, p0))
            if crossed is None:
                continue
            if runs and not crossed and runs[-1][2] is None \
                    and runs[-1][1] == p0:
                runs[-1][1] = p0 + tile
            else:
                runs.append([p0, p0 + tile,
                             _BOUNDS[variant] if crossed else None])
        work.append((b0, b0 + tile, [tuple(r) for r in runs]))
    return work


def bd_tile_count(t: int, block_length: int, route: str = "kernel",
                  block: Optional[int] = None, tile: Optional[int] = None):
    """(score tiles run, tiles that hold a kept pair, (query, key) pairs
    kept, pairs computed) of ONE head and sequence, tiles of `TILE`'s
    side (the whole copy where that does not divide it).

    Static, like the plan: on the kernel route the tiles run are the
    plan's pieces, on the plain route every tile of the (2t)^2 square.
    The live tiles are counted from the rule itself, tile by tile, not
    from the plan: that the two agree is what `attn.bd_tiles_run_share`
    reads as 100%.  Pairs kept: t(t + L)/2 clean to clean, t(t - L)/2
    noised to clean, t L noised to noised = t^2 + t L."""
    fit = _fit(t, block_length, block, tile)
    side = fit[1] if fit else t
    n = t // side
    # tile (a, c) of the clean quadrant is live at or below the diagonal;
    # of the noised-to-clean one too, but for the diagonal tile of a copy
    # that is ONE block long (nothing lies before its only block); of the
    # noised quadrant on the diagonal alone
    live = n * (n + 1) // 2 + n
    live += n * (n - 1) // 2 + (n if side > block_length else 0)
    if route == "kernel" and fit:
        table, steps = bd_plan(t, fit[0])
        run = sum(len(_pieces(int(v), fit[0], side))
                  for v in table[2 * steps:3 * steps])
    else:
        run = (2 * n) ** 2
    return run, live, t * t + t * block_length, run * side * side


def _bwd_vmem(heads: int, s: int, d: int, itemsize: int, block: int) -> int:
    """Bytes of VMEM the backward holds at `heads` query heads and (block
    x block) a step: the whole-length float32 sums with their two output
    buffers (the unit's dq, the kv head's dk and dv), the step's operands
    twice (lse's and delta's rows on 8 sublanes), three float32 score
    blocks.  MiB reckoned | the least Mosaic compiles under at the cell's
    shape: (1024, 2) 79 | 75, (1024, 1) 62 | 57, (512, 4) 102 | 101."""
    step = 4 * (itemsize * block * d * (heads + 1) + 4 * 8 * heads * block)
    return s * d * (heads + 2) * (4 + 2 * itemsize) + step + 12 * block ** 2


def bd_route(t: int, block_length: int, n_head: int, n_kv: int, d: int,
             mesh=None, itemsize: int = 2) -> str:
    """"kernel" or "plain" for a call over copies of `t` tokens, from
    what the call can observe; "plain" too where not ONE head's whole dq
    fits VMEM beside its kv head's dk and dv (`_bwd_vmem`: past 2 x
    14,336 positions at heads of 128 and operands of two bytes)."""
    fit = _fit(t, block_length, None, None, "backward")
    ok = (mosaic.kernel_site(mesh) in _SITES and d % mosaic.LANES == 0
          and n_head % n_kv == 0
          and block_length & (block_length - 1) == 0
          and fit is not None
          and _bwd_vmem(1, 2 * t, d, itemsize, fit[0]) <= _VMEM_LIMIT)
    return "kernel" if ok else "plain"


# ------------------------------------------------------------ the kernels


def _keep(nq: int, nk: int, bounds, shift: int, transposed: bool):
    """Mask of an (nq, nk) piece whose first row and first key open the
    same block of the copies — (nk, nq), queries in lanes, if transposed:
    kept iff bounds[0] <= row block - key block <= bounds[1]."""
    if transposed:
        diff = (jax.lax.broadcasted_iota(jnp.int32, (1, nq), 1) >> shift) \
            - (jax.lax.broadcasted_iota(jnp.int32, (nk, 1), 0) >> shift)
    else:
        diff = (jax.lax.broadcasted_iota(jnp.int32, (nq, 1), 0) >> shift) \
            - (jax.lax.broadcasted_iota(jnp.int32, (1, nk), 1) >> shift)
    lo, hi = bounds
    if lo == hi:
        return diff == lo
    return diff >= lo if hi == _FAR else (diff >= lo) & (diff <= hi)


def _by_variant(plan_ref, n: int, inner) -> None:
    """`inner(variant)` under the step's own variant, each a static
    program."""
    variant = plan_ref[2 * n + pl.program_id(2)]
    for v in (WHOLE, CLEAN, NOISED, SAME):
        pl.when(variant == v)(functools.partial(inner, v))


def _scaled(q, scale: float):
    return (q.astype(jnp.float32) * (scale * LOG2E)).astype(q.dtype)


def _fwd_kernel(plan_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, n: int, heads: int, width: int, scale: float,
                tile: int, shift: int):
    """One entry of the plan: `heads` query heads of one kv head, a block
    of their rows against one block of its keys.  The band's order is
    `flash_attention._fa_grp_fwd_kernel`'s: the group's first products,
    then its softmaxes, then its products with v."""
    step = pl.program_id(2)
    block = q_ref.shape[1]

    @pl.when(plan_ref[3 * n + step] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _band(q0, q1, pieces):
        rows = slice(q0, q1)
        kv = [(k_ref[0, k0:k1], v_ref[0, k0:k1]) for k0, k1, _ in pieces]
        masks = [None if b is None else _keep(q1 - q0, k1 - k0, b, shift,
                                              False)
                 for k0, k1, b in pieces]
        scores = []
        for a in range(heads):
            q = _scaled(q_ref[0, rows, a * width:(a + 1) * width], scale)
            scores.append([
                s if mask is None else jnp.where(mask, s, NEG_INF)
                for s, mask in zip([_dot_t(q, k) for k, _ in kv], masks)])
        probs = []
        for a, ss in enumerate(scores):
            m_prev = m_scr[a, rows]
            m_new = functools.reduce(jnp.maximum, [
                m_prev] + [s.max(axis=-1, keepdims=True) for s in ss])
            ps = [jnp.exp2(s - m_new) for s in ss]
            alpha = jnp.exp2(m_prev - m_new)
            m_scr[a, rows] = m_new
            l_scr[a, rows] = l_scr[a, rows] * alpha + functools.reduce(
                jnp.add, [p.sum(axis=-1, keepdims=True) for p in ps])
            probs.append((alpha, [p.astype(v_ref.dtype) for p in ps]))
        for a, (alpha, ps) in enumerate(probs):
            acc_scr[a, rows] = acc_scr[a, rows] * alpha + functools.reduce(
                jnp.add, [_dot(p, v) for p, (_, v) in zip(ps, kv)])

    def _inner(variant):
        for q0, q1, pieces in _work(variant, block, tile, False):
            _band(q0, q1, pieces)

    _by_variant(plan_ref, n, _inner)

    @pl.when(plan_ref[4 * n + step] == 1)
    def _finalize():
        for a in range(heads):
            l = l_scr[a]
            o_ref[0, :, a * width:(a + 1) * width] = (
                acc_scr[a] / l).astype(o_ref.dtype)
            # natural log, queries in lanes: what the backward reads
            lse_ref[0, a] = (m_scr[a] * (1.0 / LOG2E) + jnp.log(l)).T


def _ds_transposed(q, k, v, do, lse, delta, mask, scale: float):
    """(p^T, ds^T) of one piece, both (keys, queries) float32: p^T =
    exp(s^T - lse), ds^T = p^T (dp^T - delta) scale; lse and delta are
    (1, queries) rows and broadcast over the keys as they are."""
    sT = _dot_t(k, _scaled(q, scale))
    if mask is not None:
        sT = jnp.where(mask, sT, NEG_INF)
    pT = jnp.exp2(sT - lse * LOG2E)  # every row has a live key: lse finite
    return pT, pT * (_dot_t(v, do) - delta) * scale


def _bwd_kernel(plan_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, n: int,
                heads: int, parts: int, width: int, scale: float, tile: int,
                shift: int):
    """dq, dk and dv of one entry of the plan BY KEYS from ONE p: five
    products and one exponential a head and piece.  Every sum rests in
    float32 scratch over the WHOLE 2T rows, its output block the whole
    length, written once: dq at the unit's last step; dk and dv, sums
    over the kv head's `parts` units, rounded once at the group's last."""
    unit, step = pl.program_id(1) % parts, pl.program_id(2)
    block = q_ref.shape[1]
    row0, key0 = plan_ref[step] * block, plan_ref[n + step] * block

    def each_block(when, body):  # a loop, not 2T / block copies of the code
        def _one(r, carry):
            body(pl.ds(pl.multiple_of(r * block, block), block))
            return carry

        @pl.when(when)
        def _():
            jax.lax.fori_loop(0, dq_scr.shape[0] // block, _one, 0)

    def _open_unit(rows):
        dq_scr[rows] = jnp.zeros((block, heads * width), jnp.float32)

    def _open_group(rows):
        dk_scr[rows] = jnp.zeros((block, width), jnp.float32)
        dv_scr[rows] = jnp.zeros((block, width), jnp.float32)

    each_block(step == 0, _open_unit)
    each_block((step == 0) & (unit == 0), _open_group)

    def _inner(variant):
        for k0, k1, pieces in _work(variant, block, tile, True):
            k, v = k_ref[0, k0:k1], v_ref[0, k0:k1]
            keys = pl.ds(pl.multiple_of(key0 + k0, tile), k1 - k0)
            for q0, q1, bounds in pieces:
                mask = None if bounds is None else _keep(
                    q1 - q0, k1 - k0, bounds, shift, True)
                rows = pl.ds(pl.multiple_of(row0 + q0, tile), q1 - q0)
                for a in range(heads):
                    lanes = slice(a * width, (a + 1) * width)
                    q, do = q_ref[0, q0:q1, lanes], do_ref[0, q0:q1, lanes]
                    pT, dsT = _ds_transposed(
                        q, k, v, do, lse_ref[0, a, :, q0:q1],
                        delta_ref[0, a, :, q0:q1], mask, scale)
                    dsT = dsT.astype(q.dtype)
                    dv_scr[keys] += _dot(pT.astype(do.dtype), do)
                    dk_scr[keys] += _dot(dsT, q)
                    dq_scr[rows, lanes] += _dot_c0(dsT, k)

    _by_variant(plan_ref, n, _inner)

    def _close_unit(rows):
        dq_ref[0, rows] = dq_scr[rows].astype(dq_ref.dtype)

    def _close_group(rows):
        dk_ref[0, rows] = dk_scr[rows].astype(dk_ref.dtype)
        dv_ref[0, rows] = dv_scr[rows].astype(dv_ref.dtype)

    each_block(step == n - 1, _close_unit)
    each_block((step == n - 1) & (unit == parts - 1), _close_group)


def _geometry(q, k, n_head: int, n_kv: int, block_length: int, block, tile,
              heads, which: str):
    b, s, lanes = q.shape
    d, rep = lanes // n_head, n_head // n_kv
    fit = _fit(s // 2, block_length, block, tile, which)
    if fit is None or k.shape[-1] != n_kv * d:
        raise ValueError(f"no block of whole {block_length}-token blocks "
                         f"divides a copy of {s // 2}, or k is no "
                         f"{n_kv} heads of {d}")
    held = _bwd_vmem if which == "backward" else lambda *_: 0
    heads = heads or max(g for g in range(1, _STEPS[which][1] + 1)
                         if rep % g == 0 and g * d <= _GROUP_LANES and held(
                             g, s, d, q.dtype.itemsize, fit[0]) <= _VMEM_LIMIT)
    return b, s, d, rep, fit[0], fit[1], heads


def _specs(block: int, d: int, heads: int, rep: int, n: int):
    """(q-like, k-like, row) BlockSpecs of a grid (batch, unit, step):
    the unit is a part of a kv head's group (`heads` query heads), rows
    and keys found in the plan."""
    parts = rep // heads

    def rows(b, u, t, plan):
        return (b, plan[t], u)

    def keys(b, u, t, plan):
        return (b, plan[n + t], u // parts)

    def row(b, u, t, plan):
        return (b, u, 0, plan[t])

    return (pl.BlockSpec((1, block, heads * d), rows),
            pl.BlockSpec((1, block, d), keys),
            pl.BlockSpec((1, heads, 1, block), row))


def _forward(q, k, v, n_head: int, n_kv: int, block_length: int,
             scale: float, block=None, tile=None, heads=None,
             interpret: bool = False):
    """q (b, 2T, H*d), k and v (b, 2T, KV*d) -> (o as q, lse (b, H, 1, 2T)
    float32)."""
    b, s, d, rep, block, tile, heads = _geometry(
        q, k, n_head, n_kv, block_length, block, tile, heads, "forward")
    table, n = bd_plan(s // 2, block)
    rows, keys, row = _specs(block, d, heads, rep, n)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, n=n, heads=heads, width=d, scale=scale, tile=tile,
            shift=block_length.bit_length() - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n_head // heads, n),
            in_specs=[rows, keys, keys], out_specs=(rows, row),
            scratch_shapes=[pltpu.VMEM((heads, block, 1), jnp.float32),
                            pltpu.VMEM((heads, block, 1), jnp.float32),
                            pltpu.VMEM((heads, block, d), jnp.float32)]),
        out_shape=(_out_struct(q.shape, q.dtype, q),
                   _out_struct((b, n_head, 1, s), jnp.float32, q)),
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=_VMEM_LIMIT),
        interpret=interpret,
        name="dwt_fa_bd_fwd",
    )(jnp.asarray(table), q, k, v)


def _backward(q, k, v, o, lse, do, n_head: int, n_kv: int,
              block_length: int, scale: float, block=None, tile=None,
              heads=None, interpret: bool = False):
    """(dq, dk, dv) in the operands' own layouts: ONE sweep over the plan
    by keys, `heads` query heads a grid step, a kv head's units in turn."""
    b, s, d, rep, block, tile, heads = _geometry(
        q, k, n_head, n_kv, block_length, block, tile, heads, "backward")
    parts = rep // heads
    # delta = rowsum(dO . O) a head, queries in lanes as lse is
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        b, s, n_head, d).sum(-1).transpose(0, 2, 1)[:, :, None]
    table, n = bd_plan(s // 2, block, True)
    rows, keys, row = _specs(block, d, heads, rep, n)
    # the sums' blocks: the unit's and the kv head's WHOLE length
    dq_spec = pl.BlockSpec((1, s, heads * d), lambda i, u, t, p: (i, 0, u))
    dkv_spec = pl.BlockSpec((1, s, d), lambda i, u, t, p: (i, 0, u // parts))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, heads=heads, parts=parts, width=d,
                          scale=scale, tile=tile,
                          shift=block_length.bit_length() - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n_head // heads, n),
            in_specs=[rows, keys, keys, rows, row, row],
            out_specs=(dq_spec, dkv_spec, dkv_spec),
            scratch_shapes=[pltpu.VMEM((s, lanes), jnp.float32)
                            for lanes in (heads * d, d, d)]),
        out_shape=tuple(_out_struct(x.shape, x.dtype, x) for x in (q, k, v)),
        compiler_params=_compiler_params("parallel", "arbitrary", "arbitrary",
                                         vmem_limit=_VMEM_LIMIT),
        interpret=interpret,
        name="dwt_fa_bd_bwd",
    )(jnp.asarray(table), q, k, v, do, lse, delta)


# static plan: behind `jax.jit` a kernel body is traced and lowered to
# Mosaic once a shape, not once a layer
_STATIC = ("n_head", "n_kv", "block_length", "scale", "block", "tile",
           "heads", "interpret")
_forward_jit = jax.jit(_forward, static_argnames=_STATIC)
_backward_jit = jax.jit(_backward, static_argnames=_STATIC)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _kernels(q, k, v, n_head, n_kv, block_length, scale, plan=()):
    """The kernel route; `plan` = (block, tile, heads a step, interpret),
    () the route's own: tests and sweeps hand another."""
    return _kernels_fwd(q, k, v, n_head, n_kv, block_length, scale, plan)[0]


def _plan_kw(plan) -> dict:
    return dict(zip(("block", "tile", "heads", "interpret"), plan))


def _kernels_fwd(q, k, v, n_head, n_kv, block_length, scale, plan):
    o, lse = _forward_jit(q, k, v, n_head=n_head, n_kv=n_kv,
                          block_length=block_length, scale=scale,
                          **_plan_kw(plan))
    return o, (q, k, v, o, lse)


def _kernels_bwd(n_head, n_kv, block_length, scale, plan, res, g):
    return _backward_jit(*res, g, n_head=n_head, n_kv=n_kv,
                         block_length=block_length, scale=scale,
                         **_plan_kw(plan))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _plain(q, k, v, n_head: int, n_kv: int, block_length: int, scale: float):
    """The dense `jax.numpy` lines under `kept_mask_bd`: float32 scores
    and softmax, grouped heads by a reshape, differentiated by JAX."""
    b, s, _ = q.shape
    d, rep = q.shape[-1] // n_head, n_head // n_kv
    qg = q.reshape(b, s, n_kv, rep, d)
    kg, vg = (x.reshape(b, s, n_kv, d) for x in (k, v))
    att = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kg).astype(jnp.float32) * scale
    att = jnp.where(kept_mask_bd(s // 2, block_length), att, -jnp.inf)
    prob = jax.nn.softmax(att, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", prob, vg).reshape(q.shape)


def block_diffusion_attention(q, k, v, n_head: int, n_kv: int,
                              block_length: int,
                              sm_scale: Optional[float] = None, mesh=None):
    """Attention of `[clean ; noised]` under the block mask, on the
    projections' own layout: q (b, 2T, H*d), k and v (b, 2T, KV*d) ->
    (b, 2T, H*d).  `mesh` is the model config's; the route is
    `bd_route`'s."""
    b, s, lanes = q.shape
    if s % (2 * block_length):
        raise ValueError(f"{s} positions are no two copies of whole "
                         f"blocks of {block_length}")
    d = lanes // n_head
    scale = float(sm_scale) if sm_scale else 1.0 / math.sqrt(d)
    if bd_route(s // 2, block_length, n_head, n_kv, d, mesh,
                q.dtype.itemsize) == "kernel":
        return _kernels(q, k, v, n_head, n_kv, block_length, scale)
    return _plain(q, k, v, n_head, n_kv, block_length, scale)
