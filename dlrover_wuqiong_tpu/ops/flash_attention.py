"""Flash attention for TPU: Pallas kernels (fwd + bwd) with online softmax,
causal or not, under a sliding window or not.

Parity: reference flash-attn integrations — atorch
`modules/transformer/layers.py:1167` (`flash_attn_with_mask_bias`,
`FlashAttnModule` :1278) and tfplus FMHA ops
(`tfplus/tfplus/flash_attn/ops/flash_attention_ops.cc:8,39`).  Those wrap the
CUDA flash-attn library; here the kernels are written natively in Pallas
against the MXU/VMEM model (guide: /opt/skills/guides/pallas_guide.md).

Design (FA2 scheme, canonical Mosaic structure):
- the KV loop lives in the *grid* (innermost dim), not a fori_loop: Mosaic
  double-buffers the KV block HBM→VMEM copies against compute, and the
  q/o blocks stay resident in VMEM across the KV sweep.  Online-softmax
  state (m, l, acc) lives in VMEM scratch that persists across grid steps;
  `@pl.when` initializes it on the first KV step and finalizes o/lse on the
  last.
- causal masking is bottom-right aligned (a query at position i attends to
  keys k_idx <= i + (sk - sq)).  Nothing above the diagonal is computed,
  at two grains: a grid block that lies above it never runs (`@pl.when`),
  and INSIDE a block it crosses only the score tiles at or below it are
  (`_causal_bands`: a static, trace-time loop over `_CAUSAL_TILE`-aligned
  slices of the resident block, only the crossed tiles masked).  At
  T = 1024 — one block each way, where the grid skips nothing — that
  is 3 of 4 tiles; `causal_tile_count` gives the count for any call.
  The tile set is a pure function of (block_q, block_k, offset, tile): a
  single block takes any offset sk - sq, several blocks take it when they
  are square and sq - sk is a multiple of them (the crossed blocks then
  all sit at offset 0); block_q != block_k or a ragged offset keeps the
  whole-block mask by grid position.  A non-causal call is the whole-block
  program, untouched.
- a WINDOW is a second diagonal (`window=` on every entry; a query at i
  sees key j iff 0 <= i + (sk - sq) - j < window): what is kept is a
  band, not a triangle.  Where the grid places the diagonals (square
  blocks, sq - sk a multiple of them) the swept grid axis is NARROWED to
  the blocks a query block (for dk/dv: a key block) can see
  (`_window_plan`, `_sweep_place`, `_swept`): a block wholly below the
  window is no grid step, is not fetched and costs nothing; the block on
  the causal diagonal and the one or two the window's diagonal crosses
  run the tiles it leaves, masked only where crossed (`_window_tiles`,
  the windowed twin of `_causal_bands`); the blocks between run whole.
  At T = 16,384, window 4,096, that is 4.5 blocks' work in 5 grid steps
  a query block where a causal call runs 8.5 on average.  Blocks the
  grid cannot place keep the full grid and a whole-block mask under both
  diagonals.  A windowed call's kernels are named `dwt_fa_win_*` (the
  prefix `dwt_fa_` keeps them in every reader of the kernels' time, the
  rest lets one take them alone); `causal_tile_count(window=...)` stays
  the counter of what they compute.  A call without a window — or with
  one no shorter than the keys — traces the program it did before any
  of this existed (pinned: tests/test_flash_attention_tiles.py).  The
  jnp paths off the TPU take the same window (`kept_mask`).
- backward: ONE kernel wherever it can be — dq, dk and dv from a single
  recompute of p = exp(s - lse) per tile, IN TRANSPOSED SPACE (queries
  in lanes) so the (sq, sk) attention matrix never hits HBM and the
  per-row lse/delta broadcast without relayouts: five products and one
  elementwise pass a kept tile.  One block each way is a kernel of its
  own on a grid of heads (`_fa_bwd_fused_kernel`).  Several blocks run
  the dk/dv sweep (grid: kv outer, q inner; dk and dv summed in float32
  scratch over a key block's queries) with dq summed in a float32
  scratch that spans the unit's WHOLE query length and written to an
  output block of the same span: that block's index is constant over
  both inner grid axes, so it stays in VMEM from the group's first grid
  step to its last (16,384 x 128 lanes: 8 MiB of scratch and 4 of
  bfloat16 block, double-buffered; the packed heads a grid step are
  two where two fit, since PR 52 never more).  Where a unit's dq does
  not fit the VMEM the call states (131,072 x 128 lanes) it is two
  kernels — dq (grid: q outer, kv inner) and dk/dv — each with a
  recompute of its own, seven products and two passes.
  `backward_route` says which, from shapes alone; the
  kernels' names say it in a trace (`dwt_fa_bwd_fused`, or
  `dwt_fa_bwd_dq` + `dwt_fa_bwd_dkv`).
- TWO LAYOUTS, one set of kernels.  TRANSPOSED: `flash_attention` /
  `flash_attention_with_lse` take (b, h, s, d) and index the flat
  (b*h, s, d) arrays a group of `_fit_pack` heads a grid step; head_dim
  runs natively when lane-aligned (d % 8 == 0), else zero-padded to the
  128 boundary.  At d = 64 those arrays are stored padded to 128 lanes:
  every read, write and transpose around them moves twice its bytes.
  DIRECT: `flash_attention_projected` takes the projections' own
  (b, s, h*d) output — GPT-2's whole `c_attn` (b, s, 3*h*d) array,
  handed in three times — and a second set of BlockSpecs (`_Slabs`,
  `_block_specs`) walks it where it lies: a grid step is one batch row,
  a run of positions and ONE 128-lane SLAB, q's, k's and v's slabs found
  by a column offset.  o, dq, dk and dv are written the same way, so the
  output projection and the backward of the input projection take them
  as they are and the compiled step holds no split, cut to heads or
  transpose around the kernels.  GROUPED HEADS are indexed too, not
  repeated: where a head is a slab k and v keep their projections' own
  (b, s, n_kv*d) and query slab s reads slab s // rep of them (`kv_route`,
  `_Slabs.kv_rep`); dk and dv leave the kernels a query head and the
  entry sums a group's.  A grouped CAUSAL forward over several key
  blocks (no window, sq == sk) takes another step: the GROUP
  (`forward_route`, `_fa_grp_fwd_kernel`, `dwt_fa_grp_fwd`) — the group's
  query slabs lie side by side, so its q and o block is ONE lane range
  of (block q rows) x (`_group_heads` heads x d lanes) against the kv
  head's one (block keys, d) slab, fetched once for the step's heads
  and not at all above the diagonal (the key block's index is clamped
  there); inside the step the group's first products, then its
  softmaxes, then its products with v, in three runs.  o and lse leave
  as the slab step leaves them, the backward reads them unchanged.
  `attention_route(h, d)` says which
  layout a shape takes: direct when the heads fall on slab boundaries —
  a head is a slab (d % 128 == 0), or two heads of 64 share one and
  their number is even — transposed otherwise (GPT-2 XL's 25 heads,
  ring and Ulysses, the shard_map of a mesh).
- two heads in a slab are told apart by a lane MASK, never by a slice
  (`_own`, `_join`): a 64-lane slice, roll or concatenate at a non-128
  offset is a relayout a head (a kernel built that way once ran slower
  than the transposes and the flat kernel together: PERF.md section 6,
  PR 29), while every matmul of the d = 64 kernels is 64 wide — half an
  MXU — so a 128-wide operand
  whose other head's lanes are zero costs the same passes and adds
  exactly 0 to the sum.  q (and dO, where it is contracted over d) is
  masked before the contraction; a product against the slab's unmasked
  k or v is right in the head's own lanes, which one select a slab
  keeps.  The body is one: `slab_heads` 1 traces none of it.
- lse lives as (bh, 1, sq) f32 everywhere — residuals, kernel outputs and
  inputs, both layouts — with a cheap in-kernel (block_q, 1) <->
  (1, block_q) relayout instead of padded HBM traffic; the causal mask is
  one broadcast compare, not 2D iotas.  delta is computed outside into
  the same form, except where two heads share a slab: there the backward
  kernels take o and sum each head's lanes themselves (`_delta_rows`).
- on non-TPU backends a jnp reference path keeps tests runnable; the kernels
  themselves are additionally tested in interpret mode.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import _compiler_params, _dot, _dot_c0, _dot_t, _out_struct

NEG_INF = -1e30  # avoids inf-inf NaNs while dominating any real score
LOG2E = 1.4426950408889634  # exp(x) == exp2(x * LOG2E); folding LOG2E
# into the q pre-scale turns every exp over the (block_q, block_k) score
# matrix into a bare exp2 — one VPU multiply pass saved per exp site
# (the hardware exponent unit is base-2; jnp.exp emits the mul per call)


# what every kernel here asks of the v5e's 128 MiB of VMEM, and what
# `backward_route` reckons a fused backward's resident set against
_VMEM_LIMIT = 100 * 1024 * 1024
_DIRECT_SITES = frozenset({"device"})  # `attend` places the (b, h, s, d) entry


def _rel_mask(nq, nk, delta, transposed=False):
    """Causal mask of an (nq, nk) score piece whose local entry (r, c) is
    kept iff r + delta >= c — (nk, nq), queries in lanes, if transposed.

    (nq, 1) >= (1, nk) broadcast: one VPU pass over the piece, vs two
    materialized 2D iotas + compare (3 extra full passes).  `delta` is a
    Python int for a tile inside a block, a traced scalar for a whole
    block placed by its grid position.
    """
    if transposed:
        return delta + jax.lax.broadcasted_iota(
            jnp.int32, (1, nq), 1) >= jax.lax.broadcasted_iota(
                jnp.int32, (nk, 1), 0)
    return delta + jax.lax.broadcasted_iota(
        jnp.int32, (nq, 1), 0) >= jax.lax.broadcasted_iota(
            jnp.int32, (1, nk), 1)


# ------------------------------------------- causal tiles inside one block


# side of the score tiles inside a block the diagonal crosses.  Swept on
# the chip over {128, 256, 512} at (d = 64, T = 1024, pack 8 and pack 4)
# and (d = 128, T = 4096): with n tiles a side a block computes (n+1)/2n
# of its square, but a smaller tile feeds the MXU shorter operands, and
# every piece is traced and lowered again for every layer of a model.
# 512 and 256 run within 1.5% of each other at all three shapes (128 is
# slower); 512 has 3 pieces a head where 256 has 7, which keeps a warm
# `setup_s` where it was.  One tile serves all three, so it is a
# constant, not yet a function of the head size (PERF.md section 6,
# PR 27, has the times).
_CAUSAL_TILE = 512


def _causal_tile(block_q: int, block_k: int,
                 tile: Optional[int] = None) -> Optional[int]:
    """The tile these block sizes are cut into — `tile` if given (tests,
    sweeps), else `_CAUSAL_TILE`; None = the block stays whole (a side
    the tile does not divide, or nothing above one tile)."""
    tile = tile or _CAUSAL_TILE
    if block_q % tile or block_k % tile or max(block_q, block_k) <= tile:
        return None
    return tile


def _diag_offset(num_q: int, num_kv: int, block_q: int, block_k: int,
                 kv_offset: int) -> Optional[int]:
    """`off` such that EVERY block the diagonal crosses keeps its local
    entry (r, c) iff r + off >= c, when the grid alone decides that:
    one block each way (off = sk - sq), or square blocks and sq - sk a
    multiple of them (the crossed blocks are the qi + kv_offset/block ==
    ki ones, off = 0).  None otherwise (block_q != block_k, a ragged
    kv_offset): the offset then depends on the grid position, and the
    kernels keep the whole-block mask."""
    if num_q == 1 and num_kv == 1:
        return kv_offset
    if block_q == block_k and kv_offset % block_q == 0:
        return 0
    return None


def _causal_bands(block_q: int, block_k: int, off: int,
                  tile: Optional[int]):
    """The score tiles of one (block_q, block_k) block whose entry (r, c)
    is kept iff r + off >= c, one band per query tile:
    [(q0, q1, k_full, k_end)] — every row of [q0, q1) sees the keys
    [0, k_full), some see [k_full, k_end) (the only part that is masked),
    none sees [k_end, block_k) (never computed).

    THE source of what the causal kernels compute: the forward and dq
    loops are built from it, dk/dv and the fused backward from its
    transpose (`_causal_bands_t`), `causal_tile_count` sums it.  Without
    a tile (`_causal_tile` gave none) the block is one band, all masked."""
    if not tile:
        return [(0, block_q, 0, block_k)]
    nk = block_k // tile
    return [(q0, q0 + tile,
             tile * min(nk, max(0, (q0 + off + 1) // tile)),
             tile * min(nk, max(0, -(-(q0 + tile + off) // tile))))
            for q0 in range(0, block_q, tile)]


def _causal_bands_t(block_q: int, block_k: int, off: int,
                    tile: Optional[int]):
    """The same tiles by key tile: [(k0, k1, q_start, q_full)] — no query
    before q_start sees a key of [k0, k1), some of [q_start, q_full) do
    (masked), every one of [q_full, block_q) sees them all."""
    if not tile:
        return [(0, block_k, 0, block_q)]
    nq = block_q // tile
    return [(k0, k0 + tile,
             tile * min(nq, max(0, (k0 - off) // tile)),
             tile * min(nq, max(0, -(-(k0 + tile - 1 - off) // tile))))
            for k0 in range(0, block_k, tile)]


def causal_tile_count(sq: int, sk: int, block_q: int = 1024,
                      block_k: int = 1024, tile: Optional[int] = None,
                      window: Optional[int] = None):
    """(score tiles a causal call computes, tiles in its sq x sk square),
    in tiles of `_causal_tile`'s side (whole blocks where it gives none).

    Static, like the decision itself: grid blocks above the diagonal
    never run, blocks below it run whole, and a block the diagonal
    crosses runs the tiles of `_causal_bands`.  With a `window` (a query
    sees the `window` keys that end at its own) the blocks wholly below
    the window never run either, and a block either diagonal crosses
    runs the tiles of `_window_tiles`."""
    block_q = _fit_block(sq, block_q) or sq
    block_k = _fit_block(sk, block_k) or sk
    tile = _causal_tile(block_q, block_k, tile)
    num_q, num_kv, kv_offset = sq // block_q, sk // block_k, sk - sq
    window = _effective_window(window, True, sk)
    off = _diag_offset(num_q, num_kv, block_q, block_k, kv_offset)
    if not tile or off is None:
        tq, tk, off = block_q, block_k, None  # count whole blocks
    else:
        tq = tk = tile
    per_block = (block_q // tq) * (block_k // tk)
    done = 0
    for qi in range(num_q):
        for ki in range(num_kv):
            lo = qi * block_q + kv_offset - ki * block_k  # r - c at (0, 0)
            if lo + block_q <= 0:        # run is False: above the diagonal
                continue
            if window is not None:
                if lo - (block_k - 1) >= window:    # below the window
                    continue
                done += per_block if off is None else len(_window_tiles(
                    block_q, block_k, lo, window, tile)[0])
            elif lo >= block_k - 1 or off is None:  # below it, or kept whole
                done += per_block
            else:
                done += sum((q1 - q0) // tq * (k_end // tk) for
                            q0, q1, _, k_end in
                            _causal_bands(block_q, block_k, off, tile))
    return done, num_q * num_kv * per_block


def _effective_window(window: Optional[int], causal: bool,
                      sk: int) -> Optional[int]:
    """The window the kernels are handed: None where the call has none
    or it leaves no key out that the causal mask keeps (a query's
    farthest key lies sk - 1 behind it), so that such a call is the
    causal program, kernel names and all."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"a window ({window}) is at least one key wide "
                         f"and belongs to a causal call")
    return None if window >= sk else window


def _window_tiles(block_q: int, block_k: int, rel: int, window: int,
                  tile: Optional[int]):
    """({(q0, k0): (crossed by the causal diagonal, crossed by the
    window's)}, tile rows, tile columns): the score tiles of one block
    that hold a kept entry, when local (r, c) is kept iff
    0 <= r - c + rel < window (`rel` is the block's query position less
    its key position, bottom-right aligned).  A tile neither diagonal
    crosses is computed unmasked, a tile of no kept entry is not in the
    dict and never computed.  Without a tile the block is one.

    THE source of what the windowed kernels compute, as `_causal_bands`
    is of the causal ones: `_window_work` builds their loops from it and
    `causal_tile_count` sums it."""
    tq, tk = (tile, tile) if tile else (block_q, block_k)
    tiles = {}
    for q0 in range(0, block_q, tq):
        for k0 in range(0, block_k, tk):
            lo = q0 - (k0 + tk - 1) + rel       # least r - c + rel
            hi = q0 + tq - 1 - k0 + rel         # largest
            if hi >= 0 and lo < window:
                tiles[q0, k0] = (lo < 0, hi >= window)
    return tiles, tq, tk


def _window_work(by_keys: bool, transposed: bool, block_q: int,
                 block_k: int, rel: int, window: int,
                 tile: Optional[int]):
    """`_block_work`'s bands for a block a window's diagonal (or both
    diagonals) crosses, from `_window_tiles`: neighbouring tiles of one
    kind are one piece, masked by the diagonals that cross them and by
    no other."""
    tiles, tq, tk = _window_tiles(block_q, block_k, rel, window, tile)
    masks = {}

    def mask(q_lo, q_hi, k_lo, k_hi, upper, lower):
        if not (upper or lower):
            return None
        key = (q_hi - q_lo, k_hi - k_lo, rel + q_lo - k_lo, upper, lower)
        if key not in masks:
            nq, nk, delta = key[:3]
            kept = _rel_mask(nq, nk, delta, transposed) if upper else None
            if lower:  # r - c + delta < window
                inside = jnp.logical_not(
                    _rel_mask(nq, nk, delta - window, transposed))
                kept = inside if kept is None else kept & inside
            masks[key] = kept
        return masks[key]

    n_band, t_band, n_piece, t_piece = (
        (block_k, tk, block_q, tq) if by_keys else (block_q, tq, block_k, tk))
    work = []
    for b0 in range(0, n_band, t_band):
        runs = []  # [lo, hi, kind] of neighbouring tiles of one kind
        for p0 in range(0, n_piece, t_piece):
            kind = tiles.get((p0, b0) if by_keys else (b0, p0))
            if kind is None:
                continue
            if runs and runs[-1][1] == p0 and runs[-1][2] == kind:
                runs[-1][1] = p0 + t_piece
            else:
                runs.append([p0, p0 + t_piece, kind])
        work.append((b0, b0 + t_band, [
            (lo, hi, mask(lo, hi, b0, b0 + t_band, *kind) if by_keys
             else mask(b0, b0 + t_band, lo, hi, *kind))
            for lo, hi, kind in runs]))
    return work


def _block_work(mask_block: bool, by_keys: bool, transposed: bool,
                block_q: int, block_k: int, diag_off: Optional[int],
                tile: Optional[int], qi, ki, kv_offset: int,
                window: Optional[int] = None, rel: Optional[int] = None):
    """What a kernel computes of its resident block, as
    [(lo, hi, [(piece_lo, piece_hi, mask | None), ...]), ...]: bands of
    queries whose pieces are key ranges (forward, dq), or with `by_keys`
    bands of keys whose pieces are query ranges (dk/dv, fused).

    A block off the diagonal (`mask_block` False) is one band of one
    unmasked piece: the program it always was.  A block on it is the
    tiles of `_causal_bands`, only the pieces the diagonal crosses
    masked — or, where no static offset exists (`diag_off` None), one
    whole-block piece masked by its grid position (qi, ki).  With a
    `window` a masked block is one either diagonal of the window
    crosses: `_window_work`'s tiles at its static place `rel`, or
    without one (`rel` None) the whole block under both diagonals by its
    grid position."""
    n_band, n_piece = (block_k, block_q) if by_keys else (block_q, block_k)
    if not mask_block:
        return [(0, n_band, [(0, n_piece, None)])]
    if window is not None and rel is not None:
        return _window_work(by_keys, transposed, block_q, block_k, rel,
                            window, tile)
    if window is not None:
        pos = qi * block_q + kv_offset - ki * block_k
        return [(0, n_band, [(0, n_piece, _rel_mask(
            block_q, block_k, pos, transposed) & jnp.logical_not(_rel_mask(
                block_q, block_k, pos - window, transposed)))])]
    if diag_off is None:
        return [(0, n_band, [(0, n_piece, _rel_mask(
            block_q, block_k, qi * block_q + kv_offset - ki * block_k,
            transposed))])]
    masks = {}  # off == 0: every crossed tile has the same mask

    def mask(q_lo, q_hi, k_lo, k_hi):
        key = (q_hi - q_lo, k_hi - k_lo, diag_off + q_lo - k_lo)
        if key not in masks:
            masks[key] = _rel_mask(*key, transposed)
        return masks[key]

    work = []
    if by_keys:
        for k0, k1, q_start, q_full in _causal_bands_t(
                block_q, block_k, diag_off, tile):
            pieces = []
            if q_full > q_start:
                pieces.append((q_start, q_full,
                               mask(q_start, q_full, k0, k1)))
            if block_q > q_full:
                pieces.append((q_full, block_q, None))
            work.append((k0, k1, pieces))
    else:
        for q0, q1, k_full, k_end in _causal_bands(
                block_q, block_k, diag_off, tile):
            pieces = [(0, k_full, None)] if k_full else []
            if k_end > k_full:
                pieces.append((k_full, k_end,
                               mask(q0, q1, k_full, k_end)))
            work.append((q0, q1, pieces))
    return work


def _rows(lo: int, hi: int, n: int):
    """Index of rows [lo, hi) of an n-row ref dim: nothing at all when
    that is the whole dim (the program a whole-block kernel always had)."""
    return () if (lo, hi) == (0, n) else (slice(lo, hi),)


def _lanes(lo: int, hi: int, n: int):
    """The same for the (1, n) lse / delta rows: a lane slice."""
    return () if (lo, hi) == (0, n) else (slice(None), slice(lo, hi))


# ------------------------------------------- a window: a second diagonal
#
# A windowed call (query i sees key j iff 0 <= i + (sk - sq) - j <
# window) keeps a band of blocks, not a triangle.  Where the grid alone
# places the diagonals (`_diag_offset`: square blocks, sq - sk a
# multiple of them) a block's class is its distance d = qi + koff - ki
# from the causal diagonal, so the grid's swept axis is NARROWED to the
# d_max + 1 blocks a query block (a key block) can see and walks them by
# d: a block wholly below the window is no grid step at all, is not
# fetched and costs nothing; d = 0 and the one or two d the window's
# diagonal crosses run `_window_work`'s tiles at their static place
# d * block, every d between runs whole and unmasked.


def _window_plan(window: Optional[int], num_q: int, num_kv: int,
                 block_q: int, block_k: int, kv_offset: int) -> dict:
    """The kernels' static `window` and where its diagonals lie (the
    other keywords of `_sweep_place`); nothing for a call without one,
    whose kernels are the causal program."""
    if window is None:
        return {}
    if num_q == 1 and num_kv == 1:  # one block, wherever its diagonals
        return {"window": window, "crossed": ((0, kv_offset),)}
    if _diag_offset(num_q, num_kv, block_q, block_k, kv_offset) is None:
        return {"window": window}  # whole blocks, masked by grid position
    d_max = (window + block_q - 2) // block_q
    inside = [d for d in range(1, d_max + 1) if (d + 1) * block_q <= window]
    return {"window": window, "steps": d_max + 1,
            "koff": kv_offset // block_q,
            "crossed": tuple((d, d * block_q) for d in range(d_max + 1)
                             if d not in inside),
            "whole": (inside[0], inside[-1]) if inside else None}


def _sweep_place(outer, step, by_keys: bool, block_q: int, block_k: int,
                 kv_offset: int, window: int, steps: Optional[int] = None,
                 koff: int = 0, limit: int = 0, crossed=None, whole=None):
    """(the swept axis' block, whether it runs, `_window_blocks`' place)
    of a windowed kernel's grid step: `outer` is the query block and the
    sweep walks its key blocks from the farthest to its own, or with
    `by_keys` the key block and its query blocks from its own to the
    farthest.  Without `steps` the grid is not narrowed and `step` is
    the block itself."""
    d, pos = None, step
    if steps is not None:
        d = step if by_keys else steps - 1 - step
        pos = outer - koff + d if by_keys else outer + koff - d
    qi, ki = (pos, outer) if by_keys else (outer, pos)
    rel = qi * block_q + kv_offset - ki * block_k
    run = (rel + block_q > 0) & (rel - (block_k - 1) < window)
    if steps is not None:
        run = run & (pos >= 0) & (pos < limit)
    return pos, run, (d, rel, block_q, block_k, window, crossed, whole)


def _window_blocks(inner, run, d, rel, block_q: int, block_k: int,
                   window: int, crossed, whole) -> None:
    """`inner(masked, static place)` for the resident block of a
    windowed call, by its class (`_window_plan`)."""
    if crossed is None:  # no static place: by grid position
        inside = (rel - (block_k - 1) >= 0) & (rel + block_q - 1 < window)
        pl.when(run & inside)(functools.partial(inner, False))
        pl.when(run & jnp.logical_not(inside))(functools.partial(inner, True))
        return
    for at, place in crossed:
        pl.when(run if d is None else run & (d == at))(
            functools.partial(inner, True, place))
    if whole:
        pl.when(run & (d >= whole[0]) & (d <= whole[1]))(
            functools.partial(inner, False))


def _swept(steps: int, koff: int, limit: int, by_keys: bool):
    """The block of the swept array a narrowed grid step reads, as a
    function of the grid indices after the first: `_sweep_place`'s,
    held inside the array (a step outside it does not run, and a block
    index that repeats is not fetched again)."""
    def at(ij):
        pos = ij[0] - koff + ij[1] if by_keys \
            else ij[0] + koff - (steps - 1 - ij[1])
        return jnp.clip(pos, 0, limit - 1)
    return at


# heads per iteration of the loop over a tiled block's packed heads: pairs
# ran as fast as or faster than 1, 4 and all of them (unrolled) at all
# three swept shapes (PERF.md section 6, PR 27).  Also the most units a
# grid step `backward_route` gives a several-block fused sweep, whose
# whole blocks `_each_head` unrolls: held four or eight times beside the
# looped tiles, the body ran at 0.57-0.75 of its speed at two (PR 52)
_HEAD_GROUP = 2


def _each_head(pack: int, work, body):
    """body(hh) for each packed head.  A block cut into tiles runs them in
    a `fori_loop`, `_HEAD_GROUP` heads an iteration: the tiles of a head
    are traced, lowered and held as instructions once or twice, not
    `pack` times (unrolled, they added a quarter to a warm `setup_s`, and
    a several-block kernel ran at half the speed).  A block computed
    whole keeps the Python loop it always had: the forward and the
    one-block backward run it at any pack; the several-block fused
    sweep, which holds a whole block's body BESIDE the diagonal block's
    tiles, is handed at most `_HEAD_GROUP` units (`backward_route`), so
    nothing of it is unrolled further than the loop's own body is."""
    if len(work) == 1 or pack <= _HEAD_GROUP:
        for hh in range(pack):
            body(hh)
        return

    def _group(i, carry):
        for g in range(_HEAD_GROUP):
            body(i * _HEAD_GROUP + g)
        return carry

    jax.lax.fori_loop(0, pack // _HEAD_GROUP, _group, 0)


class _BandState(dict):
    """Stands in for the forward's VMEM scratch where a band's softmax
    state never outlives the band: values, keyed by the rows alone where
    the head in front is a loop index, by head and rows where it is
    static (the two heads of a slab are both live until the band ends)."""

    @staticmethod
    def _key(idx):
        return idx if isinstance(idx[0], int) else idx[1:]

    def __setitem__(self, idx, value):
        super().__setitem__(self._key(idx), value)

    def __getitem__(self, idx):
        return super().__getitem__(self._key(idx))


# ------------------------------------------------- two heads in one slab
#
# A unit of the kernels' work is what `ref[u]` holds: one head in the
# transposed layout (`slab_heads` 1: nothing below traces anything), or
# one 128-lane SLAB of the projections' own layout, which at d = 64 holds
# TWO heads side by side (`slab_heads` 2).  The two are told apart by a
# lane MASK, never by a slice: an operand whose other head's lanes are
# zero contracts over d to the one head's scores at the MXU passes the
# 64-wide operand took, and a product against an unmasked operand is
# right in the head's own lanes, which a select keeps.


def _head(u, a: int, slab_heads: int):
    """Index of head `a` of unit `u` in the per-head arrays (lse, delta,
    softmax state)."""
    return u if slab_heads == 1 else u * slab_heads + a


def _own_lanes(shape, a: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane < shape[-1] // 2) == (a == 0)


def _own(x, a: int, slab_heads: int):
    """`x` with the lanes of the slab's other head zeroed."""
    if slab_heads == 1:
        return x
    return jnp.where(_own_lanes(x.shape, a), x, jnp.zeros_like(x))


def _join(xs, width: int):
    """One (rows, width) value from one (rows, width) or (rows, 1) value
    per head of the slab: each head's own lanes."""
    if len(xs) == 1:
        return xs[0]
    lo, hi = xs
    shape = (lo.shape[0], width)
    return jnp.where(_own_lanes(shape, 0), jnp.broadcast_to(lo, shape),
                     jnp.broadcast_to(hi, shape))


def _delta_rows(aux_ref, do, a: int, rows, lanes, slab_heads: int,
                from_o: bool):
    """delta = rowsum(dO ∘ O) of head `a` as a (1, rows) row: read from
    the (bh, 1, sq) array the wrapper computed (`aux_ref` is delta), or
    with `from_o` computed here from the slab's o block (`aux_ref` is o;
    `do` the slab's dO rows): the head's own lanes of the product, summed
    across lanes, relaid once as the forward relays its lse."""
    if not from_o:
        return aux_ref[lanes]
    prod = do.astype(jnp.float32) * aux_ref[rows].astype(jnp.float32)
    return _own(prod, a, slab_heads).sum(axis=-1, keepdims=True).T


# ------------------------------------------------------------- forward kernel


def _fold(op, xs):
    """The pieces of one band of rows, folded by `op` to the width of the
    narrowest (lane slices at multiples of the tile: free), so that a row
    statistic over them costs ONE cross-lane reduction, as a whole
    block's does; one per piece made the tiled forward slower than the
    whole one (PERF.md section 6, PR 27)."""
    if len(xs) == 1:
        return xs[0]
    # the widest slice every piece is a multiple of (the narrowest piece,
    # wherever the pieces are one tile and a run of tiles)
    w = math.gcd(*[x.shape[1] for x in xs])
    return functools.reduce(op, [x[:, j:j + w] for x in xs
                                 for j in range(0, x.shape[1], w)])


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                   num_kv: int, causal: bool, sm_scale: float,
                   block_q: int, block_k: int, kv_offset: int, pack: int,
                   diag_off: Optional[int] = None,
                   tile: Optional[int] = None, slab_heads: int = 1,
                   window: Optional[int] = None, **sweep):
    """Packed forward: refs carry `pack` units in the leading dim, each a
    head or a slab of `slab_heads` heads (above).  `window`, `sweep`: a
    windowed call's place in its narrowed grid (`_sweep_place`).

    Leading-dim indexing (ref[hh]) is a free address offset (unlike lane
    slicing), so packing amortizes per-grid-step fixed costs and generates
    the causal mask once for all packed heads.

    A block the diagonal crosses is computed by query tile (`_block_work`):
    a tile's scores against the keys every row of it sees, unmasked, and
    against the one key tile the diagonal crosses, masked; the keys beyond
    are never touched.  The loop is Python, unrolled at trace time over
    static slices: sublane slices of q/k/v and of the scratch at multiples
    of the tile cost nothing.

    The softmax state (m, l, acc) lives in VMEM scratch across the KV
    sweep: m and l a head, acc a unit.  A causal call whose keys are ONE
    block is given none: each query tile is a plain softmax over the
    static prefix it sees, its state stays values (dicts keyed like the
    scratch) and its o and lse are written where they are computed.
    """
    qi = pl.program_id(1)
    ki = step = pl.program_id(2)
    single = num_kv == 1  # whole KV sweep in one step: no online state
    stateless = not scratch
    m_scr, l_scr, acc_scr = scratch or (
        _BandState(), _BandState(), _BandState())
    width = o_ref.shape[-1]
    heads = range(slab_heads)

    if window is not None:
        ki, run, place = _sweep_place(qi, step, False, block_q, block_k,
                                      kv_offset, window, **sweep)
    elif causal:
        # block fully masked when its first key exceeds the last query's reach
        run = (qi + 1) * block_q + kv_offset > ki * block_k
    else:
        run = True

    def _empty(hh, rows, n):
        for a in heads:
            head = (_head(hh, a, slab_heads),) + rows
            m_scr[head] = jnp.full((n, 1), NEG_INF, jnp.float32)
            l_scr[head] = jnp.zeros((n, 1), jnp.float32)
        acc_scr[(hh,) + rows] = jnp.zeros((n, width), jnp.float32)

    def _finish(hh, rows, lanes):
        ls = [l_scr[(_head(hh, a, slab_heads),) + rows] for a in heads]
        safe = [jnp.where(l > 0, l, 1.0) for l in ls]
        o_ref[(hh,) + rows] = (acc_scr[(hh,) + rows]
                               / _join(safe, width)).astype(o_ref.dtype)
        for a, l, l_safe in zip(heads, ls, safe):
            head = _head(hh, a, slab_heads)
            # empty key set → logsumexp = -inf (matches the jnp reference
            # path and long_context._merge_partials' isfinite handling).
            # m is in log2 units (LOG2E folded into the q pre-scale) —
            # convert back so the public lse stays natural-log.
            lse = jnp.where(l > 0, m_scr[(head,) + rows] * (1.0 / LOG2E)
                            + jnp.log(l_safe), -jnp.inf)
            # lse lives as (bh, 1, sq) in HBM — a (…, sq, 1) f32 array
            # pads its minor dim 128x in the tiled layout (~150MB of
            # padding traffic per call at the bench shape); with sq in
            # lanes the padding is 8x of a tiny array, and the (rows, 1)
            # -> (1, rows) relayout happens once per query tile in VMEM
            lse_ref[(head,) + lanes] = lse.T

    if not stateless and not single:
        @pl.when(step == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)
    elif stateless and kv_offset < 0:
        # with sq > sk a q block can be FULLY masked (run=False): _inner
        # never runs — it ends on the empty-key values, o=0, lse=-inf
        @pl.when(jnp.logical_not(run))
        def _masked():
            for hh in range(pack):
                _empty(hh, (), block_q)
                _finish(hh, (), ())

    def _inner(mask_block: bool, rel: Optional[int] = None):
        work = _block_work(mask_block, False, False, block_q, block_k,
                           diag_off, tile, qi, ki, kv_offset, window, rel)

        def _unit(hh):
            for q0, q1, pieces in work:
                rows = _rows(q0, q1, block_q)
                if pieces:
                    _band(hh, rows, pieces)
                else:  # sq > sk in one block: no row of the tile sees a key
                    _empty(hh, rows, q1 - q0)
                if stateless:
                    _finish(hh, rows, _lanes(q0, q1, block_q))

        _each_head(pack, work, _unit)

    def _band(hh, rows, pieces):
        # pre-scale q (block_q x d) instead of s (block_q x block_k):
        # one fewer full VPU pass over the score matrix.  LOG2E folds
        # here too: s lives in log2 units, every exp below is a bare
        # exp2, and only the final lse converts back to natural log.
        q = (q_ref[(hh,) + rows].astype(jnp.float32)
             * (sm_scale * LOG2E)).astype(q_ref.dtype)
        kv = [(k_ref[(hh,) + _rows(k0, k1, block_k)],
               v_ref[(hh,) + _rows(k0, k1, block_k)])
              for k0, k1, _ in pieces]
        alphas, pvs = [], []
        for a in heads:
            head = (_head(hh, a, slab_heads),) + rows
            # bf16 MXU multiply, f32 accumulate — never cast operands up
            qa = _own(q, a, slab_heads)
            ss = [_dot_t(qa, k) for k, _ in kv]
            ss = [s if mask is None else jnp.where(mask, s, NEG_INF)
                  for s, (_, _, mask) in zip(ss, pieces)]
            m_prev = None if single else m_scr[head]       # (rows, 1)
            m_new = _fold(jnp.maximum, ss).max(axis=-1, keepdims=True)
            if not single:
                m_new = jnp.maximum(m_prev, m_new)
            ps = [jnp.exp2(s - m_new) for s in ss]
            if kv_offset < 0:
                # rows can be fully masked only when sq > sk: exp(0)=1 junk
                ps = [p if mask is None else jnp.where(s <= NEG_INF, 0.0, p)
                      for p, s, (_, _, mask) in zip(ps, ss, pieces)]
            if not single:
                alphas.append(jnp.exp2(m_prev - m_new))
            m_scr[head] = m_new

            # thunks: a whole block's ops stay in the order they always
            # had (scratch read, then the reduction or the dot)
            def l_new(ps=ps):
                return _fold(jnp.add, ps).sum(axis=-1, keepdims=True)

            def pv(ps=ps):
                return functools.reduce(jnp.add, [
                    _dot(p.astype(v.dtype), v) for p, (_, v) in zip(ps, kv)])

            pvs.append(pv)
            if single:
                l_scr[head] = l_new()
            else:
                l_scr[head] = l_scr[head] * alphas[-1] + l_new()

        # a head's p against the slab's v is right in its own lanes
        acc = (hh,) + rows
        if single:
            acc_scr[acc] = _join([pv() for pv in pvs], width)
        else:
            acc_scr[acc] = acc_scr[acc] * _join(alphas, width) + _join(
                [pv() for pv in pvs], width)

    if window is not None:
        _window_blocks(_inner, run, *place)
    elif causal:
        # only blocks straddling the diagonal pay for mask generation
        diag = (qi * block_q + kv_offset < (ki + 1) * block_k) & run

        @pl.when(diag)
        def _compute_masked():
            _inner(True)

        @pl.when(jnp.logical_not(diag) & run)
        def _compute_unmasked():
            _inner(False)
    else:

        @pl.when(run)
        def _compute():
            _inner(False)

    if not stateless:
        @pl.when(step == num_kv - 1)
        def _finalize():
            for hh in range(pack):
                _finish(hh, (), ())


def _fit_pack(bh: int) -> int:
    """Heads packed per grid step: largest of 8/4/2/1 dividing bh.

    8 is the ceiling: kernel VMEM scratch scales linearly with pack
    against the fixed 100MB vmem_limit (ADVICE r4)."""
    for p in (8, 4, 2):
        if bh % p == 0:
            return p
    return 1


def _causal_plan(causal: bool, num_q: int, num_kv: int, block_q: int,
                 block_k: int, kv_offset: int, tile: Optional[int]) -> dict:
    """The kernels' static `diag_off` / `tile`; nothing for a non-causal
    call, whose kernels are the whole-block program."""
    if not causal:
        return {}
    return {"diag_off": _diag_offset(num_q, num_kv, block_q, block_k,
                                     kv_offset),
            "tile": _causal_tile(block_q, block_k, tile)}


class _Slabs(NamedTuple):
    """Where the kernels find the heads in the projections' own
    (b, s, lanes) arrays: a batch row's heads lie side by side in
    `per_row` slabs of `width` lanes, `heads` heads each; q's, k's and
    v's first slab in its array is `offsets` (one array handed in three
    times has all three: GPT-2's `c_attn` output).  Under grouped heads
    k's and v's own arrays hold `per_row // kv_rep` slabs a row and
    query slab s reads slab s // kv_rep of them (`kv_route`)."""
    per_row: int
    heads: int
    width: int
    offsets: Tuple[int, int, int] = (0, 0, 0)
    kv_rep: int = 1


def _block_specs(slabs: Optional[_Slabs], pack: int, d: int):
    """(operand, keyed, grouped, row): the BlockSpec of a (block, d)
    piece of q/o/dO/dq, of k or v, of dk or dv, and of a (1, block_q)
    piece of lse/delta, each as a function of the grid axis (after the
    first) that walks the sequence, None = the one block.  The first
    grid axis walks groups of `pack` heads of the transposed (bh, s, d)
    arrays, or with `slabs` the QUERY slabs of every batch row of
    (b, s, lanes) arrays; lse and delta are (bh, 1, s) in both.

    Grouped heads (`slabs.kv_rep` > 1; without them `keyed` and
    `grouped` are `operand` itself): k's and v's arrays hold `kv_rep`
    times fewer slabs a row and `keyed` hands query slab s slab
    s // kv_rep of them; dk and dv are written a QUERY head, and
    `grouped` puts a group's heads on an axis of their own,
    (b, kv_rep, s, kv lanes): their sum is then over a major axis, a
    pass that relays nothing (summed over lane slabs of a (b, s, lanes)
    array the compiler first transposes the whole of it)."""
    def at(ij, axis):
        if callable(axis):  # a windowed call's narrowed sweep (`_swept`)
            return axis(ij)
        return 0 if axis is None else ij[axis]

    def operand(block, axis, first_slab=0, width=d):
        if slabs is None:  # `width`: v's, o's and dO's where it is not q's
            return pl.BlockSpec((pack, block, width),
                                lambda g, *ij: (g, at(ij, axis), 0))
        n = slabs.per_row
        return pl.BlockSpec(
            (1, block, slabs.width),
            lambda g, *ij: (g // n, at(ij, axis), first_slab + g % n))

    def row(block_q, axis):
        heads = pack * (slabs.heads if slabs else 1)
        return pl.BlockSpec((heads, 1, block_q),
                            lambda g, *ij: (g, 0, at(ij, axis)))

    if slabs is None or slabs.kv_rep == 1:
        return operand, operand, operand, row
    n, rep = slabs.per_row, slabs.kv_rep

    def keyed(block, axis, first_slab=0, width=d):
        return pl.BlockSpec(
            (1, block, slabs.width),
            lambda g, *ij: (g // n, at(ij, axis), first_slab + g % n // rep))

    def grouped(block, axis, width=d):
        return pl.BlockSpec(
            (1, None, block, slabs.width),
            lambda g, *ij: (g // n, g % n % rep, at(ij, axis), g % n // rep))

    return operand, keyed, grouped, row


def _geometry(q, v, slabs: Optional[_Slabs]):
    """(heads in all, lanes a unit of q and k, lanes a unit of v and o,
    units a grid step, grid steps along the first axis, lanes of q's
    rows, lanes of v's rows) of a call on `q`'s layout.  The transposed
    layout's two widths are the arrays' own (latent attention: q and k
    192 wide, v and o 128); a slab has one."""
    if slabs is None:
        (bh, _, d), dv = q.shape, v.shape[-1]
        pack = _fit_pack(bh)
        return bh, d, dv, pack, bh // pack, d, dv
    groups = q.shape[0] * slabs.per_row
    lanes = slabs.per_row * slabs.width
    return (groups * slabs.heads, slabs.width, slabs.width, 1, groups,
            lanes, lanes)


def _fa_forward_pallas(q, k, v, causal: bool, sm_scale: float,
                       block_q: int, block_k: int, interpret: bool,
                       tile: Optional[int] = None,
                       slabs: Optional[_Slabs] = None,
                       window: Optional[int] = None,
                       route: Optional[Tuple[str, int]] = None,
                       blocks: Optional[Tuple[int, int]] = None):
    """q: (bh, sq, d), k: (bh, sk, d), v: (bh, sk, dv) → (o (bh, sq, dv),
    lse (bh, 1, sq) f32): dv is d, or v's own width (each a block's whole
    last dimension: no operand is padded to the other's); with `slabs`,
    q/k/v: (b, s, lanes) as `_Slabs` says → (o (b, sq, h*d), lse (b*h, 1,
    sq)).

    `tile` overrides `_causal_tile` (tests and sweeps: a tile the size of
    the block is the whole-block mask); no caller of the package sets it.
    `window`: `_effective_window`'s (None: the causal program).
    `route` overrides `forward_route`, and `blocks` = (q rows, keys) the
    group step's, which are the call's own (tests and sweeps again).
    """
    sq, sk = q.shape[1], k.shape[1]
    bh, d, dv, pack, groups, _, lanes = _geometry(q, v, slabs)
    heads = slabs.heads if slabs else 1
    qo, ko, vo = slabs.offsets if slabs else (0, 0, 0)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    how, group = route or forward_route(
        sq, sk, d, slabs.kv_rep if slabs else 1, causal, window, block_k)
    if how == "group":
        return _fa_group_forward(q, k, v, sm_scale, slabs, group,
                                 *(blocks or (block_q, block_k)), tile,
                                 interpret)
    num_kv, keys = sk // block_k, 1  # keys: the grid axis that walks them
    win = _window_plan(window, sq // block_q, num_kv, block_q, block_k,
                       sk - sq)
    if "steps" in win:  # the key blocks a query block sees, not all
        win["limit"], num_kv = num_kv, win["steps"]
        keys = _swept(num_kv, win["koff"], win["limit"], False)
    grid = (groups, sq // block_q, num_kv)
    operand, keyed, _, row = _block_specs(slabs, pack, d)

    kernel = functools.partial(
        _fa_fwd_kernel, num_kv=num_kv, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_offset=sk - sq, pack=pack,
        **_causal_plan(causal, sq // block_q, sk // block_k, block_q,
                       block_k, sk - sq, tile), **_slab_heads(slabs), **win)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[operand(block_q, 0, qo), keyed(block_k, keys, ko),
                  keyed(block_k, keys, vo, dv)],
        out_specs=(operand(block_q, 0, width=dv), row(block_q, 0)),
        out_shape=(
            _out_struct(q.shape[:2] + (lanes,), q.dtype, q),
            _out_struct((bh, 1, sq), jnp.float32, q),
        ),
        # softmax state across the KV sweep; a causal call with one KV
        # block ends every query tile where it computes it
        scratch_shapes=[] if causal and num_kv == 1 else [
            pltpu.VMEM((pack * heads, block_q, 1), jnp.float32),
            pltpu.VMEM((pack * heads, block_q, 1), jnp.float32),
            pltpu.VMEM((pack, block_q, dv), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=_VMEM_LIMIT),
        interpret=interpret,
        name=_kernel_name("fwd", window),
    )(q, k, v)
    return o, lse


def _kernel_name(which: str, window: Optional[int]) -> str:
    """`dwt_fa_<which>`, or `dwt_fa_win_<which>` for a windowed call: the
    prefix every reader of the kernels' time takes, and one by which a
    reader takes the windowed calls alone."""
    return f"dwt_fa_{'win_' if window is not None else ''}{which}"


def _slab_heads(slabs: Optional[_Slabs]) -> dict:
    """The kernels' static `slab_heads`; nothing for the transposed
    layout, whose unit is a head."""
    return {} if slabs is None else {"slab_heads": slabs.heads}


# ------------------------------------- a group of query heads a grid step
#
# Under grouped heads on the direct route the `rep` query heads of a kv
# head lie side by side in q's (b, s, h*d) rows and read ONE k and v
# slab: a grid step is the GROUP over that slab.

_GROUP_HEADS = 7     # the most query heads a group step takes, and
_GROUP_LANES = 1024  # the most lanes of q they span: see `_group_heads`


def _group_heads(rep: int, width: int) -> int:
    """Query heads of a group of `rep`, `width` lanes each, a grid step
    takes: the largest divisor of `rep` up to `_GROUP_HEADS` whose heads
    span at most `_GROUP_LANES` — the whole group where that is one (6,
    7), else a part of it (16 heads of 128 in four steps of 4, 8 heads
    of 256 in two of 4).

    Measured on the chip at the four grouped cells' causal calls, the
    forward alone by device time, ms a call (PERF.md section 6, PR 67;
    `tools/perf_probe.py attn_direct` runs the table again).  The step
    is the slab step's own (1,024 q rows x 1,024 keys): there o and lse
    are the slab step's bit for bit, and of the six geometries swept it
    was the fastest at every d = 128 shape —
      Laguna 1 x 16,384 x 48/8 (slab step 28.09; 6 heads a step):
        (1024 x 1024) 19.84, (512 x 1024) 20.85, (256 x 1024) 22.26,
        (1024 x 512) 33.16, (512 x 512) 34.72, (512 x 2048) 87.52;
        3 heads a step 20.78, 2 heads 21.96;
      SmallThinker 2 x 16,384 x 28/4 (32.33; 7 heads): 23.10, 24.06,
        25.32, 38.20, 39.16, 104.74;
      Nemotron 2 x 8,192 x 32/2 (9.77): 4 heads a step 7.45 at (1024 x
        1024) and 8.16 at (512 x 1024); 8 heads 17.66 and 7.66; 16 heads
        refused (VMEM) and 20.09;
      Qwen3-Next 1 x 16,384 x 16/2 x 256 (16.44): 4 heads 13.53 at (1024
        x 1024), 13.84 at (512 x 1024), 12.86 at (1024 x 512); 8 heads
        27.98, 35.39 and 39.99, 12.99 at (512 x 512); 2 heads 13.57.
    A step's time falls off a cliff, 2.4-4 times, at 8 heads of 128 on
    (1024 x 1024) (32 MiB of float32 scores a step; 7 heads, 28 MiB, run)
    and at 2,048 lanes of q on (512 x 1024) (16 heads of 128, 8 of 256;
    1,024 lanes run); (2048 x 1024) fell at 3 heads of 128 (64.66) and
    2 of 256 (34.81) and ran at 2 of 128 (20.77).  What Mosaic holds of
    a step is no byte count read off these shapes, so the two bounds are
    the largest that RAN under each, not a model of it.  The q pre-scale
    hoisted to a q block's first key step moved nothing (19.89 for
    19.84)."""
    return max((g for g in range(1, _GROUP_HEADS + 1)
                if rep % g == 0 and g * width <= _GROUP_LANES), default=1)


def forward_route(sq: int, sk: int, d: int, rep: int = 1,
                  causal: bool = True, window: Optional[int] = None,
                  block_k: int = 1024) -> Tuple[str, int]:
    """Which step a forward call's grid takes, from its shapes alone:
    ("group", query heads a grid step) — `_fa_grp_fwd_kernel`, a kv
    head's group of query heads (or a part of it: `_group_heads`) over
    their one k and v slab — or ("slab", 0): `_fa_fwd_kernel`, a slab
    (or `_fit_pack` heads of the transposed layout) a step.

    The group step is for what it was measured at: grouped heads indexed
    on the direct route (`rep` = `_Slabs.kv_rep` > 1: a head is one or
    more whole slabs, `d` lanes), causal, no window, sq == sk over
    several key blocks of `block_k`.  One key block, a window,
    `sq != sk`, a head of its own k and v, two heads a slab and the
    transposed layout keep the slab step.

    The counter of this decision, as `backward_route` is of the
    backward's; in a trace its witness is the kernel's name
    (`dwt_fa_grp_fwd` / `dwt_fa_fwd`) and grid."""
    block_k = _fit_block(sk, block_k) or sk
    heads = _group_heads(rep, d)
    if heads > 1 and causal and window is None and sq == sk > block_k:
        return "group", heads
    return "slab", 0


def _fa_grp_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                       acc_scr, *, sm_scale: float, heads: int, width: int,
                       tile: Optional[int]):
    """One (batch row x kv head x part of its group, q block, key block)
    of a causal call with sq == sk: `heads` query heads, `width` lanes
    each of the q block's one lane range, against the one k and v block.

    The mathematics is `_fa_fwd_kernel`'s, operand for operand (q
    pre-scaled by sm_scale * LOG2E in its own dtype, float32 scores,
    statistics and accumulators, the operands' own dtype into the MXU,
    only the tile the diagonal crosses masked, by `_rel_mask`); what
    differs is the step and the order of its work.  A row band
    (`_block_work`) runs the group's first products, THEN its softmaxes,
    THEN its products with v: the MXU's and the vector units' work in
    long runs, nothing of one head waiting on its own exponentials
    (`sparse_attention._fwd_kernel`'s order, PERF.md section 6, PR 63).
    The key block's index is clamped at the diagonal by the BlockSpec, so
    a step above it fetches nothing and, here, runs nothing."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)
    rel = i * bq - j * bk  # the block's first query less its first key

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _inner(mask_block: bool, off: Optional[int] = None):
        for q0, q1, pieces in _block_work(mask_block, False, False, bq, bk,
                                          off, tile, 0, 0, 0):
            if pieces:  # else bq > bk: no row of the tile sees a key here
                _band(slice(q0, q1), pieces)

    def _band(rows, pieces):
        kv = [(k_ref[0, k0:k1], v_ref[0, k0:k1]) for k0, k1, _ in pieces]
        scores = []
        for a in range(heads):
            q = (q_ref[0, rows, a * width:(a + 1) * width].astype(
                jnp.float32) * (sm_scale * LOG2E)).astype(q_ref.dtype)
            scores.append([
                s if mask is None else jnp.where(mask, s, NEG_INF)
                for s, (_, _, mask) in zip(
                    [_dot_t(q, k) for k, _ in kv], pieces)])
        probs = []
        for a, ss in enumerate(scores):
            m_prev = m_scr[a, rows]
            m_new = jnp.maximum(m_prev, _fold(jnp.maximum, ss).max(
                axis=-1, keepdims=True))
            ps = [jnp.exp2(s - m_new) for s in ss]
            alpha = jnp.exp2(m_prev - m_new)
            m_scr[a, rows] = m_new
            l_scr[a, rows] = l_scr[a, rows] * alpha + _fold(
                jnp.add, ps).sum(axis=-1, keepdims=True)
            probs.append((alpha, [p.astype(v_ref.dtype) for p in ps]))
        for a, (alpha, ps) in enumerate(probs):
            acc_scr[a, rows] = acc_scr[a, rows] * alpha + functools.reduce(
                jnp.add, [_dot(p, v) for p, (_, v) in zip(ps, kv)])

    # below the diagonal a block runs whole; one it crosses runs the
    # tiles at or below it, by its static place; one above it nothing
    pl.when(rel >= bk)(functools.partial(_inner, False))
    places = math.gcd(bq, bk)  # `rel` is a multiple of it
    for off in range(places - bq, bk, places):
        pl.when(rel == off)(functools.partial(_inner, True, off))

    @pl.when(j == ((i + 1) * bq - 1) // bk)  # the diagonal's last block
    def _finalize():
        for a in range(heads):
            l = l_scr[a]
            o_ref[0, :, a * width:(a + 1) * width] = (
                acc_scr[a] / l).astype(o_ref.dtype)
            # as `_fa_fwd_kernel._finish` leaves it: natural log, (1, bq)
            lse_ref[a] = (m_scr[a] * (1.0 / LOG2E) + jnp.log(l)).T


def _fa_group_forward(q, k, v, sm_scale: float, slabs: _Slabs, heads: int,
                      bq: int, bk: int, tile: Optional[int],
                      interpret: bool):
    """`_fa_forward_pallas` where `forward_route` says ("group", heads):
    q (b, s, h*d), k and v (b, s, n_kv*d) -> (o (b, s, h*d), lse (b*h, 1,
    s) float32), what the slab step gives and the backward reads; a
    grid step (bq q rows x bk keys), a block the diagonal crosses cut
    into `tile`s (None: `_CAUSAL_TILE`, or the block's shorter side
    where a sweep's is shorter)."""
    b, s, _ = q.shape
    width = slabs.width
    parts = slabs.kv_rep // heads
    n = slabs.per_row // heads  # groups a batch row: kv heads x parts

    def keys_of(i, j):  # the last key block at or below the q block's end
        return jnp.minimum(j, ((i + 1) * bq - 1) // bk)

    rows = pl.BlockSpec((1, bq, heads * width),
                        lambda u, i, j: (u // n, i, u % n))
    keys = pl.BlockSpec(
        (1, bk, width),
        lambda u, i, j: (u // n, keys_of(i, j), u % n // parts))
    return pl.pallas_call(
        functools.partial(
            _fa_grp_fwd_kernel, sm_scale=sm_scale, heads=heads, width=width,
            tile=_causal_tile(bq, bk, tile or min(_CAUSAL_TILE, bq, bk))),
        grid=(b * n, s // bq, s // bk),
        in_specs=[rows, keys, keys],
        out_specs=(rows, pl.BlockSpec((heads, 1, bq),
                                      lambda u, i, j: (u, 0, i))),
        out_shape=(_out_struct(q.shape, q.dtype, q),
                   _out_struct((b * slabs.per_row, 1, s), jnp.float32, q)),
        scratch_shapes=[pltpu.VMEM((heads, bq, 1), jnp.float32),
                        pltpu.VMEM((heads, bq, 1), jnp.float32),
                        pltpu.VMEM((heads, bq, width), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=_VMEM_LIMIT),
        interpret=interpret,
        name="dwt_fa_grp_fwd",
    )(q, k, v)


# ------------------------------------------------------------ backward kernels


def _p_transposed(q, k, lse, mask, sm_scale):
    """Recompute p^T = exp(s^T - lse) as (block_k, block_q).

    The backward kernels work in transposed space — scores with queries in
    LANES — so the per-row lse/delta arrive as native (1, block_q) row
    vectors and broadcast straight across sublanes.  The row-major layout
    (bh, 1, sq) costs no 128x lane padding in HBM and no per-grid-step
    sublane<->lane relayouts in VMEM (measured ~1.5ms/call at the bench
    shape for the (block_q, 1) variant).  It also removes the full
    (block_q, block_k) p.T / ds.T transposes the dkv kernel otherwise pays:
    dv = dot(p^T, do) and dk = dot(ds^T, q) contract directly.
    """
    qs = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
    sT = _dot_t(k, qs)                          # (block_k, block_q)
    if mask is not None:
        sT = jnp.where(mask, sT, NEG_INF)
    # lse = -inf marks a fully-masked row: its p must be 0, not
    # exp(s + inf) = nan.  sT is in log2 units (LOG2E folded into the q
    # pre-scale, a (block_q, d) array 16x smaller than the score matrix);
    # the natural-log lse converts on its (1, block_q) row, so the only
    # score-matrix-sized transcendental is a bare exp2.
    finite = jnp.isfinite(lse)
    return jnp.where(
        finite, jnp.exp2(sT - jnp.where(finite, lse * LOG2E, 0.0)), 0.0)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, num_kv: int, causal: bool,
                      sm_scale: float, block_q: int, block_k: int,
                      kv_offset: int, pack: int,
                      diag_off: Optional[int] = None,
                      tile: Optional[int] = None, slab_heads: int = 1,
                      delta_from_o: bool = False,
                      window: Optional[int] = None, **sweep):
    qi = pl.program_id(1)
    ki = step = pl.program_id(2)
    heads = range(slab_heads)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if window is not None:
        ki, run, place = _sweep_place(qi, step, False, block_q, block_k,
                                      kv_offset, window, **sweep)
    elif causal:
        run = (qi + 1) * block_q + kv_offset > ki * block_k
    else:
        run = True

    def _inner(mask_block: bool, rel: Optional[int] = None):
        # by query tile, like the forward: each dq tile is added to once
        # per piece, its keys the prefix the tile sees
        work = _block_work(mask_block, False, True, block_q, block_k,
                           diag_off, tile, qi, ki, kv_offset, window, rel)
        def _unit(hh):
            for q0, q1, pieces in work:
                rows = (hh,) + _rows(q0, q1, block_q)
                lanes = [(_head(hh, a, slab_heads),)
                         + _lanes(q0, q1, block_q) for a in heads]
                for k0, k1, mask in pieces:
                    keys = (hh,) + _rows(k0, k1, block_k)
                    k = k_ref[keys]
                    dsTs = []
                    for a in heads:
                        pT = _p_transposed(
                            _own(q_ref[rows], a, slab_heads), k,
                            lse_ref[lanes[a]], mask, sm_scale)
                        v, do = v_ref[keys], do_ref[rows]
                        dpT = _dot_t(v, _own(do, a, slab_heads))
                        delta = _delta_rows(delta_ref, do, a, rows, lanes[a],
                                            slab_heads, delta_from_o)
                        dsTs.append((pT * (dpT - delta)
                                     * sm_scale).astype(k.dtype))
                    dq_scr[rows] += _join(
                        [_dot_c0(dsT, k) for dsT in dsTs],
                        dq_scr.shape[-1])                    # (rows, d)

        _each_head(pack, work, _unit)

    if window is not None:
        _window_blocks(_inner, run, *place)
    elif causal:
        diag = (qi * block_q + kv_offset < (ki + 1) * block_k) & run

        @pl.when(diag)
        def _compute_masked():
            _inner(True)

        @pl.when(jnp.logical_not(diag) & run)
        def _compute_unmasked():
            _inner(False)
    else:

        @pl.when(run)
        def _compute():
            _inner(False)

    @pl.when(step == num_kv - 1)
    def _finalize():
        for hh in range(pack):
            dq_ref[hh] = dq_scr[hh].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       *outs, num_q: int, causal: bool, sm_scale: float,
                       block_q: int, block_k: int, kv_offset: int, pack: int,
                       diag_off: Optional[int] = None,
                       tile: Optional[int] = None, slab_heads: int = 1,
                       delta_from_o: bool = False,
                       window: Optional[int] = None, **sweep):
    """dk and dv of a key block, summed in float32 scratch over the query
    blocks that see it (grid: key blocks outer, query blocks inner):
    `outs` = dk, dv and their scratch.

    Handed dq's too (`outs` = dq, dk, dv, then the three scratches) it is
    the several-block FUSED backward: the same sweep, the same recompute
    of p, and one more product a tile, `_dot_c0(dsT, k)`, added into a
    float32 dq that spans the unit's WHOLE query length (`dq_scr`:
    (pack * query blocks, block_q, lanes), a query block found by its
    leading index).  dq's output block is the whole sequence of the
    group as well: its index is constant over both inner grid axes, so
    it stays in VMEM from the group's first grid step to its last, is
    written once, at the last, and leaves for HBM while the next group
    runs.  A query block's sum runs over its key blocks in ascending
    order, as the dq kernel's does."""
    ki = pl.program_id(1)
    qi = step = pl.program_id(2)
    heads = range(slab_heads)
    if len(outs) == 6:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = outs
        q_blocks = dq_scr.shape[0] // pack
    else:
        (dk_ref, dv_ref, dk_scr, dv_scr), dq_ref, dq_scr = outs, None, None

    def _each_dq_block(body):  # a loop, not q_blocks copies of the code
        def _block(i, carry):
            for hh in range(pack):
                body(hh, i)
            return carry

        jax.lax.fori_loop(0, q_blocks, _block, 0)

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if dq_scr is not None:
        @pl.when((step == 0) & (ki == 0))
        def _init_dq():
            def _zero(hh, i):
                dq_scr[hh * q_blocks + i] = jnp.zeros(
                    dq_scr.shape[1:], jnp.float32)

            _each_dq_block(_zero)

    if window is not None:
        qi, run, place = _sweep_place(ki, step, True, block_q, block_k,
                                      kv_offset, window, **sweep)
    elif causal:
        run = (qi + 1) * block_q + kv_offset > ki * block_k
    else:
        run = True

    def _inner(mask_block: bool, rel: Optional[int] = None):
        # by key tile: its queries are the suffix that sees it (under a
        # window: the run of queries that do)
        work = _block_work(mask_block, True, True, block_q, block_k,
                           diag_off, tile, qi, ki, kv_offset, window, rel)
        def _unit(hh):
            for k0, k1, pieces in work:
                keys = (hh,) + _rows(k0, k1, block_k)
                for q0, q1, mask in pieces:
                    rows = (hh,) + _rows(q0, q1, block_q)
                    lanes = [(_head(hh, a, slab_heads),)
                             + _lanes(q0, q1, block_q) for a in heads]
                    # each head's q and dO with the other's lanes zeroed:
                    # the products over the rows then add up to the slab
                    q = q_ref[rows]
                    qs = [_own(q, a, slab_heads) for a in heads]
                    do = do_ref[rows]
                    dos = [_own(do, a, slab_heads) for a in heads]
                    k = k_ref[keys]
                    pTs = [_p_transposed(qs[a], k, lse_ref[lanes[a]], mask,
                                         sm_scale).astype(q.dtype)
                           for a in heads]
                    dv_scr[keys] += functools.reduce(jnp.add, [
                        _dot(pTs[a], dos[a]) for a in heads])  # (keys, d)
                    v = v_ref[keys]
                    dpTs = [_dot_t(v, dos[a]) for a in heads]
                    dsTs = [(pTs[a].astype(jnp.float32)
                             * (dpTs[a] - _delta_rows(
                                 delta_ref, do, a, rows, lanes[a],
                                 slab_heads, delta_from_o))
                             * sm_scale).astype(q.dtype) for a in heads]
                    dk_scr[keys] += functools.reduce(jnp.add, [
                        _dot(dsTs[a], qs[a]) for a in heads])  # (keys, d)
                    if dq_scr is not None:
                        # against the slab's k, right in the head's own
                        # lanes
                        dq_scr[(hh * q_blocks + qi,)
                               + _rows(q0, q1, block_q)] += _join(
                            [_dot_c0(dsTs[a], k) for a in heads],
                            dq_scr.shape[-1])                # (rows, d)

        _each_head(pack, work, _unit)

    if window is not None:
        _window_blocks(_inner, run, *place)
    elif causal:
        diag = (qi * block_q + kv_offset < (ki + 1) * block_k) & run

        @pl.when(diag)
        def _compute_masked():
            _inner(True)

        @pl.when(jnp.logical_not(diag) & run)
        def _compute_unmasked():
            _inner(False)
    else:

        @pl.when(run)
        def _compute():
            _inner(False)

    @pl.when(step == num_q - 1)
    def _finalize():
        for hh in range(pack):
            dk_ref[hh] = dk_scr[hh].astype(dk_ref.dtype)
            dv_ref[hh] = dv_scr[hh].astype(dv_ref.dtype)

    if dq_scr is not None:
        @pl.when((step == num_q - 1) & (ki == pl.num_programs(1) - 1))
        def _finalize_dq():
            def _write(hh, i):
                rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
                dq_ref[hh, rows] = dq_scr[hh * q_blocks + i].astype(
                    dq_ref.dtype)

            _each_dq_block(_write)


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, *dq_scr, causal: bool,
                         sm_scale: float, block_q: int, block_k: int,
                         kv_offset: int, pack: int,
                         diag_off: Optional[int] = None,
                         tile: Optional[int] = None, slab_heads: int = 1,
                         delta_from_o: bool = False,
                         window: Optional[int] = None):
    """Single-block fused backward: dq, dk AND dv in one pass, for a call
    whose whole sequence is one block each way (num_q == num_kv == 1): a
    grid of heads alone, no sum across grid steps, dk and dv written
    where they are computed.  Against the split pair's 7 dots it saves
    the second S and dP recomputes and one full exp pass over the score
    matrix.  Several blocks fuse too, by another means: dq accumulates
    over the kv grid axis while dk/dv accumulate over the q axis, and a
    Pallas TPU output block only stays resident across CONSECUTIVE grid
    steps — so `_fa_bwd_dkv_kernel` keeps a dq block of the WHOLE
    sequence, whose index never changes inside a group.

    Causal, the block is computed by key tile (`_block_work`): a tile's
    dk and dv come out whole from the queries that see it, and only dq
    accumulates across tiles, in the one f32 scratch `dq_scr` (absent
    from a call that computes its block whole).  Still one pass and five
    dots a head, each on the tiles at or below the diagonal.
    """
    work = _block_work(causal, True, True, block_q, block_k, diag_off, tile,
                       0, 0, kv_offset, window,
                       None if window is None else kv_offset)
    # the first key tile is seen by every query that sees any: it sets
    # dq's rows, the later tiles add to them.  Not under a window, whose
    # late queries see no early key: dq's rows are zeroed first there
    seen_from = 0 if window is not None else \
        work[0][2][0][0] if work[0][2] else block_q
    heads = range(slab_heads)
    width = dq_ref.shape[-1]

    def _unit(hh):
        if dq_scr and window is not None:
            dq_scr[0][...] = jnp.zeros_like(dq_scr[0])
        for k0, k1, pieces in work:
            keys = (hh,) + _rows(k0, k1, block_k)
            k = dk = dv = None
            for q0, q1, mask in pieces:
                rows = (hh,) + _rows(q0, q1, block_q)
                lanes = [(_head(hh, a, slab_heads),)
                         + _lanes(q0, q1, block_q) for a in heads]
                last = q1 == pieces[-1][1]
                q = q_ref[rows]
                qs = [_own(q, a, slab_heads) for a in heads]
                k = k_ref[keys] if k is None else k
                do = do_ref[rows]
                dos = [_own(do, a, slab_heads) for a in heads]
                pTs = [_p_transposed(qs[a], k, lse_ref[lanes[a]], mask,
                                     sm_scale) for a in heads]  # (keys, rows)
                dvp = functools.reduce(jnp.add, [
                    _dot(pTs[a].astype(q.dtype), dos[a])
                    for a in heads])                         # (keys, d)
                dv = dvp if dv is None else dv + dvp
                if last:
                    dv_ref[keys] = dv.astype(dv_ref.dtype)
                v = v_ref[keys]
                dsTs = [(pTs[a] * (_dot_t(v, dos[a]) - _delta_rows(
                    delta_ref, do, a, rows, lanes[a], slab_heads,
                    delta_from_o)) * sm_scale).astype(q.dtype)
                        for a in heads]
                dkp = functools.reduce(jnp.add, [
                    _dot(dsTs[a], qs[a]) for a in heads])    # (keys, d)
                dk = dkp if dk is None else dk + dkp
                if last:
                    dk_ref[keys] = dk.astype(dk_ref.dtype)
                # against the slab's k, right in the head's own lanes
                dqp = _join([_dot_c0(dsT, k) for dsT in dsTs],
                            width)                           # (rows, d)
                if not dq_scr:
                    dq_ref[rows] = dqp.astype(dq_ref.dtype)
                elif k0 == 0 and window is None:
                    dq_scr[0][q0:q1] = dqp
                else:
                    dq_scr[0][q0:q1] += dqp
            if not pieces:  # sq > sk: keys no query sees
                dk_ref[keys] = jnp.zeros((k1 - k0,) + dk_ref.shape[2:],
                                         dk_ref.dtype)
                dv_ref[keys] = jnp.zeros((k1 - k0,) + dv_ref.shape[2:],
                                         dv_ref.dtype)
        if dq_scr:
            if seen_from:  # sq > sk: queries that see no key
                dq_scr[0][:seen_from] = jnp.zeros(
                    (seen_from,) + dq_scr[0].shape[1:], jnp.float32)
            dq_ref[hh] = dq_scr[0][...].astype(dq_ref.dtype)

    _each_head(pack, work, _unit)


def _fused_bwd_vmem(pack: int, sq: int, block_q: int, block_k: int,
                    d_qk: int, d_v: int, slab_heads: int,
                    itemsize: int) -> int:
    """Bytes of VMEM a fused several-block backward holds at `pack` units
    a grid step, reckoned from shapes: every block at the 128-lane tiles
    it is stored in (192 lanes lie on 256), operands and results
    double-buffered, and two float32 score tiles a head of the slab for
    the values of the body (what Mosaic holds of them, compiled for a
    described v5e at the cells' shapes, is half of that: 43, 81, 24 and
    22 MiB used where this says 46, 84, 28 and 29).  It is the test of
    whether a unit's dq fits AT ALL, and whether two units' do; above
    two it picks nothing any more: the bytes were never what a larger
    pack cost (PR 52: four units ran as slow at 4,096 rows, 64.5 MiB
    reckoned, as at 8,192 rows and 96.5)."""
    wq, wv = (-(-w // 128) * 128 for w in (d_qk, d_v))
    dq = pack * sq * wq * (4 + 2 * itemsize)     # scratch, output block
    dkv = pack * block_k * (wq + wv) * (4 + 2 * itemsize)
    # q, k, v, dO, and o where the kernel sums delta itself
    ins = 2 * pack * itemsize * (block_q * (wq + wv) + block_k * (wq + wv)
                                 + block_q * wv * (slab_heads > 1))
    rows = 2 * 2 * pack * slab_heads * 8 * block_q * 4   # lse, delta
    live = 2 * 4 * block_q * block_k * slab_heads
    return dq + dkv + ins + rows + live


def backward_route(sq: int, sk: int, d_qk: int, d_v: Optional[int] = None,
                   slabs: int = 0, bh: int = 8, block_q: int = 1024,
                   block_k: int = 1024, itemsize: int = 2) -> Tuple[str, int]:
    """Which kernels a backward call runs, from its shapes alone:
    ("fused", units a grid step) — ONE kernel gives dq, dk and dv from a
    single recompute of p — or ("split", units a grid step): the dq and
    dk/dv pair, each with a recompute of its own.

    One block each way is fused as it always was.  Several blocks are
    fused where the WHOLE query length of a unit's dq — float32 scratch
    and a double-buffered output block — fits `_VMEM_LIMIT` beside the
    sweep's blocks (`_fused_bwd_vmem`): 16,384 x 128 lanes is 16 MiB,
    131,072 x 128 does not fit and takes the pair.  `slabs`: the heads a
    slab of the direct layout (0: the transposed layout, whose `bh`
    heads the one-block kernel and the pair pack by the largest of
    8/4/2/1 that divides them).

    A several-block fused sweep takes `_HEAD_GROUP` units a grid step
    where two divide the heads and fit, else one: the most `_each_head`
    runs without unrolling a whole block's body further than its loop
    over a tiled block does.  Until PR 52 it took the largest of 8/4/2/1
    that fit, by the byte count alone; timed on the chip (32 heads, 192
    | 128 wide, ms a call at 8 / 4 / 2 / 1 units): 8,192 rows - / 23.44
    / 13.46 / 13.66, 4,096 rows - / 6.03 / 3.62 / 3.68, 2,048 rows 1.35
    / 1.61 / 1.00 / 1.02, Kimi's 16,384 rows - / - / 51.90 / 52.58
    (PERF.md section 6, PR 52).

    The counter of this decision, as `attention_route` is of the layout
    and `causal_tile_count` of the tiles; in a trace its witness is the
    kernels' names (`dwt_fa_bwd_fused` / `dwt_fa_bwd_dq` + `_dkv`) and
    the sweep's grid (heads / units, key blocks, query blocks)."""
    d_v = d_qk if d_v is None else d_v
    d_qk, d_v = _kernel_head_dim(d_qk), _kernel_head_dim(d_v)
    block_q = _fit_block(sq, block_q) or sq
    block_k = _fit_block(sk, block_k) or sk
    most = 1 if slabs else _fit_pack(bh)  # what the pair packs
    if sq == block_q and sk == block_k:
        return "fused", most
    for pack in (_HEAD_GROUP, 1):
        if pack <= most and _fused_bwd_vmem(
                pack, sq, block_q, block_k, d_qk, d_v, slabs or 1,
                itemsize) <= _VMEM_LIMIT:
            return "fused", pack
    return "split", most


def _fa_backward_pallas(q, k, v, o, lse, do, causal: bool, sm_scale: float,
                        block_q: int, block_k: int, interpret: bool,
                        glse=None, tile: Optional[int] = None,
                        slabs: Optional[_Slabs] = None,
                        window: Optional[int] = None,
                        route: Optional[Tuple[str, int]] = None):
    """All operands flat (bh, s, d) — v, o and dO (bh, s, dv) where v has
    a width of its own — or with `slabs` (b, s, lanes) as
    `_fa_forward_pallas` takes and gives them; lse (bh, 1, sq) f32.
    Returns dq, dk, dv, each in its operand's layout — under grouped
    heads (`slabs.kv_rep` > 1) dk and dv a QUERY head, a group's heads
    on an axis of their own, (b, kv_rep, s, kv lanes): the caller sums
    over it (`_fa_projected_bwd`).

    The kernels recompute p in TRANSPOSED space (queries in lanes) so the
    per-row lse/delta broadcast natively — see `_p_transposed`.  delta and
    the optional lse cotangent `glse` (bh, 1, sq) fold together outside
    (d lse / d s = p, so ds = p * (dp - delta + glse)).

    `route` overrides `backward_route` for a several-block call (tests
    and sweeps: the pair at a shape that fuses, another pack); no caller
    of the package sets it."""
    sq, sk = q.shape[1], k.shape[1]
    bh, d, dv, pack, groups, lanes, v_lanes = _geometry(q, v, slabs)
    qo, ko, vo = slabs.offsets if slabs else (0, 0, 0)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    how, pack = route or backward_route(
        sq, sk, d, dv, slabs.heads if slabs else 0, bh, block_q, block_k,
        q.dtype.itemsize)
    fused = how == "fused"
    if slabs is None:
        groups = bh // pack
    kv_offset = sk - sq
    num_q = sq // block_q
    num_kv = sk // block_k
    plan = dict(_causal_plan(causal, num_q, num_kv, block_q, block_k,
                             kv_offset, tile), **_slab_heads(slabs))
    win = _window_plan(window, num_q, num_kv, block_q, block_k, kv_offset)

    operand, keyed, grouped, row = _block_specs(slabs, pack, d)
    # delta = rowsum(dO ∘ O) a head — cheap fused reduce; (bh, 1, sq)
    # row-major layout avoids the 128x lane padding a (bh, sq, 1) array
    # would pay.  Two heads a slab: the kernels take o and sum each
    # head's lanes themselves (`_delta_rows`) — as an XLA reduce over
    # half a lane tile into a sequence-minor result it is a relayout of
    # the f32 product first, four passes over it where the kernel makes
    # one over o
    delta_from_o = slabs is not None and slabs.heads > 1
    if delta_from_o:
        assert glse is None
        plan["delta_from_o"] = True
        delta, delta_spec = o, operand
    else:
        delta_spec = row
        delta = do.astype(jnp.float32) * o.astype(jnp.float32)
        if slabs is None:
            delta = delta.sum(-1)[:, None, :]
        else:
            delta = delta.reshape(o.shape[0], sq, bh // o.shape[0], -1).sum(
                -1).transpose(0, 2, 1).reshape(bh, 1, sq)
        if glse is not None:
            delta = delta - glse

    dq_shape = _out_struct(q.shape[:2] + (lanes,), q.dtype, q)
    if slabs is not None and slabs.kv_rep > 1:  # (b, kv_rep, s, kv lanes)
        dk_shape, dv_shape = (_out_struct(
            x.shape[:1] + (slabs.kv_rep,) + x.shape[1:], x.dtype, q)
            for x in (k, v))
    else:
        dk_shape = _out_struct(k.shape[:2] + (lanes,), k.dtype, q)
        dv_shape = _out_struct(v.shape[:2] + (v_lanes,), v.dtype, q)
    ops = [q, k, v, do, lse, delta]

    if num_q == 1 and num_kv == 1:
        tiled = causal and len(_causal_bands_t(
            block_q, block_k, plan["diag_off"], plan["tile"])) > 1
        return pl.pallas_call(
            functools.partial(
                _fa_bwd_fused_kernel, causal=causal, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k, kv_offset=kv_offset,
                pack=pack, window=window, **plan),
            grid=(groups,),
            in_specs=[operand(block_q, None, qo), keyed(block_k, None, ko),
                      keyed(block_k, None, vo, dv),
                      operand(block_q, None, width=dv),
                      row(block_q, None), delta_spec(block_q, None)],
            out_specs=(operand(block_q, None), grouped(block_k, None),
                       grouped(block_k, None, width=dv)),
            out_shape=(dq_shape, dk_shape, dv_shape),
            # dq across key tiles, one unit at a time
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]
            if tiled else [],
            compiler_params=_compiler_params(
                "parallel", vmem_limit=_VMEM_LIMIT),
            interpret=interpret,
            name=_kernel_name("bwd_fused", window),
        )(*ops)

    # a windowed call on blocks the grid places sweeps the blocks that
    # see each other, not all (`_window_plan`): the keys of a query block
    # for dq, the queries of a key block for dk and dv
    keys = queries = 1  # the grid axis that walks them
    dq_steps, dkv_steps, dq_win, dkv_win = num_kv, num_q, win, win
    if "steps" in win:
        dq_steps = dkv_steps = win["steps"]
        dq_win, dkv_win = dict(win, limit=num_kv), dict(win, limit=num_q)
        keys = _swept(dq_steps, win["koff"], num_kv, False)
        queries = _swept(dkv_steps, win["koff"], num_q, True)

    # grid: kv outer, q inner.  Fused (`backward_route`) the sweep gives
    # dq too, from a float32 scratch and an output block of the group's
    # whole query length; else dk and dv alone, and dq has its own kernel
    sweep = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, num_q=dkv_steps, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, kv_offset=kv_offset, pack=pack,
                          **plan, **dkv_win),
        grid=(groups, num_kv, dkv_steps),
        in_specs=[operand(block_q, queries, qo), keyed(block_k, 0, ko),
                  keyed(block_k, 0, vo, dv),
                  operand(block_q, queries, width=dv),
                  row(block_q, queries), delta_spec(block_q, queries)],
        out_specs=(operand(sq, None),) * fused + (
            grouped(block_k, 0), grouped(block_k, 0, width=dv)),
        out_shape=(dq_shape,) * fused + (dk_shape, dv_shape),
        scratch_shapes=[
            pltpu.VMEM((pack * num_q, block_q, d), jnp.float32)] * fused + [
            pltpu.VMEM((pack, block_k, d), jnp.float32),
            pltpu.VMEM((pack, block_k, dv), jnp.float32),
        ],
        # dq's block outlives a key block: the key axis is a sequence too
        compiler_params=_compiler_params(
            "parallel", "arbitrary" if fused else "parallel", "arbitrary",
            vmem_limit=_VMEM_LIMIT),
        interpret=interpret,
        name=_kernel_name("bwd_fused" if fused else "bwd_dkv", window),
    )
    if fused:
        return sweep(*ops)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, num_kv=dq_steps, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, kv_offset=kv_offset, pack=pack,
                          **plan, **dq_win),
        grid=(groups, num_q, dq_steps),
        in_specs=[operand(block_q, 0, qo), keyed(block_k, keys, ko),
                  keyed(block_k, keys, vo, dv),
                  operand(block_q, 0, width=dv),
                  row(block_q, 0), delta_spec(block_q, 0)],
        out_specs=operand(block_q, 0),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((pack, block_q, d), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=_VMEM_LIMIT),
        interpret=interpret,
        name=_kernel_name("bwd_dq", window),
    )(*ops)
    dk, dv = sweep(*ops)
    return dq, dk, dv


# ----------------------------------------------------------------- reference


def kept_mask(sq: int, sk: int, window: Optional[int] = None):
    """(sq, sk) bool: query i sees key j iff 0 <= i + (sk - sq) - j, and
    with a window iff that distance is also < window.  The jnp paths'
    one mask."""
    mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
    return mask


def _kept_at(rows, cols, window: Optional[int]):
    """`kept_mask` for one block of the streamed paths: `rows` and
    `cols` are the queries' and keys' absolute key positions."""
    dist = rows[:, None] - cols[None, :]
    return dist >= 0 if window is None else (dist >= 0) & (dist < window)


def _attention_reference(q, k, v, causal: bool, sm_scale: float,
                         window: Optional[int] = None):
    """Plain jnp attention — numerics oracle + non-TPU fallback.

    q: (b, h, sq, d); k/v: (b, h, sk, d)
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        mask = kept_mask(s.shape[-2], s.shape[-1], window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    bwd_block_q: int = 0, bwd_block_k: int = 0,
                    window: Optional[int] = None):
    """Multi-head attention, FA2-style.

    Args: q (b, h, sq, d); k (b, h, sk, d); v (b, h, sk, dv), dv = d or
    a width of v's own (latent attention: QK^T over 192, PV over 128; the
    kernels block each operand at its own width, none is padded to the
    other's).  Returns (b, h, sq, dv).  `sm_scale` None: 1/sqrt(d), q's.
    `block_q`/`block_k` are the GRID's blocks, capped at the sequence: at
    T <= 1024 the grid is one block each way.  What a causal call skips
    below that grain is not the caller's to set: the kernels cut a block
    the diagonal crosses into `_CAUSAL_TILE` tiles themselves (module
    docstring).  `bwd_block_q`/`bwd_block_k` block the backward kernels
    independently (0 = inherit block_q/block_k; no chip run of
    this repository has measured another choice — PERF.md section 6).
    `window`: a causal call's query sees the `window` keys that end at
    its own and none before (None, or a window no shorter than the keys:
    the causal call itself).
    """
    out, _ = _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                     bwd_block_q, bwd_block_k, window)
    return out


def _resolve_scale(sm_scale, d):
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)


def _fit_block(seq: int, pref: int) -> Optional[int]:
    """Largest block ≤ pref that tiles `seq`; None if nothing reasonable.

    Falls back through the standard tile sizes so e.g. seq=640 still rides
    the kernel with block 128 instead of silently hitting the dense path.
    A block equal to the whole (modest) sequence is always legal — Mosaic
    accepts blocks equal to the array dimension.
    """
    for b in (pref, 1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= pref and b <= seq and seq % b == 0:
            return b
    return seq if seq <= 2048 else None


def _use_pallas(sq, sk, d, block_q, block_k) -> bool:
    # head_dim runs natively (lane-aligned) or zero-padded, so any d
    # qualifies; sequences need a workable tile size
    return (mosaic.on_tpu() and _fit_block(sq, block_q) is not None
            and _fit_block(sk, block_k) is not None)


def _use_streamed(sq, sk) -> bool:
    """Blockwise-scan fallback instead of the dense O(sq*sk) reference.

    Only consulted when the Pallas kernels are unavailable (non-TPU
    backend).  The dense fallback materializes full f32 score matrices —
    fine for small test shapes, but it misrepresents the TPU program's
    memory on big shapes: the 8B AOT fit proof (tests/test_scale_8b.py)
    compiles on a virtual CPU mesh, where dense attention would dominate
    `memory_analysis()` with buffers the Pallas path never allocates.
    The switch is the point where a per-head score matrix reaches
    2048^2 (16MB f32)."""
    return sq * sk >= 2048 * 2048


def _kernel_head_dim(d: int) -> int:
    """Head dim as seen by the kernels.

    Mosaic accepts any block whose last dim equals the array's, so lane-
    aligned head dims (multiples of 8) run natively — d=64 (GPT-2) included,
    avoiding pad copies.  Odd dims are zero-padded to the 128-lane boundary
    (padded q/k columns add 0 to scores; padded v columns are sliced off).
    """
    return d if d % 8 == 0 else max(128, -(-d // 128) * 128)


def kernel_lanes(d_qk: int, d_v: int) -> int:
    """Lanes a score entry's two products run in the kernels, QK^T over
    q's and k's width and PV over v's, each as the kernels block it
    (`_kernel_head_dim`): the counter of what a call's widths cost
    beside what its model asks (d_qk + d_v), as `causal_tile_count` is of
    its tiles."""
    return _kernel_head_dim(d_qk) + _kernel_head_dim(d_v)


def _pad_head_dim(x, d_pad):
    d = x.shape[-1]
    if d == d_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))


def _flat_padded(q, k, v):
    """(b, h, s, d) -> (b*h, s, d as the kernels see it), each of q, k
    and v at its own width."""
    return tuple(_pad_head_dim(x.reshape(-1, *x.shape[2:]),
                               _kernel_head_dim(x.shape[-1]))
                 for x in (q, k, v))


def _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k, window=None):
    """Shared forward: returns ((out, lse_bhs), residuals).  q and k
    share a width and v may have its own, which is the output's; the
    default scale is 1/sqrt(q's)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = _resolve_scale(sm_scale, d)
    window = _effective_window(window, causal, sk)
    if _use_pallas(sq, sk, d, block_q, block_k):
        bq = _fit_block(sq, block_q)
        bk = _fit_block(sk, block_k)
        qf, kf, vf = _flat_padded(q, k, v)
        o, lse = _fa_forward_pallas(qf, kf, vf, causal, scale, bq, bk,
                                    interpret=False, window=window)
        dv = v.shape[-1]
        out = o[:, :, :dv].reshape(b, h, sq, dv)
        return (out, lse.reshape(b, h, sq)), (q, k, v, o, lse)
    if _use_streamed(sq, sk):
        out, lse = _streamed_with_lse(q, k, v, causal, scale, block_k,
                                      window)
        return (out, lse), (q, k, v, out, lse)
    out, lse = _reference_with_lse(q, k, v, causal, scale, window)
    return (out, lse), (q, k, v, out, None)


def _reference_with_lse(q, k, v, causal, scale, window=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = kept_mask(s.shape[-2], s.shape[-1], window)
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    l_safe = jnp.where(l > 0, l, 1.0)
    lse = jnp.where(l[..., 0] > 0, (m + jnp.log(l_safe))[..., 0], -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", (p / l_safe).astype(v.dtype), v)
    return o, lse


def _streamed_with_lse(q, k, v, causal, scale, block_k, window=None):
    """Online-softmax forward as a `lax.scan` over key blocks.

    Same math as the Pallas kernel, in plain jnp: peak temps are
    O(h * sq * block_k) instead of the dense path's O(h * sq * sk) — the
    memory-faithful any-backend stand-in for the kernel (used by the 8B
    AOT fit proof on the virtual CPU mesh)."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    bk = _fit_block(sk, min(block_k, 512)) or sk
    nb = sk // bk
    q32 = q.astype(jnp.float32)
    kb = jnp.moveaxis(k.reshape(b, h, nb, bk, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nb, bk, dv), 2, 0)
    rows = jnp.arange(sq) + (sk - sq)  # absolute key index each row sees

    def body(carry, inp):
        acc, m, l = carry
        j, kblk, vblk = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       kblk.astype(jnp.float32)) * scale
        mask = None
        if causal:
            cols = j * bk + jnp.arange(bk)
            mask = _kept_at(rows, cols, window)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if mask is not None:
            # a fully-masked row has m_new == NEG_INF and exp(s - m_new)
            # == 1 for its masked entries — zero them so l stays 0 and
            # the l>0 guard below yields out=0 / lse=-inf (matching the
            # dense reference; sq > sk rows exercise this)
            p = jnp.where(mask, p, 0.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (acc, m_new, l), None

    init = (jnp.zeros((b, h, sq, dv), jnp.float32),
            jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(body, init, (jnp.arange(nb), kb, vb))
    l_safe = jnp.where(l > 0, l, 1.0)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = jnp.where(l > 0, m + jnp.log(l_safe), -jnp.inf)
    return out, lse


def _streamed_bwd(q, k, v, out, lse, g, causal, scale, block_q, glse,
                  window=None):
    """Flash-style recompute backward as one `lax.scan` over query blocks.

    Each step re-derives p for its q block from the stored lse, emits the
    block's dq, and accumulates dk/dv — peak temps O(h * block_q * sk)."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    bq = _fit_block(sq, min(block_q, 512)) or sq
    nb = sq // bq
    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
    g32 = g.astype(jnp.float32)
    delta = (g32 * out.astype(jnp.float32)).sum(-1)  # (b, h, sq)
    if glse is not None:
        delta = delta - glse
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    qb = jnp.moveaxis(q32.reshape(b, h, nb, bq, d), 2, 0)
    gb = jnp.moveaxis(g32.reshape(b, h, nb, bq, dv), 2, 0)
    lb = jnp.moveaxis(lse_safe.reshape(b, h, nb, bq), 2, 0)
    db = jnp.moveaxis(delta.reshape(b, h, nb, bq), 2, 0)
    cols = jnp.arange(sk)
    off = sk - sq

    def body(carry, inp):
        dk, dv = carry
        i, qblk, gblk, lseblk, dblk = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qblk, k32) * scale
        p = jnp.exp(s - lseblk[..., None])
        if causal:
            rows = i * bq + jnp.arange(bq) + off
            p = jnp.where(_kept_at(rows, cols, window), p, 0.0)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gblk, v32)
        ds = p * (dp - dblk[..., None])
        dqblk = jnp.einsum("bhqk,bhkd->bhqd", ds, k32) * scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qblk) * scale
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, gblk)
        return (dk, dv), dqblk

    init = (jnp.zeros((b, h, sk, d), jnp.float32),
            jnp.zeros((b, h, sk, dv), jnp.float32))
    (dk, dv), dqb = jax.lax.scan(
        body, init, (jnp.arange(nb), qb, gb, lb, db))
    dq = jnp.moveaxis(dqb, 0, 2).reshape(b, h, sq, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_impl(causal, sm_scale, block_q, block_k, res, g, glse,
                 window=None):
    """Shared backward; glse (b, h, sq) f32 or None folds the lse cotangent
    into delta (d lse / d s = p, so ds = p * (dp - delta + glse))."""
    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = _resolve_scale(sm_scale, d)
    window = _effective_window(window, causal, sk)
    if lse is not None and not _use_pallas(sq, sk, d, block_q, block_k):
        # streamed forward ran (lse present, kernels unavailable): its
        # recompute backward — NOT the dense path, which would undo the
        # memory bound the streamed path exists for
        return _streamed_bwd(q, k, v, out, lse, g, causal, scale,
                             block_q, glse, window)
    if lse is not None:  # pallas forward ran: pallas backward
        bq = _fit_block(sq, block_q)
        bk = _fit_block(sk, block_k)
        qf, kf, vf = _flat_padded(q, k, v)
        gf = _pad_head_dim(g.reshape(b * h, sq, -1), vf.shape[-1])
        glse_f = None if glse is None else glse.reshape(b * h, 1, sq)
        dq, dk, dv = _fa_backward_pallas(qf, kf, vf, out, lse,
                                         gf, causal, scale, bq, bk,
                                         interpret=False, glse=glse_f,
                                         window=window)
        return tuple(dx[:, :, :x.shape[-1]].reshape(x.shape).astype(x.dtype)
                     for dx, x in ((dq, q), (dk, k), (dv, v)))
    # jnp recompute fallback (matches _attention_reference numerics)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(kept_mask(sq, sk, window), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    g32 = g.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v32)
    delta = (g32 * out.astype(jnp.float32)).sum(-1, keepdims=True)
    if glse is not None:
        delta = delta - glse[..., None]
    ds = p * (dp - delta)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32)) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32)) * scale
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k,
            bwd_block_q=0, bwd_block_k=0, window=None):
    (out, _), res = _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k,
                                window)
    return out, res


def _fa_bwd(causal, sm_scale, block_q, block_k, bwd_block_q, bwd_block_k,
            window, res, g):
    return _fa_bwd_impl(causal, sm_scale, bwd_block_q or block_q,
                        bwd_block_k or block_k, res, g, None, window)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             sm_scale: Optional[float] = None,
                             block_q: int = 1024, block_k: int = 1024,
                             bwd_block_q: int = 0, bwd_block_k: int = 0,
                             window: Optional[int] = None):
    """Like `flash_attention` but also returns lse (b, h, sq) f32 — the
    building block for ring/blockwise attention where partial results over
    disjoint key sets merge by logsumexp weights.  Differentiable in both
    outputs (the lse cotangent folds into the delta term)."""
    (out, lse), _ = _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k,
                                window)
    return out, lse


def _fa_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                bwd_block_q=0, bwd_block_k=0, window=None):
    return _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k, window)


def _fa_lse_bwd(causal, sm_scale, block_q, block_k, bwd_block_q,
                bwd_block_k, window, res, gs):
    g, glse = gs
    return _fa_bwd_impl(causal, sm_scale, bwd_block_q or block_q,
                        bwd_block_k or block_k, res, g,
                        glse.astype(jnp.float32), window)


flash_attention_with_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


# ------------------------------------- the projections' own (b, s, h*d) layout


def attention_route(n_head: int, head_dim: int,
                    v_head_dim: Optional[int] = None) -> Tuple[str, int]:
    """Which layout a model's attention hands the kernels, from its
    shape alone: ("direct", heads a slab) when the heads fall on 128-lane
    slab boundaries of the projections' (b, s, h*d) output — a head is a
    slab or more (d % 128 == 0), or two heads of 64 share one and there
    is an even number of them; ("transposed", 0) otherwise: the
    (b, h, s, d) arrays of `flash_attention`.  `head_dim` is q's and
    k's; a `v_head_dim` of its own (latent attention's 192 beside 128:
    a head of a slab and a half beside one of a slab) is transposed
    whatever the two are, a slab having one width.

    The counter of this decision, as `causal_tile_count` is of the
    tiles: `projected_ok` asks it for the dispatcher, and
    tests/test_program_from_arguments.py pins it for the benchmark's
    cells."""
    if v_head_dim not in (None, head_dim):
        return "transposed", 0
    heads = mosaic.slab_heads(head_dim)
    # what the kernels are written for: a head a slab, or a PAIR on one
    if heads == 1 or (heads == 2 and n_head % 2 == 0):
        return "direct", heads
    return "transposed", 0


def kv_route(n_head: int, n_kv: int, head_dim: int) -> Tuple[str, int]:
    """How a direct call gets at the k and v of grouped heads, from its
    shape alone: ("indexed", rep) — `flash_attention_projected` takes k
    and v as their projections leave them, (b, s, n_kv*d), and the
    kernels' BlockSpecs hand query slab s slab s // rep of them — where
    a head is a slab (d % 128 == 0) or there is nothing to repeat
    (rep = n_head // n_kv = 1); ("repeated", rep) where two heads of 64
    share a slab: one kv head is HALF a slab there and both query heads
    of a slab want the same half, so the caller repeats k and v to
    (b, s, n_head*d) first, as every call off the direct route does.

    The counter of this decision, as `attention_route` is of the layout:
    `models/llama.LlamaAttention` asks it, the entry reads `rep` off its
    operands' shapes and refuses what this calls repeated, and
    tests/test_program_from_arguments.py pins it for the benchmark's
    cells; in a compiled step its witness is that no broadcast or copy
    of a (b, s, n_head*d) k or v stands before the kernels."""
    rep, rest = divmod(n_head, n_kv)
    if rest:
        raise ValueError(f"{n_kv} kv heads do not divide {n_head} heads")
    if rep == 1 or mosaic.slab_heads(head_dim) == 1:
        return "indexed", rep
    return "repeated", rep


_PROJECTED_BLOCK = 1024  # the direct calls' preferred block, q and keys


def projected_ok(n_head: int, head_dim: int, seq: int,
                 v_head_dim: Optional[int] = None, mesh=None) -> bool:
    """Whether `flash_attention_projected` takes a self-attention of this
    shape: the heads on slab boundaries (`attention_route`), the call on
    one of `_DIRECT_SITES` (`mesh` is the model config's), at a sequence
    a block fits (`_use_pallas`).  The one predicate of the direct
    route: `models/attention.goes_direct` adds only the config's
    `attn_impl`, and the entry itself refuses what this does."""
    return (attention_route(n_head, head_dim, v_head_dim)[0] == "direct"
            and mosaic.kernel_site(mesh) in _DIRECT_SITES
            and _use_pallas(seq, seq, head_dim, _PROJECTED_BLOCK,
                            _PROJECTED_BLOCK))


def _projected_slabs(proj, n_head: int) -> Tuple[_Slabs, int]:
    """(`_Slabs` of a direct call, its head size).  `proj` is (qkv,),
    one (b, s, 3*h*d) array of q, k and v side by side, or (q, k, v):
    q (b, s, h*d), k and v that or, under grouped heads, their own
    (b, s, n_kv*d) where `kv_route` says "indexed"."""
    lanes = proj[0].shape[-1] // (3 if len(proj) == 1 else 1)
    d = lanes // n_head
    route, heads = attention_route(n_head, d)
    if route != "direct":
        raise ValueError(
            f"{n_head} heads of {d} do not fall on slab boundaries: "
            f"take flash_attention on (b, h, s, d)")
    k_lanes, v_lanes = (x.shape[-1] for x in proj[1:]) \
        if len(proj) == 3 else (lanes, lanes)
    if k_lanes != v_lanes or k_lanes % d:
        raise ValueError(f"k {k_lanes} and v {v_lanes} lanes wide are no "
                         f"kv heads of {d}, as q's are")
    how, kv_rep = kv_route(n_head, k_lanes // d, d)
    if how != "indexed":
        raise ValueError(
            f"a kv head of {d} is no lane slab: repeat k and v to "
            f"{n_head} heads first")
    per_row = n_head // heads
    offsets = (0, per_row, 2 * per_row) if len(proj) == 1 else (0, 0, 0)
    return _Slabs(per_row, heads, lanes // per_row, offsets, kv_rep), d


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def flash_attention_projected(proj, n_head: int, causal: bool = True,
                              sm_scale: Optional[float] = None,
                              window: Optional[int] = None):
    """Attention on the projections' own layout: `proj` = (qkv,), one
    (b, s, 3*h*d) array (GPT-2's `c_attn` output, never split), or
    (q, k, v), (b, s, h*d) each → (b, s, h*d), which the output
    projection takes as it is.  Under grouped heads k and v stay their
    projections' own (b, s, n_kv*d) where `kv_route` says "indexed" (a
    head a slab): `rep` = h // n_kv is read off the operands' shapes and
    query slab s reads kv slab s // rep, nothing is repeated.  The
    cotangent comes back in the same form: dk and dv leave the kernels
    a query head and a group's `rep` are summed here, the sum the
    transpose of a repeat would run (in float32, rounded once).  No
    array is split, reshaped to heads or transposed on the way: the
    kernels' BlockSpecs index the slabs where they lie (`_Slabs`).
    `window` as `flash_attention` takes it.

    For calls `projected_ok` takes; any other raises ValueError (the
    caller asks first: `models/attention.attend_projected`)."""
    return _fa_projected_fwd(proj, n_head, causal, sm_scale, window)[0]


def _projected_operands(proj):
    return proj * 3 if len(proj) == 1 else proj


# A model's layers call the kernels with the same shapes and the same
# static plan: behind `jax.jit` the kernel body is traced to a jaxpr and
# lowered to Mosaic ONCE a step program, not once a layer (each is a
# few hundred equations walked in Python).  XLA inlines the calls, so
# the compiled step is the one it would have been.
_STATIC = ("causal", "sm_scale", "block_q", "block_k", "interpret", "slabs",
           "window")
_projected_forward = jax.jit(_fa_forward_pallas, static_argnames=_STATIC)
_projected_backward = jax.jit(_fa_backward_pallas, static_argnames=_STATIC)


def _projected_plan(proj, n_head, causal, sm_scale, window) -> dict:
    slabs, d = _projected_slabs(proj, n_head)
    seq = proj[0].shape[1]
    if not projected_ok(n_head, d, seq):
        raise ValueError(
            f"no direct kernel off the TPU or at a sequence of {seq}: "
            f"take flash_attention on (b, h, s, d)")
    block = _fit_block(seq, _PROJECTED_BLOCK)
    plan = dict(causal=causal, sm_scale=_resolve_scale(sm_scale, d),
                block_q=block, block_k=block, interpret=False, slabs=slabs)
    window = _effective_window(window, causal, seq)
    return plan if window is None else dict(plan, window=window)


def _fa_projected_fwd(proj, n_head, causal, sm_scale, window=None):
    o, lse = _projected_forward(
        *_projected_operands(proj),
        **_projected_plan(proj, n_head, causal, sm_scale, window))
    return o, (proj, o, lse)


def _fa_projected_bwd(n_head, causal, sm_scale, window, res, g):
    proj, o, lse = res
    grads = _projected_backward(
        *_projected_operands(proj), o, lse, g,
        **_projected_plan(proj, n_head, causal, sm_scale, window))
    if len(proj) == 1:  # c_attn's cotangent: dq, dk, dv side by side
        return ((jnp.concatenate(grads, axis=-1),),)
    if grads[1].ndim == 4:  # grouped heads: a kv head's is its group's sum
        grads = (grads[0],) + tuple(dx.sum(axis=1) for dx in grads[1:])
    return (tuple(grads),)


flash_attention_projected.defvjp(_fa_projected_fwd, _fa_projected_bwd)


def mha(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
        window: Optional[int] = None):
    """`flash_attention` on the (b, s, h, d) layout (flax convention): the
    transposed route, for callers whose heads do not fall on slab
    boundaries (`attention_route`) or that hold q, k and v by head
    already.  Its transposes to (b, h, s, d) and back are materialised
    copies of arrays whose minor dimension, at d = 64, is stored padded
    to 128 lanes; a model whose shape allows it goes through
    `flash_attention_projected` and pays none of them.
    """
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(qt, kt, vt, causal, sm_scale, window=window)
    return out.transpose(0, 2, 1, 3)
