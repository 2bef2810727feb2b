"""Grouped matrix products over rows sorted by group, for a buffer that
may be part empty.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

`lhs` (M, C) holds its groups' rows one group after another from row 0;
`group_sizes` (E,) says how many each has, and their sum may be LESS than
M: a chip that holds `E` of a layer's experts receives its rows into a
buffer sized for the worst case (`models/moe.py::grouped_experts`), and
the rows behind the last group belong to nobody.

Two routes compute it, chosen by `gmm_route` from what a call can
observe (its shapes, the mesh, the backend), never by a knob:

- "kernel": Pallas (Mosaic) kernels behind one `jax.custom_vjp`.
  `dwt_gmm` is the product above, `dwt_gmm_t` the same kernel reading
  the weight transposed (the rows' gradient), `dwt_tgmm` the weights'
  gradient, lhs^T @ d_out by group.  What a grid step works on — which
  group, which tile of `_ROW_TILE` rows — is computed from
  `group_sizes` with `jax.numpy` (`group_visits`: dense comparisons, no
  sort and no loop), handed over by scalar prefetch, and
  the grid's row axis is that DYNAMIC number of visits: a tile behind
  the held rows is never visited, a tile two groups share is visited
  once a group under a row mask.  So a call costs what arrived, not
  what could arrive.  The contraction is taken whole (a step's product
  is one MXU pass over it, accumulated in float32 and rounded once);
  the result's columns are tiled where a multiple of 128 divides them,
  else taken whole (1856 = 14.5 lane tiles: whole, nothing is masked at
  an edge).  `dwt_tgmm` accumulates a group's visits in a float32 VMEM
  scratch and writes each (group, tile) once; both its operands are
  masked by SELECT, so nothing a foreign row holds (the compiler's
  kernels and these leave NaNs behind the held rows) reaches a sum.
  Rows of no group are left unwritten, as the compiler's grouped
  kernels leave them: the caller masks what it reads.
- "plain": `jax.lax.ragged_dot`, for which the TPU compiler has
  grouped-matmul kernels of its own that walk every row tile of the
  buffer.  Wherever the groups fill the buffer (a whole layer: `E ==
  num_experts`), off the TPU, on a mesh of several devices (a Mosaic
  kernel cannot be partitioned by GSPMD), at a row count the tile does
  not divide — and the tests' oracle.

What a v5e trace showed (jax 0.9.0; PERF.md section 6, PR 36): at the
hybrid cell's shapes, 98,304 rows of which ~6,800 are held in 8 groups,
the compiler's kernel takes 3.0-4.5 ms a product, most of it whatever
the groups hold (it walks all the row tiles); these take 0.49-0.59 ms,
the time of their ~35 visits at nine tenths of the MXU's peak, and grow
by a fifth of what the compiler's does with each further held row.  Row
tiles of 128 and 256 measure alike and 512 a fifth slower (the groups'
part-filled tiles); the columns' tile hardly matters, because a group's
weight tile stays in VMEM across its visits.  megablox's own kernels at
the same tiling match `dwt_gmm` and are a third slower in the weights'
gradient (a float32 transpose a step); a stand-alone jit of either adds
layout copies around the call that are as long as the product, so time
these from a trace (`tools/perf_probe.py gmm`), not with a host clock.

Scopes: the caller's (`moe/experts`); the metadata's few integer ops
and the custom calls, forward, recomputed and backward, carry it.

Parity: reference `atorch/atorch/modules/moe/grouped_gemm_moe.py` (a
CUDA grouped GEMM); the design is megablox's (`jax.experimental.pallas.
ops.tpu.megablox`, Gale et al. 2022, arXiv:2211.15841) without its
contraction axis and its group offsets.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot, _dot_c0, _dot_t, _on_tpu, _out_struct

_LANES = 128
_ROW_TILE = 256
_COLUMN_TILE = 1024  # result columns a grid step takes, where they tile
_VMEM_LIMIT = 96 * 1024 * 1024


# ------------------------------------------------------------ the route

def _column_tile(n: int, most: int = _COLUMN_TILE) -> int:
    """The largest multiple of 128 that divides `n` and is at most
    `most`; `n` whole where there is none (a block's lane axis is a
    multiple of 128 or the array's own)."""
    for t in range(most - most % _LANES, 0, -_LANES):
        if n % t == 0:
            return t
    return n


def _vmem_bytes(c: int, n: int) -> int:
    """What the larger of the kernels holds at a contraction of `c` and
    a result of `n` columns, at most (operands of four bytes):
    double-buffered blocks, the float32 product (`dwt_gmm`) or the
    scratch (`dwt_tgmm`)."""
    tn = _column_tile(n)
    gmm = 8 * (_ROW_TILE * c + c * tn + _ROW_TILE * tn) + 4 * _ROW_TILE * tn
    tc = _column_tile(c)
    tgmm = 8 * (_ROW_TILE * (tc + tn) + tc * tn) + 8 * tc * tn
    return max(gmm, tgmm)


def gmm_route(lhs_shape: Tuple[int, int], rhs_shape: Tuple[int, int, int],
              num_experts: Optional[int], mesh=None) -> str:
    """Which route `grouped_matmul` takes: "kernel" where the weights
    hold FEWER groups than the router names (`rhs_shape[0] <
    num_experts`: the only case in which the group sizes can sum to less
    than the buffer — a static fact of the call), on the TPU, on one
    device (`mesh` is the model config's, None or of size 1), at a row
    count `_ROW_TILE` divides and blocks that fit VMEM; else "plain".
    The static counter of the decision, with the compiled step's count
    of `dwt_gmm*` / `dwt_tgmm` custom calls (tests/test_tpu_compile.py),
    as `ops/ssd.scan_route` is of the scan's."""
    (m, c), (e, _, n) = lhs_shape, rhs_shape
    if num_experts is None or e >= num_experts or not _on_tpu():
        return "plain"
    if mesh is not None and mesh.size > 1:
        return "plain"
    if m % _ROW_TILE or _vmem_bytes(c, n) > _VMEM_LIMIT:
        return "plain"
    return "kernel"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   num_experts: Optional[int] = None,
                   mesh=None) -> jax.Array:
    """lhs (M, C) x rhs (E, C, N) by group -> (M, N) in the operands'
    dtype, float32 accumulation; `num_experts` is how many groups the
    rows were routed over (None: the E of `rhs`), `mesh` where the call
    runs."""
    if gmm_route(lhs.shape, rhs.shape, num_experts, mesh) == "plain":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _grouped_kernels(lhs, rhs, group_sizes)


# --------------------------------------------------------- the metadata

def group_visits(group_sizes: jax.Array, m: int, tile: int,
                 empty_groups: bool = False):
    """The grid's row axis from `group_sizes` (E,), for a buffer of `m`
    rows in tiles of `tile`: (offsets (E+1,), group of visit v, row tile
    of visit v, number of visits), all int32.  A group visits every tile
    that holds one of its rows, in order, groups in order; a tile two
    groups share is visited by both, one after the other; a tile that
    holds no group's row by none.  `empty_groups`: a group of no rows
    makes one visit all the same (`dwt_tgmm` writes its zeros there).
    The two index arrays have m // tile + E entries, the most there can
    be; behind the number of visits they repeat valid indices that no
    grid step reads.  Dense comparisons over (visits x E): no sort, no
    loop."""
    sizes = group_sizes.astype(jnp.int32)
    e, tiles = sizes.shape[0], m // tile
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    count = jnp.where(sizes > 0, (ends - 1) // tile - first + 1,
                      1 if empty_groups else 0)
    visit_ends = jnp.cumsum(count)
    v = jnp.arange(tiles + e, dtype=jnp.int32)
    group = jnp.minimum(
        (v[:, None] >= visit_ends[None, :]).sum(-1), e - 1).astype(jnp.int32)
    row_tile = jnp.clip(first[group] + v - (visit_ends - count)[group],
                        0, tiles - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, row_tile, visit_ends[-1]


def row_tiles(group_sizes: jax.Array, m: int,
              route: str) -> Tuple[jax.Array, jax.Array]:
    """(row tiles a grouped product of `route` walks, row tiles of the
    `m`-row buffer), from `group_sizes` alone: the kernels' visits, or
    every tile where the compiler's kernels run."""
    tiles = jnp.asarray(-(-m // _ROW_TILE), jnp.int32)
    if route == "plain":
        return tiles, tiles
    return group_visits(group_sizes, m, _ROW_TILE)[3], tiles


# ---------------------------------------------------------- the kernels

def _own_rows(offsets_ref, group, row_tile, tile):
    """(tile, 1) bool: which rows of this visit's tile are the group's."""
    rows = row_tile * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), 0)
    return (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])


def _gmm_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                *, tile, transposed):
    v = pl.program_id(1)
    own = _own_rows(offsets_ref, group_ref[v], tile_ref[v], tile)
    product = (_dot_t if transposed else _dot)(lhs_ref[...], rhs_ref[...])
    # a tile two groups share stays in VMEM between their visits (they
    # are consecutive): the earlier group's rows are kept
    out_ref[...] = jnp.where(own, product.astype(out_ref.dtype),
                             out_ref[...])


def _tgmm_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tile):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[v]

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group))
    def _groups_first_visit():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets_ref[group + 1] > offsets_ref[group])
    def _add():
        own = _own_rows(offsets_ref, group, tile_ref[v], tile)
        lhs, rhs = lhs_ref[...], rhs_ref[...]
        acc_ref[...] += _dot_c0(jnp.where(own, lhs, jnp.zeros_like(lhs)),
                                jnp.where(own, rhs, jnp.zeros_like(rhs)))

    @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != group))
    def _groups_last_visit():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _gmm_pallas(lhs, rhs, group_sizes, *, transposed, tile, columns,
                interpret):
    """lhs (M, C) x rhs (E, C, N) — (E, N, C) read transposed — by group
    -> (M, N).  Grid: (column tiles, visits); the weight tile of a group
    is fetched once for its consecutive visits."""
    m, c = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tn = _column_tile(n, columns)
    plan = group_visits(group_sizes, m, tile)
    weight = (pl.BlockSpec((None, tn, c), lambda j, v, o, g, t: (g[v], j, 0))
              if transposed else
              pl.BlockSpec((None, c, tn), lambda j, v, o, g, t: (g[v], 0, j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile=tile, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, plan[3]),
            in_specs=[
                pl.BlockSpec((tile, c), lambda j, v, o, g, t: (t[v], 0)),
                weight],
            out_specs=pl.BlockSpec((tile, tn),
                                   lambda j, v, o, g, t: (t[v], j))),
        out_shape=_out_struct((m, n), lhs.dtype, lhs),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="dwt_gmm_t" if transposed else "dwt_gmm",
    )(*plan[:3], lhs, rhs)


def _tgmm_pallas(lhs, rhs, group_sizes, *, dtype, tile, columns, interpret):
    """lhs (M, C)^T x rhs (M, N) by group -> (E, C, N) in `dtype`.  Grid:
    (tiles of C, tiles of N, visits), a group's visits consecutive."""
    m, c = lhs.shape
    n = rhs.shape[1]
    tc, tn = _column_tile(c, columns), _column_tile(n, columns)
    e = group_sizes.shape[0]
    plan = group_visits(group_sizes, m, tile, empty_groups=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(c // tc, n // tn, plan[3]),
            in_specs=[
                pl.BlockSpec((tile, tc), lambda i, j, v, o, g, t: (t[v], i)),
                pl.BlockSpec((tile, tn), lambda i, j, v, o, g, t: (t[v], j))],
            out_specs=pl.BlockSpec((None, tc, tn),
                                   lambda i, j, v, o, g, t: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tc, tn), jnp.float32)]),
        out_shape=_out_struct((e, c, n), dtype, lhs),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="dwt_tgmm",
    )(*plan[:3], lhs, rhs)


# static plan: behind `jax.jit` a kernel body is traced and lowered to
# Mosaic once a shape, not once a call (four layers, one trace)
_STATIC = ("tile", "columns", "interpret")
_gmm = jax.jit(_gmm_pallas, static_argnames=_STATIC + ("transposed",))
_tgmm = jax.jit(_tgmm_pallas, static_argnames=_STATIC + ("dtype",))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _products(lhs, rhs, group_sizes, plan):
    return _gmm(lhs, rhs, group_sizes, transposed=False, **dict(plan))


def _products_fwd(lhs, rhs, group_sizes, plan):
    return _products(lhs, rhs, group_sizes, plan), (lhs, rhs, group_sizes)


def _products_bwd(plan, res, d_out):
    lhs, rhs, group_sizes = res
    d_out = d_out.astype(lhs.dtype)
    d_lhs = _gmm(d_out, rhs, group_sizes, transposed=True, **dict(plan))
    d_rhs = _tgmm(lhs, d_out, group_sizes, dtype=jnp.dtype(rhs.dtype),
                  **dict(plan))
    return d_lhs, d_rhs, None


_products.defvjp(_products_fwd, _products_bwd)


def _grouped_kernels(lhs, rhs, group_sizes, tile=None, columns=_COLUMN_TILE,
                     interpret=False):
    """The kernel route whatever `gmm_route` says (the tests reach it in
    interpret mode off the chip, at any row tile that divides M)."""
    tile = tile or _ROW_TILE
    if lhs.shape[0] % tile:
        raise ValueError(f"{lhs.shape[0]} rows are no multiple of the row "
                         f"tile {tile}")
    plan = (("tile", tile), ("columns", columns), ("interpret", interpret))
    return _products(lhs, rhs.astype(lhs.dtype),
                     group_sizes.astype(jnp.int32), plan)
