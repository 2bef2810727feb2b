"""Grouped matrix products over rows sorted by group, for a buffer that
may be part empty.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

`lhs` (M, C) holds its groups' rows one group after another from row 0;
`group_sizes` (E,) says how many each has, and their sum may be LESS than
M: a chip that holds `E` of a layer's experts receives its rows into a
buffer sized for the worst case (`models/moe.py::grouped_experts`), and
the rows behind the last group belong to nobody.

Two routes compute it, chosen from what a call can observe (its shapes,
the mesh, the backend), never by a knob — `gmm_route` says it of one
product, `experts_route` ONCE of a layer: all its products and the
elementwise passes between them take the same route, because a pass
that leaves the tiles behind the held rows unwritten may never feed a
`lax.ragged_dot`, which reads them:

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
  What lies BETWEEN the products is `rows_map` (`dwt_rows_map_<name>`,
  one `pallas_call`): an elementwise `jax.numpy` function of one or
  more (M, c) buffers' row blocks — an activation, a gating product,
  the sum of two row gradients, a weighting by per-row operands (M, 1),
  per-row sums (M, 1) — over ceil(held rows / `_ROW_TILE`) row tiles,
  that dynamic number again the grid, the count of held rows by scalar
  prefetch.  Inside the last visited tile the rows behind the held ones
  are written as ZERO (a select on a row iota: a NaN goes no further),
  the tiles behind it are not visited.  Its backward pass is the same
  kernel over the function's own VJP (`jax.vjp` on the blocks, inside
  the body), so cotangents obey the same contract and no mask over the
  whole buffer is needed in either direction.  The blocks are widened
  to float32 in the kernel and each result is rounded once (Mosaic
  refuses a bfloat16 compare on a v5e; the compiler's fusion of the
  same lines also keeps float32 between its ops).  A pass costs the
  held share of the buffer's bytes plus a launch.
  The buffer such a layer fills from its head (`models/moe.dispatch`'s
  loop over the held rows) starts as `unwritten_rows`
  (`dwt_rows_unwritten`): (M, c) of HBM that no one has written.
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

Scopes: the caller's (`moe/experts`, for the combine's backward pair
`moe/combine`); the metadata's few integer ops and the custom calls,
forward, recomputed and backward, carry it.

Parity: reference `atorch/atorch/modules/moe/grouped_gemm_moe.py` (a
CUDA grouped GEMM); the design is megablox's (`jax.experimental.pallas.
ops.tpu.megablox`, Gale et al. 2022, arXiv:2211.15841) without its
contraction axis and its group offsets.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import (
    LANES, _compiler_params, _dot, _dot_c0, _dot_t, _out_struct,
    _reckoned_vmem)

_ROW_TILE = 256
_COLUMN_TILE = 1024  # result columns a grid step takes, where they tile
_VMEM_LIMIT = 96 * 1024 * 1024  # these kernels' own request of the compiler
_SITES = frozenset({"device"})  # M1 (ROADMAP) adds "manual", and the record


# ------------------------------------------------------------ the route

def _column_tile(n: int, most: int = _COLUMN_TILE) -> int:
    """The largest multiple of 128 that divides `n` and is at most
    `most`; `n` whole where there is none (a block's lane axis is a
    multiple of 128 or the array's own)."""
    for t in range(most - most % LANES, 0, -LANES):
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
    than the buffer — a static fact of the call), where the call runs
    on one of `_SITES` (`mesh` is the model config's), at a row count
    `_ROW_TILE` divides and blocks that fit VMEM; else "plain".
    The static counter of the decision, with the compiled step's count
    of `dwt_gmm*` / `dwt_tgmm` custom calls (tests/test_tpu_compile.py),
    as `ops/ssd.scan_route` is of the scan's."""
    (m, c), (e, _, n) = lhs_shape, rhs_shape
    if num_experts is None or e >= num_experts \
            or mosaic.kernel_site(mesh) not in _SITES:
        return "plain"
    if m % _ROW_TILE or _vmem_bytes(c, n) > _VMEM_LIMIT:
        return "plain"
    return "kernel"


def _map_vmem_bytes(blocks: Sequence[Tuple[int, int]], tile: int) -> int:
    """What `dwt_rows_map` holds at `blocks`, the (columns, bytes an
    entry) of its buffers and results: each double-buffered (a per-row
    one fills 128 lanes), and a float32 temporary a block and two
    besides."""
    lanes = [max(c, LANES) for c, _ in blocks]
    return 2 * tile * sum(n * size for n, (_, size) in zip(lanes, blocks)) \
        + (len(blocks) + 2) * tile * max(lanes) * 4


def experts_route(rows: int, weights: Sequence, num_experts: Optional[int],
                  mesh=None) -> str:
    """The ONE route of an expert layer's call, for its grouped products
    (`weights`: each an (E, C, N) array or its shape; a layer without a
    gate matrix may hand None for it) and the elementwise passes between
    them: "kernel" where `gmm_route` says so of EVERY product and the
    maps' blocks fit VMEM, else "plain".  A map leaves the tiles behind
    the held rows unwritten and `lax.ragged_dot` reads them, so the two
    may never disagree: `models/moe.py::grouped_experts` asks once and
    hands the answer to `grouped_matmul` and to `rows_map`."""
    shapes = [getattr(w, "shape", w) for w in weights if w is not None]
    routes = {gmm_route((rows, c), (e, c, n), num_experts, mesh)
              for e, c, n in shapes}
    widest = max(max(c, n) for _, c, n in shapes)
    # the widest map there is: three buffers in, two out, of four bytes
    if routes != {"kernel"} or \
            _map_vmem_bytes([(widest, 4)] * 5, _ROW_TILE) > _VMEM_LIMIT:
        return "plain"
    return "kernel"


def grouped_matmul(lhs: jax.Array, rhs, group_sizes: jax.Array,
                   route: str = "plain"):
    """lhs (M, C) x rhs (E, C, N) by group -> (M, N) in the operands'
    dtype, float32 accumulation, on `route` (`experts_route`'s answer for
    the layer; "plain" is `lax.ragged_dot` word for word).  `rhs` may be
    a tuple of weights: a tuple of products of the one `lhs`, whose
    backward pass sums their row gradients over the held tiles alone."""
    if route == "kernel":
        return _grouped_kernels(lhs, rhs, group_sizes)
    if route != "plain":
        raise ValueError(f"no route {route!r}")
    if isinstance(rhs, tuple):
        return tuple(jax.lax.ragged_dot(lhs, w, group_sizes) for w in rhs)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


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


def map_tiles(group_sizes: jax.Array, m: int,
              route: str) -> Tuple[jax.Array, jax.Array]:
    """(row tiles an elementwise pass of `route` walks, row tiles of the
    `m`-row buffer): the tiles that hold a held row (`dwt_rows_map`'s
    grid), or every tile where the pass is the compiler's fusion."""
    tiles = jnp.asarray(-(-m // _ROW_TILE), jnp.int32)
    if route == "plain":
        return tiles, tiles
    held = group_sizes.astype(jnp.int32).sum()
    return -(-held // _ROW_TILE), tiles


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


_params = functools.partial(_compiler_params, vmem_limit=_VMEM_LIMIT)


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


def _rows_map_kernel(held_ref, *refs, fn, tile, n_in):
    """One visited row tile: `fn` of the blocks, the rows at or behind
    the held rows written as zero whatever `fn` made of what lay there
    (a select: a NaN of an unwritten place goes no further)."""
    rows = pl.program_id(0) * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), 0)
    held = rows < held_ref[0]
    # in float32 whatever the buffers hold, each result rounded once (a
    # v5e's vector unit compares no bfloat16, Mosaic refuses one): what
    # the compiler's fusion of the same lines computes
    values = fn(*(ref[...].astype(jnp.float32) for ref in refs[:n_in]))
    for ref, value in zip(refs[n_in:], values):
        ref[...] = jnp.where(held, value, jnp.zeros_like(value)).astype(
            ref.dtype)


def _rows_map_pallas(held_rows, *buffers, fn, tile, interpret, alias=None):
    """`fn`, elementwise in the rows, over the row tiles that hold a held
    row.  `buffers`: arrays (M, c_i) in expert order, c_i = 1 a per-row
    operand; `fn` takes their (tile, c_i) blocks and returns a tuple of
    blocks (tile, n_j), n_j = 1 a per-row result (a sum over the
    columns); -> the tuple of (M, n_j) arrays.  Grid: ceil(held_rows /
    tile) steps, DYNAMIC, `held_rows` by scalar prefetch; behind it
    nothing is visited or written.  `alias` (i, j): result j is written
    over buffer i (same shape and dtype, dead after the call)."""
    m = buffers[0].shape[0]
    outs = jax.eval_shape(fn, *(jax.ShapeDtypeStruct((tile, b.shape[1]),
                                                     b.dtype)
                                for b in buffers))

    def block(like):
        return pl.BlockSpec((tile, like.shape[1]), lambda t, held: (t, 0))

    # What the kernel holds, and no more, and what a call costs where
    # every row is held (the compiler knows no dynamic grid).  With the
    # products' 96 MB, or with no estimate (a custom call then costs
    # nothing to the scheduler), the compiler stops staging a
    # neighbouring gather's 88 MB source in VMEM under this call: 4.2 ms
    # a gather where it takes 0.8 (PERF.md section 6, PR 38).
    blocks = [(x.shape[1], jnp.dtype(x.dtype).itemsize)
              for x in (*buffers, *outs)]
    vmem = _map_vmem_bytes(blocks, tile)
    return pl.pallas_call(
        functools.partial(_rows_map_kernel, fn=fn, tile=tile,
                          n_in=len(buffers)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(-(-held_rows // tile),),
            in_specs=[block(b) for b in buffers],
            out_specs=[block(o) for o in outs]),
        out_shape=[_out_struct((m, o.shape[1]), o.dtype, buffers[0])
                   for o in outs],
        # operand 0 is the prefetched scalar
        input_output_aliases={} if alias is None else {1 + alias[0]: alias[1]},
        compiler_params=_compiler_params(
            "arbitrary", vmem_limit=_reckoned_vmem(vmem)),
        cost_estimate=pl.CostEstimate(
            flops=m * sum(c for c, _ in blocks), transcendentals=0,
            bytes_accessed=m * sum(c * size for c, size in blocks)),
        interpret=interpret,
        name=f"dwt_rows_map_{fn.__name__.lstrip('_')}",
    )(held_rows.astype(jnp.int32).reshape(1), *buffers)


# static plan: behind `jax.jit` a kernel body is traced and lowered to
# Mosaic once a shape, not once a call (four layers, one trace)
_STATIC = ("tile", "columns", "interpret")
_gmm = jax.jit(_gmm_pallas, static_argnames=_STATIC + ("transposed",))
_tgmm = jax.jit(_tgmm_pallas, static_argnames=_STATIC + ("dtype",))
_rows_map = jax.jit(_rows_map_pallas,
                    static_argnames=("fn", "tile", "interpret", "alias"))


def _row_tile(rows: int, tile: Optional[int]) -> int:
    """The row tile of a kernel-route call (`tile` or `_ROW_TILE`), which
    has to divide the buffer's rows."""
    tile = tile or _ROW_TILE
    if rows % tile:
        raise ValueError(f"{rows} rows are no multiple of the row tile "
                         f"{tile}")
    return tile


def _add(a, b):
    return (a + b,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _products(lhs, weights, group_sizes, plan):
    """lhs x each of the tuple `weights` by group."""
    return tuple(_gmm(lhs, w, group_sizes, transposed=False, **dict(plan))
                 for w in weights)


def _products_fwd(lhs, weights, group_sizes, plan):
    return (_products(lhs, weights, group_sizes, plan),
            (lhs, weights, group_sizes))


def _products_bwd(plan, res, d_outs):
    lhs, weights, group_sizes = res
    d_outs = [d.astype(lhs.dtype) for d in d_outs]
    d_lhs = [_gmm(d, w, group_sizes, transposed=True, **dict(plan))
             for d, w in zip(d_outs, weights)]
    while len(d_lhs) > 1:
        # the sum autodiff would write over the whole buffer (`add_any`),
        # over the held tiles and in place
        d_lhs[:2] = _rows_map(
            group_sizes.sum(), *d_lhs[:2], fn=_add, alias=(0, 0),
            **{k: v for k, v in plan if k != "columns"})
    d_weights = tuple(_tgmm(lhs, d, group_sizes, dtype=jnp.dtype(w.dtype),
                            **dict(plan)) for d, w in zip(d_outs, weights))
    return d_lhs[0], d_weights, None


_products.defvjp(_products_fwd, _products_bwd)


def _grouped_kernels(lhs, rhs, group_sizes, tile=None, columns=_COLUMN_TILE,
                     interpret=False):
    """The kernel route whatever the layer's route says (the tests reach
    it in interpret mode off the chip, at any row tile that divides M);
    `rhs` one weight or a tuple of them, as `grouped_matmul` takes it."""
    plan = (("tile", _row_tile(lhs.shape[0], tile)), ("columns", columns),
            ("interpret", interpret))
    weights = rhs if isinstance(rhs, tuple) else (rhs,)
    outs = _products(lhs, tuple(w.astype(lhs.dtype) for w in weights),
                     group_sizes.astype(jnp.int32), plan)
    return outs if isinstance(rhs, tuple) else outs[0]


# -------------------------------------------- the passes between products

@functools.lru_cache(maxsize=None)
def _vjp_of(fn, n):
    """`fn`'s own VJP as a function of blocks: (n primals, the results'
    cotangents) -> the primals' cotangents.  Elementwise in the rows as
    `fn` is, so it runs in the same kernel; cached, so that a program's
    layers hand the kernel ONE function and trace it once."""
    def backward(*blocks):
        return jax.vjp(fn, *blocks[:n])[1](tuple(blocks[n:]))
    backward.__name__ = f"{fn.__name__}_bwd"
    return backward


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _mapped(fn, plan, alias, held_rows, *buffers):
    return tuple(_rows_map(held_rows, *buffers, fn=fn, alias=alias,
                           **dict(plan)))


def _mapped_fwd(fn, plan, alias, held_rows, *buffers):
    return (_mapped(fn, plan, alias, held_rows, *buffers),
            (held_rows, buffers))


def _mapped_bwd(fn, plan, alias, res, d_outs):
    held_rows, buffers = res
    n = len(buffers)
    # the first cotangent dies here: where it has the first buffer's
    # form, that buffer's cotangent is written over it
    d, b = d_outs[0], buffers[0]
    over = (n, 0) if (d.shape, d.dtype) == (b.shape, b.dtype) else None
    return (None, *_rows_map(held_rows, *buffers, *d_outs,
                             fn=_vjp_of(fn, n), alias=over, **dict(plan)))


_mapped.defvjp(_mapped_fwd, _mapped_bwd)


def _rows_map_kernels(fn, held_rows, *buffers, alias=None, tile=None,
                      interpret=False):
    """`rows_map` whatever the layer's route says, as `_grouped_kernels`
    is `grouped_matmul`'s."""
    plan = (("tile", _row_tile(buffers[0].shape[0], tile)),
            ("interpret", interpret))
    return _mapped(fn, plan, alias, held_rows, *buffers)


def rows_map(fn, held_rows: jax.Array, *buffers: jax.Array,
             alias: Optional[Tuple[int, int]] = None) -> Tuple[jax.Array, ...]:
    """The kernel route's elementwise pass over a share's row buffers
    (`dwt_rows_map_<fn's name>`): `fn(*blocks) -> (block, ...)` applied to
    the row tiles that hold one of the first `held_rows` rows and to no
    other.  `buffers` are (M, c_i) arrays in expert order, (M, 1) a
    per-row operand; a result block (tile, 1) is a per-row result (a sum
    over the columns).  Rows behind the held ones are written as zero
    inside the last visited tile and left unwritten behind it — in every
    result and, `fn`'s own VJP running through the same kernel, in every
    cotangent.  `alias` (i, j): result j is written over buffer i.  `fn`
    is a static argument: hand every layer the same object."""
    return _rows_map_kernels(fn, held_rows, *buffers, alias=alias)


def _unwritten_kernel(rows, like, interpret=False):
    """`unwritten_rows`, as `_rows_map_kernels` is `rows_map` (interpret
    mode hands back NaN where nothing was written)."""
    return pl.pallas_call(
        lambda like_ref, out_ref: None,
        out_shape=_out_struct((rows, like.shape[1]), like.dtype, like),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=0),
        interpret=interpret,
        name="dwt_rows_unwritten",
    )(like)


def unwritten_rows(rows: int, like: jax.Array) -> jax.Array:
    """(rows, like's width) of like's dtype in HBM that nobody has
    written (`dwt_rows_unwritten`, a kernel with no body): what a loop
    that fills the held rows' chunks in place starts from.  The buffer
    exists from where `like` does: the TPU compiler places one that
    waits for nothing (`jax.lax.empty`, a broadcast of zeros) a whole
    recomputed layer before its first use, alive all the while (1.5 GB
    of a step's live bytes at SmallThinker's shapes), and zeros cost a
    write of the buffer besides (1.35 ms for 1.0 GB: PERF.md section 7,
    PR 42)."""
    return _unwritten_kernel(rows, like)
