"""The chunked scan of a Mamba-2 layer (the state-space dual form).

The recurrence, per head (state S in R^{P x N}, S_0 = 0; Dao & Gu 2024,
arXiv:2405.21060):

    S_t = exp(dlt_t * A) * S_{t-1} + dlt_t * x_t (x) B_t
    y_t = S_t C_t + D * x_t

computed in chunks of L steps.  With a_t = dlt_t * A and cum_t the
running sum of a inside a chunk:

- within a chunk, y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s)
  dlt_s x_s: one masked (L x L) product a head, on the MXU;
- a chunk's own state, sum_s exp(cum_L - cum_s) dlt_s x_s (x) B_s, and
  between chunks the carried state S <- exp(cum_L) S + (that);
- the carried state's part of y_t: exp(cum_t) C_t . S_in.

The decays (`a`, its sums, every `exp`) and the carried state are float32
whatever `dtype` says; `dtype` is what the matrix products' operands are
rounded to (accumulation is float32); C B^T o decay is formed in float32
and rounded once; the mask is applied BEFORE the exp.

Two routes compute it, chosen by `scan_route` from what a call can
observe (its shapes, where it runs), never by a knob:

- "kernel": a pair of Pallas (Mosaic) kernels, `dwt_ssd_fwd` and
  `dwt_ssd_bwd`, behind one `jax.custom_vjp`.  A grid step is one
  (batch row, chunk, block of heads); the chunks are walked in order
  (in reverse by the backward kernel) and the carried state, (N x P)
  float32 a head, lives in a VMEM scratch, so the within-chunk product,
  the chunk's own state, the carry and the entering state's part of y
  are one kernel.  The (L x L) decay of a head, C B^T, their product and
  — backward — their cotangents are built, used and differentiated in
  VMEM: no array with two chunk-length axes is an operand or a result.
  The heads of a group share B and C, so their own / entering-state
  products run side by side as one wide product; the masked product is
  a head's own, the heads of a 128-lane slab told apart by lane masks.
  What stays `jax.numpy` around the kernels are T x H numbers (the
  running sums, their layouts by column and by row) and the transposes
  of B and C; JAX differentiates those.
- "plain": `jax.numpy` einsums, the backward pass their differentiation,
  the state entering a chunk as one (chunks x chunks) product.  Off the
  TPU, on a mesh of several devices (a Mosaic kernel cannot be
  partitioned by GSPMD; `_SITES`), at shapes the kernels do not take —
  and the tests' oracle.

`benchmark/`'s `kernel.ssd_roofline` counts the RECURRENCE's work from
shapes, whatever computes it.

Scopes (under the caller's): `ssd` around all of it; the kernels'
custom calls, forward, recomputed and backward, carry it.

Parity: none — the reference (atorch's modules and kernels) has no
state-space layer; this is the paper's algorithm.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import (
    LANES, _compiler_params, _dot, _dot_c0, _dot_t, _einsum, _iota,
    _out_struct, _put)


# ------------------------------------------------------------ the route

_BLOCK_LANES = 1024  # lanes of x a grid step takes: 16 heads of 64
_VMEM_LIMIT = 64 * 1024 * 1024  # this kernel's own request of the compiler
_SITES = frozenset({"device"})  # S9 (ROADMAP) adds "manual", and the record


def _heads_block(r: int, p: int) -> int:
    """The block of heads a grid step takes: whole lane slabs of one
    group, `_BLOCK_LANES` lanes of x where the group has them.  Measured
    at granite's shape (PERF.md section 6, PR 34): 4 / 8 / 16 heads a
    step run a layer's two forwards and backward in 4.7 / 4.0 / 3.5 ms,
    32 and 64 within 4% of 16 at two and four times the unrolled code."""
    s = mosaic.slab_heads(p)
    if not s or r % s:
        return 0
    hb = s
    while hb * 2 * p <= _BLOCK_LANES and r % (hb * 2) == 0:
        hb *= 2
    return hb


def _vmem_bytes(h: int, p: int, n: int, chunk: int, hb: int) -> int:
    """What the backward kernel (the larger) holds: double-buffered
    blocks, the carried state of every head, (L x L) tiles."""
    wide = chunk * hb * p * 4
    return (2 * 5 * wide + 4 * wide          # x, dy, dx, state blocks; temps
            + 2 * h * p * n * 4              # carried state and its block
            + 8 * chunk * chunk * 4          # C B^T both ways, sums, a head's
            + 2 * 6 * chunk * n * 4)         # B, C, their transposes, dB, dC


def scan_route(h: int, p: int, g: int, n: int, chunk: int, t: int,
               mesh=None) -> Tuple[str, int]:
    """Which route `ssd_scan` takes at these shapes: ("kernel", heads a
    grid step) where the call runs on one of `_SITES` (`mesh` is the
    mixer config's) when the chunk is a multiple of 128 (a chunk is the
    lane axis of the decay tile), the state size too (the lane axis of B
    and C), the heads fall on 128-lane slabs of x and a block fits VMEM;
    else ("plain", 0).  The static counter of the decision (with the
    compiled step's count of `dwt_ssd_*` custom calls), as
    `ops/flash_attention.attention_route` is of the attention's; pinned
    by tests/test_program_from_arguments.py for the benchmark's cells."""
    if mosaic.kernel_site(mesh) not in _SITES or t % chunk or h % g:
        return "plain", 0
    hb = _heads_block(h // g, p)
    if not hb or chunk % LANES or n % LANES:
        return "plain", 0
    if _vmem_bytes(h, p, n, chunk, hb) > _VMEM_LIMIT:
        return "plain", 0
    return "kernel", hb


# ------------------------------------------------------------ the scan

@jax.named_scope("ssd")
def ssd_scan(x, dlt, a, b_mat, c_mat, d_skip, chunk: int = 128,
             dtype=jnp.float32, mesh=None):
    """x (b, T, H, P); dlt (b, T, H), the step sizes AFTER softplus;
    a (H,), negative; b_mat, c_mat (b, T, G, N), head h using group
    h // (H/G); d_skip (H,); `mesh` the mixer config's.  Returns y
    (b, T, H, P) in float32.

    T must be a multiple of `chunk`: a ragged last chunk would need a
    padded copy of every operand, and no caller has one."""
    _check(x, b_mat, chunk)
    route, hb = scan_route(x.shape[2], x.shape[3], *b_mat.shape[2:], chunk,
                           x.shape[1], mesh)
    if route == "kernel":
        return _scan_kernels(x, dlt, a, b_mat, c_mat, d_skip, chunk, dtype,
                             hb)
    return _scan_plain(x, dlt, a, b_mat, c_mat, d_skip, chunk, dtype)


@jax.named_scope("ssd")
def ssd_scan_plain(x, dlt, a, b_mat, c_mat, d_skip, chunk: int = 128,
                   dtype=jnp.float32):
    """`ssd_scan` on the plain route whatever the shapes and the site:
    the tests' oracle."""
    _check(x, b_mat, chunk)
    return _scan_plain(x, dlt, a, b_mat, c_mat, d_skip, chunk, dtype)


def _check(x, b_mat, chunk):
    t, h = x.shape[1:3]
    g = b_mat.shape[2]
    if t % chunk:
        raise ValueError(f"ssd_scan: sequence {t} is no multiple of the "
                         f"chunk {chunk}")
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads do not divide into {g} "
                         f"groups")


def _scan_plain(x, dlt, a, b_mat, c_mat, d_skip, chunk, dtype):
    bsz, t, h, p = x.shape
    g, n = b_mat.shape[2:]
    c, r = t // chunk, h // g
    x32 = x.astype(jnp.float32)
    dlt = dlt.astype(jnp.float32)
    # (b, chunks, L, G, R, ...): a head is (group, rank in group)
    xs = x32.reshape(bsz, c, chunk, g, r, p)
    dl = dlt.reshape(bsz, c, chunk, g, r)
    bm = b_mat.reshape(bsz, c, chunk, g, n)
    cm = c_mat.reshape(bsz, c, chunk, g, n)
    cum = jnp.cumsum(dl * a.astype(jnp.float32).reshape(g, r), axis=2)
    xdt = xs * dl[..., None]                          # dlt_s x_s

    # within a chunk: decay[t, s] = exp(cum_t - cum_s) for s <= t, else 0
    # (the masked entries' differences are positive: mask BEFORE exp);
    # the (L x L) pair is minor, the heads are batch dimensions
    cum_h = jnp.moveaxis(cum, 2, -1)                  # (b, c, G, R, L)
    diff = cum_h[..., :, None] - cum_h[..., None, :]  # (b, c, G, R, t, s)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)),
                              diff, -jnp.inf))
    cb = _einsum("bctgn,bcsgn->bcgts", cm, bm, dtype=dtype)
    y = _einsum("bcgrts,bcsgrp->bctgrp", cb[:, :, :, None] * decay, xdt,
                dtype=dtype)

    # a chunk's own state, and the state carried from chunk to chunk
    total = cum[:, :, -1]                             # (b, c, G, R)
    to_end = jnp.exp(total[:, :, None] - cum)         # (b, c, L, G, R)
    own = _einsum("bcsgn,bcsgrp->bcgrpn", bm, xdt * to_end[..., None],
                  dtype=dtype)

    # the state ENTERING chunk j: sum over i < j of own_i decayed by the
    # totals of the chunks between them, one (chunks x chunks) product a
    # head in float32 at full precision, where a loop over the chunks
    # would be a `while` in the compiled step (an op that holds others)
    low = jnp.tril(jnp.ones((c, c), bool), -1)
    tot_h = jnp.moveaxis(total, 1, -1)                # (b, G, R, c)
    between = jnp.cumsum(                             # [j', i]: i < m <= j'
        jnp.where(low, tot_h[..., :, None], 0.0), axis=-2)
    carried = jnp.exp(jnp.where(low, jnp.roll(between, 1, axis=-2),
                                -jnp.inf))           # [j, i]: i < m < j
    entering = jnp.einsum("bgrji,bigrpn->bjgrpn", carried, own,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + _einsum("bctgn,bcgrpn->bctgrp", cm, entering, dtype=dtype) \
        * jnp.exp(cum)[..., None]
    y = y + xs * d_skip.astype(jnp.float32).reshape(g, r, 1)
    return y.reshape(bsz, t, h, p)


# ------------------------------------------------------------ the kernels
#
# Layouts.  x, y, dy, dx: (b, T, H*P), a block (L, hb*P) — the mixer's
# own layout, nothing transposed.  B, C: (b, T, G*N), a block (L, N);
# their transposes (b, G, N, T), a block (N, L).  The T x H numbers come
# twice, so that no kernel transposes a vector: BY COLUMN (b, H/hb, T,
# hb), time on sublanes, and BY ROW (b, H/hb, hb, T), time on lanes.
# The carried state is kept transposed, (N, hb*P) a block of heads: B^T
# (N, L) @ (dlt x o to_end) (L, hb*P) fills it, C (L, N) @ it is the
# entering state's part of y, every head of the block in one product.

def _spread(cols, lane_head):
    """s columns (L, 1), one a head of a slab -> (L, W): each over its
    own head's lanes (a head a slab: the column itself)."""
    out = cols[-1]
    for i in range(len(cols) - 2, -1, -1):
        out = jnp.where(lane_head == i, cols[i], out)
    return out


def _last_row(v):
    """v[-1:] as a masked sum (exact: one term is not zero): a slice at
    sublane L-1 of a column spread over lanes is a broadcast Mosaic does
    not take at a head a slab."""
    rows = _iota((v.shape[0], 1), 0)
    return jnp.sum(jnp.where(rows == v.shape[0] - 1, v, 0.0), axis=0,
                   keepdims=True)


def _own(v, lane_head, i, s):
    """v (.., W) with every lane of another head of the slab zeroed."""
    return v if s == 1 else jnp.where(lane_head == i, v, 0.0)


def _slab(refs, u, w, s, lane_head):
    """A slab's operands in float32: x (L, W), and dlt, cum spread over
    their heads' lanes; the columns of cum by head."""
    x_ref, dl_ref, cc_ref = refs
    sl = slice(u * w, (u + 1) * w)
    heads = range(u * s, (u + 1) * s)
    ccs = [cc_ref[:, h:h + 1] for h in heads]
    dl_w = _spread([dl_ref[:, h:h + 1] for h in heads], lane_head)
    cc_w = _spread(ccs, lane_head)
    return sl, heads, ccs, x_ref[:, sl].astype(jnp.float32), dl_w, cc_w


def _ssd_fwd_kernel(x_ref, dl_ref, cc_ref, cr_ref, b_ref, bt_ref, c_ref,
                    d_ref, y_ref, *rest, p, s, nbg, dtype, save):
    st_ref = rest[0] if save else None
    s_scr, cb_scr, w_scr = rest[-3:]
    k, j = pl.program_id(1), pl.program_id(2)
    chunk, hb = dl_ref.shape
    w = s * p
    lane_head = _iota((1, w), 1) // p

    @pl.when(k == 0)
    def _first_chunk():
        s_scr[j] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    cm = c_ref[...]

    @pl.when(j % nbg == 0)
    def _groups_first_block():
        cb_scr[...] = _dot_t(cm, b_ref[...])          # [t, s], float32

    cb = cb_scr[...]
    s_in = s_scr[j]                                    # (N, hb*P)
    if save:
        st_ref[...] = s_in
    y_in = _dot(cm, s_in.astype(dtype))                # (L, hb*P)
    tril = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
    for u in range(hb // s):
        sl, heads, ccs, xs, dl_w, cc_w = _slab(
            (x_ref, dl_ref, cc_ref), u, w, s, lane_head)
        xdt = xs * dl_w
        tot_w = _last_row(cc_w)
        w_scr[:, sl] = (xdt * jnp.exp(tot_w - cc_w)).astype(dtype)
        y = None
        for i, h in enumerate(heads):
            decay = jnp.exp(jnp.where(tril, ccs[i] - cr_ref[h:h + 1, :],
                                      -jnp.inf))
            part = _dot((cb * decay).astype(dtype),
                        _own(xdt, lane_head, i, s).astype(dtype))
            y = part if y is None else y + part
        y_ref[:, sl] = y + y_in[:, sl] * jnp.exp(cc_w) + xs * d_ref[:, sl]
        s_scr[j, :, sl] = s_in[:, sl] * jnp.exp(tot_w)
    s_scr[j] += _dot(bt_ref[...], w_scr[...])


def _ssd_bwd_kernel(x_ref, dl_ref, cc_ref, cr_ref, b_ref, c_ref, ct_ref,
                    d_ref, st_ref, dy_ref,
                    dx_ref, ddl_ref, dcc_ref, dcr_ref, db_ref, dc_ref,
                    dd_ref, ds_scr, cb_scr, cbt_scr, dcb_scr, w_scr, g_scr,
                    *, p, s, nbg, dtype):
    k, j = pl.program_id(1), pl.program_id(2)
    chunk, hb = dl_ref.shape
    w = s * p
    lane_head = _iota((1, w), 1) // p

    @pl.when(k == 0)
    def _last_chunk():
        ds_scr[j] = jnp.zeros(ds_scr.shape[1:], jnp.float32)
        dd_ref[j] = jnp.zeros(dd_ref.shape[1:], jnp.float32)

    bm, cm = b_ref[...], c_ref[...]

    @pl.when(j % nbg == 0)
    def _groups_first_block():
        cb_scr[...] = _dot_t(cm, bm)                   # [t, s]
        cbt_scr[...] = _dot_t(bm, cm)                  # [s, t]
        dcb_scr[...] = jnp.zeros(dcb_scr.shape, jnp.float32)

    cb, cbt = cb_scr[...], cbt_scr[...]
    s_in = st_ref[...]                                 # (N, hb*P)
    sb = s_in.astype(dtype)
    dso = ds_scr[j]            # cotangent of the state LEAVING the chunk
    dsob = dso.astype(dtype)
    dw_all = _dot(bm, dsob)                            # (L, hb*P)
    y_in = _dot(cm, sb)
    tril = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
    triu = _iota((chunk, chunk), 0) <= _iota((chunk, chunk), 1)
    last = _iota((chunk, 1), 0) == chunk - 1
    col_head, row_head = _iota((1, hb), 1), _iota((hb, 1), 0)
    ddl = jnp.zeros((chunk, hb), jnp.float32)
    dcc = jnp.zeros((chunk, hb), jnp.float32)
    dcr = jnp.zeros((hb, chunk), jnp.float32)
    dcb = dcb_scr[...]
    for u in range(hb // s):
        sl, heads, ccs, xs, dl_w, cc_w = _slab(
            (x_ref, dl_ref, cc_ref), u, w, s, lane_head)
        xdt = xs * dl_w
        tot_w = _last_row(cc_w)
        te_w, ec_w, et_w = (jnp.exp(tot_w - cc_w), jnp.exp(cc_w),
                            jnp.exp(tot_w))
        w_scr[:, sl] = (xdt * te_w).astype(dtype)
        dy = dy_ref[:, sl]
        g_scr[:, sl] = (dy * ec_w).astype(dtype)
        dw = dw_all[:, sl]
        # d(cum) by column through exp(cum) and through to_end; the
        # second also reaches the chunk's total, with the carry's decay
        through_ec = dy * (y_in[:, sl] * ec_w)
        through_te = dw * xdt * te_w
        through_et = jnp.sum(dso[:, sl] * s_in[:, sl], axis=0,
                             keepdims=True) * et_w     # (1, W)
        dxdt = dw * te_w
        xb = xdt.astype(dtype)
        for i, h in enumerate(heads):
            cr = cr_ref[h:h + 1, :]
            decay = jnp.exp(jnp.where(tril, ccs[i] - cr, -jnp.inf))
            decay_t = jnp.exp(jnp.where(triu, cr - ccs[i], -jnp.inf))
            dyb = _own(dy, lane_head, i, s).astype(dtype)
            dm = _dot_t(dyb, xb) * decay               # d(C B^T), [t, s]
            dcb = dcb + dm
            q = dm * cb                                # d(cum_t - cum_s)
            dxdt = dxdt + _dot((cbt * decay_t).astype(dtype), dyb)
            te_sum = jnp.sum(_own(through_te, lane_head, i, s), axis=1,
                             keepdims=True)            # (L, 1)
            d_total = jnp.sum(te_sum, axis=0, keepdims=True) + jnp.sum(
                _own(through_et, lane_head, i, s), axis=1, keepdims=True)
            d_col = jnp.sum(q, axis=1, keepdims=True) - te_sum + jnp.sum(
                _own(through_ec, lane_head, i, s), axis=1, keepdims=True)
            d_col = d_col + jnp.where(last, d_total, 0.0)
            dcc = _put(dcc, col_head, h, d_col)
            dcr = _put(dcr, row_head, h, -jnp.sum(q, axis=0, keepdims=True))
        for i, h in enumerate(heads):
            ddl = _put(ddl, col_head, h, jnp.sum(
                _own(dxdt * xs, lane_head, i, s), axis=1, keepdims=True))
        dx_ref[:, sl] = (dxdt * dl_w + dy * d_ref[:, sl]).astype(
            dx_ref.dtype)
        dd_ref[j, :, sl] += jnp.sum(dy * xs, axis=0, keepdims=True)
        ds_scr[j, :, sl] = dso[:, sl] * et_w
    ddl_ref[...], dcc_ref[...], dcr_ref[...] = ddl, dcc, dcr
    dcb_scr[...] = dcb
    g_all, w_all = g_scr[...], w_scr[...]
    ds_scr[j] += _dot(ct_ref[...], g_all)
    dc_part = _dot_t(g_all, sb)                        # (L, N)
    db_part = _dot_t(w_all, dsob)

    @pl.when(j % nbg == 0)
    def _start():
        dc_ref[...] = dc_part
        db_ref[...] = db_part

    @pl.when(j % nbg != 0)
    def _add():
        dc_ref[...] += dc_part
        db_ref[...] += db_part

    @pl.when(j % nbg == nbg - 1)
    def _groups_last_block():
        dcbb = dcb.astype(dtype)
        dc_ref[...] += _dot(dcbb, bm)
        db_ref[...] += _dot_c0(dcbb, cm)


_PARAMS = _compiler_params("parallel", "arbitrary", "arbitrary",
                           vmem_limit=_VMEM_LIMIT)


def _specs(chunk, hb, p, n, nb, nbg, at):
    """BlockSpecs by operand kind; `at(k)` is the chunk a grid step
    works on (the backward kernel walks them in reverse)."""
    wide = hb * p
    return dict(
        x=pl.BlockSpec((None, chunk, wide), lambda b, k, j: (b, at(k), j)),
        col=pl.BlockSpec((None, None, chunk, hb),
                         lambda b, k, j: (b, j, at(k), 0)),
        row=pl.BlockSpec((None, None, hb, chunk),
                         lambda b, k, j: (b, j, 0, at(k))),
        bc=pl.BlockSpec((None, chunk, n),
                        lambda b, k, j: (b, at(k), j // nbg)),
        bc_t=pl.BlockSpec((None, None, n, chunk),
                          lambda b, k, j: (b, j // nbg, 0, at(k))),
        d=pl.BlockSpec((1, wide), lambda b, k, j: (0, j)),
        # resident over a batch row's chunks and blocks: a sum over time
        dd=pl.BlockSpec((None, nb, 1, wide), lambda b, k, j: (b, 0, 0, 0)),
        state=pl.BlockSpec((None, None, n, wide),
                           lambda b, k, j: (b, at(k), 0, j)))


def _ssd_forward_pallas(x, dl_col, cc, cr, bm, bt, cm, d_vec, *, chunk, p,
                        hb, dtype, save, interpret):
    """y (b, T, H*P) float32 and, with `save`, the state ENTERING every
    chunk, (b, chunks, N, H*P) float32, for the backward kernel."""
    bsz, t, hp = x.shape
    n, g = bt.shape[2], bt.shape[1]
    c, nb = t // chunk, hp // (hb * p)
    nbg = nb // g
    sp = _specs(chunk, hb, p, n, nb, nbg, lambda k: k)
    out_shape = [_out_struct((bsz, t, hp), jnp.float32, x)]
    out_specs = [sp["x"]]
    if save:
        out_shape.append(_out_struct((bsz, c, n, hp), jnp.float32, x))
        out_specs.append(sp["state"])
    out = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, p=p, s=mosaic.slab_heads(p) or 1,
                          nbg=nbg, dtype=dtype, save=save),
        grid=(bsz, c, nb),
        in_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["bc"],
                  sp["bc_t"], sp["bc"], sp["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nb, n, hb * p), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, hb * p), dtype)],
        compiler_params=_PARAMS, interpret=interpret,
        name="dwt_ssd_fwd",
    )(x, dl_col, cc, cr, bm, bt, cm, d_vec)
    return tuple(out) if save else (out[0], None)


def _ssd_backward_pallas(x, dl_col, cc, cr, bm, cm, ct, d_vec, states, dy,
                         *, chunk, p, hb, dtype, interpret):
    bsz, t, hp = x.shape
    n, g = ct.shape[2], ct.shape[1]
    c, nb = t // chunk, hp // (hb * p)
    nbg = nb // g
    sp = _specs(chunk, hb, p, n, nb, nbg, lambda k: c - 1 - k)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, p=p, s=mosaic.slab_heads(p) or 1,
                          nbg=nbg, dtype=dtype),
        grid=(bsz, c, nb),
        in_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["bc"],
                  sp["bc"], sp["bc_t"], sp["d"], sp["state"], sp["x"]],
        out_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["bc"],
                   sp["bc"], sp["dd"]],
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _out_struct(dl_col.shape, f32, x), _out_struct(cc.shape, f32, x),
                   _out_struct(cr.shape, f32, x), _out_struct(bm.shape, f32, x),
                   _out_struct(cm.shape, f32, x),
                   _out_struct((bsz, nb, 1, hb * p), f32, x)],
        scratch_shapes=[pltpu.VMEM((nb, n, hb * p), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, hb * p), dtype),
                        pltpu.VMEM((chunk, hb * p), dtype)],
        compiler_params=_PARAMS, interpret=interpret,
        name="dwt_ssd_bwd",
    )(x, dl_col, cc, cr, bm, cm, ct, d_vec, states, dy)


# A model's layers call the kernels with the same shapes and the same
# static plan: behind `jax.jit` a kernel body is traced and lowered to
# Mosaic once a step program, not once a layer
# (`ops/flash_attention._projected_forward`'s way).
_STATIC = ("chunk", "p", "hb", "dtype", "interpret")
_forward = jax.jit(_ssd_forward_pallas, static_argnames=_STATIC + ("save",))
_backward = jax.jit(_ssd_backward_pallas, static_argnames=_STATIC)


def _transposed(m, g):  # (b, T, G*N) -> (b, G, N, T)
    bsz, t, gn = m.shape
    return m.reshape(bsz, t, g, gn // g).transpose(0, 2, 3, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chunks(x, dl_col, cc, cr, bm, cm, d_vec, g, plan):
    """The kernels' pair: (x (b, T, H*P), dlt and cum by column, cum by
    row, B and C (b, T, G*N) in the products' dtype, D over its head's
    lanes) -> y (b, T, H*P) float32.  `plan`: the static arguments."""
    return _forward(x, dl_col, cc, cr, bm, _transposed(bm, g), cm, d_vec,
                    save=False, **dict(plan))[0]


def _chunks_fwd(x, dl_col, cc, cr, bm, cm, d_vec, g, plan):
    y, states = _forward(x, dl_col, cc, cr, bm, _transposed(bm, g), cm,
                         d_vec, save=True, **dict(plan))
    return y, (x, dl_col, cc, cr, bm, cm, d_vec, states)


def _chunks_bwd(g, plan, res, dy):
    x, dl_col, cc, cr, bm, cm, d_vec, states = res
    dx, ddl, dcc, dcr, db, dc, dd = _backward(
        x, dl_col, cc, cr, bm, cm, _transposed(cm, g), d_vec, states, dy,
        **dict(plan))
    return (dx, ddl, dcc, dcr, db.astype(bm.dtype), dc.astype(cm.dtype),
            dd.sum(0).reshape(d_vec.shape))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _kernel_operands(x, dlt, a, b_mat, c_mat, d_skip, chunk, dtype, hb):
    """What the kernels are handed, from `ssd_scan`'s arguments: x
    (b, T, H*P) as it is; dlt and cum by column, cum by row; B and C
    (b, T, G*N) in the products' dtype; D spread over its head's lanes.
    Small arrays in `jax.numpy`, differentiated by JAX."""
    bsz, t, h, p = x.shape
    g, n = b_mat.shape[2:]
    nb = h // hb
    dl = dlt.astype(jnp.float32)
    cum = jnp.cumsum(
        (dl * a.astype(jnp.float32)).reshape(bsz, t // chunk, chunk, h),
        axis=2).reshape(bsz, t, nb, hb)
    return (x.reshape(bsz, t, h * p),
            dl.reshape(bsz, t, nb, hb).transpose(0, 2, 1, 3),
            cum.transpose(0, 2, 1, 3), cum.transpose(0, 2, 3, 1),
            b_mat.reshape(bsz, t, g * n).astype(dtype),
            c_mat.reshape(bsz, t, g * n).astype(dtype),
            jnp.repeat(d_skip.astype(jnp.float32), p).reshape(1, h * p))


def _scan_kernels(x, dlt, a, b_mat, c_mat, d_skip, chunk, dtype, hb,
                  interpret=False):
    """The kernel route: `_chunks` on `_kernel_operands`."""
    plan = (("chunk", chunk), ("p", x.shape[3]), ("hb", hb),
            ("dtype", jnp.dtype(dtype)), ("interpret", interpret))
    y = _chunks(*_kernel_operands(x, dlt, a, b_mat, c_mat, d_skip, chunk,
                                  dtype, hb), b_mat.shape[2], plan)
    return y.reshape(x.shape)
