"""Flash attention for TPU: Pallas kernels (fwd + bwd) with online softmax.

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
  keys k_idx <= i + (sk - sq)); fully-masked KV blocks skip compute via
  `@pl.when`.
- backward: two kernels — dq (grid: q outer, kv inner) and dk/dv (grid: kv
  outer, q inner) — each recomputing p = exp(s - lse) per tile IN
  TRANSPOSED SPACE (queries in lanes) so the (sq, sk) attention matrix
  never hits HBM and the per-row lse/delta broadcast without relayouts.
  delta = rowsum(dO ∘ O) is one fused XLA reduce into the row-major
  (bh, 1, sq) layout the kernels consume.
- head_dim runs natively when lane-aligned (d % 8 == 0, e.g. GPT-2's 64);
  otherwise it is zero-padded to the 128 boundary.  lse lives as (bh, sq)
  f32 everywhere — residuals, kernel outputs and inputs — with a cheap
  in-kernel (block_q, 1) <-> (block_q,) relayout instead of padded HBM
  traffic; the causal mask is one broadcast compare, not 2D iotas.
- on non-TPU backends a jnp reference path keeps tests runnable; the kernels
  themselves are additionally tested in interpret mode.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..common.log import get_logger

logger = get_logger("flash_attention")

NEG_INF = -1e30  # avoids inf-inf NaNs while dominating any real score
LOG2E = 1.4426950408889634  # exp(x) == exp2(x * LOG2E); folding LOG2E
# into the q pre-scale turns every exp over the (block_q, block_k) score
# matrix into a bare exp2 — one VPU multiply pass saved per exp site
# (the hardware exponent unit is base-2; jnp.exp emits the mul per call)


def _on_tpu() -> bool:
    # a backend that fails to initialise raises from here: training on
    # the jnp reference because the chip did not come up is not a mode
    return jax.default_backend() == "tpu"


def _compiler_params(*semantics, vmem_limit: Optional[int] = None):
    kw = {}
    if vmem_limit is not None:
        # the fused multi-head kernels hold q/k/v/o blocks for ALL heads
        # plus per-head f32 scratch: past the 16MB default scoped limit,
        # well inside v5e's 128MB physical VMEM
        kw["vmem_limit_bytes"] = vmem_limit
    return pltpu.CompilerParams(dimension_semantics=semantics, **kw)


def _out_struct(shape, dtype, like):
    """Kernel output struct.  Inside a shard_map (the only way a Mosaic
    kernel runs on a multi-device mesh) the outputs vary over the same
    manual axes as the operands; outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _dot(a, b):
    """a @ b with native-dtype (bf16) MXU multiply, f32 accumulation."""
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a @ b.T with native-dtype MXU multiply, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_mask_block(qi, ki, block_q, block_k, kv_offset):
    # (block_q, 1) >= (1, block_k) broadcast: one VPU pass over the block,
    # vs two materialized 2D iotas + compare (3 extra full passes)
    q_idx = qi * block_q + kv_offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    k_idx = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    return q_idx >= k_idx


# ------------------------------------------------------------- forward kernel


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *,
                   num_kv: int, causal: bool, sm_scale: float,
                   block_q: int, block_k: int, kv_offset: int, pack: int):
    """Packed forward: refs carry `pack` heads in the leading dim.

    Leading-dim indexing (ref[hh]) is a free address offset (unlike lane
    slicing), so packing amortizes per-grid-step fixed costs and generates
    the causal mask once for all packed heads.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    single = num_kv == 1  # whole KV sweep in one step: no online state

    if causal:
        # block fully masked when its first key exceeds the last query's reach
        run = (qi + 1) * block_q + kv_offset > ki * block_k
    else:
        run = True

    if not single:
        @pl.when(ki == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)
    elif causal and kv_offset < 0:
        # single-step path skips the init, but with sq > sk a q block can be
        # FULLY masked (run=False): _inner never writes the scratch while
        # _finalize still reads it — seed the empty-key values so it
        # finalizes to o=0, lse=-inf instead of stale VMEM
        @pl.when(jnp.logical_not(run))
        def _init_masked():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def _inner(mask_block: bool):
        mask = (_causal_mask_block(qi, ki, block_q, block_k, kv_offset)
                if mask_block else None)
        for hh in range(pack):
            # pre-scale q (block_q x d) instead of s (block_q x block_k):
            # one fewer full VPU pass over the score matrix.  LOG2E folds
            # here too: s lives in log2 units, every exp below is a bare
            # exp2, and only the final lse converts back to natural log.
            q = (q_ref[hh].astype(jnp.float32)
                 * (sm_scale * LOG2E)).astype(q_ref.dtype)
            k = k_ref[hh]                              # (block_k, d)
            v = v_ref[hh]
            # bf16 MXU multiply, f32 accumulate — never cast operands up
            s = _dot_t(q, k)                           # (block_q, block_k)
            if mask_block:
                s = jnp.where(mask, s, NEG_INF)
            if single:
                m_new = s.max(axis=-1, keepdims=True)
                p = jnp.exp2(s - m_new)
                if mask_block and kv_offset < 0:
                    p = jnp.where(s <= NEG_INF, 0.0, p)
                m_scr[hh] = m_new
                l_scr[hh] = p.sum(axis=-1, keepdims=True)
                acc_scr[hh] = _dot(p.astype(v.dtype), v)
                continue
            m_prev = m_scr[hh]                         # (block_q, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)
            if mask_block and kv_offset < 0:
                # rows can be fully masked only when sq > sk: exp(0)=1 junk
                p = jnp.where(s <= NEG_INF, 0.0, p)
            alpha = jnp.exp2(m_prev - m_new)
            m_scr[hh] = m_new
            l_scr[hh] = l_scr[hh] * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[hh] = acc_scr[hh] * alpha + _dot(p.astype(v.dtype), v)

    if causal:
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

    @pl.when(ki == num_kv - 1)
    def _finalize():
        for hh in range(pack):
            l = l_scr[hh]
            l_safe = jnp.where(l > 0, l, 1.0)
            o_ref[hh] = (acc_scr[hh] / l_safe).astype(o_ref.dtype)
            # empty key set → logsumexp = -inf (matches the jnp reference
            # path and long_context._merge_partials' isfinite handling).
            # m is in log2 units (LOG2E folded into the q pre-scale) —
            # convert back so the public lse stays natural-log.
            lse = jnp.where(l > 0, m_scr[hh] * (1.0 / LOG2E)
                            + jnp.log(l_safe), -jnp.inf)
            # lse lives as (bh, 1, sq) in HBM — a (…, sq, 1) f32 array pads
            # its minor dim 128x in the tiled layout (~150MB of padding
            # traffic per call at the bench shape); with sq in lanes the
            # padding is 8x of a tiny array, and the (block_q, 1) ->
            # (1, block_q) relayout happens once per q block in VMEM
            lse_ref[hh] = lse.T


def _fit_pack(bh: int) -> int:
    """Heads packed per grid step: largest of 8/4/2/1 dividing bh.

    DWT_FA_PACK overrides the preference order's head (sweep hook).  The
    override is clamped to 8: kernel VMEM scratch scales linearly with
    pack against the fixed 100MB vmem_limit, and an oversized value would
    fail at Mosaic compile time with an opaque error (ADVICE r4)."""
    import os

    try:
        pref = int(os.getenv("DWT_FA_PACK", "8"))
    except ValueError:  # empty/garbage env value: fall back, don't abort
        pref = 8
    if pref > 8:
        logger.warning("DWT_FA_PACK=%d exceeds the VMEM-safe maximum of 8 "
                       "— clamping", pref)
        pref = 8
    for p in (pref, 8, 4, 2):
        if p >= 1 and bh % p == 0:
            return p
    return 1


def _fa_forward_pallas(q, k, v, causal: bool, sm_scale: float,
                       block_q: int, block_k: int, interpret: bool):
    """q: (bh, sq, d), k/v: (bh, sk, d) → (o, lse (bh, 1, sq) f32)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    num_kv = sk // block_k
    pack = _fit_pack(bh)
    grid = (bh // pack, sq // block_q, num_kv)

    kernel = functools.partial(
        _fa_fwd_kernel, num_kv=num_kv, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_offset=sk - sq, pack=pack)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pack, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((pack, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((pack, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((pack, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((pack, 1, block_q), lambda b, i, j: (b, 0, i)),
        ),
        out_shape=(
            _out_struct((bh, sq, d), q.dtype, q),
            _out_struct((bh, 1, sq), jnp.float32, q),
        ),
        scratch_shapes=[
            pltpu.VMEM((pack, block_q, 1), jnp.float32),
            pltpu.VMEM((pack, block_q, 1), jnp.float32),
            pltpu.VMEM((pack, block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=100 * 1024 * 1024),
        interpret=interpret,
        name="dwt_fa_fwd",
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------ backward kernels


def _causal_mask_block_t(qi, ki, block_q, block_k, kv_offset):
    """Transposed-space causal mask: (block_k, block_q), queries in lanes."""
    q_idx = qi * block_q + kv_offset + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q), 1)
    k_idx = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)
    return q_idx >= k_idx


def _dot_c0(a, b):
    """Contract dim 0 of both: (K, M) x (K, N) -> (M, N), f32 accumulate."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _p_transposed(q, k, lse, mask, sm_scale):
    """Recompute p^T = exp(s^T - lse) as (block_k, block_q).

    Both backward kernels work in transposed space — scores with queries in
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
                      kv_offset: int, pack: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if causal:
        run = (qi + 1) * block_q + kv_offset > ki * block_k
    else:
        run = True

    def _inner(mask_block: bool):
        mask = (_causal_mask_block_t(qi, ki, block_q, block_k, kv_offset)
                if mask_block else None)
        for hh in range(pack):
            k = k_ref[hh]
            pT = _p_transposed(q_ref[hh], k, lse_ref[hh], mask, sm_scale)
            dpT = _dot_t(v_ref[hh], do_ref[hh])    # (block_k, block_q)
            dsT = (pT * (dpT - delta_ref[hh]) * sm_scale).astype(k.dtype)
            dq_scr[hh] += _dot_c0(dsT, k)          # (block_q, d)

    if causal:
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

    @pl.when(ki == num_kv - 1)
    def _finalize():
        for hh in range(pack):
            dq_ref[hh] = dq_scr[hh].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, num_q: int,
                       causal: bool, sm_scale: float, block_q: int,
                       block_k: int, kv_offset: int, pack: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if causal:
        run = (qi + 1) * block_q + kv_offset > ki * block_k
    else:
        run = True

    def _inner(mask_block: bool):
        mask = (_causal_mask_block_t(qi, ki, block_q, block_k, kv_offset)
                if mask_block else None)
        for hh in range(pack):
            q = q_ref[hh]
            do = do_ref[hh]
            pT = _p_transposed(q, k_ref[hh], lse_ref[hh], mask,
                               sm_scale).astype(q.dtype)
            dv_scr[hh] += _dot(pT, do)             # (block_k, d)
            dpT = _dot_t(v_ref[hh], do)
            dsT = (pT.astype(jnp.float32)
                   * (dpT - delta_ref[hh]) * sm_scale).astype(q.dtype)
            dk_scr[hh] += _dot(dsT, q)             # (block_k, d)

    if causal:
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

    @pl.when(qi == num_q - 1)
    def _finalize():
        for hh in range(pack):
            dk_ref[hh] = dk_scr[hh].astype(dk_ref.dtype)
            dv_ref[hh] = dv_scr[hh].astype(dv_ref.dtype)


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, *, causal: bool,
                         sm_scale: float, block_q: int, block_k: int,
                         kv_offset: int, pack: int):
    """Single-block fused backward: dq, dk AND dv in one pass.

    Only legal when the whole sequence fits one block each way (num_q ==
    num_kv == 1) — the general case cannot fuse because dq accumulates
    over the kv grid axis while dk/dv accumulate over the q axis, and a
    Pallas TPU output block only stays resident across CONSECUTIVE grid
    steps (the reason the split kernels exist).  At the 1k-context bench
    shape this saves 2 of the split path's 7 dots (the second S and dP
    recomputes) and one full exp pass over the score matrix.
    """
    mask = (_causal_mask_block_t(0, 0, block_q, block_k, kv_offset)
            if causal else None)
    for hh in range(pack):
        q = q_ref[hh]
        k = k_ref[hh]
        do = do_ref[hh]
        pT = _p_transposed(q, k, lse_ref[hh], mask, sm_scale)  # (bk, bq)
        pTb = pT.astype(q.dtype)
        dv_ref[hh] = _dot(pTb, do).astype(dv_ref.dtype)        # (bk, d)
        dpT = _dot_t(v_ref[hh], do)                            # (bk, bq)
        dsT = (pT * (dpT - delta_ref[hh]) * sm_scale).astype(q.dtype)
        dk_ref[hh] = _dot(dsT, q).astype(dk_ref.dtype)         # (bk, d)
        dq_ref[hh] = _dot_c0(dsT, k).astype(dq_ref.dtype)      # (bq, d)


def _fa_backward_pallas(q, k, v, o, lse, do, causal: bool, sm_scale: float,
                        block_q: int, block_k: int, interpret: bool,
                        glse=None):
    """All operands flat (bh, s, d); lse (bh, 1, sq) f32. Returns dq, dk, dv.

    The kernels recompute p in TRANSPOSED space (queries in lanes) so the
    per-row lse/delta broadcast natively — see `_p_transposed`.  delta and
    the optional lse cotangent `glse` (bh, 1, sq) fold together outside
    (d lse / d s = p, so ds = p * (dp - delta + glse))."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    kv_offset = sk - sq
    num_q = sq // block_q
    num_kv = sk // block_k
    pack = _fit_pack(bh)

    # delta = rowsum(dO ∘ O) — cheap fused reduce; (bh, 1, sq) row-major
    # layout avoids the 128x lane padding a (bh, sq, 1) array would pay
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        -1)[:, None, :]
    if glse is not None:
        delta = delta - glse

    qspec = pl.BlockSpec((pack, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((pack, block_k, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((pack, 1, block_q), lambda b, i, j: (b, 0, i))
    ops = [q, k, v, do, lse, delta]

    if num_q == 1 and num_kv == 1 and not os.getenv("DWT_FA_NO_FUSED"):
        bspec_q = pl.BlockSpec((pack, block_q, d), lambda b: (b, 0, 0))
        bspec_k = pl.BlockSpec((pack, block_k, d), lambda b: (b, 0, 0))
        bspec_row = pl.BlockSpec((pack, 1, block_q), lambda b: (b, 0, 0))
        return pl.pallas_call(
            functools.partial(
                _fa_bwd_fused_kernel, causal=causal, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k, kv_offset=kv_offset,
                pack=pack),
            grid=(bh // pack,),
            in_specs=[bspec_q, bspec_k, bspec_k, bspec_q, bspec_row,
                      bspec_row],
            out_specs=(bspec_q, bspec_k, bspec_k),
            out_shape=(
                _out_struct((bh, sq, d), q.dtype, q),
                _out_struct((bh, sk, d), k.dtype, q),
                _out_struct((bh, sk, d), v.dtype, q),
            ),
            compiler_params=_compiler_params(
                "parallel", vmem_limit=100 * 1024 * 1024),
            interpret=interpret,
            name="dwt_fa_bwd_fused",
        )(*ops)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, num_kv=num_kv, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, kv_offset=kv_offset, pack=pack),
        grid=(bh // pack, num_q, num_kv),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=pl.BlockSpec((pack, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((bh, sq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((pack, block_q, d), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=100 * 1024 * 1024),
        interpret=interpret,
        name="dwt_fa_bwd_dq",
    )(*ops)

    # dkv grid: kv outer, q inner — same operands, transposed index maps
    qspec_t = pl.BlockSpec((pack, block_q, d), lambda b, j, i: (b, i, 0))
    kspec_t = pl.BlockSpec((pack, block_k, d), lambda b, j, i: (b, j, 0))
    rowspec_t = pl.BlockSpec((pack, 1, block_q), lambda b, j, i: (b, 0, i))

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, num_q=num_q, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, kv_offset=kv_offset, pack=pack),
        grid=(bh // pack, num_kv, num_q),
        in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, rowspec_t, rowspec_t],
        out_specs=(
            pl.BlockSpec((pack, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((pack, block_k, d), lambda b, j, i: (b, j, 0)),
        ),
        out_shape=(
            _out_struct((bh, sk, d), k.dtype, q),
            _out_struct((bh, sk, d), v.dtype, q),
        ),
        scratch_shapes=[
            pltpu.VMEM((pack, block_k, d), jnp.float32),
            pltpu.VMEM((pack, block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary",
                                         vmem_limit=100 * 1024 * 1024),
        interpret=interpret,
        name="dwt_fa_bwd_dkv",
    )(*ops)
    return dq, dk, dv


# ----------------------------------------------------------------- reference


def _attention_reference(q, k, v, causal: bool, sm_scale: float):
    """Plain jnp attention — numerics oracle + non-TPU fallback.

    q: (b, h, sq, d); k/v: (b, h, sk, d)
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    bwd_block_q: int = 0, bwd_block_k: int = 0):
    """Multi-head attention, FA2-style.

    Args: q (b, h, sq, d); k, v (b, h, sk, d).  Returns (b, h, sq, d).
    `bwd_block_q`/`bwd_block_k` tile the dq/dkv backward kernels
    independently (0 = inherit block_q/block_k — swept best at the bench
    shape, README table).
    """
    out, _ = _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                     bwd_block_q, bwd_block_k)
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
    if not _on_tpu():
        return False
    # head_dim runs natively (lane-aligned) or zero-padded, so any d
    # qualifies; sequences need a workable tile size
    return (_fit_block(sq, block_q) is not None
            and _fit_block(sk, block_k) is not None)


def _use_streamed(sq, sk) -> bool:
    """Blockwise-scan fallback instead of the dense O(sq*sk) reference.

    Only consulted when the Pallas kernels are unavailable (non-TPU
    backend).  The dense fallback materializes full f32 score matrices —
    fine for small test shapes, but it misrepresents the TPU program's
    memory on big shapes: the 8B AOT fit proof (tests/test_scale_8b.py)
    compiles on a virtual CPU mesh, where dense attention would dominate
    `memory_analysis()` with buffers the Pallas path never allocates.
    DWT_FA_STREAMED=1/0 forces the choice; the default switches at the
    point where a per-head score matrix reaches 2048^2 (16MB f32)."""
    env = os.getenv("DWT_FA_STREAMED")
    if env is not None:
        return env == "1"
    return sq * sk >= 2048 * 2048


def _kernel_head_dim(d: int) -> int:
    """Head dim as seen by the kernels.

    Mosaic accepts any block whose last dim equals the array's, so lane-
    aligned head dims (multiples of 8) run natively — d=64 (GPT-2) included,
    avoiding pad copies.  Odd dims are zero-padded to the 128-lane boundary
    (padded q/k columns add 0 to scores; padded v columns are sliced off).
    """
    return d if d % 8 == 0 else max(128, -(-d // 128) * 128)


def _pad_head_dim(x, d_pad):
    d = x.shape[-1]
    if d == d_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))


def _flat_padded(q, k, v, d_pad):
    b, h, sq, d = q.shape
    qf = _pad_head_dim(q.reshape(b * h, sq, d), d_pad)
    kf = _pad_head_dim(k.reshape(b * h, k.shape[2], d), d_pad)
    vf = _pad_head_dim(v.reshape(b * h, v.shape[2], d), d_pad)
    return qf, kf, vf


def _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k):
    """Shared forward: returns ((out, lse_bhs), residuals)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = _resolve_scale(sm_scale, d)
    if _use_pallas(sq, sk, d, block_q, block_k):
        bq = _fit_block(sq, block_q)
        bk = _fit_block(sk, block_k)
        d_pad = _kernel_head_dim(d)
        qf, kf, vf = _flat_padded(q, k, v, d_pad)
        o, lse = _fa_forward_pallas(qf, kf, vf, causal, scale, bq, bk,
                                    interpret=False)
        out = o[:, :, :d].reshape(b, h, sq, d)
        return (out, lse.reshape(b, h, sq)), (q, k, v, o, lse)
    if _use_streamed(sq, sk):
        out, lse = _streamed_with_lse(q, k, v, causal, scale, block_k)
        return (out, lse), (q, k, v, out, lse)
    out, lse = _reference_with_lse(q, k, v, causal, scale)
    return (out, lse), (q, k, v, out, None)


def _reference_with_lse(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    l_safe = jnp.where(l > 0, l, 1.0)
    lse = jnp.where(l[..., 0] > 0, (m + jnp.log(l_safe))[..., 0], -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", (p / l_safe).astype(v.dtype), v)
    return o, lse


def _streamed_with_lse(q, k, v, causal, scale, block_k):
    """Online-softmax forward as a `lax.scan` over key blocks.

    Same math as the Pallas kernel, in plain jnp: peak temps are
    O(h * sq * block_k) instead of the dense path's O(h * sq * sk) — the
    memory-faithful any-backend stand-in for the kernel (used by the 8B
    AOT fit proof on the virtual CPU mesh)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = _fit_block(sk, min(block_k, 512)) or sk
    nb = sk // bk
    q32 = q.astype(jnp.float32)
    kb = jnp.moveaxis(k.reshape(b, h, nb, bk, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nb, bk, d), 2, 0)
    rows = jnp.arange(sq) + (sk - sq)  # absolute key index each row sees

    def body(carry, inp):
        acc, m, l = carry
        j, kblk, vblk = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       kblk.astype(jnp.float32)) * scale
        mask = None
        if causal:
            cols = j * bk + jnp.arange(bk)
            mask = rows[:, None] >= cols[None, :]
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

    init = (jnp.zeros((b, h, sq, d), jnp.float32),
            jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(body, init, (jnp.arange(nb), kb, vb))
    l_safe = jnp.where(l > 0, l, 1.0)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = jnp.where(l > 0, m + jnp.log(l_safe), -jnp.inf)
    return out, lse


def _streamed_bwd(q, k, v, out, lse, g, causal, scale, block_q, glse):
    """Flash-style recompute backward as one `lax.scan` over query blocks.

    Each step re-derives p for its q block from the stored lse, emits the
    block's dq, and accumulates dk/dv — peak temps O(h * block_q * sk)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _fit_block(sq, min(block_q, 512)) or sq
    nb = sq // bq
    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
    g32 = g.astype(jnp.float32)
    delta = (g32 * out.astype(jnp.float32)).sum(-1)  # (b, h, sq)
    if glse is not None:
        delta = delta - glse
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    qb = jnp.moveaxis(q32.reshape(b, h, nb, bq, d), 2, 0)
    gb = jnp.moveaxis(g32.reshape(b, h, nb, bq, d), 2, 0)
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
            p = jnp.where(rows[:, None] >= cols[None, :], p, 0.0)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gblk, v32)
        ds = p * (dp - dblk[..., None])
        dqblk = jnp.einsum("bhqk,bhkd->bhqd", ds, k32) * scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qblk) * scale
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, gblk)
        return (dk, dv), dqblk

    init = (jnp.zeros((b, h, sk, d), jnp.float32),
            jnp.zeros((b, h, sk, d), jnp.float32))
    (dk, dv), dqb = jax.lax.scan(
        body, init, (jnp.arange(nb), qb, gb, lb, db))
    dq = jnp.moveaxis(dqb, 0, 2).reshape(b, h, sq, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_impl(causal, sm_scale, block_q, block_k, res, g, glse):
    """Shared backward; glse (b, h, sq) f32 or None folds the lse cotangent
    into delta (d lse / d s = p, so ds = p * (dp - delta + glse))."""
    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = _resolve_scale(sm_scale, d)
    if lse is not None and not _use_pallas(sq, sk, d, block_q, block_k):
        # streamed forward ran (lse present, kernels unavailable): its
        # recompute backward — NOT the dense path, which would undo the
        # memory bound the streamed path exists for
        return _streamed_bwd(q, k, v, out, lse, g, causal, scale,
                             block_q, glse)
    if lse is not None:  # pallas forward ran: pallas backward
        bq = _fit_block(sq, block_q)
        bk = _fit_block(sk, block_k)
        d_pad = _kernel_head_dim(d)
        qf, kf, vf = _flat_padded(q, k, v, d_pad)
        gf = _pad_head_dim(g.reshape(b * h, sq, d), d_pad)
        glse_f = None if glse is None else glse.reshape(b * h, 1, sq)
        dq, dk, dv = _fa_backward_pallas(qf, kf, vf, out, lse,
                                         gf, causal, scale, bq, bk,
                                         interpret=False, glse=glse_f)
        return (dq[:, :, :d].reshape(b, h, sq, d).astype(q.dtype),
                dk[:, :, :d].reshape(b, h, sk, d).astype(k.dtype),
                dv[:, :, :d].reshape(b, h, sk, d).astype(v.dtype))
    # jnp recompute fallback (matches _attention_reference numerics)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
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
            bwd_block_q=0, bwd_block_k=0):
    (out, _), res = _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k)
    return out, res


def _fa_bwd(causal, sm_scale, block_q, block_k, bwd_block_q, bwd_block_k,
            res, g):
    return _fa_bwd_impl(causal, sm_scale, bwd_block_q or block_q,
                        bwd_block_k or block_k, res, g, None)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             sm_scale: Optional[float] = None,
                             block_q: int = 1024, block_k: int = 1024,
                             bwd_block_q: int = 0, bwd_block_k: int = 0):
    """Like `flash_attention` but also returns lse (b, h, sq) f32 — the
    building block for ring/blockwise attention where partial results over
    disjoint key sets merge by logsumexp weights.  Differentiable in both
    outputs (the lse cotangent folds into the delta term)."""
    (out, lse), _ = _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k)
    return out, lse


def _fa_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                bwd_block_q=0, bwd_block_k=0):
    return _fa_fwd_lse(q, k, v, causal, sm_scale, block_q, block_k)


def _fa_lse_bwd(causal, sm_scale, block_q, block_k, bwd_block_q,
                bwd_block_k, res, gs):
    g, glse = gs
    return _fa_bwd_impl(causal, sm_scale, bwd_block_q or block_q,
                        bwd_block_k or block_k, res, g,
                        glse.astype(jnp.float32))


flash_attention_with_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


def mha(q, k, v, causal: bool = True, sm_scale: Optional[float] = None):
    """Convenience wrapper accepting (b, s, h, d) layout (flax convention).

    The transposes to (b, h, s, d) cost ~1ms/layer at the bench shape; a
    fused kernel taking (b, s, h*d) directly was built and measured SLOWER
    (lane slices at non-128 offsets relayout per head: ~7.2ms vs 5.6ms
    fwd+bwd), so the transpose + flat-kernel route stays.
    """
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(qt, kt, vt, causal, sm_scale)
    return out.transpose(0, 2, 1, 3)
