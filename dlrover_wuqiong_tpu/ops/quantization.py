"""Quantization ops: blockwise int8 kernels + scaled FP8 matmul.

Parity: reference `atorch/atorch/ops/csrc/` CUDA suite (`quantize.cu`,
`dequantize.cu`, `swizzled_quantize.cu`, `quant_reduce.cu`) and the fp8
module filter (`auto/opt_lib/amp_optimization.py:197` Fp8Optimization via
TransformerEngine).

TPU redesign:
- int8: blockwise absmax quantize/dequantize as Pallas kernels (VPU
  elementwise + per-block reduction in VMEM) with a jnp fallback that XLA
  fuses; used by the low-bit optimizer states.
- fp8: e4m3/e5m2 live natively in XLA (ml_dtypes).  `fp8_dot` runs a
  scaled matmul: per-tensor dynamic scaling into fp8, dot with f32
  accumulation, rescale.  On hardware without fp8 MXU paths XLA upcasts —
  numerics (the fp8 rounding) are preserved either way, which is the
  property training cares about.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import mosaic

BLOCK = 256


# ------------------------------------------------------------- int8 blockwise


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)         # (rows, BLOCK)
    absmax = jnp.abs(x).max(axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_int8_blockwise(x: jax.Array, block: int = BLOCK
                            ) -> Tuple[jax.Array, jax.Array]:
    """x (any shape) → (int8 (n_blocks, block), f32 scales (n_blocks, 1)).

    Flat blockwise absmax: the layout the low-bit optimizer stores.
    Pallas on TPU, fused jnp elsewhere.
    """
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.size // block
    tiled = flat.reshape(rows, block)
    if mosaic.on_tpu() and rows % 8 == 0:
        grid = (rows // 8,)
        q, s = pl.pallas_call(
            _quant_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((8, block), lambda i: (i, 0))],
            out_specs=(pl.BlockSpec((8, block), lambda i: (i, 0)),
                       pl.BlockSpec((8, 1), lambda i: (i, 0))),
            out_shape=(jax.ShapeDtypeStruct((rows, block), jnp.int8),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
            name="dwt_int8_quant",
        )(tiled)
        return q, s
    xf = tiled.astype(jnp.float32)
    absmax = jnp.abs(xf).max(axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8_blockwise(q: jax.Array, scale: jax.Array,
                              size: int, shape: Tuple[int, ...],
                              dtype=jnp.float32) -> jax.Array:
    """Inverse of quantize_int8_blockwise."""
    rows, block = q.shape
    if mosaic.on_tpu() and rows % 8 == 0:
        x = pl.pallas_call(
            _dequant_kernel,
            grid=(rows // 8,),
            in_specs=[pl.BlockSpec((8, block), lambda i: (i, 0)),
                      pl.BlockSpec((8, 1), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, block), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, block), jnp.float32),
            name="dwt_int8_dequant",
        )(q, scale)
    else:
        x = q.astype(jnp.float32) * scale
    return x.reshape(-1)[:size].reshape(shape).astype(dtype)


# ------------------------------------------------------------------- fp8


E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2

_FP8_MAX = {E4M3: 448.0, E5M2: 57344.0}


def fp8_quantize(x: jax.Array, dtype=E4M3,
                 scale: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Dynamic per-tensor scaling into fp8; returns (fp8 x, f32 scale).

    scale maps the tensor's amax onto the format's max representable —
    te-style current scaling (amax history is the caller's policy).
    """
    if scale is None:
        amax = jnp.abs(x).max().astype(jnp.float32)
        scale = jnp.where(amax > 0, _FP8_MAX[dtype] / amax, 1.0)
    q = (x.astype(jnp.float32) * scale).astype(dtype)
    return q, scale


def fp8_dequantize(q: jax.Array, scale: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) / scale).astype(dtype)


def fp8_dot(a: jax.Array, b: jax.Array, out_dtype=jnp.bfloat16,
            fwd_dtype=E4M3) -> jax.Array:
    """Scaled fp8 matmul: a @ b with both operands rounded through fp8.

    The contraction accumulates in f32 (`preferred_element_type`), then the
    combined scale divides out.  Parity target: the Fp8Optimization module
    filter — this is the op it swaps into Linear layers.
    """
    qa, sa = fp8_quantize(a, fwd_dtype)
    qb, sb = fp8_quantize(b, fwd_dtype)
    acc = jax.lax.dot_general(
        qa, qb, (((qa.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc / (sa * sb)).astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fp8_matmul(a, b, out_dtype=jnp.bfloat16):
    """2D fp8 matmul: e4m3 forward, e5m2 gradients (te convention).

    a (m, k) @ b (k, n) → (m, n).  Callers flatten leading batch dims.
    """
    return fp8_dot(a, b, out_dtype, E4M3)


def _fp8_mm_fwd(a, b, out_dtype):
    return fp8_dot(a, b, out_dtype, E4M3), (a, b)


def _fp8_mm_bwd(out_dtype, res, g):
    a, b = res
    # grads flow through e5m2 (wider range, lower precision)
    qg, sg = fp8_quantize(g, E5M2)
    qb, sb = fp8_quantize(b, E5M2)
    qa, sa = fp8_quantize(a, E5M2)
    ga = jax.lax.dot_general(
        qg, qb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / (sg * sb)
    gb = jax.lax.dot_general(
        qa, qg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / (sa * sg)
    return ga.astype(a.dtype), gb.astype(b.dtype)


fp8_matmul.defvjp(_fp8_mm_fwd, _fp8_mm_bwd)


class Fp8Einsum:
    """Drop-in helper for (B, T, C) x (C, F) projections via fp8_matmul."""

    @staticmethod
    def project(x: jax.Array, w: jax.Array,
                out_dtype=jnp.bfloat16) -> jax.Array:
        B = x.shape[:-1]
        y = fp8_matmul(x.reshape(-1, x.shape[-1]), w, out_dtype)
        return y.reshape(*B, w.shape[-1])
