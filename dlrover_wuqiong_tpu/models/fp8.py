"""FP8 projection layer — the module-filter target of the amp/fp8 strategy.

Parity: reference `atorch/atorch/auto/opt_lib/amp_optimization.py:197-260`
(`Fp8Optimization`) filters a model's Linear modules by name and swaps them
for TransformerEngine fp8 layers.  TPU redesign: the model builds its
projections through `dense()` below; when the strategy sets `cfg.fp8`, the
name-filtered projections become `Fp8Dense` — master weights stay in f32,
the matmul runs through `ops.quantization.fp8_matmul` (e4m3 forward, e5m2
gradients, per-tensor *current* scaling — amax recomputed per call, no
delayed-scaling history) with f32 accumulation on the MXU.

Parameter names/shapes are identical to `nn.Dense` ("kernel"/"bias"), so the
TP/FSDP PartitionSpec rules in `parallel/sharding.py` bind unchanged.
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..ops.quantization import Fp8Einsum


class Fp8Dense(nn.Module):
    """Drop-in nn.Dense with the matmul routed through fp8_matmul."""

    features: int
    dtype: Any = jnp.bfloat16
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features))
        # mirror nn.Dense promotion (params → compute dtype) before the fp8
        # rounding so bf16 and fp8 runs share the same master-weight path
        y = Fp8Einsum.project(x, kernel.astype(self.dtype),
                              out_dtype=self.dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,))
            y = y + bias.astype(self.dtype)
        return y


def fp8_selected(cfg, name: str) -> bool:
    """Module filter: does this projection fall under the fp8 strategy?"""
    flt: Tuple[str, ...] = getattr(cfg, "fp8_filter", ())
    return bool(getattr(cfg, "fp8", False)) and any(p in name for p in flt)


def dense(cfg, features: int, name: str, use_bias: bool = True):
    """`nn.Dense` or `Fp8Dense` per the config's fp8 flag + name filter."""
    if fp8_selected(cfg, name):
        return Fp8Dense(features, dtype=cfg.dtype, use_bias=use_bias,
                        name=name)
    return nn.Dense(features, dtype=cfg.dtype, use_bias=use_bias, name=name)
