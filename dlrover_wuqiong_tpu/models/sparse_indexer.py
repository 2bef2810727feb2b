"""The indexer of a learned sparse attention (DeepSeek Sparse
Attention's "lightning indexer", the V3.2-Exp report): a few light heads
over ONE shared key score every causal pair, and the attention keeps
each query's best-scored keys (`ops/sparse_attention.py`).

    hd = stop_gradient(h)              the block's normalised input
    qI = hd W_q            -> heads x dim        (`wq_idx`)
    kI = LayerNorm(hd W_k) -> ONE key of dim     (`wk_idx`, `k_norm`)
    w  = hd W_w            -> a weight a head    (`w_proj`, float32)
    qI, kI rotated like the attention's q and k, over all their lanes
    I[t, s] = sum_j w[t, j] heads^-1/2 dim^-1/2 relu(qI[t, j] . kI[s])

The module returns the three operands of `sparse_attention` — the
rotated queries (b, T, heads, dim), the rotated key (b, T, dim) and the
weights with both constant factors in them — and forms no score itself.
Its input is DETACHED: the indexer learns from its own KL term and from
nothing else, and nothing of the main model learns through it.  A model
carries it inside `models/llama.LlamaAttention` (`LlamaConfig.
attn_index_topk`); it asks nothing of the attention it sits beside but
the block's input and the rotation's tables, so a latent attention could
carry it the same way.

Parity: none — the reference has no sparse attention.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


class SparseIndexer(nn.Module):
    heads: int
    dim: int
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    mesh: Any = None

    @nn.compact
    def __call__(self, h, cos, sin):
        """`cos`, `sin`: the rotation's tables for heads of `dim` lanes
        ((T', dim / 2), or (b, T, dim / 2) under explicit positions)."""
        from .llama import apply_rope

        b, t, _ = h.shape
        with jax.named_scope("sparse_attn/index"):
            hd = jax.lax.stop_gradient(h)
            q = nn.Dense(self.heads * self.dim, use_bias=False,
                         dtype=self.dtype, name="wq_idx")(hd)
            k = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                         name="wk_idx")(hd)
            k = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype,
                             name="k_norm")(k)
            w = nn.Dense(self.heads, use_bias=False, dtype=jnp.float32,
                         name="w_proj")(hd.astype(jnp.float32))
            q = apply_rope(q.reshape(b, t, self.heads, self.dim), cos, sin,
                           mesh=self.mesh)
            k = apply_rope(k.reshape(b, t, 1, self.dim), cos, sin,
                           mesh=self.mesh).reshape(b, t, self.dim)
            return q, k, w * (self.heads * self.dim) ** -0.5
