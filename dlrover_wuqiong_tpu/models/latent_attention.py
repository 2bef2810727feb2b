"""Latent attention (multi-head latent attention, DeepSeek-V2's MLA,
arXiv:2405.04434): keys and values are up-projections of ONE normed
low-rank latent a token, and the part of a key that carries the position
is one rotated vector shared by every head.

    q        = h Wq                    -> heads x (nope | rope), or with
               a latent for q (`q_lora_rank`): RMSNorm(h Wq_a) Wq_b
    c        = h Wkv_a                 -> (latent of kv_lora_rank | rope key)
    kv       = RMSNorm(c[:rank]) Wkv_b -> heads x (k_nope | v)
    q_rope, k_rope rotated; k_h = (k_nope_h | k_rope), the same k_rope
    o_h      = softmax_causal(q_h k_h^T * scale) v_h, scale 1 / sqrt(nope
               + rope) unless `attn_scale` sets it (YaRN's m^2 factor)
    o_h     *= sigmoid(h Wgate)[h]     with `attn_gate`: one number a head
                                       and token (`LlamaConfig.attn_gate`'s
                                       form), before Wo
    out      = concat_h(o_h) Wo

`num_heads` is the heads HELD here: every head has its own columns of
Wq, Wkv_b and Wgate and its own rows of Wo, while Wkv_a, the latent's
norm and the one rotated key part are computed alike wherever a head
lives, so a share of the heads is a share of the layer and Wo's partial
sums over the shares add up to the whole (tests/test_bailing_hybrid.py).

So a head's q and k are `qk_nope_head_dim + qk_rope_head_dim` wide (192)
and its v `v_head_dim` (128): `ops/flash_attention.py`'s kernels take the
two widths as they are, QK^T over the one and PV over the other, on the
transposed (b, h, T, d) route (`attention_route(h, 192, 128)`: a head of
a slab and a half lies on no slab boundary).  The tables it is handed
carry the positions' scaling (`models/llama.py::rope_freqs`, YaRN's
through `RopeScaling`).  What is NOT here: a kernel that takes `k_rope`
once instead of broadcast to the heads and joined to `k_nope` in HBM
(ROADMAP, Speed), the cache of latents a server would keep (`serving/`:
no latent cache, no absorbed up-projections), and a mesh (ring, Ulysses
and the shard_map of `parallel/long_context.py` are handed one width: two
widths on a mesh are not built).

Scopes in the compiled step, all under the module's own name:
`q_proj` (or `q_a_proj`, `q_a_norm`, `q_b_proj` with a q latent),
`kv_a_proj`, `kv_a_norm`, `kv_b_proj`, `o_proj`, `g_proj` (the flax
modules' names), `gate` (the output gate's sigmoid and multiply; the
layer then sows `attn_gate_mean` as a gated `LlamaAttention` does),
`rope` (the two rotations) and `assemble` (the cut of
the projections into their parts, the broadcast of `k_rope` and the
joins into the 192-wide q and k).  The module sows `attn_lanes`: the
lanes of q/k and v a score entry's two products run as the kernels block
them, and the lanes the model's widths ask
(`models/attention.collect_attention_stats`).

Parity: none — the reference trains Llama/GLM-class stacks only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import RMSNorm, apply_rope


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    hidden_size: int = 2048
    num_heads: int = 16         # the heads HELD here
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    # a latent for q as well: q = RMSNorm(h Wq_a) Wq_b (None = h Wq)
    q_lora_rank: Optional[int] = None
    # the softmax's scale (`models/attention.softmax_scale`); 0 = 1 /
    # sqrt(nope + rope)
    attn_scale: float = 0.0
    # one sigmoid gate a head and token on the heads' output, before
    # `o_proj`: sigmoid(x @ g_proj), x the block's normalised input
    attn_gate: bool = False
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_flash_attention: bool = True
    mesh: Any = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def attention_params(self) -> int:
        """q (one product, or the latent's two and its norm), kv_a, the
        latent's norm, kv_b, o, the output gate's; no block norm."""
        h, n, r = self.hidden_size, self.num_heads, self.kv_lora_rank
        qr = self.q_lora_rank
        q = h * qr + qr + qr * n * self.qk_head_dim if qr \
            else h * n * self.qk_head_dim
        return (q + h * (r + self.qk_rope_head_dim)
                + r + r * n * (self.qk_nope_head_dim + self.v_head_dim)
                + n * self.v_head_dim * h
                + (h * n if self.attn_gate else 0))


class LatentAttention(nn.Module):
    config: LatentAttentionConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        """x (B, T, hidden); cos, sin: `rope_freqs(qk_rope_head_dim, ...)`."""
        from ..ops.flash_attention import kept_mask, kernel_lanes
        from .attention import attend
        from .fp8 import dense

        cfg = self.config
        B, T, C = x.shape
        H, rank = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        if cfg.q_lora_rank:
            q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_a_norm")(
                dense(cfg, cfg.q_lora_rank, "q_a_proj", use_bias=False)(x))
            q = dense(cfg, H * (dn + dr), "q_b_proj", use_bias=False)(q)
        else:
            q = dense(cfg, H * (dn + dr), "q_proj", use_bias=False)(x)
        c = dense(cfg, rank + dr, "kv_a_proj", use_bias=False)(x)
        latent = RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_a_norm")(
            c[..., :rank])
        kv = dense(cfg, H * (dn + dv), "kv_b_proj", use_bias=False)(latent)
        with jax.named_scope("assemble"):
            q = q.reshape(B, T, H, dn + dr)
            kv = kv.reshape(B, T, H, dn + dv)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
            k_nope, v = kv[..., :dn], kv[..., dn:]
            k_rope = c[..., rank:].reshape(B, T, 1, dr)
        with jax.named_scope("rope"):
            # on one TPU device both take the kernel route: the q
            # heads' parts side by side are rows of H x 64 lanes, two
            # heads a slab, and the ONE key part for all heads half a
            # slab, padded to one
            q_rope = apply_rope(q_rope, cos, sin, mesh=cfg.mesh)
            k_rope = apply_rope(k_rope, cos, sin, mesh=cfg.mesh)
        with jax.named_scope("assemble"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
        # counted, not timed (static numbers): a score entry's lanes
        flash = cfg.use_flash_attention
        self.sow("intermediates", "attn_lanes", jnp.asarray(
            [kernel_lanes(dn + dr, dv) if flash else dn + dr + dv,
             dn + dr + dv], jnp.float32))
        if flash:
            y = attend(q, k, v, cfg, causal=True)
        else:
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            att = att * cfg.attn_scale if cfg.attn_scale \
                else att / jnp.sqrt(jnp.float32(dn + dr))
            att = jnp.where(kept_mask(T, T), att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        if cfg.attn_gate:
            g = dense(cfg, H, "g_proj", use_bias=False)(x)
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(g.astype(jnp.float32))
                self.sow("intermediates", "attn_gate_mean",
                         jax.lax.stop_gradient(g.mean()))
                y = (y * g[..., None]).astype(cfg.dtype)
        return dense(cfg, C, "o_proj", use_bias=False)(
            y.reshape(B, T, H * dv))
