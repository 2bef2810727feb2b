"""`granitemoehybrid` without experts: a hybrid stack whose every block
holds TWO sublayers — a mixer, whose kind a per-layer list gives
(`mamba`: models/mamba2.py's Mamba-2 mixer; `attention`: models/llama.py's
grouped-query `LlamaAttention` with no position term and the softmax
scaled by `attention_multiplier`), and a dense SwiGLU (`LlamaMLP`) —
each behind its own RMSNorm, each added to the residual stream scaled:

    x = embed[ids] * embedding_multiplier
    for kind in layer_types:
        x = x + residual_multiplier * mixer_kind(RMSNorm(x))
        x = x + residual_multiplier * mlp(RMSNorm(x))
    logits = (RMSNorm(x) @ embed^T) / logits_scaling          (tied)

The head is the embedding table itself (no `lm_head` leaf): the table
takes the lookup's gradient and the head's.  Parameter names are
`layers_<i>/{input_norm,post_mixer_norm}`, `layers_<i>/{mamba|attention}`
and `layers_<i>/feed_forward`, matched by `parallel/sharding.py`; the
stack and the tied head are `models/stack.py`'s.

Parity: none — the reference trains Llama/GLM-class stacks only
(models/llama.py); this stack exists for the dense hybrid's benchmark
cell (`granite-4.0-h-micro`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, RMSNorm
from .mamba2 import Mamba2Config, Mamba2Mixer

KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    # periods of ten: nine `mamba`, the sixth layer `attention`
    layer_types: Tuple[str, ...] = tuple(
        "attention" if i % 10 == 5 else "mamba" for i in range(40))
    max_seq_len: int = 131072
    rms_eps: float = 1e-5
    intermediate_size: int = 8192
    # the four scalars: on the embedding, on both residual branches, on
    # the softmax's scores (in place of 1/sqrt(head size)), under the
    # logits
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # `attention`: grouped-query, heads of hidden / num_heads, no rotation
    num_heads: int = 32
    num_kv_heads: int = 8
    # `mamba`: Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 1
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            layer_types=("mamba", "attention", "mamba"), max_seq_len=64,
            num_heads=4, num_kv_heads=2, mamba_heads=8, mamba_head_dim=16,
            state_size=16, chunk_size=16), **over})

    def attention_config(self) -> LlamaConfig:
        """`LlamaAttention`'s and `LlamaMLP`'s config: one object serves
        both sublayers."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=len(self.layer_types), num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, max_seq_len=self.max_seq_len,
            rms_eps=self.rms_eps, dtype=self.dtype,
            use_flash_attention=self.use_flash_attention, mesh=self.mesh,
            rope=False, attn_scale=self.attention_multiplier)

    def mamba_config(self) -> Mamba2Config:
        return Mamba2Config(
            hidden_size=self.hidden_size, num_heads=self.mamba_heads,
            head_dim=self.mamba_head_dim, n_groups=self.n_groups,
            state_size=self.state_size, conv_kernel=self.conv_kernel,
            chunk_size=self.chunk_size, eps=self.rms_eps, dtype=self.dtype,
            mesh=self.mesh, dt_min=self.dt_min, dt_max=self.dt_max,
            dt_floor=self.dt_floor)

    def num_params(self) -> int:
        h, llama = self.hidden_size, self.attention_config()
        mixer = {"mamba": self.mamba_config().num_params(),
                 "attention": llama.attention_params()}
        return (self.vocab_size * h + h  # the tied table, the final norm
                + sum(mixer[kind] + llama.ffn_params() + 2 * h
                      for kind in self.layer_types))


def _scaled(x, scale: float, plus=None):
    """scale * x (+ plus), computed in float32 and rounded ONCE to x's
    dtype: how every multiplier of the config meets a bfloat16 array.  A
    Python scalar times a bfloat16 array is rounded to bfloat16 first,
    and 0.22 is none (0.2197: every residual branch 0.12% low, which read
    as 5e-4 on the gradient's norm against the reference on every seed;
    PERF.md section 6, PR 33)."""
    out = scale * x.astype(jnp.float32)
    if plus is not None:
        out = out + plus.astype(jnp.float32)
    return out.astype(x.dtype)


class GraniteHybridBlock(nn.Module):
    config: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        llama = cfg.attention_config()
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        if self.kind == "mamba":
            out = Mamba2Mixer(cfg.mamba_config(), name="mamba")(h)
        else:
            out = LlamaAttention(llama, name="attention")(h, None, None)
        # the save/offload anchors of the *_names remat policies
        x = _scaled(checkpoint_name(out, "attn_out"),
                    cfg.residual_multiplier, plus=x)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_mixer_norm")(x)
        out = LlamaMLP(llama, name="feed_forward")(h)
        return _scaled(checkpoint_name(out, "mlp_out"),
                       cfg.residual_multiplier, plus=x)


class GraniteHybrid(nn.Module):
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if set(cfg.layer_types) - set(KINDS):
            raise ValueError(f"layer_types {cfg.layer_types!r}: a layer is "
                             f"one of {KINDS}")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed_tokens")
        x = _scaled(embed(idx), cfg.embedding_multiplier)
        x = stack.layers(GraniteHybridBlock, cfg,
                         [(kind,) for kind in cfg.layer_types], x)
        return stack.tied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            embed.embedding, cfg.dtype,
            scaled=lambda logits: _scaled(logits, 1.0 / cfg.logits_scaling))

    def init_params(self, rng, batch: int = 1, seq: int = 0):
        return stack.init_params(self, rng, batch,
                                 seq or self.config.chunk_size)
