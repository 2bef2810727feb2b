"""`olmo_hybrid`: a hybrid stack whose every block holds TWO sublayers —
a mixer, whose kind a per-layer list gives (`linear_attention`:
models/gated_delta.py's gated delta-rule mixer; `full_attention`:
models/llama.py's `LlamaAttention` with an RMSNorm over the whole of q's
and of k's projection and no rotation), and a dense SwiGLU (`LlamaMLP`)
— under Olmo 2's REORDERED norm: the sublayer reads the residual stream
as it is and its OUTPUT is normed before the add:

    x = embed[ids]
    for kind in layer_types:
        x = x + RMSNorm(mixer_kind(x))
        x = x + RMSNorm(mlp(x))
    logits = RMSNorm(x) @ W_head                              (untied)

`linear_heads` is how many of a linear mixer's heads are HELD here (a
chip's share of the published count); the attention layer is whole.
Parameter names are `layers_<i>/{linear_attention|attention}`,
`layers_<i>/feed_forward` and `layers_<i>/{post_mixer_norm,
post_feedforward_norm}`, matched by `parallel/sharding.py`; the stack
and the head are `models/stack.py`'s.

Parity: none — the reference trains Llama/GLM-class stacks only
(models/llama.py); this stack exists for the linear-attention hybrid's
benchmark cell (`Olmo-Hybrid-7B`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .gated_delta import GatedDeltaConfig, GatedDeltaMixer
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, RMSNorm

KINDS = ("linear_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    # periods of four: three `linear_attention`, then `full_attention`
    layer_types: Tuple[str, ...] = tuple(
        "full_attention" if i % 4 == 3 else "linear_attention"
        for i in range(32))
    max_seq_len: int = 65536
    rms_eps: float = 1e-6
    intermediate_size: int = 11008
    # `full_attention`: heads of hidden / num_heads, QK-norm, no rotation
    num_heads: int = 30
    num_kv_heads: int = 30
    # `linear_attention`: the gated delta rule
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    chunk_size: int = 64
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
            layer_types=("linear_attention", "full_attention",
                         "linear_attention"), max_seq_len=64,
            num_heads=4, num_kv_heads=4, linear_heads=3, linear_key_dim=8,
            linear_value_dim=24, chunk_size=16), **over})

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
            rope=False, qk_norm=True)

    def linear_config(self) -> GatedDeltaConfig:
        return GatedDeltaConfig(
            hidden_size=self.hidden_size, num_heads=self.linear_heads,
            key_dim=self.linear_key_dim, value_dim=self.linear_value_dim,
            conv_kernel=self.conv_kernel,
            chunk_size=self.chunk_size, eps=self.rms_eps, dtype=self.dtype,
            mesh=self.mesh)

    def num_params(self) -> int:
        h, llama = self.hidden_size, self.attention_config()
        mixer = {"linear_attention": self.linear_config().num_params(),
                 "full_attention": llama.attention_params()}
        return (2 * self.vocab_size * h + h  # table, head, the final norm
                + sum(mixer[kind] + llama.ffn_params() + 2 * h
                      for kind in self.layer_types))


class OlmoHybridBlock(nn.Module):
    config: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        llama = cfg.attention_config()

        def norm(name):
            return RMSNorm(cfg.rms_eps, cfg.dtype, name=name)

        x = pin_activation(x, cfg.mesh)
        if self.kind == "linear_attention":
            out = GatedDeltaMixer(cfg.linear_config(),
                                  name="linear_attention")(x)
        else:
            out = LlamaAttention(llama, name="attention")(x, None, None)
        # the save/offload anchors of the *_names remat policies
        x = x + norm("post_mixer_norm")(checkpoint_name(out, "attn_out"))
        out = LlamaMLP(llama, name="feed_forward")(x)
        return x + norm("post_feedforward_norm")(
            checkpoint_name(out, "mlp_out"))


class OlmoHybrid(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if set(cfg.layer_types) - set(KINDS):
            raise ValueError(f"layer_types {cfg.layer_types!r}: a layer is "
                             f"one of {KINDS}")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        x = stack.layers(OlmoHybridBlock, cfg,
                         [(kind,) for kind in cfg.layer_types], x)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 0):
        return stack.init_params(self, rng, batch,
                                 seq or self.config.chunk_size)
