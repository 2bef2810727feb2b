"""`qwen3_next` (Qwen3-Next-80B-A3B's shape): a hybrid stack in periods
of `full_attention_interval` blocks — gated delta-rule mixers whose VALUE
heads outnumber their key heads, then one output-gated grouped-query
attention — every block followed by a softmax-routed expert layer beside
a sigmoid-gated shared expert, under zero-centred norms:

    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)         w drawn at 0
    x = embed[ids]
    for i in range(num_layers):
        h = N(x)
        (i + 1) % interval == 0:                        full attention
            [q | gate] = h Wq    per head 2 x d lanes: query, then gate
            k, v = h Wk, h Wv    kv heads x d, no bias
            q, k = N_d(q), N_d(k)          a head's lanes, BEFORE RoPE
            q, k rotated on their FIRST rotary_dim lanes (rotate_half)
            x = x + (softmax(q k^T / sqrt d) v * sigmoid(gate)) Wo
        else:                                           gated delta rule
            x = x + GatedDeltaMixer(h)   H value heads over Hk key heads,
                                         beta = sigmoid(b): no factor 2
        u = N(x)
        x = x + sum_{e chosen, held} g_e swiglu_e(u)
              + sigmoid(u w_s) * swiglu_shared(u)       one gate a token
    logits = N(x) W_head                                         (untied)
    loss   = cross-entropy (+ router_aux_loss_weight * mean_layers
             balance term)

The mixer's own output norm is plain (`* w`, w at 1: models/gated_delta.py).
`experts_held` / `first_expert` are a chip's share of the experts, as
`models/keye.py` has them.  Nothing here is a copy: the mixer is
`models/gated_delta.py`'s `GatedDeltaMixer` (`num_key_heads`,
`neg_eigval=False`), the attention `models/llama.py`'s `LlamaAttention`
under `attn_out_gate`, `qk_head_norm` and `norm_zero_centred` with
tables `rotary_dim` wide (a head rotated in part), the norms its
`RMSNorm(zero_centred=True)`, the expert layer `models/moe.py`'s `MoEMLP`
on its grouped path under `shared_gate`, the loop and the head
`models/stack.py`'s.  Parameter names are `layers_<i>/{input_norm,
linear_attention | attention, post_attn_norm, feed_forward}`,
`embed_tokens`, `norm`, `lm_head`, so `parallel/sharding.py`'s rules
bind.

Refused, not guessed: a mesh of several devices beside a chip's share of
the experts (a share runs its kernels on one device).  Not built: the
multi-token-prediction module (the published modelling code drops its
weights).

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the linear-attention hybrid MoE's benchmark cell
(`Qwen3-Next-80B-A3B-Instruct`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .gated_delta import GatedDeltaConfig, GatedDeltaMixer
from .llama import LlamaAttention, LlamaConfig, RMSNorm, rope_freqs
from .moe import MoEConfig, MoEMLP

KINDS = ("linear_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    # block i is `full_attention` iff (i + 1) % interval == 0
    full_attention_interval: int = 4
    max_seq_len: int = 262144
    rms_eps: float = 1e-6
    # `full_attention`: grouped heads, the first `rotary_dim` lanes of a
    # head rotated, an elementwise output gate out of q_proj
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 10000000.0
    # `linear_attention`: the gated delta rule, value heads over key heads
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    # the expert layer: softmax over num_experts, the top_k largest
    # renormalised to sum 1, SwiGLU experts of expert_width, one shared
    # expert of shared_width under a sigmoid gate a token
    num_experts: int = 512
    top_k: int = 10
    expert_width: int = 512
    shared_width: int = 512
    experts_held: int = 0
    first_expert: int = 0
    # a load-balancing term over all the router's experts (HF's
    # `load_balancing_loss_func`), the MEAN over the layers times this;
    # 0 = none
    router_aux_loss_weight: float = 0.0
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_layers=4, max_seq_len=64,
            num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
            linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
            linear_value_dim=8, chunk_size=16, num_experts=16, top_k=3,
            expert_width=32, shared_width=32), **over})

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            KINDS[(i + 1) % self.full_attention_interval == 0]
            for i in range(self.num_layers))

    def attention_config(self) -> LlamaConfig:
        """`LlamaAttention`'s config, and the counter of an expert
        layer's parameters (`ffn_params` with `moe` set)."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.expert_width, num_layers=self.num_layers,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            attn_head_dim=self.head_dim, max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta, rms_eps=self.rms_eps,
            dtype=self.dtype, use_flash_attention=self.use_flash_attention,
            mesh=self.mesh, qk_head_norm=True, norm_zero_centred=True,
            attn_out_gate=True, moe=self.moe_config())

    def linear_config(self) -> GatedDeltaConfig:
        return GatedDeltaConfig(
            hidden_size=self.hidden_size, num_heads=self.linear_value_heads,
            num_key_heads=self.linear_key_heads, neg_eigval=False,
            key_dim=self.linear_key_dim, value_dim=self.linear_value_dim,
            conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
            eps=self.rms_eps, dtype=self.dtype, mesh=self.mesh)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, impl="grouped",
            dtype=self.dtype, norm_topk_prob=True,
            aux_loss="topk" if self.router_aux_loss_weight else "none",
            aux_loss_weight=self.router_aux_loss_weight / self.num_layers,
            score_func="softmax", expert_act="swiglu",
            shared_width=self.shared_width, shared_gate=True,
            experts_held=self.experts_held, first_expert=self.first_expert,
            mesh=self.mesh)

    def num_params(self) -> int:
        h, llama = self.hidden_size, self.attention_config()
        mixer = {"linear_attention": self.linear_config().num_params(),
                 "full_attention": llama.attention_params()}
        return (2 * self.vocab_size * h + h  # table, head, the final norm
                + sum(mixer[kind] + llama.ffn_params() + 2 * h
                      for kind in self.layer_types))


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_eps, cfg.dtype, zero_centred=True,
                           name=name)

        x = pin_activation(x, cfg.mesh)
        h = norm("input_norm")(x)
        if self.kind == "linear_attention":
            out = GatedDeltaMixer(cfg.linear_config(),
                                  name="linear_attention")(h)
        else:
            out = LlamaAttention(cfg.attention_config(),
                                 name="attention")(h, cos, sin)
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(out, "attn_out")
        u = norm("post_attn_norm")(x)
        out = MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                     name="feed_forward")(u)
        return x + checkpoint_name(out, "mlp_out")


class Qwen3Next(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if cfg.experts_held and cfg.mesh is not None and cfg.mesh.size > 1:
            raise ValueError(
                "qwen3_next runs a chip's share of the experts on one "
                "device: a share has no route on a mesh")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        # tables `rotary_dim` wide: a head's first lanes turn, the rest pass
        cos, sin = rope_freqs(cfg.rotary_dim, idx.shape[1], cfg.rope_theta)
        x = stack.layers(Qwen3NextBlock, cfg,
                         [(kind,) for kind in cfg.layer_types], x, cos, sin)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, zero_centred=True,
                    name="norm")(x), cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 0):
        return stack.init_params(self, rng, batch,
                                 seq or self.config.chunk_size)
