"""A latent-attention MoE stack (the DeepSeek-V2 family's shape, as
Kimi-VL-A3B's language model publishes it): every block's attention is
`models/latent_attention.py`'s, the first `first_dense_layers` blocks'
feed-forward is a wide SwiGLU and every later one's an expert layer with
a sigmoid router, a selection bias, normalised and scaled gates, SwiGLU
experts and a SwiGLU shared expert on every token.

    x = embed[ids]
    for l in layers:
        x = x + latent_attention(RMSNorm(x))
        u = RMSNorm(x)
        x = x + (swiglu_dense(u) if l < first_dense_layers
                 else sum_{e chosen, held} g_e swiglu_e(u) + swiglu_shared(u))
    logits = RMSNorm(x) @ W_head                              (untied)

Nothing here is a copy: the attention is `LatentAttention`, the norms
`models/llama.py`'s `RMSNorm`, the dense feed-forward its `LlamaMLP`, the
expert layer `models/moe.py`'s `MoEMLP` on its grouped path
(`score_func="sigmoid"`, `selection_bias`, `routed_scaling`,
`expert_act="swiglu"` for the routed experts and the shared one).  A
chip's share of the experts is `experts_held` / `first_expert`, as
`models/nemotron_h.py` has it; the selection bias is named in
`untrained_params` and its out-of-band rule is `bias_update_rate`.
Parameter names follow `models/llama.py` (`layers_<i>/{input_norm,
attention,post_attn_norm,feed_forward}`, `embed_tokens`, `norm`,
`lm_head`), so `parallel/sharding.py`'s rules bind.

Not built: a vision or audio tower in front of the embedding (text ids
go in), a limit on the groups of experts a token may choose from
(`n_group` = `topk_group` = 1 is the only form), a sequence-wise
auxiliary loss.

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the latent-attention MoE's benchmark cell.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .latent_attention import LatentAttention, LatentAttentionConfig
from .llama import LlamaConfig, LlamaMLP, RMSNorm, rope_freqs
from .moe import MoEConfig, MoEMLP


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    num_layers: int = 27
    # the leading blocks whose feed-forward is one SwiGLU of dense_width
    first_dense_layers: int = 1
    dense_width: int = 11264
    # latent attention
    num_heads: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None  # LatentAttention refuses one
    max_seq_len: int = 131072
    rope_theta: float = 800000.0
    rms_eps: float = 1e-5
    # the expert layer: SwiGLU experts of `expert_width`, the router over
    # all `num_experts`, of which this chip holds `experts_held` from
    # `first_expert` on (0 = all); a shared SwiGLU of shared_experts x
    # expert_width on every token
    num_experts: int = 64
    top_k: int = 6
    expert_width: int = 1408
    shared_experts: int = 2
    routed_scaling: float = 2.446
    experts_held: int = 0
    first_expert: int = 0
    # the selection bias's out-of-band rule (MoEConfig.bias_update_rate);
    # 0 = not run
    bias_update_rate: float = 0.0
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_layers=3, dense_width=96,
            num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=24, max_seq_len=64, num_experts=8,
            top_k=3, expert_width=32), **over})

    def attention_config(self) -> LatentAttentionConfig:
        return LatentAttentionConfig(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, kv_lora_rank=self.kv_lora_rank,
            q_lora_rank=self.q_lora_rank, rms_eps=self.rms_eps,
            dtype=self.dtype, use_flash_attention=self.use_flash_attention,
            mesh=self.mesh)

    def dense_config(self) -> LlamaConfig:
        """`LlamaMLP`'s config for a leading block, and the counter of
        an expert layer's parameters (`ffn_params` with `moe` set)."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.dense_width, num_layers=self.num_layers,
            rms_eps=self.rms_eps, dtype=self.dtype, mesh=self.mesh)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, impl="grouped",
            dtype=self.dtype, norm_topk_prob=True,
            aux_loss="none", aux_loss_weight=0.0, score_func="sigmoid",
            selection_bias=True, routed_scaling=self.routed_scaling,
            expert_act="swiglu",
            shared_width=self.shared_experts * self.expert_width,
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=self.bias_update_rate, mesh=self.mesh)

    def num_params(self) -> int:
        h = self.hidden_size
        dense = self.dense_config()
        experts = dataclasses.replace(
            dense, moe=self.moe_config(), intermediate_size=self.expert_width)
        n_dense = min(self.first_dense_layers, self.num_layers)
        per_block = self.attention_config().attention_params() + 2 * h
        return (2 * self.vocab_size * h + h + self.num_layers * per_block
                + n_dense * dense.ffn_params()
                + (self.num_layers - n_dense) * experts.ffn_params())


class LatentMoEBlock(nn.Module):
    config: LatentMoEConfig
    layer: int

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        attn = LatentAttention(cfg.attention_config(), name="attention")(
            h, cos, sin)
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(attn, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        if self.layer < cfg.first_dense_layers:
            out = LlamaMLP(cfg.dense_config(), name="feed_forward")(u)
        else:
            out = MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                         name="feed_forward")(u)
        return x + checkpoint_name(out, "mlp_out")


class LatentMoE(nn.Module):
    config: LatentMoEConfig

    # leaves the optimizer leaves alone, as models/nemotron_h.py's: the
    # selection bias has no gradient, its rule runs out of band
    untrained_params = (r"layers_\d+/feed_forward/selection_bias",)

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        # the rotated part alone carries the positions
        cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len,
                              cfg.rope_theta)
        x = stack.layers(LatentMoEBlock, cfg,
                         [(i,) for i in range(cfg.num_layers)], x, cos, sin)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        return stack.init_params(self, rng, batch, seq)
