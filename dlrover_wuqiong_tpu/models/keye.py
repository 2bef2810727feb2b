"""`KeyeVL2`'s language model (Keye-VL-2.0-30B-A3B's shape): every block
a grouped-query attention whose queries each keep a LEARNED top-k choice
of the causal keys, and a softmax-routed expert layer with no shared
expert.

    x = embed[ids]
    for every layer:
        h = RMSNorm(x)
        q, k, v = h Wq, h Wk, h Wv       heads x d, kv heads x d, no bias
        q, k = RMSNorm_d(q), RMSNorm_d(k)     a head's lanes, one scale
        q, k rotated under M-RoPE        `mrope_sections` pairs a stream
        S_t = the indexer's top-k keys of query t  (models/sparse_indexer)
        x = x + concat_a(softmax_{S_t}(q_a k^T / sqrt d) v) Wo
        u = RMSNorm(x)
        x = x + sum_{e chosen, held} g_e swiglu_e(u)    softmax router,
                                         top-k renormalised, no shared
    logits = RMSNorm(x) W_head                                  (untied)
    loss   = cross-entropy + index_loss_weight * sum_layers L_I
             (+ router_aux_loss_weight * mean_layers balance term)

`L_I` is the indexer's KL to the attention's own distribution over S_t
(`ops/sparse_attention.py`); it reaches the indexer's leaves alone and
the cross-entropy none of them.  Positions are an optional (3, b, T)
operand — the temporal, height and width stream of M-RoPE; absent, the
three are `arange(T)` and the rotation is RoPE's (text).

`experts_held` / `first_expert` are a chip's share of the experts, as
`models/latent_moe.py` has them.  Nothing here is a copy: the attention
is `models/llama.py`'s `LlamaAttention` under `qk_head_norm` and
`attn_index_topk`, the norms its `RMSNorm`, the expert layer
`models/moe.py`'s `MoEMLP` on its grouped path, the loop and the head
`models/stack.py`'s.  Parameter names are `layers_<i>/{input_norm,
attention, post_attn_norm, feed_forward}`, `embed_tokens`, `norm`,
`lm_head`, so `parallel/sharding.py`'s rules bind.

Refused, not guessed: a mesh of several devices (the choice over a
sharded sequence and a share of the experts have no route there).  Not
built: the vision tower — the published `config.json`'s language model
alone.

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the sparse-attention benchmark cell
(`Keye-VL-2.0-30B-A3B`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .llama import (
    LlamaAttention,
    LlamaConfig,
    RMSNorm,
    mrope_tables,
    rope_freqs,
)
from .moe import MoEConfig, MoEMLP


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    max_seq_len: int = 262144
    rope_theta: float = 10000000.0
    mrope_sections: Tuple[int, ...] = (16, 24, 24)
    rms_eps: float = 1e-6
    # the indexer (`sa_config`): heads of index_dim over ONE key, top-k
    index_topk: int = 2048
    index_heads: int = 16
    index_dim: int = 64
    index_loss_weight: float = 1.0
    # the expert layer: softmax over num_experts, the top_k largest
    # renormalised to sum 1, SwiGLU experts of expert_width, none shared
    num_experts: int = 128
    top_k: int = 8
    expert_width: int = 768
    experts_held: int = 0
    first_expert: int = 0
    # a load-balancing term over all the router's experts (OLMoE's / HF's
    # `load_balancing_loss_func`), the MEAN over the layers times this;
    # 0 = none (the published config has no coefficient)
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
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, max_seq_len=64,
            mrope_sections=(2, 2, 4), index_topk=16, index_heads=2,
            index_dim=8, num_experts=16, top_k=3, expert_width=32), **over})

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
            mesh=self.mesh, qk_head_norm=True, moe=self.moe_config(),
            attn_index_topk=self.index_topk,
            attn_index_heads=self.index_heads,
            attn_index_dim=self.index_dim,
            attn_index_loss_weight=self.index_loss_weight)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, impl="grouped",
            dtype=self.dtype, norm_topk_prob=True,
            aux_loss="topk" if self.router_aux_loss_weight else "none",
            aux_loss_weight=self.router_aux_loss_weight / self.num_layers,
            score_func="softmax", expert_act="swiglu",
            experts_held=self.experts_held, first_expert=self.first_expert,
            mesh=self.mesh)

    def num_params(self) -> int:
        return self.attention_config().num_params()

    def rope_tables(self, seq: int, positions=None) -> tuple:
        """((cos, sin) of the main heads, (cos, sin) of the indexer's):
        `seq` rows of RoPE's tables, or under `positions` (3, b, T) each
        sequence's own M-RoPE angles."""
        widths = (self.head_dim, self.index_dim)
        if positions is None:
            return tuple(rope_freqs(d, seq, self.rope_theta) for d in widths)
        return tuple(mrope_tables(d, self.rope_theta, self.mrope_sections,
                                  positions) for d in widths)


class KeyeBlock(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, x, cos, sin, idx_cos, idx_sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        llama = cfg.attention_config()
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        out = LlamaAttention(llama, name="attention")(
            h, cos, sin, (idx_cos, idx_sin))
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(out, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        out = MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                     name="feed_forward")(u)
        return x + checkpoint_name(out, "mlp_out")


class Keye(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, idx, positions=None):
        cfg = self.config
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise ValueError(
                "keye runs on one device: a learned choice of keys and a "
                "chip's share of the experts have no route on a mesh")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        main, index = cfg.rope_tables(idx.shape[1], positions)
        x = stack.layers(KeyeBlock, cfg, [()] * cfg.num_layers, x, *main,
                         *index)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        # a few tokens: nothing here needs a whole block of anything
        return stack.init_params(self, rng, batch, seq)
