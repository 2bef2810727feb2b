"""SmallThinker (`smallthinker`, arXiv:2507.20984): a Llama-shaped MoE
stack with two kinds of attention layer and its router in front of the
attention.

    x = embed[ids]
    for l in layers:
        h = RMSNorm(x)
        r = h @ W_router                      # the block's INPUT, normed
        x = x + attention_l(h)                # kind from the two layouts
        x = x + experts(RMSNorm(x), chosen by r)
    logits = RMSNorm(x) @ W_head              (untied)

`rope_layout[l]` says whether layer l rotates q and k, and
`sliding_window_layout[l]` whether a query sees only the
`sliding_window_size` keys that end at its own; as published the two are
one list, a GLOBAL layer without positions followed by three WINDOWED
ones with RoPE.  The experts are ReGLU, `(relu(u Wg) * (u Wu)) Wd`, the
k largest router logits are chosen and their softmax is the gates, no
shared expert, dropless.

Nothing here is a copy: the attention is `models/llama.py`'s
`LlamaAttention` under a per-layer `LlamaConfig` (`rope`, `attn_window`),
the norms its `RMSNorm`, the expert layer `models/moe.py`'s `MoEMLP` on
its grouped path with `expert_act="reglu"` and the router handed the
block's normalised input (`router_input`).  A chip's share of the
experts is `experts_held` / `first_expert`, as `models/nemotron_h.py`
has it.  Parameter names follow `models/llama.py` (`layers_<i>/
{input_norm,attention,post_attn_norm,feed_forward}`, `embed_tokens`,
`norm`, `lm_head`), so `parallel/sharding.py`'s rules bind unchanged.

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the windowed MoE's benchmark cell.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .llama import LlamaAttention, LlamaConfig, RMSNorm, rope_freqs
from .moe import MoEConfig, MoEMLP

_PERIOD = (0, 1, 1, 1)  # a global layer, then three windowed ones


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    # one entry a layer: 1 = the layer rotates q and k / sees a window
    rope_layout: Tuple[int, ...] = _PERIOD * 13
    sliding_window_layout: Tuple[int, ...] = _PERIOD * 13
    sliding_window_size: int = 4096
    max_seq_len: int = 16384
    rope_theta: float = 1500000.0
    rms_eps: float = 1e-6
    # the expert layer: ReGLU experts of `expert_width`, the router over
    # all `num_experts`, of which this chip holds `experts_held` from
    # `first_expert` on (0 = all)
    num_experts: int = 64
    top_k: int = 6
    expert_width: int = 768
    experts_held: int = 0
    first_expert: int = 0
    # OLMoE's top-k load-balancing term over all experts, the mean over
    # the layers; 0 = the cross-entropy alone (config.json has no key)
    aux_loss_weight: float = 0.0
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, rope_layout=_PERIOD, sliding_window_layout=_PERIOD,
            sliding_window_size=24, max_seq_len=64, num_experts=8, top_k=3,
            expert_width=32), **over})

    @property
    def num_layers(self) -> int:
        return len(self.rope_layout)

    def attention_config(self, layer: int) -> LlamaConfig:
        """`LlamaAttention`'s config for layer `layer`: its kind is two
        fields of it."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.expert_width, num_layers=self.num_layers,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            rms_eps=self.rms_eps, dtype=self.dtype,
            use_flash_attention=self.use_flash_attention, mesh=self.mesh,
            attn_head_dim=self.head_dim, rope=bool(self.rope_layout[layer]),
            attn_window=self.sliding_window_size
            if self.sliding_window_layout[layer] else 0)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, dtype=self.dtype,
            impl="grouped", norm_topk_prob=True,
            score_func="softmax", expert_act="reglu",
            aux_loss="topk" if self.aux_loss_weight else "none",
            aux_loss_weight=self.aux_loss_weight / self.num_layers,
            experts_held=self.experts_held, first_expert=self.first_expert,
            mesh=self.mesh)

    def num_params(self) -> int:
        h = self.hidden_size
        llama = dataclasses.replace(self.attention_config(0),
                                    moe=self.moe_config())
        per_layer = llama.attention_params() + llama.ffn_params() + 2 * h
        return 2 * self.vocab_size * h + self.num_layers * per_layer + h


class SmallThinkerBlock(nn.Module):
    config: SmallThinkerConfig
    layer: int

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        attn = LlamaAttention(cfg.attention_config(self.layer),
                              name="attention")(h, cos, sin)
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(attn, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        # the router's product is the expert layer's (scope `moe/router`)
        # though what it reads is h, from before the attention
        out = MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                     name="feed_forward")(u, router_input=h)
        return x + checkpoint_name(out, "mlp_out")


class SmallThinker(nn.Module):
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if len(cfg.sliding_window_layout) != cfg.num_layers:
            raise ValueError("rope_layout and sliding_window_layout have "
                             "one entry a layer each")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        x = stack.layers(SmallThinkerBlock, cfg,
                         [(i,) for i in range(cfg.num_layers)], x, cos, sin)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        return stack.init_params(self, rng, batch, seq)
