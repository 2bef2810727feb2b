"""Laguna (`laguna`, poolside's Laguna-XS.2 / S-2.1 config.json): a
Llama-shaped MoE stack whose attention layers come in two kinds with two
head counts, each with a gate on its output.

    x = embed[ids]
    for l in layers:
        h = RMSNorm(x)
        a = attention_l(h)          # kind and head count from the lists
        g = sigmoid(h @ W_gate_l)   # one number a head and token
        x = x + (g * a) @ W_o_l
        u = RMSNorm(x)
        x = x + (swiglu_dense(u) if mlp_layer_types[l] == "dense"
                 else 2.5 * sum_{e chosen, held} w_e swiglu_e(u)
                      + swiglu_shared(u))
    logits = RMSNorm(x) @ W_head                              (untied)

`layer_types[l]` is `full_attention` — a query sees every earlier key,
the FIRST `full_rotary_factor` of a head's features rotate by YaRN-scaled
tables (`full_rope_theta`, `full_rope_scaling`, computed over the rotary
width), the rest pass — or `sliding_attention` — a query sees the
`sliding_window` keys that end at its own and the whole head rotates by
plain tables (`sliding_rope_theta`).  `num_heads_per_layer[l]` query
heads share `num_kv_heads` key/value heads.  The router is a softmax
over all `num_experts`; the `top_k` largest are renormalised and scaled
by `routed_scaling`; one shared expert runs on every token.

Nothing here is a copy: the attention is `models/llama.py`'s
`LlamaAttention` under a per-layer `LlamaConfig` (`num_heads`,
`attn_window`, `attn_gate`), handed its kind's pair of the two rotation
tables this model builds (a full layer's half as wide as its head's); the norms are its `RMSNorm`, the
dense feed-forward its `LlamaMLP`, the expert layer `models/moe.py`'s
`MoEMLP` on its grouped path (`shared_width`, `routed_scaling`).  A
chip's share of the experts is `experts_held` / `first_expert`, as
`models/nemotron_h.py` has it.  Parameter names follow `models/llama.py`
(`layers_<i>/{input_norm,attention,post_attn_norm,feed_forward}`,
`embed_tokens`, `norm`, `lm_head`; the gate is `attention/g_proj`), so
`parallel/sharding.py`'s rules bind.  A windowed layer also sows
`attn_pairs` (`models/attention.window_pairs`: the pairs its window
keeps beside the pairs its kernels' tiles hold).

Not built: a QK-norm, a gate on the shared expert, a selection bias, a
soft cap on the router's logits (no key of the XS.2 config names one), a
server's two kinds of cache.

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the gated two-kind attention's benchmark cell.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .attention import window_pairs
from .llama import (
    LlamaAttention,
    LlamaConfig,
    LlamaMLP,
    RMSNorm,
    RopeScaling,
    rope_freqs,
)
from .moe import MoEConfig, MoEMLP

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    # one entry a layer: its attention's kind, its query heads, and
    # whether its feed-forward is the dense SwiGLU or the expert layer
    layer_types: Tuple[str, ...] = _PERIOD * 10
    num_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    attn_gate: bool = True  # sigmoid(h @ g_proj), a number a head
    max_seq_len: int = 262144
    # the two rotations: a full layer's first features under YaRN, a
    # sliding layer's whole head unscaled
    full_rope_theta: float = 500000.0
    full_rope_scaling: Optional[RopeScaling] = RopeScaling(
        factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
        beta_slow=1.0)
    full_rotary_factor: float = 0.5
    sliding_rope_theta: float = 10000.0
    sliding_rotary_factor: float = 1.0
    rms_eps: float = 1e-6
    dense_width: int = 8192
    # the expert layer: SwiGLU experts of `expert_width`, the router over
    # all `num_experts`, of which this chip holds `experts_held` from
    # `first_expert` on (0 = all); one shared SwiGLU of `shared_width`
    num_experts: int = 256
    top_k: int = 8
    expert_width: int = 512
    shared_width: int = 512
    routed_scaling: float = 2.5
    experts_held: int = 0
    first_expert: int = 0
    # OLMoE's top-k load-balancing term over all experts, the mean over
    # the expert layers; 0 = the cross-entropy alone (config.json has no
    # key)
    aux_loss_weight: float = 0.0
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        """Five layers in the published pattern: a dense full layer,
        three sliding ones and a full one, 6 and 8 heads over 2."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64,
            layer_types=_PERIOD + (FULL,),
            num_heads_per_layer=(6, 8, 8, 8, 6),
            mlp_layer_types=(DENSE,) + (SPARSE,) * 4, num_kv_heads=2,
            head_dim=16, sliding_window=24, max_seq_len=64,
            full_rope_theta=100.0, full_rope_scaling=RopeScaling(
                factor=16.0, original_max_position_embeddings=16,
                beta_fast=2.0, beta_slow=0.25),
            dense_width=96, num_experts=8, top_k=3, expert_width=32,
            shared_width=32), **over})

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def sparse_layers(self) -> int:
        return sum(kind == SPARSE for kind in self.mlp_layer_types)

    def rotary_dim(self, kind: str) -> int:
        """How many of a head's features a layer of `kind` rotates."""
        factor = self.full_rotary_factor if kind == FULL \
            else self.sliding_rotary_factor
        return int(self.head_dim * factor)

    def attention_config(self, layer: int) -> LlamaConfig:
        """`LlamaAttention`'s config for layer `layer`: its kind and its
        head count are fields of it (and `LlamaMLP`'s, by the width)."""
        kind = self.layer_types[layer]
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.dense_width, num_layers=self.num_layers,
            num_heads=self.num_heads_per_layer[layer],
            num_kv_heads=self.num_kv_heads, max_seq_len=self.max_seq_len,
            rms_eps=self.rms_eps, dtype=self.dtype,
            use_flash_attention=self.use_flash_attention, mesh=self.mesh,
            attn_head_dim=self.head_dim, attn_gate=self.attn_gate,
            attn_window=self.sliding_window if kind == SLIDING else 0)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, dtype=self.dtype,
            impl="grouped", norm_topk_prob=True, score_func="softmax",
            routed_scaling=self.routed_scaling, expert_act="swiglu",
            shared_width=self.shared_width,
            aux_loss="topk" if self.aux_loss_weight else "none",
            aux_loss_weight=self.aux_loss_weight
            / max(self.sparse_layers, 1),
            experts_held=self.experts_held, first_expert=self.first_expert,
            mesh=self.mesh)

    def rope_tables(self, seq: int) -> tuple:
        """(cos, sin) of the full layers, (cos, sin) of the sliding ones,
        `seq` rows each: a table is as wide as HALF the features its
        kind rotates, and YaRN's ramp is computed over that width."""
        return (rope_freqs(self.rotary_dim(FULL), seq, self.full_rope_theta,
                           self.full_rope_scaling),
                rope_freqs(self.rotary_dim(SLIDING), seq,
                           self.sliding_rope_theta))

    def num_params(self) -> int:
        h = self.hidden_size
        total = 2 * self.vocab_size * h + h
        for layer, kind in enumerate(self.mlp_layer_types):
            llama = self.attention_config(layer)
            if kind == SPARSE:
                llama = dataclasses.replace(
                    llama, moe=self.moe_config(),
                    intermediate_size=self.expert_width)
            total += llama.attention_params() + llama.ffn_params() + 2 * h
        return total


class LagunaBlock(nn.Module):
    config: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x, full_rope, sliding_rope):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        attention = cfg.attention_config(self.layer)
        windowed = cfg.layer_types[self.layer] == SLIDING
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        pairs = window_pairs(attention, x.shape[0], attention.num_heads,
                             x.shape[1])
        if pairs is not None:  # counted, not timed (static numbers)
            self.sow("intermediates", "attn_pairs",
                     jnp.asarray(pairs, jnp.float32))
        attn = LlamaAttention(attention, name="attention")(
            h, *(sliding_rope if windowed else full_rope))
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(attn, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        if cfg.mlp_layer_types[self.layer] == DENSE:
            out = LlamaMLP(attention, name="feed_forward")(u)
        else:
            out = MoEMLP(cfg.hidden_size, cfg.expert_width,
                         cfg.moe_config(), name="feed_forward")(u)
        return x + checkpoint_name(out, "mlp_out")


class Laguna(nn.Module):
    config: LagunaConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        kinds = set(cfg.layer_types) | set(cfg.mlp_layer_types)
        if len(cfg.num_heads_per_layer) != cfg.num_layers \
                or len(cfg.mlp_layer_types) != cfg.num_layers \
                or kinds - {FULL, SLIDING, DENSE, SPARSE}:
            raise ValueError("layer_types, mlp_layer_types and "
                             "num_heads_per_layer have one entry a layer")
        if idx.shape[1] > cfg.max_seq_len:
            raise ValueError(f"a sequence of {idx.shape[1]} is longer than "
                             f"the {cfg.max_seq_len} positions")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        # the rows the sequence reads, not max_seq_len's: 262,144 rows of
        # two float32 tables are 200 MB of a step's temporaries
        full_rope, sliding_rope = cfg.rope_tables(idx.shape[1])
        x = stack.layers(LagunaBlock, cfg,
                         [(i,) for i in range(cfg.num_layers)], x,
                         full_rope, sliding_rope)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        return stack.init_params(self, rng, batch, seq)
