"""`bailing_hybrid`: a hybrid stack whose blocks take their MIXER and
their FEED-FORWARD from per-layer kinds (Ling-3.0-flash's shape): the
mixer of block l is latent attention (`models/latent_attention.py`, with
its head-wise output gate) where (l + 1) % `layer_group_size` == 0 and a
KDA mixer (`models/kda.py`: a delta rule with a decay a channel)
elsewhere; the feed-forward of the first `first_dense_layers` blocks is a
wide SwiGLU and of every later one an expert layer with a sigmoid router,
a selection bias, a GROUP LIMIT on the choice (`MoEConfig.n_group` /
`topk_group`), normalised and scaled gates, SwiGLU experts and a SwiGLU
shared expert on every token.

    x = embed[ids]
    for l in layers:
        x = x + mixer_l(RMSNorm(x))            KDA | gated latent attention
        u = RMSNorm(x)
        x = x + (swiglu_dense(u) if l < first_dense_layers
                 else sum_{e chosen, held} g_e swiglu_e(u) + swiglu_shared(u))
    logits = RMSNorm(x) @ W_head                              (untied)

`num_heads` is how many heads of EVERY mixer are held here (a chip's
share of the published count), `experts_held` / `first_expert` its share
of the experts, as `models/latent_moe.py` has them.  Nothing here is a
copy: the mixers are `KDAMixer` and `LatentAttention`, the norms
`models/llama.py`'s `RMSNorm`, the dense feed-forward its `LlamaMLP`, the
expert layer `models/moe.py`'s `MoEMLP` on its grouped path.  Parameter
names are `layers_<i>/{input_norm, linear_attention | attention,
post_attn_norm, feed_forward}`, `embed_tokens`, `norm`, `lm_head`, so
`parallel/sharding.py`'s rules bind; the selection bias is named in
`untrained_params` and its out-of-band rule is `bias_update_rate`.

Refused, not guessed: a clamp on an expert's SwiGLU (`swiglu_limits`
holds a non-zero entry: the published model has one from layer 34 on,
and its form is not in the config), a multi-token-prediction module with
a weight in the loss (`mtp_layers` > 0 and `mtp_loss_weight` != 0; at
the published weight 0 it adds nothing to the loss or to any gradient,
and nothing is built for it), and a mesh of several devices (the delta
rule's and the two-width attention's routes on a mesh are not built).

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the KDA hybrid's benchmark cell (`Ling-3.0-flash`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .kda import KDAConfig, KDAMixer
from .latent_attention import LatentAttention, LatentAttentionConfig
from .llama import LlamaConfig, LlamaMLP, RMSNorm, rope_freqs
from .moe import MoEConfig, MoEMLP

MIXERS = ("linear_attention", "attention")


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_layers: int = 42
    # block l is latent attention where (l + 1) % layer_group_size == 0
    layer_group_size: int = 6
    # the leading blocks whose feed-forward is one SwiGLU of dense_width
    first_dense_layers: int = 2
    dense_width: int = 6144
    # the heads HELD here, of every mixer
    num_heads: int = 32
    # KDA
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    kda_lower_bound: float = -5.0
    # latent attention
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    max_seq_len: int = 262144
    rope_theta: float = 6000000.0
    rms_eps: float = 1e-6
    # the expert layer (`models/latent_moe.py`'s fields) under a group
    # limit: the router's num_experts in n_group groups, topk_group kept
    num_experts: int = 512
    top_k: int = 8
    expert_width: int = 768
    shared_experts: int = 1
    routed_scaling: float = 2.5
    n_group: int = 8
    topk_group: int = 4
    experts_held: int = 0
    first_expert: int = 0
    bias_update_rate: float = 0.0
    # refused off zero (see the module docstring)
    swiglu_limits: Tuple[float, ...] = ()
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.0
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_layers=4,
            layer_group_size=3, first_dense_layers=1, dense_width=96,
            num_heads=4, linear_key_dim=16, linear_value_dim=16,
            chunk_size=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=24, max_seq_len=64, num_experts=16,
            top_k=3, expert_width=32, n_group=4, topk_group=2), **over})

    def mixer_kind(self, layer: int) -> str:
        return MIXERS[(layer + 1) % self.layer_group_size == 0]

    def attention_config(self) -> LatentAttentionConfig:
        return LatentAttentionConfig(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, kv_lora_rank=self.kv_lora_rank,
            attn_gate=True, rms_eps=self.rms_eps, dtype=self.dtype,
            use_flash_attention=self.use_flash_attention, mesh=self.mesh)

    def linear_config(self) -> KDAConfig:
        return KDAConfig(
            hidden_size=self.hidden_size, num_heads=self.num_heads,
            key_dim=self.linear_key_dim, value_dim=self.linear_value_dim,
            conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
            lower_bound=self.kda_lower_bound, eps=self.rms_eps,
            dtype=self.dtype, mesh=self.mesh)

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
            n_group=self.n_group, topk_group=self.topk_group,
            expert_act="swiglu",
            shared_width=self.shared_experts * self.expert_width,
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=self.bias_update_rate, mesh=self.mesh)

    def moe_ffn_params(self) -> int:
        """An expert layer's: router, held experts, bias, shared expert."""
        return dataclasses.replace(
            self.dense_config(), moe=self.moe_config(),
            intermediate_size=self.expert_width).ffn_params()

    def num_params(self) -> int:
        h = self.hidden_size
        dense, sparse = self.dense_config().ffn_params(), \
            self.moe_ffn_params()
        mixer = {"linear_attention": self.linear_config().num_params(),
                 "attention": self.attention_config().attention_params()}
        return (2 * self.vocab_size * h + h  # table, head, the final norm
                + sum(mixer[self.mixer_kind(i)] + 2 * h
                      + (dense if i < self.first_dense_layers else sparse)
                      for i in range(self.num_layers)))


class BailingHybridBlock(nn.Module):
    config: BailingHybridConfig
    layer: int

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        if cfg.mixer_kind(self.layer) == "linear_attention":
            out = KDAMixer(cfg.linear_config(), name="linear_attention")(h)
        else:
            out = LatentAttention(cfg.attention_config(), name="attention")(
                h, cos, sin)
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(out, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        if self.layer < cfg.first_dense_layers:
            out = LlamaMLP(cfg.dense_config(), name="feed_forward")(u)
        else:
            out = MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                         name="feed_forward")(u)
        return x + checkpoint_name(out, "mlp_out")


class BailingHybrid(nn.Module):
    config: BailingHybridConfig

    # the selection bias has no gradient, its rule runs out of band
    untrained_params = (r"layers_\d+/feed_forward/selection_bias",)

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if any(cfg.swiglu_limits):
            raise ValueError(
                f"swiglu_limits={cfg.swiglu_limits!r}: a clamp on an "
                f"expert's SwiGLU is not built (its form is not published)")
        if cfg.mtp_layers and cfg.mtp_loss_weight:
            raise ValueError(
                "a multi-token-prediction module with a weight in the loss "
                "is models/latent_moe.py's, not built on this stack")
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise ValueError(
                "bailing_hybrid runs on one device: the channel-decay delta "
                "rule and the two-width attention have no route on a mesh")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        # the rotated part alone carries the positions
        cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len,
                              cfg.rope_theta, None)
        x = stack.layers(BailingHybridBlock, cfg,
                         [(i,) for i in range(cfg.num_layers)], x, cos, sin)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        # a few tokens: the recurrence draws on its sequential route,
        # which traces in a fraction of the chunked form's time
        return stack.init_params(self, rng, batch, seq)
