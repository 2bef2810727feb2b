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

With `residual_lanes` n > 1 (Xing4.0's `hc_mult`) the stream between the
blocks is n lanes of the hidden size, carried (b, n, T, d), and each of
a block's two sublayers sits in a manifold-constrained hyper-connection
(`models/hyper_connection.py`: its own `attention_hc` / `feed_forward_hc`
leaves): X_0 is the embedding in every lane, a sublayer reads u = sum_i
h_pre[i] X[i] and writes X'[i] = sum_j H_res[i, j] X[j] + h_post[i] F(u),
and the lanes' SUM goes to the last norm.  `q_lora_rank` gives q a latent
of its own, `rope_scaling` YaRN's tables and the softmax its m^2
(`models/llama.py::RopeScaling`).  `mtp_layers` 1 puts DeepSeek-V3's
multi-token-prediction module (arXiv:2412.19437, section 2.2) behind the
trunk, `mtp_0/{hnorm, enorm, eh_proj, block_0, norm}`:

    g      = [RMSNorm(x_t) ; RMSNorm(embed[id_{t+1}])] W_eh    (2 d -> d)
    logits2 = RMSNorm(block(g)) @ W_head       the SAME table and head

with x_t the trunk's summed, un-normed output; the model sows
`mtp_logits` and `mtp_loss_weight`, and `collect_mtp_loss`, the term
this file registers with `models/sown.py`, adds lambda x the
cross-entropy of logits2[t] against id_{t+2} (`labels[:, t + 1]`; the
last position has none) to the loss.  id_{t+1} is read off the ids
the model is given, shifted by one (`labels[:, t]` for every t the term
counts).

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
go in), a sequence-wise auxiliary loss (a limit on the groups of experts
a token may choose from is `MoEConfig.n_group` / `topk_group` since
PR 57; this stack's configurations have none and pass 1 / 1), several lanes on a mesh (the (b, n, T, d) carry has no
pins: refused), two widths of attention on a mesh, a server's cache of
latents.

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the latent-attention MoE's benchmark cell.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import hyper_connection as hc
from . import stack
from .latent_attention import LatentAttention, LatentAttentionConfig
from .llama import LlamaConfig, LlamaMLP, RMSNorm, RopeScaling, rope_freqs
from .moe import MoEConfig, MoEMLP
from .sown import sown, term


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
    q_lora_rank: Optional[int] = None  # a latent for q as well
    max_seq_len: int = 131072
    rope_theta: float = 800000.0
    rope_scaling: Optional[RopeScaling] = None  # YaRN
    rms_eps: float = 1e-5
    # the residual stream: lanes (1 = x + branch), mixed by manifold-
    # constrained hyper-connections (models/hyper_connection.py)
    residual_lanes: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # multi-token prediction: modules behind the trunk (0 or 1) and the
    # weight of their cross-entropy in the loss
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
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

    def hyper_config(self) -> hc.HyperConnectionConfig:
        return hc.HyperConnectionConfig(
            hidden_size=self.hidden_size, lanes=self.residual_lanes,
            sinkhorn_iters=self.hc_sinkhorn_iters, eps=self.hc_eps,
            norm_eps=self.rms_eps, clamp=self.hc_res_clamp)

    def attention_config(self) -> LatentAttentionConfig:
        scaling = self.rope_scaling
        return LatentAttentionConfig(
            attn_scale=(self.qk_nope_head_dim + self.qk_rope_head_dim)
            ** -0.5 * scaling.softmax_mscale ** 2 if scaling else 0.0,
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
        if self.residual_lanes > 1:
            per_block += 2 * self.hyper_config().num_params()
        # an MTP module: two norms, the joining product, an expert block,
        # its last norm
        mtp = self.mtp_layers * (3 * h + 2 * h * h + per_block
                                 + experts.ffn_params())
        return (2 * self.vocab_size * h + h + self.num_layers * per_block
                + n_dense * dense.ffn_params()
                + (self.num_layers - n_dense) * experts.ffn_params() + mtp)


class LatentMoEBlock(nn.Module):
    config: LatentMoEConfig
    layer: int

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        if cfg.residual_lanes > 1:
            return self._lanes(x, cos, sin)
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        attn = LatentAttention(cfg.attention_config(), name="attention")(
            h, cos, sin)
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(attn, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        return x + checkpoint_name(self._feed_forward(u), "mlp_out")

    @nn.nowrap
    def _feed_forward(self, u):
        cfg = self.config
        if self.layer < cfg.first_dense_layers:
            return LlamaMLP(cfg.dense_config(), name="feed_forward")(u)
        return MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                      name="feed_forward")(u)

    @nn.nowrap
    def _lanes(self, x, cos, sin):
        """x (b, n, T, d): each sublayer between a hyper-connection's
        read and its write."""
        from jax.ad_checkpoint import checkpoint_name

        cfg, hyper = self.config, self.config.hyper_config()

        def around(x, name, norm, branch, anchor):
            leaves = hc.HyperConnection(hyper, name=f"{name}_hc")()
            u, x, h_post, h_res = hc.mix_in(leaves, x, hyper, cfg.mesh)
            self.sow("intermediates", "hc_sinkhorn_err",
                     jax.lax.stop_gradient(hc.sinkhorn_err(h_res)))
            u = RMSNorm(cfg.rms_eps, cfg.dtype, name=norm)(u)
            return hc.mix_out(h_res, h_post, x,
                              checkpoint_name(branch(u), anchor), cfg.mesh)

        x = around(x, "attention", "input_norm",
                   lambda u: LatentAttention(
                       cfg.attention_config(), name="attention")(u, cos, sin),
                   "attn_out")
        return around(x, "feed_forward", "post_attn_norm",
                      self._feed_forward, "mlp_out")


class MTPModule(nn.Module):
    """One multi-token-prediction module: the trunk's un-normed output
    and the next token's embedding, each normed, joined by one product,
    through one more expert block, to its own last norm."""
    config: LatentMoEConfig
    layer: int  # the block's index in the depth (an expert layer)

    @nn.compact
    def __call__(self, x, next_embedding, cos, sin):
        cfg = self.config
        g = jnp.concatenate(
            [RMSNorm(cfg.rms_eps, cfg.dtype, name="hnorm")(x),
             RMSNorm(cfg.rms_eps, cfg.dtype, name="enorm")(next_embedding)],
            axis=-1)
        g = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                     name="eh_proj")(g)
        g = _through(cfg, [(self.layer,)], g, cos, sin, prefix="block")
        return RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(g)


def _through(cfg, per_layer, x, cos, sin, prefix: str = "layers"):
    """x (b, T, d) through the blocks: as it is with one lane; with
    several, every lane starts as x and their sum comes back."""
    if cfg.residual_lanes > 1:
        x = hc.expand(x, cfg.residual_lanes)
    x = stack.layers(LatentMoEBlock, cfg, per_layer, x, cos, sin,
                     prefix=prefix)
    return hc.read_out(x) if cfg.residual_lanes > 1 else x


class LatentMoE(nn.Module):
    config: LatentMoEConfig

    # leaves the optimizer leaves alone, as models/nemotron_h.py's: the
    # selection bias has no gradient, its rule runs out of band
    untrained_params = (
        r"(layers|mtp_\d+/block)_\d+/feed_forward/selection_bias",)

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if cfg.residual_lanes > 1 and cfg.mesh is not None \
                and cfg.mesh.size > 1:
            raise ValueError(
                f"residual_lanes={cfg.residual_lanes}: the (b, n, T, d) "
                f"stream runs on one device (no pins on a mesh)")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed_tokens")
        x = embed(idx)
        # the rotated part alone carries the positions
        cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len,
                              cfg.rope_theta, cfg.rope_scaling)
        x = _through(cfg, [(i,) for i in range(cfg.num_layers)], x, cos, sin)
        if not cfg.mtp_layers:
            return stack.untied_head(
                RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
                cfg.vocab_size, cfg.dtype)
        if cfg.mtp_layers != 1:
            raise ValueError("one multi-token-prediction module or none")
        # id_{t+1} off the ids, shifted; the last position's is masked out
        # of the term, so what stands there is never read
        following = jnp.concatenate([idx[:, 1:], idx[:, -1:]], axis=1)
        second = MTPModule(cfg, cfg.num_layers, name="mtp_0")(
            x, embed(following), cos, sin)
        logits, mtp_logits = stack.untied_heads(
            [RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x), second],
            cfg.vocab_size, cfg.dtype)
        self.sow("intermediates", "mtp_logits", mtp_logits)
        self.sow("intermediates", "mtp_loss_weight",
                 jnp.float32(cfg.mtp_loss_weight))
        return logits

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        return stack.init_params(self, rng, batch, seq)


@term
def collect_mtp_loss(intermediates, batch, ce):
    """The loss's term of a forward pass that sowed `mtp_logits`, or
    None: lambda x the second prediction's cross-entropy, and that
    cross-entropy as `mtp_ce` — position t's second logits against
    `labels[:, t + 1]`, the last position left out."""
    from .gpt import cross_entropy_loss

    logits = list(sown(intermediates, "mtp_logits"))
    if not logits:
        return None
    weight, = sown(intermediates, "mtp_loss_weight")
    labels = batch["labels"]
    targets = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    mtp_ce = cross_entropy_loss(logits[0], targets)
    return weight.reshape(()) * mtp_ce, {"mtp_ce": mtp_ce}
