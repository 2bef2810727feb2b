"""`SDAR` (SDAR-30B-A3B-Chat's shape, `sdar_moe`): a Qwen3-MoE trunk
trained by DIFFUSION OVER BLOCKS — a clean and a noised copy of every
sequence through the same weights under a static block mask, a loss on
the masked positions weighed by 1 / t (BD3-LMs, arXiv:2503.09573,
sections 3-4 and its vectorised training, which SDAR, arXiv:2510.06303,
adopts).

    on a sequence x_0 .. x_{T-1}, blocks of L, b(i) = i // L:
    t_b ~ eps + (1 - eps) U(0, 1)        one a block and sequence
    m_i ~ Bernoulli(t_{b(i)})            masked or not
    xn_i = MASK if m_i else x_i          the noised copy
    ids  = [x ; xn]       2T positions   position ids [0..T-1 ; 0..T-1]
    for every layer, over the 2T positions:
        h = RMSNorm(x)
        q, k, v = h Wq, h Wk, h Wv       heads x d, kv heads x d, no bias
        q, k = RMSNorm_d(q), RMSNorm_d(k)     a head's lanes, one scale
        q, k rotated by POSITION ID (RoPE, all d lanes)
        K(clean i)  = { clean j : b(j) <= b(i) }
        K(noised i) = { clean j : b(j) < b(i) } u { noised j : b(j) = b(i) }
        x = x + concat_a(softmax_{K(i)}(q_a k^T / sqrt d) v) Wo
        u = RMSNorm(x)
        x = x + sum_{e chosen, held} g_e swiglu_e(u)   softmax router,
                                         top-k renormalised, no shared
    logits = RMSNorm(x[noised copy]) W_head      T rows, untied
    loss = (1 / T) sum_i m_i / t_{b(i)} * -log softmax(logits_i)[x_i]
           (+ router_aux_loss_weight * mean_layers balance term)

The prediction is UNSHIFTED: the noised copy's position i names token i.
The draw is a pure function of the batch: a sequence's key is
`fold_in(key(noise_seed), h)`, h a 32-bit hash of the sequence's own ids
(`sequence_hash`), so repeats of a sequence in one batch draw alike and a
resumed worker draws what the dead one would have; it sits under the
scope `diffusion/noise`.  The model hands the step its OBJECTIVE through
`models/sown.objective` — the targets are the inputs, the weights m / t —
and two counters, `diffusion_masked_share` and `diffusion_weight_mean`
(1 in expectation).

Nothing here is a copy: the block is `models/keye.py`'s `KeyeBlock` (the
same Qwen3-MoE trunk: pin, norm, `LlamaAttention`, residual, norm,
`MoEMLP`, residual) handed no index tables, and its expert layer's
config that file's `moe_config`; the attention is `models/llama.py`'s
`LlamaAttention` under `qk_head_norm` and `attn_block_diffusion`
(`ops/block_attention.py`), the expert layer `models/moe.py`'s `MoEMLP`
on its grouped path (`experts_held` / `first_expert`: a chip's share),
the loop and the head `models/stack.py`'s.  Parameter names are `layers_<i>/{input_norm,
attention, post_attn_norm, feed_forward}`, `embed_tokens`, `norm`,
`lm_head`, so `parallel/sharding.py`'s rules bind.

Refused, not guessed: a mesh of several devices and with it a pipeline
(the mask over a sharded sequence and a share of the experts have no
route there), a block length that does not divide the attention tile, a
sequence that is no whole number of blocks.  Not built: generation (a
step that yields a block of tokens).

Parity: none — the reference trains next-token Llama/GLM-class stacks
only; this stack exists for the block-diffusion benchmark cell.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import block_attention
from . import stack
from .gpt import weighted_cross_entropy
from .keye import KeyeBlock, KeyeConfig
from .llama import LlamaConfig, RMSNorm, rope_freqs
from .sown import counters, objective, sown


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    max_seq_len: int = 32768
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    # the diffusion: blocks of block_length tokens, t on [noise_eps, 1]
    # one a block, the noised copy's masked tokens read mask_token_id
    # (-1 = the vocabulary's last row)
    block_length: int = 4
    noise_eps: float = 1e-3
    noise_seed: int = 0
    mask_token_id: int = -1
    # the expert layer: softmax over num_experts, the top_k largest
    # renormalised to sum 1, SwiGLU experts of expert_width, none shared
    num_experts: int = 128
    top_k: int = 8
    expert_width: int = 768
    experts_held: int = 0
    first_expert: int = 0
    # OLMoE's / HF's load-balancing term over all the router's experts
    # and BOTH copies, the MEAN over the layers times this; 0 = none
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
            num_kv_heads=2, head_dim=16, max_seq_len=64, num_experts=16,
            top_k=3, expert_width=32), **over})

    @property
    def mask_id(self) -> int:
        return self.mask_token_id % self.vocab_size

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
            attn_block_diffusion=self.block_length)

    # softmax top-k renormalised on the grouped path, a chip's share, the
    # balance term a layer's part of the mean: the trunk Keye's file runs
    moe_config = KeyeConfig.moe_config

    def num_params(self) -> int:
        return self.attention_config().num_params()


def sequence_hash(ids):
    """(b, T) ids -> (b,) uint32: sum_i ids_i * (i * 2654435761 + 40503)
    modulo 2^32 — a sequence's own ids and their places, nothing of the
    batch around it."""
    place = jnp.arange(ids.shape[1], dtype=jnp.uint32) \
        * jnp.uint32(2654435761) + jnp.uint32(40503)
    return (ids.astype(jnp.uint32) * place).sum(axis=1, dtype=jnp.uint32)


def draw_noise(ids, noise_seed: int, block_length: int, eps: float):
    """(t (b, T / L) float32 on [eps, 1], m (b, T) bool) of a batch of
    sequences: each sequence's from `fold_in(key(noise_seed), its hash)`
    — t from the key's first half, one uniform a block; m from its
    second, one uniform a token under its block's t."""
    seq = ids.shape[1]

    def one(h):
        t_key, m_key = jax.random.split(
            jax.random.fold_in(jax.random.key(noise_seed), h))
        t = eps + (1.0 - eps) * jax.random.uniform(
            t_key, (seq // block_length,), jnp.float32)
        return t, jax.random.uniform(m_key, (seq,), jnp.float32) \
            < jnp.repeat(t, block_length)

    return jax.vmap(one)(sequence_hash(ids))


@objective
def diffusion_objective(intermediates, batch, logits):
    """The weighted cross-entropy of the noised copy's logits against the
    CLEAN ids the model sowed, each token under its m / t; None for a
    model that sowed none."""
    targets = list(sown(intermediates, "diffusion_targets"))
    if not targets:
        return None
    weights, = sown(intermediates, "diffusion_weights")
    return weighted_cross_entropy(logits, targets[0], weights)


@counters
def collect_diffusion_stats(intermediates) -> dict:
    """`diffusion_masked_share` (the mean of m: the loss's support) and
    `diffusion_weight_mean` (the mean of m / t, 1 in expectation); {} for
    a model that sowed none."""
    both = list(sown(intermediates, "diffusion_noise"))
    if not both:
        return {}
    return dict(zip(("diffusion_masked_share", "diffusion_weight_mean"),
                    both[0]))


class SDAR(nn.Module):
    config: SDARConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        length, seq = cfg.block_length, idx.shape[1]
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise ValueError(
                "sdar runs on one device: block-diffusion's mask over a "
                "sharded sequence, a pipeline's stages and a chip's share "
                "of the experts have no route on a mesh")
        tile = block_attention.TILE
        if tile % length or seq % length:
            raise ValueError(
                f"a block length of {length} does not divide the attention "
                f"tile ({tile}) and the sequence ({seq})")
        with jax.named_scope("diffusion/noise"):
            t, masked = draw_noise(idx, cfg.noise_seed, length,
                                   cfg.noise_eps)
            both = jnp.concatenate(
                [idx, jnp.where(masked, cfg.mask_id, idx)], axis=1)
            weights = masked / jnp.repeat(t, length, axis=1)
            self.sow("intermediates", "diffusion_noise", jnp.stack(
                [masked.mean(dtype=jnp.float32), weights.mean()]))
        # what the objective is computed against (`diffusion_objective`)
        self.sow("intermediates", "diffusion_targets", idx)
        self.sow("intermediates", "diffusion_weights", weights)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(both)
        # a row a POSITION ID: each copy's own 0 .. T-1
        cos, sin = (jnp.concatenate([table, table]) for table in
                    rope_freqs(cfg.head_dim, seq, cfg.rope_theta))
        x = stack.layers(KeyeBlock, cfg, [()] * cfg.num_layers, x, cos, sin,
                         None, None)  # no indexer: no tables of its own
        # the head reads the noised copy alone
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x[:, seq:]),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        # a few tokens, one block of the diffusion at least
        return stack.init_params(self, rng, batch,
                                 max(seq, self.config.block_length))
