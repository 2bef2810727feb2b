"""GPT-2-family language model (nanoGPT-class), TPU-first.

Parity: the reference trains nanoGPT/GPT-2 in its examples and benchmarks
(`examples/pytorch/nanogpt`, BASELINE.md flash-ckpt rows use GPT-2 xl 1.5B).
This is a native flax implementation: bf16 compute, flash-attention kernel for
the hot op, `jax.checkpoint` rematerialization per block, parameter names
aligned with `parallel/sharding.py` TRANSFORMER_RULES so TP/FSDP specs apply
with no per-model glue.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # padded to multiple of 128 for the MXU
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    block_size: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # jax.checkpoint policy when remat is on: "full" (recompute all) |
    # "dots" (save matmul outputs) | "offload_dots" (matmul outputs ->
    # pinned host) | "save_names"/"offload_names" (the attn_out/mlp_out
    # checkpoint_name annotations) — ops/remat.py
    remat_policy: str = "full"
    # checkpoint_name anchors for the *_names policies; () = the models'
    # built-in ("attn_out", "mlp_out")
    remat_names: tuple = ()
    use_flash_attention: bool = True
    attn_impl: str = "flash"  # "flash" | "ring" | "ulysses"
    mesh: Any = None  # required by ring/ulysses (set by auto_accelerate)
    # fp8 matmuls on the name-filtered projections (models/fp8.py; set by
    # the ("amp", {"fp8": True}) strategy)
    fp8: bool = False
    fp8_filter: tuple = ("c_attn", "c_proj", "c_fc")
    # MoE: 0 experts = dense MLP (parity atorch modules/moe)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @classmethod
    def nano(cls):  # tiny config for tests
        return cls(vocab_size=512, n_layer=2, n_head=2, n_embd=128,
                   block_size=128)

    @classmethod
    def gpt2(cls):
        return cls(n_layer=12, n_head=12, n_embd=768)

    @classmethod
    def gpt2_medium(cls):
        return cls(n_layer=24, n_head=16, n_embd=1024)

    @classmethod
    def gpt2_large(cls):
        return cls(n_layer=36, n_head=20, n_embd=1280)

    @classmethod
    def gpt2_xl(cls):  # 1.5B — the flash-ckpt baseline model
        return cls(n_layer=48, n_head=25, n_embd=1600)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def num_params(self) -> int:
        wte = self.vocab_size * self.n_embd
        wpe = self.block_size * self.n_embd
        per_layer = 12 * self.n_embd * self.n_embd + 13 * self.n_embd
        return wte + wpe + self.n_layer * per_layer + 2 * self.n_embd


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        from .fp8 import dense

        cfg = self.config
        B, T, C = x.shape
        qkv = dense(cfg, 3 * C, "c_attn")(x)
        if cfg.use_flash_attention:
            from .attention import attend_projected

            # c_attn's output as it is: where the heads fall on lane
            # slabs the kernels index it, and nothing is split or
            # transposed (models/attention.py)
            y = attend_projected((qkv,), cfg.n_head, cfg, causal=True)
        else:
            q, k, v = (t.reshape(B, T, cfg.n_head, cfg.head_dim)
                       for t in jnp.split(qkv, 3, axis=-1))
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(cfg.head_dim)).astype(cfg.dtype)
            mask = jnp.tril(jnp.ones((T, T), bool))
            att = jnp.where(mask, att, jnp.finfo(att.dtype).min)
            att = jax.nn.softmax(att.astype(jnp.float32),
                                 axis=-1).astype(cfg.dtype)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, C)
        y = dense(cfg, C, "c_proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        from .fp8 import dense

        cfg = self.config
        h = dense(cfg, 4 * cfg.n_embd, "c_fc")(x)
        h = jax.nn.gelu(h)
        h = dense(cfg, cfg.n_embd, "c_proj")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        # the residual stream lives in the batch layout: stated once a
        # block, INSIDE it, so that remat's recomputed forward and the
        # backward (the cotangent gets the same constraint) carry it too,
        # and a sharded plan gathers kernels, not activations; no mesh
        # (one device): x itself
        x = pin_activation(x, cfg.mesh)
        # checkpoint_name marks the save/offload anchors for the
        # "save_names"/"offload_names" remat policies (ops/remat.py);
        # identity under every other policy
        attn = CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x), deterministic)
        x = x + checkpoint_name(attn, "attn_out")
        if cfg.moe_experts:
            from .moe import MoEConfig, MoEMLP

            mlp = MoEMLP(cfg.n_embd, 4 * cfg.n_embd,
                         MoEConfig(num_experts=cfg.moe_experts,
                                   top_k=cfg.moe_top_k,
                                   capacity_factor=cfg.moe_capacity_factor,
                                   dtype=cfg.dtype), name="moe_mlp")
            h = mlp(nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x))
        else:
            h = MLP(cfg, name="mlp")(
                nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x), deterministic)
        return x + checkpoint_name(h, "mlp_out")


class GPT(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, idx, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.config
        B, T = idx.shape
        tok = nn.Embed(cfg.vocab_size, cfg.n_embd,
                       dtype=cfg.dtype, name="wte")(idx)
        pos = nn.Embed(cfg.block_size, cfg.n_embd,
                       dtype=cfg.dtype, name="wpe")(jnp.arange(T)[None, :])
        x = stack.layers(Block, cfg, [()] * cfg.n_layer, tok + pos,
                         deterministic, prefix="h",
                         remat_names=cfg.remat_names, static_argnums=(2,))
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        logits = stack.tied_head(
            x, self.variables["params"]["wte"]["embedding"], cfg.dtype)
        if return_hidden:  # e.g. a value head on the trunk (rl/ppo.py)
            return logits, x
        return logits

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        return stack.init_params(self, rng, batch, seq)


def cross_entropy_loss(logits, targets, ignore_index: int = -1):
    """Token cross-entropy, f32 math over bf16 logits (stable + cheap).

    Custom VJP so neither pass materializes a (B, T, V) f32 array in HBM
    (GBs at vocab 50k; autodiff of log_softmax saves one):
    - forward reduces to lse (B, T) via logsumexp — XLA fuses the bf16→f32
      cast into the reduction;
    - backward emits (softmax - onehot) * scale as ONE fused elementwise
      expression straight to a bf16 store, with lse/logits as the only
      saved residuals.
    The loss is HBM-bandwidth-bound, not FLOPs-bound.
    """
    return _ce(logits, targets, ignore_index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ce(logits, targets, ignore_index):
    return _ce_fwd(logits, targets, ignore_index)[0]


# both rules open the `loss` scope themselves: the backward rule is
# traced from the transpose, outside any scope the caller had open
@jax.named_scope("loss")
def _ce_fwd(logits, targets, ignore_index):
    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)
    target_logits = jnp.take_along_axis(
        logits, safe_targets[..., None], axis=-1).squeeze(-1)
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1)
    n_valid = jnp.maximum(valid.sum(), 1)
    nll = lse - target_logits.astype(jnp.float32)
    loss = (nll * valid).sum() / n_valid
    return loss, (logits, safe_targets, valid, lse, n_valid)


@jax.named_scope("loss")
def _ce_bwd(ignore_index, res, g):
    logits, safe_targets, valid, lse, n_valid = res
    scale = (g * valid / n_valid).astype(jnp.float32)[..., None]
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(safe_targets, logits.shape[-1],
                            dtype=jnp.float32)
    dlogits = ((p - onehot) * scale).astype(logits.dtype)
    return dlogits, None


_ce.defvjp(_ce_fwd, _ce_bwd)


def weighted_cross_entropy(logits, targets, weights):
    """sum_i weights_i * -log softmax(logits_i)[targets_i] / (number of
    tokens): the mean over ALL the tokens of a token cross-entropy each
    weighs by its own float32 weight (a diffusion objective's m / t; 0
    leaves a token out).  `cross_entropy_loss`'s float32 statistics and
    its custom VJP — lse (B, T) in the forward, (softmax - onehot) *
    weight as one fused expression in the backward, no (B, T, V) float32
    array in either — under the same `loss` scope.  The weights are
    data: no cotangent reaches them."""
    return _wce(logits, targets, jax.lax.stop_gradient(weights))


@jax.custom_vjp
def _wce(logits, targets, weights):
    return _wce_fwd(logits, targets, weights)[0]


@jax.named_scope("loss")
def _wce_fwd(logits, targets, weights):
    target_logits = jnp.take_along_axis(
        logits, targets[..., None], axis=-1).squeeze(-1)
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1)
    nll = lse - target_logits.astype(jnp.float32)
    loss = (nll * weights).sum() / weights.size
    return loss, (logits, targets, weights, lse)


@jax.named_scope("loss")
def _wce_bwd(res, g):
    logits, targets, weights, lse = res
    scale = (g * weights / weights.size).astype(jnp.float32)[..., None]
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    dlogits = ((p - onehot) * scale).astype(logits.dtype)
    return dlogits, None, jnp.zeros_like(weights)


_wce.defvjp(_wce_fwd, _wce_bwd)
