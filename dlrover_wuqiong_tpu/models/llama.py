"""Llama-family model (RMSNorm, RoPE, SwiGLU, GQA), TPU-first.

Two fields of `LlamaConfig` reach the Llama-shaped MoE families through
the same block (OLMoE: both): `moe` puts `models/moe.py`'s expert layer
in every block's feed-forward slot (expert width `intermediate_size`),
`qk_norm` an RMSNorm over the whole q and k projections before the split
into heads and before RoPE; `qk_head_norm` is the per-head form, an
RMSNorm over each head's lanes (models/lfm2.py's layers).
`attn_window` makes the attention a sliding window
(models/smallthinker.py's local layers), as `attn_scale` and
`rope` make it Granite's.  `attn_gate` puts one sigmoid gate a head and
token on the attention's output; tables narrower than half a head rotate
the head's first features only (models/laguna.py's layers).
`attn_out_gate` is the ELEMENTWISE output gate that comes out of
`q_proj` itself (a head's 2 x d lanes are `[query | gate]`, and the
attention's output is multiplied lane by lane by sigmoid(gate):
models/qwen3_next.py's layers), and `norm_zero_centred` the `(1 + w)`
form of `RMSNorm`, w drawn at 0, for the per-head QK norms.
`attn_index_topk` makes the attention SPARSE by a learned choice: a
`models/sparse_indexer.SparseIndexer` scores the causal keys, each query
keeps the top `attn_index_topk`, and `ops/sparse_attention.py` runs the
softmax over that set and the indexer's KL term (models/keye.py's
layers); tables with a batch axis (`mrope_tables`: M-RoPE under explicit
positions, the sections the MODEL's to name) rotate each sequence by its
own angles.  `attn_block_diffusion` (a block length L) makes the rows
TWO copies of a sequence, `[clean ; noised]`, under block-diffusion's
static mask (`ops/block_attention.py`; models/sdar.py's layers): the
tables then carry a row a POSITION, each copy's own 0 .. T-1.

Parity: the reference's flagship workloads are GLM/Llama-class LMs via atorch
(`BASELINE.json` configs: Llama-3 8B auto_accelerate, Llama-3 70B Megatron
flash-ckpt).  Native flax implementation with names matched to
`parallel/sharding.py` rules (q_proj/k_proj/v_proj/o_proj, gate/up/down_proj,
embed_tokens, lm_head) so TP/FSDP/SP specs bind without per-model glue.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"  # ops/remat.py policy names
    remat_names: tuple = ()  # () = built-in ("attn_out", "mlp_out")
    use_flash_attention: bool = True
    attn_impl: str = "flash"  # "flash" | "ring" | "ulysses"
    mesh: Any = None  # required by ring/ulysses (set by auto_accelerate)
    # fp8 matmuls on the name-filtered projections (models/fp8.py; set by
    # the ("amp", {"fp8": True}) strategy)
    fp8: bool = False
    fp8_filter: tuple = ("q_proj", "k_proj", "v_proj", "o_proj",
                         "gate_proj", "up_proj", "down_proj")
    # a models/moe.py MoEConfig: every layer's feed-forward is that
    # expert layer, each expert a SwiGLU of width `intermediate_size`
    moe: Any = None
    # RMSNorm (own scale, `rms_eps`) over all of q's and all of k's
    # features, before the heads are split and before RoPE (OLMoE)
    qk_norm: bool = False
    # the per-head form: RMSNorm (`rms_eps`) over each head's lanes of q
    # and of k, ONE (head size,) scale each shared by the heads, before
    # RoPE (models/lfm2.py); not both
    qk_head_norm: bool = False
    # an explicit head size where heads x size is not the hidden size
    # (q and o are then hidden x heads*size); 0 = hidden_size // num_heads
    attn_head_dim: int = 0
    # False: q and k are not rotated (a hybrid whose other layers carry
    # the positions)
    rope: bool = True
    # the softmax's scale where it is not 1/sqrt(head size) (Granite's
    # `attention_multiplier`); 0 = 1/sqrt(head size)
    attn_scale: float = 0.0
    # a sliding window: a query sees this many keys, its own the last
    # (models/attention.py hands it to the kernels); 0 = every key at or
    # before it
    attn_window: int = 0
    # one gate a head and token on the attention's output, before
    # `o_proj`: sigmoid(x @ g_proj), x the block's normalised input
    # (arXiv:2505.06708's head-wise form); False = none
    attn_gate: bool = False
    # an ELEMENTWISE gate on the attention's output that `q_proj` itself
    # carries: q_proj is hidden x heads * 2 * size, a head's lanes
    # `[query | gate]`, and the output is multiplied lane by lane by
    # sigmoid(gate) before `o_proj` (models/qwen3_next.py); not both gates
    attn_out_gate: bool = False
    # the QK norms' scales in the zero-centred form: `RMSNorm(...,
    # zero_centred=True)`, x * rsqrt(...) * (1 + w), w drawn at 0
    norm_zero_centred: bool = False
    # a learned choice of keys (ops/sparse_attention.py): each query
    # keeps the `attn_index_topk` causal keys its indexer scores highest
    # (`attn_index_heads` heads of `attn_index_dim` over ONE shared key);
    # the layer sows the indexer's KL term times `attn_index_loss_weight`
    # as a loss; 0 = every causal key, no indexer
    attn_index_topk: int = 0
    attn_index_heads: int = 0
    attn_index_dim: int = 0
    attn_index_loss_weight: float = 1.0
    # block-diffusion's mask over rows that are `[clean ; noised]`, two
    # copies of one sequence in blocks of this many tokens: a clean query
    # sees the clean blocks up to its own, a noised one the clean blocks
    # BEFORE its own and its own noised block (ops/block_attention.py);
    # 0 = one copy, causal
    attn_block_diffusion: int = 0

    @classmethod
    def nano(cls):
        return cls(vocab_size=512, hidden_size=128, intermediate_size=256,
                   num_layers=2, num_heads=4, num_kv_heads=2,
                   max_seq_len=128)

    @classmethod
    def llama3_8b(cls):
        return cls()  # defaults are 8B

    @classmethod
    def llama3_70b(cls):
        return cls(hidden_size=8192, intermediate_size=28672, num_layers=80,
                   num_heads=64, num_kv_heads=8)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    def attention_params(self) -> int:
        """q (twice as wide under `attn_out_gate`), k, v, o, the
        QK-norm's scales, the head-wise output gate's product and the
        sparse indexer's leaves (its q heads, its one key with the key's
        LayerNorm, a weight a head); no block norm."""
        h, q = self.hidden_size, self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        idx = self.attn_index_dim
        return 2 * h * q + 2 * h * kv + (q + kv if self.qk_norm else 0) \
            + (h * q if self.attn_out_gate else 0) \
            + (2 * self.head_dim if self.qk_head_norm else 0) \
            + (h * self.num_heads if self.attn_gate else 0) \
            + (h * (self.attn_index_heads * (idx + 1) + idx) + 2 * idx
               if self.attn_index_topk else 0)

    def ffn_params(self) -> int:
        """The feed-forward slot: a SwiGLU, or `moe`'s expert layer — the
        router over all experts, the experts HELD here (three matrices
        each, relu2 two), a selection bias, a shared expert of the same
        form and its gate's vector — each routed expert
        `intermediate_size` wide."""
        h, i = self.hidden_size, self.intermediate_size
        if self.moe is None:
            return 3 * h * i
        m = self.moe
        mats = 2 if m.expert_act == "relu2" else 3
        return (m.num_experts * h + m.held * mats * h * i
                + (m.num_experts if m.selection_bias else 0)
                + mats * h * m.shared_width
                + (h if m.shared_gate else 0))

    def num_params(self) -> int:
        h = self.hidden_size
        per_layer = self.attention_params() + self.ffn_params() + 2 * h
        return (2 * self.vocab_size * h + self.num_layers * per_layer + h)


class RMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * w, w drawn at 1 — or, `zero_centred`,
    * (1 + w) with w drawn at 0 (the form whose weight decay pulls the
    scale to 1, not to 0); the statistics in float32 either way."""
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.zero_centred
            else nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        if self.zero_centred:
            scale = 1.0 + scale
        return (norm * scale).astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN (Peng et al., arXiv:2309.00071) as the DeepSeek-V2/V3 family's
    published code applies it: the slow pairs' frequencies are divided by
    `factor`, the fast ones kept, a linear ramp between the pairs that
    turn `beta_fast` and `beta_slow` times over the original positions;
    the tables carry `mscale / mscale_all_dim`'s ratio and the SOFTMAX's
    scale the square of `softmax_mscale`."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def table_mscale(self) -> float:
        return self._mscale(self.factor, self.mscale) \
            / self._mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_mscale(self) -> float:
        """m: the softmax's 1 / sqrt(d) is multiplied by m * m."""
        return self._mscale(self.factor, self.mscale_all_dim)

    def ramp_ends(self, head_dim: int, theta: float) -> tuple:
        """(lo, hi): the pairs between which the ramp runs."""
        def pair(turns):
            return head_dim * math.log(
                self.original_max_position_embeddings
                / (turns * 2 * math.pi)) / (2 * math.log(theta))

        return (max(math.floor(pair(self.beta_fast)), 0),
                min(math.ceil(pair(self.beta_slow)), head_dim - 1))


def rope_freqs(head_dim: int, max_seq: int, theta: float,
               scaling: Optional[RopeScaling] = None):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    if scaling is not None:
        lo, hi = scaling.ramp_ends(head_dim, theta)
        ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - lo) \
            / max(hi - lo, 0.001)
        kept = 1.0 - jnp.clip(ramp, 0.0, 1.0)  # 1 = the unscaled pair
        inv = inv / scaling.factor * (1.0 - kept) + inv * kept
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (seq, head_dim/2)
    if scaling is not None and scaling.table_mscale != 1.0:
        return (jnp.cos(freqs) * scaling.table_mscale,
                jnp.sin(freqs) * scaling.table_mscale)
    return jnp.cos(freqs), jnp.sin(freqs)


def mrope_tables(head_dim: int, theta: float, sections, positions):
    """(cos, sin), each (b, T, head_dim / 2), of M-RoPE: `positions` is
    (3, b, T) — the temporal, height and width stream — and pair i of a
    head takes its angle from the stream its consecutive section names
    (`sections` pairs each, summing to head_dim / 2; a head of another
    width than the sections were published for takes them in proportion).
    Where the three streams coincide these are `rope_freqs`' rows."""
    import numpy as np

    half, total = head_dim // 2, sum(sections)
    if len(sections) != 3 or any(n * half % total for n in sections):
        raise ValueError(f"mrope sections {sections!r}: three counts of "
                         f"pairs in proportion to {half}")
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    stream = np.repeat(np.arange(3), [n * half // total for n in sections])
    # (half, b, T) -> (b, T, half): each pair's own stream
    angle = jnp.moveaxis(positions.astype(jnp.float32)[stream], 0, -1) * inv
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope(x, cos, sin, mesh=None, head_dim: int = 0):
    """Rotate each head's pairs (even, odd interleaved by halves).  x is
    (b, s, h, d), or the projections' own (b, s, h*d) with the heads side
    by side and never cut apart — the one rotation of both layouts.
    `head_dim` d wider than the tables' 2 * half (0 = as wide) rotates a
    head's FIRST 2 * half features and passes the rest as they are.

    Two routes, chosen by what the call can observe (`mesh` is the model
    config's), never a knob: where `ops/rope.rope_route` says "kernel"
    of the rows' width, the head's and the tables', `ops/rope.py`'s
    `dwt_rope`, one read and one write of the rows, differentiated by
    the same kernel — SmallThinker's and OLMoE's q and k, latent
    attention's q heads and its one shared key part, Laguna's sliding
    layers and its full layers' half-rotated heads (the passed lanes
    pass inside the kernel).  Every other call takes the formula below:
    the plain route, and the tests' oracle.

    A head's two halves trade places by two rolls of the last axis and a
    select (a roll never wraps into a lane that is kept; over one head's
    d the two rolls are the same swap), and the sign rides on the sine:
    two products and one sum an element."""
    from ..ops.rope import rope_route, rotate_rows

    if cos.ndim == 3:
        # a sequence's own angles (`mrope_tables`): whole heads, cut to
        # (b, s, h, d), by the formula
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        c, si = cos[:, :, None], sin[:, :, None]
        return jnp.concatenate([x1 * c - x2 * si, x2 * c + x1 * si],
                               axis=-1).astype(x.dtype)
    s, lanes, half = x.shape[1], x.shape[-1], cos.shape[-1]
    row = math.prod(x.shape[2:])  # a position's heads side by side
    d = head_dim or 2 * half
    if rope_route(row, d, mesh, 2 * half) == "kernel":
        return rotate_rows(x.reshape(*x.shape[:2], row), cos, sin,
                           d).reshape(x.shape)
    heads = lanes // d
    # a head's lanes are [cos | cos | 1 ..] and [-sin | sin | 0 ..]:
    # those behind the rotated ones pass, whatever their partner
    passed = (s, d - 2 * half)
    c = jnp.tile(jnp.concatenate(
        [cos[:s], cos[:s]] + [jnp.ones(passed, cos.dtype)] * (d > 2 * half),
        axis=-1), (1, heads))
    si = jnp.tile(jnp.concatenate(
        [-sin[:s], sin[:s]] + [jnp.zeros(passed, sin.dtype)]
        * (d > 2 * half), axis=-1), (1, heads))
    if x.ndim == 4:
        c, si = c[:, None], si[:, None]
    x32 = x.astype(jnp.float32)
    lane = jnp.arange(lanes)
    if d > 2 * half:
        lane = lane % d  # a lane's place in its head
    second = (lane // half) % 2 == 1  # a rotated pair's upper half
    partner = jnp.where(second, jnp.roll(x32, half, axis=-1),
                        jnp.roll(x32, -half, axis=-1))
    return (x32 * c + partner * si).astype(x.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, index_tables=None):
        from .attention import (
            attend,
            attend_projected,
            goes_direct,
            window_tiles,
        )
        from ..ops.flash_attention import kept_mask, kv_route
        from ..ops.head_gate import gate_route, gate_rows
        from .fp8 import dense

        cfg = self.config
        B, T, C = x.shape
        hd = cfg.head_dim
        q = dense(cfg, cfg.num_heads * hd * (2 if cfg.attn_out_gate else 1),
                  "q_proj", use_bias=False)(x)
        if cfg.attn_out_gate:
            if cfg.attn_gate or cfg.attn_index_topk:
                raise ValueError("attn_out_gate beside a head-wise gate or "
                                 "a learned choice of keys: no layer has "
                                 "asked for both")
            # a head's 2 * hd lanes are [query | gate]: two slices of the
            # head-major view, each laid out again as the projections' own
            q, out_gate = (
                part.reshape(B, T, cfg.num_heads * hd) for part in jnp.split(
                    q.reshape(B, T, cfg.num_heads, 2 * hd), 2, axis=-1))
        k = dense(cfg, cfg.num_kv_heads * hd, "k_proj", use_bias=False)(x)
        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
                k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
        if cfg.qk_head_norm:
            if cfg.qk_norm:
                raise ValueError("qk_norm and qk_head_norm: one norm of q "
                                 "and k, over the projection or over a head")
            with jax.named_scope("qk_norm"):
                # a head's lanes last for the statistic, then the
                # projections' own layout again
                zc = cfg.norm_zero_centred
                q = RMSNorm(cfg.rms_eps, cfg.dtype, zc, name="q_norm")(
                    q.reshape(B, T, cfg.num_heads, hd)).reshape(q.shape)
                k = RMSNorm(cfg.rms_eps, cfg.dtype, zc, name="k_norm")(
                    k.reshape(B, T, cfg.num_kv_heads, hd)).reshape(k.shape)
        v = dense(cfg, cfg.num_kv_heads * hd, "v_proj", use_bias=False)(x)
        # where a head is a lane slab the kernels index q, k and v in the
        # projections' own (B, T, heads*hd) — k and v at their kv heads,
        # a group's query heads reading one slab (`kv_route`) — and
        # nothing here cuts them to heads or repeats them
        # (models/attention.py); every other call cuts, before the
        # rotation, and repeats the kv heads, as it always did
        # (block-diffusion's entry takes that layout on both its routes)
        direct = bool(cfg.attn_block_diffusion) or (
            cfg.use_flash_attention and not cfg.attn_index_topk
            and goes_direct(cfg, cfg.num_heads, hd, T))
        if not direct:
            q = q.reshape(B, T, cfg.num_heads, hd)
            k = k.reshape(B, T, cfg.num_kv_heads, hd)
            v = v.reshape(B, T, cfg.num_kv_heads, hd)
        if cfg.rope:
            # tables narrower than half the head rotate its first
            # features only: under a scope of their own
            partial = 2 * cos.shape[-1] < hd
            width = {"head_dim": hd} if partial else {}
            with jax.named_scope("rope_partial") if partial \
                    else contextlib.nullcontext():
                q = apply_rope(q, cos, sin, mesh=cfg.mesh, **width)
                k = apply_rope(k, cos, sin, mesh=cfg.mesh, **width)
        if cfg.attn_index_topk:
            # the attention over the indexer's choice of keys: q (B, T,
            # H, d), k and v (B, T, KV, d) rotated; the kv heads are
            # indexed by their group, never repeated
            from ..ops.sparse_attention import sparse_attention
            from .attention import softmax_scale
            from .sparse_indexer import SparseIndexer

            if cfg.mesh is not None and cfg.mesh.size > 1:
                raise ValueError(
                    "a learned choice of keys (attn_index_topk) runs on "
                    "one device: the choice over a sharded sequence has "
                    "no route")
            if cfg.attn_window or cfg.attn_gate:
                raise ValueError("attn_index_topk beside a window or a "
                                 "gate: no layer has asked for both")
            if index_tables is None:  # one position stream: RoPE's rows
                index_tables = rope_freqs(cfg.attn_index_dim, T,
                                          cfg.rope_theta)
            operands = SparseIndexer(
                cfg.attn_index_heads, cfg.attn_index_dim, cfg.rms_eps,
                cfg.dtype, cfg.mesh, name="indexer")(x, *index_tables)
            y, kl, counted = sparse_attention(
                q, k, v, *operands, cfg.attn_index_topk, softmax_scale(cfg),
                cfg.mesh)
            # the term joins the loss (`collect_attention_aux_loss`); the
            # bare KL and the counters are read, never summed into it
            self.sow("intermediates", "attn_index_loss",
                     cfg.attn_index_loss_weight * kl)
            self.sow("intermediates", "attn_index_kl",
                     jax.lax.stop_gradient(kl))
            self.sow("intermediates", "attn_sparse", counted)
            return dense(cfg, C, "o_proj", use_bias=False)(
                y.reshape(B, T, cfg.num_heads * hd))
        if cfg.attn_block_diffusion:
            from ..ops.block_attention import (
                bd_route,
                bd_tile_count,
                block_diffusion_attention,
            )
            from .attention import softmax_scale

            if cfg.attn_window or cfg.attn_gate or cfg.attn_out_gate \
                    or cfg.attn_index_topk:
                raise ValueError("attn_block_diffusion beside a window, a "
                                 "gate or a learned choice of keys: no "
                                 "layer has asked for both")
            length = cfg.attn_block_diffusion
            route = bd_route(T // 2, length, cfg.num_heads,
                             cfg.num_kv_heads, hd, cfg.mesh,
                             q.dtype.itemsize)
            # counted, not timed (static numbers): the plan's tiles and
            # pairs of every head and sequence of this layer
            self.sow("intermediates", "attn_bd", B * cfg.num_heads
                     * jnp.asarray(bd_tile_count(T // 2, length, route),
                                   jnp.float32))
            y = block_diffusion_attention(
                q, k, v, cfg.num_heads, cfg.num_kv_heads, length,
                softmax_scale(cfg), cfg.mesh)
            return dense(cfg, C, "o_proj", use_bias=False)(y)
        how, rep = kv_route(cfg.num_heads, cfg.num_kv_heads, hd)
        if rep > 1 and not (direct and how == "indexed"):  # GQA: repeat
            k, v = (jnp.repeat(t.reshape(B, T, cfg.num_kv_heads, hd), rep,
                               axis=2).reshape(q.shape) for t in (k, v))
        tiles = window_tiles(cfg, B, cfg.num_heads, T)
        if tiles is not None:  # counted, not timed (static numbers)
            self.sow("intermediates", "attn_tiles",
                     jnp.asarray(tiles, jnp.float32))
        if direct:
            y = attend_projected((q, k, v), cfg.num_heads, cfg, causal=True)
        elif cfg.use_flash_attention:
            y = attend(q, k, v, cfg, causal=True)
        else:
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            att = att * cfg.attn_scale if cfg.attn_scale else \
                att / jnp.sqrt(jnp.float32(hd))
            att = jnp.where(kept_mask(T, T, cfg.attn_window or None), att,
                            -jnp.inf)
            att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
            y = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        y = y.reshape(B, T, cfg.num_heads * hd)
        if cfg.attn_gate:
            # the flax Dense names the product's ops `g_proj`; the
            # sigmoid and the 128-lane slabs' multiply sit under `gate`
            g = dense(cfg, cfg.num_heads, "g_proj", use_bias=False)(x)
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(g.astype(jnp.float32))
                self.sow("intermediates", "attn_gate_mean",
                         jax.lax.stop_gradient(g.mean()))
                # y keeps the layout the kernels wrote, not cut to
                # heads: where a head is whole lane slabs a kernel
                # reads each head's g once (ops/head_gate.py), else the
                # gate is spread over the head's lanes
                took = gate_route(y.shape[-1], hd, cfg.mesh) == "kernel"
                self.sow("intermediates", "attn_gate_kernel",
                         jnp.float32(took))
                if took:
                    y = gate_rows(y, g).astype(cfg.dtype)
                else:
                    y = (y * jnp.repeat(g, hd, axis=-1)).astype(cfg.dtype)
        if cfg.attn_out_gate:
            from ..ops.flash_attention import kernel_lanes

            with jax.named_scope("gate"):
                # lane by lane on the layout the kernels wrote: one fused
                # pass, nothing to spread over a head
                g = jax.nn.sigmoid(out_gate.astype(jnp.float32))
                self.sow("intermediates", "attn_gate_mean",
                         jax.lax.stop_gradient(g.mean()))
                y = (y * g).astype(cfg.dtype)
            # counted, not timed (static numbers): the lanes a score
            # entry's two products run at this head size, the model's
            self.sow("intermediates", "attn_lanes", jnp.asarray(
                (kernel_lanes(hd, hd), 2 * hd), jnp.float32))
        return dense(cfg, C, "o_proj", use_bias=False)(y)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from .fp8 import dense

        cfg = self.config
        gate = dense(cfg, cfg.intermediate_size, "gate_proj",
                     use_bias=False)(x)
        up = dense(cfg, cfg.intermediate_size, "up_proj", use_bias=False)(x)
        h = jax.nn.silu(gate) * up
        return dense(cfg, cfg.hidden_size, "down_proj", use_bias=False)(h)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        # the residual stream in the batch layout, as models/gpt.py's Block
        x = pin_activation(x, cfg.mesh)
        # save/offload anchors for the *_names remat policies (ops/remat.py)
        attn = LlamaAttention(cfg, name="attention")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x), cos, sin)
        x = x + checkpoint_name(attn, "attn_out")
        if cfg.moe is not None:
            from .moe import MoEMLP

            # the auxiliary terms are the MEAN over the layers (every
            # layer sows its own and make_lm_loss sums what is sown)
            moe = dataclasses.replace(
                cfg.moe, mesh=cfg.mesh,
                aux_loss_weight=cfg.moe.aux_loss_weight / cfg.num_layers,
                z_loss_weight=cfg.moe.z_loss_weight / cfg.num_layers)
            ffn = MoEMLP(cfg.hidden_size, cfg.intermediate_size, moe,
                         name="feed_forward")
        else:
            ffn = LlamaMLP(cfg, name="feed_forward")
        h = ffn(RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x))
        return x + checkpoint_name(h, "mlp_out")


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        B, T = idx.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        x = stack.layers(LlamaBlock, cfg, [()] * cfg.num_layers, x, cos, sin,
                         remat_names=cfg.remat_names)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        return stack.init_params(self, rng, batch, seq)
