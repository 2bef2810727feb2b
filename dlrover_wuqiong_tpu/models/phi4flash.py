"""`phi4flash`: a decoder-hybrid-decoder stack (SambaY, arXiv:2507.06607).

Every block is `x += mixer(LN(x)); x += SwiGLU(LN'(x))`, LayerNorms with
scale and bias, no position term anywhere, a tied head.  Which mixer is
the block's PUBLISHED index i to say (of n = `num_layers_published`
layers; a cut keeps some of them, `layer_ids`, and each keeps its index):

    even i <  n/2 + 2   `mamba`   models/mamba1.py's Mamba-1 mixer; layer
                                  n/2 also HANDS ON m = its scan's output
                                  (after the D x term, before the gate)
    even i >= n/2 + 2   `gmu`     gated memory unit: (m * silu(u W_1)) W_2
    odd  i <  n/2       `window`  differential attention under a window
    odd  i == n/2 + 1   `full`    differential attention, causal; HANDS ON
                                  its keys and values
    odd  i >  n/2 + 1   `cross`   differential attention of its own
                                  queries over the `full` layer's keys
                                  and values: Wq and Wo only

Differential attention (arXiv:2410.05258) over heads of d: q (H heads),
k, v (KV heads) are paired as (2j, 2j + 1) — q1/q2 H/2 heads, k1/k2 and
v1/v2 KV/2, H/KV query pairs a key pair — and

    a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2]      2d wide
    a2 = softmax(q2 k2^T / sqrt(d)) [v1 | v2]
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i)
    o = RMSNorm_2d(a1 - lam * a2) * (1 - lam0)

a1 and a2 are ONE call of `ops/flash_attention.flash_attention` on H
heads of q and k at d and v at 2d (`[v1 | v2]` is v's own lanes, a pair
a head): the two-width kernels, on the transposed (b, h, T, d) layout
(`attention_route` sends every q/k width beside another v width there),
k and v repeated to the query heads as that route repeats them.

What a block hands on rides beside x through `models/stack.layers`
(`handed`): every later block is given it, the readers' cotangents sum
into it, and each block is still recomputed in the backward pass.

Refused, not guessed: a mesh of several devices (the scan has no route
there and the two-width attention runs on one device), a pipeline split
(`handed_on`: a stage boundary behind layer n/2 would have to carry x, m,
K and V; `parallel/pipeline.py` refuses the model by these names), a cut
whose reader comes before what it reads.

Parameter names: `layers_<i>/{input_norm,post_mixer_norm}`,
`layers_<i>/{mamba|gmu|attention}`, `layers_<i>/feed_forward`, i the
place in `layer_ids`; the stack and the tied head are `models/stack.py`'s.

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the `Phi-4-mini-flash-reasoning` benchmark cell.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash_attention import (
    causal_tile_count,
    flash_attention,
    kernel_lanes,
)
from ..parallel.sharding import pin_activation
from . import attention  # noqa: F401 — registers `attn_tiles`' counters
from . import stack
from .llama import LlamaMLP
from .mamba1 import Mamba1Config, Mamba1Mixer
from .sown import counters, sown

KINDS = ("mamba", "gmu", "window", "full", "cross")


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    num_layers_published: int = 32
    # the published indices of the layers held, in order; () = all
    layer_ids: Tuple[int, ...] = ()
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    # `mamba`: Mamba-1
    mamba_expand: int = 2
    mamba_state_size: int = 16
    mamba_conv_kernel: int = 4
    mamba_dt_rank: int = 0  # 0 = ceil(hidden / 16)
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    mesh: Any = None

    # what blocks hand on beside x: a pipeline stage boundary would have
    # to carry them (parallel/pipeline.py refuses the model by this)
    handed_on = ("m", "k", "v")

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_heads=4, num_kv_heads=2, sliding_window=8,
            num_layers_published=8, max_seq_len=64), **over})

    @property
    def layers(self) -> Tuple[int, ...]:
        return self.layer_ids or tuple(range(self.num_layers_published))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def memory_layer(self) -> int:
        """The `mamba` layer whose scan output the `gmu` layers read."""
        return self.num_layers_published // 2

    @property
    def kv_layer(self) -> int:
        """The `full` layer whose keys and values the `cross` layers read."""
        return self.num_layers_published // 2 + 1

    def kind(self, i: int) -> str:
        half = self.num_layers_published // 2
        if i % self.mb_per_layer == 0:
            return "mamba" if i < half + self.mb_per_layer else "gmu"
        if i < half:
            return "window"
        return "full" if i == self.kv_layer else "cross"

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    def mamba_config(self) -> Mamba1Config:
        return Mamba1Config(
            hidden_size=self.hidden_size, expand=self.mamba_expand,
            state_size=self.mamba_state_size,
            conv_kernel=self.mamba_conv_kernel, dt_rank=self.mamba_dt_rank,
            dtype=self.dtype, mesh=self.mesh)

    def mixer_params(self, kind: str) -> int:
        h, hd = self.hidden_size, self.head_dim
        q, kv = self.num_heads * hd, self.num_kv_heads * hd
        diff = 4 * hd + 2 * hd  # four lambda vectors, the sub-norm
        own = h * (q + 2 * kv) + q + 2 * kv + q * h + h + diff  # Wqkv, Wo
        return {"mamba": self.mamba_config().num_params(),
                "gmu": 2 * h * self.mamba_config().d_inner,
                "window": own, "full": own,
                "cross": 2 * (h * q + q) + diff}[kind]

    def num_params(self) -> int:
        h = self.hidden_size
        block = 3 * h * self.intermediate_size + 4 * h  # SwiGLU, two norms
        return (self.vocab_size * h + 2 * h  # the tied table, the last norm
                + sum(self.mixer_params(self.kind(i)) + block
                      for i in self.layers))


def _layer_norm(cfg, name):
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


class GatedMemoryUnit(nn.Module):
    """out = (m * silu(u W_1)) W_2: the block's input gates, element by
    element, the memory an earlier layer's scan left (arXiv:2507.06607)."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, m):
        cfg = self.config
        gate = jax.nn.silu(nn.Dense(m.shape[-1], use_bias=False,
                                    dtype=cfg.dtype, name="in_proj")(u))
        self.sow("intermediates", "gmu_gate_mean", jax.lax.stop_gradient(
            gate.astype(jnp.float32).mean()))
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(m.astype(cfg.dtype) * gate)


class DiffAttention(nn.Module):
    """Differential attention of one layer: `window` and `full` project
    q, k and v (`qkv_proj`, with bias), `cross` its queries alone
    (`q_proj`) and reads `kv`, another layer's (k, v) (b, T, KV * d).
    Returns (out, (k, v))."""
    config: Phi4FlashConfig
    layer: int  # the PUBLISHED index: lam0 reads it

    @nn.compact
    def __call__(self, x, kv=None):
        cfg = self.config
        bsz, t, _ = x.shape
        h, n_kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        kind = cfg.kind(self.layer)
        if kind == "cross":
            q = nn.Dense(h * d, dtype=cfg.dtype, name="q_proj")(x)
            k, v = kv
        else:
            q, k, v = jnp.split(
                nn.Dense((h + 2 * n_kv) * d, dtype=cfg.dtype,
                         name="qkv_proj")(x),
                [h * d, (h + n_kv) * d], axis=-1)
        window = cfg.sliding_window if kind == "window" else None
        rep = h // n_kv
        # heads (2j, 2j + 1) are a pair: head `which * H/2 + j` of the
        # call is q_which of pair j, over k_which of pair j // rep and
        # that pair's [v1 | v2]
        qt = q.reshape(bsz, t, h // 2, 2, d).transpose(0, 3, 2, 1, 4)
        kt = jnp.repeat(k.reshape(bsz, t, n_kv // 2, 2, d)
                        .transpose(0, 3, 2, 1, 4), rep, axis=2)
        vt = jnp.repeat(v.reshape(bsz, t, n_kv // 2, 2 * d)
                        .transpose(0, 2, 1, 3), rep, axis=1)
        a = flash_attention(
            qt.reshape(bsz, h, t, d), kt.reshape(bsz, h, t, d),
            jnp.concatenate([vt, vt], axis=1), True, 1.0 / math.sqrt(d),
            window=window)
        if window is not None:  # counted, not timed (static numbers)
            self.sow("intermediates", "attn_tiles", jnp.asarray(
                [bsz * h * causal_tile_count(t, t, window=w)[0]
                 for w in (window, None)], jnp.float32))
        self.sow("intermediates", "attn_lanes", jnp.asarray(
            (kernel_lanes(d, 2 * d), 3 * d), jnp.float32))

        lams = [self.param(name, nn.initializers.normal(0.1), (d,))
                for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2")]
        scale = self.param("subln_scale", nn.initializers.ones, (2 * d,))
        lam0 = cfg.lambda_init(self.layer)
        with jax.named_scope("diff"):
            lq1, lk1, lq2, lk2 = (p.astype(jnp.float32) for p in lams)
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
                + lam0
            self.sow("intermediates", "attn_diff_lambda",
                     jax.lax.stop_gradient(lam))
            a = a.astype(jnp.float32)
            y = a[:, :h // 2] - lam * a[:, h // 2:]   # (b, H/2, T, 2d)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
            y = (y * (scale * (1.0 - lam0))).astype(cfg.dtype)
            y = y.transpose(0, 2, 1, 3).reshape(bsz, t, h * d)
        return nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                        name="o_proj")(y), (k, v)


@counters
def collect_phi4flash_stats(intermediates) -> dict:
    """What the differential attentions and the gated memory units of
    one forward pass counted — {} for a model without them:
    `attn_diff_lambda_mean`, the layers' mean lam, and `gmu_gate_mean`,
    the mean of silu(u W_1) over the units, tokens and channels."""
    stats = {}
    for under, name in (("attn_diff_lambda", "attn_diff_lambda_mean"),
                        ("gmu_gate_mean", "gmu_gate_mean")):
        leaves = [v.reshape(()) for v in sown(intermediates, under)]
        if leaves:
            with jax.named_scope(under):  # the mean's copies get an owner
                stats[name] = jnp.stack(leaves).mean()
    return stats


class Phi4FlashBlock(nn.Module):
    config: Phi4FlashConfig
    layer: int  # the PUBLISHED index

    @nn.compact
    def __call__(self, x, handed):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        kind = cfg.kind(self.layer)
        x = pin_activation(x, cfg.mesh)
        u = _layer_norm(cfg, "input_norm")(x)
        if kind == "mamba":
            out, y = Mamba1Mixer(cfg.mamba_config(), name="mamba")(u)
            if self.layer == cfg.memory_layer:
                handed = {**handed, "m": y}
        elif kind == "gmu":
            out = GatedMemoryUnit(cfg, name="gmu")(u, _read(handed, "m"))
        else:
            kv = (_read(handed, "k"), _read(handed, "v")) \
                if kind == "cross" else None
            out, (k, v) = DiffAttention(cfg, self.layer,
                                        name="attention")(u, kv)
            if self.layer == cfg.kv_layer:
                handed = {**handed, "k": k, "v": v}
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(out, "attn_out")
        out = LlamaMLP(cfg, name="feed_forward")(
            _layer_norm(cfg, "post_mixer_norm")(x))
        return x + checkpoint_name(out, "mlp_out"), handed


def _read(handed: dict, name: str):
    if name not in handed:
        raise ValueError(
            f"a layer reads {name!r} before any layer held here has handed "
            f"it on: a cut keeps the memory and the key-value layer with "
            f"their readers")
    return handed[name]


class Phi4Flash(nn.Module):
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise ValueError(
                "phi4flash runs on one device: the selective scan and the "
                "two-width attention have no route on a mesh")
        if cfg.num_kv_heads % 2 or cfg.num_heads % cfg.num_kv_heads:
            raise ValueError("differential attention pairs the heads: an "
                             "even number of kv heads that divides the "
                             "query heads")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed_tokens")
        x = stack.layers(Phi4FlashBlock, cfg, [(i,) for i in cfg.layers],
                         embed(idx), handed={})
        return stack.tied_head(_layer_norm(cfg, "norm")(x), embed.embedding,
                               cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        # a few tokens: the plain routes take any length
        return stack.init_params(self, rng, batch, seq)
