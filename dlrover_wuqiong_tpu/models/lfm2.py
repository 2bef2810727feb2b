"""`lfm2_moe`: a hybrid stack whose blocks take their MIXER and their
FEED-FORWARD from per-layer kinds (LFM2-24B-A2B's shape): the mixer of
block l is `layer_types[l]` — `conv`, a GATED SHORT CONVOLUTION
(`ShortConvMixer`, below), or `full_attention`, `models/llama.py`'s
grouped-query `LlamaAttention` with an RMSNorm over each head's lanes of
q and k before the rotation (`LlamaConfig.qk_head_norm`); the
feed-forward of the first `num_dense_layers` blocks is a wide SwiGLU and
of every later one an expert layer with a sigmoid router, a selection
bias, normalised gates and SwiGLU experts, NO shared expert.

    x = embed[ids]
    for l, kind in enumerate(layer_types):
        x = x + mixer_kind(RMSNorm(x))         short conv | QK-normed GQA
        u = RMSNorm(x)
        x = x + (swiglu_dense(u) if l < num_dense_layers
                 else sum_{e chosen, held} g_e swiglu_e(u))
    logits = RMSNorm(x) @ embed^T                              (tied)

The gated short convolution, h the block's normalised input:

    [B | C | X] = h W_in                       hidden -> 3 x hidden
    z    = B * X
    c[t] = sum_{s < taps} w[taps - 1 - s] * z[t - s]
                one filter a channel, its LAST tap on the current step,
                zeros before the sequence's start, no bias, no activation
    y    = (C * c) W_out                       hidden -> hidden

Nothing is carried beyond `taps` - 1 steps and nothing says where a
token stands.  The two gates and the convolution are plain `jax.numpy`
lines under the scope `short_conv/gated`, in the config's `dtype` as
the published code runs them; the mixer sows how many of its calls ran
those lines (`collect_shortconv_stats`: all of them — no kernel
computes this form: `ops/short_conv.py`'s pair is silu(conv(x) + bias)).

`experts_held` / `first_expert` are a chip's share of the experts, as
`models/latent_moe.py` has them.  Nothing here is a copy: the norms are
`models/llama.py`'s `RMSNorm`, the dense feed-forward its `LlamaMLP`, the
expert layer `models/moe.py`'s `MoEMLP` on its grouped path, the head
`models/stack.py`'s tied one.  Parameter names are
`layers_<i>/{operator_norm, short_conv | attention, ffn_norm,
feed_forward}`, `embed_tokens`, `norm`, so `parallel/sharding.py`'s rules
bind; the selection bias is named in `untrained_params` and its
out-of-band rule is `bias_update_rate`.

Refused, not guessed: a kind `layer_types` does not name and a mesh of
several devices (a share of the experts runs its kernels on one device);
the filter has no bias (the published model has none).

Parity: none — the reference trains Llama/GLM-class stacks only; this
stack exists for the short-convolution hybrid's benchmark cell
(`LFM2-24B-A2B`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, RMSNorm, rope_freqs
from .mamba2 import _conv_init
from .moe import MoEConfig, MoEMLP
from .sown import counters, sown

KINDS = ("conv", "full_attention")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    # periods of four: `full_attention` at blocks 2, 6, ..., 38
    layer_types: Tuple[str, ...] = tuple(
        "full_attention" if i % 4 == 2 else "conv" for i in range(40))
    # the leading blocks whose feed-forward is one SwiGLU of dense_width
    num_dense_layers: int = 2
    dense_width: int = 11776
    # `conv`: taps of the depthwise filter (`conv_L_cache`)
    conv_taps: int = 3
    # `full_attention`: grouped-query, heads of hidden / num_heads
    num_heads: int = 32
    num_kv_heads: int = 8
    max_seq_len: int = 128000
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    # the expert layer: a sigmoid router over num_experts, the top_k
    # largest `score + bias` chosen, gates score / (sum + gate_norm_eps)
    num_experts: int = 64
    top_k: int = 4
    expert_width: int = 1536
    routed_scaling: float = 1.0
    gate_norm_eps: float = 1e-6
    experts_held: int = 0
    first_expert: int = 0
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
            vocab_size=256, hidden_size=64,
            layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, dense_width=96, num_heads=4, num_kv_heads=2,
            max_seq_len=64, num_experts=16, top_k=3, expert_width=32),
            **over})

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def attention_config(self) -> LlamaConfig:
        """`LlamaAttention`'s and `LlamaMLP`'s config, and the counter of
        an expert layer's parameters (`ffn_params` with `moe` set)."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.dense_width, num_layers=self.num_layers,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            rms_eps=self.rms_eps, dtype=self.dtype,
            use_flash_attention=self.use_flash_attention, mesh=self.mesh,
            qk_head_norm=True)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, impl="grouped",
            dtype=self.dtype, norm_topk_prob=True,
            aux_loss="none", aux_loss_weight=0.0, score_func="sigmoid",
            selection_bias=True, routed_scaling=self.routed_scaling,
            gate_norm_eps=self.gate_norm_eps, expert_act="swiglu",
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=self.bias_update_rate, mesh=self.mesh)

    def conv_params(self) -> int:
        """A short-convolution mixer's: W_in, the filter, W_out."""
        h = self.hidden_size
        return 4 * h * h + self.conv_taps * h

    def moe_ffn_params(self) -> int:
        """An expert layer's: router, bias, the held experts."""
        return dataclasses.replace(
            self.attention_config(), moe=self.moe_config(),
            intermediate_size=self.expert_width).ffn_params()

    def num_params(self) -> int:
        h, llama = self.hidden_size, self.attention_config()
        mixer = {"conv": self.conv_params(),
                 "full_attention": llama.attention_params()}
        dense, sparse = llama.ffn_params(), self.moe_ffn_params()
        return (self.vocab_size * h + h  # the tied table, the final norm
                + sum(mixer[kind] + 2 * h
                      + (dense if i < self.num_dense_layers else sparse)
                      for i, kind in enumerate(self.layer_types)))


def gated_short_conv(bcx, kernel, dtype):
    """(C * conv(B * X)) of `bcx` = [B | C | X] (b, T, 3 x channels):
    `kernel` (taps, channels), one filter a channel, its LAST tap on the
    current step (`models/mamba2.causal_conv_silu`'s layout), no bias, no
    activation.  Shifted products in `dtype`, as that function's plain
    lines and as the published code: in float32 from the projection's
    output on, the compiler writes the projection itself in float32
    (0.8 GB a layer and phase at the cell's shape) and the step is 5%
    slower for a check against the reference that reads no closer
    (PERF.md section 6, PR 60)."""
    taps, t = kernel.shape[0], bcx.shape[1]
    gate_b, gate_c, x = jnp.split(bcx, 3, axis=-1)
    padded = jnp.pad(gate_b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * kernel[j].astype(dtype)
               for j in range(taps))
    return gate_c * conv


class ShortConvMixer(nn.Module):
    """The gated short convolution of the module docstring on (b, T,
    hidden); leaves `in_proj`, `conv_kernel` (taps, hidden), `out_proj`."""
    hidden: int
    taps: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        bcx = nn.Dense(3 * self.hidden, use_bias=False, dtype=self.dtype,
                       name="in_proj")(h)
        kernel = self.param("conv_kernel", _conv_init(self.taps),
                            (self.taps, self.hidden))
        with jax.named_scope("gated"):
            y = gated_short_conv(bcx, kernel, self.dtype)
        # counted, not timed (static numbers): the calls that ran the
        # plain lines, the calls
        self.sow("intermediates", "shortconv_calls",
                 jnp.asarray([1.0, 1.0], jnp.float32))
        return nn.Dense(self.hidden, use_bias=False, dtype=self.dtype,
                        name="out_proj")(y)


@counters
def collect_shortconv_stats(intermediates) -> dict:
    """What the gated short convolutions of one forward pass counted — {}
    for a model without one: `shortconv_plain_calls`, the mixers that ran
    the `jax.numpy` lines, and `shortconv_calls`, the mixers."""
    rows = [v.reshape(-1, 2) for v in sown(intermediates,
                                           "shortconv_calls")]
    if not rows:
        return {}
    with jax.named_scope("shortconv_calls"):  # the sum's copies get an owner
        plain, calls = jnp.concatenate(rows).sum(0)
    return {"shortconv_plain_calls": plain, "shortconv_calls": calls}


class Lfm2Block(nn.Module):
    config: Lfm2Config
    layer: int

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        llama = cfg.attention_config()
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="operator_norm")(x)
        if cfg.layer_types[self.layer] == "conv":
            out = ShortConvMixer(cfg.hidden_size, cfg.conv_taps, cfg.dtype,
                                 name="short_conv")(h)
        else:
            out = LlamaAttention(llama, name="attention")(h, cos, sin)
        # the save/offload anchors of the *_names remat policies
        x = x + checkpoint_name(out, "attn_out")
        u = RMSNorm(cfg.rms_eps, cfg.dtype, name="ffn_norm")(x)
        if self.layer < cfg.num_dense_layers:
            out = LlamaMLP(llama, name="feed_forward")(u)
        else:
            out = MoEMLP(cfg.hidden_size, cfg.expert_width, cfg.moe_config(),
                         name="feed_forward")(u)
        return x + checkpoint_name(out, "mlp_out")


class Lfm2(nn.Module):
    config: Lfm2Config

    # the selection bias has no gradient, its rule runs out of band
    untrained_params = (r"layers_\d+/feed_forward/selection_bias",)

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if set(cfg.layer_types) - set(KINDS):
            raise ValueError(f"layer_types {cfg.layer_types!r}: a layer is "
                             f"one of {KINDS}")
        if cfg.mesh is not None and cfg.mesh.size > 1:
            raise ValueError(
                "lfm2 runs on one device: a chip's share of the experts has "
                "no route on a mesh")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed_tokens")
        cos, sin = rope_freqs(cfg.hidden_size // cfg.num_heads,
                              cfg.max_seq_len, cfg.rope_theta)
        x = stack.layers(Lfm2Block, cfg,
                         [(i,) for i in range(cfg.num_layers)], embed(idx),
                         cos, sin)
        return stack.tied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            embed.embedding, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        # a few tokens: nothing here needs a whole chunk of anything
        return stack.init_params(self, rng, batch, seq)
