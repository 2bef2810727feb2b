"""Kimi Delta Attention mixer (KDA: a delta rule whose decay is a number
a key CHANNEL; Kimi Linear, arXiv:2510.26692, in its public
implementation's form) over `ops/delta_rule.py`.

With H heads held here, keys of dk and values of dv:

    q~ = x Wq (H*dk)   k~ = x Wk (H*dk)   v~ = x Wv (H*dv)
    f  = x Wf (H*dk)   ONE full matrix, no low-rank pair (`no_kda_lora`)
    b  = x Wb (H)      z  = x Wg (H)
    q, k, v = silu(causal_depthwise_conv1d(q~ | k~ | v~))     no bias
    q^ = q / ||q||_2 / sqrt(dk)     k^ = k / ||k||_2          per head
    g  = lower_bound * sigmoid(exp(A_log_h) * (f + dt_bias))  in
         (lower_bound, 0), a CHANNEL's; alpha = exp(g)        (the safe gate)
    beta = sigmoid(b)                                         one a head
    o   = gated_delta_rule(q^, k^, v, g, beta)     g (b, T, H, dk)
    y   = sigmoid(z)[head] * RMSNorm_dv(o)         ONE number a head and
                                                   token, one (dv,) scale
    out = concat_heads(y) Wo

The lower bound is what lets the chunked form and the kernels scale
their operands in sub-blocks (`ops/delta_rule.CHANNEL_DECAY_FLOOR`): a
config below it is refused.  The recurrence runs where
`ops/delta_rule.delta_route(..., channel_decay=True)` says: on one TPU
device the Pallas pair `dwt_kda_fwd` / `dwt_kda_bwd` (the cell's shape:
four heads a grid step), everywhere else the chunked `jax.numpy` form,
the pair's oracle.  The mixer is told how many heads it holds and nothing else:
the state, both norms, the three gates and the output norm are per head
and the convolution and the decay per channel, so a share of the heads
IS a share of the mixer, and `Wo`'s partial sums over the shares add up
to the whole (tests/test_bailing_hybrid.py).

Scopes, under the module's own name: `q_proj`, `k_proj`, `v_proj`,
`f_proj`, `decay` (the decay's activation), `gates` (`b_proj`, beta),
`conv`, `delta` (the L2 norms and all of the recurrence — the kernels'
custom calls, the decay's running sums and the head-major re-layouts
with it), `g_proj` and `gate` (the head-wise output gate),
`gate_norm`, `o_proj`.  Parameter names are matched by
`parallel/sharding.py`.  The module sows `delta_stats` as
`models/gated_delta.py`'s mixer does (the decay averaged over a head's
channels) and `kda_stats`: the decay channels within 1% of the lower
bound and their count — a safe gate that has saturated — and the output
gate's sum and count.  They ride the step's metrics
(`collect_delta_stats`, `collect_kda_stats`, through
`make_lm_loss.with_stats`).

The short convolution and the draw of `dt_bias` are `models/mamba2.py`'s
(`causal_conv_silu`: on one TPU device at whole lane tiles of channels —
Ling's three calls of 2,048 a layer — `ops/short_conv.py`'s Pallas pair
`dwt_conv_fwd` / `dwt_conv_bwd`, its plain lines elsewhere), the L2 norms
and the draw of `A_log` `models/gated_delta.py`'s.

Parity: none — the reference's model zoo (atorch) is attention-only; the
equations are benchmark/reference_bailing_hybrid.py's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.delta_rule import (
    CHANNEL_DECAY_FLOOR,
    gated_delta_rule,
    product_lanes,
)
from .gated_delta import _a_log_init, _l2_normalised
from .llama import RMSNorm
from .mamba2 import _conv_init, _dt_bias_init, causal_conv_silu
from .sown import counters, sown

_FLOOR_BAND = 0.99  # a channel "at the floor": g within 1% of the bound


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    hidden_size: int = 256
    num_heads: int = 4          # the heads HELD here
    key_dim: int = 16
    value_dim: int = 16
    conv_kernel: int = 4
    chunk_size: int = 64
    lower_bound: float = -5.0   # of a step's log-decay (`kda_lower_bound`)
    eps: float = 1e-6           # the output norm's
    dtype: Any = jnp.bfloat16
    # initialiser settings of dt_bias (`mamba2._dt_bias_init`)
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # where the mixer runs (`ops/delta_rule.delta_route` reads it)
    mesh: Any = None

    @property
    def conv_dim(self) -> int:
        return self.num_heads * (2 * self.key_dim + self.value_dim)

    def num_params(self) -> int:
        h, heads = self.hidden_size, self.num_heads
        qk, v = heads * self.key_dim, heads * self.value_dim
        return (h * (3 * qk + v) + v * h          # q k f v, o
                + 2 * h * heads                   # b, the output gate
                + self.conv_kernel * self.conv_dim
                + heads + qk + self.value_dim)    # A_log dt_bias, the norm


class KDAMixer(nn.Module):
    config: KDAConfig

    @nn.compact
    def __call__(self, x):  # (B, T, hidden)
        cfg = self.config
        if not CHANNEL_DECAY_FLOOR <= cfg.lower_bound < 0:
            raise ValueError(
                f"lower_bound={cfg.lower_bound}: the chunked form's "
                f"sub-blocks hold a step's log-decay in "
                f"[{CHANNEL_DECAY_FLOOR}, 0)")
        bsz, t, _ = x.shape
        heads, dk, dv = cfg.num_heads, cfg.key_dim, cfg.value_dim

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)

        q = dense(heads * dk, "q_proj")(x)
        k = dense(heads * dk, "k_proj")(x)
        v = dense(heads * dv, "v_proj")(x)
        f = dense(heads * dk, "f_proj")(x)
        z = dense(heads, "g_proj")(x)

        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads * dk,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        with jax.named_scope("decay"):
            # float32 from the projection's output on
            rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
            g = cfg.lower_bound * jax.nn.sigmoid(rate * (
                f.astype(jnp.float32) + dt_bias).reshape(bsz, t, heads, dk))
        with jax.named_scope("gates"):
            beta = jax.nn.sigmoid(
                dense(heads, "b_proj")(x).astype(jnp.float32))
            # counted, not timed: static lanes and a few sums
            self.sow("intermediates", "delta_stats", jnp.stack([
                *jnp.asarray(product_lanes(dk, dv), jnp.float32),
                jnp.sum(jnp.exp(g).mean(-1)), jnp.sum(beta),
                jnp.float32(beta.size)]))
            at_floor = g <= _FLOOR_BAND * cfg.lower_bound

        # one filter a channel over q | k | v: three slices of one leaf
        kernel = self.param("conv_kernel", _conv_init(cfg.conv_kernel),
                            (cfg.conv_kernel, cfg.conv_dim))
        bounds = (0, heads * dk, 2 * heads * dk, cfg.conv_dim)
        with jax.named_scope("conv"):
            filters = [kernel[:, lo:hi] for lo, hi in zip(bounds, bounds[1:])]
        q, k, v = (causal_conv_silu(a_, f_, None, cfg.dtype, cfg.mesh)
                   for a_, f_ in zip((q, k, v), filters))

        with jax.named_scope("delta"):
            q = _l2_normalised(q.reshape(bsz, t, heads, dk),
                               1.0 / math.sqrt(dk))
            k = _l2_normalised(k.reshape(bsz, t, heads, dk))
            v = v.reshape(bsz, t, heads, dv)
        o = gated_delta_rule(q, k, v, g, beta, chunk=cfg.chunk_size,
                             dtype=cfg.dtype, mesh=cfg.mesh)

        o = RMSNorm(cfg.eps, jnp.float32, name="gate_norm")(o)
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(z.astype(jnp.float32))
            self.sow("intermediates", "kda_stats", jax.lax.stop_gradient(
                jnp.stack([jnp.sum(at_floor, dtype=jnp.float32),
                           jnp.float32(g.size), jnp.sum(gate),
                           jnp.float32(gate.size)])))
            y = (o * gate[..., None]).reshape(bsz, t, heads * dv)
        return dense(cfg.hidden_size, "o_proj")(y.astype(cfg.dtype))


@counters
def collect_kda_stats(intermediates) -> dict:
    """What the KDA mixers of one forward pass counted — {} for a model
    without one: `kda_decay_floor_share`, the share of decay channels
    (heads, tokens, layers) within 1% of the lower bound, and
    `kda_gate_mean`, the head-wise output gate's mean."""
    rows = [v.reshape(-1, 4) for v in sown(intermediates, "kda_stats")]
    if not rows:
        return {}
    with jax.named_scope("kda_stats"):  # the sum's copies get an owner
        floor, channels, gate, n = jnp.concatenate(rows).sum(0)
    return {"kda_decay_floor_share": floor / channels,
            "kda_gate_mean": gate / n}
