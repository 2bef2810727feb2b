"""Gated delta-rule mixer (linear attention) over `ops/delta_rule.py`.

With H value heads held here over Hk key heads (`num_key_heads`; Hk = H
unless a config says otherwise), keys of dk and values of dv:

    q~ = x Wq (Hk*dk)  k~ = x Wk (Hk*dk)  v~ = x Wv (H*dv)   z = x Wg (H*dv)
    a  = x Wa (H)      b  = x Wb (H)
    q, k, v = silu(causal_depthwise_conv1d(q~ | k~ | v~))     no bias
    q^ = q / ||q||_2 / sqrt(dk)     k^ = k / ||k||_2          per key head
    beta = 2 * sigmoid(b)           g = -exp(A_log) * softplus(a + dt_bias)
    o   = gated_delta_rule(q^, k^, v, g, beta)                alpha = exp(g)
    y   = RMSNorm_dv(o) * silu(z)   per head, one (dv,) scale for all
    out = concat_heads(y) Wo

The factor 2 on the write gate lets a state's eigenvalue along a key go
negative (beta in (0, 2): the published models' `allow_neg_eigval`;
`neg_eigval=False` is the write gate without it, beta in (0, 1)).
The mixer is told how many heads it holds and nothing else: the state,
both norms, both gates and the output norm are per head and the
convolution per channel, so a share of the heads IS a share of the
mixer, and `Wo`'s partial sums over the shares add up to the whole
(tests/test_olmo_hybrid.py).

VALUE HEADS OVER KEY HEADS (H = rep x Hk): the state, both gates, `A_log`,
`dt_bias` and the output norm go by VALUE head; q, k, their convolution
and their L2 norms by KEY head; value head j reads key head j // rep
(the published code's `repeat_interleave`).  Every route of
`ops/delta_rule.py` takes one q and one k a state, so the normalised q
and k are REPEATED to the value heads in front of it (`jnp.repeat`, whose
transpose sums a key head's rep cotangents) — rep x the key rows in HBM,
which the mixer counts: its `delta_stats` then carry two more numbers,
(q and k head-rows the recurrence's route reads, those the model has), H
and Hk today on every route; kernels that index a key head for its rep
states would read Hk and Hk.  At Hk = H nothing is repeated and nothing
more is sown: the program is what it was.

Scopes, under the module's own name: `q_proj`, `k_proj`, `v_proj`,
`g_proj`, `gates` (`a_proj`, `b_proj` and the two gates), `conv`,
`delta` (the L2 norms and all of the recurrence), `gate_norm`, `o_proj`.
Parameter names are matched by `parallel/sharding.py`.  The module sows
`delta_stats`: the lanes the products that meet a head's state run and
the lanes dk | dv ask (`ops/delta_rule.product_lanes`; static numbers),
and the sums and count of the decay alpha and the write gate beta over
heads and tokens — what says a gate has saturated.  They ride the step's
metrics (`collect_delta_stats`, through `make_lm_loss.with_stats`).

Which route the recurrence takes is `ops/delta_rule.delta_route`'s to
say, from the call's shapes and where it runs (the backend and
`GatedDeltaConfig.mesh`, the model config's own).  This mixer's decay is
ONE number a head, g (b, T, H): every route takes that form — the
kernels, the chunked `_chunked`, the sequential scan; a decay a key
CHANNEL, g (b, T, H, dk), is `models/kda.py`'s mixer and runs
`_chunked_channel` or the same sequential scan, never the kernels.

The short convolution and the draw of `dt_bias` are `models/mamba2.py`'s:
`causal_conv_silu` runs `ops/short_conv.py`'s Pallas pair where
`conv_route` lets a call's shape in — whole 128-lane tiles of channels,
so not the Olmo hybrid's 1,440 and 2,880 — and its plain lines elsewhere.

Parity: none — the reference's model zoo (atorch) is attention-only; the
equations are arXiv:2412.06464's in the form of its public
implementations, as benchmark/reference_olmo_hybrid.py writes them out.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.delta_rule import gated_delta_rule, product_lanes
from .mamba2 import _conv_init, _dt_bias_init, causal_conv_silu
from .sown import counters, sown

_NORM_EPS = 1e-6  # inside the root of both L2 norms


@dataclasses.dataclass(frozen=True)
class GatedDeltaConfig:
    hidden_size: int = 256
    num_heads: int = 4          # the (value) heads HELD here
    # key heads, each read by num_heads // num_key_heads value heads in
    # turn (head j reads key head j // rep); 0 = as many as value heads
    num_key_heads: int = 0
    # the factor 2 on the write gate (beta in (0, 2)); False: beta in (0, 1)
    neg_eigval: bool = True
    key_dim: int = 16
    value_dim: int = 32
    conv_kernel: int = 4
    chunk_size: int = 64
    eps: float = 1e-6           # the output norm's
    dtype: Any = jnp.bfloat16
    # initialiser settings of dt_bias (`mamba2._dt_bias_init`)
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # where the mixer runs: the model config's mesh (`auto_accelerate`
    # hands it over); `ops/delta_rule.delta_route` reads it
    mesh: Any = None

    @property
    def key_heads(self) -> int:
        return self.num_key_heads or self.num_heads

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_heads * self.key_dim \
            + self.num_heads * self.value_dim

    def num_params(self) -> int:
        h, heads = self.hidden_size, self.num_heads
        qk, v = self.key_heads * self.key_dim, heads * self.value_dim
        return (h * (2 * qk + 2 * v) + v * h      # q k v g, o
                + 2 * h * heads                   # a, b
                + self.conv_kernel * self.conv_dim
                + 2 * heads + self.value_dim)     # A_log dt_bias, the norm


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(uniform(0, 16)): a decay rate up to 16, as slow as float32
    resolves at the low end."""
    return jnp.log(jax.random.uniform(
        key, shape, jnp.float32, jnp.finfo(jnp.float32).tiny, 16.0)
    ).astype(dtype)


def _l2_normalised(x, scale: float = 1.0):
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _NORM_EPS)
                * scale)


class GatedDeltaMixer(nn.Module):
    config: GatedDeltaConfig

    @nn.compact
    def __call__(self, x):  # (B, T, hidden)
        cfg = self.config
        bsz, t, _ = x.shape
        heads, dk, dv = cfg.num_heads, cfg.key_dim, cfg.value_dim
        key_heads = cfg.key_heads
        rep, rest = divmod(heads, key_heads)
        if rest:
            raise ValueError(f"{key_heads} key heads do not divide "
                             f"{heads} value heads")

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)

        q = dense(key_heads * dk, "q_proj")(x)
        k = dense(key_heads * dk, "k_proj")(x)
        v = dense(heads * dv, "v_proj")(x)
        z = dense(heads * dv, "g_proj")(x)

        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        with jax.named_scope("gates"):
            # float32 from the projections' outputs on
            a = dense(heads, "a_proj")(x).astype(jnp.float32)
            b = dense(heads, "b_proj")(x).astype(jnp.float32)
            beta = jax.nn.sigmoid(b)
            if cfg.neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log.astype(jnp.float32)) \
                * jax.nn.softplus(a + dt_bias)
            # counted, not timed: static lanes, two sums of T x H numbers
            # (value heads over key heads: and the q and k head-rows the
            # recurrence's route reads — repeated below — beside the
            # model's)
            grouped = (heads, key_heads) if rep > 1 else ()
            self.sow("intermediates", "delta_stats", jnp.stack([
                *jnp.asarray(product_lanes(dk, dv) + grouped, jnp.float32),
                jnp.sum(jnp.exp(g)), jnp.sum(beta), jnp.float32(g.size)]))

        # one filter a channel over q | k | v: three slices of one leaf, so
        # the three projections are never laid side by side
        kernel = self.param("conv_kernel", _conv_init(cfg.conv_kernel),
                            (cfg.conv_kernel, cfg.conv_dim))
        bounds = (0, key_heads * dk, 2 * key_heads * dk, cfg.conv_dim)
        with jax.named_scope("conv"):
            filters = [kernel[:, lo:hi] for lo, hi in zip(bounds, bounds[1:])]
        q, k, v = (causal_conv_silu(a_, f, None, cfg.dtype, cfg.mesh)
                   for a_, f in zip((q, k, v), filters))

        with jax.named_scope("delta"):
            q = _l2_normalised(q.reshape(bsz, t, key_heads, dk),
                               1.0 / math.sqrt(dk))
            k = _l2_normalised(k.reshape(bsz, t, key_heads, dk))
            v = v.reshape(bsz, t, heads, dv)
            if rep > 1:
                # value head j reads key head j // rep: every route takes
                # one q and one k a state (counted, not timed)
                q, k = (jnp.repeat(a, rep, axis=2) for a in (q, k))
        o = gated_delta_rule(q, k, v, g, beta, chunk=cfg.chunk_size,
                             dtype=cfg.dtype, mesh=cfg.mesh)

        scale = self.param("gate_norm_scale", nn.initializers.ones, (dv,))
        with jax.named_scope("gate_norm"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + cfg.eps) * scale
            y = o * jax.nn.silu(z.astype(jnp.float32)
                                ).reshape(bsz, t, heads, dv)
            y = y.reshape(bsz, t, heads * dv).astype(cfg.dtype)
        return dense(cfg.hidden_size, "o_proj")(y)


@counters
def collect_delta_stats(intermediates) -> dict:
    """What the gated delta-rule mixers of one forward pass counted — {}
    for a model without one: `delta_lanes_run` and `delta_lanes_model`
    summed over the layers, `delta_alpha_mean` and `delta_beta_mean` over
    heads, tokens and layers; of the mixers whose value heads outnumber
    their key heads, `delta_qk_rows_run` and `delta_qk_rows_model` (the q
    and k head-rows the recurrence's route reads, those the model has),
    summed over those layers."""
    rows = [v.reshape(-1, v.shape[-1])
            for v in sown(intermediates, "delta_stats")]
    if not rows:
        return {}
    with jax.named_scope("delta_stats"):  # the sum's copies get an owner
        # five numbers a layer, seven where value heads outnumber key heads
        run, model, *grouped, alpha, beta, n = jnp.concatenate(rows).sum(0)
    stats = {"delta_lanes_run": run, "delta_lanes_model": model,
             "delta_alpha_mean": alpha / n, "delta_beta_mean": beta / n}
    if grouped:
        stats["delta_qk_rows_run"], stats["delta_qk_rows_model"] = grouped
    return stats
