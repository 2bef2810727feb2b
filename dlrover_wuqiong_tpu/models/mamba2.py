"""Mamba-2 mixer (state-space layer) over `ops/ssd.py`'s chunked scan.

    [z | xBC | dt] = u @ W_in           d_inner | d_inner + 2*G*N | H
    xBC = silu(causal_depthwise_conv1d(xBC) + b_conv)
    [x | B | C] = xBC                   x (T,H,P); B, C (T,G,N)
    y   = ssd_scan(x, softplus(dt + dt_bias), -exp(A_log), B, C, D)
    y   = RMSNorm over each group of d_inner/G features of y * silu(z),
          one (d_inner,) scale
    out = y @ W_out

d_inner = heads x head_dim: the projections' widths are given, not
derived from an expansion factor.  Scopes, under the module's own name:
`in_proj`, `conv` (on one TPU device the `dwt_conv_*` kernels' custom
calls, `ops/short_conv.py`), `ssd` (the step sizes, the decay rates and
all of the scan: on one TPU device the `dwt_ssd_*` kernels' custom
calls, forward, recomputed and backward, carry it), `gate_norm`,
`out_proj`.  Parameter names are matched by `parallel/sharding.py` (the
two projections as dense kernels, everything else replicated).

Which route the scan takes is `ops/ssd.scan_route`'s to say, from the
call's shapes and where it runs (the backend and `Mamba2Config.mesh`).

Parity: none — the reference's model zoo (atorch) is attention-only; the
equations are `nemotron_h`'s, as benchmark/reference_nemotron_h.py
writes them out.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import short_conv
from ..ops.ssd import ssd_scan


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    hidden_size: int = 256
    num_heads: int = 8
    head_dim: int = 32
    n_groups: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    mesh: Any = None  # the model config's (set by auto_accelerate)
    # initialiser settings of dt_bias, not a clamp in the forward pass:
    # softplus(dt_bias) is drawn log-uniform in [dt_min, dt_max], >= floor
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    def num_params(self) -> int:
        h, di = self.hidden_size, self.d_inner
        return (h * (di + self.conv_dim + self.num_heads)  # in_proj
                + (self.conv_kernel + 1) * self.conv_dim   # conv + bias
                + 3 * self.num_heads                       # dt_bias A_log D
                + di + di * h)                             # gate_norm out


def _dt_bias_init(cfg: Mamba2Config):
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(cfg.dt_max) - math.log(cfg.dt_min))
                     + math.log(cfg.dt_min))
        dt = jnp.maximum(dt, cfg.dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _conv_init(width: int):
    bound = 1.0 / math.sqrt(width)  # a depthwise filter's fan-in

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


@jax.named_scope("conv")
def causal_conv_silu(x, kernel, bias, dtype, mesh=None, source=None):
    """silu(causal depthwise convolution of x (b, T, channels) along T):
    `kernel` (taps, channels), one filter a channel, its LAST tap on the
    current step; `bias` (channels,) or None.  The short convolution of
    this mixer, of `models/gated_delta.py`'s and of `models/kda.py`'s.

    Which route it takes is `ops/short_conv.conv_route`'s to say, from
    the call's shapes and where it runs (the backend and `mesh`, the
    model config's): the Pallas pair `dwt_conv_fwd` / `dwt_conv_bwd`
    (one read and one write of the rows, float32 inside, rounded once),
    or these lines — shifted products in `dtype`, which the compiler
    runs as four fusions and 20 to 25 passes over the rows a layer
    (PERF.md section 6, PR 59) — on every CPU run, a mesh of several
    devices, channels that are no whole lane tiles, and as the kernels'
    oracle.  `source` (rows, lane), for an x that is the slice
    `rows[..., lane:lane + channels]`, lets the kernels read it where it
    lies; the plain lines never look at it."""
    k, t = kernel.shape[0], x.shape[1]
    if short_conv.conv_route(t, x.shape[2], k, dtype, mesh) == "kernel":
        return short_conv.conv_silu_rows(x, kernel, bias, dtype, source)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * kernel[j].astype(dtype)
               for j in range(k))
    return jax.nn.silu(conv if bias is None else conv + bias.astype(dtype))


class Mamba2Mixer(nn.Module):
    config: Mamba2Config

    @nn.compact
    def __call__(self, u):  # (B, T, hidden)
        cfg = self.config
        bsz, t, _ = u.shape
        di, gn = cfg.d_inner, cfg.n_groups * cfg.state_size
        proj = nn.Dense(di + cfg.conv_dim + cfg.num_heads, use_bias=False,
                        dtype=cfg.dtype, name="in_proj")(u)
        z, xbc, dt = jnp.split(proj, [di, di + cfg.conv_dim], axis=-1)

        kernel = self.param("conv_kernel", _conv_init(cfg.conv_kernel),
                            (cfg.conv_kernel, cfg.conv_dim))
        bias = self.param("conv_bias", _conv_init(cfg.conv_kernel),
                          (cfg.conv_dim,))
        xbc = causal_conv_silu(xbc, kernel, bias, cfg.dtype, cfg.mesh,
                               source=(proj, di))
        x, b_mat, c_mat = jnp.split(xbc, [di, di + gn], axis=-1)

        dt_bias = self.param("dt_bias", _dt_bias_init(cfg),
                             (cfg.num_heads,))
        a_log = self.param("A_log", _a_log_init, (cfg.num_heads,))
        d_skip = self.param("D", nn.initializers.ones, (cfg.num_heads,))
        # `ssd_scan` opens the `ssd` scope itself; the step sizes and the
        # decay rates are float32 from here on
        with jax.named_scope("ssd"):
            dlt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            a = -jnp.exp(a_log.astype(jnp.float32))
        y = ssd_scan(
            x.reshape(bsz, t, cfg.num_heads, cfg.head_dim), dlt, a,
            b_mat.reshape(bsz, t, cfg.n_groups, cfg.state_size),
            c_mat.reshape(bsz, t, cfg.n_groups, cfg.state_size),
            d_skip, chunk=cfg.chunk_size, dtype=cfg.dtype, mesh=cfg.mesh)

        scale = self.param("gate_norm_scale", nn.initializers.ones, (di,))
        with jax.named_scope("gate_norm"):
            y = y.reshape(bsz, t, di) * jax.nn.silu(z.astype(jnp.float32))
            grouped = y.reshape(bsz, t, cfg.n_groups, di // cfg.n_groups)
            grouped = grouped * jax.lax.rsqrt(
                jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.eps)
            y = (grouped.reshape(bsz, t, di) * scale).astype(cfg.dtype)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(y)
