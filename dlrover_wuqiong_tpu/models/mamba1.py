"""Mamba-1 mixer (selective state-space layer) over
`ops/selective_scan.py`'s scan.

    [x | z] = u @ W_in                   d_inner | d_inner, no bias
    x   = silu(causal_depthwise_conv1d(x) + b_conv)
    [r | B | C] = x @ W_x                dt_rank | N | N, no bias
    dt  = softplus(r @ W_dt + b_dt)      (T, d_inner), float32
    y   = selective_scan(x, dt, -exp(A_log), B, C, D)    A_log (d_inner, N)
    out = (y * silu(z)) @ W_out          no bias

The mixer returns (out, y): y — after the D x term, before the gate — is
what a decoder-hybrid-decoder stack hands its gated memory units
(models/phi4flash.py); a caller that hands nothing on drops it.

Scopes, under the module's own name: `in_proj`, `conv` (on one TPU
device the `dwt_conv_*` kernels' custom calls, `ops/short_conv.py`, at
d_inner channels read where they lie inside [x | z]), `x_proj`,
`dt_proj` (the product, its bias and the softplus), `sscan` (the decay
rates and all of the scan: on one TPU device the `dwt_sscan_*` kernels'
custom calls), `gate`, `out_proj`.  Parameter names are matched by
`parallel/sharding.py` (the projections as dense kernels, everything
else replicated).

Which route the scan takes is `ops/selective_scan.sscan_route`'s to say,
from the call's shapes and where it runs (the backend and
`Mamba1Config.mesh`).

Parity: none — the reference's model zoo (atorch) is attention-only; the
equations are the paper's (Gu & Dao 2023, arXiv:2312.00752), as
benchmark/reference_phi4flash.py writes them out.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.selective_scan import selective_scan
from .mamba2 import _conv_init, _dt_bias_init, causal_conv_silu


@dataclasses.dataclass(frozen=True)
class Mamba1Config:
    hidden_size: int = 256
    expand: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    dt_rank: int = 0  # 0 = ceil(hidden_size / 16), the paper's "auto"
    dtype: Any = jnp.bfloat16
    mesh: Any = None  # the model config's (set by auto_accelerate)
    # initialiser settings of dt_proj's bias, not a clamp in the forward
    # pass: softplus(bias) is drawn log-uniform in [dt_min, dt_max]
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    def num_params(self) -> int:
        h, di, n, r = (self.hidden_size, self.d_inner, self.state_size,
                       self.rank)
        return (h * 2 * di                       # in_proj
                + (self.conv_kernel + 1) * di    # conv + bias
                + di * (r + 2 * n)               # x_proj
                + r * di + di                    # dt_proj + bias
                + di * n + di                    # A_log, D
                + di * h)                        # out_proj


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(1 .. N) along the states, the same for every channel (the
    paper's S4D-real initialiser)."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
        shape).astype(dtype)


def _dt_kernel_init(rank: int):
    bound = 1.0 / math.sqrt(rank)  # the paper's dt_init "random"

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class Mamba1Mixer(nn.Module):
    config: Mamba1Config

    @nn.compact
    def __call__(self, u):  # (B, T, hidden) -> (out, y)
        cfg = self.config
        di, n, r = cfg.d_inner, cfg.state_size, cfg.rank
        proj = nn.Dense(2 * di, use_bias=False, dtype=cfg.dtype,
                        name="in_proj")(u)
        x, z = jnp.split(proj, 2, axis=-1)

        kernel = self.param("conv_kernel", _conv_init(cfg.conv_kernel),
                            (cfg.conv_kernel, di))
        bias = self.param("conv_bias", _conv_init(cfg.conv_kernel), (di,))
        x = causal_conv_silu(x, kernel, bias, cfg.dtype, cfg.mesh,
                             source=(proj, 0))

        rbc = nn.Dense(r + 2 * n, use_bias=False, dtype=cfg.dtype,
                       name="x_proj")(x)
        low, b_mat, c_mat = jnp.split(rbc, [r, r + n], axis=-1)
        # the step sizes are float32 from the product's accumulator on
        w_dt = self.param("dt_proj_kernel", _dt_kernel_init(r), (r, di))
        b_dt = self.param("dt_proj_bias", _dt_bias_init(cfg), (di,))
        with jax.named_scope("dt_proj"):
            dt = jax.nn.softplus(jnp.einsum(
                "btr,rd->btd", low, w_dt.astype(cfg.dtype),
                preferred_element_type=jnp.float32) + b_dt)

        a_log = self.param("A_log", _a_log_init, (di, n))
        d_skip = self.param("D", nn.initializers.ones, (di,))
        # `selective_scan` opens the `sscan` scope itself
        with jax.named_scope("sscan"):
            a = -jnp.exp(a_log.astype(jnp.float32))
        y = selective_scan(x, dt, a, b_mat, c_mat, d_skip, mesh=cfg.mesh)
        with jax.named_scope("gate"):
            gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
        out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                       name="out_proj")(gated)
        return out, y.astype(cfg.dtype)
