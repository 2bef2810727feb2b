"""Manifold-constrained hyper-connections (Xie et al., "mHC:
Manifold-Constrained Hyper-Connections", arXiv:2512.24880, over Zhu et
al., "Hyper-Connections", arXiv:2409.19606): the residual stream is n
lanes of the hidden size, a sublayer F reads ONE learned mix of them and
writes back through a learned 1 x n and a doubly-stochastic n x n.

    z      = RMSNorm_{n d}(vec(X))              no learned scale
    a_pre  = alpha_pre  (z Phi_pre)  + b_pre    in R^n
    a_post = alpha_post (z Phi_post) + b_post   in R^n
    A_res  = alpha_res mat(z Phi_res) + b_res   in R^{n x n}
    h_pre  = sigmoid(a_pre);  h_post = 2 sigmoid(a_post)
    H_res  = Sinkhorn(clip(A_res, lo, hi)):  M = exp(.), then `iters`
             times  M /= colsum(M) + eps;  M /= rowsum(M) + eps
    u      = sum_i h_pre[i] X[i];   y = F(u)
    X'[i]  = sum_j H_res[i, j] X[j] + h_post[i] y

The stream is carried as (b, n, T, d): a lane is a LEADING axis, so the
bf16 tiles of (T, d) hold no padding (with (.., n, d) as the last two
axes a (16, 128) tile would pad 4 lanes to 16) and a lane is a slice
along a major axis.  The coefficients are float32 with the tokens on
the LANE axis — (b, n, T) and (b, n, n, T) — so Sinkhorn's twenty
rounds (one `lax.scan`) and what their backward keeps are whole tiles
of tokens, not one padded tile a token.  `z Phi` is computed as `(X
Phi) * rsqrt(mean(X^2) + eps)`: the norm's factor is one number a
token, so z itself is never written.

Two routes, chosen by what a call can observe (`ops/hc_mix.hc_route`:
the backend, the devices, the hidden size, the tokens), never by a
knob; a block enters through `mix_in` and leaves through `mix_out`:

- "kernel" (the TPU, one device, d a whole number of 128-lane slabs):
  `ops/hc_mix.py`'s four Pallas kernels.  `dwt_hc_pre` reads the
  stream once and makes the norm's statistic, the product, h_pre and u
  from it; `dwt_hc_post` writes all n lanes of X' from one read of the
  n + 1 inputs; backward `dwt_hc_post_bwd` makes dy, H_res^T dX' and
  the n^2 + n dot products from one read, and `dwt_hc_pre_bwd` adds
  every cotangent of the stream — through `write`, through `read`,
  through the product and through the statistic — in float32 into ONE
  rounding and accumulates Phi's.  55 hidden vectors a token a sublayer
  where the plain route's fusions moved 130 (ROADMAP S16; PERF.md
  section 6, PR 54).  Sinkhorn, the gains, the biases and h_post's
  sigmoid stay `jax.numpy` on (b, k, T) float32 arrays.
- "plain" (the CPU, a mesh, any other width): the formulas below, the
  tests' oracle.  `read` and `write` are sums and stacks of lane
  slices with written-out backward rules (plain autodiff of the same
  forward pads each lane's cotangent into a zero stream and adds them
  up: the described-`v5e` compile of Xing4.0's step at 1 x 8192 held
  5.14 GB of temporaries so, 4.75 with the rules); `coefficients` is
  plain autodiff, so on this route the stream's three cotangents (of
  `write`, of `read`, of the product and the statistic) are written a
  lane at a time, joined and added by the compiler's fusions.

Scopes in the compiled step, the same on both routes: `hc/coeff` (the
gains, biases and sigmoids; on the plain route also the statistic and
the product), `hc/sinkhorn`, `hc/pre` (u; `dwt_hc_pre`, `dwt_hc_pre_bwd`)
and `hc/post_res` (X'; `dwt_hc_post`, `dwt_hc_post_bwd`), forward and
backward alike (the custom rules open them themselves), and
`hc/expand` / `hc/read_out` at the stack's two ends.  The leaves sit in
the module `<sublayer>_hc` (`HyperConnection`): `phi` (n, d, n^2 + 2n),
`alpha` (3: pre, post, res), `b_pre`, `b_post` (n), `b_res` (n, n).

Not built: a mesh (the (b, n, T, d) carry has no pins under `fsdp` or
`tp`), a learned read-out of the lanes (the stack sums them), Sinkhorn's
rounds inside a kernel (3 ms of a 420 ms step: launches, not bytes).

Parity: none — the reference trains Llama/GLM-class stacks only.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import hc_mix
from .sown import counters, sown


@dataclasses.dataclass(frozen=True)
class HyperConnectionConfig:
    hidden_size: int = 3584
    lanes: int = 4  # hc_mult
    sinkhorn_iters: int = 20
    eps: float = 1e-6  # hc_eps: Sinkhorn's denominators
    norm_eps: float = 1e-6  # the mixing norm's (rms_norm_eps)
    clamp: tuple = (-30.0, 30.0)  # on A_res before exp

    @property
    def outputs(self) -> int:
        return self.lanes * (self.lanes + 2)

    def num_params(self) -> int:
        n = self.lanes
        return n * self.hidden_size * self.outputs + 3 + 2 * n + n * n


# the papers' init: Phi = 0, every gain 0.01, b_res a scaled identity
_ALPHA_INIT = 0.01
_RES_INIT = 2.0


class HyperConnection(nn.Module):
    """The leaves of one sublayer's hyper-connection, float32.  At init
    the block is a plain residual on the lanes' mean: Phi = 0, h_pre =
    1/n, h_post = 1, H_res near the identity."""
    config: HyperConnectionConfig

    @nn.compact
    def __call__(self) -> dict:
        cfg, n = self.config, self.config.lanes
        return {
            "phi": self.param("phi", nn.initializers.zeros,
                              (n, cfg.hidden_size, cfg.outputs)),
            "alpha": self.param(
                "alpha", nn.initializers.constant(_ALPHA_INIT), (3,)),
            "b_pre": self.param(
                "b_pre", nn.initializers.constant(-math.log(n - 1.0)), (n,)),
            "b_post": self.param("b_post", nn.initializers.zeros, (n,)),
            "b_res": self.param(
                "b_res", lambda *_: _RES_INIT * jnp.eye(n), (n, n))}


def sinkhorn(logits, iters: int, eps: float, clamp: tuple):
    """logits (b, n, n, T) float32 -> H_res, rows and columns summing to
    1 within what `iters` rounds leave: columns first, then rows."""
    def one_round(m, _):
        m = m / (m.sum(1, keepdims=True) + eps)  # colsum: over i
        return m / (m.sum(2, keepdims=True) + eps), None  # rowsum: over j

    # a loop, not `iters` copies of the round: the rounds are 16 numbers
    # a token, so unrolled they buy no fusion across rounds and cost a
    # step of ten sublayers a minute of compiling
    return jax.lax.scan(one_round, jnp.exp(jnp.clip(logits, *clamp)), None,
                        length=iters)[0]


def sinkhorn_err(h_res):
    """The largest |rowsum - 1| and |colsum - 1| over the tokens: what
    the iterations left."""
    with jax.named_scope("hc"), jax.named_scope("sinkhorn"):
        return jnp.maximum(jnp.abs(h_res.sum(2) - 1).max(),
                           jnp.abs(h_res.sum(1) - 1).max())


def _post_res(leaves: dict, raw, cfg: HyperConnectionConfig):
    """The raw coefficients of the normed stream (b, >= n^2 + 2n, T),
    z Phi's rows -> h_post (b, n, T) and H_res (b, n, n, T)."""
    n = cfg.lanes
    with jax.named_scope("hc"), jax.named_scope("coeff"):
        alpha = leaves["alpha"]
        a_post = alpha[1] * raw[:, n:2 * n] + leaves["b_post"][:, None]
        a_res = alpha[2] * raw[:, 2 * n:n * (n + 2)].reshape(
            -1, n, n, raw.shape[-1]) + leaves["b_res"][:, :, None]
        h_post = 2 * jax.nn.sigmoid(a_post)
    with jax.named_scope("hc"), jax.named_scope("sinkhorn"):
        h_res = sinkhorn(a_res, cfg.sinkhorn_iters, cfg.eps, cfg.clamp)
    return h_post, h_res


def coefficients(leaves: dict, x, cfg: HyperConnectionConfig):
    """x (b, n, T, d) -> h_pre (b, n, T), h_post (b, n, T), H_res
    (b, n, n, T), float32."""
    n = cfg.lanes
    with jax.named_scope("hc"), jax.named_scope("coeff"):
        x32 = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=(1, 3)) + cfg.norm_eps)
        phi = leaves["phi"].astype(x.dtype)
        raw = sum(jnp.einsum("btd,dk->btk", x[:, i], phi[i],
                             preferred_element_type=jnp.float32)
                  for i in range(n))
        # tokens to the lane axis: (b, n^2 + 2n, T)
        raw = (raw * rms[..., None]).transpose(0, 2, 1)
        h_pre = jax.nn.sigmoid(leaves["alpha"][0] * raw[:, :n]
                               + leaves["b_pre"][:, None])
    return (h_pre, *_post_res(leaves, raw, cfg))


def mix_in(leaves: dict, x, cfg: HyperConnectionConfig, mesh=None):
    """A sublayer's way in: x (b, n, T, d) -> u (b, T, d), the stream
    for `mix_out`, h_post and H_res.  On the kernel route
    (`ops/hc_mix.hc_route`) ONE pass over the stream makes u and the raw
    coefficients, and the stream it returns carries the cotangent of
    `mix_out` back into that pass's backward; on the plain route it is x
    itself, `coefficients` and `read`."""
    _, n, t, d = x.shape
    if hc_mix.hc_route(n, t, d, mesh) == "plain":
        h_pre, h_post, h_res = coefficients(leaves, x, cfg)
        return read(h_pre, x), x, h_post, h_res
    u, coef, x = hc_mix.mix_in(x, leaves["phi"], leaves["alpha"][0],
                               leaves["b_pre"], cfg.norm_eps, hc_mix.plan(t))
    return (u, x, *_post_res(leaves, coef, cfg))


def mix_out(h_res, h_post, x, y, mesh=None):
    """A sublayer's way out, X' of `write`, on `mix_in`'s route."""
    _, n, t, d = x.shape
    if hc_mix.hc_route(n, t, d, mesh) == "plain":
        return write(h_res, h_post, x, y)
    return hc_mix.mix_out(h_res, h_post, x, y, hc_mix.plan(t))


def _weighted(weights, parts, dtype):
    """sum_k weights[k] (b, T) * parts[k] (b, T, d), float32 sums."""
    return sum(w[..., None] * p.astype(jnp.float32)
               for w, p in zip(weights, parts)).astype(dtype)


def _dots(a, b):
    """(b, T): a . b over the hidden size, float32."""
    return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32), axis=-1)


@jax.custom_vjp
def read(h_pre, x):
    """u = sum_i h_pre[i] X[i]: (b, n, T), (b, n, T, d) -> (b, T, d)."""
    return _read_fwd(h_pre, x)[0]


def _read_fwd(h_pre, x):
    n = x.shape[1]
    with jax.named_scope("hc"), jax.named_scope("pre"):
        u = _weighted([h_pre[:, i] for i in range(n)],
                      [x[:, i] for i in range(n)], x.dtype)
    return u, (h_pre, x)


def _read_bwd(res, du):
    h_pre, x = res
    n = x.shape[1]
    with jax.named_scope("hc"), jax.named_scope("pre"):
        dx = jnp.stack([_weighted([h_pre[:, i]], [du], x.dtype)
                        for i in range(n)], axis=1)
        dh = jnp.stack([_dots(du, x[:, i]) for i in range(n)], axis=1)
    return dh, dx


read.defvjp(_read_fwd, _read_bwd)


@jax.custom_vjp
def write(h_res, h_post, x, y):
    """X'[i] = sum_j H_res[i, j] X[j] + h_post[i] y."""
    return _write_fwd(h_res, h_post, x, y)[0]


def _write_fwd(h_res, h_post, x, y):
    n = x.shape[1]
    with jax.named_scope("hc"), jax.named_scope("post_res"):
        lanes = [x[:, j] for j in range(n)]
        out = jnp.stack([_weighted(
            [h_res[:, i, j] for j in range(n)] + [h_post[:, i]],
            lanes + [y], x.dtype) for i in range(n)], axis=1)
    return out, (h_res, h_post, x, y)


def _write_bwd(res, d_out):
    h_res, h_post, x, y = res
    n = x.shape[1]
    with jax.named_scope("hc"), jax.named_scope("post_res"):
        d_lanes = [d_out[:, i] for i in range(n)]
        dx = jnp.stack([_weighted([h_res[:, i, j] for i in range(n)],
                                  d_lanes, x.dtype) for j in range(n)],
                       axis=1)
        dy = _weighted([h_post[:, i] for i in range(n)], d_lanes, y.dtype)
        d_res = jnp.stack([jnp.stack([_dots(d_lanes[i], x[:, j])
                                      for j in range(n)], axis=1)
                           for i in range(n)], axis=1)
        d_post = jnp.stack([_dots(d_lanes[i], y) for i in range(n)], axis=1)
    return d_res, d_post, dx, dy


write.defvjp(_write_fwd, _write_bwd)


def expand(x, lanes: int):
    """(b, T, d) -> (b, n, T, d): every lane starts as the embedding."""
    with jax.named_scope("hc"), jax.named_scope("expand"):
        return jnp.broadcast_to(x[:, None],
                                (x.shape[0], lanes, *x.shape[1:]))


def read_out(x):
    """(b, n, T, d) -> (b, T, d): the lanes' sum."""
    with jax.named_scope("hc"), jax.named_scope("read_out"):
        return x.sum(1).astype(x.dtype)


@counters
def collect_residual_stats(intermediates) -> dict:
    """What the hyper-connections of one forward pass counted — {} for a
    model without one: `resmix_sinkhorn_err`, the largest deviation of a
    row or column sum of any sublayer's H_res from 1 over the step's
    tokens."""
    errs = [v.reshape(-1) for v in sown(intermediates, "hc_sinkhorn_err")]
    if not errs:
        return {}
    with jax.named_scope("hc_stats"):  # the max's copies get an owner
        return {"resmix_sinkhorn_err": jnp.concatenate(errs).max()}
