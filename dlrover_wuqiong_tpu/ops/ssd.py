"""The chunked scan of a Mamba-2 layer (the state-space dual form).

The recurrence, per head (state S in R^{P x N}, S_0 = 0; Dao & Gu 2024,
arXiv:2405.21060):

    S_t = exp(dlt_t * A) * S_{t-1} + dlt_t * x_t (x) B_t
    y_t = S_t C_t + D * x_t

computed in chunks of L steps.  With a_t = dlt_t * A and cum_t the
running sum of a inside a chunk:

- within a chunk, y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s)
  dlt_s x_s: one masked (L x L) product a head, on the MXU;
- a chunk's own state, sum_s exp(cum_L - cum_s) dlt_s x_s (x) B_s, and
  between chunks the carried state S <- exp(cum_L) S + (that), written
  out as a sum over the earlier chunks (T/L of them: a small product);
- the carried state's part of y_t: exp(cum_t) C_t . S_in.

Everything is `jax.numpy` einsums; the backward pass is their
differentiation.  The decays (`a`, its sums, every `exp`) and the
carried state are float32 whatever `dtype` says; `dtype` is what the
matrix products' operands are rounded to (accumulation is float32).
No Pallas kernel: `benchmark/`'s `kernel.ssd_roofline` is there to say
what one would be worth.

Scopes (under the caller's): `ssd` around all of it.

Parity: none — the reference (atorch's modules and kernels) has no
state-space layer; this is the plain form of the paper's algorithm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _einsum(spec, *operands, dtype):
    return jnp.einsum(spec, *(o.astype(dtype) for o in operands),
                      preferred_element_type=jnp.float32)


@jax.named_scope("ssd")
def ssd_scan(x, dlt, a, b_mat, c_mat, d_skip, chunk: int = 128,
             dtype=jnp.float32):
    """x (b, T, H, P); dlt (b, T, H), the step sizes AFTER softplus;
    a (H,), negative; b_mat, c_mat (b, T, G, N), head h using group
    h // (H/G); d_skip (H,).  Returns y (b, T, H, P) in float32.

    T must be a multiple of `chunk`: a ragged last chunk would need a
    padded copy of every operand, and no caller has one."""
    bsz, t, h, p = x.shape
    g, n = b_mat.shape[2:]
    if t % chunk:
        raise ValueError(f"ssd_scan: sequence {t} is no multiple of the "
                         f"chunk {chunk}")
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads do not divide into {g} "
                         f"groups")
    c, r = t // chunk, h // g
    x32 = x.astype(jnp.float32)
    dlt = dlt.astype(jnp.float32)
    # (b, chunks, L, G, R, ...): a head is (group, rank in group)
    xs = x32.reshape(bsz, c, chunk, g, r, p)
    dl = dlt.reshape(bsz, c, chunk, g, r)
    bm = b_mat.reshape(bsz, c, chunk, g, n)
    cm = c_mat.reshape(bsz, c, chunk, g, n)
    cum = jnp.cumsum(dl * a.astype(jnp.float32).reshape(g, r), axis=2)
    xdt = xs * dl[..., None]                          # dlt_s x_s

    # within a chunk: decay[t, s] = exp(cum_t - cum_s) for s <= t, else 0
    # (the masked entries' differences are positive: mask BEFORE exp);
    # the (L x L) pair is minor, the heads are batch dimensions
    cum_h = jnp.moveaxis(cum, 2, -1)                  # (b, c, G, R, L)
    diff = cum_h[..., :, None] - cum_h[..., None, :]  # (b, c, G, R, t, s)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)),
                              diff, -jnp.inf))
    cb = _einsum("bctgn,bcsgn->bcgts", cm, bm, dtype=dtype)
    y = _einsum("bcgrts,bcsgrp->bctgrp", cb[:, :, :, None] * decay, xdt,
                dtype=dtype)

    # a chunk's own state, and the state carried from chunk to chunk
    total = cum[:, :, -1]                             # (b, c, G, R)
    to_end = jnp.exp(total[:, :, None] - cum)         # (b, c, L, G, R)
    own = _einsum("bcsgn,bcsgrp->bcgrpn", bm, xdt * to_end[..., None],
                  dtype=dtype)

    # the state ENTERING chunk j: sum over i < j of own_i decayed by the
    # totals of the chunks between them, one (chunks x chunks) product a
    # head in float32 at full precision, where a loop over the chunks
    # would be a `while` in the compiled step (an op that holds others)
    low = jnp.tril(jnp.ones((c, c), bool), -1)
    tot_h = jnp.moveaxis(total, 1, -1)                # (b, G, R, c)
    between = jnp.cumsum(                             # [j', i]: i < m <= j'
        jnp.where(low, tot_h[..., :, None], 0.0), axis=-2)
    carried = jnp.exp(jnp.where(low, jnp.roll(between, 1, axis=-2),
                                -jnp.inf))           # [j, i]: i < m < j
    entering = jnp.einsum("bgrji,bigrpn->bjgrpn", carried, own,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + _einsum("bctgn,bcgrpn->bctgrp", cm, entering, dtype=dtype) \
        * jnp.exp(cum)[..., None]
    y = y + xs * d_skip.astype(jnp.float32).reshape(g, r, 1)
    return y.reshape(bsz, t, h, p)
