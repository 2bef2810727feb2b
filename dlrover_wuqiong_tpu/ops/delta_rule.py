"""The gated delta rule of a linear-attention layer, in chunks.

The recurrence, per head (state S in R^{dk x dv}, S_0 = 0; Yang, Kautz &
Hatamizadeh 2024, arXiv:2412.06464), with k and q already normalised, a
log-decay g_t <= 0 (alpha_t = exp(g_t)) and a write gate beta_t:

    u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)
    S_t = alpha_t S_{t-1} + k_t u_t^T
    o_t = S_t^T q_t

The state is CORRECTED by what it already returns for the key, which is
what a Mamba-2 state (`ops/ssd.py`: a decayed sum of outer products) is
not: u_t depends on every earlier u of the chunk, and a chunk's
transition of the entering state is a (dk x dk) MATRIX, not a number.

In chunks of C steps, with b_r the running sum of g inside the chunk and
S_in the state entering it:

- the solve.  (I + L) U = diag(beta) V - diag(beta e^b) K S_in, where
  L_rs = beta_r e^{b_r - b_s} (k_r . k_s) for s < r and 0 elsewhere.
  With T = (I + L)^{-1}: U = U0 - W S_in, U0 = T diag(beta) V,
  W = T diag(beta e^b) K;
- the output.  o_r = e^{b_r} S_in^T q_r + sum_{s<=r} e^{b_r - b_s}
  (k_s . q_r) u_s: two masked (C x C) products a head and one against
  the entering state;
- the carry.  S_out = e^{b_C} S_in + Kd^T U with Kd_s = e^{b_C - b_s}
  k_s, an AFFINE step S_out = A S_in + B with A = e^{b_C} I - Kd^T W
  (dk x dk) and B = Kd^T U0 (dk x dv).

T, by forward substitution in blocks: (I + L) cut into diagonal blocks
of s steps has the inverse D_s; with E the sub-diagonal blocks that
join two s-blocks into one of 2s, D_2s = D_s - D_s E D_s exactly (E D_s E
= 0), so log2 C rounds of two (C x C) products from D_1 = I.  NOT the
nilpotent product (I - L)(I + L^2)(I + L^4)...: its factors hold powers
of L, and where a chunk's keys agree and beta nears 2 (the write gate's
range is (0, 2)) L^32's entries pass 1e27 while the inverse's stay
under 2 — float32 cancels to nothing.  The block form only ever
multiplies inverses of diagonal blocks, which are bounded as the result
is.  Its cotangent is -T^T dT T^T (a `custom_vjp`: two products, not
the differentiation of the rounds).

The chunk-to-chunk step holds no loop: the entering states are one
`jax.lax.associative_scan` over the (A, B) pairs ((A2, B2) o (A1, B1) =
(A2 A1, A2 B1 + B2)), which unrolls into log2(chunks) rounds of batched
products — a `while` in the compiled step would be an op that holds
others, which a device trace counts beside them.  Its backward pass is
the same scan run from the last chunk with A^T (a `custom_vjp`: the
entering states are kept, the rounds are not).

The decays (g, its sums, every `exp`), the gates, the solve and the
carried state are float32 whatever `dtype` says; `dtype` is what the
other products' operands are rounded to (accumulation is float32); the
mask is applied BEFORE every exp.

Routes, chosen by `delta_route` from what a call can observe (its
shapes), never by a knob:

- "chunked": the form above in `jax.numpy`, the backward pass its
  differentiation but for the two `custom_vjp`s.  GSPMD partitions it, so
  a mixer on a mesh of several devices runs it too.
- "sequential": `lax.scan` over time, for a sequence that is no whole
  number of chunks (a parameter draw on a few tokens) — and the tests'
  oracle.

There is no kernel route yet: a Pallas pair with the state in a VMEM
scratch along a sequential grid axis (`ops/ssd.py`'s design) would take
the within-chunk products, the solve and the carry into one kernel
(ROADMAP M6).  `benchmark/`'s `kernel.delta_roofline` counts the
RECURRENCE's work from shapes, whatever computes it.

Scopes (under the caller's): `delta` around all of it.

Parity: none — the reference (atorch's modules and kernels) has no
linear-attention layer; this is the paper's algorithm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def _einsum(spec, *operands, dtype):
    return jnp.einsum(spec, *(o.astype(dtype) for o in operands),
                      preferred_element_type=jnp.float32)


def _einsum32(spec, *operands):
    return jnp.einsum(spec, *operands, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def delta_route(t: int, chunk: int) -> str:
    """Which route `gated_delta_rule` takes at these shapes: "chunked"
    where the sequence is a whole number of chunks, else "sequential"."""
    return "chunked" if t >= chunk and t % chunk == 0 else "sequential"


def product_lanes(dk: int, dv: int) -> tuple:
    """(lanes run, lanes the model asks) of the products that meet a head's
    state, key side | value side.  No route pads a head today (XLA tiles
    dk and dv as they are), so both read dk + dv; a kernel route that lays
    keys of 96 on a 128-lane slab would run 128 + dv."""
    return dk + dv, dk + dv


@jax.named_scope("delta")
def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.float32):
    """q, k (b, T, H, dk), normalised and q scaled; v (b, T, H, dv);
    g (b, T, H), the log of the decay (<= 0); beta (b, T, H), the write
    gate.  Returns o (b, T, H, dv) in float32."""
    if delta_route(q.shape[1], chunk) == "chunked":
        return _chunked(q, k, v, g, beta, chunk, dtype)
    return gated_delta_rule_sequential(q, k, v, g, beta)


def gated_delta_rule_sequential(q, k, v, g, beta):
    """The recurrence as it is written, one step at a time, float32."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    bsz, _, h, dk = k.shape

    def step(state, qkvgb):
        q_t, k_t, v_t, g_t, b_t = qkvgb
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - _einsum32("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, _einsum32("bhkv,bhk->bhv", state, q_t)

    s0 = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


# --------------------------------------------------------------- the solve

@jax.custom_vjp
def _unit_lower_inverse(low):
    """(I + low)^{-1} for `low` (..., C, C) strictly lower triangular."""
    n = low.shape[-1]
    rows = np.arange(n)[:, None]
    cols = np.arange(n)[None, :]
    inv = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), low.shape)
    s = 1
    while s < n:
        joins = (rows // (2 * s) == cols // (2 * s)) & \
            (rows // s != cols // s)
        e = jnp.where(joins, low, 0.0)
        inv = inv - _einsum32("...rm,...mn,...ns->...rs", inv, e, inv)
        s *= 2
    return inv


def _unit_lower_inverse_fwd(low):
    inv = _unit_lower_inverse(low)
    return inv, inv


def _unit_lower_inverse_bwd(inv, d_inv):
    return (-_einsum32("...mr,...mn,...sn->...rs", inv, d_inv, inv),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


# --------------------------------------------------------------- the carry

def _compose(first, second):
    a1, b1 = first
    a2, b2 = second
    return (_einsum32("...ij,...jk->...ik", a2, a1),
            _einsum32("...ij,...jv->...iv", a2, b1) + b2)


def _run(a_mat, b_mat):
    """S_j = A_j S_{j-1} + B_j with S_{-1} = 0, every j, along axis 1."""
    return jax.lax.associative_scan(_compose, (a_mat, b_mat), axis=1)[1]


def _shift(x):
    """x moved one chunk later along axis 1, zeros first."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))


@jax.custom_vjp
def _entering(a_mat, b_mat):
    """The state ENTERING each chunk: a_mat (b, c, H, dk, dk), b_mat
    (b, c, H, dk, dv) -> (b, c, H, dk, dv); chunk 0's is zero."""
    return _shift(_run(a_mat, b_mat))


def _entering_fwd(a_mat, b_mat):
    states = _entering(a_mat, b_mat)
    return states, (a_mat, states)


def _entering_bwd(res, d_states):
    a_mat, states = res
    # G_j, the whole cotangent of the state entering chunk j:
    # G_j = d_states_j + A_j^T G_{j+1} — the same scan from the last chunk
    flipped = _run(jnp.flip(jnp.swapaxes(a_mat, -1, -2), 1),
                   jnp.flip(d_states, 1))
    later = jnp.flip(_shift(flipped), 1)              # G_{j+1}; last: zero
    return (_einsum32("bchiv,bchjv->bchij", later, states), later)


_entering.defvjp(_entering_fwd, _entering_bwd)


# ------------------------------------------------------------ the chunks

def _chunked(q, k, v, g, beta, chunk, dtype):
    bsz, t, h, dk = k.shape
    dv = v.shape[-1]
    c = t // chunk

    def cut(x):  # (b, T, H, ...) -> (b, chunks, C, H, ...)
        return x.reshape(bsz, c, chunk, *x.shape[2:])

    def by_step(x):  # (b, chunks, H, C) -> (b, chunks, C, H, 1)
        return jnp.moveaxis(x, -1, 2)[..., None]

    qs, ks, vs = cut(q), cut(k), cut(v)
    gs = jnp.moveaxis(cut(g.astype(jnp.float32)), 2, -1)      # (b, c, H, C)
    bt = jnp.moveaxis(cut(beta.astype(jnp.float32)), 2, -1)
    cum = jnp.cumsum(gs, axis=-1)
    total = cum[..., -1]                                      # (b, c, H)

    # decay[r, s] = exp(b_r - b_s) for s <= r (masked entries' differences
    # are positive: mask BEFORE exp)
    tril = np.tril(np.ones((chunk, chunk), bool))
    diff = cum[..., :, None] - cum[..., None, :]
    decay = jnp.exp(jnp.where(tril, diff, -jnp.inf))          # (b,c,H,r,s)

    kk = _einsum("bcrhd,bcshd->bchrs", ks, ks, dtype=dtype)
    low = jnp.where(np.tril(tril, -1), bt[..., :, None] * decay * kk, 0.0)
    solve = _unit_lower_inverse(low)                          # T, float32

    u0 = _einsum("bchrs,bcshv->bcrhv", solve,
                 vs.astype(jnp.float32) * by_step(bt), dtype=dtype)
    w = _einsum("bchrs,bcshd->bcrhd", solve,
                ks.astype(jnp.float32) * by_step(bt * jnp.exp(cum)),
                dtype=dtype)

    kd = ks.astype(jnp.float32) \
        * by_step(jnp.exp(total[..., None] - cum))            # (b,c,C,H,dk)
    a_mat = jnp.exp(total)[..., None, None] * jnp.eye(dk, dtype=jnp.float32) \
        - _einsum("bcshi,bcshj->bchij", kd, w, dtype=dtype)
    b_mat = _einsum("bcshi,bcshv->bchiv", kd, u0, dtype=dtype)
    s_in = _entering(a_mat, b_mat)                            # (b,c,H,dk,dv)

    u = u0 - _einsum("bcrhd,bchdv->bcrhv", w, s_in, dtype=dtype)
    qk = _einsum("bcrhd,bcshd->bchrs", qs, ks, dtype=dtype)
    o = _einsum("bchrs,bcshv->bcrhv", qk * decay, u, dtype=dtype)
    o = o + _einsum("bcrhd,bchdv->bcrhv", qs, s_in, dtype=dtype) \
        * by_step(jnp.exp(cum))
    return o.reshape(bsz, t, h, dv)
