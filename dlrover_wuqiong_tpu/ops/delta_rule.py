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
the differentiation of the rounds).  (`_unit_lower_inverse` is these
rounds as written, the `jax.numpy` routes' and the kernels' oracle; the
kernels' `_solve` runs the first three as one substitution, "kernel"
below.)

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
shapes, the backend, the mesh it runs on), never by a knob:

- "kernel": a pair of Pallas (Mosaic) kernels behind one
  `jax.custom_vjp` (`ops/ssd.py`'s design), `dwt_gdr_fwd` and
  `dwt_gdr_bwd` for a decay a HEAD — described here — and `dwt_kda_fwd`
  and `dwt_kda_bwd` for a decay a CHANNEL (below: the same grid, carry
  and wrappers; what differs is said there).  A
  grid step is one (batch row, block of heads, chunk — or the few chunks
  that fill the MXU's 128 rows side by side, their tiles block
  diagonal), the chunk axis last and sequential: the forward kernel
  walks it in order, the backward in reverse.  In VMEM and nowhere
  else, a head and a chunk: the decay tile, K K^T, L, T by the forward
  substitution in blocks above (`_solve`, below), U0, W, U, P = Q K^T o
  decay and o; the carried state,
  (dk x dv) float32 a head, is a scratch the chunk axis walks (zeroed at
  chunk 0) and the carry is APPLIED, S <- e^{b_C} S + Kd^T U: no (dk x
  dk) transition, no associative scan, no shift and no pad is on this
  route (a sequential axis needs no associative form).  The forward
  kernel writes o and the state ENTERING each chunk ((b, H, chunks, dk,
  dv) float32); the backward kernel reads those, REBUILDS the chunk's
  tiles, carries dS (dk x dv) float32 in reverse and emits dq, dk, dv,
  dbeta and the cotangents of the running sums by column and by row; the
  solve's cotangent is -T^T dT T^T in the kernel.  What stays `jax.numpy`
  around the kernels, differentiated by JAX: the T x H numbers (the
  running sums of g, their layouts by column and by row, beta's by
  column) and ONE head-major re-layout of q, k, v and o a pass.  The
  operands `_chunked` rounds to `dtype` are rounded in the kernel too,
  the same ones at the same places; what differs is the order of float32
  sums and that U, rounded, meets Kd^T where `_chunked` composes (A, B).
  Where `delta_route` says so (`_SITES`).
  The solve in the kernels (`_solve`; `solve_rounds(chunk)` is its
  static record, PR 69): the rounds that join blocks SMALLER than a
  float32 sublane tile (s = 1, 2, 4: the diagonal blocks of 8 steps) are
  no products at all.  The tile's sixteen (8 x 8) diagonal blocks are
  laid side by side in one (8 x 128) register (16 selects, 15 adds), read
  out by sub-diagonal as (1 x 128) rows, and inverted by the forward
  substitution T = I - T L run down the sub-diagonals: 21 lane rolls,
  multiplies and adds on single rows, plain float32 on the vector units
  (an exact multiply-add where a six-pass bfloat16 product is float32 to
  its last bit or two; a term is an entry of a diagonal block's inverse
  times one of L, bounded as the result is: still NOT the nilpotent
  product).  The rounds that join blocks of 8, 16, ... stay D_2s = D_s -
  D_s E D_s in two float32 products at `Precision.HIGHEST` each, but
  over the LOWER-half rows of each block of 2s alone — D_s E D_s is
  B E21 A, zero everywhere else, and from s = 8 on those rows are whole
  sublane tiles — so a product pushes 64 rows and not 128, and the mask
  sits on the result (B's rows are zero outside B: L needs none).  At a
  chunk of 64: 36 MXU passes of 64 rows a tile where there were 60 of
  128; the solve alone 1.80 -> 0.93 us a tile, a forward call 2.67 ->
  2.12 us a head-tile and a backward one (it rebuilds the tiles) 3.56 ->
  3.03 at Qwen3-Next's shape (PERF.md section 6, PR 69, with what was
  tried and left: blocks of 16 on the vector units are slower than none).
  No product of the solve or of its cotangent runs below float32.
- "chunked": the form above in `jax.numpy` (`_chunked` for a decay a
  head, `_chunked_channel` for a decay a channel), the backward pass its
  differentiation but for the two `custom_vjp`s.  GSPMD partitions it, so
  a mixer on a mesh of several devices runs it, as does every CPU run and
  a shape the kernels do not take — and it is the kernels' oracle.
- "sequential": `lax.scan` over time, for a sequence that is no whole
  number of chunks (a parameter draw on a few tokens) — and the tests'
  oracle; either form of the decay.

A decay a CHANNEL (Kimi Delta Attention, arXiv:2510.26692: g (b, T, H,
dk), alpha_t in R^dk): S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T with u_t =
beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t) — the recurrence above where
a head's channels decay alike.  In chunks the decay then sits INSIDE the
contraction over a key's channels,

    L_rs = beta_r sum_d k_rd e^{b_rd - b_sd} k_sd      (s < r)
    P_rs =        sum_d q_rd e^{b_rd - b_sd} k_sd      (s <= r)

W = T diag(beta) (K o e^b), Kd_s = k_s o e^{b_C - b_s}, A = Diag(e^{b_C})
- Kd^T W, and the entering state meets q o e^b.  Every exponent there is
<= 0 but L's and P's: (k_r o e^{b_r}) . (k_s o e^{-b_s}) would form
e^{-b_s}, and a chunk of 64 steps that each decay by e^-5 overflows
float32 (5 x 64 = 320 > 88).  So L and P are formed in sub-blocks of
`_SUB` = 16 steps, the row's operand scaled e^{b_r - b_ref} and the
column's e^{b_ref - b_s} with b_ref the running sum at the MIDDLE step of
the ROW's block: a column of an earlier block has b_ref - b_s <= 0, one
of the row's own block and the row itself lie at most 8 steps from the
reference (the caller keeps a step's g at or over `CHANNEL_DECAY_FLOOR`:
8 x 8 = 64 < 88, and e^-64 times a key's small entry is still no
denormal, which the TPU and the CPU both flush to zero: a reference at
the block's FIRST step spans 15 steps one way, e^-75 at the published
bound of -5, and loses the small entries of the rows furthest from it),
and a later block's is masked BEFORE the exp.  No (C x C x dk) array exists: the scaled column
operand is (chunk / 16) copies of K, one a row block.  The solve, its
cotangent, the carry's associative scan and its reverse are the scalar
form's functions; the sequential route scales the state a channel.

Its kernel pair, `dwt_kda_fwd` / `dwt_kda_bwd` (PR 58), is the scalar
pair's design — grid (batch row, block of heads, step), the step's
chunks side by side at the MXU's 128 rows, the solve in VMEM, the carried
state a float32 scratch the sequential step axis walks, the entering
states saved for a backward kernel that rebuilds the tiles and carries dS
in reverse — and differs in this only: L and P are built in VMEM a row
block of 16 steps at a time, exactly the scaling above (the block's k
rows over its q rows, (32 x dk), against ONE scaled column operand: one
product a row block, no `decay` tile); a decay multiplies an operand's
channels (W, Kd, Q o e^b), and the carry scales the state's rows by
e^{b_C}, a vector over dk — so the state's scratch is held TRANSPOSED,
(dv, dk), the decay broadcasts along its sublanes and the products that
meet it are `_dot_t` / `_dot_c0`: no kernel transposes a vector; the
running sums and their cotangent are (R, dk) float32 a head, head-major
like k (beta keeps its layout by column).  The sub-blocks' reference
carries no cotangent (L and P do not depend on it).  The cumulative sum
of g stays `jax.numpy` in front of the kernels, differentiated by JAX.
The operands `_chunked_channel` rounds to `dtype` are rounded in the
kernels at the same places.

Keys of 96, values of 192 (ROADMAP M6(b2)): NOTHING is padded in HBM.
The kernels' operands are head-major, (b, H, T, dk) and (b, H, T, dv),
and a block is (heads, C, dk) or (heads, C, dv): its last dimension is
the array's own, which Mosaic takes at any width.  In VMEM a row of 96
lies on a 128-lane tile as every narrow row does, and a 96-wide
contraction costs the MXU what a 128-wide one does, so padding keys to a
slab in HBM would buy no speed and cost a third more key bytes each way:
`product_lanes` reads dk + dv run for dk + dv asked.  The carried state's
scratch is (dk x dv): it has no padding lanes a stray value could sit in.

`benchmark/`'s `kernel.delta_roofline` counts the RECURRENCE's work from
shapes, whatever computes it.

Scopes (under the caller's): `delta` around all of it; the kernels'
custom calls of either pair, forward, recomputed and backward, carry it;
the chunked channel form's stages under it, `sums`, `tiles`, `solve`,
`operands`, `carry`, `output` (what a probe splits its time by).

Parity: none — the reference (atorch's modules and kernels) has no
linear-attention layer; this is the paper's algorithm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import (
    _compiler_params, _dot, _dot_c0, _dot_t, _einsum, _iota, _out_struct,
    _put, _round_up)

_HIGHEST = jax.lax.Precision.HIGHEST


def _einsum32(spec, *operands):
    return jnp.einsum(spec, *operands, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------ the route

_WIDEST = 256            # lanes of a key or a value the kernels were built at
_HEADS_A_STEP = 5        # see `_heads_block`
_ROWS = 128              # the MXU's: a grid step's chunks fill them
_VMEM_LIMIT = 64 * 1024 * 1024  # this kernel's own request of the compiler
_SITES = frozenset({"device"})  # S9 (ROADMAP) adds "manual", and the record
_SUB = 16                # steps of a sub-block where the decay is a channel's
_BLOCK = 8               # steps of a block the solve inverts without the MXU:
#                          a float32 sublane tile; see `solve_rounds`
# the least log-decay a step of the channel form may carry: the 8 steps
# either side of a sub-block's reference stay inside e^+-64, where a key's
# small entry times the scale is no denormal yet
CHANNEL_DECAY_FLOOR = -8.0


def _heads_block(h: int) -> int:
    """Heads a grid step takes: the largest divisor of `h` up to
    `_HEADS_A_STEP`.  The heads of a step are independent chains of
    products (the solve's dependent float32 products a tile above all:
    ten when these were measured, six since PR 69, whose probe of the
    solve alone still reads five heads a step 3% under four), which the
    scheduler interleaves.  Measured at the cell's shape,
    fifteen heads, two chunks a step (PERF.md section 6, PR 48): 1 / 3 /
    5 / 15 heads a step run a layer's forward kernel in 2.77 / 2.69 /
    2.68 / 2.65 ms and its backward in 3.89 / 3.70 / 3.61 / 4.37 (at
    fifteen the unrolled backward is 3 times the code for nothing).  The
    channel pair at its cell's shape, sixteen heads of 128 | 128, two
    chunks a step (PERF.md section 6, PR 58): 1 / 2 / 4 heads a step run
    a layer's forward kernel in 3.09 / 3.04 / 2.96 ms and its backward in
    4.30 / 4.22 / 4.11."""
    return max(d for d in range(1, _HEADS_A_STEP + 1) if h % d == 0)


def _vmem_bytes(dk: int, dv: int, rows: int, hb: int,
                channel: bool = False) -> int:
    """What the backward kernel (the larger) holds at `rows` rows a grid
    step: its double-buffered blocks (q, k, dq, dk float32; v, dv; dO
    float32; the entering states; with a decay a `channel`, the running
    sums and their cotangent, two more key-sized blocks), the carried
    cotangent, and a head's tiles and temporaries (the channel form's
    scaled column operands, one a sub-block, beside them)."""
    lanes = functools.partial(_round_up, m=mosaic.LANES)
    blocks = rows * 4 * ((6 if channel else 4) * lanes(dk) + 3 * lanes(dv))
    state = dk * lanes(dv) * 4
    states = -(-rows // 64) * state
    tiles = 4 * rows * (16 * lanes(rows) + 8 * (lanes(dk) + lanes(dv)))
    if channel:  # a sub-block's column scale (float32) and operand (bf16)
        tiles += rows // _SUB * rows * (4 + 2) * lanes(dk)
    return 2 * hb * (blocks + states) + hb * state + 3 * tiles + 3 * state


def delta_route(t: int, chunk: int, heads: int, dk: int, dv: int,
                mesh=None, channel_decay: bool = False):
    """Which route `gated_delta_rule` takes, from what the call can
    observe: ("kernel", heads a grid step) where the call runs on one of
    `_SITES` (`mesh` is the mixer config's: a Mosaic kernel cannot be
    partitioned by GSPMD), when the sequence is a whole number of chunks,
    the chunk a multiple of the sublane tile and a power of two (the
    solve's rounds double the blocks: at a chunk of 48, two a grid step,
    the kernels' answer was wrong by 0.16 until PR 69 sent it to the
    chunked form), dk and dv multiples of 32
    up to 256 lanes, and the blocks plus the state of a block of heads
    fit the VMEM the call states; else "chunked" where the sequence is a
    whole number of chunks, else "sequential".  With `channel_decay` (g a
    number a key channel) a chunk is whole sub-blocks too, on either
    route, and the kernels are the channel pair.  The static counter of
    the decision (with the compiled step's count of `dwt_gdr_*` or
    `dwt_kda_*` custom calls); pinned by
    tests/test_program_from_arguments.py for the benchmark's cells."""
    if t < chunk or t % chunk or (channel_decay and chunk % _SUB):
        return "sequential"
    if mosaic.kernel_site(mesh) not in _SITES:
        return "chunked"
    if chunk % mosaic.SUBLANES or dk % 32 or dv % 32 or max(dk, dv) > _WIDEST:
        return "chunked"
    if chunk & (chunk - 1):  # the solve's rounds double the blocks
        return "chunked"
    hb = _heads_block(heads)
    rows = chunk * _chunks_a_step(chunk, t // chunk)
    if _vmem_bytes(dk, dv, rows, hb, channel_decay) > _VMEM_LIMIT:
        return "chunked"
    return "kernel", hb


def product_lanes(dk: int, dv: int) -> tuple:
    """(lanes run, lanes the model asks) of the products that meet a head's
    state, key side | value side, by the blocks' declared last dimensions
    (as `ops/flash_attention.kernel_lanes` counts the attention's).  No
    route pads a head in HBM: XLA tiles dk and dv as they are on the
    `jax.numpy` routes, and the kernels' head-major blocks are (C, dk) and
    (C, dv), their last dimension the array's own, so both read dk + dv.
    (Keys of 96 laid on a 128-lane slab in HBM would run 128 + dv.)"""
    return dk + dv, dk + dv


@jax.named_scope("delta")
def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     dtype=jnp.float32, mesh=None):
    """q, k (b, T, H, dk), normalised and q scaled; v (b, T, H, dv);
    g, the log of the decay (<= 0): (b, T, H), one a head, or (b, T, H,
    dk), one a key channel (then >= `CHANNEL_DECAY_FLOOR` a step); beta
    (b, T, H), the write gate; `mesh`, where the call runs (the mixer
    config's).  Returns o (b, T, H, dv) in float32."""
    channel = g.ndim == 4
    route = delta_route(q.shape[1], chunk, *k.shape[2:], v.shape[-1], mesh,
                        channel_decay=channel)
    if route == "chunked":
        return (_chunked_channel if channel else _chunked)(
            q, k, v, g, beta, chunk, dtype)
    if route == "sequential":
        return gated_delta_rule_sequential(q, k, v, g, beta)
    return _chunk_kernels(q, k, v, g, beta, chunk, dtype, route[1])


def gated_delta_rule_sequential(q, k, v, g, beta):
    """The recurrence as it is written, one step at a time, float32; g a
    head's number or a key channel's."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    bsz, _, h, dk = k.shape
    if g.ndim == 3:
        g = g[..., None]

    def step(state, qkvgb):
        q_t, k_t, v_t, g_t, b_t = qkvgb
        state = state * jnp.exp(g_t)[..., None]
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


def _chunked_channel(q, k, v, g, beta, chunk, dtype):
    """`_chunked` where g is (b, T, H, dk): the module docstring's vector
    form.  What differs from `_chunked` is how L and P are formed (the
    sub-blocks) and where a decay multiplies (an operand's channels, not
    a product's entries)."""
    bsz, t, h, dk = k.shape
    dv = v.shape[-1]
    c, n = t // chunk, chunk // _SUB
    f32 = jnp.float32

    def cut(x):  # (b, T, H, ...) -> (b, chunks, C, H, ...)
        return x.reshape(bsz, c, chunk, *x.shape[2:])

    def blocks(x):  # (b, chunks, C, H, d) -> (b, chunks, n, _SUB, H, d)
        return x.reshape(bsz, c, n, _SUB, *x.shape[3:])

    def by_step(x):  # (b, chunks, H, C) -> (b, chunks, C, H, 1)
        return jnp.moveaxis(x, -1, 2)[..., None]

    qs, ks, vs = (cut(x).astype(f32) for x in (q, k, v))
    bt = jnp.moveaxis(cut(beta.astype(f32)), 2, -1)           # (b, c, H, C)
    with jax.named_scope("sums"):
        cum = jnp.cumsum(cut(g.astype(f32)), axis=2)          # (b,c,C,H,dk)
        total = cum[:, :, -1]                                 # (b, c, H, dk)
        e_cum = jnp.exp(cum)

    # L and P by sub-blocks: rows (I, i) scaled e^{b_r - b_ref(I)},
    # columns (J, j) e^{b_ref(I) - b_s} for J <= I (mask BEFORE exp),
    # b_ref(I) the running sum at block I's middle step.
    # Recomputed in the backward pass (`jax.checkpoint`): the scaled
    # column operand is n copies of K, kept it would outweigh the rest
    earlier = (np.arange(n)[:, None] >= np.arange(n)[None, :]
               ).reshape(n, n, 1, 1, 1)

    @jax.checkpoint
    @jax.named_scope("tiles")
    def tiles(qs, ks, cum):  # sum_d rows[I,i,d] kc[I,J,j,d], (b,c,H,C,C)
        cum_b = blocks(cum)
        ref = cum_b[:, :, :, _SUB // 2]                       # (b,c,n,H,dk)
        row_scale = jnp.exp(cum_b - ref[:, :, :, None])
        col_scale = jnp.exp(jnp.where(
            earlier, ref[:, :, :, None, None] - cum_b[:, :, None], -jnp.inf))
        kc = blocks(ks)[:, :, None] * col_scale           # (b,c,I,J,j,H,dk)
        return tuple(
            _einsum("bcIihd,bcIJjhd->bchIiJj", blocks(rows) * row_scale, kc,
                    dtype=dtype).reshape(bsz, c, h, chunk, chunk)
            for rows in (ks, qs))

    kk, qk = tiles(qs, ks, cum)
    tril = np.tril(np.ones((chunk, chunk), bool))
    with jax.named_scope("solve"):
        # the mask FIRST: over the diagonal an entry's two scales multiply
        # to e^{b_r - b_s} > 1, past float32 at the floor (inf - inf)
        low = bt[..., :, None] * jnp.where(np.tril(tril, -1), kk, 0.0)
        solve = _unit_lower_inverse(low)                      # T, float32
        p_mat = jnp.where(tril, qk, 0.0)

    with jax.named_scope("operands"):
        u0 = _einsum("bchrs,bcshv->bcrhv", solve, vs * by_step(bt),
                     dtype=dtype)
        w = _einsum("bchrs,bcshd->bcrhd", solve, ks * e_cum * by_step(bt),
                    dtype=dtype)
        kd = ks * jnp.exp(total[:, :, None] - cum)            # (b,c,C,H,dk)
        a_mat = jnp.exp(total)[..., :, None] * jnp.eye(dk, dtype=f32) \
            - _einsum("bcshi,bcshj->bchij", kd, w, dtype=dtype)
        b_mat = _einsum("bcshi,bcshv->bchiv", kd, u0, dtype=dtype)
    with jax.named_scope("carry"):
        s_in = _entering(a_mat, b_mat)                        # (b,c,H,dk,dv)

    with jax.named_scope("output"):
        u = u0 - _einsum("bcrhd,bchdv->bcrhv", w, s_in, dtype=dtype)
        o = _einsum("bchrs,bcshv->bcrhv", p_mat, u, dtype=dtype) \
            + _einsum("bcrhd,bchdv->bcrhv", qs * e_cum, s_in, dtype=dtype)
    return o.reshape(bsz, t, h, dv)


# ------------------------------------------------------------ the kernels
#
# Layouts.  q, k (b, H, T, dk), v, o, dO (b, H, T, dv): head-major, a
# block (hb, R, d) of R = n x C rows, n chunks side by side, whose last
# dimension is the array's own (nothing is padded in HBM; in VMEM a row
# of 96 lies on a 128-lane tile as any narrow row does).  The T x H
# numbers come BY COLUMN, (b, H/hb, steps, R, hb), time on sublanes — the
# running sums of g inside a chunk and beta — and the running sums also
# BY ROW, (b, H/hb, steps, hb, R), time on lanes, so that no kernel
# transposes a vector.  The entering states, (b, H, chunks, dk, dv)
# float32, a block (hb, n, dk, dv).  The grid is (batch row, block of
# heads, step), the step axis sequential: the carried state (backward:
# its cotangent) of the block's heads is a VMEM scratch.
#
# A step's n chunks share every (R x R) tile: the tiles are block
# diagonal (a mask keeps a chunk to itself), so the solve's rounds, T's
# products and the masked products run once for n chunks at the MXU's
# full 128 rows — a (64 x 64 x 64) product occupies a pass as a (128 x
# 128 x 128) one does, and the solve's float32 products were most of a
# tile's passes (60 of some 79; 36 half passes of some 55 since PR 69).
# Only what meets the carried state is a chunk's own, in order (backward:
# in reverse) inside the step.

def _dot32(a, b, dims=((1,), (0,))):
    """A float32 product at full precision (the solve's, its cotangent's)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _row_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _col_sum(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _stack(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _masks(chunk, n):
    """Of an (R x R) tile of n chunks: s <= r and s < r, each inside its
    chunk; and what `_solve` selects by — the diagonal blocks of `_BLOCK`
    steps, (R x R); each sub-diagonal d of those blocks where they lie
    side by side, (`_BLOCK` x R); and for each round s that stays on the
    MXU, of the LOWER-half rows of the blocks of 2s it makes, (R / 2 x R),
    their upper-half columns."""
    size = n * chunk
    rows, cols = _iota((size, size), 0), _iota((size, size), 1)
    same = rows // chunk == cols // chunk
    band = _iota((_BLOCK, size), 0) - _iota((_BLOCK, size), 1) % _BLOCK
    half = (size // 2, size)
    joins = {s: _iota(half, 1) // s == 2 * (_iota(half, 0) // s)
             for s in solve_rounds(chunk)["mxu"]}
    return (rows >= cols) & same, (rows > cols) & same, (
        rows // _BLOCK == cols // _BLOCK,
        [band == d for d in range(_BLOCK)], joins)


def _solve(low, masks):
    """`_unit_lower_inverse` on one (R x R) tile, `low` strictly lower
    inside each chunk (a power of two, `_BLOCK` steps at least), as the
    module's docstring says ("The solve in the kernels").  `side` is the
    diagonal blocks side by side, (`_BLOCK` x R), a block's column on its
    lane; `sub[d]` and `inv[d]`, (1 x R), are L's and T's sub-diagonal d
    by column, T[c + d, c] = -(L[c + d, c] + sum_{0 < e < d} T[c + d,
    c + e] L[c + e, c]); a round on the MXU takes B E21 A from the
    lower-half rows of D_s times L times D_s and masks the RESULT (B's
    rows are zero outside B, so L needs no mask)."""
    blocks, bands, joins = masks
    size = low.shape[0]
    tiled = (size // _BLOCK, _BLOCK, size)
    side = jnp.sum(jnp.where(blocks, low, 0.0).reshape(tiled), axis=0)
    sub = [None] + [jnp.sum(jnp.where(band, side, 0.0), axis=0, keepdims=True)
                    for band in bands[1:]]
    inv = [jnp.ones((1, size), jnp.float32)]
    for d in range(1, _BLOCK):
        inv.append(-sum((pltpu.roll(inv[d - e], size - e, 1) * sub[e]
                         for e in range(1, d)), sub[d]))
    side = jnp.zeros_like(side)
    for band, row in zip(bands, inv):
        side = jnp.where(band, row, side)
    tm = jnp.where(blocks, jnp.broadcast_to(side[None], tiled).reshape(
        size, size), 0.0)
    for s, join in joins.items():
        starts = range(0, size, 2 * s)
        lower = [tm[i + s:i + 2 * s] for i in starts]
        upd = jnp.where(join, _dot32(_dot32(_stack(lower), low), tm), 0.0)
        tm = _stack([x for j, i in enumerate(starts) for x in (
            tm[i:i + s], lower[j] - upd[j * s:(j + 1) * s])])
    return tm


class _Tiles:
    """A head's tiles of one step's chunks that do not depend on the
    entering state — what the forward kernel builds and the backward
    REBUILDS — named as the module's docstring names them; `*b` is the
    operand rounded to the products' dtype, as `_chunked` rounds it."""

    def __init__(self, refs, h, masks, chunk, dtype):
        q_ref, k_ref, v_ref, bc_ref, br_ref, bt_ref = refs
        tril, strict, solve = masks
        f32 = jnp.float32
        b_col, self.beta = bc_ref[:, h:h + 1], bt_ref[:, h:h + 1]
        size = b_col.shape[0]
        row = _iota((size, 1), 0)
        # a chunk's total, b_C, over its rows; e^{b_C} a chunk
        total, self.ets = None, []
        for c in range(size // chunk):
            last = _col_sum(jnp.where(row == (c + 1) * chunk - 1, b_col,
                                      0.0))                      # (1, 1)
            total = last if c == 0 else jnp.where(
                row >= c * chunk, last, total)
            self.ets.append(jnp.exp(last))
        self.decay = jnp.exp(jnp.where(
            tril, b_col - br_ref[h:h + 1, :], -jnp.inf))
        self.eb, self.te = jnp.exp(b_col), jnp.exp(total - b_col)
        self.kf, self.vf = k_ref[h].astype(f32), v_ref[h].astype(f32)
        self.kb, self.qb = self.kf.astype(dtype), q_ref[h].astype(dtype)
        self.kk = _dot_t(self.kb, self.kb)
        self.tm = _solve(
            jnp.where(strict, self.beta * self.decay * self.kk, 0.0), solve)
        self.tb = self.tm.astype(dtype)
        self.vbb = (self.vf * self.beta).astype(dtype)
        self.keb = (self.kf * (self.beta * self.eb)).astype(dtype)
        self.kdb = (self.kf * self.te).astype(dtype)
        self.u0 = _dot(self.tb, self.vbb)
        self.wb = _dot(self.tb, self.keb).astype(dtype)
        self.qk = _dot_t(self.qb, self.kb)
        self.pb = (self.qk * self.decay).astype(dtype)


def _gdr_fwd_kernel(q_ref, k_ref, v_ref, bc_ref, br_ref, bt_ref, o_ref,
                    *rest, chunk, dtype, save):
    st_ref = rest[0] if save else None
    s_scr = rest[-1]
    hb, size, _ = q_ref.shape
    n = size // chunk

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    masks = _masks(chunk, n)
    for h in range(hb):
        t = _Tiles((q_ref, k_ref, v_ref, bc_ref, br_ref, bt_ref), h, masks,
                   chunk, dtype)
        state, ubs, qss = s_scr[h], [], []
        for c in range(n):
            rows = slice(c * chunk, (c + 1) * chunk)
            if save:
                st_ref[h, c] = state
            sb = state.astype(dtype)
            ubs.append((t.u0[rows] - _dot(t.wb[rows], sb)).astype(dtype))
            qss.append(_dot(t.qb[rows], sb))
            state = t.ets[c] * state + _dot_c0(t.kdb[rows], ubs[c])
        s_scr[h] = state
        o_ref[h] = _dot(t.pb, _stack(ubs)) + _stack(qss) * t.eb


def _gdr_bwd_kernel(q_ref, k_ref, v_ref, bc_ref, br_ref, bt_ref, st_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, dbc_ref, dbr_ref,
                    dbt_ref, ds_scr, *, chunk, dtype):
    hb, size, _ = q_ref.shape
    n = size // chunk
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = jnp.zeros(ds_scr.shape, f32)

    masks = _masks(chunk, n)
    strict = masks[1]
    row = _iota((size, 1), 0)
    col_head, row_head = _iota((1, hb), 1), _iota((hb, 1), 0)
    dbc = jnp.zeros((size, hb), f32)
    dbt = jnp.zeros((size, hb), f32)
    dbr = jnp.zeros((hb, size), f32)
    for h in range(hb):
        t = _Tiles((q_ref, k_ref, v_ref, bc_ref, br_ref, bt_ref), h, masks,
                   chunk, dtype)
        chunks = [slice(c * chunk, (c + 1) * chunk) for c in range(n)]
        sbs = [st_ref[h, c].astype(dtype) for c in range(n)]
        ubs = [(t.u0[rows] - _dot(t.wb[rows], sb)).astype(dtype)
               for rows, sb in zip(chunks, sbs)]
        qs = _stack([_dot(t.qb[rows], sb) for rows, sb in zip(chunks, sbs)])
        do = do_ref[h].astype(f32)
        dob = do.astype(dtype)
        # o = P U + e^b (Q S_in);  S_out = e^{b_C} S_in + Kd^T U
        dp = _dot_t(dob, _stack(ubs))                          # (R, R)
        dqs = (do * t.eb).astype(dtype)
        du = _dot_c0(t.pb, dob)                                # (R, dv)
        # what meets the carried state, a chunk at a time from the last:
        # U = U0 - W S_in
        ds_out = ds_scr[h]     # cotangent of the state LEAVING a chunk
        dubs, dwbs, dkds, dq_s, d_et = ([None] * n for _ in range(5))
        for c in reversed(range(n)):
            rows, sb, gb = chunks[c], sbs[c], ds_out.astype(dtype)
            dubs[c] = (du[rows] + _dot(t.kdb[rows], gb)).astype(dtype)
            dkds[c] = _dot_t(ubs[c], gb)                       # (C, dk)
            dwbs[c] = (-_dot_t(dubs[c], sb)).astype(dtype)     # (C, dk)
            dq_s[c] = _dot_t(dqs[rows], sb)
            d_et[c] = t.ets[c] * _col_sum(_row_sum(ds_out * st_ref[h, c]))
            ds_out = t.ets[c] * ds_out + _dot_c0(t.qb[rows], dqs[rows]) \
                - _dot_c0(t.wb[rows], dubs[c])
        ds_scr[h] = ds_out
        dub, dwb, dkd = _stack(dubs), _stack(dwbs), _stack(dkds)
        # U0 = T (beta V);  W = T (beta e^b K)
        dvb = _dot_c0(t.tb, dub)                               # (R, dv)
        dke = _dot_c0(t.tb, dwb)                               # (R, dk)
        # T = (I + L)^-1: dL = -T^T dT T^T on the strict lower triangle
        d_tm = _dot_t(dub, t.vbb) + _dot_t(dwb, t.keb)
        dlow = jnp.where(strict, -_dot32(
            _dot32(t.tm, d_tm, ((0,), (0,))), t.tm, ((1,), (1,))), 0.0)
        dkkb = (dlow * (t.beta * t.decay)).astype(dtype)
        dqkb = (dp * t.decay).astype(dtype)
        m = (dp * t.qk + dlow * (t.beta * t.kk)) * t.decay  # d(b_r - b_s)
        dq_ref[h] = (_stack(dq_s) + _dot(dqkb, t.kb)).astype(dq_ref.dtype)
        dk_ref[h] = (dkd * t.te + dke * (t.beta * t.eb)
                     + _dot_c0(dqkb, t.qb) + _dot(dkkb, t.kb)
                     + _dot_c0(dkkb, t.kb)).astype(dk_ref.dtype)
        dv_ref[h] = (dvb * t.beta).astype(dv_ref.dtype)
        through_ke = _row_sum(dke * t.kf) * t.eb               # (R, 1)
        through_te = _row_sum(dkd * t.kf) * t.te
        d_col = _row_sum(m) + _row_sum(do * qs) * t.eb \
            + through_ke * t.beta - through_te
        for c, rows in enumerate(chunks):    # d(b_C), at a chunk's last row
            d_total = d_et[c] + _col_sum(jnp.where(
                (row >= rows.start) & (row < rows.stop), through_te, 0.0))
            d_col = d_col + jnp.where(row == rows.stop - 1, d_total, 0.0)
        dbc = _put(dbc, col_head, h, d_col)
        dbt = _put(dbt, col_head, h, _row_sum(dlow * (t.decay * t.kk))
                   + _row_sum(dvb * t.vf) + through_ke)
        dbr = _put(dbr, row_head, h, -_col_sum(m))
    dbc_ref[...], dbr_ref[...], dbt_ref[...] = dbc, dbr, dbt


# ------------------------------------------- the kernels, a decay a CHANNEL
#
# What differs from the pair above is the module docstring's ("Its kernel
# pair").  Layouts: the running sums of g inside a chunk and their
# cotangent are ONE operand each, (b, H, T, dk) float32, head-major like
# k, a block (hb, R, dk); the entering states and the carried state's
# scratch are TRANSPOSED, (dv, dk) a head; the rest as above.

class _SubBlock:
    """Row block `i` of a step's tiles: `rs`, the rows' scale (_SUB, dk);
    `cs`, the columns' (R, dk), zero outside the row's chunk and past the
    row's block; the two operands as the product takes them."""

    def __init__(self, b_ref, h, i, b, kf, qf, row, chunk, dtype):
        lo, mid = i * _SUB, i * _SUB + _SUB // 2
        ref = b_ref[h, mid:mid + 1, :]                          # (1, dk)
        seen = (row >= lo // chunk * chunk) & (row < lo + _SUB)
        self.rs = jnp.exp(b[lo:lo + _SUB] - ref)
        self.cs = jnp.exp(jnp.where(seen, ref - b, -jnp.inf))
        self.rows = jnp.concatenate(
            [kf[lo:lo + _SUB] * self.rs, qf[lo:lo + _SUB] * self.rs],
            axis=0).astype(dtype)                               # (32, dk)
        self.cols = (kf * self.cs).astype(dtype)                # (R, dk)


class _ChannelTiles:
    """`_Tiles` where the decay is a channel's: what the forward kernel
    builds of a head and a step that does not depend on the entering
    state, and the backward REBUILDS; `*b` is an operand rounded to the
    products' dtype, as `_chunked_channel` rounds it."""

    def __init__(self, refs, h, masks, chunk, dtype):
        q_ref, k_ref, v_ref, b_ref, bt_ref = refs
        tril, strict, solve = masks
        f32 = jnp.float32
        b, self.beta = b_ref[h], bt_ref[:, h:h + 1]       # (R, dk), (R, 1)
        size = b.shape[0]
        row = _iota((size, 1), 0)
        # a chunk's total, b_C (1, dk), over its rows; e^{b_C} a chunk
        total, self.ets = None, []
        for c in range(size // chunk):
            last = b_ref[h, (c + 1) * chunk - 1:(c + 1) * chunk, :]
            total = last if c == 0 else jnp.where(
                row >= c * chunk, last, total)
            self.ets.append(jnp.exp(last))
        self.eb, self.te = jnp.exp(b), jnp.exp(total - b)
        self.kf, self.qf = k_ref[h].astype(f32), q_ref[h].astype(f32)
        self.vf = v_ref[h].astype(f32)
        self.subs = [_SubBlock(b_ref, h, i, b, self.kf, self.qf, row, chunk,
                               dtype) for i in range(size // _SUB)]
        both = [_dot_t(s.rows, s.cols) for s in self.subs]      # (32, R)
        # the mask FIRST: over the diagonal an entry's two scales multiply
        # to e^{b_r - b_s} > 1, past float32 at the floor
        self.kk = jnp.where(strict, _stack([x[:_SUB] for x in both]), 0.0)
        self.pb = jnp.where(tril, _stack([x[_SUB:] for x in both]),
                            0.0).astype(dtype)
        self.tm = _solve(self.beta * self.kk, solve)
        self.tb = self.tm.astype(dtype)
        self.vbb = (self.vf * self.beta).astype(dtype)
        self.keb = (self.kf * self.eb * self.beta).astype(dtype)
        self.kdb = (self.kf * self.te).astype(dtype)
        self.qeb = (self.qf * self.eb).astype(dtype)
        self.u0 = _dot(self.tb, self.vbb)
        self.wb = _dot(self.tb, self.keb).astype(dtype)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, b_ref, bt_ref, o_ref, *rest,
                    chunk, dtype, save):
    st_ref = rest[0] if save else None
    s_scr = rest[-1]                            # (hb, dv, dk): transposed
    hb, size, _ = q_ref.shape
    n = size // chunk

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    masks = _masks(chunk, n)
    for h in range(hb):
        t = _ChannelTiles((q_ref, k_ref, v_ref, b_ref, bt_ref), h, masks,
                          chunk, dtype)
        state, ubs, qss = s_scr[h], [], []
        for c in range(n):
            rows = slice(c * chunk, (c + 1) * chunk)
            if save:
                st_ref[h, c] = state
            sb = state.astype(dtype)
            ubs.append((t.u0[rows] - _dot_t(t.wb[rows], sb)).astype(dtype))
            qss.append(_dot_t(t.qeb[rows], sb))
            state = t.ets[c] * state + _dot_c0(ubs[c], t.kdb[rows])
        s_scr[h] = state
        o_ref[h] = _dot(t.pb, _stack(ubs)) + _stack(qss)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, b_ref, bt_ref, st_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, db_ref, dbt_ref, ds_scr, *,
                    chunk, dtype):
    hb, size, _ = q_ref.shape
    n = size // chunk
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = jnp.zeros(ds_scr.shape, f32)

    masks = _masks(chunk, n)
    tril, strict = masks[:2]
    row = _iota((size, 1), 0)
    col_head = _iota((1, hb), 1)
    dbt = jnp.zeros((size, hb), f32)
    for h in range(hb):
        t = _ChannelTiles((q_ref, k_ref, v_ref, b_ref, bt_ref), h, masks,
                          chunk, dtype)
        chunks = [slice(c * chunk, (c + 1) * chunk) for c in range(n)]
        sbs = [st_ref[h, c].astype(dtype) for c in range(n)]
        ubs = [(t.u0[rows] - _dot_t(t.wb[rows], sb)).astype(dtype)
               for rows, sb in zip(chunks, sbs)]
        dob = do_ref[h].astype(dtype)
        # o = P U + (Q o e^b) S_in;  S_out = e^{b_C} o S_in + Kd^T U
        dp = _dot_t(dob, _stack(ubs))                          # (R, R)
        du = _dot_c0(t.pb, dob)                                # (R, dv)
        # what meets the carried state, a chunk at a time from the last:
        # U = U0 - W S_in
        ds_out = ds_scr[h]     # cotangent of the state LEAVING, (dv, dk)
        dubs, dwbs, dkds, dqes, d_tot = ([None] * n for _ in range(5))
        for c in reversed(range(n)):
            rows, sb, gb = chunks[c], sbs[c], ds_out.astype(dtype)
            dubs[c] = (du[rows] + _dot_t(t.kdb[rows], gb)).astype(dtype)
            dkds[c] = _dot(ubs[c], gb)                         # (C, dk)
            dwbs[c] = (-_dot(dubs[c], sb)).astype(dtype)       # (C, dk)
            dqes[c] = _dot(dob[rows], sb)
            d_tot[c] = t.ets[c] * _col_sum(ds_out * st_ref[h, c])  # (1, dk)
            ds_out = t.ets[c] * ds_out + _dot_c0(dob[rows], t.qeb[rows]) \
                - _dot_c0(dubs[c], t.wb[rows])
        ds_scr[h] = ds_out
        dub, dwb, dkd = _stack(dubs), _stack(dwbs), _stack(dkds)
        # U0 = T (beta V);  W = T (beta e^b o K)
        dvb = _dot_c0(t.tb, dub)                               # (R, dv)
        dke = _dot_c0(t.tb, dwb)                               # (R, dk)
        # T = (I + L)^-1: dL = -T^T dT T^T on the strict lower triangle
        d_tm = _dot_t(dub, t.vbb) + _dot_t(dwb, t.keb)
        dlow = jnp.where(strict, -_dot32(
            _dot32(t.tm, d_tm, ((0,), (0,))), t.tm, ((1,), (1,))), 0.0)
        dkk, dqk = dlow * t.beta, jnp.where(tril, dp, 0.0)
        # the sub-blocks' products, a row block at a time: through the
        # rows (k over q) and through the one column operand.  b_ref's
        # own cotangent is zero: L and P do not depend on the reference
        through_cols = jnp.zeros(t.kf.shape, f32)
        k_rows, q_rows = [], []
        for i, sub in enumerate(t.subs):
            block = slice(i * _SUB, (i + 1) * _SUB)
            d_both = jnp.concatenate([dkk[block], dqk[block]],
                                     axis=0).astype(dtype)     # (32, R)
            d_rows = _dot(d_both, sub.cols)                    # (32, dk)
            k_rows.append(d_rows[:_SUB] * sub.rs)
            q_rows.append(d_rows[_SUB:] * sub.rs)
            through_cols = through_cols + _dot_c0(d_both, sub.rows) * sub.cs
        dq = _stack(dqes) * t.eb + _stack(q_rows)
        # dk's terms whose scale rises with b, then those whose falls
        dk_up = dke * t.beta * t.eb + _stack(k_rows)
        dk_down = dkd * t.te + through_cols
        dq_ref[h] = dq.astype(dq_ref.dtype)
        dk_ref[h] = (dk_up + dk_down).astype(dk_ref.dtype)
        dv_ref[h] = (dvb * t.beta).astype(dv_ref.dtype)
        db = dq * t.qf + (dk_up - dk_down) * t.kf
        through_te = dkd * t.kf * t.te
        for c, rows in enumerate(chunks):    # d(b_C), at a chunk's last row
            d_total = d_tot[c] + _col_sum(jnp.where(
                (row >= rows.start) & (row < rows.stop), through_te, 0.0))
            db = db + jnp.where(row == rows.stop - 1, d_total, 0.0)
        db_ref[h] = db
        dbt = _put(dbt, col_head, h, _row_sum(dlow * t.kk)
                   + _row_sum(dvb * t.vf) + _row_sum(dke * t.kf * t.eb))
    dbt_ref[...] = dbt


# ------------------------------------------------------ the kernels' calls

_PARAMS = _compiler_params("parallel", "parallel", "arbitrary",
                           vmem_limit=_VMEM_LIMIT)
# by the decay's form (a CHANNEL's or not): the kernels, their names, and
# the operands' kinds in order (`_specs`); then come the entering states
# and dO, and the backward kernel's outputs are the operands' cotangents
_PAIRS = {
    False: (_gdr_fwd_kernel, _gdr_bwd_kernel, "dwt_gdr",
            ("key", "key", "value", "col", "row", "col")),
    True: (_kda_fwd_kernel, _kda_bwd_kernel, "dwt_kda",
           ("key", "key", "value", "key", "col")),
}


def _state_shape(dk, dv, channel):
    return (dv, dk) if channel else (dk, dv)


def _specs(size, n, hb, dk, dv, at, channel=False):
    """BlockSpecs by operand kind; `at(k)` is the step (n chunks, `size`
    rows) a grid step works on (the backward kernel walks them in
    reverse).  The channel pair's states are held transposed."""
    def rows(d):
        return pl.BlockSpec((None, hb, size, d),
                            lambda b, j, k: (b, j, at(k), 0))
    return dict(
        key=rows(dk), value=rows(dv),
        col=pl.BlockSpec((None, None, None, size, hb),
                         lambda b, j, k: (b, j, at(k), 0, 0)),
        row=pl.BlockSpec((None, None, None, hb, size),
                         lambda b, j, k: (b, j, at(k), 0, 0)),
        state=pl.BlockSpec((None, hb, n, *_state_shape(dk, dv, channel)),
                           lambda b, j, k: (b, j, at(k), 0, 0)))


def _gdr_forward_pallas(*operands, chunk, hb, dtype, save, interpret,
                        channel=False):
    """o (b, H, T, dv) float32 and, with `save`, the state ENTERING every
    chunk, (b, H, chunks, dk, dv) float32, for the backward kernel."""
    q, k, v = operands[:3]
    bsz, h, t, dk = k.shape
    dv, size = v.shape[-1], operands[-1].shape[-2]
    n, steps = size // chunk, t // size
    kernel, _, name, kinds = _PAIRS[channel]
    state = _state_shape(dk, dv, channel)
    sp = _specs(size, n, hb, dk, dv, lambda k: k, channel)
    out_shape = [_out_struct((bsz, h, t, dv), jnp.float32, q)]
    out_specs = [sp["value"]]
    if save:
        out_shape.append(_out_struct((bsz, h, t // chunk, *state),
                                     jnp.float32, q))
        out_specs.append(sp["state"])
    out = pl.pallas_call(
        functools.partial(kernel, chunk=chunk, dtype=dtype, save=save),
        grid=(bsz, h // hb, steps),
        in_specs=[sp[kind] for kind in kinds],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, *state), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name=name + "_fwd",
    )(*operands)
    return tuple(out) if save else (out[0], None)


def _gdr_backward_pallas(*operands_states_do, chunk, hb, dtype, interpret,
                         channel=False):
    operands = operands_states_do[:-2]
    q, k, v = operands[:3]
    bsz, h, t, dk = k.shape
    dv, size = v.shape[-1], operands[-1].shape[-2]
    n, steps = size // chunk, t // size
    _, kernel, name, kinds = _PAIRS[channel]
    sp = _specs(size, n, hb, dk, dv, lambda k: steps - 1 - k, channel)
    specs = [sp[kind] for kind in kinds]
    return pl.pallas_call(
        functools.partial(kernel, chunk=chunk, dtype=dtype),
        grid=(bsz, h // hb, steps),
        in_specs=[*specs, sp["state"], sp["value"]],
        out_specs=specs,
        # q's, k's and v's cotangents in their dtypes, the rest float32
        out_shape=[_out_struct(x.shape, x.dtype if i < 3 else jnp.float32, q)
                   for i, x in enumerate(operands)],
        scratch_shapes=[pltpu.VMEM((hb, *_state_shape(dk, dv, channel)),
                                   jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name=name + "_bwd",
    )(*operands_states_do)


# A model's layers call the kernels with the same shapes and the same
# static plan: behind `jax.jit` a kernel body is traced and lowered to
# Mosaic once a step program, not once a layer (`ops/ssd.py`'s way).
_STATIC = ("chunk", "hb", "dtype", "interpret", "channel")
_forward = jax.jit(_gdr_forward_pallas, static_argnames=_STATIC + ("save",))
_backward = jax.jit(_gdr_backward_pallas, static_argnames=_STATIC)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _chunks(operands, plan):
    """A kernels' pair on its operands — q, k, v head-major, then the
    running sums by column and by row (a decay a head) or head-major as k
    is (a decay a channel), beta by column -> o (b, H, T, dv) float32.
    `plan`: the static arguments."""
    return _forward(*operands, save=False, **dict(plan))[0]


def _chunks_fwd(operands, plan):
    o, states = _forward(*operands, save=True, **dict(plan))
    return o, (*operands, states)


def _chunks_bwd(plan, res, do):
    return (tuple(_backward(*res, do, **dict(plan))),)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def _chunks_a_step(chunk: int, chunks: int) -> int:
    """Chunks side by side a grid step: as many as fill the MXU's rows
    (`_ROWS`) and divide the sequence's chunks.  Measured at the cell's
    shape, five heads a step (PERF.md section 6, PR 48): one chunk of 64
    a step runs a layer's forward kernel in 3.62 ms and its backward in
    4.97, two in 2.68 and 3.61, the same numbers bit for bit; the channel
    pair, four heads a step (PR 58): one in 3.84 and 5.51, two in 2.96
    and 4.11."""
    n = max(1, _ROWS // chunk)
    while chunks % n:
        n -= 1
    return n


def solve_rounds(chunk: int) -> dict:
    """Where each round of a tile's solve runs, by the size s of the
    blocks it joins (s = 2, 4, ... below the chunk; the round from D_1 = I
    was never a computation): "vector" — inside the diagonal blocks of
    `_BLOCK` = 8 steps, ONE forward substitution in float32 on the vector
    units — and "mxu" — two `_dot32` products each, over the lower-half
    rows of the blocks joined: 12 x len(mxu) float32 MXU passes a tile, 36
    of 64 rows at a chunk of 64 where there were 60 of 128.  From the chunk
    alone, and `_solve` reads it (through `_masks`), so the record cannot
    drift from the code; pinned by tests/test_program_from_arguments.py.
    Measured (PERF.md section 6, PR 69), us a head-tile: the solve ALONE
    over 4,096 tiles of two chunks of 64, four a grid step, then the
    forward | backward call at `qwen3_next_80b_a3b.steady`'s shape (1 x
    16,384, 32 heads of 128 | 128).  Every round two products of 128 rows
    (the form before): 1.80, 2.67 | 3.56.  Blocks of 4 on the vector
    units: 1.48, 2.45 | 3.32; of 8: 1.20, 2.37 | 3.27; of 16 (round s = 8
    too, 105 lane rolls a tile): 1.40, 2.72 | 3.61 — slower than none.
    Blocks of 8 and the products over the lower-half rows (this): 0.93,
    2.12 | 3.03; of 16 and the same: 1.31, 2.56 | 3.46; blocks of 8, the
    lower-half rows AND the contraction cut to the 64 lanes that are not
    zero: 1.46, 2.55 | 3.56.  The Olmo hybrid's and Ling's shapes order
    them the same way."""
    rounds = tuple(2 ** i for i in range(1, max(chunk - 1, 0).bit_length()))
    vector = tuple(s for s in rounds if 2 * s <= _BLOCK)
    return {"vector": vector, "mxu": rounds[len(vector):]}


def _kernel_operands(q, k, v, g, beta, chunk, hb, n):
    """What the kernels are handed, from `gated_delta_rule`'s arguments:
    q, k, v head-major (ONE re-layout each a pass); the running sums of g
    inside a chunk — a head's by column and by row, a channel's head-major
    as k is — and beta by column, n chunks a step.  `jax.numpy`,
    differentiated by JAX."""
    bsz, t, h = beta.shape
    c = t // chunk

    def steps(x):  # (b, chunks, C, H) -> (b, H/hb, steps, R, hb)
        return x.reshape(bsz, c // n, n * chunk, h // hb, hb).transpose(
            0, 3, 1, 2, 4)

    cum = jnp.cumsum(g.astype(jnp.float32).reshape(
        bsz, c, chunk, *g.shape[2:]), axis=2)
    if g.ndim == 4:
        return (*(x.transpose(0, 2, 1, 3)
                  for x in (q, k, v, cum.reshape(g.shape))),
                steps(beta.astype(jnp.float32)))
    cum = steps(cum)
    return (*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
            cum, cum.swapaxes(-1, -2), steps(beta.astype(jnp.float32)))


def _chunk_kernels(q, k, v, g, beta, chunk, dtype, hb, interpret=False,
                   chunks_a_step=None):
    """The kernel route: `_chunks` on `_kernel_operands`, the pair g's
    rank names."""
    n = chunks_a_step or _chunks_a_step(chunk, q.shape[1] // chunk)
    plan = (("chunk", chunk), ("hb", hb), ("dtype", jnp.dtype(dtype)),
            ("interpret", interpret), ("channel", g.ndim == 4))
    o = _chunks(_kernel_operands(q, k, v, g, beta, chunk, hb, n), plan)
    return o.transpose(0, 2, 1, 3)
