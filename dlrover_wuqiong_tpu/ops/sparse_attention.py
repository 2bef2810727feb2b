"""Attention over a LEARNED choice of keys (DeepSeek Sparse Attention's
form, arXiv 2512.02556 / the V3.2-Exp report): a light indexer scores
every causal (query, key) pair, each query keeps the `topk` keys of
largest score, the main attention's softmax runs over exactly that set,
and the indexer learns from a KL term to the main attention's own
distribution over the set.

    I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])        s <= t, float32
    S_t      = the min(topk, t + 1) keys s <= t of largest I[t, s],
               a tie to the lower s
    p_a[t, .] = softmax over s in S_t of q_a[t] . k[s] * scale
    o_a[t]   = sum_{s in S_t} p_a[t, s] v[s]
    pbar     = mean_a p_a                                   (a constant)
    L_I      = mean_t sum_{s in S_t} pbar (log pbar - log softmax_S(I))

Four parts, each under its own scope (`sparse_attn/index`, `/select`,
`/attend`, `/index_loss`) and each with two routes chosen by what the
call can observe (`sparse_route`: shapes and `mosaic.kernel_site(mesh)`,
never a knob):

- "kernel", on one TPU device at whole blocks of `_BLOCK` positions,
  heads of whole 128-lane slabs: `dwt_idx_scores` forms I a (block x
  block) tile at a time — no per-head (T x T) array exists anywhere —
  `dwt_idx_select` finds each row's EXACT threshold by a bitwise search
  over the scores' ordered integer form in VMEM (32 counts of a row
  block, then the tie's cut by position) and writes the choice as an
  int8 (b, T, T) mask with the row's log-sum over the kept scores;
  `dwt_fa_sp_fwd` / `dwt_fa_sp_bwd_fused` are a flash attention, every
  causal tile computed and masked by the choice (dense work, the
  mathematics of the kept set: the counters say so — `tiles_run` =
  `tiles_causal`; a grid that skips tiles without a kept pair is a later
  change) — the forward a kv head's GROUP of query heads a grid step,
  the backward a UNIT of one or two of them (below).  What a
  masked tile costs the forward: the int8 tile becomes ONE float32 bias
  a grid step (0 kept, `_NEG` not), added to each of the group's heads'
  scores — no select a head, and none after the exponent, because the
  running maximum starts at a floor ABOVE a masked score (`_NEG / 2`):
  a masked entry's exponent is exactly 0 whatever the row has seen, a
  row whose leading key blocks hold no kept key included.  The scores
  live in base 2 (float32 `s * (scale * log2 e)`, one multiply on the
  tile, `exp2` bare; q stays the bfloat16 it was) and the log-sum goes
  back to the natural one, `(m + log2 l) ln 2`, where it is written.
  A forward step takes `_fwd_blocks`' (512 q rows x 1,024 keys), two
  tiles of keys: a head's row state (m, l, alpha: (512, 1) columns, a
  quarter of a tile pass each) and the rescale of its (512 x 128)
  accumulator are paid once for 1,024 keys; where the diagonal crosses
  such a block only the tiles at or below it run (`_fwd_bands`), and a
  sequence of an odd number of tiles keeps (512 x 512).  Inside a
  step the group's eight first products come first, then its eight
  softmaxes, then its eight products with v: the MXU's and the vector
  units' work in long runs.  Measured by op at the cell's shape
  (PERF.md section 6, PR 63): 30.3 ms a call as (512 x 512) with two
  selects, 18.1 as (512 x 1,024) under the bias in base 2 head after
  head, 15.2 as this; (1024 x 512) 26.6 and (1024 x 1024) 42.3 head
  after head.  `_BLOCK` stays the tile of the mask's COUNTERS
  (`tiles_of`, `tile_counts`, the choice's `chunk`): a live tile is a
  (512 x 512) one, whatever a step of either kernel takes.
  The backward is ONE sweep (PR 65; until then a dq and a dk/dv kernel,
  seven products and two recomputations of p a head and tile, each at
  89% of the MXU's time for its own products): grid (batch, kv head,
  unit of the group's heads, key block, q block at or below it), the
  two inner axes sequential; a step recomputes p ONCE a head under the
  same bias and folded constant (the saved natural log-sum to base 2, a
  column), and from it dv += pT dO, ds = p (dO vT - delta), dk += dsT q
  and dq += ds k — five products.  dq cannot leave a step (its sum runs
  over key blocks, the OUTER axis), so it is summed in a float32 scratch
  that spans the unit's whole query length and leaves once, at the
  unit's last step, `ops/flash_attention.py`'s form since PR 40; a
  whole group's (16,384 x 1,024 lanes, 64 MiB) does not fit, one or two
  heads' do.  dk and dv are sums over every head of the group, and a
  unit is not the group: they are resident for the kv head's WHOLE key
  length the same way (2 x 16,384 x 128 float32; their block's index is
  constant over the unit, key and q axes), summed in float32 across all
  the group's heads and rounded once — no temporary leaves the kernel.
  `scale` meets the sums of dq and dk where they are written.  What a
  step takes is `bwd_step`'s, from shapes alone: the first of
  `_BWD_STEPS` that divides the group and the sequence and fits VMEM —
  one head at (1,024 q rows x 2,048 keys) at the cell's shape, 33.1 ms
  a call where the pair took 45.1 (PERF.md section 6, PR 65: the
  per-step cost, 0.67 us, is what a (512 x 512) step of one head loses,
  46.3 ms, and two heads there halve, 40.6; 4,096 keys a step lose
  everything, 112-249 ms); the diagonal's blocks run only the tiles at
  or below it, as the forward's do (`_run_bands`).
  `dwt_idx_kl` (still (512 x 512) a step; `_probs` takes the same bias
  and folded constant) recomputes the heads' probabilities from the saved
  log-sums a tile at a time, sums them over the heads in VMEM (no
  (heads x T x T) array), and leaves the tile of `softmax_S(I) - pbar`
  where I's tile was; `dwt_idx_bwd` takes that to the indexer's three
  operands.
- "plain": every CPU run, a mesh GSPMD partitions, any other shape —
  dense `jax.numpy` lines, the kernels' oracle.  `lax.top_k` a row makes
  the choice there (stable: the lower index first among equals).

The selection passes no gradient, the indexer's operands are detached by
the caller (`models/sparse_indexer.py`), and q and k reach the index
term as constants: the cross-entropy reaches the main leaves alone, the
index term the indexer's alone.

Parity: none — the reference has no sparse attention.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import (
    LANES,
    _compiler_params,
    _dot,
    _dot_c0,
    _dot_t,
    _iota,
    _out_struct,
)

_BLOCK = 512      # positions a tile's side: q rows, keys, the mask's tile
_FWD_TILES = (1, 2)  # tiles a forward step's (q rows, keys) take: swept
# (heads of a group, tiles of q rows, tiles of keys) a backward step takes,
# the fastest first (ms a call at the cell's shape, PERF.md section 6, PR
# 65: 33.1, 35.0, 35.5, 40.6, 46.3); `bwd_step` takes the first that fits
_BWD_STEPS = ((1, 2, 4), (2, 2, 2), (1, 2, 2), (2, 1, 1), (1, 1, 1))
_SELECT_ROWS = 128  # rows whose whole score row sits in VMEM for the search
_SITES = frozenset({"device"})  # a whole sequence's keys: no shard is one
_VMEM = 96 * 1024 * 1024
_NEG = -1e30  # a masked score: finite, so an empty tile's row stays finite
_LN2 = math.log(2.0)
_LOG2E = 1.0 / _LN2  # exp(x) = exp2(x * _LOG2E): the exponent unit's base
_INT_MIN = -2 ** 31


def sparse_route(t: int, d: int, idx_d: int, mesh=None,
                 itemsize: int = 2) -> str:
    """Which route a sparse attention over `t` positions, main heads of
    `d` and indexer heads of `idx_d` lanes, takes: "kernel" on one TPU
    device (`mesh` is the model config's) where the sequence is whole
    blocks, a main head is whole slabs, an indexer head's lanes are
    whole sublane tiles and ONE head's whole-length dq fits VMEM beside
    the kv head's dk and dv (`bwd_step`; operands of `itemsize` bytes:
    30,720 positions at two, far past what the (T x T) float32 scores
    leave of one chip's memory at any model); else "plain"."""
    if t % _BLOCK or d % LANES or idx_d % 8 \
            or mosaic.kernel_site(mesh) not in _SITES \
            or not bwd_step(t, d, 1, itemsize):
        return "plain"
    return "kernel"


def kept_pairs(t: int, topk: int) -> int:
    """(query, key) pairs a sequence of `t` keeps: min(topk, row + 1) a
    row."""
    k = min(topk, t)
    return k * (k + 1) // 2 + (t - k) * k


# ------------------------------------------------------------ plain route

def _plain_scores(q_idx, k_idx, w):
    """I (b, T, T) float32 of q_idx (b, T, H, di), k_idx (b, T, di) and
    the weights w (b, T, H) with every constant factor in them."""
    r = jnp.einsum("bthd,bsd->bhts", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bhts,bth->bts", jnp.maximum(r, 0.0),
                      w.astype(jnp.float32))


def _plain_select(scores, topk: int):
    """The kept set as a (b, T, T) bool mask: each row's min(topk, t + 1)
    largest scores among s <= t, a tie to the lower s (`lax.top_k` is
    stable)."""
    b, t, _ = scores.shape
    valid = jnp.tril(jnp.ones((t, t), bool))
    masked = jnp.where(valid, scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(topk, t))
    rows = jnp.arange(t)[None, :, None]
    chosen = jnp.zeros((b, t, t), bool).at[
        jnp.arange(b)[:, None, None], rows, idx].set(True)
    return chosen & valid


def _plain_attend(q, k, v, mask, scale):
    """(o (b, T, H, d), p (b, H, T, T) float32) over the kept set; q
    (b, T, H, d), k and v (b, T, KV, d)."""
    rep = q.shape[2] // k.shape[2]
    kr, vr = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, kr,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), vr,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), p


def _plain_kl(scores, mask, pbar):
    """sum_t sum_{S_t} pbar (log pbar - log softmax_S(I)); pbar a
    constant."""
    logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    live = mask & (pbar > 0)
    safe = jnp.where(live, pbar, 1.0)
    return jnp.where(live, safe * (jnp.log(safe)
                                   - jnp.where(live, logq, 0.0)), 0.0).sum()


# ----------------------------------------------------------- kernel bodies

def _ordered(x):
    """float32 -> int32 in the floats' TOTAL order, `lax.top_k`'s: -0.0
    below 0.0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _fold(x):
    """(rows, n * 128) -> (rows, 128): the slabs' sum, no lane crossed."""
    n = x.shape[-1]
    if n <= LANES or n % LANES:
        return x
    out = x[:, :LANES]
    for i in range(1, n // LANES):
        out = out + x[:, i * LANES:(i + 1) * LANES]
    return out


def _scores_kernel(q_ref, k_ref, w_ref, o_ref):
    """One (q block, key block) tile of I: the heads' relu'd products
    against the ONE key, weighted and summed."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j <= i)
    def _():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for h in range(q_ref.shape[1]):
            acc = acc + w[:, h:h + 1] * jnp.maximum(_dot_t(q_ref[0, h], k),
                                                    0.0)
        o_ref[0] = acc


def _select_kernel(s_ref, mask_ref, logz_ref, count_ref, keys_ref, *, topk,
                   chunk):
    """One block of rows: every row's exact threshold among its causal
    keys by a bitwise search over the ordered integers (the largest x
    with count(key >= x) >= the row's k), the tie's cut by position the
    same way, the kept set as int8 and its log-sum."""
    rows = s_ref.shape[1]
    row0 = pl.program_id(1) * rows
    chunks = (row0 + rows + chunk - 1) // chunk  # to the diagonal's end
    t_of = row0 + _iota((rows, 1), 0)
    want = jnp.minimum(topk, t_of + 1)

    width = LANES if chunk % LANES == 0 else chunk  # of a folded count

    def cols(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def keys_at(c):
        return c * chunk + _iota((rows, chunk), 1)

    def valid(c):
        return keys_at(c) <= t_of

    def prepare(c, top):
        x = s_ref[0, :, cols(c)]
        keys_ref[:, cols(c)] = jnp.where(valid(c), _ordered(x), _INT_MIN)
        return jnp.maximum(top, jnp.where(valid(c), x, _NEG).max(
            -1, keepdims=True))

    top = jax.lax.fori_loop(0, chunks, prepare,
                            jnp.full((rows, 1), _NEG, jnp.float32))

    def count(pred):
        def body(c, acc):
            return acc + _fold(pred(c, keys_ref[:, cols(c)]).astype(
                jnp.int32))

        return jax.lax.fori_loop(
            0, chunks, body, jnp.zeros((rows, width), jnp.int32)).sum(
                -1, keepdims=True)

    def bit_of_threshold(n, found):
        cand = found | jnp.left_shift(jnp.int32(1), 31 - n)
        signed = cand ^ _INT_MIN
        enough = count(lambda c, keys: keys >= signed) >= want
        return jnp.where(enough, cand, found)

    # the bits are the key's with the sign flipped: unsigned order
    thr = jax.lax.fori_loop(0, 32, bit_of_threshold,
                            jnp.zeros((rows, 1), jnp.int32)) ^ _INT_MIN
    need = want - count(lambda c, keys: keys > thr)  # ties to keep: >= 1
    bits = max(1, math.ceil(math.log2(s_ref.shape[2])))

    def bit_of_cut(n, found):
        cand = found | jnp.left_shift(jnp.int32(1), bits - 1 - n)
        fits = count(lambda c, keys: (keys == thr)
                     & (keys_at(c) <= cand)) <= need
        return jnp.where(fits, cand, found)

    cut = jax.lax.fori_loop(0, bits, bit_of_cut,
                            jnp.zeros((rows, 1), jnp.int32))

    def write(c, total):
        keys = keys_ref[:, cols(c)]
        kept = ((keys > thr) | ((keys == thr) & (keys_at(c) <= cut))) \
            & valid(c)
        mask_ref[0, :, cols(c)] = kept.astype(jnp.int8)
        # the chunk's kept pairs, for the counters: a row of the count
        count_ref[0, 0, pl.ds(c, 1), :] = jnp.broadcast_to(
            kept.astype(jnp.int32).sum(-1, keepdims=True).sum(
                0, keepdims=True), (1, count_ref.shape[-1]))
        return total + _fold(jnp.where(
            kept, jnp.exp(s_ref[0, :, cols(c)] - top), 0.0))

    total = jax.lax.fori_loop(0, chunks, write,
                              jnp.zeros((rows, width), jnp.float32))
    logz_ref[0] = top + jnp.log(total.sum(-1, keepdims=True))


def _kept(mask_ref):
    return mask_ref[0].astype(jnp.float32) > 0.0


def _bias_of(mask):
    """The choice as what is ADDED to a score, float32: 0 on a kept
    entry, `_NEG` elsewhere.  Formed once a grid step for the group's
    heads; a score under it leaves every exponent as exactly 0."""
    return jnp.where(mask.astype(jnp.float32) > 0.0, 0.0, _NEG)


def _fwd_blocks(t: int, block: int):
    """(q rows, keys) a grid step of the forward takes, in whole tiles of
    `block`: `_FWD_TILES` of them each way where the sequence is whole
    such blocks, else one."""
    return tuple(n * block if t % (n * block) == 0 else block
                 for n in _FWD_TILES)


def _fwd_bands(bq: int, bk: int, rel: int, tile: int):
    """[(q0, q1, k_end)] of a (bq x bk) block whose first query lies
    `rel` positions after its first key: the rows [q0, q1) run the keys
    [0, k_end), whole tiles at or below the diagonal (the choice masks
    inside them), rows of one reach joined; a row tile that sees no key
    of the block is in no band."""
    bands = []
    for q0 in range(0, bq, tile):
        k_end = min(bk, max(0, (q0 + rel) // tile + 1) * tile)
        if bands and bands[-1][2] == k_end:
            bands[-1] = (bands[-1][0], q0 + tile, k_end)
        elif k_end:
            bands.append((q0, q0 + tile, k_end))
    return bands


def _run_bands(run, bq: int, bk: int, rel, tile: int):
    """run(bands) of the (bq x bk) block whose first query lies `rel`
    (traced) positions after its first key: below the diagonal every
    tile of the block runs; a block the diagonal crosses runs the tiles
    at or below it, by its static place; a block above it runs nothing."""
    pl.when(rel >= bk - tile)(lambda: run([(0, bq, bk)]))
    for at in range(tile - bq, bk - tile, tile):
        if at % math.gcd(bq, bk) == 0:
            pl.when(rel == at)(functools.partial(
                run, _fwd_bands(bq, bk, at, tile)))


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, rep, d, tile):
    """One (batch row, kv head, q block, key block): the group's `rep`
    query heads against the one kv head, online softmax in base 2 over
    the tile's scores under the choice's bias."""
    bq, bk = mask_ref.shape[1:]
    i, j = pl.program_id(2), pl.program_id(3)
    rel = i * bq - j * bk  # the block's first query less its first key
    to_log2 = scale * _LOG2E

    @pl.when(j == 0)
    def _():
        # a floor ABOVE a masked score: exp2(masked - m) is exactly 0
        # whatever the row has seen, a row of no kept key yet included
        m_scr[...] = jnp.full(m_scr.shape, _NEG / 2, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def run(bands):
        for q0, q1, k_end in bands:
            rows = slice(q0, q1)
            bias = _bias_of(mask_ref[0, rows, :k_end])
            k, v = k_ref[0, :k_end], v_ref[0, :k_end]
            # the group's first products, THEN its softmaxes, THEN its
            # products with v: head after head (product, softmax,
            # product) the same work took 18.2 ms a call where this
            # order takes 15.2 (PERF.md section 6, PR 63)
            scores = [_dot_t(q_ref[0, rows, h * d:(h + 1) * d], k)
                      for h in range(rep)]
            probs = []
            for h, s in enumerate(scores):
                s = s * to_log2 + bias
                m_old = m_scr[h, rows]
                m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
                p = jnp.exp2(s - m_new)
                alpha = jnp.exp2(m_old - m_new)
                l_scr[h, rows] = alpha * l_scr[h, rows] + p.sum(
                    -1, keepdims=True)
                m_scr[h, rows] = m_new
                probs.append((alpha, p.astype(v.dtype)))
            for h, (alpha, p) in enumerate(probs):
                acc_scr[h, rows] = alpha * acc_scr[h, rows] + _dot(p, v)

    _run_bands(run, bq, bk, rel, tile)

    @pl.when(j == ((i + 1) * bq - 1) // bk)
    def _():
        for h in range(rep):
            o_ref[0, :, h * d:(h + 1) * d] = (
                acc_scr[h] / l_scr[h]).astype(o_ref.dtype)
            lse_ref[0, 0, :, h:h + 1] = (
                m_scr[h] + jnp.log2(l_scr[h])) * _LN2


def _probs(q, k, bias, lse, scale):
    """A head's probabilities on the tile from its saved (natural)
    log-sum: base-2 scores under the choice's bias."""
    return jnp.exp2(_dot_t(q, k) * (scale * _LOG2E) + bias - lse * _LOG2E)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, lse_scr,
                delta_scr, *, scale, units, d, tile):
    """One (batch row, kv head, unit of the group's heads, key block, q
    block at or below it): dq, dk and dv from ONE recomputation of p a
    head under the choice's bias — five products a head and tile.

    dq of the unit's `units` heads is summed in a float32 scratch that
    spans the WHOLE query length, at the q block's rows; its output
    block is the unit's whole sequence (an index constant over both
    inner axes), written once, at the unit's last step.  dk and dv are
    sums over every head of the GROUP, and a unit is not the group: they
    are resident for the kv head's whole key length the same way — their
    block's index is constant over the unit, key and q axes — summed in
    float32 across all the group's heads and rounded once, at the
    group's last step.  `scale` meets the sums of dq and dk where they
    are written, not every ds tile."""
    bq, bk = mask_ref.shape[1:]
    c, j, i = (pl.program_id(a) for a in (2, 3, 4))
    group_ends, *unit_ends = (
        pl.program_id(a) == pl.num_programs(a) - 1 for a in (2, 3, 4))
    unit_ends = unit_ends[0] & unit_ends[1]  # its last key and q block
    rel = i * bq - j * bk  # the block's first query less its first key
    to_log2 = scale * _LOG2E

    def each_block(body, rows):  # a loop, not T / rows copies of the code
        def _block(r, carry):
            body(pl.ds(pl.multiple_of(r * rows, rows), rows))
            return carry

        jax.lax.fori_loop(0, dq_scr.shape[0] // rows, _block, 0)

    @pl.when((j == 0) & (i == 0))
    def _():
        def zero(rows):
            dq_scr[rows] = jnp.zeros((bq,) + dq_scr.shape[1:], jnp.float32)

        each_block(zero, bq)

    @pl.when((c == 0) & (j == 0) & (i == 0))
    def _():
        def zero(rows):
            dk_scr[rows] = jnp.zeros((bk, d), jnp.float32)
            dv_scr[rows] = jnp.zeros((bk, d), jnp.float32)

        each_block(zero, bk)

    # the unit's columns of the group's log-sums (to base 2) and deltas,
    # where a tile of the block runs: which they are is the grid's, a
    # static slice a case
    for unit in range(lse_ref.shape[-1] // units):
        @pl.when((rel > -bq) & (c == unit))
        def _():
            for a in range(units):
                h = unit * units + a
                lse_scr[a] = lse_ref[0, 0, :, h:h + 1] * _LOG2E
                delta_scr[a] = delta_ref[0, 0, :, h:h + 1]

    def run(bands):
        for q0, q1, k_end in bands:
            rows = slice(q0, q1)
            at = pl.ds(pl.multiple_of(i * bq + q0, tile), q1 - q0)
            keys = pl.ds(pl.multiple_of(j * bk, tile), k_end)
            bias = _bias_of(mask_ref[0, rows, :k_end])
            k, v = k_ref[0, :k_end], v_ref[0, :k_end]
            heads = [slice(a * d, (a + 1) * d) for a in range(units)]
            qs = [q_ref[0, rows, lanes] for lanes in heads]
            dos = [do_ref[0, rows, lanes] for lanes in heads]
            # the unit's first products, THEN its elementwise passes,
            # THEN its products with them: the forward's order (head
            # after head took the same time here, to 0.4 ms a call)
            scores = [_dot_t(q, k) for q in qs]
            dps = [_dot_t(do, v) for do in dos]
            ps, dss = [], []
            for a in range(units):
                p = jnp.exp2(scores[a] * to_log2 + bias - lse_scr[a, rows])
                ds = p * (dps[a] - delta_scr[a, rows])
                ps.append(p.astype(v.dtype))
                dss.append(ds.astype(k.dtype))
            dv_scr[keys] += functools.reduce(jnp.add, [
                _dot_c0(p, do) for p, do in zip(ps, dos)])
            dk_scr[keys] += functools.reduce(jnp.add, [
                _dot_c0(ds, q) for ds, q in zip(dss, qs)])
            for ds, lanes in zip(dss, heads):
                dq_scr[at, lanes] += _dot(ds, k)

    _run_bands(run, bq, bk, rel, tile)

    @pl.when(unit_ends)
    def _():
        def write(rows):
            dq_ref[0, rows] = (dq_scr[rows] * scale).astype(dq_ref.dtype)

        each_block(write, bq)

    @pl.when(group_ends & unit_ends)
    def _():
        def write(rows):
            dk_ref[0, rows] = (dk_scr[rows] * scale).astype(dk_ref.dtype)
            dv_ref[0, rows] = dv_scr[rows].astype(dv_ref.dtype)

        each_block(write, bk)


def _kl_kernel(q_ref, k_ref, lse_ref, mask_ref, s_ref, logz_ref,
               part_ref, ds_ref, pbar_scr, *, scale, rep, d, heads):
    """One (batch row, q block, key block, kv head): the group's heads'
    probabilities summed into the tile's pbar; at the last group the
    tile's part of the KL sum and softmax_S(I) - pbar."""
    i, j, g = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(g == 0)
    def _():
        pbar_scr[...] = jnp.zeros(pbar_scr.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        bias = _bias_of(mask_ref[0])
        k = k_ref[0]
        total = pbar_scr[...]
        for h in range(rep):
            total = total + _probs(q_ref[0, :, h * d:(h + 1) * d], k, bias,
                                   lse_ref[0, 0, :, h:h + 1], scale)
        pbar_scr[...] = total

    @pl.when((j <= i) & (g == pl.num_programs(3) - 1))
    def _():
        kept = _kept(mask_ref)
        pbar = pbar_scr[...] * (1.0 / heads)
        logq = s_ref[0] - logz_ref[0]
        live = kept & (pbar > 0.0)
        safe = jnp.where(live, pbar, 1.0)
        part = jnp.where(live, safe * (jnp.log(safe) - logq), 0.0)
        part_ref[0, 0, 0] = jnp.broadcast_to(
            part.sum(-1, keepdims=True).sum(0, keepdims=True),
            part_ref.shape[3:])
        ds_ref[0] = jnp.where(kept, jnp.exp(logq), 0.0) - pbar


def _idx_bwd_kernel(ds_ref, q_ref, k_ref, w_ref, dq_ref, dk_ref, dw_ref):
    """One (batch row, q block, key block) of the indexer's backward:
    the tile of dI to the heads' queries and weights (summed over the
    key blocks in their resident blocks) and to the ONE key (summed over
    every tile of the sequence in its resident block)."""
    i, j = pl.program_id(1), pl.program_id(2)
    block = k_ref.shape[1]

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        ds, k, w = ds_ref[0], k_ref[0], w_ref[0]
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        dk = jnp.zeros(k.shape, jnp.float32)
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h]
            r = _dot_t(q, k)
            g = jnp.where(r > 0.0, ds * w[:, h:h + 1], 0.0).astype(k.dtype)
            dq_ref[0, h] += _dot(g, k)
            dk = dk + _dot_c0(g, q)
            dw_ref[0, :, h:h + 1] += (ds * jnp.maximum(r, 0.0)).sum(
                -1, keepdims=True)
        dk_ref[0, rows, :] += dk


# ------------------------------------------------------------ pallas calls

def _causal(j, i):
    return jnp.minimum(j, i)


def _params(*semantics):
    return _compiler_params(*semantics, vmem_limit=_VMEM)


def _scores_pallas(q_t, k_idx, w, *, block, interpret):
    """I (b, T, T) float32 from q_t (b, H, T, di), k_idx (b, T, di) and
    w (b, T, H): the tiles at or below the diagonal are written, the
    rest is never read."""
    b, heads, t, di = q_t.shape
    n = t // block
    return pl.pallas_call(
        _scores_kernel,
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, block, di), lambda b_, i, j: (b_, 0, i, 0)),
            pl.BlockSpec((1, block, di),
                         lambda b_, i, j: (b_, _causal(j, i), 0)),
            pl.BlockSpec((1, block, heads), lambda b_, i, j: (b_, i, 0))],
        out_specs=pl.BlockSpec((1, block, block),
                               lambda b_, i, j: (b_, i, _causal(j, i))),
        out_shape=_out_struct((b, t, t), jnp.float32, q_t),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=b * heads * di * t * t, transcendentals=0,
            bytes_accessed=2 * b * t * t + q_t.size * 2),
        interpret=interpret,
        name="dwt_idx_scores",
    )(q_t, k_idx, w)


def _select_pallas(scores, *, topk, rows, chunk, interpret):
    """(mask int8 (b, T, T), logz (b, T, 1), the kept pairs of each
    (row block, chunk) up to the diagonal's (b, T / rows, T / chunk,
    128), the rest unwritten) of the scores' rows."""
    b, t, _ = scores.shape
    row_block = pl.BlockSpec((1, rows, t), lambda b_, r: (b_, r, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, chunk=chunk),
        grid=(b, t // rows),
        in_specs=[row_block],
        out_specs=[row_block,
                   pl.BlockSpec((1, rows, 1), lambda b_, r: (b_, r, 0)),
                   pl.BlockSpec((1, 1, t // chunk, LANES),
                                lambda b_, r: (b_, r, 0, 0))],
        out_shape=[_out_struct((b, t, t), jnp.int8, scores),
                   _out_struct((b, t, 1), jnp.float32, scores),
                   _out_struct((b, t // rows, t // chunk, LANES), jnp.int32,
                               scores)],
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        cost_estimate=pl.CostEstimate(
            flops=80 * b * t * t, transcendentals=b * t * t // 2,
            bytes_accessed=5 * b * t * t // 2),
        interpret=interpret,
        name="dwt_idx_select",
    )(scores)


def _dims(q, k, n_kv, block):
    """(b, t, q's lanes, head size, query heads a kv head, blocks)."""
    b, t, lanes = q.shape
    d = k.shape[-1] // n_kv
    return b, t, lanes, d, lanes // d // n_kv, t // block


def _fwd_pallas(q, k, v, mask, *, scale, n_kv, block, interpret,
                blocks=None):
    """(o (b, T, H*d), lse (b, KV, T, rep)) over the mask's kept set, a
    grid step `_fwd_blocks`' (q rows x keys) in tiles of `block`
    (`blocks` overrides them: sweeps and tests, no caller of the package
    sets it)."""
    b, t, lanes, d, rep, _ = _dims(q, k, n_kv, block)
    bq, bk = blocks or _fwd_blocks(t, block)

    def keys_of(i, j):  # the last key block at or below the q block's end
        return jnp.minimum(j, ((i + 1) * bq - 1) // bk)

    rows = pl.BlockSpec((1, bq, rep * d), lambda b_, g, i, j: (b_, i, g))
    keys = pl.BlockSpec((1, bk, d),
                        lambda b_, g, i, j: (b_, keys_of(i, j), g))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, rep=rep, d=d,
                          tile=block),
        grid=(b, n_kv, t // bq, t // bk),
        in_specs=[rows, keys, keys,
                  pl.BlockSpec((1, bq, bk),
                               lambda b_, g, i, j: (b_, i, keys_of(i, j)))],
        out_specs=[rows, pl.BlockSpec((1, 1, bq, rep),
                                      lambda b_, g, i, j: (b_, g, i, 0))],
        out_shape=[_out_struct(q.shape, q.dtype, q),
                   _out_struct((b, n_kv, t, rep), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((rep, bq, 1), jnp.float32),
                        pltpu.VMEM((rep, bq, 1), jnp.float32),
                        pltpu.VMEM((rep, bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * lanes * t * t, transcendentals=b * lanes // d
            * t * t // 2, bytes_accessed=4 * q.size + n_kv * b * t * t // 2),
        interpret=interpret,
        name="dwt_fa_sp_fwd",
    )(q, k, v, mask)


def _bwd_vmem(units: int, t: int, d: int, itemsize: int, bq: int,
              bk: int) -> int:
    """Bytes of VMEM the fused backward holds at `units` heads and
    (bq x bk) a grid step, reckoned from shapes: the whole-length
    float32 sums with their double-buffered output blocks (dq a unit; dk
    and dv the kv head), the step's operands double-buffered (the
    log-sums' and deltas' columns lie on 128 lanes), and three float32
    score tiles a head for the values of the body.  Against the least
    limit Mosaic compiles each under for a described v5e at the cell's
    shape: 81 / 95 / 66 / 73 / 54 MiB reckoned for `_BWD_STEPS`' five
    where 73 / 91 / 61 / 73 / 52 are held."""
    whole = t * d * (units + 2) * (4 + 2 * itemsize)
    step = 2 * (2 * itemsize * d * (bq * units + bk) + bq * bk
                + 2 * 4 * bq * LANES)
    return whole + step + 3 * 4 * bq * bk * units


def bwd_step(t: int, d: int, rep: int, itemsize: int = 2,
             block: int = _BLOCK) -> tuple | None:
    """(heads of a group, q rows, keys) a grid step of the fused
    backward takes, from the shapes alone — the first of `_BWD_STEPS`
    whose heads divide the group's `rep`, whose blocks divide the
    sequence and whose VMEM (`_bwd_vmem`: a unit's whole-length dq
    beside the kv head's dk and dv and the step's blocks) fits `_VMEM`;
    None where not one head's at one tile does (`sparse_route` then says
    "plain").  At the cell's 16,384 x 128: one head at (1,024 x 2,048) —
    two heads there do not fit, and one head at that step is faster than
    two at any that does.  The counter of this decision; in a trace its
    witness is the sweep's grid, (batch, kv heads, rep / heads, key
    blocks, q blocks)."""
    for units, *tiles in _BWD_STEPS:
        bq, bk = (n * block for n in tiles)
        if rep % units == 0 and t % bq == 0 and t % bk == 0 and _bwd_vmem(
                units, t, d, itemsize, bq, bk) <= _VMEM:
            return units, bq, bk
    return None


def _bwd_pallas(q, k, v, do, lse, delta, mask, *, scale, n_kv, block,
                interpret, step=None):
    """(dq, dk, dv) of `_fwd_pallas` over the mask's kept set in ONE
    sweep, `bwd_step`'s (heads of a group, q rows, keys) a grid step
    (`step` overrides it: sweeps and tests, no caller of the package
    sets it)."""
    b, t, lanes, d, rep, _ = _dims(q, k, n_kv, block)
    units, bq, bk = step or bwd_step(t, d, rep, q.dtype.itemsize, block)
    n_units = rep // units

    def below(j, i):  # the first q block that sees the key block, or i
        return jnp.maximum(i, j * bk // bq)

    # a grid (b, kv head g, unit c, key block j, q block i): the unit's
    # q rows, the kv head's rows, the group's per-head numbers of the
    # rows and the mask's tile; dq the unit's and dk, dv the kv head's
    # WHOLE length
    rows = pl.BlockSpec((1, bq, units * d), lambda b_, g, c, j, i: (
        b_, below(j, i), g * n_units + c))
    keys = pl.BlockSpec((1, bk, d), lambda b_, g, c, j, i: (b_, j, g))
    nums = pl.BlockSpec((1, 1, bq, rep), lambda b_, g, c, j, i: (
        b_, g, below(j, i), 0))
    tile = pl.BlockSpec((1, bq, bk), lambda b_, g, c, j, i: (
        b_, below(j, i), j))
    whole_q = pl.BlockSpec((1, t, units * d), lambda b_, g, c, j, i: (
        b_, 0, g * n_units + c))
    whole_k = pl.BlockSpec((1, t, d), lambda b_, g, c, j, i: (b_, 0, g))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, units=units, d=d,
                          tile=block),
        grid=(b, n_kv, n_units, t // bk, t // bq),
        in_specs=[rows, keys, keys, rows, nums, nums, tile],
        out_specs=[whole_q, whole_k, whole_k],
        out_shape=[_out_struct(q.shape, q.dtype, q),
                   _out_struct(k.shape, k.dtype, k),
                   _out_struct(v.shape, v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((t, units * d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((units, bq, 1), jnp.float32),
                        pltpu.VMEM((units, bq, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=5 * b * lanes * t * t, transcendentals=b * lanes // d
            * t * t // 2, bytes_accessed=b * lanes // d * t * t // 2 * (
                2 * q.dtype.itemsize * d * units + bk) // (bk * units)),
        interpret=interpret,
        name="dwt_fa_sp_bwd_fused",
    )(q, k, v, do, lse, delta, mask)


def _kl_pallas(q, k, lse, mask, scores, logz, *, scale, n_kv, block,
               interpret):
    """(the tiles' parts of the KL sum (b, n, n, 8, 128), softmax_S(I) -
    pbar (b, T, T) in the scores' own buffer): tiles above the diagonal
    are written by neither."""
    b, t, lanes, d, rep, n = _dims(q, k, n_kv, block)
    tile = pl.BlockSpec((1, block, block),
                        lambda b_, i, j, g: (b_, i, _causal(j, i)))
    return pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, rep=rep, d=d,
                          heads=lanes // d),
        grid=(b, n, n, n_kv),
        in_specs=[
            pl.BlockSpec((1, block, rep * d),
                         lambda b_, i, j, g: (b_, i, g)),
            pl.BlockSpec((1, block, d),
                         lambda b_, i, j, g: (b_, _causal(j, i), g)),
            pl.BlockSpec((1, 1, block, rep),
                         lambda b_, i, j, g: (b_, g, i, 0)),
            tile, tile,
            pl.BlockSpec((1, block, 1), lambda b_, i, j, g: (b_, i, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, 1, 8, LANES),
                         lambda b_, i, j, g: (b_, i, _causal(j, i), 0, 0)),
            tile],
        out_shape=[_out_struct((b, n, n, 8, LANES), jnp.float32, q),
                   _out_struct((b, t, t), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=b * lanes * t * t, transcendentals=b * lanes // d
            * t * t // 2, bytes_accessed=2 * q.size * n + 5 * b * t * t),
        interpret=interpret,
        name="dwt_idx_kl",
    )(q, k, lse, mask, scores, logz)


def _idx_bwd_pallas(ds, q_t, k_idx, w, *, block, interpret):
    """(dq_t, dk, dw) float32 of `_scores_pallas` from dI's tiles."""
    b, heads, t, di = q_t.shape
    n = t // block
    q_block = pl.BlockSpec((1, heads, block, di),
                           lambda b_, i, j: (b_, 0, i, 0))
    w_block = pl.BlockSpec((1, block, heads), lambda b_, i, j: (b_, i, 0))
    return pl.pallas_call(
        _idx_bwd_kernel,
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, block, block),
                         lambda b_, i, j: (b_, i, _causal(j, i))),
            q_block,
            pl.BlockSpec((1, block, di),
                         lambda b_, i, j: (b_, _causal(j, i), 0)),
            w_block],
        out_specs=[q_block,
                   pl.BlockSpec((1, t, di), lambda b_, i, j: (b_, 0, 0)),
                   w_block],
        out_shape=[_out_struct(q_t.shape, jnp.float32, q_t),
                   _out_struct(k_idx.shape, jnp.float32, q_t),
                   _out_struct(w.shape, jnp.float32, q_t)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=3 * b * heads * di * t * t, transcendentals=0,
            bytes_accessed=2 * b * t * t + 3 * q_t.size * 4),
        interpret=interpret,
        name="dwt_idx_bwd",
    )(ds, q_t, k_idx, w)


_scores = jax.jit(_scores_pallas, static_argnames=("block", "interpret"))
_select = jax.jit(_select_pallas,
                  static_argnames=("topk", "rows", "chunk", "interpret"))
_STATIC = ("scale", "n_kv", "block", "interpret")
_fwd = jax.jit(_fwd_pallas, static_argnames=_STATIC)
_bwd = jax.jit(_bwd_pallas, static_argnames=_STATIC + ("step",))
_kl = jax.jit(_kl_pallas, static_argnames=_STATIC)
_idx_bwd = jax.jit(_idx_bwd_pallas, static_argnames=("block", "interpret"))


# ----------------------------------------------------- the kernel route

def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend_kernels(q, k, v, mask, plan, step):
    return tuple(_fwd(q, k, v, mask, **dict(plan)))


def _attend_fwd(q, k, v, mask, plan, step):
    o, lse = _fwd(q, k, v, mask, **dict(plan))
    return (o, lse), (q, k, v, mask, o, lse)


def _attend_bwd(plan, step, kept, cotangents):
    q, k, v, mask, o, lse = kept
    do = cotangents[0]  # the log-sums feed constants only
    b, t, _ = q.shape
    n_kv, rep = lse.shape[1], lse.shape[3]
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
        b, t, n_kv, rep, -1).sum(-1).transpose(0, 2, 1, 3)
    return *_bwd(q, k, v, do, lse, delta, mask, step=step,
                 **dict(plan)), _float0(mask)


_attend_kernels.defvjp(_attend_fwd, _attend_bwd)


def _tile_sum(parts):
    """The parts of the tiles at or below the diagonal, summed."""
    n = parts.shape[1]
    return jnp.where(jnp.tril(jnp.ones((n, n), bool)),
                     parts[..., 0, 0], 0.0).sum()


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _kl_kernels(q_t, k_idx, w, scores, mask, logz, q, k, lse, plan):
    return _tile_sum(_kl(q, k, lse, mask, scores, logz, **dict(plan))[0])


def _kl_fwd(q_t, k_idx, w, scores, mask, logz, q, k, lse, plan):
    parts, ds = _kl(q, k, lse, mask, scores, logz, **dict(plan))
    # logz, q, k and lse live on as the attention's own residuals
    return _tile_sum(parts), (ds, q_t, k_idx, w, mask, logz, q, k, lse)


def _kl_bwd(plan, kept, g):
    ds, q_t, k_idx, w, mask, *constants = kept
    plan = dict(plan)
    with jax.named_scope("scores"):  # the scores' own backward
        dq, dk, dw = _idx_bwd(ds, q_t, k_idx, w, block=plan["block"],
                              interpret=plan["interpret"])
    # the constants' cotangents meet a stop_gradient: never computed
    return ((g * dq).astype(q_t.dtype), (g * dk).astype(k_idx.dtype),
            (g * dw).astype(w.dtype), jnp.zeros_like(ds), _float0(mask),
            *(jnp.zeros_like(x) for x in constants))


_kl_kernels.defvjp(_kl_fwd, _kl_bwd)


def _sparse_kernels(q, k, v, q_idx, k_idx, w, topk, scale, block=None,
                    rows=None, interpret=False, bwd=None):
    """`sparse_attention` on the kernel route whatever the route says
    (tests reach the kernels in interpret mode through here, and the
    attention's backward at the step `bwd` names, `_bwd_pallas`'s
    override)."""
    b, t, n_kv = *q.shape[:2], k.shape[2]
    block = block or _BLOCK
    rows = min(rows or _SELECT_ROWS, block)
    heads, d = q.shape[2:]
    stop = jax.lax.stop_gradient
    plan = (("scale", scale), ("n_kv", n_kv), ("block", block),
            ("interpret", interpret))
    q_t = q_idx.transpose(0, 2, 1, 3)  # a head's rows together
    w = w.astype(jnp.float32)
    with jax.named_scope("index/scores"):
        scores = _scores(stop(q_t), stop(k_idx), stop(w), block=block,
                         interpret=interpret)
    with jax.named_scope("select"):
        mask, logz, counts = _select(scores, topk=topk, rows=rows,
                                     chunk=block, interpret=interpret)
        # a tile's kept pairs: its row blocks' counts
        tiles = counts[..., 0].reshape(b, t // block, block // rows,
                                       t // block).sum(2)
    with jax.named_scope("attend"):
        rows_of = [x.reshape(b, t, -1) for x in (q, k, v)]
        o, lse = _attend_kernels(*rows_of, mask, plan, bwd)
    with jax.named_scope("index_loss"):
        kl = _kl_kernels(q_t, k_idx, w, scores, mask, logz,
                         stop(rows_of[0]), stop(rows_of[1]), stop(lse), plan)
    return o.reshape(b, t, heads, d), kl / (b * t), mask, tiles


# ------------------------------------------------------------- the entry

def _sparse_plain(q, k, v, q_idx, k_idx, w, topk, scale):
    stop = jax.lax.stop_gradient
    with jax.named_scope("index"):
        scores = _plain_scores(q_idx, k_idx, w)
    with jax.named_scope("select"):
        mask = _plain_select(stop(scores), topk)
    with jax.named_scope("attend"):
        o, p = _plain_attend(q, k, v, mask, scale)
    with jax.named_scope("index_loss"):
        kl = _plain_kl(scores, mask, stop(p.mean(1)))
    return o, kl / math.prod(q.shape[:2]), mask, tiles_of(mask, _BLOCK)


def tiles_of(mask, block: int):
    """The kept pairs of each score tile of side `block` (the whole
    sequence's where it is shorter), (b, n, n) int32, of a (b, T, T)
    choice."""
    b, t, _ = mask.shape
    block = min(block, t)
    n = t // block
    return mask[:, :n * block, :n * block].astype(jnp.int32).reshape(
        b, n, block, n, block).sum((2, 4))


def tile_counts(tiles):
    """(kept pairs, score tiles that hold a kept pair, causal tiles) of
    a (b, n, n) count of kept pairs a tile, float32: data, counted from
    the step's own choice; what lies above the diagonal's tiles is not
    read."""
    b, n, _ = tiles.shape
    tiles = jnp.where(jnp.tril(jnp.ones((n, n), bool)), tiles, 0)
    return (tiles.sum().astype(jnp.float32),
            (tiles > 0).sum().astype(jnp.float32),
            jnp.float32(b * n * (n + 1) // 2))


def sparse_attention(q, k, v, q_idx, k_idx, w, topk: int, scale=None,
                     mesh=None):
    """(o, index KL, stats) of the module docstring's equations.

    q (b, T, H, d), k and v (b, T, KV, d), rotated and normed by the
    caller; q_idx (b, T, Hi, di), k_idx (b, T, di) and w (b, T, Hi) the
    indexer's, w carrying every constant factor.  The cotangent of o
    reaches q, k and v alone and the KL's the indexer's three alone (the
    choice is a constant, q and k are constants to the KL): the caller
    detaches what FEEDS the indexer.  `stats` is (kept pairs, causal
    pairs, live tiles, causal tiles, tiles the implementation computes)
    of this call, float32 scalars."""
    b, t, heads, d = q.shape
    scale = scale or 1.0 / math.sqrt(d)
    with jax.named_scope("sparse_attn"):
        if sparse_route(t, d, q_idx.shape[-1], mesh,
                        q.dtype.itemsize) == "kernel":
            o, kl, _, tiles = _sparse_kernels(q, k, v, q_idx, k_idx, w,
                                              topk, scale)
        else:
            o, kl, _, tiles = _sparse_plain(q, k, v, q_idx, k_idx, w, topk,
                                            scale)
        with jax.named_scope("counters"):
            kept, live, causal_tiles = tile_counts(
                jax.lax.stop_gradient(tiles))
    stats = jnp.stack([kept, jnp.float32(b * t * (t + 1) // 2), live,
                       causal_tiles, causal_tiles])
    return o, kl, stats
