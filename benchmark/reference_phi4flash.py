"""Plain reference: the `phi4flash` decoder-hybrid-decoder stack (Mamba-1
selective scans, differential attention under a window or none, one full
layer whose keys and values the cross-decoder reads, gated memory units
on the last scan's output) forward pass and training loss in
`jax.numpy`, float32.

Follows `microsoft/Phi-4-mini-flash-reasoning` config.json (`model_type:
phi4flash`) and the papers its card names: SambaY (arXiv:2507.06607),
Differential Transformer (arXiv:2410.05258), Mamba (arXiv:2312.00752),
YOCO (arXiv:2405.05254).  n = the PUBLISHED `num_hidden_layers`; a layer
keeps its published index i whatever cut holds it (`layer_ids`).

    x = E[ids]                                     no position term anywhere
    for i in layer_ids:
        x = x + mixer_i(LN(x))                     LayerNorm, scale and bias,
        x = x + (silu(g) * w) W_2,                 eps `layer_norm_eps`
            [g | w] = LN'(x) [W_gate | W_up]
    logits = LN_f(x) E^T                           tied
    loss   = mean next-token cross-entropy, nothing added

Mamba-1 (even i <= n/2; d_inner = 2 x hidden, N = 16 states, 4 taps,
dt_rank = ceil(hidden / 16)):

    [x | z] = u W_in
    x  = silu(conv4(x) + b)                        depthwise, causal
    [r | B | C] = x W_x                            dt_rank | N | N
    dt = softplus(r W_dt + b_dt);  A = -exp(A_log) (d_inner, N)
    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t,   h_0 = 0
    y_t = h_t C_t + D * x_t
    out = (y * silu(z)) W_out
    layer n/2 also hands on m = y.

Gated memory unit (even i >= n/2 + 2):  out = (m * silu(u W_1)) W_2.

Differential attention (odd i): q (H heads of d), k, v (KV heads) from
Wqkv (with bias); heads paired (2j, 2j + 1): q1/q2 H/2 heads, k1/k2 and
v1/v2 KV/2 heads, H/KV query pairs a key pair;

    a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2],  a2 = softmax(q2 k2^T / sqrt(d)) [v1 | v2]
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i)
    o = RMSNorm_2d(a1 - lam * a2) * (1 - lam0),  then Wo (with bias)

causal, and for odd i < n/2 under a window of `sliding_window` keys that
ends at the query's own.  Layer n/2 + 1 hands on its (K, V); odd i above
it have Wq and Wo only and attend, causally and without a window, to
those.

`wrong=` names ONE term to get wrong (the controls the cell's tolerances
must tell from the truth): "no_subtraction" (lam = 0), "no_window",
"no_skip" (D * x dropped), "gmu_memory" (the unit gates its own
projection u W_1 in place of m), "cross_own_kv" (a cross layer projects
its OWN input by the key-value layer's matrices).  `dtype=bfloat16` is
the control one precision below: parameters, activations, products AND
every statistic (the norms', the softmax's, the loss's) in bfloat16,
only the recurrence's carried state float32 — nothing here casts to
float32 but that state, so in float32 every line is float32's.

No kernel, no chunked or parallel scan, no import from the program's
model code: the recurrence is a SEQUENTIAL `lax.scan` over time, the
softmax a masked softmax.  It reads the parameter tree by the names the
program's `Phi4Flash` gives its leaves, which is the only thing it
shares with it (the program keeps W_1 of the SwiGLU as two matrices,
`gate_proj` and `up_proj`).

What changes no number, only what is compiled and kept: the time scan
nested in blocks of `_TIME_BLOCK` steps, each block and each layer under
`jax.checkpoint`; attention one head and one block of `_QUERY_BLOCK`
queries at a time (`lax.map` over both), each under `jax.checkpoint`;
the SwiGLU, the memory units and the head with its loss `_ROW_BLOCK`
positions at a time and a Mamba mixer `_CHANNEL_BLOCK` of its channels
at a time, the same way (1 x 16,384 tokens beside 8.4 GB of training
state: whole, the logits and their gradient alone are 3.3 GB and a
mixer's float32 intermediates 5).
Call under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_TIME_BLOCK = 128
_QUERY_BLOCK = 1024
_ROW_BLOCK = 2048  # rows of the SwiGLU, the memory units and the loss
_CHANNEL_BLOCK = 1024  # channels of a Mamba mixer's d_inner
WRONG = ("no_subtraction", "no_window", "no_skip", "gmu_memory",
         "cross_own_kv")


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _by_rows(fn, *arrays):
    """fn over blocks of `_ROW_BLOCK` positions, each under
    `jax.checkpoint`: `arrays` (b, t, ...) -> (b, t, ...)."""
    b, t = arrays[0].shape[:2]
    blk = math.gcd(b * t, _ROW_BLOCK)
    out = jax.lax.map(
        jax.checkpoint(lambda rows: fn(*rows)),
        tuple(a.reshape(-1, blk, a.shape[-1]) for a in arrays))
    return out.reshape(b, t, out.shape[-1])


def _swiglu(x, p):
    return _by_rows(
        lambda rows: (jax.nn.silu(rows @ p["gate_proj"]["kernel"])
                      * (rows @ p["up_proj"]["kernel"]))
        @ p["down_proj"]["kernel"], x)


# ------------------------------------------------------------- Mamba-1

def _causal_conv(x, kernel, bias):
    """x (b, t, c); kernel (k, c), its LAST tap on the current step."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * kernel[j] for j in range(k)) + bias


def recurrence(x, dt, a, b_mat, c_mat):
    """The selective recurrence, one step at a time, a float32 state.
    x, dt (b, t, D); a (D, N); b_mat, c_mat (b, t, N).  Returns
    sum_n h_t C_t, (b, t, D), float32."""
    bsz, t, d = x.shape
    f32 = jnp.float32

    def step(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    @jax.checkpoint
    def block(h, rows):
        return jax.lax.scan(step, h, rows)

    blk = math.gcd(t, _TIME_BLOCK)
    rows = tuple(v.astype(f32).swapaxes(0, 1).reshape(
        t // blk, blk, bsz, v.shape[-1]) for v in (x, dt, b_mat, c_mat))
    _, y = jax.lax.scan(block, jnp.zeros((bsz, d, a.shape[1]), f32), rows)
    return y.reshape(t, bsz, d).swapaxes(0, 1)


def mamba_mixer(u, p, *, state, wrong=()):
    """u (b, t, hidden) -> (out (b, t, hidden), y (b, t, d_inner)).

    The equations in the module's docstring, `_CHANNEL_BLOCK` channels
    of d_inner at a time (every step between W_in and W_out is a
    channel's own but `x W_x`, a sum over the channels, which is taken
    first, block by block): what is kept is then a block's, not the
    layer's."""
    f32 = jnp.float32
    d_inner = p["A_log"].shape[0]
    blk = math.gcd(d_inner, _CHANNEL_BLOCK)
    rank = p["dt_proj_kernel"].shape[0]

    def by_block(a, axis):  # the channel axis cut into blocks, blocks first
        a = jnp.moveaxis(a, axis, 0)
        return jnp.moveaxis(a.reshape(-1, blk, *a.shape[1:]), 1, axis + 1)

    w_x, w_z = (by_block(w, 1) for w in jnp.split(p["in_proj"]["kernel"], 2,
                                                  axis=1))
    leaves = (w_x, by_block(p["conv_kernel"], 1), by_block(p["conv_bias"], 0))

    def conv_x(w_in, taps, bias):
        return jax.nn.silu(_causal_conv(u @ w_in, taps, bias))

    rbc = jax.lax.map(
        jax.checkpoint(lambda a: conv_x(*a[:3]) @ a[3]),
        leaves + (by_block(p["x_proj"]["kernel"], 0),)).sum(0)
    low, b_mat, c_mat = jnp.split(rbc, [rank, rank + state], axis=-1)

    @jax.checkpoint
    def one_block(out, a):
        w_in, taps, bias, w_gate, w_dt, b_dt, a_log, d_skip, w_out = a
        x = conv_x(w_in, taps, bias)
        dt = jax.nn.softplus(low @ w_dt + b_dt)
        y = recurrence(x, dt, -jnp.exp(a_log).astype(f32), b_mat,
                       c_mat).astype(u.dtype)
        if "no_skip" not in wrong:
            y = y + d_skip * x
        return out + (y * jax.nn.silu(u @ w_gate)) @ w_out, y

    out, y = jax.lax.scan(one_block, jnp.zeros_like(u), leaves + (
        w_z, by_block(p["dt_proj_kernel"], 1), by_block(p["dt_proj_bias"], 0),
        by_block(p["A_log"], 0), by_block(p["D"], 0),
        by_block(p["out_proj"]["kernel"], 0)))
    # (blocks, b, t, blk) -> (b, t, d_inner)
    return out, jnp.moveaxis(y, 0, 2).reshape(*u.shape[:2], d_inner)


def gated_memory_unit(u, m, p, *, wrong=()):
    def rows(u, m):
        gate = u @ p["in_proj"]["kernel"]
        if "gmu_memory" in wrong:
            m = gate
        return (m * jax.nn.silu(gate)) @ p["out_proj"]["kernel"]
    return _by_rows(rows, u, m)


# ------------------------------------------ differential attention

def _masked_attention(q, k, v, *, window):
    """q (H, b, t, d), k (H, b, t, d), v (H, b, t, dv): a masked softmax
    a head and a block of queries; (H, b, t, dv)."""
    heads, b, t, d = q.shape
    blk = math.gcd(t, _QUERY_BLOCK)
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def one_block(q_blk, first, k_h, v_h):
        att = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) * scale
        dist = first + jnp.arange(blk)[:, None] - jnp.arange(t)[None, :]
        kept = dist >= 0 if window is None else (dist >= 0) & (dist < window)
        att = jnp.where(kept, att, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v_h)

    def one_head(qkv):
        q_h, k_h, v_h = qkv  # q_h (blocks, b, blk, d)
        return jax.lax.map(
            lambda fq: one_block(fq[1], fq[0], k_h, v_h),
            (jnp.arange(t // blk) * blk, q_h))

    y = jax.lax.map(one_head, (
        q.reshape(heads, b, t // blk, blk, d).swapaxes(1, 2), k, v))
    # (H, blocks, b, blk, dv) -> (H, b, t, dv)
    return y.swapaxes(1, 2).reshape(heads, b, t, v.shape[-1])


def diff_attention(u, p, *, layer, n_head, n_kv_head, window, eps, kv=None,
                   wrong=()):
    """u (b, t, hidden) -> (out, (k, v)); `kv` another layer's (k, v),
    (b, t, KV * d) each, for a layer that projects queries alone."""
    b, t, _ = u.shape
    if kv is None:
        d = p["qkv_proj"]["kernel"].shape[1] // (n_head + 2 * n_kv_head)
        q, k, v = jnp.split(
            u @ p["qkv_proj"]["kernel"] + p["qkv_proj"]["bias"],
            [n_head * d, (n_head + n_kv_head) * d], axis=-1)
    else:
        d = p["q_proj"]["kernel"].shape[1] // n_head
        q = u @ p["q_proj"]["kernel"] + p["q_proj"]["bias"]
        k, v = kv
    rep = n_head // n_kv_head
    # (pair, which, b, t, d): q pair j reads key pair j // rep
    q = q.reshape(b, t, n_head // 2, 2, d).transpose(2, 3, 0, 1, 4)
    k_pairs = k.reshape(b, t, n_kv_head // 2, 2, d).transpose(2, 3, 0, 1, 4)
    v_pairs = v.reshape(b, t, n_kv_head // 2, 2 * d).transpose(2, 0, 1, 3)
    pair = jnp.arange(n_head // 2) // rep
    a = [_masked_attention(q[:, which], k_pairs[pair, which], v_pairs[pair],
                           window=window) for which in (0, 1)]
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lq1, lk1, lq2, lk2 = (p[name] for name in (
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    if "no_subtraction" in wrong:
        lam = 0.0
    y = a[0] - lam * a[1]
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = (y * p["subln_scale"] * (1.0 - lam0)).astype(u.dtype)
    # (pairs, b, t, 2d) -> (b, t, H * d)
    y = y.transpose(1, 2, 0, 3).reshape(b, t, n_head * d)
    return y @ p["o_proj"]["kernel"] + p["o_proj"]["bias"], (k, v)


# ------------------------------------------------------------- the stack

def kind_of(i: int, n_published: int, mb_per_layer: int) -> str:
    half = n_published // 2
    if i % mb_per_layer == 0:
        return "mamba" if i < half + mb_per_layer else "gmu"
    if i < half:
        return "window"
    return "full" if i == half + 1 else "cross"


def hidden(params, idx, *, layer_ids, n_published: int, mb_per_layer: int,
           n_head: int, n_kv_head: int, window: int, state: int,
           eps: float, dtype=jnp.float32, wrong=()):
    """(the last norm's output (batch, seq, hidden), the tied table), in
    `dtype`."""
    if set(wrong) - set(WRONG):
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    table = params["embed_tokens"]["embedding"]
    x = table[idx]
    half = n_published // 2

    def block(x, handed, p, kv_params, i):
        kind = kind_of(i, n_published, mb_per_layer)
        u = _layer_norm(x, p["input_norm"], eps)
        if kind == "mamba":
            out, y = mamba_mixer(u, p["mamba"], state=state, wrong=wrong)
            if i == half:
                handed = {**handed, "m": y}
        elif kind == "gmu":
            out = gated_memory_unit(u, handed["m"], p["gmu"], wrong=wrong)
        else:
            kv = (handed["k"], handed["v"]) if kind == "cross" else None
            if kind == "cross" and "cross_own_kv" in wrong:
                w, bias = (kv_params["qkv_proj"][name]
                           for name in ("kernel", "bias"))
                cut = w.shape[1] - handed["k"].shape[-1] * 2
                kv = jnp.split(u @ w[:, cut:] + bias[cut:], 2, axis=-1)
            out, (k, v) = diff_attention(
                u, p["attention"], layer=i, n_head=n_head,
                n_kv_head=n_kv_head, eps=eps, kv=kv, wrong=wrong,
                window=window if kind == "window"
                and "no_window" not in wrong else None)
            if i == half + 1:
                handed = {**handed, "k": k, "v": v}
        x = x + out.astype(x.dtype)
        return x + _swiglu(_layer_norm(x, p["post_mixer_norm"], eps),
                           p["feed_forward"]), handed

    handed = {}
    kv_at = list(layer_ids).index(half + 1) if half + 1 in layer_ids else None
    kv_params = params[f"layers_{kv_at}"]["attention"] \
        if kv_at is not None else None
    for place, i in enumerate(layer_ids):
        x, handed = jax.checkpoint(functools.partial(block, i=i))(
            x, handed, params[f"layers_{place}"], kv_params)
    return _layer_norm(x, params["norm"], eps), table


def forward(params, idx, **sizes):
    """Logits (batch, seq, vocab)."""
    x, table = hidden(params, idx, **sizes)
    return x @ table.T


def loss(params, batch, **sizes):
    """Mean next-token cross-entropy: the total the program's step
    reports as `loss`."""
    x, table = hidden(params, batch["input_ids"], **sizes)

    def rows(x, labels):
        logits = x @ table.T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return (lse - jnp.take_along_axis(logits, labels, axis=-1)[..., 0]
                )[..., None]

    return _by_rows(rows, x, batch["labels"][..., None]).mean().astype(
        jnp.float32)
