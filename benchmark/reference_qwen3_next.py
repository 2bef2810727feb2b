"""Plain reference: the `qwen3_next` stack (periods of gated delta-rule
mixers and one output-gated attention, an expert layer beside a gated
shared expert behind every mixer, zero-centred norms, an untied head)
forward pass and training loss in `jax.numpy`, float32.

Follows `Qwen/Qwen3-Next-80B-A3B-Instruct` config.json (`model_type:
qwen3_next`) and the gated delta rule (Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464) in the form of the family's published code.  Every
symbol below is a key of that config.

    N(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * (1 + w)      w drawn at 0
    x = E[ids]
    for i in range(num_hidden_layers):
        x = x + mixer_i(N(x))     full attention iff (i + 1) %
                                  full_attention_interval == 0
        x = x + experts(N(x))
    logits = N(x) @ W_head                                     untied
    loss   = mean next-token cross-entropy
             + aux_weight * mean over layers of the balance term

Gated delta-rule mixer, Hk = `linear_num_key_heads`, H =
`linear_num_value_heads` (rep = H / Hk), dk = `linear_key_head_dim`,
dv = `linear_value_head_dim`:

    q~ = u Wq (Hk dk)   k~ = u Wk (Hk dk)   v~ = u Wv (H dv)   z = u Wz (H dv)
    b = u Wb (H)        a = u Wa (H)
    q, k, v = silu(causal depthwise conv of q~ | k~ | v~)
              `linear_conv_kernel_dim` taps a channel, no bias
    q^ = q / sqrt(|q|^2 + 1e-6) / sqrt(dk)    k^ = k / sqrt(|k|^2 + 1e-6)
    beta_t  = sigmoid(b_t)                    NO factor 2
    alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))     a VALUE head
    value head j = (g, r), g = j // rep, reads key head g:
    S_t = alpha_t S_{t-1} + beta_t k^_t (v_t - alpha_t S_{t-1}^T k^_t)^T
    o_t = S_t^T q^_t                          S in R^{dk x dv}, S_0 = 0
    y_t = RMSNorm_dv(o_t) * w * silu(z_t)     PLAIN norm: one (dv,) scale
    out = concat_heads(y) Wo

the recurrence ONE STEP AT A TIME (`lax.scan` over time) with the state
laid out (key head, rank in its group): a key head's q and k are used
where they lie and never repeated; no chunk, no solve, no kernel.

Gated attention, `num_attention_heads` query heads over
`num_key_value_heads` kv heads of d = `head_dim`:

    [q | gate] = u Wq    a head's 2 d lanes: the query, then the gate
    k = u Wk   v = u Wv  no bias
    q = N_d(q)  k = N_d(k)        a head's lanes, one scale, BEFORE RoPE
    the FIRST d * partial_rotary_factor lanes of a head rotated by halves
    (pair i with i + rot / 2; theta `rope_theta`), the rest unrotated
    y = causal softmax(q k^T / sqrt d) v * sigmoid(gate)    lane by lane
    out = concat_heads(y) Wo

Expert layer: r = softmax(u Wr) over all `num_experts` the ROUTER has
(float32); the `num_experts_per_tok` largest, renormalised to sum 1
(`norm_topk_prob`); SwiGLU experts of `moe_intermediate_size`;

    out = sum_{e chosen, held} g_e E_e(u) + sigmoid(u w_s) E_shared(u)

THE SHARE: the parameter tree holds some experts, numbers `first_expert
..` of those the router scores; what the absent experts would have added
is left out, the shared expert and its gate are whole.  The balance term
of a layer is experts x sum_e (share of the tokens that chose e) x (mean
probability of e) over ALL the router's experts.

`wrong` names ONE equation to get wrong (`WRONG`), for the controls that
show the cell's tolerances tell a wrong equation from the right one:
"no_output_gate" (the attention's gate dropped), "head_gate" (the gate's
mean a head, one number a head), "full_rotary" (all d lanes rotated),
"beta_x2" (beta = 2 sigmoid(b)), "value_grouping" (value head j reads key
head j % Hk), "plain_norm" (`w` for `1 + w` in every zero-centred norm),
"shared_gate" (the shared expert ungated), "topk_8" (8 experts a token).
`dtype` bfloat16 is the control one precision below: everything in it
but the float32 islands written below (the router, the recurrence's
state and gates).

No kernel, no chunked form, no sort, no `ragged_dot`, no import from the
program's model code (the causal convolution is the other hybrids'
references').  It reads the parameter tree by the names the
program's `Qwen3Next` gives its leaves, which is the only thing it shares
with it.

What changes no number, only what is compiled and kept: each layer under
`jax.checkpoint`; the recurrence in blocks of `_TIME_BLOCK` steps, each
under `jax.checkpoint`; attention a block of `_QUERY_BLOCK` queries at a
time (`lax.map`), each under `jax.checkpoint`; every held expert on every
token under its gate, in a `lax.scan`; the cross-entropy over
`_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.

Parity: none — the reference repository has no linear-attention or
expert-layer model; this is the published architecture written out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference_nemotron_h import _causal_conv

_QUERY_BLOCK = 256
_TIME_BLOCK = 128
_LOSS_BLOCK = 2048
_L2_EPS = 1e-6
WRONG = ("no_output_gate", "head_gate", "full_rotary", "beta_x2",
         "value_grouping", "plain_norm", "shared_gate", "topk_8")


def _norm(x, scale, eps, wrong=None):
    """The zero-centred RMSNorm: `* (1 + w)`."""
    gain = scale if wrong == "plain_norm" else 1.0 + scale
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


# ---------------------------------------------------- the gated delta rule

def delta_recurrence(q, k, v, alpha, beta):
    """The gated delta rule, one step at a time.  q, k (b, t, G, dk), one
    a KEY head; v (b, t, G, R, dv), alpha, beta (b, t, G, R): value head
    (g, r) reads key head g.  Returns o (b, t, G, R, dv).  The state is
    float32 whatever the operands are."""
    bsz, t, g, r, dv = v.shape
    dk = k.shape[-1]

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = (x.astype(jnp.float32) for x in inp)
        state = a_t[..., None, None] * state
        read = jnp.einsum("bgrkv,bgk->bgrv", state, k_t)
        state = state + b_t[..., None, None] \
            * k_t[:, :, None, :, None] * (v_t - read)[..., None, :]
        return state, jnp.einsum("bgrkv,bgk->bgrv", state, q_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    blk = math.gcd(t, _TIME_BLOCK)
    time_first = tuple(
        x.swapaxes(0, 1).reshape(t // blk, blk, bsz, *x.shape[2:])
        for x in (q, k, v, alpha, beta))
    _, o = jax.lax.scan(block, jnp.zeros((bsz, g, r, dk, dv), jnp.float32),
                        time_first)
    return o.reshape(t, bsz, g, r, dv).swapaxes(0, 1).astype(v.dtype)


def linear_attention(u, p, *, key_heads, value_heads, key_dim, value_dim,
                     eps, wrong=None):
    """u (b, t, hidden) -> (b, t, hidden)."""
    b, t, _ = u.shape
    rep = value_heads // key_heads
    qk = key_heads * key_dim
    kernel = p["conv_kernel"]

    def conv(name, lo, hi):  # no bias
        return jax.nn.silu(_causal_conv(u @ p[name]["kernel"],
                                        kernel[:, lo:hi], 0.0))

    q, k = conv("q_proj", 0, qk), conv("k_proj", qk, 2 * qk)
    v = conv("v_proj", 2 * qk, kernel.shape[1])
    z = u @ p["g_proj"]["kernel"]
    q = q.reshape(b, t, key_heads, key_dim)
    k = k.reshape(b, t, key_heads, key_dim)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _L2_EPS) \
        / math.sqrt(key_dim)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _L2_EPS)
    u32 = u.astype(jnp.float32)  # the gates: float32 whatever u is
    beta = jax.nn.sigmoid(u32 @ p["b_proj"]["kernel"].astype(jnp.float32))
    if wrong == "beta_x2":
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        u32 @ p["a_proj"]["kernel"].astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32)))

    # value head j -> (key head, rank): j = g * rep + r.  The control
    # reads key head j % Hk instead: j = r * Hk + g
    def grouped(x):  # (b, t, H, ...) -> (b, t, G, R, ...)
        if wrong == "value_grouping":
            return x.reshape(b, t, rep, key_heads, *x.shape[3:]).swapaxes(2, 3)
        return x.reshape(b, t, key_heads, rep, *x.shape[3:])

    def ungrouped(x):  # (b, t, G, R, dv) -> (b, t, H, dv)
        if wrong == "value_grouping":
            x = x.swapaxes(2, 3)
        return x.reshape(b, t, value_heads, value_dim)

    o = ungrouped(delta_recurrence(
        q, k, grouped(v.reshape(b, t, value_heads, value_dim)),
        grouped(alpha), grouped(beta)))
    # the mixer's own norm is PLAIN: * w, w drawn at 1
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * p["gate_norm_scale"]
    y = y * jax.nn.silu(z).reshape(b, t, value_heads, value_dim)
    return y.reshape(b, t, value_heads * value_dim) @ p["o_proj"]["kernel"]


# ------------------------------------------------------ the gated attention

def _rope(x, theta, rot):
    """x (b, t, heads, d): the FIRST `rot` lanes of a head rotated by
    halves (pair i with i + rot / 2), the rest as they are."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (f(ang)[None, :, None, :].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def attention(u, p, *, n_head, n_kv, theta, rotary, eps, wrong=None):
    """u (b, t, hidden) -> (b, t, hidden)."""
    b, t, _ = u.shape
    both = (u @ p["q_proj"]["kernel"]).reshape(b, t, n_head, -1)
    d = both.shape[-1] // 2
    q, gate = both[..., :d], both[..., d:]
    k = (u @ p["k_proj"]["kernel"]).reshape(b, t, n_kv, d)
    v = (u @ p["v_proj"]["kernel"]).reshape(b, t, n_kv, d)
    q = _norm(q, p["q_norm"]["scale"], eps, wrong)
    k = _norm(k, p["k_norm"]["scale"], eps, wrong)
    rot = d if wrong == "full_rotary" else int(d * rotary)
    q, k = _rope(q, theta, rot), _rope(k, theta, rot)
    rep = n_head // n_kv  # query heads g * rep .. read kv head g
    blk = math.gcd(t, _QUERY_BLOCK)

    @jax.checkpoint
    def one_block(first, q_blk):
        q_grp = q_blk.reshape(b, blk, n_kv, rep, d)
        att = jnp.einsum("bqgrd,bkgd->bgrqk", q_grp, k) / math.sqrt(d)
        rows = first + jnp.arange(blk)[:, None]
        att = jnp.where(jnp.arange(t)[None, :] <= rows, att, -jnp.inf)
        prob = jax.nn.softmax(att.astype(jnp.float32), -1).astype(v.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", prob, v).reshape(
            b, blk, n_head, d)

    y = jax.lax.map(lambda args: one_block(*args), (
        jnp.arange(t // blk) * blk,
        q.reshape(b, t // blk, blk, n_head, d).swapaxes(0, 1)))
    y = y.swapaxes(0, 1).reshape(b, t, n_head, d)
    if wrong == "head_gate":
        gate = jnp.broadcast_to(gate.mean(-1, keepdims=True), gate.shape)
    if wrong != "no_output_gate":
        y = y * jax.nn.sigmoid(gate)
    return y.reshape(b, t, n_head * d) @ p["o_proj"]["kernel"]


# --------------------------------------------------------- the expert layer

def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


@jax.checkpoint
def _one_expert(u, w_gate, w_up, w_down, gate):
    return _swiglu(u, w_gate, w_up, w_down) * gate[:, None]


def expert_layer(u, p, *, top_k, first_expert, wrong=None, balance=False):
    """u (tokens, hidden) -> the held experts' part plus the gated shared
    expert; with `balance` (that, the layer's load-balancing term over
    ALL the router's experts)."""
    r = jax.nn.softmax(u.astype(jnp.float32)
                       @ p["router"]["kernel"].astype(jnp.float32), -1)
    _, chosen = jax.lax.top_k(r, 8 if wrong == "topk_8" else top_k)
    member = (chosen[..., None] == jnp.arange(r.shape[-1])).any(-2)
    term = r.shape[-1] * jnp.sum(member.mean(0) * r.mean(0))
    gates = jnp.where(member, r, 0.0)
    gates = (gates / gates.sum(-1, keepdims=True)).astype(u.dtype)
    held = p["experts_w_in"].shape[0]

    def add_expert(acc, ew):
        return acc + _one_expert(u, *ew), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (p["experts_w_gate"], p["experts_w_in"], p["experts_w_down"],
         gates[:, first_expert:first_expert + held].T))
    shared = _swiglu(u, p["shared_gate_proj"]["kernel"],
                     p["shared_up_proj"]["kernel"],
                     p["shared_down_proj"]["kernel"])
    if wrong != "shared_gate":  # ONE gate a token, float32
        shared = shared * jax.nn.sigmoid(
            u.astype(jnp.float32)
            @ p["shared_expert_gate"]["kernel"].astype(jnp.float32)
        ).astype(u.dtype)
    out = out + shared
    return (out, term) if balance else out


# ------------------------------------------------------------------ the stack

def layer_kinds(n_layer: int, interval: int) -> tuple:
    return tuple("full_attention" if (i + 1) % interval == 0
                 else "linear_attention" for i in range(n_layer))


def forward(params, idx, *, n_layer: int, interval: int, n_head: int,
            n_kv: int, theta: float, rotary: float, key_heads: int,
            value_heads: int, key_dim: int, value_dim: int, top_k: int,
            first_expert: int, eps: float, dtype=jnp.float32, wrong=None):
    """(the last norm's output (batch, seq, hidden), the sum over the
    layers of the router's balance term, the head) in `dtype`.  float32
    is the reference; bfloat16 is the control one precision below."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape

    def block(x, p, kind):
        h = _norm(x, p["input_norm"]["scale"], eps, wrong)
        if kind == "linear_attention":
            out = linear_attention(
                h, p["linear_attention"], key_heads=key_heads,
                value_heads=value_heads, key_dim=key_dim,
                value_dim=value_dim, eps=eps, wrong=wrong)
        else:
            out = attention(h, p["attention"], n_head=n_head, n_kv=n_kv,
                            theta=theta, rotary=rotary, eps=eps, wrong=wrong)
        x = x + out
        u = _norm(x, p["post_attn_norm"]["scale"], eps, wrong)
        out, term = expert_layer(
            u.reshape(b * t, c), p["feed_forward"], top_k=top_k,
            first_expert=first_expert, wrong=wrong, balance=True)
        return x + out.reshape(b, t, c), term.astype(jnp.float32)

    balance = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(layer_kinds(n_layer, interval)):
        x, term = jax.checkpoint(block, static_argnums=2)(
            x, params[f"layers_{i}"], kind)
        balance = balance + term
    return _norm(x, params["norm"]["scale"], eps, wrong), balance, \
        params["lm_head"]["kernel"]


def loss(params, batch, *, aux_weight: float = 0.0, **sizes):
    """Mean next-token cross-entropy + aux_weight x the layers' MEAN
    balance term: the total the program's step reports as `loss`."""
    x, balance, head = forward(params, batch["input_ids"], **sizes)
    b, t, c = x.shape
    blk = math.gcd(b * t, _LOSS_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        rows, labels = xl
        logits = rows @ head
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return (lse - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]).sum()

    ce = (jax.lax.map(one_block, (x.reshape(-1, blk, c),
                                  batch["labels"].reshape(-1, blk))).sum()
          / (b * t)).astype(jnp.float32)
    return ce + aux_weight * balance / sizes["n_layer"]
