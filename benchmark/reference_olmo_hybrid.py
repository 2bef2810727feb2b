"""Plain reference: the `olmo_hybrid` stack (a gated delta-rule mixer or
a full-attention mixer AND a SwiGLU in every block, Olmo 2's reordered
norm, an untied head) forward pass and training loss in `jax.numpy`,
float32.

Follows `allenai/Olmo-Hybrid-7B` config.json (`model_type: olmo_hybrid`)
and the gated delta rule (Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464) in the form of its public implementations, whose key
names the config carries.  Every symbol below is a key of that config.

    x = E[ids]
    for kind in layer_types:          "linear_attention" | "full_attention"
        x = x + RMSNorm(mixer_kind(x))         eps rms_norm_eps, own scale
        x = x + RMSNorm(W_down (silu(W_gate x) * (W_up x)))
    logits = RMSNorm(x) @ W_head               untied
    loss   = mean next-token cross-entropy, nothing added

`full_attention`: `num_attention_heads` heads of hidden / heads, no
bias, `q = RMSNorm(x Wq)`, `k = RMSNorm(x Wk)` over the WHOLE projection
(all heads' features, one scale each) before the split into heads, no
rotary or other position term, causal `softmax(q k^T / sqrt(d)) v`, Wo.

`linear_attention`, H = `linear_num_key_heads` = `linear_num_value_heads`
heads HELD, dk = `linear_key_head_dim`, dv = `linear_value_head_dim`:

    q~ = x Wq   k~ = x Wk   v~ = x Wv   z = x Wg   a = x Wa   b = x Wb
    q, k, v = silu(causal depthwise conv of q~ | k~ | v~)
              `linear_conv_kernel_dim` taps a channel, no bias
    q^ = q / sqrt(|q|^2 + 1e-6) / sqrt(dk)    k^ = k / sqrt(|k|^2 + 1e-6)
    beta_t  = 2 sigmoid(b_t)                  2: `linear_allow_neg_eigval`
    alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t k^_t (v_t - alpha_t S_{t-1}^T k^_t)^T
    o_t = S_t^T q^_t                          S in R^{dk x dv}, S_0 = 0
    y_t = RMSNorm_dv(o_t) * silu(z_t)         one (dv,) scale for all heads
    out = concat_heads(y) Wo

the recurrence ONE STEP AT A TIME (`lax.scan` over time): it knows no
chunk, no triangular solve, no chunk-to-chunk transition.
`delta_chunk_size` is how the program's chunked form is computed, not
what it computes: nothing here reads it.

`wrong` names ONE term to get wrong, for the controls that show the
cell's tolerances tell a wrong equation from the right one (PERF.md
section 6, PR 47): "beta_factor" (the write gate without its 2),
"decay" (alpha = 1), "k_norm" (k not normalised), "correction"
(S_t = alpha S + beta k v^T: a plain gated linear attention),
"output_gate" (no silu(z)).  None is the reference.

No kernel, no chunked form, no import from the program's model code (the
RMSNorm, the SwiGLU and the causal convolution are the other hybrids'
references').  It reads the parameter tree by the names the program's `OlmoHybrid` gives
its leaves, which is the only thing it shares with it.

Departures from the published model: none known in the equations; what
the catalog row does not fix (the reordered norm, the QK-norm's extent,
no rotation for a null `rope_theta`, the gates' forms, both eps, the one
shared output-norm scale, no convolution bias) is the family's published
code as the configuration file lists it under `assumed`.

What changes no number, only what is compiled and kept: each layer
under `jax.checkpoint`; the recurrence in blocks of `_TIME_BLOCK` steps,
each block under `jax.checkpoint` (8192 carried states of 15 x 96 x 192
float32 would be 9 GB; 64 block boundaries and one block's 128 are 0.2);
attention one head and one block of `_QUERY_BLOCK` queries at a time
(`lax.map` over both), each under `jax.checkpoint`.  Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference_granite_hybrid import _swiglu
from benchmark.reference_nemotron_h import _causal_conv, _rms_norm

_QUERY_BLOCK = 1024
_TIME_BLOCK = 128
_L2_EPS = 1e-6
WRONG = ("beta_factor", "decay", "k_norm", "correction", "output_gate")


def delta_recurrence(q, k, v, alpha, beta, correct=True):
    """The gated delta rule, one step at a time.  q, k (b, t, H, dk);
    v (b, t, H, dv); alpha, beta (b, t, H).  Returns o (b, t, H, dv).
    The state is float32 whatever the operands are."""
    bsz, t, h, dk = k.shape
    dv = v.shape[-1]

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = (x.astype(jnp.float32) for x in inp)
        state = a_t[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t) if correct else 0.0
        state = state + b_t[..., None, None] * k_t[..., :, None] \
            * (v_t - read)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    blk = math.gcd(t, _TIME_BLOCK)
    time_first = tuple(
        x.swapaxes(0, 1).reshape(t // blk, blk, bsz, *x.shape[2:])
        for x in (q, k, v, alpha, beta))
    _, o = jax.lax.scan(block, jnp.zeros((bsz, h, dk, dv), jnp.float32),
                        time_first)
    return o.reshape(t, bsz, h, dv).swapaxes(0, 1).astype(v.dtype)


def linear_attention(x, p, *, heads, key_dim, value_dim, eps, wrong=None):
    """x (b, t, hidden) -> (b, t, hidden): the HELD heads' part."""
    b, t, _ = x.shape
    qk = heads * key_dim
    kernel = p["conv_kernel"]

    def conv(name, lo, hi):  # no bias
        return jax.nn.silu(_causal_conv(x @ p[name]["kernel"],
                                        kernel[:, lo:hi], 0.0))

    q, k = conv("q_proj", 0, qk), conv("k_proj", qk, 2 * qk)
    v = conv("v_proj", 2 * qk, kernel.shape[1])
    z = x @ p["g_proj"]["kernel"]
    q = q.reshape(b, t, heads, key_dim)
    k = k.reshape(b, t, heads, key_dim)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _L2_EPS) \
        / math.sqrt(key_dim)
    if wrong != "k_norm":
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _L2_EPS)
    beta = jax.nn.sigmoid(x @ p["b_proj"]["kernel"])
    if wrong != "beta_factor":
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        x @ p["a_proj"]["kernel"] + p["dt_bias"]))
    if wrong == "decay":
        alpha = jnp.ones_like(alpha)
    o = delta_recurrence(q, k, v.reshape(b, t, heads, value_dim), alpha,
                         beta, correct=wrong != "correction")
    y = _rms_norm(o, p["gate_norm_scale"], eps)
    if wrong != "output_gate":
        y = y * jax.nn.silu(z).reshape(b, t, heads, value_dim)
    return y.reshape(b, t, heads * value_dim) @ p["o_proj"]["kernel"]


def attention(x, p, *, n_head, eps):
    """x (b, t, hidden) -> (b, t, hidden): a masked softmax over heads of
    hidden / n_head, q and k normed over the whole projection first."""
    b, t, _ = x.shape
    d = p["q_proj"]["kernel"].shape[1] // n_head
    blk = math.gcd(t, _QUERY_BLOCK)
    q = _rms_norm(x @ p["q_proj"]["kernel"], p["q_norm"]["scale"], eps)
    k = _rms_norm(x @ p["k_proj"]["kernel"], p["k_norm"]["scale"], eps)
    q = q.reshape(b, t // blk, blk, n_head, d)
    k = k.reshape(b, t, n_head, d).transpose(2, 0, 1, 3)  # (heads, b, t, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, n_head, d) \
        .transpose(2, 0, 1, 3)

    @jax.checkpoint
    def one_block(q_blk, first, k_h, v_h):
        att = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) / math.sqrt(d)
        rows = first + jnp.arange(blk)[:, None]
        att = jnp.where(jnp.arange(t)[None, :] <= rows, att, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v_h)

    def one_head(hq):
        head, q_h = hq  # q_h (blocks, b, blk, d)
        return jax.lax.map(
            lambda fq: one_block(fq[1], fq[0], k[head], v[head]),
            (jnp.arange(t // blk) * blk, q_h))

    y = jax.lax.map(one_head, (jnp.arange(n_head),
                               q.transpose(3, 1, 0, 2, 4)))
    # (heads, blocks, b, blk, d) -> (b, t, heads * d)
    return y.transpose(2, 1, 3, 0, 4).reshape(b, t, n_head * d) \
        @ p["o_proj"]["kernel"]


def forward(params, idx, *, layer_types, n_head: int, linear_heads: int,
            key_dim: int, value_dim: int, eps: float, dtype=jnp.float32,
            wrong=None):
    """Logits (batch, seq, vocab) in `dtype`.  float32 is the reference;
    bfloat16 is the control one precision below, which the cell's
    tolerances must tell from it."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    mixers = {
        "linear_attention": lambda h, p: linear_attention(
            h, p["linear_attention"], heads=linear_heads, key_dim=key_dim,
            value_dim=value_dim, eps=eps, wrong=wrong),
        "full_attention": lambda h, p: attention(
            h, p["attention"], n_head=n_head, eps=eps),
    }

    def block(x, p, mix):
        x = x + _rms_norm(mix(x, p), p["post_mixer_norm"]["scale"], eps)
        return x + _rms_norm(_swiglu(x, p["feed_forward"]),
                             p["post_feedforward_norm"]["scale"], eps)

    x = params["embed_tokens"]["embedding"][idx]
    for i, kind in enumerate(layer_types):
        x = jax.checkpoint(functools.partial(block, mix=mixers[kind]))(
            x, params[f"layers_{i}"])
    x = _rms_norm(x, params["norm"]["scale"], eps)
    return x @ params["lm_head"]["kernel"]


def loss(params, batch, **sizes):
    """Mean next-token cross-entropy: the total the program's step
    reports as `loss`."""
    logits = forward(params, batch["input_ids"], **sizes)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return (lse - tgt).mean()
