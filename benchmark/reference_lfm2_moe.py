"""Plain reference: LFM2-24B-A2B (`LiquidAI/LFM2-24B-A2B` config.json,
`model_type` `lfm2_moe`) forward pass and training loss in `jax.numpy`,
float32.

Every symbol below is a key of the source's config.json.

    x = E[ids]
    for l in range(num_hidden_layers):
        h = RMSNorm_operator(x)                     eps norm_eps, own scale
        layer_types[l] == "conv":                   the gated short convolution
            [B | C | X] = h W_in                    hidden -> 3 x hidden
            z    = B * X
            c[t] = sum_{s < conv_L_cache} w[conv_L_cache - 1 - s] * z[t - s]
                   one filter a channel, its last tap on the current
                   step, zeros before the sequence's start; conv_bias
                   false: no bias; no activation
            x    = x + (C * c) W_out
        layer_types[l] == "full_attention":
            q, k, v = h Wq, h Wk, h Wv              num_attention_heads /
                                                    num_key_value_heads heads
                                                    of hidden / heads lanes
            q_h = RMSNorm_q(q_h), k_h = RMSNorm_k(k_h)   over a head's lanes,
                                                    ONE scale for all heads
            q, k rotated (rope_theta, rotate-half, no scaling)
            s[i, j] = q_i . k_j / sqrt(head size), kept iff j <= i; a kv
                      head serves heads / kv heads query heads
            x = x + concat_h(softmax(s_h) v_h) Wo
        u = RMSNorm_ffn(x)
        l <  num_dense_layers:  x = x + (silu(u W1) * (u W3)) W2
                                                    width intermediate_size
        l >= num_dense_layers:
            s      = sigmoid(u W_r)                 num_experts scores
            chosen = the num_experts_per_tok largest of s + b
                     (b: the expert bias of use_expert_bias; it chooses
                     and does not weigh)
            g      = s[chosen] / (sum s[chosen] + 1e-6)    (norm_topk_prob)
                     * routed_scaling_factor
            x = x + sum_{e chosen, HELD here} g_e swiglu_e(u)
                                                   width moe_intermediate_size;
                                                    no shared expert
    logits = RMSNorm(x) E^T                         tied
    loss   = mean next-token cross-entropy, nothing added

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.

Departures from the published model, each what the program computes and
each named in the configuration file's `assumed`: the table is tied (the
family's `tie_embedding`; the catalog's row has no key for it); the
router's scores are float32.

No kernel, no tiles, no sort, no `ragged_dot`, no import from the
program's model code.  The convolution is written out a tap at a time
from shifted copies; the attention's mask from i and j; EVERY held
expert is applied to EVERY token under a membership mask, which is found
by counting (an expert is chosen when fewer than k beat it; ties go to
the lower index).  It reads the parameter tree by the names the
program's `Lfm2` gives its leaves, which is the only thing it shares
with it.

What changes no number, only what is compiled and kept, so that one
sequence of 8,192 tokens fits beside the training state on one chip:
each layer under `jax.checkpoint`; attention one head and one block of
`_QUERY_BLOCK` queries at a time (`lax.map` over both, each body under
`jax.checkpoint`); the experts in a `lax.scan` over the stacked weights;
head and cross-entropy over `_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.

The controls: `wrong` names ONE equation to get wrong (`WRONG`):
"gate_b" (z = X: the input gate dropped), "gate_c" (y = c W_out: the
output gate dropped), "filter_reversed" (the filter's FIRST tap on the
current step), "qk_norm" (the per-head norms dropped), "bias_weighs" (the
gates are s + b at the chosen: the bias weighs); `dtype` bfloat16 is one
precision below, EVERYTHING in it, with `ce_dtype` the type of the logits
under the cross-entropy.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 1024
_LOSS_BLOCK = 2048
_GATE_NORM_EPS = 1e-6
WRONG = ("gate_b", "gate_c", "filter_reversed", "qk_norm", "bias_weighs")


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, theta):
    """x (b, t, heads, d): rotate (x1, x2) = the two HALVES of d."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def short_conv(h, p, *, wrong=None):
    """h (b, t, hidden) -> (b, t, hidden): the gated short convolution."""
    t, taps = h.shape[1], p["conv_kernel"].shape[0]
    gate_b, gate_c, x = jnp.split(h @ p["in_proj"]["kernel"], 3, axis=-1)
    z = x if wrong == "gate_b" else gate_b * x
    w = p["conv_kernel"][::-1] if wrong == "filter_reversed" \
        else p["conv_kernel"]
    c = jnp.zeros_like(z)
    for s in range(taps):  # z[t - s], zeros before the start
        shifted = jnp.pad(z, ((0, 0), (s, 0), (0, 0)))[:, :t]
        c = c + w[taps - 1 - s] * shifted
    return (c if wrong == "gate_c" else gate_c * c) @ p["out_proj"]["kernel"]


def attention(x, p, *, n_head, n_kv, theta, eps, wrong=None):
    """x (b, t, hidden) -> (b, t, hidden): grouped-query attention as a
    masked softmax, q and k normed a head before the rotation."""
    b, t, _ = x.shape
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, n_head, -1)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, n_kv, -1)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, n_kv, -1)
    d = q.shape[-1]
    if wrong != "qk_norm":
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(b, t // blk, blk, n_head, d)
    # a query head's own copy of its kv head: (heads, b, t, d)
    k, v = (jnp.repeat(a, n_head // n_kv, axis=2).transpose(2, 0, 1, 3)
            for a in (k, v))

    @jax.checkpoint
    def one_block(q_blk, first, k_h, v_h):
        att = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) / math.sqrt(d)
        i = first + jnp.arange(blk)[:, None]
        j = jnp.arange(t)[None, :]
        att = jnp.where(j <= i, att, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v_h)

    def one_head(hq):
        k_h, v_h, q_h = hq  # q_h (blocks, b, blk, d)
        return jax.lax.map(lambda fq: one_block(fq[1], fq[0], k_h, v_h),
                           (jnp.arange(t // blk) * blk, q_h))

    y = jax.lax.map(one_head, (k, v, q.transpose(3, 1, 0, 2, 4)))
    # (heads, blocks, b, blk, d) -> (b, t, heads * d)
    return y.transpose(2, 1, 3, 0, 4).reshape(b, t, -1) \
        @ p["o_proj"]["kernel"]


def _top_k_member(scores, k):
    """(tokens, E) bool: expert e is among the token's k largest scores —
    fewer than k experts beat it (a tie goes to the lower index)."""
    e = scores.shape[-1]
    mine, other = scores[:, :, None], scores[:, None, :]
    lower_index = jnp.arange(e)[None, :] < jnp.arange(e)[:, None]
    beats = (other > mine) | ((other == mine) & lower_index[None])
    return beats.sum(-1) < k


@jax.checkpoint
def _one_expert(u, w_gate, w_up, w_down, gate):
    return _swiglu(u, w_gate, w_up, w_down) * gate[:, None]


def expert_layer(u, p, *, top_k, routed_scaling, first_expert, wrong=None):
    """u (tokens, hidden) -> the held experts' part."""
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    biased = s + p["selection_bias"]
    member = _top_k_member(biased, top_k)
    gates = jnp.where(member, biased if wrong == "bias_weighs" else s, 0.0)
    gates = routed_scaling * gates \
        / (gates.sum(-1, keepdims=True) + _GATE_NORM_EPS)
    held = p["experts_w_in"].shape[0]

    def add_expert(acc, ew):
        return acc + _one_expert(u, *ew), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (p["experts_w_gate"], p["experts_w_in"], p["experts_w_down"],
         gates[:, first_expert:first_expert + held].T))
    return out


def dense_layer(u, p):
    return jax.checkpoint(_swiglu)(
        u, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"])


def forward(params, idx, *, layer_types, n_dense: int, n_head: int,
            n_kv: int, theta: float, top_k: int, routed_scaling: float,
            first_expert: int, eps: float, dtype=jnp.float32, wrong=None):
    """(the last norm's output (batch, seq, hidden), the table) in
    `dtype`.  float32 is the reference; bfloat16 is the control one
    precision below — EVERYTHING in it, norms' statistics, the gates and
    the convolution, the router, the softmax too, where the program keeps
    those in float32."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape

    def block(x, p, kind, dense):
        h = _rms_norm(x, p["operator_norm"], eps)
        if kind == "conv":
            x = x + short_conv(h, p["short_conv"], wrong=wrong)
        else:
            x = x + attention(h, p["attention"], n_head=n_head, n_kv=n_kv,
                              theta=theta, eps=eps, wrong=wrong)
        u = _rms_norm(x, p["ffn_norm"], eps).reshape(b * t, c)
        out = dense_layer(u, p["feed_forward"]) if dense else expert_layer(
            u, p["feed_forward"], top_k=top_k,
            routed_scaling=routed_scaling, first_expert=first_expert,
            wrong=wrong)
        return x + out.reshape(b, t, c)

    for i, kind in enumerate(layer_types):
        x = jax.checkpoint(block, static_argnums=(2, 3))(
            x, params[f"layers_{i}"], kind, i < n_dense)
    return _rms_norm(x, params["norm"], eps), \
        params["embed_tokens"]["embedding"]


def loss(params, batch, *, ce_dtype=None, **sizes):
    """Mean next-token cross-entropy: the total the program's step
    reports as `loss`.  `ce_dtype` (a control's: None = `dtype`) is the
    type the head's logits are cast to before the cross-entropy."""
    x, table = forward(params, batch["input_ids"], **sizes)
    b, t, c = x.shape
    blk = math.gcd(b * t, _LOSS_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        rows, labels = xl
        logits = (rows @ table.T).astype(ce_dtype or rows.dtype)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return (lse - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]).sum()

    ce = jax.lax.map(one_block, (x.reshape(-1, blk, c),
                                 batch["labels"].reshape(-1, blk))).sum()
    return (ce / (b * t)).astype(jnp.float32)
