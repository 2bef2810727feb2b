"""Plain reference: SmallThinker (`PowerInfer/SmallThinker-21BA3B-
Instruct`, arXiv:2507.20984) forward pass and training loss in
`jax.numpy`, float32.

Every symbol below is a key of the source's config.json.

    x = E[ids]
    for l in range(num_hidden_layers):
        h = RMSNorm_in(x)                         eps rms_norm_eps, own scale
        r = h W_r                                 moe_num_primary_experts
                                                  logits, no bias: the router
                                                  reads the block's normalised
                                                  INPUT, before the attention
        q, k, v = h Wq, h Wk, h Wv                num_attention_heads /
                                                  num_key_value_heads heads of
                                                  head_dim, no bias
        if rope_layout[l]: q, k = rope(q), rope(k)   rope_theta, rotate-half,
                                                  no scaling
        s[i, j] = q_i . k_j / sqrt(head_dim), kept iff j <= i and, where
            sliding_window_layout[l], i - j < sliding_window_size
        x = x + softmax(s) v Wo
        u = RMSNorm_post(x)
        chosen = the moe_num_active_primary_experts largest of r
        gate   = softmax over the chosen logits   (moe_primary_router_apply_
                                                  softmax; float32)
        x = x + sum_{e chosen, HELD here} gate_e (relu(u Wg_e) * (u Wu_e)) Wd_e
    logits = RMSNorm(x) W_head                    untied
    loss   = mean next-token cross-entropy (+ aux_weight x the mean over
             the layers of E sum_i f_i P_i where the file assumes one)

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.

No kernel, no tiles, no sort, no `ragged_dot`, no import from the
program's model code.  The attention's mask is written out from i and j;
EVERY held expert is applied to EVERY token under a membership mask,
which is found by counting (an expert is chosen when fewer than k beat
it; ties go to the lower index).  It reads the parameter tree by the
names the program's `SmallThinker` gives its leaves, which is the only
thing it shares with it.

Departures from the published model, each what the program computes:

- the catalog's "primary + secondary experts" has no key in config.json:
  one set of experts is built, the primary ones;
- where `aux_weight` is set the load-balancing term is OLMoE's (HF
  `load_balancing_loss_func` over all the router's experts, the mean
  over the layers): config.json has no key for one.

What changes no number, only what is compiled and kept, so that one
sequence of 16,384 tokens fits beside the training state on one chip:
each layer under `jax.checkpoint`; attention one head and one block of
`_QUERY_BLOCK` queries at a time (`lax.map` over both, each body under
`jax.checkpoint`: a (queries x keys) score matrix is 64 MB at 16,384
keys); the experts in a `lax.scan` over the stacked weights; head and
cross-entropy over `_LOSS_BLOCK` tokens at a time (the logits of 16,384
tokens are 1.2 GB a copy).  Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 1024
_LOSS_BLOCK = 2048


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


def attention(x, p, *, n_head, n_kv_head, rope, theta, window):
    """x (b, t, hidden) -> (b, t, hidden): a masked softmax, query head h
    reading key/value head h // (n_head / n_kv_head).  `window` None: a
    query sees every key at or before it."""
    b, t, _ = x.shape
    d = p["q_proj"]["kernel"].shape[1] // n_head
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, n_head, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, n_kv_head, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, n_kv_head, d)
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(b, t // blk, blk, n_head, d)
    rep = n_head // n_kv_head
    k, v = (a.transpose(2, 0, 1, 3) for a in (k, v))  # (kv heads, b, t, d)

    @jax.checkpoint
    def one_block(q_blk, first, k_h, v_h):
        att = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) / math.sqrt(d)
        i = first + jnp.arange(blk)[:, None]
        j = jnp.arange(t)[None, :]
        kept = j <= i
        if window is not None:
            kept = kept & (i - j < window)
        att = jnp.where(kept, att, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v_h)

    def one_head(hq):
        head, q_h = hq  # q_h (blocks, b, blk, d)
        return jax.lax.map(
            lambda fq: one_block(fq[1], fq[0], k[head // rep],
                                 v[head // rep]),
            (jnp.arange(t // blk) * blk, q_h))

    y = jax.lax.map(one_head, (jnp.arange(n_head),
                               q.transpose(3, 1, 0, 2, 4)))
    # (heads, blocks, b, blk, d) -> (b, t, heads * d)
    return y.transpose(2, 1, 3, 0, 4).reshape(b, t, n_head * d) \
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
    h = jax.nn.relu(u @ w_gate) * (u @ w_up)
    return (h @ w_down) * gate[:, None]


def expert_layer(u, h, p, *, top_k, first_expert):
    """u, h (tokens, c): the experts' input and the router's -> (the
    held experts' part of the layer's output, the load-balancing term
    over all the router's experts)."""
    logits = h @ p["router"]["kernel"]
    n_exp = logits.shape[-1]
    member = _top_k_member(logits, top_k)
    # the softmax over the chosen logits alone
    top = jnp.max(logits, axis=-1, keepdims=True)
    gates = jnp.where(member, jnp.exp(logits - top), 0.0)
    gates = gates / gates.sum(-1, keepdims=True)
    held = p["experts_w_in"].shape[0]

    def add_expert(acc, ew):
        w_gate, w_up, w_down, gate = ew
        return acc + _one_expert(u, w_gate, w_up, w_down, gate), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (p["experts_w_gate"], p["experts_w_in"], p["experts_w_down"],
         gates[:, first_expert:first_expert + held].T))
    f = member.astype(logits.dtype).mean(0)  # sums to top_k
    lb = n_exp * jnp.sum(f * jax.nn.softmax(logits, axis=-1).mean(0))
    return out, lb


def forward(params, idx, *, rope_layout, sliding_window_layout,
            window: int, n_head: int, n_kv_head: int, top_k: int,
            first_expert: int, eps: float, theta: float,
            dtype=jnp.float32):
    """(the last norm's output (batch, seq, hidden), the head's matrix,
    the mean load-balancing term) in `dtype`.  float32 is the reference;
    bfloat16 is the control one precision below — EVERYTHING in it,
    norms' statistics, router, softmaxes and cross-entropy too, where
    the program keeps those in float32 — which the cell's tolerances
    must tell from it."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape

    def block(x, p, rope, win):
        h = _rms_norm(x, p["input_norm"], eps)
        x = x + attention(h, p["attention"], n_head=n_head,
                          n_kv_head=n_kv_head, rope=rope, theta=theta,
                          window=win)
        u = _rms_norm(x, p["post_attn_norm"], eps)
        out, lb = expert_layer(u.reshape(b * t, c), h.reshape(b * t, c),
                               p["feed_forward"], top_k=top_k,
                               first_expert=first_expert)
        return x + out.reshape(b, t, c), lb

    lb_sum = 0.0
    for i, (rope, windowed) in enumerate(zip(rope_layout,
                                             sliding_window_layout)):
        x, lb = jax.checkpoint(block, static_argnums=(2, 3))(
            x, params[f"layers_{i}"], bool(rope),
            window if windowed else None)
        lb_sum = lb_sum + lb
    x = _rms_norm(x, params["norm"], eps)
    return x, params["lm_head"]["kernel"], lb_sum / len(rope_layout)


def loss(params, batch, *, aux_weight: float = 0.0, ce_dtype=None,
         **sizes):
    """Mean next-token cross-entropy (+ the load-balancing term where
    the file assumes one): the total the program's step reports as
    `loss`.  `ce_dtype` (a control's: None = `dtype`) is the type the
    head's logits are cast to before the cross-entropy: bfloat16
    everywhere BUT float32 there keeps the result off bfloat16's grid."""
    x, w_head, lb = forward(params, batch["input_ids"], **sizes)
    b, t, c = x.shape
    blk = math.gcd(b * t, _LOSS_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        rows, labels = xl
        logits = (rows @ w_head).astype(ce_dtype or rows.dtype)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return (lse - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]).sum()

    ce = jax.lax.map(one_block, (x.reshape(-1, blk, c),
                                 batch["labels"].reshape(-1, blk))).sum()
    return (ce / (b * t) + aux_weight * lb).astype(jnp.float32)
