"""Plain reference: SDAR-30B-A3B-Chat (`JetLM/SDAR-30B-A3B-Chat`
config.json, `model_type` `sdar_moe`) — a Qwen3-MoE trunk's forward pass
and its BLOCK-DIFFUSION training loss (BD3-LMs, arXiv:2503.09573,
sections 3-4; SDAR, arXiv:2510.06303) in `jax.numpy`, float32.

    a sequence x_0 .. x_{T-1}, blocks of L (block_length), b(i) = i // L
    t_b ~ eps + (1 - eps) U(0, 1)        one a block and sequence
    m_i ~ Bernoulli(t_{b(i)})
    xn_i = MASK if m_i else x_i
    ids = [x ; xn]                       2T positions,
    position ids [0 .. T-1 ; 0 .. T-1]
    x = E[ids]
    for l in range(num_hidden_layers):
        h = RMSNorm(x)                                  eps rms_norm_eps
        q, k, v = h Wq, h Wk, h Wv      num_attention_heads x head_dim,
                                        num_key_value_heads x head_dim
        q_a = RMSNorm_q(q_a), k_g = RMSNorm_k(k_g)      over a head's lanes,
                                        ONE scale for all heads
        q, k rotated by POSITION ID: pairs by halves, rope_theta
        K(clean i)  = { clean j : b(j) <= b(i) }
        K(noised i) = { clean j : b(j) < b(i) } u { noised j : b(j) = b(i) }
        x = x + concat_a(softmax_{K(i)}(q_a k^T / sqrt(head_dim)) v) Wo
        u = RMSNorm(x)
        r = softmax(u W_r) over num_experts, float32
        chosen = the num_experts_per_tok largest; g = r[chosen] / sum
        x = x + sum_{e chosen, HELD here} g_e (silu(u W1) * (u W3)) W2
    logits = RMSNorm(x[noised copy]) W_head             T rows, untied
    loss = (1 / T) sum_i m_i / t_{b(i)} * -log softmax(logits_i)[x_i]
           (+ aux_weight x mean_l num_experts sum_e f_e(l) rbar_e(l): the
           load-balancing term over ALL the router's experts and both
           copies; the cell's file says why it assumes one)

The draw is data, as the batch is: a sequence's key is
`fold_in(key(noise_seed), h)`, h = sum_i x_i (i * 2654435761 + 40503)
mod 2^32, t from the key's first half and m from its second (`draw`, a
few lines of `jax.random` of this file's own; tests/test_sdar.py holds
it to the program's bit for bit).

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.  The vocabulary slice is
a smaller table and head, MASK its last row.

No kernel, no tiles, no plan, no import from the program's model code.
The mask is a dense boolean built from the definitions above, a block
of queries at a time; the attention a masked softmax over all 2T keys;
every held expert is applied to every token under a membership mask
found by counting.  It reads the parameter tree by the names the
program's `SDAR` gives its leaves, which is the only thing it shares
with it.

What changes no number, only what is compiled and kept, so that 16,384
positions fit beside the training state on one chip: each layer under
`jax.checkpoint`; mask and attention a block of `_QUERY_BLOCK` queries
at a time (`lax.map`, each body under `jax.checkpoint`); the experts in
a `lax.scan`; head and loss over `_LOSS_BLOCK` tokens at a time.  Call
under `jax.default_matmul_precision("highest")`.

The controls: `wrong` names ONE equation to get wrong (`WRONG`):
"token_causal" (the clean copy's mask read as token-causal, j <= i),
"leak" (a noised query also sees its own block's CLEAN keys),
"positions" (the noised copy's position ids run on from T), "weight"
(the 1 / t left out: the mean over the masked tokens' plain losses / T),
"qk_norm" (the per-head norms dropped); `dtype` bfloat16 is one
precision below, EVERYTHING in it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 64
_LOSS_BLOCK = 2048
WRONG = ("token_causal", "leak", "positions", "weight", "qk_norm")


def draw(ids, noise_seed: int, block_length: int, eps: float):
    """(t (b, T / L), m (b, T) bool) of a batch of sequences."""
    seq = ids.shape[1]
    place = jnp.arange(seq, dtype=jnp.uint32) * jnp.uint32(2654435761) \
        + jnp.uint32(40503)
    ts, ms = [], []
    for row in ids:
        h = (row.astype(jnp.uint32) * place).sum(dtype=jnp.uint32)
        t_key, m_key = jax.random.split(
            jax.random.fold_in(jax.random.key(noise_seed), h))
        t = eps + (1.0 - eps) * jax.random.uniform(
            t_key, (seq // block_length,), jnp.float32)
        ts.append(t)
        ms.append(jax.random.uniform(m_key, (seq,), jnp.float32)
                  < jnp.repeat(t, block_length))
    return jnp.stack(ts), jnp.stack(ms)


def kept(rows, t: int, block_length: int, wrong=None):
    """(len(rows), 2t) bool: whether query `rows[i]` of `[clean ;
    noised]` sees key j."""
    cols = jnp.arange(2 * t)
    q_noised, k_noised = (rows >= t)[:, None], (cols >= t)[None, :]
    q_at, k_at = (rows % t)[:, None], (cols % t)[None, :]
    q_blk, k_blk = q_at // block_length, k_at // block_length
    clean = k_at <= q_at if wrong == "token_causal" else k_blk <= q_blk
    before = k_blk <= q_blk if wrong == "leak" else k_blk < q_blk
    return jnp.where(
        q_noised,
        jnp.where(k_noised, k_blk == q_blk, before),
        ~k_noised & clean)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, positions, theta):
    """x (b, s, heads, d), positions (s,): rotate (x1, x2) = the two
    HALVES of d by position x theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = (f(ang)[None, :, None, :].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, *, n_head, n_kv, block_length, theta, eps, wrong=None):
    """h (b, 2t, hidden) -> (b, 2t, hidden)."""
    b, s, _ = h.shape
    t = s // 2
    blk = math.gcd(s, _QUERY_BLOCK)
    q = (h @ p["q_proj"]["kernel"]).reshape(b, s, n_head, -1)
    k = (h @ p["k_proj"]["kernel"]).reshape(b, s, n_kv, -1)
    v = (h @ p["v_proj"]["kernel"]).reshape(b, s, n_kv, -1)
    d = q.shape[-1]
    if wrong != "qk_norm":
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    positions = jnp.arange(s) if wrong == "positions" else jnp.arange(s) % t
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = n_head // n_kv  # query heads g * rep .. read kv head g

    @jax.checkpoint
    def one_block(first, q_blk):
        mask = kept(first + jnp.arange(blk), t, block_length, wrong)
        att = jnp.einsum("bqgrd,bkgd->bgrqk",
                         q_blk.reshape(b, blk, n_kv, rep, d), k) \
            / math.sqrt(d)
        prob = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", prob, v).reshape(
            b, blk, n_head * d)

    out = jax.lax.map(lambda args: one_block(*args), (
        jnp.arange(s // blk) * blk,
        q.reshape(b, s // blk, blk, n_head, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, s, -1) @ p["o_proj"]["kernel"]


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
    return ((jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down) * gate[:, None]


def expert_layer(u, p, *, top_k, first_expert):
    """u (tokens, hidden) -> (the held experts' part, the layer's
    load-balancing term: experts x sum_e (share of the tokens that chose
    e) x (mean probability of e), over ALL the router's experts)."""
    r = jax.nn.softmax((u @ p["router"]["kernel"]).astype(jnp.float32), -1)
    member = jax.checkpoint(_top_k_member, static_argnums=1)(r, top_k)
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
    return out, term


def forward(params, both, *, n_layer: int, n_head: int, n_kv: int,
            block_length: int, theta: float, top_k: int, first_expert: int,
            eps: float, dtype=jnp.float32, wrong=None):
    """`both` (b, 2t) ids of `[clean ; noised]` -> (the last norm's output
    on the NOISED copy (b, t, hidden), the sum over the layers of the
    router's balance term, the head) in `dtype`."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][both]
    b, s, c = x.shape

    def block(x, p):
        h = _rms_norm(x, p["input_norm"], eps)
        x = x + attention(h, p["attention"], n_head=n_head, n_kv=n_kv,
                          block_length=block_length, theta=theta, eps=eps,
                          wrong=wrong)
        u = _rms_norm(x, p["post_attn_norm"], eps).reshape(b * s, c)
        out, balance = expert_layer(u, p["feed_forward"], top_k=top_k,
                                    first_expert=first_expert)
        return x + out.reshape(b, s, c), balance.astype(jnp.float32)

    balance = jnp.zeros((), jnp.float32)
    for i in range(n_layer):
        x, term = jax.checkpoint(block)(x, params[f"layers_{i}"])
        balance = balance + term
    return _rms_norm(x[:, s // 2:], params["norm"], eps), balance, \
        params["lm_head"]["kernel"]


def loss(params, batch, *, noise_seed: int, block_length: int,
         noise_eps: float, mask_id: int, aux_weight: float = 0.0, **sizes):
    """(1 / T) sum_i m_i / t_{b(i)} nll_i over the noised copy, the mean
    over the sequences, + aux_weight x the layers' MEAN balance term: the
    total the program's step reports as `loss`."""
    ids = batch["input_ids"]
    t, masked = draw(ids, noise_seed, block_length, noise_eps)
    both = jnp.concatenate([ids, jnp.where(masked, mask_id, ids)], axis=1)
    x, balance, head = forward(params, both, block_length=block_length,
                               **sizes)
    weights = masked.astype(jnp.float32)
    if sizes.get("wrong") != "weight":
        weights = weights / jnp.repeat(t, block_length, axis=1)
    b, seq, c = x.shape
    blk = math.gcd(b * seq, _LOSS_BLOCK)

    @jax.checkpoint
    def one_block(xlw):
        rows, labels, w = xlw
        logits = rows @ head
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return ((lse - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]) * w).sum()

    total = (jax.lax.map(one_block, (
        x.reshape(-1, blk, c), ids.reshape(-1, blk),
        weights.reshape(-1, blk))).sum() / (b * seq)).astype(jnp.float32)
    return total + aux_weight * balance / sizes["n_layer"]
