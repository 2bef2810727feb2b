"""Plain reference: Keye-VL-2.0-30B-A3B's language model
(`Kwai-Keye/Keye-VL-2.0-30B-A3B` config.json, `model_type` `KeyeVL2`)
forward pass and training loss in `jax.numpy`, float32.

Every symbol below is a key of the source's config.json (`sa_config`'s
for the indexer).

    x = E[ids]
    for l in range(num_hidden_layers):
        h = RMSNorm(x)                                  eps rms_norm_eps
        q, k, v = h Wq, h Wk, h Wv      num_attention_heads x head_dim,
                                        num_key_value_heads x head_dim
        q_a = RMSNorm_q(q_a), k_g = RMSNorm_k(k_g)      over a head's lanes,
                                        ONE scale for all heads
        q, k rotated: pairs by halves, rope_theta; pair i takes its angle
            from the temporal stream for i < 16, the height stream for
            16 <= i < 40, the width stream behind (mrope_section
            [16, 24, 24]); text: the three are arange(T)
        hd = stop_gradient(h)
        qI = hd W_qI  -> indexer_num_heads x indexer_head_dim
        kI = LayerNorm(hd W_kI) -> ONE key (indexer_num_kv_heads 1)
        w  = hd W_w   -> a weight a head
        qI, kI rotated the same way over their own lanes
        I[t, s] = sum_j w[t, j] heads^-1/2 dim^-1/2 relu(qI[t, j] . kI[s])
        S_t = the min(topk, t + 1) keys s <= t of largest I[t, s]
              (`lax.top_k` a row: the lower s among equals)
        p_a[t, .] = softmax over S_t of q_a[t] . k[s] / sqrt(head_dim)
        x = x + concat_a(sum_{S_t} p_a[t, s] v[s]) Wo
        pbar = stop_gradient(mean_a p_a)
        L_I(l) = mean_t sum_{S_t} pbar (log pbar - log softmax_{S_t}(I))
        u = RMSNorm(x)
        r = softmax(u W_r) over num_experts, float32
        chosen = the num_experts_per_tok largest; g = r[chosen] / sum
        x = x + sum_{e chosen, HELD here} g_e (silu(u W1) * (u W3)) W2
    logits = RMSNorm(x) W_head                          untied
    loss = mean next-token cross-entropy + index_loss_weight sum_l L_I(l)
           (+ aux_weight x mean_l 128 sum_e f_e(l) rbar_e(l): a load-
           balancing term over ALL the router's experts, f_e the share of
           the tokens that chose e, rbar_e its mean probability — OLMoE's
           / HF's `load_balancing_loss_func`; the source has no key for
           one, and the cell's file says why it assumes one)

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.  The vocabulary slice is
a smaller table and head.

No kernel, no tiles, no threshold search, no import from the program's
model code.  The choice is `lax.top_k` over a query's whole masked row;
the attention a masked softmax over all T keys; every held expert is
applied to every token under a membership mask found by counting.  It
reads the parameter tree by the names the program's `Keye` gives its
leaves, which is the only thing it shares with it.

What changes no number, only what is compiled and kept, so that one
sequence of 16,384 tokens fits beside the training state on one chip:
each layer under `jax.checkpoint`; scores, choice, attention and KL a
block of `_QUERY_BLOCK` queries at a time (`lax.map`, each body under
`jax.checkpoint`); the experts in a `lax.scan`; head and cross-entropy
over `_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.

The controls: `wrong` names ONE equation to get wrong (`WRONG`): "dense"
(no choice: S_t every causal key), "topk_half" (topk / 2), "relu" (the
indexer's ReLU dropped), "head_weights" (w = 1), "index_term" (the loss
without L_I), "qk_norm" (the per-head norms dropped), "mrope_sections"
(the three streams' sections in another order: seen only under distinct
streams); `dtype` bfloat16 is one precision below, EVERYTHING in it.
`loss(..., parts=True)` returns (total, cross-entropy, mean L_I).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 64
_LOSS_BLOCK = 2048
WRONG = ("dense", "topk_half", "relu", "head_weights", "index_term",
         "qk_norm", "mrope_sections")


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, positions, theta, sections):
    """x (b, t, heads, d), positions (3, b, t): rotate (x1, x2) = the two
    HALVES of d, pair i by the stream its section names (the published
    sections count the pairs of a 128-lane head; a head of another width
    takes them in proportion)."""
    d = x.shape[-1]
    half, total = d // 2, sum(sections)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.concatenate([
        positions[i].astype(jnp.float32)[:, :, None]
        * inv[None, None, start:start + n]
        for i, (start, n) in enumerate(zip(
            [sum(sections[:i]) * half // total for i in range(3)],
            [n * half // total for n in sections]))], axis=-1)
    cos, sin = (f(ang)[:, :, None, :].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def chosen_keys(scores, first, topk):
    """(b, blk, T) bool: row i keeps the min(topk, first + i + 1) largest
    of its scores among keys j <= first + i; the lower j among equals."""
    b, blk, t = scores.shape
    i = first + jnp.arange(blk)[:, None]
    valid = jnp.arange(t)[None, :] <= i
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(topk, t))
    chosen = jnp.zeros((b, blk, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(blk)[None, :, None],
        idx].set(True)
    return chosen & valid


def sparse_attention(h, p, positions, *, n_head, n_kv, topk, theta,
                     sections, eps, wrong=None):
    """h (b, t, hidden) -> ((b, t, hidden), L_I of the layer)."""
    b, t, _ = h.shape
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (h @ p["q_proj"]["kernel"]).reshape(b, t, n_head, -1)
    k = (h @ p["k_proj"]["kernel"]).reshape(b, t, n_kv, -1)
    v = (h @ p["v_proj"]["kernel"]).reshape(b, t, n_kv, -1)
    d = q.shape[-1]
    if wrong != "qk_norm":
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    if wrong == "mrope_sections":
        sections = sections[1:] + sections[:1]
    q, k = (_rope(a, positions, theta, sections) for a in (q, k))

    idx = p["indexer"]
    hd = jax.lax.stop_gradient(h)
    k_i = _layer_norm(hd @ idx["wk_idx"]["kernel"], idx["k_norm"], eps)
    di = k_i.shape[-1]
    q_i = (hd @ idx["wq_idx"]["kernel"]).reshape(b, t, -1, di)
    n_idx = q_i.shape[2]
    w = hd @ idx["w_proj"]["kernel"]
    if wrong == "head_weights":
        w = jnp.ones_like(w)
    w = w * (n_idx ** -0.5 * di ** -0.5)
    q_i = _rope(q_i, positions, theta, sections)
    k_i = _rope(k_i[:, :, None], positions, theta, sections)[:, :, 0]
    keep = t if wrong == "dense" else topk // 2 if wrong == "topk_half" \
        else topk
    rep = n_head // n_kv  # query heads g * rep .. read kv head g

    @jax.checkpoint
    def one_block(first, q_blk, qi_blk, w_blk):
        r = jnp.einsum("bqhd,bkd->bhqk", qi_blk, k_i)
        if wrong != "relu":
            r = jnp.maximum(r, 0.0)
        scores = jnp.einsum("bhqk,bqh->bqk", r, w_blk).astype(jnp.float32)
        kept = chosen_keys(jax.lax.stop_gradient(scores), first, keep)
        q_grp = q_blk.reshape(b, blk, n_kv, rep, d)
        att = jnp.einsum("bqgrd,bkgd->bgrqk", q_grp, k) / math.sqrt(d)
        prob = jax.nn.softmax(
            jnp.where(kept[:, None, None], att, -jnp.inf), -1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", prob, v).reshape(
            b, blk, n_head, d)
        pbar = jax.lax.stop_gradient(
            prob.astype(jnp.float32).mean((1, 2)))
        logq = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
        live = kept & (pbar > 0)
        safe = jnp.where(live, pbar, 1.0)
        kl = jnp.where(live, safe * (jnp.log(safe)
                                     - jnp.where(live, logq, 0.0)), 0.0)
        return out, kl.sum()

    def blocks(a):
        return a.reshape(b, t // blk, blk, *a.shape[2:]).swapaxes(0, 1)

    out, kl = jax.lax.map(lambda args: one_block(*args), (
        jnp.arange(t // blk) * blk, blocks(q), blocks(q_i), blocks(w)))
    out = out.swapaxes(0, 1).reshape(b, t, -1)
    return out @ p["o_proj"]["kernel"], kl.sum() / (b * t)


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


def expert_layer(u, p, *, top_k, first_expert, balance=False):
    """u (tokens, hidden) -> the held experts' part; with `balance`
    (that, the layer's load-balancing term: experts x sum_e (share of
    the tokens that chose e) x (mean probability of e), over ALL the
    router's experts)."""
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
    return (out, term) if balance else out


def forward(params, idx, positions=None, *, n_layer: int, n_head: int,
            n_kv: int, topk: int, theta: float, sections: tuple,
            top_k: int, first_expert: int, eps: float, dtype=jnp.float32,
            wrong=None):
    """(the last norm's output (batch, seq, hidden), the sums over the
    layers of L_I and of the router's balance term, the head) in `dtype`.  float32 is the reference; bfloat16 is the
    control one precision below — EVERYTHING in it."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (3, b, t))

    def block(x, p):
        h = _rms_norm(x, p["input_norm"], eps)
        out, kl = sparse_attention(
            h, p["attention"], positions, n_head=n_head, n_kv=n_kv,
            topk=topk, theta=theta, sections=tuple(sections), eps=eps,
            wrong=wrong)
        x = x + out
        u = _rms_norm(x, p["post_attn_norm"], eps).reshape(b * t, c)
        out, balance = expert_layer(u, p["feed_forward"], top_k=top_k,
                                    first_expert=first_expert, balance=True)
        return x + out.reshape(b, t, c), jnp.stack(
            [kl.astype(jnp.float32), balance.astype(jnp.float32)])

    terms = jnp.zeros((2,), jnp.float32)  # sums of L_I, of the balance term
    for i in range(n_layer):
        x, both = jax.checkpoint(block)(x, params[f"layers_{i}"])
        terms = terms + both
    return _rms_norm(x, params["norm"], eps), terms, \
        params["lm_head"]["kernel"]


def loss(params, batch, *, index_loss_weight: float = 1.0,
         aux_weight: float = 0.0, parts=False, **sizes):
    """Mean next-token cross-entropy + index_loss_weight x the layers'
    L_I summed + aux_weight x the layers' MEAN balance term: the total
    the program's step reports as `loss`.  `parts=True`: (total,
    cross-entropy, the layers' MEAN L_I)."""
    x, (index_term, balance), head = forward(
        params, batch["input_ids"], batch.get("positions"), **sizes)
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
    total = ce + aux_weight * balance / sizes["n_layer"]
    if sizes.get("wrong") != "index_term":
        total = total + index_loss_weight * index_term
    if parts:
        return total, ce, index_term / sizes["n_layer"]
    return total
