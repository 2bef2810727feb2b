"""Plain reference: Xing4.0-29B-A4B (`XingChen-AGI/Xing4.0-29B-A4B`
config.json, `model_type` `xing4_0`) forward pass and training loss in
`jax.numpy`, float32.  The trunk is the DeepSeek-V3 family's
(arXiv:2412.19437; latent attention arXiv:2405.04434); the `hc_*` /
`mhc_*` keys are manifold-constrained hyper-connections' (Xie et al.,
arXiv:2512.24880, over Zhu et al., arXiv:2409.19606).

Every symbol below is a key of the source's config.json; n = hc_mult.

    X = (e, e, .., e), e = E[ids]                     n lanes of hidden_size
    for l in range(num_hidden_layers), for each of the block's two
    sublayers F (attention behind RMSNorm_in, feed-forward behind
    RMSNorm_post), each with its own Phi, alpha, b:
        z      = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)   over n x hidden
        a_pre  = alpha_pre  (z Phi_pre)  + b_pre                R^n
        a_post = alpha_post (z Phi_post) + b_post               R^n
        A_res  = alpha_res mat(z Phi_res) + b_res               R^{n x n}
        h_pre  = sigmoid(a_pre);  h_post = 2 sigmoid(a_post)
        M      = exp(clip(A_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
        hc_sinkhorn_iters times:  M = M / (colsum(M) + hc_eps)
                                  M = M / (rowsum(M) + hc_eps)
        u      = sum_i h_pre[i] X[i];   y = F(RMSNorm(u))
        X[i]   = sum_j M[i, j] X[j] + h_post[i] y
    attention(h):
        q  = RMSNorm_q(h Wq_a) Wq_b   -> heads x (qk_nope_head_dim |
                                         qk_rope_head_dim); q_lora_rank wide
        c  = h Wkv_a -> (kv_lora_rank latent | qk_rope_head_dim rope key)
        kv = RMSNorm_latent(c[:kv_lora_rank]) Wkv_b -> heads x (k_nope | v)
        q_rope, k_rope rotated by YaRN's frequencies (rope_scaling):
            inv_extra = rope_theta^(-2i/d), inv_interp = inv_extra / factor
            lo, hi = floor / ceil of d ln(original / (beta 2 pi)) / (2 ln
                     rope_theta) at beta_fast / beta_slow, in [0, d - 1]
            mask = 1 - clip((i - lo) / (hi - lo), 0, 1)
            inv  = inv_interp (1 - mask) + inv_extra mask
            (tables times mscale's ratio to mscale_all_dim's: 1 here)
        s[i, j] = q_i . k_j (192)^-1/2 m^2, m = 0.1 mscale_all_dim ln factor
                  + 1, kept iff j <= i;  out = concat_h(softmax(s_h) v_h) Wo
    feed-forward: l < first_k_dense_replace: (silu(u Wg) * (u Wu)) Wd;
        else s = sigmoid(u W_r), chosen = the num_experts_per_tok largest
        of s + b, g = s[chosen] / (sum + 1e-20) * routed_scaling_factor,
        sum_{e chosen, HELD here} g_e swiglu_e(u) + swiglu_shared(u)
    x      = sum_i X[i];  logits = RMSNorm(x) W_head          untied
    loss   = mean next-token cross-entropy
    with num_nextn_predict_layers = 1 (DeepSeek-V3's MTP module):
        g       = [RMSNorm_h(x_t) ; RMSNorm_e(E[id_{t+1}])] W_eh
        x2      = the lanes' sum of one more expert block on (g, .., g)
        logits2 = RMSNorm_2(x2) W_head                 the SAME E and head
        loss   += mtp_weight * mean_{t < T-1} ce(logits2[t], id_{t+2})

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.

Departures from the published model, each what the program computes and
each named in the configuration file's `assumed`: the rotated lanes are
paired by HALVES (a permutation of Wq_b's and Wkv_a's rope columns); no
sequence-wise auxiliary term; the read-out of the lanes is their sum and
the mixing norm has no learned scale (the row has no key for either);
Sinkhorn normalises columns first and adds `hc_eps` to each denominator;
the MTP module norms the hidden state first in the join and its weight
is a field of the file.

No kernel, no tiles, no sort, no import from the program's model code.
The lanes are a Python list of n arrays (b, t, hidden), so no array has
n as one of its last two axes; the coefficients are (b, t, n) and
(b, t, n, n).  It reads the parameter tree by the names the program's
`LatentMoE` gives its leaves, which is the only thing it shares with it.

What changes no number, only what is compiled and kept, so that one
sequence at the timed length fits beside the training state on one
chip: the layers under `jax.checkpoint` by halves, each half by halves
again down to one block, and each sublayer under one of its own inside
(four lanes of 8,192 tokens are 470 MB in float32: the backward keeps
the lanes at one boundary a level and rebuilds the others); attention
one head and one
block of `_QUERY_BLOCK` queries at a time; the experts in a `lax.scan`
over the stacked weights, Sinkhorn's rounds in one over nothing; head
and cross-entropy over `_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.

The controls (keywords of `forward`, each off its published value in a
control alone): `sinkhorn_iters` (1 where the model has 20),
`post_factor` (1.0: h_post without its 2), `q_norm` False (the q
latent's RMSNorm left out), `scale_mscale` False (the softmax's scale
without m^2), `dtype` bfloat16 (one precision below, EVERYTHING in it)
with `ce_dtype` the type of the logits under the cross-entropy.

Parity: none — the reference repository trains no such stack; this file
is the benchmark's own oracle.
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


def yarn_inv_freq(d: int, theta: float, yarn):
    """(d / 2,) inverse frequencies, and the tables' factor."""
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    inv_extra = theta ** (-i / d)
    if yarn is None:
        return inv_extra, 1.0
    factor = yarn["factor"]

    def pair(beta):
        return d * math.log(yarn["original_max_position_embeddings"]
                            / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair(yarn["beta_fast"])), 0)
    hi = min(math.ceil(pair(yarn["beta_slow"])), d - 1)
    ramp = (jnp.arange(d // 2, dtype=jnp.float32) - lo) / max(hi - lo, 0.001)
    mask = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return (inv_extra / factor * (1.0 - mask) + inv_extra * mask,
            _mscale(factor, yarn["mscale"])
            / _mscale(factor, yarn["mscale_all_dim"]))


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, theta, yarn):
    """x (b, t, heads, d): rotate (x1, x2) = the two HALVES of d."""
    t, d = x.shape[1], x.shape[-1]
    inv, table_scale = yarn_inv_freq(d, theta, yarn)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * table_scale)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * table_scale)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def attention(x, p, *, n_head, nope, rope, theta, yarn, eps, q_norm=True,
              scale_mscale=True):
    """x (b, t, hidden) -> (b, t, hidden): latent attention with a q
    latent as a masked softmax, QK^T over nope + rope lanes and PV over
    v's own."""
    b, t, _ = x.shape
    rank = p["kv_a_norm"]["scale"].shape[0]
    blk = math.gcd(t, _QUERY_BLOCK)
    q = x @ p["q_a_proj"]["kernel"]
    if q_norm:
        q = _rms_norm(q, p["q_a_norm"], eps)
    q = (q @ p["q_b_proj"]["kernel"]).reshape(b, t, n_head, nope + rope)
    c = x @ p["kv_a_proj"]["kernel"]
    latent, k_rope = c[..., :rank], c[..., rank:].reshape(b, t, 1, rope)
    latent = _rms_norm(latent, p["kv_a_norm"], eps)
    kv = (latent @ p["kv_b_proj"]["kernel"]).reshape(b, t, n_head, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta, yarn)],
                        -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        _rope(k_rope, theta, yarn), (b, t, n_head, rope))], -1)
    scale = 1.0 / math.sqrt(nope + rope)
    if yarn is not None and scale_mscale:
        scale *= _mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    q = q.reshape(b, t // blk, blk, n_head, nope + rope)
    k, v = (a.transpose(2, 0, 1, 3) for a in (k, v))  # (heads, b, t, d)

    @jax.checkpoint
    def one_block(q_blk, first, k_h, v_h):
        att = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) * scale
        i = first + jnp.arange(blk)[:, None]
        j = jnp.arange(t)[None, :]
        att = jnp.where(j <= i, att, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v_h)

    def one_head(hq):
        k_h, v_h, q_h = hq  # q_h (blocks, b, blk, d)
        return jax.lax.map(lambda fq: one_block(fq[1], fq[0], k_h, v_h),
                           (jnp.arange(t // blk) * blk, q_h))

    y = jax.lax.map(one_head, (k, v, q.transpose(3, 1, 0, 2, 4)))
    # (heads, blocks, b, blk, dv) -> (b, t, heads * dv)
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


def expert_layer(u, p, *, top_k, routed_scaling, first_expert):
    """u (tokens, hidden) -> the held experts' part + the shared
    expert's."""
    s = jax.nn.sigmoid(u @ p["router"]["kernel"])
    member = _top_k_member(s + p["selection_bias"], top_k)
    gates = jnp.where(member, s, 0.0)
    gates = routed_scaling * gates / (gates.sum(-1, keepdims=True) + 1e-20)
    held = p["experts_w_in"].shape[0]

    def add_expert(acc, ew):
        return acc + _one_expert(u, *ew), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (p["experts_w_gate"], p["experts_w_in"], p["experts_w_down"],
         gates[:, first_expert:first_expert + held].T))
    return out + jax.checkpoint(_swiglu)(
        u, p["shared_gate_proj"]["kernel"], p["shared_up_proj"]["kernel"],
        p["shared_down_proj"]["kernel"])


def dense_layer(u, p):
    return jax.checkpoint(_swiglu)(
        u, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"])


def mixing_coefficients(lanes, p, *, iters, hc_eps, res_clamp, eps,
                        post_factor=2.0):
    """The lanes (n arrays (b, t, hidden)) -> h_pre (b, t, n), h_post
    (b, t, n), H_res (b, t, n, n)."""
    n = len(lanes)
    mean_sq = sum(jnp.mean(x * x, -1, keepdims=True) for x in lanes) / n
    r = jax.lax.rsqrt(mean_sq + eps)
    raw = sum((x * r) @ p["phi"][i] for i, x in enumerate(lanes))
    alpha = p["alpha"]
    h_pre = jax.nn.sigmoid(alpha[0] * raw[..., :n] + p["b_pre"])
    h_post = post_factor * jax.nn.sigmoid(
        alpha[1] * raw[..., n:2 * n] + p["b_post"])
    a_res = alpha[2] * raw[..., 2 * n:].reshape(*raw.shape[:-1], n, n) \
        + p["b_res"]
    def one_round(m, _):
        m = m / (m.sum(-2, keepdims=True) + hc_eps)  # colsum
        return m / (m.sum(-1, keepdims=True) + hc_eps), None  # rowsum

    m, _ = jax.lax.scan(one_round, jnp.exp(jnp.clip(a_res, *res_clamp)),
                        None, length=iters)
    return h_pre, h_post, m


def hyper_connection(lanes, p, branch, **how):
    """One sublayer inside its hyper-connection: n lanes -> n lanes."""
    n = len(lanes)
    h_pre, h_post, h_res = mixing_coefficients(lanes, p, **how)
    u = sum(h_pre[..., i, None] * lanes[i] for i in range(n))
    y = branch(u)
    return [sum(h_res[..., i, j, None] * lanes[j] for j in range(n))
            + h_post[..., i, None] * y for i in range(n)]


def block(x, p, dense: bool, *, n_head, nope, rope, theta, yarn, eps,
          top_k, routed_scaling, first_expert, iters, hc_eps, res_clamp,
          post_factor=2.0, **controls):
    """One block on the lanes x (n arrays (b, t, hidden)): attention
    behind its input norm, then the feed-forward behind its norm, each
    inside its hyper-connection (and under `jax.checkpoint`)."""
    b, t, c = x[0].shape
    mix = dict(iters=iters, hc_eps=hc_eps, res_clamp=res_clamp, eps=eps,
               post_factor=post_factor)

    def attend(x, p):
        def branch(u):
            return attention(_rms_norm(u, p["input_norm"], eps),
                             p["attention"], n_head=n_head, nope=nope,
                             rope=rope, theta=theta, yarn=yarn, eps=eps,
                             **controls)

        return hyper_connection(x, p["attention_hc"], branch, **mix)

    def feed(x, p):
        def branch(u):
            u = _rms_norm(u, p["post_attn_norm"], eps).reshape(b * t, c)
            out = dense_layer(u, p["feed_forward"]) if dense \
                else expert_layer(
                    u, p["feed_forward"], top_k=top_k,
                    routed_scaling=routed_scaling, first_expert=first_expert)
            return out.reshape(b, t, c)

        return hyper_connection(x, p["feed_forward_hc"], branch, **mix)

    return jax.checkpoint(feed)(jax.checkpoint(attend)(x, p), p)


def forward(params, idx, *, n_layer: int, first_dense: int, n_head: int,
            nope: int, rope: int, top_k: int, routed_scaling: float,
            first_expert: int, eps: float, theta: float, yarn, lanes: int,
            sinkhorn_iters: int, hc_eps: float, res_clamp, mtp: int = 0,
            dtype=jnp.float32, post_factor: float = 2.0, **controls):
    """(the last norm's output (batch, seq, hidden), the MTP module's or
    None, the head's matrix) in `dtype`.  float32 is the reference;
    bfloat16 is the control one precision below — EVERYTHING in it.
    `controls`: `attention`'s wrong-equation keywords."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    table = params["embed_tokens"]["embedding"]
    how = dict(
        n_head=n_head, nope=nope, rope=rope, theta=theta, yarn=yarn, eps=eps,
        top_k=top_k, routed_scaling=routed_scaling,
        first_expert=first_expert, iters=sinkhorn_iters, hc_eps=hc_eps,
        res_clamp=res_clamp, post_factor=post_factor, **controls)

    def blocks(x, named):
        """x (b, t, hidden) into every lane, through the blocks, the
        lanes' sum out."""
        def span(x, ps, dense):
            if len(ps) == 1:
                return block(x, ps[0], dense[0], **how)
            half = len(ps) // 2
            halves = jax.checkpoint(span, static_argnums=(2,))
            return halves(halves(x, ps[:half], dense[:half]), ps[half:],
                          dense[half:])

        x = span([x] * lanes, [p for p, _ in named],
                 tuple(d for _, d in named))
        return sum(x)

    x = blocks(table[idx], [(params[f"layers_{i}"], i < first_dense)
                            for i in range(n_layer)])
    second = None
    if mtp:
        p = params["mtp_0"]
        following = jnp.concatenate([idx[:, 1:], idx[:, -1:]], axis=1)
        g = jnp.concatenate(
            [_rms_norm(x, p["hnorm"], eps),
             _rms_norm(table[following], p["enorm"], eps)], -1) \
            @ p["eh_proj"]["kernel"]
        second = _rms_norm(blocks(g, [(p["block_0"], False)]), p["norm"],
                           eps)
    return (_rms_norm(x, params["norm"], eps), second,
            params["lm_head"]["kernel"])


def _cross_entropy(x, w_head, labels, ce_dtype):
    """Summed cross-entropy of rows x (b, t, hidden) through the head,
    and the number of rows whose label is not -1."""
    c = x.shape[-1]
    blk = math.gcd(x.shape[0] * x.shape[1], _LOSS_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        rows, labels = xl
        logits = (rows @ w_head).astype(ce_dtype or rows.dtype)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(labels >= 0, lse - picked, 0.0).sum()

    total = jax.lax.map(one_block, (x.reshape(-1, blk, c),
                                    labels.reshape(-1, blk))).sum()
    return total / jnp.maximum((labels >= 0).sum(), 1)


def loss(params, batch, *, ce_dtype=None, mtp_weight: float = 0.3, **sizes):
    """Mean next-token cross-entropy, plus `mtp_weight` x the MTP
    module's where the model has one: the total the program's step
    reports as `loss`.  `ce_dtype` (a control's: None = `dtype`) is the
    type the head's logits are cast to before the cross-entropy."""
    x, second, w_head = forward(params, batch["input_ids"], **sizes)
    labels = batch["labels"]
    total = _cross_entropy(x, w_head, labels, ce_dtype)
    if second is not None:
        further = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        total = total + mtp_weight * _cross_entropy(
            second, w_head, further, ce_dtype)
    return total.astype(jnp.float32)
