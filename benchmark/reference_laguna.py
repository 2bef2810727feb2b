"""Plain reference: Laguna-XS.2 (`poolside/Laguna-XS.2`, `model_type:
laguna`) forward pass and training loss in `jax.numpy`, float32.

Every symbol below is a key of the source's config.json.

    x = E[ids]
    for l in range(num_hidden_layers):
        h = RMSNorm_in(x)                         eps rms_norm_eps, own scale
        q, k, v = h Wq, h Wk, h Wv                num_attention_heads_per_layer
                                                  [l] query heads over
                                                  num_key_value_heads kv heads
                                                  of head_dim, no bias
        layer_types[l] == "full_attention":
            the first head_dim * partial_rotary_factor features of every q
            and k head are rotated (rotate-half inside them), the rest pass;
            inv_freq over THOSE features at rope_theta, YaRN: divided by
            factor below the ramp, kept above it, the ramp between the
            pairs that turn beta_fast and beta_slow times over
            original_max_position_embeddings (ends floored and ceiled,
            computed over the rotary width); cos and sin times
            attention_factor.  s[i, j] kept iff j <= i
        layer_types[l] == "sliding_attention":
            the whole head rotated, rope_theta of its own, no scaling.
            s[i, j] kept iff 0 <= i - j < sliding_window
        a = softmax(q k^T / sqrt(head_dim)) v     a group of query heads
                                                  reads one kv head
        g = sigmoid(h Wgate)                      "gating": one number a
                                                  head and token
        x = x + (g * a) Wo
        u = RMSNorm_post(x)
        mlp_layer_types[l] == "dense":
            x = x + (silu(u Wg) * (u Wu)) Wd      intermediate_size
        mlp_layer_types[l] == "sparse":
            p = softmax(u Wr)                     num_experts, float32
            chosen = the num_experts_per_tok largest of p
            w = p_chosen / sum(p_chosen) * moe_routed_scaling_factor
            x = x + sum_{e chosen, HELD here} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
                  + (silu(u Wg_s) * (u Wu_s)) Wd_s   the shared expert
    logits = RMSNorm(x) W_head                    untied
    loss   = mean next-token cross-entropy (+ aux_weight x the mean over
             the sparse layers of E sum_i f_i P_i where the file assumes
             one)

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.

No kernel, no tiles, no sort, no `ragged_dot`, no import from the
program's model code.  The attention's mask is written out from i and j;
the rotation cuts a head into its rotated and its passed features and
joins them again; EVERY held expert is applied to EVERY token under a
membership mask, which is found by counting (an expert is chosen when
fewer than k beat it; ties go to the lower index).  It reads the
parameter tree by the names the program's `Laguna` gives its leaves
(a layer's head count is its `q_proj`'s width over head_dim), which is
the only thing it shares with it.

Departures from the published model, each what the program computes and
the configuration file's `assumed` lists:

- `"gating": true` is read as ONE gate a head and token, a sigmoid of
  the block's normalised input's product (the parameter count decides
  per-head against element-wise; the activation and the input are
  assumed);
- the router's score function has no key: softmax, renormalised over
  the chosen, scaled on the experts' OUTPUT;
- no QK-norm and no gate on the shared expert: no key names either;
- where `aux_weight` is set the load-balancing term is OLMoE's (HF
  `load_balancing_loss_func` over all the router's experts, the mean
  over the sparse layers): config.json has no key for one.

The controls (`wrong`): a set of names, each one equation got wrong —
"gate" (the gate dropped), "window" (dropped from the mask), "rotary"
(the whole head rotated on the full layers, tables over the whole
head), "yarn" (the full layers' tables unscaled), "yarn_width" (the
ramp's ends computed over head_dim, not over the rotary width),
"shared" (the shared expert dropped).  `dtype=jnp.bfloat16` is the
control one precision below.

What changes no number, only what is compiled and kept, so that one
sequence of 16,384 tokens at 64 heads fits beside the training state on
one chip: each layer under `jax.checkpoint`; attention one head and one
block of `_QUERY_BLOCK` queries at a time (`lax.map` over both, each
body under `jax.checkpoint`: a (queries x keys) score matrix is 64 MB at
16,384 keys, where all heads' would be 64 GB); the membership by blocks
of `_MEMBER_BLOCK` tokens (its count compares every pair of 256 scores);
the experts in a `lax.scan` over the stacked weights; head and
cross-entropy over `_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 1024
_LOSS_BLOCK = 2048
_MEMBER_BLOCK = 1024

FULL, SLIDING = "full_attention", "sliding_attention"


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def inv_freq(rope: dict, rotary: int, ramp_width: int | None = None):
    """(inv_freq over `rotary` features, the factor on cos and sin) of
    one entry of `rope_parameters`.  `ramp_width` (a control's) is the
    width YaRN's ramp ends are computed over: the rotary width."""
    theta = float(rope["rope_theta"])
    inv = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                          / rotary)
    if rope["rope_type"] == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    width = ramp_width or rotary

    def pair(turns):  # the pair that turns `turns` times over the original
        return width * math.log(rope["original_max_position_embeddings"]
                                / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), width - 1)
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = inv / rope["factor"] * ramp + inv * (1.0 - ramp)
    return inv, float(rope["attention_factor"])


def _rope(x, inv, factor):
    """x (b, t, heads, d): the first 2 * len(inv) features rotated
    ((x1, x2) = their two HALVES), the rest passed."""
    t, rotary = x.shape[1], 2 * inv.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * factor)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : rotary // 2], x[..., rotary // 2: rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], -1)


def attention(x, p, *, n_kv_head, head_dim, inv, factor, window, gate):
    """x (b, t, hidden) -> (b, t, hidden): a masked softmax, query head h
    reading key/value head h // (heads / n_kv_head), each head's output
    under its gate.  `window` None: a query sees every key at or before
    it."""
    b, t, _ = x.shape
    d = head_dim
    n_head = p["q_proj"]["kernel"].shape[1] // d
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, n_head, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, n_kv_head, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, n_kv_head, d)
    q, k = _rope(q, inv, factor), _rope(k, inv, factor)
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
    # (heads, blocks, b, blk, d) -> (b, t, heads, d)
    y = y.transpose(2, 1, 3, 0, 4).reshape(b, t, n_head, d)
    if gate:
        y = y * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return y.reshape(b, t, n_head * d) @ p["o_proj"]["kernel"]


def _top_k_member(scores, k):
    """(tokens, E) bool: expert e is among the token's k largest scores —
    fewer than k experts beat it (a tie goes to the lower index)."""
    e = scores.shape[-1]
    lower_index = jnp.arange(e)[None, :] < jnp.arange(e)[:, None]

    def one_block(s):
        mine, other = s[:, :, None], s[:, None, :]
        beats = (other > mine) | ((other == mine) & lower_index[None])
        return beats.sum(-1) < k

    blk = math.gcd(scores.shape[0], _MEMBER_BLOCK)
    return jax.lax.map(one_block, scores.reshape(-1, blk, e)).reshape(
        scores.shape)


@jax.checkpoint
def _one_expert(u, w_gate, w_up, w_down, gate):
    return _swiglu(u, w_gate, w_up, w_down) * gate[:, None]


def expert_layer(u, p, *, top_k, routed_scaling, first_expert, shared):
    """u (tokens, c) -> (the held experts' part of the layer's output
    plus the shared expert's, the load-balancing term over all the
    router's experts)."""
    logits = u @ p["router"]["kernel"]
    n_exp = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    member = _top_k_member(jax.lax.stop_gradient(probs), top_k)
    gates = jnp.where(member, probs, 0.0)
    gates = routed_scaling * gates / gates.sum(-1, keepdims=True)
    held = p["experts_w_in"].shape[0]

    def add_expert(acc, ew):
        return acc + _one_expert(u, *ew), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (p["experts_w_gate"], p["experts_w_in"], p["experts_w_down"],
         gates[:, first_expert:first_expert + held].T))
    if shared:
        out = out + jax.checkpoint(_swiglu)(
            u, p["shared_gate_proj"]["kernel"], p["shared_up_proj"]["kernel"],
            p["shared_down_proj"]["kernel"])
    f = member.astype(logits.dtype).mean(0)  # sums to top_k
    return out, n_exp * jnp.sum(f * probs.mean(0))


def forward(params, idx, *, layer_types, mlp_layer_types, rope_parameters,
            window: int, n_kv_head: int, head_dim: int, top_k: int,
            routed_scaling: float, first_expert: int, eps: float,
            wrong=(), dtype=jnp.float32):
    """(the last norm's output (batch, seq, hidden), the head's matrix,
    the mean load-balancing term) in `dtype`.  float32 is the reference;
    bfloat16 is the control one precision below — EVERYTHING in it,
    norms' statistics, router, softmaxes, gates and cross-entropy too,
    where the program keeps those in float32."""
    wrong = set(wrong)
    unknown = wrong - {"gate", "window", "rotary", "yarn", "yarn_width",
                       "shared"}
    if unknown:
        raise ValueError(f"no such control: {sorted(unknown)}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape

    tables = {}
    for kind in (FULL, SLIDING):
        rope = dict(rope_parameters[kind])
        if kind == FULL and "rotary" in wrong:
            rope["partial_rotary_factor"] = 1
        if kind == FULL and "yarn" in wrong:
            rope["rope_type"] = "default"
        tables[kind] = inv_freq(
            rope, int(head_dim * rope["partial_rotary_factor"]),
            head_dim if "yarn_width" in wrong else None)

    def block(x, p, kind, sparse):
        h = _rms_norm(x, p["input_norm"], eps)
        inv, factor = tables[kind]
        x = x + attention(
            h, p["attention"], n_kv_head=n_kv_head, head_dim=head_dim,
            inv=inv, factor=factor, gate="gate" not in wrong,
            window=window if kind == SLIDING and "window" not in wrong
            else None)
        u = _rms_norm(x, p["post_attn_norm"], eps)
        ff = p["feed_forward"]
        if not sparse:
            return x + _swiglu(u, ff["gate_proj"]["kernel"],
                               ff["up_proj"]["kernel"],
                               ff["down_proj"]["kernel"]), 0.0
        out, lb = expert_layer(
            u.reshape(b * t, c), ff, top_k=top_k,
            routed_scaling=routed_scaling, first_expert=first_expert,
            shared="shared" not in wrong)
        return x + out.reshape(b, t, c), lb

    lb_sum, n_sparse = 0.0, 0
    for i, (kind, mlp) in enumerate(zip(layer_types, mlp_layer_types)):
        x, lb = jax.checkpoint(block, static_argnums=(2, 3))(
            x, params[f"layers_{i}"], kind, mlp == "sparse")
        lb_sum, n_sparse = lb_sum + lb, n_sparse + (mlp == "sparse")
    x = _rms_norm(x, params["norm"], eps)
    return x, params["lm_head"]["kernel"], lb_sum / max(n_sparse, 1)


def loss(params, batch, *, aux_weight: float = 0.0, ce_dtype=None,
         **sizes):
    """Mean next-token cross-entropy (+ the load-balancing term where
    the file assumes one): the total the program's step reports as
    `loss`.  `ce_dtype` (a control's: None = `dtype`) is the type the
    head's logits are cast to before the cross-entropy."""
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
