"""Plain reference: Kimi-VL-A3B-Instruct's LANGUAGE model
(`moonshotai/Kimi-VL-A3B-Instruct` config.json, the text decoder's keys;
report arXiv:2504.07491; the decoder is DeepSeek-V2's, arXiv:2405.04434)
forward pass and training loss in `jax.numpy`, float32.

Every symbol below is a key of the source's config.json.

    x = E[ids]
    for l in range(num_hidden_layers):
        h  = RMSNorm_in(x)                          eps rms_norm_eps, own scale
        q  = h Wq        -> num_attention_heads x (qk_nope_head_dim |
                            qk_rope_head_dim); q_lora_rank null: no q latent
        c  = h Wkv_a     -> (kv_lora_rank latent | qk_rope_head_dim rope key,
                            ONE for all heads)
        kv = RMSNorm_latent(c[:kv_lora_rank]) Wkv_b
                         -> heads x (qk_nope_head_dim k_nope | v_head_dim v)
        q_rope, k_rope rotated (rope_theta, rotate-half, no scaling);
        k_h = (k_nope_h | k_rope)
        s[i, j] = q_i . k_j / sqrt(qk_nope_head_dim + qk_rope_head_dim),
                  kept iff j <= i
        x  = x + concat_h(softmax(s_h) v_h) Wo
        u  = RMSNorm_post(x)
        l <  first_k_dense_replace:  x = x + (silu(u Wg) * (u Wu)) Wd
                                                    width intermediate_size
        l >= first_k_dense_replace:
            s      = sigmoid(u W_r)                 n_routed_experts scores
            chosen = the num_experts_per_tok largest of s + b
                     (b: the selection bias of topk_method noaux_tc;
                     n_group = topk_group = 1: no group limit)
            g      = s[chosen] / (sum s[chosen] + 1e-20)   (norm_topk_prob)
                     * routed_scaling_factor
            x = x + sum_{e chosen, HELD here} g_e swiglu_e(u)
                  + swiglu_shared(u)       width n_shared_experts x
                                           moe_intermediate_size, every token
    logits = RMSNorm(x) W_head                      untied
    loss   = mean next-token cross-entropy, nothing added

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width; what the absent experts would
have added is left out, here as in the program.

Departures from the published model, each what the program computes and
each named in the configuration file's `assumed`:

- the pairing of the rotated lanes: the source's checkpoint interleaves
  the pairs and HF's code permutes them to halves before `rotate_half`;
  that is a permutation of Wq's and Wkv_a's rope columns, so with seeded
  weights either is the same model.  This reference rotates HALVES;
- `seq_aux` is true in the source but its coefficient is not among the
  config's keys: no sequence-wise auxiliary term is computed;
- the vision tower and its projector are not in the config's text keys:
  not built, text ids go in.

No kernel, no tiles, no sort, no `ragged_dot`, no import from the
program's model code.  The attention's mask is written out from i and j;
EVERY held expert is applied to EVERY token under a membership mask,
which is found by counting (an expert is chosen when fewer than k beat
it; ties go to the lower index).  It reads the parameter tree by the
names the program's `LatentMoE` gives its leaves, which is the only
thing it shares with it.

What changes no number, only what is compiled and kept, so that one
sequence of 16,384 tokens fits beside the training state on one chip:
each layer under `jax.checkpoint`; attention one head and one block of
`_QUERY_BLOCK` queries at a time (`lax.map` over both, each body under
`jax.checkpoint`); the experts in a `lax.scan` over the stacked weights;
head and cross-entropy over `_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.

The controls (keywords of `forward`, each off its published value in a
control alone): `scale` (the softmax's), `latent_norm` False (the
latent's RMSNorm left out), `rotate_key` False (`k_rope` not rotated),
`dtype` bfloat16 (one precision below, EVERYTHING in it) with `ce_dtype`
the type of the logits under the cross-entropy.
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


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def attention(x, p, *, n_head, nope, rope, theta, eps, scale=None,
              latent_norm=True, rotate_key=True):
    """x (b, t, hidden) -> (b, t, hidden): latent attention as a masked
    softmax, QK^T over nope + rope lanes and PV over v's own."""
    b, t, _ = x.shape
    rank = p["kv_a_norm"]["scale"].shape[0]
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, n_head, nope + rope)
    c = x @ p["kv_a_proj"]["kernel"]
    latent, k_rope = c[..., :rank], c[..., rank:].reshape(b, t, 1, rope)
    if latent_norm:
        latent = _rms_norm(latent, p["kv_a_norm"], eps)
    kv = (latent @ p["kv_b_proj"]["kernel"]).reshape(b, t, n_head, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = _rope(q[..., nope:], theta)
    if rotate_key:
        k_rope = _rope(k_rope, theta)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, n_head, rope))], -1)
    if scale is None:
        scale = 1.0 / math.sqrt(nope + rope)
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


def forward(params, idx, *, n_layer: int, first_dense: int, n_head: int,
            nope: int, rope: int, top_k: int, routed_scaling: float,
            first_expert: int, eps: float, theta: float,
            dtype=jnp.float32, **controls):
    """(the last norm's output (batch, seq, hidden), the head's matrix)
    in `dtype`.  float32 is the reference; bfloat16 is the control one
    precision below — EVERYTHING in it, norms' statistics, router,
    softmaxes too, where the program keeps those in float32.
    `controls`: `attention`'s wrong-equation keywords."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape

    def block(x, p, dense):
        h = _rms_norm(x, p["input_norm"], eps)
        x = x + attention(h, p["attention"], n_head=n_head, nope=nope,
                          rope=rope, theta=theta, eps=eps, **controls)
        u = _rms_norm(x, p["post_attn_norm"], eps).reshape(b * t, c)
        out = dense_layer(u, p["feed_forward"]) if dense else expert_layer(
            u, p["feed_forward"], top_k=top_k,
            routed_scaling=routed_scaling, first_expert=first_expert)
        return x + out.reshape(b, t, c)

    for i in range(n_layer):
        x = jax.checkpoint(block, static_argnums=(2,))(
            x, params[f"layers_{i}"], i < first_dense)
    return _rms_norm(x, params["norm"], eps), params["lm_head"]["kernel"]


def loss(params, batch, *, ce_dtype=None, **sizes):
    """Mean next-token cross-entropy: the total the program's step
    reports as `loss`.  `ce_dtype` (a control's: None = `dtype`) is the
    type the head's logits are cast to before the cross-entropy."""
    x, w_head = forward(params, batch["input_ids"], **sizes)
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
    return (ce / (b * t)).astype(jnp.float32)
