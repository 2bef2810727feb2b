"""Plain reference: Ling-3.0-flash's language model
(`inclusionAI/Ling-3.0-flash` config.json, `model_type: bailing_hybrid`)
forward pass and training loss in `jax.numpy`, float32.  The linear mixer
is Kimi Delta Attention (Kimi Linear, arXiv:2510.26692, in its public
implementation's form), the full layer DeepSeek-V2's latent attention
(arXiv:2405.04434) with Kimi-VL's widths, the router DeepSeek-V3's
`noaux_tc` (arXiv:2412.19437).  Every symbol below is a key of the
source's config.json.

    x = E[ids]
    for l in range(num_hidden_layers):
        h = RMSNorm_in(x)                      eps rms_norm_eps, own scale
        x = x + (latent(h) if (l + 1) % layer_group_size == 0 else kda(h))
        u = RMSNorm_post(x)
        l <  first_k_dense_replace:  x = x + (silu(u Wg) * (u Wu)) Wd
                                               width intermediate_size
        l >= first_k_dense_replace:  x = x + experts(u)
    logits = RMSNorm(x) W_head                 untied
    loss   = mean next-token cross-entropy, nothing added

`kda`, H heads HELD of num_attention_heads, dk = dv = head_dim:

    q~ = h Wq   k~ = h Wk   v~ = h Wv   f = h Wf       each H * 128
    q, k, v = silu(causal depthwise conv of q~ | k~ | v~)
              short_conv_kernel_size taps a channel, no bias (linear_silu)
    q^ = q / sqrt(|q|^2 + 1e-6) / sqrt(dk)   k^ = k / sqrt(|k|^2 + 1e-6)
    g  = kda_lower_bound * sigmoid(exp(A_log_head) * (f + dt_bias))
    alpha = exp(g)  in R^dk a head and token   (kda_safe_gate)
    beta  = sigmoid(h Wb)                      one a head and token
    S_t = Diag(alpha_t) S_{t-1} + k^_t u_t^T
    u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k^_t)
    o_t = S_t^T q^_t                           S in R^{dk x dv}, S_0 = 0
    y   = sigmoid(h Wgate)[head] * RMSNorm_dv(o)    head_wise: one number
                                               a head and token; one (dv,)
                                               scale (group_norm_size 1)
    out = concat_heads(y) Wo

the recurrence ONE STEP AT A TIME (`lax.scan` over time) with `Diag(alpha)`
written as such: it knows no chunk, no sub-block, no triangular solve.

`latent`, H heads HELD:

    q  = h Wq        -> H x (qk_nope_head_dim | qk_rope_head_dim)
    c  = h Wkv_a     -> (kv_lora_rank latent | ONE rope key for all heads)
    kv = RMSNorm_latent(c[:kv_lora_rank]) Wkv_b -> H x (k_nope | v)
    q_rope, k_rope rotated (rope_theta, halves, no scaling)
    s[i, j] = q_i . k_j / sqrt(nope + rope), kept iff j <= i
    o_h = softmax(s_h) v_h * sigmoid(h Wgate)[h]
    out = concat_h(o_h) Wo

`experts` (score_function sigmoid, topk_method noaux_tc):

    s      = sigmoid(u W_r)                    num_experts scores, float32
    group  = sum of the two largest (s + b) in each of n_group groups
    kept   = the topk_group groups of the largest group score
    chosen = the num_experts_per_tok largest s + b inside the kept groups
    g      = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    out    = sum_{e chosen, HELD here} g_e swiglu_e(u) + swiglu_shared(u)

A chip's share: the tree holds the weights of `held` experts, numbers
`first_expert ..` of the router's width, and of H heads of every mixer;
what the absent experts would have added is left out, here as in the
program.

What the row does not fix, each as the configuration file lists it under
`assumed`: the layer-kind rule from `layer_group_size`; the safe gate's
form; beta without a factor 2; the output gate's input, sigmoid and place
(on the NORMED head); one shared (dv,) norm scale; `use_qk_norm` read as
KDA's L2 norms; the rope lanes paired by halves; no sequence-wise
auxiliary term; no multi-token-prediction module (its weight is 0).

No kernel, no chunk, no sort, no `ragged_dot`, no import from the
program's model code.  A largest-k is found one entry at a time (the
largest, then the largest of the rest: a tie goes to the lower index);
EVERY held expert is applied to EVERY token under the membership mask.
It reads the parameter tree by the names the program's `BailingHybrid`
gives its leaves, which is the only thing it shares with it.

What changes no number, only what is compiled and kept, so that one
sequence of 16,384 tokens fits beside the training state on one chip:
each layer and each sublayer under `jax.checkpoint`; the recurrence in
blocks of `_TIME_BLOCK` steps, each under `jax.checkpoint`; attention one
head and one block of `_QUERY_BLOCK` queries at a time; the experts in a
`lax.scan` over the stacked weights; head and cross-entropy over
`_LOSS_BLOCK` tokens at a time.  Call under
`jax.default_matmul_precision("highest")`.

`wrong` names ONE term to get wrong (the controls of the cell's
tolerances): "channel_decay" (a head's channels all decay by their mean
g: the scalar rule), "lower_bound" (the safe gate's bound -1 in place
of kda_lower_bound), "correction" (u_t = beta_t v_t: a gated
linear attention), "output_gate" and "attn_gate" (the head-wise gate of
the KDA mixers / of the latent layer left out), "group_limit" (the k
largest of all the experts).  `dtype=jnp.bfloat16` is the control one
precision below: EVERYTHING in it but the recurrence's carried state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference_kimi_vl import _rope
from benchmark.reference_nemotron_h import _causal_conv

_QUERY_BLOCK = 1024
_TIME_BLOCK = 128
_LOSS_BLOCK = 2048
_L2_EPS = 1e-6
WRONG = ("channel_decay", "lower_bound", "correction", "output_gate",
         "attn_gate", "group_limit")


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def kda_recurrence(q, k, v, alpha, beta, correct=True):
    """The delta rule with a decay a channel, one step at a time.  q, k,
    alpha (b, t, H, dk); v (b, t, H, dv); beta (b, t, H).  Returns o
    (b, t, H, dv).  The state is float32 whatever the operands are."""
    bsz, t, h, dk = k.shape
    dv = v.shape[-1]

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = (x.astype(jnp.float32) for x in inp)
        state = a_t[..., :, None] * state                # Diag(alpha) S
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t) if correct else 0.0
        u = b_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * u[..., None, :]
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


def kda(x, p, *, heads, lower_bound, eps, wrong=None):
    """x (b, t, hidden) -> (b, t, hidden): the HELD heads' part."""
    b, t, _ = x.shape
    dk = p["q_proj"]["kernel"].shape[1] // heads
    dv = p["v_proj"]["kernel"].shape[1] // heads
    qk = heads * dk
    kernel = p["conv_kernel"]

    def conv(name, lo, hi):  # no bias
        return jax.nn.silu(_causal_conv(x @ p[name]["kernel"],
                                        kernel[:, lo:hi], 0.0))

    q = conv("q_proj", 0, qk).reshape(b, t, heads, dk)
    k = conv("k_proj", qk, 2 * qk).reshape(b, t, heads, dk)
    v = conv("v_proj", 2 * qk, kernel.shape[1]).reshape(b, t, heads, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _L2_EPS) \
        / math.sqrt(dk)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _L2_EPS)
    f = (x @ p["f_proj"]["kernel"] + p["dt_bias"]).reshape(b, t, heads, dk)
    bound = 1.0 if wrong == "lower_bound" else -lower_bound
    g = -bound * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    if wrong == "channel_decay":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(x @ p["b_proj"]["kernel"])
    o = kda_recurrence(q, k, v, jnp.exp(g), beta,
                       correct=wrong != "correction")
    y = _rms_norm(o, p["gate_norm"], eps)
    if wrong != "output_gate":
        y = y * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return y.reshape(b, t, heads * dv) @ p["o_proj"]["kernel"]


def latent(x, p, *, heads, nope, rope, theta, eps, wrong=None):
    """x (b, t, hidden) -> (b, t, hidden): latent attention as a masked
    softmax, QK^T over nope + rope lanes and PV over v's own, a sigmoid
    gate a head on the result."""
    b, t, _ = x.shape
    rank = p["kv_a_norm"]["scale"].shape[0]
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, heads, nope + rope)
    c = x @ p["kv_a_proj"]["kernel"]
    k_rope = _rope(c[..., rank:].reshape(b, t, 1, rope), theta)
    kv = (_rms_norm(c[..., :rank], p["kv_a_norm"], eps)
          @ p["kv_b_proj"]["kernel"]).reshape(b, t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, heads, rope))], -1)
    scale = 1.0 / math.sqrt(nope + rope)
    q = q.reshape(b, t // blk, blk, heads, nope + rope)
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
    # (heads, blocks, b, blk, dv) -> (b, t, heads, dv)
    y = y.transpose(2, 1, 3, 0, 4).reshape(b, t, heads, -1)
    if wrong != "attn_gate":
        y = y * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return y.reshape(b, t, -1) @ p["o_proj"]["kernel"]


def _largest(scores, k):
    """(..., n) bool: the k largest entries along the last axis, found one
    at a time (a tie goes to the lower index)."""
    member = jnp.zeros(scores.shape, bool)
    for _ in range(k):
        pick = jnp.argmax(jnp.where(member, -jnp.inf, scores), axis=-1)
        member = member | (pick[..., None] == jnp.arange(scores.shape[-1]))
    return member


def chosen_experts(biased, *, top_k, n_group, topk_group):
    """(tokens, E) bool from the scores WITH the selection bias, in the
    three steps: the groups' scores, the kept groups, the k largest
    inside them."""
    tokens, e = biased.shape
    grouped = biased.reshape(tokens, n_group, e // n_group)
    group_score = jnp.where(_largest(grouped, 2), grouped, 0.0).sum(-1)
    kept = _largest(group_score, topk_group)
    inside = jnp.where(kept[..., None], grouped, -jnp.inf)
    return _largest(inside.reshape(tokens, e), top_k)


@jax.checkpoint
def _one_expert(u, w_gate, w_up, w_down, gate):
    return _swiglu(u, w_gate, w_up, w_down) * gate[:, None]


def expert_layer(u, p, *, top_k, n_group, topk_group, routed_scaling,
                 first_expert, wrong=None):
    """u (tokens, hidden) -> the held experts' part + the shared
    expert's."""
    s = jax.nn.sigmoid(u.astype(jnp.float32)
                       @ p["router"]["kernel"].astype(jnp.float32))
    if wrong == "group_limit":
        n_group = topk_group = 1
    member = chosen_experts(
        s + p["selection_bias"].astype(jnp.float32), top_k=top_k,
        n_group=n_group, topk_group=topk_group)
    gates = jnp.where(member, s, 0.0)
    gates = (routed_scaling * gates
             / (gates.sum(-1, keepdims=True) + 1e-20)).astype(u.dtype)
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


def forward(params, idx, *, n_layer: int, group_size: int, first_dense: int,
            heads: int, lower_bound: float, nope: int, rope: int,
            theta: float, top_k: int, n_group: int, topk_group: int,
            routed_scaling: float, first_expert: int, eps: float,
            dtype=jnp.float32, wrong=None):
    """(the last norm's output (batch, seq, hidden), the head's matrix) in
    `dtype`.  float32 is the reference; bfloat16 is the control one
    precision below."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong={wrong!r}: one of {WRONG}")
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape

    @jax.checkpoint
    def mixer_kda(h, p):
        return kda(h, p, heads=heads, lower_bound=lower_bound, eps=eps,
                   wrong=wrong)

    @jax.checkpoint
    def mixer_latent(h, p):
        return latent(h, p, heads=heads, nope=nope, rope=rope, theta=theta,
                      eps=eps, wrong=wrong)

    def block(x, p, layer):
        h = _rms_norm(x, p["input_norm"], eps)
        if (layer + 1) % group_size == 0:
            x = x + mixer_latent(h, p["attention"])
        else:
            x = x + mixer_kda(h, p["linear_attention"])
        u = _rms_norm(x, p["post_attn_norm"], eps).reshape(b * t, c)
        out = dense_layer(u, p["feed_forward"]) if layer < first_dense \
            else expert_layer(
                u, p["feed_forward"], top_k=top_k, n_group=n_group,
                topk_group=topk_group, routed_scaling=routed_scaling,
                first_expert=first_expert, wrong=wrong)
        return x + out.reshape(b, t, c)

    for i in range(n_layer):
        x = jax.checkpoint(block, static_argnums=(2,))(
            x, params[f"layers_{i}"], i)
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
