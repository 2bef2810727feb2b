"""Plain reference: the `nemotron_h` hybrid (Mamba-2 / expert / attention
layers from a pattern string) forward pass and training loss in
`jax.numpy`, float32.

Follows `nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16` config.json
(`model_type: nemotron_h`) and the Mamba-2 paper (Dao & Gu 2024,
arXiv:2405.21060).  Every symbol below is a key of that config.

Model: `x = embed[ids]`; for each character of
`hybrid_override_pattern`, `x = x + mixer(RMSNorm(x))` (eps
`layer_norm_epsilon`, own scale); `logits = RMSNorm(x) @ head` (untied).
Loss: next-token cross-entropy, nothing added (the config has no
auxiliary-loss key).

`M`, Mamba-2 mixer.  H = `mamba_num_heads` heads of P = `mamba_head_dim`
(d_inner = H*P, NOT `expand` * hidden), G = `n_groups`, N =
`ssm_state_size`, convolution width `conv_kernel`:

    [z | xBC | dt] = u @ W_in          widths d_inner | d_inner + 2GN | H
    xBC = silu(causal_depthwise_conv1d(xBC) + b_conv)
    [x | B | C] = xBC                  x (T,H,P); B, C (T,G,N); head h
                                       uses group h // (H/G)
    dlt = softplus(dt + dt_bias)       (T,H)
    A   = -exp(A_log)                  (H,)
    S_t = exp(dlt_t A) S_{t-1} + dlt_t x_t (x) B_t     S in R^{P x N}, S_0 = 0
    y_t = S_t C_t + D x_t
    y   = RMSNorm_grouped(y * silu(z))  groups of d_inner/G features,
                                        one (d_inner,) scale
    out = y @ W_out

`E`, expert layer.  `s = sigmoid(x @ W_r)` over all `n_routed_experts`
(128); chosen: the `num_experts_per_tok` largest of `s + b` (`b` the
selection bias; `n_group` = `topk_group` = 1: no group limit); gates
`g = s[chosen]` WITHOUT b, `g = g / (sum g + 1e-20)` (`norm_topk_prob`),
`g = routed_scaling_factor * g`.  Expert e: `relu(x @ U_e)^2 @ V_e`
(`mlp_hidden_act: relu2`, no gate matrix, no bias).  Shared expert: the
same form at `moe_shared_expert_intermediate_size`, on every token.
`out = shared(x) + sum_k g_k expert_{e_k}(x)`.

THE SHARE: the parameter tree holds `experts_held` experts, numbers
`first_expert .. first_expert + experts_held - 1` of the 128 the router
scores.  What the absent experts would have added is left out (the
model-configs guide, section 4): the sum above runs over the chosen
experts that are held.  With all held it is the uncut layer.

`*`, attention.  `num_attention_heads` query heads and
`num_key_value_heads` key/value heads of `head_dim`, no bias, causal,
scale 1/sqrt(head_dim), NO rotary embedding (`nemotron_h`'s attention
takes its positions from the Mamba layers; `rope_theta` and
`partial_rotary_factor` are unused keys).

No kernel, no chunked scan, no sort, no `ragged_dot`, no import from the
program's model code: the recurrence is a SEQUENTIAL `lax.scan` over
time, every held expert is applied to every token under the membership
mask, top-k membership is found by counting.  It reads the parameter
tree by the names the program's `NemotronH` gives its leaves, which is
the only thing it shares with it.

What changes no number, only what is compiled and kept: the time scan is
nested in blocks of `_TIME_BLOCK` steps, each block and each layer under
`jax.checkpoint` (a layer's state is H*P*N floats = 2 MB at the
published sizes: 17 GB a layer over 8192 steps if every carry were
kept); experts in a `lax.scan`, heads in a `lax.map`, as
`reference_olmoe.py`.  Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_TIME_BLOCK = 128


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


# ------------------------------------------------------------- Mamba-2

def _causal_conv(x, kernel, bias):
    """x (b, t, c); kernel (k, c), its LAST tap on the current step."""
    k = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    t = x.shape[1]
    return sum(padded[:, j:j + t] * kernel[j] for j in range(k)) + bias


def recurrence(x, dlt, a, b_mat, c_mat, d_skip):
    """The state-space recurrence, one step at a time.

    x (b, t, H, P); dlt (b, t, H); a, d_skip (H,); b_mat, c_mat
    (b, t, G, N), head h using group h // (H/G).  Returns y (b, t, H, P).
    A head is written (group, rank in group), so that B and C are used
    where they lie and never copied once a head."""
    bsz, t, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    a = a.reshape(g, r)

    def step(state, inp):
        x_t, dlt_t, b_t, c_t = inp  # (b,G,R,P) (b,G,R) (b,G,N) (b,G,N)
        decay = jnp.exp(dlt_t * a)[..., None, None]
        state = decay * state + (dlt_t[..., None] * x_t)[..., None] \
            * b_t[:, :, None, None, :]
        return state, jnp.einsum("bgrpn,bgn->bgrp", state, c_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    blk = math.gcd(t, _TIME_BLOCK)
    operands = (x.reshape(bsz, t, g, r, p), dlt.reshape(bsz, t, g, r),
                b_mat, c_mat)
    time_first = [v.swapaxes(0, 1).reshape(t // blk, blk, bsz, *v.shape[2:])
                  for v in operands]
    _, y = jax.lax.scan(block, jnp.zeros((bsz, g, r, p, n), jnp.float32),
                        tuple(time_first))
    y = y.reshape(t, bsz, h, p).swapaxes(0, 1)
    return y + d_skip[:, None] * x


def mamba_mixer(u, p, *, heads, head_dim, groups, state, eps):
    """u (b, t, hidden) -> (b, t, hidden)."""
    bsz, t, _ = u.shape
    d_inner, gn = heads * head_dim, groups * state
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_kernel"],
                                   p["conv_bias"]))
    x, b_mat, c_mat = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    y = recurrence(
        x.reshape(bsz, t, heads, head_dim),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b_mat.reshape(bsz, t, groups, state),
        c_mat.reshape(bsz, t, groups, state), p["D"])
    y = y.reshape(bsz, t, d_inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(bsz, t, groups, d_inner // groups), 1.0, eps)
    return (y.reshape(bsz, t, d_inner) * p["gate_norm_scale"]) \
        @ p["out_proj"]["kernel"]


# ------------------------------------------------------------- experts

def _top_k_member(scores, k):
    """(tokens, E) bool: fewer than k experts beat it (ties: lower index)."""
    e = scores.shape[-1]
    mine, other = scores[:, :, None], scores[:, None, :]
    lower_index = jnp.arange(e)[None, :] < jnp.arange(e)[:, None]
    beats = (other > mine) | ((other == mine) & lower_index[None])
    return beats.sum(-1) < k


@jax.checkpoint
def _relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def expert_layer(x, p, *, top_k, routed_scaling, first_expert):
    """x (tokens, hidden) -> the held experts' part + the shared expert's."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    member = _top_k_member(s + p["selection_bias"], top_k)
    gates = jnp.where(member, s, 0.0)
    gates = routed_scaling * gates / (gates.sum(-1, keepdims=True) + 1e-20)
    held = p["experts_w_in"].shape[0]
    gates = gates[:, first_expert:first_expert + held]

    def add_expert(acc, ew):
        w_up, w_down, gate = ew
        return acc + _relu2_mlp(x, w_up, w_down) * gate[:, None], None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          (p["experts_w_in"], p["experts_w_down"], gates.T))
    return out + _relu2_mlp(x, p["shared_up_proj"]["kernel"],
                            p["shared_down_proj"]["kernel"])


# ----------------------------------------------------------- attention

@jax.checkpoint
def _one_head(qkv):
    q, k, v = qkv  # (b, t, d) each
    t, d = q.shape[1], q.shape[2]
    att = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(d)
    att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v)


def attention(x, p, *, n_head, n_kv_head, head_dim):
    b, t, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, n_head, head_dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, n_kv_head, head_dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, n_kv_head, head_dim)
    rep = n_head // n_kv_head  # query head h reads key/value head h // rep
    k, v = (jnp.repeat(a, rep, axis=2) for a in (k, v))
    heads_first = [a.transpose(2, 0, 1, 3) for a in (q, k, v)]
    y = jax.lax.map(_one_head, tuple(heads_first))  # (heads, b, t, d)
    return y.transpose(1, 2, 0, 3).reshape(b, t, n_head * head_dim) \
        @ p["o_proj"]["kernel"]


# --------------------------------------------------------------- model

def forward(params, idx, *, pattern: str, n_head: int, n_kv_head: int,
            head_dim: int, mamba_heads: int, mamba_head_dim: int,
            n_groups: int, state: int, top_k: int, routed_scaling: float,
            first_expert: int, eps: float):
    """Logits (batch, seq, vocab), float32."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape
    mixers = {
        "M": lambda h, p: mamba_mixer(
            h, p["mamba"], heads=mamba_heads, head_dim=mamba_head_dim,
            groups=n_groups, state=state, eps=eps),
        "E": lambda h, p: expert_layer(
            h.reshape(b * t, c), p["feed_forward"], top_k=top_k,
            routed_scaling=routed_scaling,
            first_expert=first_expert).reshape(b, t, c),
        "*": lambda h, p: attention(
            h, p["attention"], n_head=n_head, n_kv_head=n_kv_head,
            head_dim=head_dim),
    }
    for i, kind in enumerate(pattern):
        layer = jax.checkpoint(functools.partial(
            lambda h, p, mix: h + mix(_rms_norm(h, p["norm"]["scale"], eps),
                                      p), mix=mixers[kind]))
        x = layer(x, params[f"layers_{i}"])
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
