"""Plain reference: OLMoE forward pass and training loss in `jax.numpy`,
float32.

Follows the published OLMoE description (Muennighoff et al. 2024,
arXiv:2409.02060; `allenai/OLMoE-1B-7B-0125-Instruct` config.json and
HF `modeling_olmoe.py`): token embedding, pre-RMSNorm blocks of causal
multi-head attention with an RMSNorm over the whole q and k projections
(before the split into heads, before RoPE in the half-split
`rotate_half` convention, no biases) and a feed-forward of E SwiGLU
experts of which each token takes its k most probable, weighted by the
router's softmax probabilities AS THEY ARE (`norm_topk_prob: false`; no
token dropped, no shared expert), a final RMSNorm and an untied head:

    h   = x + O(softmax_causal(rope(q) rope(k)^T / sqrt(d)) v)
    out = h + sum_{e in topk(p)} p_e Wdown_e(silu(Wgate_e n2) * Wup_e n2)
    loss = CE + w_lb * E * sum_i f_i P_i + w_z * mean(logsumexp(Wr n2)^2)

`f_i` = the share of tokens that have expert i among their k (so the
`f_i` sum to k), `P_i` = the mean router probability of expert i (HF
`load_balancing_loss_func`, `router_aux_loss_coef`).

No kernel, no sort, no `ragged_dot`, no import from the program's model
code: EVERY expert is applied to EVERY token and masked by the top-k
membership, which is found by counting (an expert is chosen when fewer
than k experts beat it; ties go to the lower index).  It reads the
parameter tree by the names the program's Llama gives its leaves
(`embed_tokens/embedding`, `layers_<i>/attention/q_proj/kernel`,
`layers_<i>/feed_forward/experts_w_gate`, ...), which is the only thing
it shares with it.

Departures from the published model, each what the program computes:

- the router z-loss is the PAPER's (section 4.1.5, coefficient 0.001);
  HF's modeling code has none and config.json no key for it — the
  configuration file lists it under `assumed`;
- with several layers both auxiliary terms are the mean over the layers
  of each layer's own term (the paper's training code); HF pools the
  rows of all layers before it multiplies.  At the benchmark's depth 1
  they are the same number;
- the vocabulary needs no padding: 50,304 is the published one.

What changes no number, only what is compiled and kept: the experts run
in a `lax.scan` over the stacked expert weights and the heads in a
`lax.map`, each body under `jax.checkpoint`, so that one sequence of
4,096 tokens fits beside the training state on one chip (64 experts x
4,096 tokens x 1,024 x three float32 activations are 3 GB otherwise,
16 heads of 4,096 x 4,096 scores 1 GB a copy).  Call under
`jax.default_matmul_precision("highest")`
(`reference.loss_and_grad_norm(..., precision="highest")` does).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, theta):
    """x (b, t, heads, d): rotate (x1, x2) = the two HALVES of d."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.checkpoint
def _one_head(qkv):
    q, k, v = qkv  # (b, t, d) each
    t, d = q.shape[1], q.shape[2]
    att = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(d)
    att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(att, axis=-1), v)


def _attention(x, p, n_head, eps, theta):
    b, t, c = x.shape
    q = _rms_norm(x @ p["q_proj"]["kernel"], p["q_norm"], eps)
    k = _rms_norm(x @ p["k_proj"]["kernel"], p["k_norm"], eps)
    v = x @ p["v_proj"]["kernel"]
    q, k, v = (a.reshape(b, t, n_head, c // n_head) for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    heads_first = [a.transpose(2, 0, 1, 3) for a in (q, k, v)]
    y = jax.lax.map(_one_head, tuple(heads_first))  # (heads, b, t, d)
    return y.transpose(1, 2, 0, 3).reshape(b, t, c) @ p["o_proj"]["kernel"]


def _top_k_member(probs, k):
    """(tokens, E) bool: expert e is among the token's k most probable —
    fewer than k experts beat it (a tie goes to the lower index)."""
    e = probs.shape[-1]
    mine, other = probs[:, :, None], probs[:, None, :]
    lower_index = jnp.arange(e)[None, :] < jnp.arange(e)[:, None]
    beats = (other > mine) | ((other == mine) & lower_index[None])
    return beats.sum(-1) < k


@jax.checkpoint
def _one_expert(x, w_gate, w_up, w_down, gate):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return (h @ w_down) * gate[:, None]


def _experts(x, p, top_k, norm_topk_prob):
    """x (tokens, c) -> (layer output, lb term, z term)."""
    n_exp = p["router"]["kernel"].shape[-1]
    logits = x @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    member = _top_k_member(probs, top_k)
    gates = jnp.where(member, probs, 0.0)
    if norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)

    def add_expert(acc, ew):
        w_gate, w_up, w_down, gate = ew
        return acc + _one_expert(x, w_gate, w_up, w_down, gate), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (p["experts_w_gate"], p["experts_w_in"], p["experts_w_down"],
         gates.T))
    f = member.astype(jnp.float32).mean(0)  # sums to top_k
    lb = n_exp * jnp.sum(f * probs.mean(0))
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return out, lb, z


def forward(params, idx, *, n_layer: int, n_head: int, top_k: int,
            norm_topk_prob: bool, eps: float, theta: float):
    """(logits (batch, seq, vocab), mean lb term, mean z term), float32."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["embed_tokens"]["embedding"][idx]
    b, t, c = x.shape
    lb_sum = z_sum = 0.0
    for i in range(n_layer):
        p = params[f"layers_{i}"]
        x = x + _attention(_rms_norm(x, p["input_norm"], eps),
                           p["attention"], n_head, eps, theta)
        n2 = _rms_norm(x, p["post_attn_norm"], eps).reshape(b * t, c)
        out, lb, z = _experts(n2, p["feed_forward"], top_k, norm_topk_prob)
        x = x + out.reshape(b, t, c)
        lb_sum, z_sum = lb_sum + lb, z_sum + z
    x = _rms_norm(x, params["norm"], eps)
    return x @ params["lm_head"]["kernel"], lb_sum / n_layer, z_sum / n_layer


def loss(params, batch, *, n_layer: int, n_head: int, top_k: int,
         norm_topk_prob: bool, eps: float, theta: float,
         aux_weight: float, z_weight: float):
    """Cross-entropy + load-balancing term + router z-loss: the total the
    program's step reports as `loss`."""
    logits, lb, z = forward(
        params, batch["input_ids"], n_layer=n_layer, n_head=n_head,
        top_k=top_k, norm_topk_prob=norm_topk_prob, eps=eps, theta=theta)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return (lse - tgt).mean() + aux_weight * lb + z_weight * z
