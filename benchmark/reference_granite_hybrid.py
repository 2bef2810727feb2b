"""Plain reference: the `granitemoehybrid` dense hybrid (a Mamba-2 or an
attention mixer AND a SwiGLU in every block, four scalar multipliers, a
tied head) forward pass and training loss in `jax.numpy`, float32.

Follows `ibm-granite/granite-4.0-h-micro` config.json (`model_type:
granitemoehybrid`, `num_local_experts` 0) and the Mamba-2 paper (Dao & Gu
2024, arXiv:2405.21060).  Every symbol below is a key of that config.

    x = E[ids] * embedding_multiplier
    for kind in layer_types:                       "mamba" | "attention"
        h = RMSNorm(x)                             eps rms_norm_eps, own scale
        m = Mamba2(h) if kind == "mamba" else Attn(h)
        x = x + residual_multiplier * m
        h = RMSNorm(x)
        x = x + residual_multiplier * (W_down (silu(W_gate h) * (W_up h)))
    logits = (RMSNorm(x) @ E^T) / logits_scaling   tied: E is the embedding
    loss   = mean next-token cross-entropy, nothing added

`Mamba2`: `reference_nemotron_h.mamba_mixer` as it stands (the same
paper's mixer: in-projection to [z | xBC | dt], depthwise causal
convolution with bias then silu, `dt = softplus(dt + dt_bias)`,
`A = -exp(A_log)`, the SEQUENTIAL recurrence `S_t = exp(dt_t A) S_{t-1}
+ dt_t x_t (x) B_t`, `y_t = S_t C_t + D x_t`, RMSNorm of `y * silu(z)`,
out-projection) at `mamba_n_heads` heads of `mamba_d_head`, state
`mamba_d_state` and `mamba_n_groups` = 1: all heads share one B, C pair
and the gate's norm runs over all of d_inner (one group: the grouped and
the ungrouped norm coincide).  `mamba_chunk_size` is how a chunked scan
is computed, not what it computes: nothing here reads it.

`Attn`: `num_attention_heads` query heads and `num_key_value_heads`
key/value heads of hidden / heads, no bias, causal, NO rotary or other
position term (`position_embedding_type: nope`), and
`softmax(q k^T * attention_multiplier) v` — the multiplier in place of
1/sqrt(head size).

No kernel, no chunked scan, no import from the program's model code.  It
reads the parameter tree by the names the program's `GraniteHybrid`
gives its leaves, which is the only thing it shares with it.

What changes no number, only what is compiled and kept: each layer
under `jax.checkpoint`; attention one head and one block of
`_QUERY_BLOCK` queries at a time (`lax.map` over both), each under
`jax.checkpoint`, so that a (queries x keys) score matrix is 32 MB at
8192 keys and not 268.  Call under
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference_nemotron_h import _rms_norm, mamba_mixer

_QUERY_BLOCK = 1024


@jax.checkpoint
def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"])
            * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def attention(x, p, *, n_head, n_kv_head, scale):
    """x (b, t, hidden) -> (b, t, hidden): a masked softmax, query head h
    reading key/value head h // (n_head / n_kv_head)."""
    b, t, _ = x.shape
    d = p["q_proj"]["kernel"].shape[1] // n_head
    blk = math.gcd(t, _QUERY_BLOCK)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t // blk, blk, n_head, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(b, t, n_kv_head, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(b, t, n_kv_head, d)
    rep = n_head // n_kv_head
    k, v = (a.transpose(2, 0, 1, 3) for a in (k, v))  # (kv heads, b, t, d)

    @jax.checkpoint
    def one_block(q_blk, first, k_h, v_h):
        att = jnp.einsum("bqd,bkd->bqk", q_blk, k_h) * scale
        rows = first + jnp.arange(blk)[:, None]
        att = jnp.where(jnp.arange(t)[None, :] <= rows, att, -jnp.inf)
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


def forward(params, idx, *, layer_types, n_head: int, n_kv_head: int,
            mamba_heads: int, mamba_head_dim: int, n_groups: int,
            state: int, embedding_multiplier: float,
            residual_multiplier: float, attention_multiplier: float,
            logits_scaling: float, eps: float, dtype=jnp.float32):
    """Logits (batch, seq, vocab) in `dtype`.  float32 is the reference;
    bfloat16 is the control one precision below, which the cell's
    tolerances must tell from it (PERF.md section 6, PR 33)."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    table = params["embed_tokens"]["embedding"]
    x = table[idx] * embedding_multiplier
    mixers = {
        "mamba": lambda h, p: mamba_mixer(
            h, p["mamba"], heads=mamba_heads, head_dim=mamba_head_dim,
            groups=n_groups, state=state, eps=eps),
        "attention": lambda h, p: attention(
            h, p["attention"], n_head=n_head, n_kv_head=n_kv_head,
            scale=attention_multiplier),
    }

    def block(x, p, mix):
        h = _rms_norm(x, p["input_norm"]["scale"], eps)
        # (the recurrence carries a float32 state whatever it is given)
        x = x + residual_multiplier * mix(h, p).astype(x.dtype)
        h = _rms_norm(x, p["post_mixer_norm"]["scale"], eps)
        return x + residual_multiplier * _swiglu(h, p["feed_forward"])

    for i, kind in enumerate(layer_types):
        x = jax.checkpoint(functools.partial(block, mix=mixers[kind]))(
            x, params[f"layers_{i}"])
    x = _rms_norm(x, params["norm"]["scale"], eps)
    return (x @ table.T) / logits_scaling


def loss(params, batch, **sizes):
    """Mean next-token cross-entropy: the total the program's step
    reports as `loss`."""
    logits = forward(params, batch["input_ids"], **sizes)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return (lse - tgt).mean()
