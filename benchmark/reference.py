"""Plain reference: GPT-2 forward pass and loss in `jax.numpy`, float32.

Follows the published GPT-2 description (Radford et al. 2019; the
`openai-community/gpt2` config): learned token + position embeddings,
pre-LayerNorm blocks of causal multi-head attention and a 4x GELU(tanh)
MLP, a final LayerNorm, and the output head tied to the token embedding;
loss = mean token cross-entropy.  No kernels, no cache, no import from
the program's model code.  It reads the parameter tree by the names the
program's GPT gives its leaves (`wte/embedding`, `h_<i>/attn/c_attn/
kernel`, ...), which is the only thing it shares with it.

Departures from the published model, both taken from the configuration
file and both what the program computes: the LayerNorm epsilon
(`layer_norm_epsilon` in the file) and the padded vocabulary.

The blocks run as one `lax.scan` over the per-layer parameters stacked
inside the call, each under `jax.checkpoint`: that changes no number,
only what is compiled (one block, not 48) and what the backward pass
keeps, so the 48-layer model's gradient compiles in seconds and fits
beside the training state.  Call under
`jax.default_matmul_precision("highest")` (`loss_and_grad_norm` does on
request): a float32 matmul on a TPU is otherwise computed in bfloat16
passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _linear(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, eps):
    b, t, c = x.shape
    d = c // n_head
    h = _layer_norm(x, p["ln_1"], eps)
    q, k, v = jnp.split(_linear(h, p["attn"]["c_attn"]), 3, axis=-1)
    q, k, v = (a.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)
               for a in (q, k, v))
    att = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(d)
    att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -jnp.inf)
    y = jax.nn.softmax(att, axis=-1) @ v
    y = y.transpose(0, 2, 1, 3).reshape(b, t, c)
    x = x + _linear(y, p["attn"]["c_proj"])
    h = _layer_norm(x, p["ln_2"], eps)
    h = _gelu_tanh(_linear(h, p["mlp"]["c_fc"]))
    return x + _linear(h, p["mlp"]["c_proj"])


def forward(params, idx, *, n_layer: int, n_head: int, eps: float):
    """Logits (batch, seq, vocab), float32."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    t = idx.shape[1]
    x = params["wte"]["embedding"][idx] + params["wpe"]["embedding"][:t]
    block = jax.checkpoint(_block, static_argnums=(2, 3))
    layers = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                          *(params[f"h_{i}"] for i in range(n_layer)))
    x, _ = jax.lax.scan(lambda h, p: (block(h, p, n_head, eps), None),
                        x, layers)
    x = _layer_norm(x, params["ln_f"], eps)
    return x @ params["wte"]["embedding"].T


def loss(params, batch, *, n_layer: int, n_head: int, eps: float):
    logits = forward(params, batch["input_ids"], n_layer=n_layer,
                     n_head=n_head, eps=eps)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    return (lse - tgt).mean()


def loss_and_grad_norm(loss_fn, params, batch, precision=None):
    """(loss, global L2 norm of d loss / d params) of ANY `loss_fn(params,
    batch)` in one jitted call.  The reference is called with
    `precision="highest"`; the system's own loss with None, i.e. as it is
    configured (its Pallas kernels refuse an fp32 contraction of bf16
    operands, and they are what is being checked)."""

    def f(p, b):
        val, grads = jax.value_and_grad(loss_fn)(p, b)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        return val, jnp.sqrt(sq)

    if precision is None:
        val, norm = jax.jit(f)(params, batch)
    else:
        with jax.default_matmul_precision(precision):
            val, norm = jax.jit(f)(params, batch)
    return float(val), float(norm)
