"""The decoder stack, once: embed -> blocks -> norm -> head.

A model file keeps what is its architecture's, the config and the block
(pin -> norm -> mixer -> residual); its top-level module says which
block, which per-layer arguments, which norm and which head.  How a
block is rematerialised, how the layers are named, where the head's ops
sit in the compiled step and how parameters are drawn is decided here.

Plain functions, called from INSIDE the model's `@nn.compact __call__`:
the modules they build bind to the calling model, so the parameter tree
and every `op_name` are what they would be had the model written the
lines itself (a wrapping `nn.Module` or a public method on the model
adds a level to both).  Those names are interfaces:
`parallel/sharding.py`'s rules, `untrained_params`,
`parallel/pipeline.split_layer_params`, checkpoints and the benchmark's
`*.scopes.json` files bind to `<prefix>_<i>/...`, `head` and `lm_head`.

Parity: none — the reference wraps torch modules after the fact
(atorch's activation_checkpointing.py); here the wrapper is the stack's.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.remat import MODEL_CHECKPOINT_NAMES, resolve_remat_policy


def layers(block, cfg, per_layer, x, *shared, prefix: str = "layers",
           remat_names: tuple = (), static_argnums: tuple = (),
           handed=None):
    """x through `block(cfg, *per_layer[i], name="<prefix>_<i>")(x,
    *shared)` for every i, each block recomputed in the backward pass
    where `cfg.remat` says so.  `handed` (a dict of arrays, `{}` to begin
    with) is what blocks hand on BESIDE x: a block is then called `(x,
    handed, *shared)` and returns `(x, handed)`, the dict it was given
    or one with more in it, which every later block is given — a
    decoder-hybrid-decoder's memory and keys and values
    (models/phi4flash.py).  A handed array is an output of the
    recomputed block that made it and an input of each that follows: its
    readers' cotangents sum into it, and it is kept once.  `per_layer` holds one tuple a layer: what
    the block class takes beside the config (a hybrid's kind, the layer's
    index, nothing).  `remat_names` are the `checkpoint_name` anchors of
    the `*_names` policies where the config lets a strategy choose them
    (() = the two every block marks); `static_argnums` the `shared`
    arguments that are no arrays, counted as `nn.remat` counts (x is 1): a
    Python bool a block branches on must not come back as a tracer."""
    if cfg.remat:
        # prevent_cse=True: the layers run in a python loop (not scan),
        # and without the CSE barrier XLA merges the rematerialized
        # forward back into the saved one — measured on v5e as remat
        # silently becoming a no-op (identical step time AND activation
        # temps with remat on/off)
        block = nn.remat(
            block, prevent_cse=True, static_argnums=static_argnums,
            policy=resolve_remat_policy(
                cfg.remat_policy, remat_names or MODEL_CHECKPOINT_NAMES))
    for i, args in enumerate(per_layer):
        layer = block(cfg, *args, name=f"{prefix}_{i}")
        if handed is None:
            x = layer(x, *shared)
        else:
            x, handed = layer(x, handed, *shared)
    return x


def untied_head(x, vocab_size: int, dtype):
    """Logits of the normed stream x through the head's own matrix."""
    return untied_heads([x], vocab_size, dtype)[0]


def untied_heads(xs, vocab_size: int, dtype) -> list:
    """Logits of several normed streams through ONE head matrix (a
    multi-token-prediction module shares the trunk's)."""
    head = nn.Dense(vocab_size, use_bias=False, dtype=dtype, name="lm_head")
    with jax.named_scope("head"):
        return [head(x) for x in xs]


def tied_head(x, table, dtype, scaled=None):
    """Logits of the normed stream x against the embedding table, which
    takes the lookup's gradient and the head's.  The product sits in no
    flax module, so the scope is what names its ops in the compiled step
    (analysis/hlo_scopes.py); `scaled` is applied to the logits under it
    (Granite's division)."""
    with jax.named_scope("head"):
        logits = jnp.einsum("bte,ve->btv", x, table.astype(dtype))
        return logits if scaled is None else scaled(logits)


def init_params(model, rng, batch: int, seq: int):
    """The model's parameter tree, drawn on a batch of zeros."""
    return model.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]
