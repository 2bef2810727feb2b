"""Attention dispatch for model modules: flash / ring / Ulysses.

Parity: reference module-replace optimization swapping attention impls
in place (atorch `auto/opt_lib/module_replace_optimization.py:1-120`
REPLACEMENT_PAIRS) and its distributed attention dispatch
(`modules/distributed_modules/transformer.py:1`).  TPU redesign: instead
of swapping nn.Module classes post-hoc, the model config carries
`attn_impl` ("flash" | "ring" | "ulysses") and, for the SP impls, the
`mesh` whose `sp` axis shards the sequence.  The `sequence_parallel`
strategy (auto/accelerate.py:424) rewrites these fields so the same
model definition runs single-chip, GSPMD-sharded, or context-parallel
(parallel/long_context.py) without code changes.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops.flash_attention import (
    _on_tpu,
    flash_attention_projected,
    mha,
    projected_ok,
)


def goes_direct(cfg, n_head: int, head_dim: int, seq: int) -> bool:
    """Whether a self-attention of this shape, under this config, takes
    the DIRECT route: the kernels take it (`ops/flash_attention.
    projected_ok`: heads on 128-lane slabs, on the TPU, a sequence a
    block fits), and it runs where `attend` would have ended in `mha`'s
    kernels — on one device, not over ring, Ulysses or a shard_map."""
    mesh = getattr(cfg, "mesh", None)
    on_one_device = mesh is None or (
        mesh.size == 1 and getattr(cfg, "attn_impl", "flash") == "flash")
    return on_one_device and projected_ok(n_head, head_dim, seq)


def softmax_scale(cfg):
    """The scale the kernels are handed: `cfg.attn_scale` where a config
    has the field and sets it (Granite's `attention_multiplier`), else
    None, which every entry reads as 1/sqrt(head size)."""
    return getattr(cfg, "attn_scale", 0.0) or None


def attend_projected(proj, n_head: int, cfg, causal: bool = True):
    """Self-attention on the projections' own layout: `proj` is (qkv,),
    one (b, T, 3*h*d) array of q, k and v side by side (`c_attn`'s
    output), or (q, k, v), (b, T, h*d) each; returns (b, T, h*d) for the
    output projection.

    Which route a call takes is its shape and where it runs
    (`goes_direct`), nothing else:

    - heads on 128-lane slab boundaries (d % 128 == 0, or an even number
      of heads of 64), one device, on the TPU: DIRECT.  The kernels index
      the arrays as they are (`flash_attention_projected`); nothing is
      split, reshaped to heads or transposed, forward or backward.
      GPT-2 124M (12 x 64: two heads a slab) and OLMoE (16 x 128: a head
      a slab) go this way.
    - every other call: q, k and v are cut to (b, T, h, d) and go through
      `attend`, as they always did — an odd number of heads of 64 (GPT-2
      XL's 25), a head size off the slab, ring and Ulysses attention, the
      shard_map of a multi-device mesh, and the jnp reference off the
      TPU.
    """
    b, t = proj[0].shape[:2]
    lanes = proj[0].shape[-1] // (3 if len(proj) == 1 else 1)
    d = lanes // n_head
    if goes_direct(cfg, n_head, d, t):
        return flash_attention_projected(proj, n_head, causal,
                                         softmax_scale(cfg))
    if len(proj) == 1:
        proj = jnp.split(proj[0], 3, axis=-1)
    q, k, v = (x.reshape(b, t, n_head, d) for x in proj)
    return attend(q, k, v, cfg, causal=causal).reshape(b, t, lanes)


def attend(q, k, v, cfg, causal: bool = True):
    """q/k/v in flax layout (b, T, h, d); returns (b, T, h, d): the
    TRANSPOSED route of every impl — ring and Ulysses over the mesh's
    `sp` axis, the kernels inside a shard_map on any other multi-device
    mesh, `mha` on one device (the kernels on (b, h, T, d) on the TPU,
    the jnp reference off it)."""
    impl = getattr(cfg, "attn_impl", "flash")
    mesh = getattr(cfg, "mesh", None)
    scale = softmax_scale(cfg)
    if impl in ("ring", "ulysses") and mesh is not None:
        from ..parallel.long_context import ring_attention, ulysses_attention

        fn = ring_attention if impl == "ring" else ulysses_attention
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return fn(qt, kt, vt, mesh, causal=causal,
                  sm_scale=scale).transpose(0, 2, 1, 3)
    if mesh is not None and mesh.size > 1 and _on_tpu():
        # the Pallas kernels need a shard_map on a multi-device mesh; the
        # jnp reference off-TPU is partitioned by GSPMD like any other op
        from ..parallel.long_context import sharded_flash_attention

        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return sharded_flash_attention(
            qt, kt, vt, mesh, causal=causal,
            sm_scale=scale).transpose(0, 2, 1, 3)
    return mha(q, k, v, causal=causal, sm_scale=scale)
