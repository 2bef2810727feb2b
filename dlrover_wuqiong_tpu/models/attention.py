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

from ..ops.flash_attention import _on_tpu, mha


def attend(q, k, v, cfg, causal: bool = True):
    """q/k/v in flax layout (b, T, h, d); returns (b, T, h, d)."""
    impl = getattr(cfg, "attn_impl", "flash")
    mesh = getattr(cfg, "mesh", None)
    if impl in ("ring", "ulysses") and mesh is not None:
        from ..parallel.long_context import ring_attention, ulysses_attention

        fn = ring_attention if impl == "ring" else ulysses_attention
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return fn(qt, kt, vt, mesh, causal=causal).transpose(0, 2, 1, 3)
    if mesh is not None and mesh.size > 1 and _on_tpu():
        # the Pallas kernels need a shard_map on a multi-device mesh; the
        # jnp reference off-TPU is partitioned by GSPMD like any other op
        from ..parallel.long_context import sharded_flash_attention

        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return sharded_flash_attention(qt, kt, vt, mesh, causal=causal
                                       ).transpose(0, 2, 1, 3)
    return mha(q, k, v, causal=causal)
