"""Selective activation checkpointing + host offload policies.

Parity: reference
`atorch/atorch/auto/opt_lib/selective_offloading_checkpoint.py:1-252`
(OffloadOpManager moving selected saved tensors to CPU) and
`atorch/atorch/modules/distributed_modules/activation_checkpointing.py:1-366`
(module-granular checkpoint wrapping).

TPU redesign: XLA already gives first-class hooks for both halves —
`jax.checkpoint` policies decide per-primitive what is SAVED vs RECOMPUTED,
and offload variants move the saved residuals to host memory
(`pinned_host` memory kind) instead of holding HBM.  The policy is a
config string resolved here, applied by the model's `nn.remat` wrapper, and
selected through `auto_accelerate`'s ("checkpoint", {...}) strategy:

    ("checkpoint", {})                          # full remat (recompute all)
    ("checkpoint", {"policy": "dots"})          # save matmul outputs in HBM
    ("checkpoint", {"policy": "offload_dots"})  # matmul outputs -> host
    ("checkpoint", {"policy": "save_names", "names": ["attn_out"]})
    ("checkpoint", {"policy": "offload_names", "names": ["attn_out"]})

The named policies key on `checkpoint_name` annotations the models place on
their attention/MLP block outputs ("attn_out", "mlp_out").
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

#: annotation names the in-tree models emit (models/gpt.py Block)
MODEL_CHECKPOINT_NAMES = ("attn_out", "mlp_out")


def resolve_remat_policy(policy: Optional[str],
                         names: Sequence[str] = MODEL_CHECKPOINT_NAMES):
    """Map a config string to a jax.checkpoint policy callable.

    Returns None for "full" — `jax.checkpoint` with no policy saves nothing
    and recomputes everything, the classic full-remat behavior.
    """
    if policy in (None, "", "full"):
        return None
    cp = jax.checkpoint_policies
    if policy == "dots":
        # save matmul outputs on device, recompute elementwise — the
        # standard "selective" policy: most recompute FLOPs are avoided
        # while activations shrink to the dot outputs
        return cp.dots_with_no_batch_dims_saveable
    if policy == "offload_dots":
        return cp.offload_dot_with_no_batch_dims("device", "pinned_host")
    if policy == "save_names":
        return cp.save_only_these_names(*names)
    if policy == "offload_names":
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(names),
            offload_src="device", offload_dst="pinned_host")
    raise ValueError(
        f"unknown remat policy {policy!r}; expected one of "
        "'full', 'dots', 'offload_dots', 'save_names', 'offload_names'")
