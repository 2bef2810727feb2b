"""Long-context sequence/context parallelism: ring attention + Ulysses SP.

Parity: the reference's two long-context mechanisms (SURVEY.md §5) —
(1) blockwise distributed attention with global softmax over the SP group
(atorch `modules/distributed_transformer/distributed_attention.py:21-312`,
`DistributedSoftmax`, `DistributedSelfAttention`), and (2) Ulysses-style
sequence parallelism via all-to-all head scatter (atorch
`distributed/distributed.py:435-502`, `_SeqAllToAll`).

TPU redesign:
- **Ring attention** (`ring_attention`): sequence sharded over the mesh's
  `sp` axis; KV shards rotate around the ring with `jax.lax.ppermute` (rides
  ICI neighbor links) while each device accumulates blockwise attention of
  its local Q against the visiting KV chunk with the Pallas flash kernel.
  Partial results merge with the standard logsumexp combine, so memory is
  O(seq/sp) per device and the full score matrix never exists.  This is the
  true ring version of the reference's blockwise attention (which all-reduces
  softmax stats instead of rotating KV).
- **Ulysses** (`ulysses_attention`): `jax.lax.all_to_all` scatters heads /
  gathers sequence so each device runs full-sequence attention on h/sp heads,
  then the inverse all-to-all restores the sequence sharding.  One collective
  pair per attention, best when h >= sp and sequence moderately long.

Both are written against `shard_map` (functional SPMD) so they compose with
the GSPMD-sharded rest of the model, and both differentiate (ppermute and
all_to_all have registered transposes; the flash kernel has a custom VJP).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import flash_attention

def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=_context_mesh(mesh), in_specs=in_specs,
                         out_specs=out_specs, check_vma=True)


def _context_mesh(mesh: "Mesh"):
    """Nested-shard_map mesh resolution — see parallel/mesh.py
    context_mesh (shared with the pipeline)."""
    from .mesh import context_mesh

    return context_mesh(mesh)


_BATCH_AXES = ("dp", "fsdp")  # mesh data axes (parallel/mesh.py AXIS_ORDER)


def _qkv_spec(mesh: Mesh, seq_axis: Optional[str], batch_size: int) -> P:
    """(b, h, S, d) spec: seq over `seq_axis`, batch over the mesh's data
    axes.  Leaving batch unsharded would all-gather the global batch to every
    device at the shard_map boundary and redundantly compute attention over
    it, breaking the O(S/sp) memory claim under dp/fsdp>1.  Axes that don't
    divide the batch are dropped (shard_map requires even division)."""
    batch = []
    div = 1
    for a in _BATCH_AXES:
        n = mesh.shape.get(a, 1) if a in mesh.axis_names else 1
        if n > 1 and batch_size % (div * n) == 0:
            batch.append(a)
            div *= n
    return P(tuple(batch) if batch else None, None, seq_axis, None)


def sharded_flash_attention(q, k, v, mesh: Mesh, causal: bool = True,
                            sm_scale: Optional[float] = None,
                            window: Optional[int] = None):
    """`flash_attention` for (b, h, S, d) operands that live on a mesh of
    more than one device.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under any sharded strategy the
    Pallas call has to sit inside a shard_map: batch over the mesh's
    data axes, heads over `tp` when they divide, the sequence whole —
    each device runs the kernel on its own (b_local, h_local) slab, no
    collective.  A sequence sharded over `sp` by GSPMD is gathered at
    the boundary (ring/ulysses are the paths that keep it sharded).  A
    `window` goes to the kernel as it is: every device holds whole
    sequences."""
    spec = _qkv_spec(mesh, None, q.shape[0])
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and q.shape[1] % tp == 0:
        spec = P(spec[0], "tp", None, None)
    fn = shard_map(
        functools.partial(flash_attention, causal=causal,
                          sm_scale=sm_scale, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


# ------------------------------------------------------------- lse utilities


def _merge_partials(o1, lse1, o2, lse2):
    """Combine two blockwise attention partials over disjoint key sets."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(lse1 - m_safe), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(lse2 - m_safe), 0.0)
    tot = w1 + w2
    tot_safe = jnp.where(tot > 0, tot, 1.0)
    o = (o1.astype(jnp.float32) * (w1 / tot_safe)[..., None]
         + o2.astype(jnp.float32) * (w2 / tot_safe)[..., None])
    lse = jnp.where(tot > 0, m_safe + jnp.log(tot_safe), -jnp.inf)
    return o.astype(o1.dtype), lse


# -------------------------------------------------------------- ring attention


def _chunk_attention(q, k, v, causal: bool, sm_scale: Optional[float]):
    """(o, lse) for one KV chunk — the Pallas kernel on TPU (O(s_local) VMEM
    working set, no score matrix in HBM), jnp reference elsewhere."""
    from ..ops import flash_attention as fa
    from ..ops import mosaic

    if mosaic.kernel_site() != "off":  # a ring step is inside a shard_map
        return fa.flash_attention_with_lse(q, k, v, causal, sm_scale)
    return fa._reference_with_lse(
        q, k, v, causal, fa._resolve_scale(sm_scale, q.shape[-1]))


def _ring_attention_local(q, k, v, *, axis_name: str, n: int, causal: bool,
                          sm_scale: Optional[float]):
    """Per-device body under shard_map: q/k/v are the local seq shards
    (b, h, s_local, d).  The ring is unrolled (n is the static sp size) so
    the whole loop differentiates through ppermute's transpose.

    Step 0 attends the local chunk (causal within); steps 1..n-1 receive
    rotated KV from chunk src=(my-t)%n — never the local chunk again — so
    they run the cheaper non-causal kernel, gated to earlier chunks only by
    zeroing the merge weight (lse=-inf) for src > my.  The accumulator stays
    f32 across merges (no per-step requantization)."""
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    o0, lse = _chunk_attention(q, k, v, causal, sm_scale)
    o = o0.astype(jnp.float32)
    k_cur, v_cur = k, v

    for t in range(1, n):
        # rotate KV to the next device (ICI neighbor ring)
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my - t) % n  # which global seq chunk this KV shard holds
        oc, lc = _chunk_attention(q, k_cur, v_cur, False, sm_scale)
        if causal:
            lc = jnp.where(src < my, lc, -jnp.inf)
        o, lse = _merge_partials(o, lse, oc, lc)
    return o.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   axis: str = "sp"):
    """Context-parallel attention; q/k/v (b, h, S, d) seq-sharded over `axis`.

    Returns (b, h, S, d) with the same sharding.  Memory per device is
    O(S/sp); the KV ring rides ICI neighbor links.
    """
    n = mesh.shape.get(axis, 1)
    if n == 1:
        return flash_attention(q, k, v, causal, sm_scale)

    spec = _qkv_spec(mesh, axis, q.shape[0])
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis, n=n,
                          causal=causal, sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


# ------------------------------------------------------------------- Ulysses


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool,
                   sm_scale: Optional[float], window: Optional[int] = None):
    """Per-device body: q/k/v (b, h, s_local, d) → all-to-all to
    (b, h/sp, S, d), full-seq attention, inverse all-to-all."""
    # scatter heads (axis 1), gather sequence (axis 2)
    qh = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    o = flash_attention(qh, kh, vh, causal, sm_scale, window=window)
    # scatter sequence back, gather heads
    return jax.lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      axis: str = "sp", window: Optional[int] = None):
    """Ulysses-style SP attention (parity `_SeqAllToAll` distributed.py:474).

    q/k/v (b, h, S, d) seq-sharded over `axis`; heads must divide the axis
    size.  Each device computes full-sequence attention for h/sp heads,
    so a `window` goes to the kernel as it is.
    """
    sp = mesh.shape.get(axis, 1)
    if sp == 1:
        return flash_attention(q, k, v, causal, sm_scale, window=window)
    if q.shape[1] % sp:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by {axis}={sp}")

    spec = _qkv_spec(mesh, axis, q.shape[0])
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                          sm_scale=sm_scale, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
