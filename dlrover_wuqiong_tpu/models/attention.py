"""Attention dispatch for model modules: flash / ring / Ulysses.

Parity: reference module-replace optimization swapping attention impls
in place (atorch `auto/opt_lib/module_replace_optimization.py:1-120`
REPLACEMENT_PAIRS) and its distributed attention dispatch
(`modules/distributed_modules/transformer.py:1`).  TPU redesign: instead
of swapping nn.Module classes post-hoc, the model config carries
`attn_impl` ("flash" | "ring" | "ulysses") and, for the SP impls, the
`mesh` whose `sp` axis shards the sequence.  Two more fields reach the
kernels the same way, read here and nowhere else: `attn_scale` (the
softmax's scale) and `attn_window` (a sliding window: ring attention
refuses one, Ulysses and the shard_map of a mesh pass it through).  The
`sequence_parallel` strategy (auto/accelerate.py:424) rewrites the
first two so the same model definition runs single-chip, GSPMD-sharded,
or context-parallel (parallel/long_context.py) without code changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import mosaic
from ..ops.flash_attention import (
    causal_tile_count,
    flash_attention_projected,
    mha,
    projected_ok,
)
from .sown import counters, sown, term


def goes_direct(cfg, n_head: int, head_dim: int, seq: int) -> bool:
    """Whether a self-attention of this shape, under this config, takes
    the DIRECT route: the kernels take it (`ops/flash_attention.
    projected_ok`: heads on 128-lane slabs, one TPU device, a sequence a
    block fits), and `attend` would have ended in `mha`'s kernels — not
    over ring or Ulysses, which a config with a mesh may name."""
    mesh = getattr(cfg, "mesh", None)
    flash = mesh is None or getattr(cfg, "attn_impl", "flash") == "flash"
    return flash and projected_ok(n_head, head_dim, seq, mesh=mesh)


def softmax_scale(cfg):
    """The scale the kernels are handed: `cfg.attn_scale` where a config
    has the field and sets it (Granite's `attention_multiplier`), else
    None, which every entry reads as 1/sqrt(head size)."""
    return getattr(cfg, "attn_scale", 0.0) or None


def attention_window(cfg):
    """The window the kernels are handed: `cfg.attn_window` where a
    config has the field and sets it (a sliding-window layer: a query
    sees that many keys, its own the last), else None: every key at or
    before the query."""
    return getattr(cfg, "attn_window", 0) or None


def window_tiles(cfg, batch: int, n_head: int, seq: int):
    """(score tiles a windowed layer's kernels compute in one pass over
    `batch` sequences, what a causal call of the same shape would), or
    None for a layer without a window: `causal_tile_count` at the
    kernels' own blocks — the count the kernels' grids are PLANNED from,
    static numbers, not a count of what ran: whether the kernels skip
    what the plan skips is what `kernel.attn_window_ms` and the compiled
    grid (tests/test_program_from_arguments.py) show.  What
    `LlamaAttention` sows as `attn_tiles` and `collect_attention_stats`
    sums."""
    window = attention_window(cfg)
    if window is None:
        return None
    return tuple(batch * n_head * causal_tile_count(seq, seq, window=w)[0]
                 for w in (window, None))


def window_pairs(cfg, batch: int, n_head: int, seq: int):
    """((query, key) pairs a windowed layer KEEPS in one pass over
    `batch` sequences, pairs in the score tiles its kernels compute), or
    None for a layer without a window: the band's window * seq - window
    * (window - 1) / 2 a head, beside `window_tiles`' count at the side
    of the tile it is counted in.  What a tile's grain costs: a window of
    half a tile keeps about half of what the tiles on its band hold.
    Static numbers, as `window_tiles`'; sown as `attn_pairs`."""
    window = attention_window(cfg)
    if window is None:
        return None
    w = min(window, seq)
    done, square = causal_tile_count(seq, seq, window=window)
    return (batch * n_head * (w * seq - w * (w - 1) // 2),
            batch * n_head * done * seq * seq // square)


@counters
def collect_attention_stats(intermediates) -> dict:
    """What the attention layers of one forward pass counted, summed over
    the layers — {} for a model that sows none of it: `attn_tiles_window`
    and `attn_tiles_causal` of the windowed layers (`window_tiles`),
    `attn_pairs_kept` and `attn_pairs_computed` of the same layers where
    they sow them (`window_pairs`), `attn_lanes_run` and
    `attn_lanes_model` of the latent ones (`models/latent_attention.py`:
    the lanes a score entry's two products run as the kernels block
    them, and the lanes the model's widths ask), and `attn_gate_mean`,
    the gated layers' mean output gate (`LlamaConfig.attn_gate`), with
    `attn_gate_kernel_share`, the share of those layers whose multiply
    took `ops/head_gate.py`'s kernels (`gate_route`: counted, not
    timed).  Of the layers under a learned choice of keys
    (`LlamaConfig.attn_index_topk`): `attn_sparse_kept` and
    `attn_sparse_causal`, the (query, key) pairs a sequence-layer keeps
    and its causal pairs; `attn_sparse_live_tiles` and
    `attn_sparse_tiles_causal`, the score tiles of the kernels' block
    that hold a kept pair — data, counted from the step's own choice —
    beside the causal ones; `attn_sparse_tiles_run`, the tiles the
    implementation computes; and `index_kl`, the layers' mean KL term
    (`collect_attention_aux_loss` is what joins the loss).  Of the
    layers under block-diffusion's mask (`LlamaConfig.
    attn_block_diffusion`): `attn_bd_tiles_run` and `attn_bd_tiles_live`,
    the score tiles the route computes and those that hold a kept pair,
    `attn_bd_pairs_kept` and `attn_bd_pairs_computed`
    (`ops/block_attention.bd_tile_count`)."""
    stats = {}
    for under, names in (
            ("attn_tiles", ("attn_tiles_window", "attn_tiles_causal")),
            ("attn_pairs", ("attn_pairs_kept", "attn_pairs_computed")),
            ("attn_lanes", ("attn_lanes_run", "attn_lanes_model")),
            ("attn_bd", ("attn_bd_tiles_run", "attn_bd_tiles_live",
                         "attn_bd_pairs_kept", "attn_bd_pairs_computed"))):
        pairs = [v.reshape(-1, len(names))
                 for v in sown(intermediates, under)]
        if pairs:
            with jax.named_scope(under):  # the sum's copies get an owner
                stats.update(zip(names, jnp.concatenate(pairs).sum(0)))
    sparse = [v.reshape(-1, 5) for v in sown(intermediates, "attn_sparse")]
    if sparse:
        with jax.named_scope("attn_sparse"):
            stats.update(zip(
                ("attn_sparse_kept", "attn_sparse_causal",
                 "attn_sparse_live_tiles", "attn_sparse_tiles_causal",
                 "attn_sparse_tiles_run"), jnp.concatenate(sparse).sum(0)))
            stats["index_kl"] = jnp.stack([
                v.reshape(()) for v in sown(intermediates,
                                            "attn_index_kl")]).mean()
    gates = [v.reshape(()) for v in sown(intermediates, "attn_gate_mean")]
    # `LlamaAttention`'s gates say which route they took; latent
    # attention's, lines of its own on another layout, have no other
    took = [v.reshape(()) for v in sown(intermediates, "attn_gate_kernel")]
    if gates:
        with jax.named_scope("attn_gate_mean"):
            if len(took) == len(gates):
                # ONE mean over the layers' (gate, took) pairs: a static
                # share on its own folds to a constant the step returns
                # through a copy that nothing names
                pairs = jnp.stack([jnp.stack(gates), jnp.stack(took)], 1)
                stats["attn_gate_mean"], stats["attn_gate_kernel_share"] \
                    = pairs.mean(0)
            else:
                stats["attn_gate_mean"] = jnp.stack(gates).mean()
    return stats


@term
def collect_attention_aux_loss(intermediates, batch, ce):
    """The loss's term of the sparse layers, or None for a model without
    one: the sum of the sown `attn_index_loss` leaves — the layers'
    weighted KL terms, which reach the indexers' leaves alone — and
    nothing else an attention layer sows; beside it `ce`, the bare
    cross-entropy: the two terms, seen apart."""
    terms = [jnp.sum(v) for v in sown(intermediates, "attn_index_loss")]
    if not terms:
        return None
    return sum(terms, jnp.zeros((), jnp.float32)), {"ce": ce}


def attend_projected(proj, n_head: int, cfg, causal: bool = True):
    """Self-attention on the projections' own layout: `proj` is (qkv,),
    one (b, T, 3*h*d) array of q, k and v side by side (`c_attn`'s
    output), or (q, k, v), (b, T, h*d) each — k and v their own
    (b, T, n_kv*d) where the call goes direct and
    `ops/flash_attention.kv_route` says "indexed" (grouped heads, a head
    a slab: the kernels hand a group's query heads one kv slab and
    nothing is repeated; the caller asks both, `models/llama.
    LlamaAttention`); returns (b, T, h*d) for the output projection.

    Which route a call takes is its shape and where it runs
    (`goes_direct`), nothing else:

    - heads on 128-lane slab boundaries (d % 128 == 0, or an even number
      of heads of 64), one device, on the TPU: DIRECT.  The kernels index
      the arrays as they are (`flash_attention_projected`); nothing is
      split, reshaped to heads or transposed, forward or backward.
      GPT-2 124M (12 x 64: two heads a slab), OLMoE (16 x 128: a head a
      slab) and SmallThinker (28 x 128 over 4 kv heads, k and v 512
      lanes wide) go this way.
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
                                         softmax_scale(cfg),
                                         attention_window(cfg))
    if len(proj) == 1:
        proj = jnp.split(proj[0], 3, axis=-1)
    q, k, v = (x.reshape(b, t, n_head, d) for x in proj)
    return attend(q, k, v, cfg, causal=causal).reshape(b, t, lanes)


def attend(q, k, v, cfg, causal: bool = True):
    """q/k/v in flax layout (b, T, h, d) — v, and what comes back, may
    have a width of their own on one device — returns (b, T, h, d): the
    TRANSPOSED route of every impl — ring and Ulysses over the mesh's
    `sp` axis, the kernels inside a shard_map on any other multi-device
    mesh, `mha` on one device (the kernels on (b, h, T, d) on the TPU,
    the jnp reference off it)."""
    impl = getattr(cfg, "attn_impl", "flash")
    mesh = getattr(cfg, "mesh", None)
    scale, window = softmax_scale(cfg), attention_window(cfg)
    if q.shape[-1] != v.shape[-1] and mesh is not None and mesh.size > 1:
        raise ValueError(
            f"q and k {q.shape[-1]} wide beside v {v.shape[-1]} (latent "
            f"attention) runs on one device: ring, Ulysses and the "
            f"shard_map of a mesh are handed one width")
    if impl in ("ring", "ulysses") and mesh is not None:
        from ..parallel.long_context import ring_attention, ulysses_attention

        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if impl == "ring":
            if window is not None:
                # its chunks meet as whole blocks, gated by the ONE
                # diagonal; a window would cut some of them short
                raise ValueError("ring attention knows no window: a "
                                 "windowed layer takes ulysses or flash")
            out = ring_attention(qt, kt, vt, mesh, causal=causal,
                                 sm_scale=scale)
        else:
            out = ulysses_attention(qt, kt, vt, mesh, causal=causal,
                                    sm_scale=scale, window=window)
        return out.transpose(0, 2, 1, 3)
    if mosaic.kernel_site(mesh) in ("mesh", "manual"):
        # the Pallas kernels need a shard_map on a multi-device mesh; the
        # jnp reference off-TPU is partitioned by GSPMD like any other op
        from ..parallel.long_context import sharded_flash_attention

        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return sharded_flash_attention(
            qt, kt, vt, mesh, causal=causal, sm_scale=scale,
            window=window).transpose(0, 2, 1, 3)
    return mha(q, k, v, causal=causal, sm_scale=scale, window=window)
