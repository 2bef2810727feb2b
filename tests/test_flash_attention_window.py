"""A WINDOWED attention call keeps a band between two diagonals
(`ops/flash_attention._window_tiles`, `_window_plan`): interpret-mode
parity of the forward and all three gradients with the plain reference
under the mask written out — on both layouts, at windows that are and
are not multiples of the block and of the tile, on the narrowed grid and
on blocks the grid cannot place — the tile count against a brute-force
one, and the public entries' jnp paths.  The cases sit beside
tests/test_flash_attention_tiles.py's and use its inputs, kernels and
reference; they are a file of their own so that another worker runs them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_flash_attention_tiles import (
    _inputs,
    _kernels,
    _reference,
    _route,
    _several,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa


# ------------------------------------------------------------- a window
#
# CASES' columns with a window in place of `causal` (a windowed call is
# causal): windows that are a multiple of the block, of the tile alone,
# of neither; shorter than a block, than a tile, one key; sq != sk both
# ways; blocks the grid cannot place (block_q != block_k: masked whole by
# grid position); one block each way (the fused backward) tiled and whole

WINDOWED = [
    # sq, sk, block_q, block_k, tile, window, bh, d, form, heads
    (256, 256, 64, 64, 16, 128, 2, 64, None, 0),     # two blocks
    (256, 256, 64, 64, 16, 64, 2, 64, None, 0),      # one block
    (256, 256, 64, 64, 16, 96, 2, 64, None, 0),      # tiles, not blocks
    (256, 256, 64, 64, 16, 100, 2, 64, None, 0),     # neither
    (256, 256, 64, 64, 16, 40, 2, 64, None, 0),      # under a block
    (256, 256, 64, 64, 16, 8, 2, 64, None, 0),       # under a tile
    (256, 256, 64, 64, 16, 1, 2, 64, None, 0),       # its own key alone
    (512, 512, 64, 64, 16, 128, 8, 64, None, 0),     # pack 8: head loop
    (256, 256, 128, 128, 32, 100, 4, 128, None, 0),
    (128, 256, 64, 64, 16, 100, 2, 64, None, 0),     # kv_offset > 0
    (256, 128, 64, 64, 16, 100, 2, 64, None, 0),     # kv_offset < 0
    (256, 256, 64, 128, 16, 100, 2, 64, None, 0),    # no static place
    (256, 256, 64, 64, 64, 100, 2, 64, None, 0),     # blocks kept whole
    (128, 128, 128, 128, 32, 64, 4, 128, None, 0),   # one block each way
    (128, 128, 128, 128, 32, 50, 3, 64, None, 0),
    (128, 128, 128, 128, 128, 50, 2, 64, None, 0),
    (64, 128, 64, 128, 32, 40, 2, 64, None, 0),
    # the projections' own layout, one head and two a slab
    (256, 256, 64, 64, 16, 128, 8, 64, "qkv", 4),
    (256, 256, 64, 64, 16, 100, 4, 64, "q,k,v", 2),
    (256, 256, 128, 128, 32, 100, 2, 128, "q,k,v", 2),
    (256, 256, 64, 64, 16, 40, 6, 128, "qkv", 3),
    (128, 128, 128, 128, 32, 50, 4, 64, "q,k,v", 2),
    (128, 128, 128, 128, 32, 64, 6, 128, "qkv", 3),
]


# a several-block case runs its backward on both routes: the ONE sweep
# that gives dq too (the rule's answer at these shapes), and the pair
ROUTED = [c + (route,) for c in WINDOWED for route in (
    ("fused", "split") if _several(*c[:4]) else ("one",))]


@pytest.mark.parametrize(
    "sq,sk,block_q,block_k,tile,window,bh,d,form,heads,route", ROUTED)
def test_windowed_forward_and_all_three_gradients_match_the_mask(
        sq, sk, block_q, block_k, tile, window, bh, d, form, heads, route):
    """Forward, lse, dq, dk and dv of a windowed call — on a narrowed
    grid the fused sweep or the split kernels, or the one-block fused
    one — against `jax.grad` of the plain reference under the mask
    written out."""
    q, k, v, g, _ = _inputs(sq, sk, bh, d, seed=3)
    scale = d ** -0.5
    assert fa._effective_window(window, True, sk) == window
    forward, backward = _kernels(form, heads, q, k, v)
    o, lse = forward(q, k, v, True, scale, block_q, block_k,
                     interpret=True, tile=tile, window=window)
    dq, dk, dv = backward(q, k, v, o, lse, g, True, scale, block_q, block_k,
                          interpret=True, tile=tile, window=window,
                          **_route(route, sq, sk, block_q, block_k, bh, d, d,
                                   form, heads))
    ro, rlse, (rq, rk, rv) = _reference(q, k, v, g, None, True, scale,
                                        window)
    kept = np.asarray(fa.kept_mask(sq, sk, window))
    i, j = np.arange(sq)[:, None] + sk - sq, np.arange(sk)[None, :]
    np.testing.assert_array_equal(kept, (j <= i) & (i - j < window))
    np.testing.assert_allclose(o, ro, atol=2e-5)
    seen = kept.any(-1)  # sq > sk: rows that see no key read -inf
    np.testing.assert_allclose(lse[:, 0][:, seen], rlse[:, seen], atol=2e-5)
    np.testing.assert_allclose(dq, rq, atol=5e-4)
    np.testing.assert_allclose(dk, rk, atol=5e-4)
    np.testing.assert_allclose(dv, rv, atol=5e-4)
    # and it is not the causal call's answer
    co, _, _ = _reference(q, k, v, g, None, True, scale)
    assert float(jnp.abs(co - o).max()) > 1e-2


@pytest.mark.parametrize("sq,sk,block_q,block_k,tile,window", [
    c[:6] for c in WINDOWED if c[8] is None] + [
    (16384, 16384, 1024, 1024, None, 4096),   # the benchmark cell's layers
    (16384, 16384, 1024, 1024, None, 4000),
    (4096, 4096, 1024, 1024, 256, 1000),
    (2048, 2048, 1024, 1024, None, 4096),     # no shorter than the keys
])
def test_windowed_tile_count_against_a_brute_force_count(
        sq, sk, block_q, block_k, tile, window):
    """`causal_tile_count(window=...)`: every tile that holds a kept
    entry and no other — or, where the blocks stay whole (no tile, or no
    static place), every such block."""
    done, of = fa.causal_tile_count(sq, sk, block_q, block_k, tile, window)
    side = fa._causal_tile(block_q, block_k, tile)
    lattice = fa._diag_offset(sq // block_q, sk // block_k, block_q,
                              block_k, sk - sq) is not None
    tq, tk = (side, side) if side and lattice else (block_q, block_k)
    j = np.arange(sk, dtype=np.int32)[None, :]
    held = 0
    for q0 in range(0, sq, tq):  # a row of tiles at a time: 16,384^2 is 268M
        dist = np.arange(q0, q0 + tq, dtype=np.int32)[:, None] + sk - sq - j
        kept = (dist >= 0) & (dist < window)
        held += int(kept.reshape(tq, sk // tk, tk).any(axis=(0, 2)).sum())
    assert (done, of) == (held, (sq // tq) * (sk // tk))
    if window >= sk:
        assert (done, of) == fa.causal_tile_count(sq, sk, block_q, block_k,
                                                  tile)


def test_the_cells_windowed_layers_skip_more_than_half_a_causal_call():
    """T = 16,384, window 4,096, blocks of 1,024 in tiles of 512: a query
    block runs its own block at 3 of 4 tiles, three whole and the one
    the window's diagonal crosses at 3 of 4 (4.5 blocks' work in 5 grid
    steps), where a causal call runs 8.5 on average."""
    assert fa.causal_tile_count(16384, 16384) == (528, 1024)
    assert fa.causal_tile_count(16384, 16384, window=4096) == (252, 1024)
    plan = fa._window_plan(4096, 16, 16, 1024, 1024, 0)
    assert plan == {"window": 4096, "steps": 5, "koff": 0,
                    "crossed": ((0, 0), (4, 4096)), "whole": (1, 3)}
    # off the block: two blocks a query block are crossed below
    assert fa._window_plan(4000, 16, 16, 1024, 1024, 0)["crossed"] == (
        (0, 0), (3, 3072), (4, 4096))
    assert fa._window_plan(None, 16, 16, 1024, 1024, 0) == {}
    for which in ("fwd", "bwd_dq", "bwd_dkv", "bwd_fused"):
        assert fa._kernel_name(which, None) == f"dwt_fa_{which}"
        assert fa._kernel_name(which, 4096) == f"dwt_fa_win_{which}"


def test_a_window_belongs_to_a_causal_call():
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, False, None, window=8)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, True, None, window=0)


@pytest.mark.parametrize("window", [24, 200])
def test_the_public_entries_take_a_window_off_the_tpu(window):
    """`flash_attention` / `mha` on the jnp paths (dense, and streamed
    past 2048^2), forward and gradients."""
    t = {24: 64, 200: 2048}[window]
    q, k, v, g, _ = _inputs(t, t, 2, 16, seed=4)

    def run(q, k, v):
        return (fa.flash_attention(q[None], k[None], v[None], True, None,
                                   window=window)[0] * g).sum()

    assert fa._use_streamed(t, t) == (t == 2048)
    got = jax.grad(run, argnums=(0, 1, 2))(q, k, v)
    _, _, want = _reference(q, k, v, g, None, True, 0.25, window)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-4)

