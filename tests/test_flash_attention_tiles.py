"""The causal kernels compute only the score tiles at or below the
diagonal of a block (`ops/flash_attention._causal_bands`): the tile set
against a brute-force mask, its count at the benchmark cells' shapes,
and interpret-mode parity of the tiled forward, the one-block, the fused
several-block and the split backward with the plain reference and its
`jax.grad` — on the transposed (bh, s, d) layout and on the projections'
own (b, s, h*d), one head or two a lane slab — and which of them a
backward call runs, from its shapes (`backward_route`).  A no-window
call's traced program is pinned; the WINDOWED cases, which reuse
this file's inputs, kernels and reference, are their own file so that
another worker runs them (tests/test_flash_attention_window.py).
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import flash_attention as fa


# ------------------------------------------------------------ the tile set

BLOCKS = [  # block_q, block_k, off, tile
    (1024, 1024, 0, 256), (1024, 1024, 0, 128), (1024, 1024, 0, 512),
    (128, 128, 0, 32), (64, 128, 64, 32), (128, 64, -64, 32),
    (128, 64, -32, 32), (96, 96, 7, 16), (96, 64, -41, 16),
    (64, 64, 0, 64), (64, 64, 0, None), (96, 96, 0, 64),
]


def _kept(block_q, block_k, off):
    r = np.arange(block_q)[:, None]
    c = np.arange(block_k)[None, :]
    return r + off >= c


@pytest.mark.parametrize("block_q,block_k,off,tile", BLOCKS)
def test_bands_cover_the_kept_entries_and_mask_only_the_crossed(
        block_q, block_k, off, tile):
    kept = _kept(block_q, block_k, off)
    seen = np.zeros_like(kept)
    tile = fa._causal_tile(block_q, block_k, tile)  # None: does not divide
    for q0, q1, k_full, k_end in fa._causal_bands(block_q, block_k, off,
                                                  tile):
        assert kept[q0:q1, :k_full].all()        # computed, never masked
        assert not kept[q0:q1, k_end:].any()     # never computed
        seen[q0:q1] = True
        if tile:
            # each crossed tile really is crossed
            for k0 in range(k_full, k_end, tile):
                part = kept[q0:q1, k0:k0 + tile]
                assert part.any() and not part.all() or off % tile
    assert seen.all()


@pytest.mark.parametrize("block_q,block_k,off,tile", BLOCKS)
def test_bands_by_key_are_the_same_tiles(block_q, block_k, off, tile):
    def rect(bands, by_keys):
        done = np.zeros((block_q, block_k), int)   # 1 unmasked, 2 masked
        for lo, hi, a, b in bands:
            if by_keys:
                done[a:b, lo:hi], done[b:, lo:hi] = 2, 1
            else:
                done[lo:hi, :a], done[lo:hi, a:b] = 1, 2
        return done

    tile = fa._causal_tile(block_q, block_k, tile)
    by_q = rect(fa._causal_bands(block_q, block_k, off, tile), False)
    by_k = rect(fa._causal_bands_t(block_q, block_k, off, tile), True)
    np.testing.assert_array_equal(by_q, by_k)
    kept = _kept(block_q, block_k, off)
    assert kept[by_k == 1].all() and not kept[by_k == 0].any()


@pytest.mark.parametrize("name,sq,want", [
    ("gpt2_124m.steady", 1024, (3, 4)),
    ("gpt2_xl.fsdp4_steady", 1024, (3, 4)),
    # 6 blocks below the diagonal whole, 4 on it at 3 of 4, 6 skipped
    ("olmoe_1b_7b.steady", 4096, (6 * 4 + 4 * 3, 64)),
])
def test_tile_count_at_the_cells_shapes(name, sq, want):
    assert fa._causal_tile(1024, 1024) == 512
    assert fa.causal_tile_count(sq, sq) == want
    # the finer tile of the sweep: 10 of 16 at T = 1024
    assert fa.causal_tile_count(sq, sq, tile=256) == {
        1024: (10, 16), 4096: (6 * 16 + 4 * 10, 256)}[sq]


@pytest.mark.parametrize("sq,sk,block_q,block_k,tile,want", [
    (1024, 1024, 1024, 1024, 128, (36, 64)),
    (1024, 1024, 1024, 1024, 512, (3, 4)),
    (1024, 1024, 1024, 1024, 1024, (1, 1)),      # the whole-block mask
    (4096, 4096, 1024, 1024, 512, (6 * 4 + 4 * 3, 64)),
    (256, 256, 64, 128, 16, (6, 8)),     # ragged blocks: counted whole
    (64, 128, 64, 128, 32, (2 * 3 + 1, 8)),      # kv_offset 64
    (128, 64, 128, 64, 32, (1 + 2, 8)),          # kv_offset -64
])
def test_tile_count_follows_the_bands(sq, sk, block_q, block_k, tile, want):
    assert fa.causal_tile_count(sq, sk, block_q, block_k, tile) == want


def test_blocks_too_small_for_the_tile_stay_whole():
    assert fa._causal_tile(512, 512) is None
    assert fa._causal_tile(768, 768) is None
    assert fa._causal_tile(2048, 1024) == 512
    assert fa.causal_tile_count(512, 512) == (1, 1)


# ------------------------------------------------- interpret-mode parity

# sq, sk, block_q, block_k, tile, causal, bh, d
CASES = [
    # one block each way, every pack, both head sizes
    (128, 128, 128, 128, 64, True, 8, 64),
    (128, 128, 128, 128, 32, True, 4, 128),
    (128, 128, 128, 128, 32, True, 2, 64),
    (128, 128, 128, 128, 16, True, 3, 64),
    # several blocks with a diagonal
    (256, 256, 64, 64, 16, True, 2, 64),
    (256, 256, 128, 128, 32, True, 4, 128),
    # kv_offset > 0: one block, and blocks whose diagonal is shifted
    (64, 128, 64, 128, 32, True, 2, 64),
    (128, 256, 64, 64, 16, True, 2, 64),
    # kv_offset < 0: rows that see no key at all
    (128, 64, 128, 64, 32, True, 2, 64),
    (256, 128, 64, 64, 16, True, 2, 64),
    # no static offset (block_q != block_k): the whole-block mask stays
    (256, 256, 64, 128, 16, True, 2, 64),
    # a tile as large as the block is the whole-block mask
    (128, 128, 128, 128, 128, True, 2, 64),
    # non-causal: nothing to skip
    (128, 128, 64, 64, 16, False, 2, 64),
    (128, 128, 128, 128, 32, False, 8, 64),
]

# the same kernels on the projections' own (b, s, h*d) layout
# (`fa._Slabs`): CASES' columns, then how the heads reach the kernel —
# "qkv": q, k and v side by side in ONE (b, s, 3*h*d) array, as GPT-2's
# c_attn leaves them; "q,k,v": three (b, s, h*d) arrays — and the heads
# a batch row has (bh / heads rows).  d = 64: two heads a slab, told
# apart by lane masks; d = 128: a head a slab
DIRECT = [
    # one block each way (the fused backward), tiled and whole
    (128, 128, 128, 128, 32, True, 8, 64, "qkv", 4),
    (128, 128, 128, 128, 128, True, 4, 64, "q,k,v", 2),
    (128, 128, 128, 128, 32, True, 4, 128, "q,k,v", 2),
    (128, 128, 128, 128, 64, True, 6, 128, "qkv", 3),
    # several blocks with a diagonal (the dq and dk/dv kernels)
    (256, 256, 64, 64, 16, True, 8, 64, "qkv", 4),
    (256, 256, 128, 128, 32, True, 2, 128, "q,k,v", 2),
    # sq != sk: shifted diagonal, rows that see no key
    (64, 128, 64, 128, 32, True, 4, 64, "q,k,v", 2),
    (256, 128, 64, 64, 16, True, 2, 64, "q,k,v", 2),
    # non-causal
    (128, 128, 64, 64, 16, False, 4, 64, "qkv", 4),
    (128, 128, 128, 128, 32, False, 2, 128, "q,k,v", 1),
]
CASES = [c + (None, 0) for c in CASES] + DIRECT


def _inputs(sq, sk, bh, d, seed=0):
    kq, kk, kv, kg, kl = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(kq, (bh, sq, d), jnp.float32)
    k = jax.random.normal(kk, (bh, sk, d), jnp.float32)
    v = jax.random.normal(kv, (bh, sk, d), jnp.float32)
    g = jax.random.normal(kg, (bh, sq, d), jnp.float32)
    gl = jax.random.normal(kl, (bh, 1, sq), jnp.float32)
    return q, k, v, g, gl


def _projected(x, heads):
    """(bh, s, d) by head -> (b, s, h*d), the heads side by side."""
    bh, s, d = x.shape
    return x.reshape(bh // heads, heads, s, d).transpose(0, 2, 1, 3).reshape(
        bh // heads, s, heads * d)


def _by_head(x, heads):
    b, s, lanes = x.shape
    return x.reshape(b, s, heads, lanes // heads).transpose(
        0, 2, 1, 3).reshape(b * heads, s, lanes // heads)


def _kernels(form, heads, q, k, v):
    """(forward, backward) on (bh, s, d) arrays whatever layout the
    kernels are handed: `fa._fa_forward_pallas` / `_fa_backward_pallas`
    themselves, or the same two behind the projected layout."""
    if form is None:
        return fa._fa_forward_pallas, fa._fa_backward_pallas
    proj = tuple(_projected(x, heads) for x in (q, k, v))
    if form == "qkv":
        proj = (jnp.concatenate(proj, axis=-1),)
    slabs, d = fa._projected_slabs(proj, heads)
    assert d == q.shape[-1]
    assert slabs.heads == (2 if d == 64 else 1)
    ops = fa._projected_operands(proj)

    def forward(q, k, v, *args, **kw):
        o, lse = fa._fa_forward_pallas(*ops, *args, slabs=slabs, **kw)
        return _by_head(o, heads), lse

    def backward(q, k, v, o, lse, g, *args, **kw):
        grads = fa._fa_backward_pallas(
            *ops, _projected(o, heads), lse, _projected(g, heads), *args,
            slabs=slabs, **kw)
        return tuple(_by_head(x, heads) for x in grads)

    return forward, backward


def _reference(q, k, v, g, gl, causal, scale, window=None):
    """o, lse and the gradients of sum(o * g) + sum(lse * gl) by
    `jax.grad` of the plain reference (the lse variant: a row that sees
    no key gives o = 0 there, not NaN)."""
    def loss(q, k, v):
        o, lse = fa._reference_with_lse(q[None], k[None], v[None], causal,
                                        scale, window)
        extra = 0.0 if gl is None else (lse[0] * gl[:, 0]).sum()
        return (o[0] * g).sum() + extra, (o[0], lse[0])

    (_, (o, lse)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return o, lse, grads


@pytest.mark.parametrize(
    "sq,sk,block_q,block_k,tile,causal,bh,d,form,heads", CASES)
def test_tiled_forward_matches_reference(sq, sk, block_q, block_k, tile,
                                         causal, bh, d, form, heads):
    q, k, v, g, _ = _inputs(sq, sk, bh, d)
    scale = d ** -0.5
    forward, _ = _kernels(form, heads, q, k, v)
    o, lse = forward(q, k, v, causal, scale, block_q, block_k,
                     interpret=True, tile=tile)
    ro, rlse, _ = _reference(q, k, v, g, None, causal, scale)
    np.testing.assert_allclose(o, ro, atol=2e-5)
    np.testing.assert_allclose(lse[:, 0], rlse, atol=2e-5)


# one block each way takes the one-block fused kernel; several blocks
# take ONE sweep that gives dq, dk and dv where a unit's whole dq fits
# VMEM (`fa.backward_route`: every shape here) and the dq and dk/dv pair
# where it does not.  Every one-block case runs both ways: as it is, and
# with its blocks halved (a 2 x 2 grid over the same arrays); every
# several-block case runs on both routes, the pair by `route=`
def _halved(case):
    sq, sk, block_q, block_k, tile, *rest = case
    return (sq, sk, block_q // 2, block_k // 2,
            min(tile, min(block_q, block_k) // 4), *rest)


_ONE_BLOCK = [c for c in CASES if c[:2] == c[2:4]]
BWD_CASES = [c + ("one",) for c in _ONE_BLOCK] + [
    (_halved(c) if c in _ONE_BLOCK else c) + (route,)
    for c in CASES for route in ("fused", "split")]
# "qkv" holds q and k in one array: sq == sk
assert all(c[0] == c[1] for c in CASES if c[8] == "qkv")


def _several(sq, sk, block_q, block_k) -> bool:
    return sq // block_q > 1 or sk // block_k > 1


def _route(route, sq, sk, block_q, block_k, bh, d, dv, form, heads) -> dict:
    """`_fa_backward_pallas`'s `route=` for a case's route: nothing
    where the rule itself gives it ("one" block; "fused", which every
    shape of these files is), the pair by hand."""
    assert (route != "one") == _several(sq, sk, block_q, block_k)
    slab_heads = fa.attention_route(heads, d)[1] if form else 0
    rule = fa.backward_route(sq, sk, d, dv, slab_heads, bh, block_q, block_k,
                             itemsize=4)
    # one block and the pair pack all they can, a several-block sweep at
    # most `_HEAD_GROUP` (PR 52)
    most = 1 if form else fa._fit_pack(bh)
    assert rule == ("fused", most if route == "one"
                    else min(most, fa._HEAD_GROUP))
    return {"route": ("split", most)} if route == "split" else {}


def _kernel_names(jaxpr):
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernel_names(sub)
    return sorted(names)


@pytest.mark.parametrize(
    "sq,sk,block_q,block_k,tile,causal,bh,d,form,heads,route", BWD_CASES)
def test_tiled_backward_matches_reference(sq, sk, block_q, block_k, tile,
                                          causal, bh, d, form, heads, route):
    q, k, v, g, _ = _inputs(sq, sk, bh, d, seed=1)
    scale = d ** -0.5
    forward, backward = _kernels(form, heads, q, k, v)
    o, lse = forward(q, k, v, causal, scale, block_q, block_k,
                     interpret=True, tile=tile)
    dq, dk, dv = backward(
        q, k, v, o, lse, g, causal, scale, block_q, block_k, interpret=True,
        tile=tile, **_route(route, sq, sk, block_q, block_k, bh, d, d, form,
                            heads))
    _, _, (rq, rk, rv) = _reference(q, k, v, g, None, causal, scale)
    np.testing.assert_allclose(dq, rq, atol=5e-4)
    np.testing.assert_allclose(dk, rk, atol=5e-4)
    np.testing.assert_allclose(dv, rv, atol=5e-4)


@pytest.mark.parametrize("sq,sk,block_q,block_k,tile,route", [
    (128, 128, 128, 128, 32, "one"),
    (128, 128, 64, 64, 16, "split"),
    (128, 128, 64, 64, 16, "fused"),
    (256, 256, 64, 64, 16, "split"),
    (256, 256, 64, 64, 16, "fused"),
    (64, 128, 64, 128, 32, "one"),
    (128, 256, 64, 64, 16, "split"),
    (128, 256, 64, 64, 16, "fused"),
])
def test_tiled_backward_takes_the_lse_cotangent(sq, sk, block_q, block_k,
                                                tile, route):
    """`flash_attention_with_lse`'s second cotangent, folded into delta,
    reaches every tile's ds."""
    q, k, v, g, gl = _inputs(sq, sk, 2, 64, seed=2)
    o, lse = fa._fa_forward_pallas(q, k, v, True, 0.125, block_q, block_k,
                                   interpret=True, tile=tile)
    dq, dk, dv = fa._fa_backward_pallas(
        q, k, v, o, lse, g, True, 0.125, block_q, block_k, interpret=True,
        glse=gl, tile=tile, **_route(route, sq, sk, block_q, block_k, 2, 64,
                                     64, None, 0))
    _, _, (rq, rk, rv) = _reference(q, k, v, g, gl, True, 0.125)
    assert float(jnp.abs(gl).max()) > 1.0
    np.testing.assert_allclose(dq, rq, atol=5e-4)
    np.testing.assert_allclose(dk, rk, atol=5e-4)
    np.testing.assert_allclose(dv, rv, atol=5e-4)


# ------------------------------------------ which kernels a backward runs

@pytest.mark.parametrize("cell,shape,want", [
    # sq, sk, d_qk, d_v, heads a slab (0: transposed), heads in the arrays
    ("gpt2_124m.steady", (1024, 1024, 64, 64, 2, 24 * 12), ("fused", 1)),
    ("gpt2_xl.fsdp4_steady", (1024, 1024, 64, 64, 0, 4 * 25), ("fused", 4)),
    ("olmoe_1b_7b.steady", (4096, 4096, 128, 128, 1, 5 * 16), ("fused", 1)),
    ("nemotron3_nano_30b_a3b.steady", (8192, 8192, 128, 128, 1, 2 * 32),
     ("fused", 1)),
    ("granite4_h_micro.steady", (8192, 8192, 64, 64, 2, 32), ("fused", 1)),
    ("smallthinker_21b_a3b.steady", (16384, 16384, 128, 128, 1, 2 * 28),
     ("fused", 1)),
    # 16 MiB of float32 dq a head and as much of output block: two fit
    ("kimi_vl_a3b.steady", (16384, 16384, 192, 128, 0, 2 * 16),
     ("fused", 2)),
    # four would fit (96.5 MiB reckoned) and ran at 0.57 of two's speed
    ("xing4_0_29b_a4b.steady", (8192, 8192, 192, 128, 0, 32), ("fused", 2)),
    # four at 64.5 MiB were as slow: never more than `_HEAD_GROUP`
    ("32 heads at 4,096, transposed", (4096, 4096, 192, 128, 0, 32),
     ("fused", 2)),
    # a head's dq alone is 64 MiB of scratch and 64 of output block
    ("128k at 128 lanes", (131072, 131072, 128, 128, 1, 16), ("split", 1)),
    ("128k, transposed", (131072, 131072, 128, 128, 0, 16), ("split", 8)),
    ("64k still fits", (65536, 65536, 128, 128, 1, 16), ("fused", 1)),
    # a ring step's block of keys: sq != sk, by the same rule
    ("ring block", (4096, 16384, 128, 128, 0, 16), ("fused", 2)),
])
def test_backward_route_at_the_cells_shapes(cell, shape, want):
    """`backward_route`: a static function of the call's shapes, fused
    wherever a unit's whole dq fits the VMEM the call states, several
    blocks at no more units a grid step than `_each_head` runs without
    a loop."""
    assert fa.backward_route(*shape) == want
    sq, sk, d, dv, slabs, bh = shape
    if want[0] == "fused" and sq > 1024:
        assert want[1] <= fa._HEAD_GROUP
        assert fa._fused_bwd_vmem(want[1], sq, 1024, 1024, d, dv, slabs or 1,
                                  2) <= fa._VMEM_LIMIT


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("route,names", [
    (None, ["bwd_fused"]), (("fused", 1), ["bwd_fused"]),
    (("split", 2), ["bwd_dkv", "bwd_dq"])])
def test_the_route_names_the_kernels(route, names, window):
    """Several blocks: ONE `pallas_call` on the fused route, whose grid
    is the dk/dv kernel's and whose dq block is the group's whole query
    length; the pair on the other."""
    x = jax.ShapeDtypeStruct((2, 256, 64), jnp.float32)
    row = jax.ShapeDtypeStruct((2, 1, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, 0.125, 64, 64, False, tile=16,
        window=window, route=route))(x, x, x, x, row, x).jaxpr
    assert _kernel_names(jaxpr) == [fa._kernel_name(n, window) for n in names]
    if len(names) == 1:
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        gm = call.params["grid_mapping"]
        pack = route[1] if route else 2
        assert gm.grid == (2 // pack, 4, 4 if window is None else 2)
        blocks = [tuple(int(getattr(b, "block_size", b))
                        for b in bm.block_shape) for bm in gm.block_mappings]
        assert blocks[6:] == [(pack, 256, 64), (pack, 64, 64), (pack, 64, 64)]


def _dots(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _dots(sub)
    return n


@pytest.mark.parametrize("causal,tile,fwd,bwd", [
    (False, None, 2, 5),          # whole block: the program it always was
    (True, 1024, 2 + 2, 5),       # whole-block mask
    (True, 256, 2 + 2 * 7, 5 * 7),  # 4 crossed + 3 unmasked pieces a head
    (True, 512, 2 + 2 * 3, 5 * 3),
])
def test_dots_traced_at_gpt2_shape(causal, tile, fwd, bwd):
    """One block each way at T = 1024: the kernels trace one set of dots
    per piece the tile set has (the causal forward also traces, and never
    runs, the branch for a block below the diagonal), and a non-causal
    call traces what it always did."""
    x = jax.ShapeDtypeStruct((1, 1024, 64), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, 1, 1024), jnp.float32)
    f = jax.make_jaxpr(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, causal, 0.125, 1024, 1024, False, tile=tile))(x, x, x)
    assert _dots(f.jaxpr) == fwd
    b = jax.make_jaxpr(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, causal, 0.125, 1024, 1024, False, tile=tile))(
            x, x, x, x, row, x)
    assert _dots(b.jaxpr) == bwd


# ---------------------------------- a call without a window is unchanged

# sha256 of the traced program (kernel bodies included; addresses and
# source positions taken out) of a no-window attention call, forward and
# gradient, at the attention shapes of the benchmark's five cells that
# had none.  The forwards, and the gradients of the two ONE-BLOCK cells,
# were read on the commit BEFORE the kernels knew a window and are
# unchanged since: their lowering cannot have moved.  The gradients of
# the three several-block cells were re-taken when their backward
# became one kernel (PR 40: `backward_route`; before it they read
# 284030d2600fe654, 468e31377f294c45, c22d4204310af046)
NO_WINDOW = {
    "gpt2_124m.steady": ("d244927abd3b6e78", "1326a3ad4f1fea22"),
    "olmoe_1b_7b.steady": ("89c61244ab8db745", "87fa5be05e083b57"),
    "nemotron3_nano_30b_a3b.steady": ("8a7845390dc1b315",
                                      "42e2beca4fe42827"),
    "granite4_h_micro.steady": ("c2e4852ee8c4fb1f", "4f689be584f47c4b"),
    "gpt2_xl.fsdp4_steady": ("e7adcde6deea4954", "3ad9d7005f42f756"),
}


def _digest(jaxpr) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    text = re.sub(r" at /[^ ]*(flash_attention|mosaic).py:\d+", "", text)
    text = re.sub(r"(flash_attention|mosaic).py:\d+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(NO_WINDOW))
def test_a_call_without_a_window_traces_the_program_it_always_did(
        on_tpu, cell):
    bf = jnp.bfloat16
    proj, heads = {
        "gpt2_124m.steady": (((24, 1024, 3 * 768),), 12),
        "olmoe_1b_7b.steady": (((5, 4096, 2048),) * 3, 16),
        "nemotron3_nano_30b_a3b.steady": (((2, 8192, 4096),) * 3, 32),
        "granite4_h_micro.steady": (((1, 8192, 2048),) * 3, 32),
        "gpt2_xl.fsdp4_steady": (((4, 25, 1024, 64),) * 3, 0),
    }[cell]
    args = [jax.ShapeDtypeStruct(s, bf) for s in proj]
    if heads:
        def call(*p):
            return fa.flash_attention_projected(tuple(p), heads, True, None)
    else:  # 25 heads of 64: the transposed route
        def call(q, k, v):
            return fa.flash_attention(q, k, v, True, None)

    def grads(*p):
        return jax.grad(lambda *pp: call(*pp).astype(jnp.float32).sum(),
                        argnums=tuple(range(len(p))))(*p)

    assert (_digest(jax.make_jaxpr(call)(*args)),
            _digest(jax.make_jaxpr(grads)(*args))) == NO_WINDOW[cell]
    t = proj[0][-2]
    assert fa.causal_tile_count(t, t) == {
        1024: (3, 4), 4096: (36, 64), 8192: (136, 256)}[t]


# ------------------------------- grouped heads: the calls that did move

# sha256, as above, of the direct calls whose k and v are the kv heads'
# own (b, T, kv*d) arrays (PR 46: `fa.kv_route` "indexed", rep > 1): the
# two cells whose program changed when the repeat left the step, the
# windowed cell's two kinds of layer each.  Read when the k / v
# BlockSpecs learned `s // rep`; the same calls on repeated k and v are
# rep 1 and trace what they did (the hybrid's pin above is that form).
# The causal ones were re-taken on purpose when their forward's grid
# step became a kv head's group of query heads (PR 67:
# `fa.forward_route`, `dwt_fa_grp_fwd`; before it SmallThinker's global
# layer read b50a6c1a4870f6a0, 102eb781f1fc6cf5 and the hybrid
# c0081390c134d497, e939ecb8af6d99fb), Laguna's full layer and
# Qwen3-Next's heads of 256 pinned beside them; the windowed call keeps
# the slab step and its digests.
GROUPED = {
    "smallthinker_21b_a3b.steady global": (
        (2, 16384, 3584), (2, 16384, 512), 28, None,
        ("21c8358582343f40", "5ec039d9f2f75019")),
    "smallthinker_21b_a3b.steady windowed": (
        (2, 16384, 3584), (2, 16384, 512), 28, 4096,
        ("78cdb769cdb5f931", "5065cc0c791113b8")),
    "nemotron3_nano_30b_a3b.steady": (
        (2, 8192, 4096), (2, 8192, 256), 32, None,
        ("c1901c642daeae0d", "492c48e183fd6bc8")),
    "laguna_xs_2_33b_a3b.steady full": (
        (1, 16384, 6144), (1, 16384, 1024), 48, None,
        ("23ff548cd557af3a", "2029d15dfcc7bcb3")),
    "qwen3_next_80b_a3b.steady": (
        (1, 16384, 4096), (1, 16384, 512), 16, None,
        ("1b5b1994eda18f2c", "b9d9732e6fcbc0c1")),
}


@pytest.mark.parametrize("cell", sorted(GROUPED))
def test_a_grouped_call_traces_the_program_pinned_for_it(on_tpu, cell):
    q, kv, heads, window, want = GROUPED[cell]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (q, kv, kv)]

    def call(*p):
        return fa.flash_attention_projected(tuple(p), heads, True, None,
                                            window)

    def grads(*p):
        return jax.grad(lambda *pp: call(*pp).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(*p)

    assert (_digest(jax.make_jaxpr(call)(*args)),
            _digest(jax.make_jaxpr(grads)(*args))) == want
    d = q[-1] // heads
    assert fa.kv_route(heads, kv[-1] // d, d) == ("indexed", q[-1] // kv[-1])


# ------------------- the calls the group step (PR 67) leaves where they were

# sha256, as above, of the gradient's trace at five more cells' attention
# shapes, read on PR 67's PARENT (6e6b2e1) and on its tree, equal: the
# transposed route at two widths, a head of its own k and v, a windowed
# grouped call, and two heads a slab on repeated k and v all keep
# `_fa_fwd_kernel` and trace the program they did.
SLAB_STEP = {
    "kimi_vl_a3b.steady": (
        "transposed", ((2, 16, 16384, 192),) * 2 + ((2, 16, 16384, 128),),
        0, None, "ace93aac02f23a48"),
    "xing4_0_29b_a4b.steady": (
        "transposed", ((1, 32, 8192, 192),) * 2 + ((1, 32, 8192, 128),),
        0, None, "3765a8e75f6a77ff"),
    "olmo_hybrid_7b.steady": (
        "direct", ((1, 8192, 30 * 128),) * 3, 30, None, "d82aafea696592a5"),
    "laguna_xs_2_33b_a3b.steady sliding": (
        "direct", ((1, 16384, 64 * 128),) + ((1, 16384, 8 * 128),) * 2, 64,
        512, "6fd858ccfa985876"),
    "lfm2_24b_a2b.steady": (
        "direct", ((1, 16384, 32 * 64),) * 3, 32, None, "990d7f9a32fc0ac4"),
}


@pytest.mark.parametrize("cell", sorted(SLAB_STEP))
def test_a_call_the_group_step_does_not_take_traces_the_parents_program(
        on_tpu, cell):
    layout, shapes, heads, window, want = SLAB_STEP[cell]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]

    def call(*p):
        if layout == "transposed":
            return fa.flash_attention(*p, True, None)
        return fa.flash_attention_projected(tuple(p), heads, True, None,
                                            window)

    grads = jax.grad(lambda *p: call(*p).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))
    assert _digest(jax.make_jaxpr(grads)(*args)) == want
    assert "dwt_fa_grp_fwd" not in str(jax.make_jaxpr(grads)(*args))
