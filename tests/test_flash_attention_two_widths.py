"""Attention whose q and k are wider than its v (latent attention: QK^T
over 192 lanes, PV over 128): `ops/flash_attention.py`'s four kernels in
interpret mode at d_qk != d_v against the plain reference — forward, the
one-block, the fused several-block and the split backward, tiled and
whole, with a shifted diagonal, under a window, with an lse cotangent —
the public entries' jnp paths
(the dense reference and the streamed scan), the default scale, and the
route's answer.  The cases sit beside tests/test_flash_attention_tiles.
py's and use its reference; equal widths stay that file's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_flash_attention_tiles import _reference, _route, _several

from dlrover_wuqiong_tpu.ops import flash_attention as fa


def _inputs(sq, sk, bh, d, dv, seed=0):
    kq, kk, kv, kg, kl = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(kq, (bh, sq, d), jnp.float32),
            jax.random.normal(kk, (bh, sk, d), jnp.float32),
            jax.random.normal(kv, (bh, sk, dv), jnp.float32),
            jax.random.normal(kg, (bh, sq, dv), jnp.float32),
            jax.random.normal(kl, (bh, 1, sq), jnp.float32))


# sq, sk, block_q, block_k, tile, causal, window, bh, d_qk, d_v
CASES = [
    # one block each way (the fused backward), whole and tiled, every pack
    (128, 128, 128, 128, 128, True, None, 2, 192, 128),
    (128, 128, 128, 128, 32, True, None, 8, 192, 128),
    (128, 128, 128, 128, 32, True, None, 3, 48, 32),
    # several blocks with a diagonal (the dq and dk/dv kernels)
    (256, 256, 64, 64, 16, True, None, 2, 192, 128),
    (256, 256, 128, 128, 32, True, None, 4, 24, 16),
    # v the wider of the two
    (128, 128, 64, 64, 16, True, None, 2, 32, 64),
    # sq != sk: a shifted diagonal, rows that see no key
    (128, 256, 64, 64, 16, True, None, 2, 48, 32),
    (256, 128, 64, 64, 16, True, None, 2, 48, 32),
    (128, 64, 128, 64, 32, True, None, 2, 48, 32),
    # block_q != block_k: the whole-block mask by grid position
    (256, 256, 64, 128, 16, True, None, 2, 48, 32),
    # non-causal
    (128, 128, 64, 64, 16, False, None, 2, 192, 128),
    (128, 128, 128, 128, 32, False, None, 4, 48, 32),
    # under a window: the narrowed grid, and one block
    (256, 256, 64, 64, 16, True, 100, 2, 48, 32),
    (128, 128, 128, 128, 32, True, 40, 2, 192, 128),
]


# several blocks: the ONE sweep that gives dq too, and the pair
ROUTED = [c + (route,) for c in CASES for route in (
    ("fused", "split") if _several(*c[:4]) else ("one",))]


@pytest.mark.parametrize(
    "sq,sk,block_q,block_k,tile,causal,window,bh,d,dv,route", ROUTED)
def test_forward_and_backward_match_the_reference(
        sq, sk, block_q, block_k, tile, causal, window, bh, d, dv, route):
    """o has v's width, dq and dk q's, dv v's; every operand is handed
    to the kernels at its own width."""
    q, k, v, g, _ = _inputs(sq, sk, bh, d, dv)
    scale = d ** -0.5
    w = fa._effective_window(window, causal, sk)
    o, lse = fa._fa_forward_pallas(q, k, v, causal, scale, block_q, block_k,
                                   interpret=True, tile=tile, window=w)
    ro, rlse, want = _reference(q, k, v, g, None, causal, scale, window)
    assert o.shape == (bh, sq, dv)
    np.testing.assert_allclose(o, ro, atol=2e-5)
    np.testing.assert_allclose(lse[:, 0], rlse, atol=2e-5)
    got = fa._fa_backward_pallas(q, k, v, o, lse, g, causal, scale, block_q,
                                 block_k, interpret=True, tile=tile,
                                 window=w, **_route(
                                     route, sq, sk, block_q, block_k, bh, d,
                                     dv, None, 0))
    assert [x.shape for x in got] == [q.shape, k.shape, v.shape]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("block,route", [
    (128, "one"), (64, "fused"), (64, "split")])
def test_backward_takes_the_lse_cotangent(block, route):
    q, k, v, g, gl = _inputs(128, 128, 2, 48, 32)
    scale = 48 ** -0.5
    o, lse = fa._fa_forward_pallas(q, k, v, True, scale, block, block,
                                   interpret=True, tile=16)
    got = fa._fa_backward_pallas(
        q, k, v, o, lse, g, True, scale, block, block, interpret=True,
        glse=gl, tile=16, **_route(route, 128, 128, block, block, 2, 48, 32,
                                   None, 0))
    _, _, want = _reference(q, k, v, g, gl, True, scale)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@functools.lru_cache(maxsize=None)
def _sweep_at(blocks, d, dv, pack):
    """(inputs, the fused sweep's dq, dk, dv) of eight heads on a
    `blocks` x `blocks` grid of 64-row blocks at `pack` units a step."""
    sq = blocks * 64
    q, k, v, g, _ = _inputs(sq, sq, 8, d, dv)
    scale = d ** -0.5
    o, lse = fa._fa_forward_pallas(q, k, v, True, scale, 64, 64,
                                   interpret=True, tile=16)
    return (q, k, v, g), fa._fa_backward_pallas(
        q, k, v, o, lse, g, True, scale, 64, 64, interpret=True, tile=16,
        route=("fused", pack))


@pytest.mark.parametrize("pack", [1, 2, 4])
@pytest.mark.parametrize("d,dv", [(192, 128), (64, 64)])
@pytest.mark.parametrize("blocks", [2, 4])
def test_the_fused_sweep_is_the_same_numbers_at_every_pack(blocks, d, dv,
                                                           pack):
    """Whatever `backward_route` hands a several-block sweep — 1 or 2
    units a grid step, and 4, which it handed Xing's shape before PR 52
    and `route=` still reaches — a head's dq, dk and dv are the
    reference's, and do not depend on which heads share its grid step:
    4 units give 1 unit's numbers bit for bit."""
    (q, k, v, g), got = _sweep_at(blocks, d, dv, pack)
    _, _, want = _reference(q, k, v, g, None, True, d ** -0.5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)
    if pack > 1:
        for a, b in zip(got, _sweep_at(blocks, d, dv, 1)[1]):
            np.testing.assert_array_equal(a, b)


def test_the_kernels_block_each_operand_at_its_own_width():
    """No operand of a two-width call is padded to the other's width:
    the blocks of q, k, dq and dk are 192 lanes and those of v, o, dO and
    dv 128, scratch likewise."""
    q = jax.ShapeDtypeStruct((8, 2048, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((8, 2048, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((8, 1, 2048), jnp.float32)

    def blocks(jaxpr):
        out = {}
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                out[eqn.params["name"]] = [
                    tuple(int(getattr(b, "block_size", b))
                          for b in bm.block_shape)
                    for bm in gm.block_mappings]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out.update(blocks(sub))
        return out

    fwd = blocks(jax.make_jaxpr(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, True, 192 ** -0.5, 1024, 1024, False))(q, q, v).jaxpr)
    wide, narrow, row = (8, 1024, 192), (8, 1024, 128), (8, 1, 1024)
    assert fwd == {"dwt_fa_fwd": [wide, wide, narrow, narrow, row]}
    bwd = blocks(jax.make_jaxpr(
        lambda q, k, v, o, l, do: fa._fa_backward_pallas(
            q, k, v, o, l, do, True, 192 ** -0.5, 1024, 1024, False))(
                q, q, v, v, lse, v).jaxpr)
    ins = [wide, wide, narrow, narrow, row, row]
    # fused: two heads a grid step (all eight fit, and ran slower: PR
    # 52), dq's block their whole query length
    assert fa.backward_route(2048, 2048, 192, 128, 0, 8) == ("fused", 2)
    assert bwd == {"dwt_fa_bwd_fused": [
        (2,) + b[1:] for b in ins + [(8, 2048, 192), wide, narrow]]}
    pair = blocks(jax.make_jaxpr(
        lambda q, k, v, o, l, do: fa._fa_backward_pallas(
            q, k, v, o, l, do, True, 192 ** -0.5, 1024, 1024, False,
            route=("split", 8)))(q, q, v, v, lse, v).jaxpr)
    assert pair == {"dwt_fa_bwd_dq": ins + [wide],
                    "dwt_fa_bwd_dkv": ins + [wide, narrow]}


@pytest.mark.parametrize("sq,sk,path", [
    (64, 64, "dense"), (48, 96, "dense"), (2048, 2048, "streamed")])
def test_the_public_entries_take_two_widths_off_the_tpu(sq, sk, path):
    """`flash_attention` and `flash_attention_with_lse` on their jnp
    paths (the tests' oracle and the CPU route): forward and all three
    gradients against `_attention_reference`, the default scale
    1/sqrt(q's width)."""
    assert fa._use_streamed(sq, sk) == (path == "streamed")
    d, dv, h = 24, 16, 2
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(keys[0], (1, h, sq, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, h, sk, d), jnp.float32)
    v = jax.random.normal(keys[2], (1, h, sk, dv), jnp.float32)
    g = jax.random.normal(keys[3], (1, h, sq, dv), jnp.float32)

    def want_fn(q, k, v):
        return (fa._attention_reference(q, k, v, True, d ** -0.5) * g).sum()

    want_o = fa._attention_reference(q, k, v, True, d ** -0.5)
    want = jax.grad(want_fn, argnums=(0, 1, 2))(q, k, v)
    for entry in (fa.flash_attention,
                  lambda *a: fa.flash_attention_with_lse(*a)[0]):
        o = entry(q, k, v)
        assert o.shape == (1, h, sq, dv)
        np.testing.assert_allclose(o, want_o, atol=2e-5)
        got = jax.grad(lambda q, k, v: (entry(q, k, v) * g).sum(),
                       argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=5e-5)
    # another scale is the caller's to give
    other = fa.flash_attention(q, k, v, True, dv ** -0.5)
    assert float(jnp.abs(other - want_o).max()) > 1e-3


@pytest.mark.parametrize("heads,d,dv,route", [
    (16, 192, 128, ("transposed", 0)),   # kimi_vl_a3b.steady
    (16, 192, None, ("transposed", 0)),  # a slab and a half on its own
    (16, 192, 192, ("transposed", 0)),
    (8, 256, 128, ("transposed", 0)),    # slabs, but of two widths
    (16, 128, 64, ("transposed", 0)),
    (16, 128, 128, ("direct", 1)),       # equal widths: as without one
    (12, 64, 64, ("direct", 2)),
    (25, 64, 64, ("transposed", 0)),
])
def test_the_route_answers_for_two_widths(on_tpu, heads, d, dv, route):
    """`attention_route(heads, d_qk, d_v)`: a v of its own width is the
    transposed (b*h, T, d) layout whatever the widths, and the direct
    route refuses it by the same predicate."""
    assert fa.attention_route(heads, d, dv) == route
    assert fa.projected_ok(heads, d, 16384, dv) == (route[0] == "direct")
