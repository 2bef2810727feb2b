"""Grouped heads on the direct route: `flash_attention_projected` on k
and v of their own (b, s, n_kv*d) width — the kernels' BlockSpecs hand
query slab s kv slab s // rep (`fa.kv_route`, `_Slabs.kv_rep`) — against
the same call on `jnp.repeat`ed k and v; and the causal forward whose
grid step is a kv head's GROUP of query heads (`fa.forward_route`,
`dwt_fa_grp_fwd`) against the slab step and the plain reference.
Interpret mode, on the CPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import flash_attention as fa


@pytest.fixture
def direct(on_tpu, monkeypatch):
    """The direct entry as the chip runs it, its kernels interpreted."""
    for name in ("_projected_forward", "_projected_backward"):
        monkeypatch.setattr(fa, name, functools.partial(
            lambda kernel, *a, **kw: kernel(*a, **{**kw, "interpret": True}),
            getattr(fa, name)))

    def blocks(block):
        monkeypatch.setattr(fa, "_PROJECTED_BLOCK", block)

    return blocks


def _operands(b, t, n_kv, rep, d, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(k, (b, t, n_kv * rep * d), jnp.float32).astype(
        dtype) for k in keys[:2])
    k, v = (jax.random.normal(k, (b, t, n_kv * d), jnp.float32).astype(dtype)
            for k in keys[2:])
    return q, k, v, g


def _repeated(x, n_kv, rep):
    """(b, t, n_kv*d) -> (b, t, n_kv*rep*d) as `LlamaAttention` repeats
    its kv heads: query head h reads kv head h // rep."""
    b, t, lanes = x.shape
    return jnp.repeat(x.reshape(b, t, n_kv, lanes // n_kv), rep,
                      axis=2).reshape(b, t, rep * lanes)


def _group_sums(dx, n_kv, rep):
    """A (b, t, n_kv*rep*d) cotangent a query head -> (b, t, n_kv*d): a
    group's heads summed as the entry sums them (float32, one rounding)."""
    b, t, lanes = dx.shape
    return dx.reshape(b, t, n_kv, rep, lanes // (n_kv * rep)).sum(
        3).reshape(b, t, lanes // rep)


def _call_and_grads(proj, n_head, g, window):
    def loss(proj):
        o = fa.flash_attention_projected(proj, n_head, True, None, window)
        return (o.astype(jnp.float32) * g.astype(jnp.float32)).sum(), o

    grads, o = jax.grad(loss, has_aux=True)(proj)
    return o, grads


# (kv heads, query heads a kv head): SmallThinker's 28 over 4 is rep 7,
# the hybrid's 32 over 2 rep 16 (here over ONE kv head, the same maps)
GROUPS = [(2, 7), (1, 16)]


@pytest.mark.parametrize("window", [None, 96], ids=["causal", "windowed"])
@pytest.mark.parametrize("block", [256, 128], ids=["one_block", "several"])
@pytest.mark.parametrize("n_kv,rep", GROUPS, ids=["rep7", "rep16"])
def test_narrow_k_and_v_are_the_repeated_call(direct, n_kv, rep, block,
                                              window):
    """o and dq bit for bit; dk and dv the repeated call's cotangents a
    query head, a group's summed — bit for bit too, the sum being the
    same float32 one rounded once.  bfloat16 operands, as every cell's."""
    direct(block)
    b, t, d = 2, 256, 128
    n_head = n_kv * rep
    q, k, v, g = _operands(b, t, n_kv, rep, d, jnp.bfloat16)
    assert fa.kv_route(n_head, n_kv, d) == ("indexed", rep)
    o, (dq, dk, dv) = _call_and_grads((q, k, v), n_head, g, window)
    wide = (q, _repeated(k, n_kv, rep), _repeated(v, n_kv, rep))
    want_o, (want_dq, dk_heads, dv_heads) = _call_and_grads(
        wide, n_head, g, window)
    assert dk.shape == k.shape and dv.shape == v.shape
    assert dk.dtype == k.dtype
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want_o, np.float32))
    np.testing.assert_array_equal(np.asarray(dq, np.float32),
                                  np.asarray(want_dq, np.float32))
    for got, heads in ((dk, dk_heads), (dv, dv_heads)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(_group_sums(heads, n_kv, rep), np.float32))


@pytest.mark.parametrize("window", [None, 96], ids=["causal", "windowed"])
def test_narrow_k_and_v_differentiate_as_the_repeat_does(direct, window):
    """Against `jax.grad` THROUGH `jnp.repeat` (the program every model
    traced before): o and dq bit for bit, dk and dv to bfloat16's
    rounding — the transpose of the repeat is a `reduce_sum` in the
    cotangent's own bfloat16, which the CPU rounds after every addend of
    seven, where the entry sums a group in float32 and rounds once."""
    direct(128)
    n_kv, rep, d = 2, 7, 128
    q, k, v, g = _operands(1, 256, n_kv, rep, d, jnp.bfloat16, seed=1)

    def through_repeat(q, k, v):
        o = fa.flash_attention_projected(
            (q, _repeated(k, n_kv, rep), _repeated(v, n_kv, rep)),
            n_kv * rep, True, None, window)
        return (o.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    want = jax.grad(through_repeat, argnums=(0, 1, 2))(q, k, v)
    _, got = _call_and_grads((q, k, v), n_kv * rep, g, window)
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    for a, w in zip(got[1:], want[1:]):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        # seven addends, each sum rounded to 8 bits of mantissa
        np.testing.assert_allclose(a, w, rtol=7 * 2 ** -8,
                                   atol=2 ** -8 * np.abs(w).max())


def test_narrow_k_and_v_match_the_plain_reference(direct):
    """float32 operands against grouped attention written out."""
    direct(128)
    b, t, n_kv, rep, d = 1, 256, 2, 7, 128
    q, k, v, g = _operands(b, t, n_kv, rep, d, jnp.float32, seed=2)

    def heads(x, n):
        return x.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    def plain(q, k, v):
        kk, vv = (jnp.repeat(heads(x, n_kv), rep, axis=1) for x in (k, v))
        o = fa._attention_reference(heads(q, n_kv * rep), kk, vv, True,
                                    d ** -0.5)
        o = o.transpose(0, 2, 1, 3).reshape(q.shape)
        return (o * g).sum(), o

    want, want_o = jax.grad(plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    o, got = _call_and_grads((q, k, v), n_kv * rep, g, None)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=5e-4)


@pytest.mark.parametrize("form,heads,d", [
    ("q,k,v", 4, 128), ("q,k,v", 4, 64), ("qkv", 4, 64), ("qkv", 2, 128)])
def test_heads_of_their_own_keep_the_slabs_they_had(form, heads, d):
    """rep 1 — every call before grouped heads were indexed, the
    single-array `c_attn` form included — is handed the `_Slabs` it
    was: `kv_rep` 1, whose BlockSpecs are the ones the kernels had
    (their traced programs are pinned at the cells' shapes by
    tests/test_flash_attention_tiles.py)."""
    x = jax.ShapeDtypeStruct((2, 128, heads * d * (3 if form == "qkv"
                                                   else 1)), jnp.bfloat16)
    proj = (x,) if form == "qkv" else (x,) * 3
    slabs, got_d = fa._projected_slabs(proj, heads)
    per_row = heads * d // 128
    assert (slabs, got_d) == (fa._Slabs(
        per_row, 128 // d, 128,
        (0, per_row, 2 * per_row) if form == "qkv" else (0, 0, 0), 1), d)
    specs = fa._block_specs(slabs, 1, d)
    assert specs[0] is specs[1] is specs[2]


@pytest.mark.parametrize("n_head,n_kv,d,want", [
    (28, 4, 128, ("indexed", 7)),
    (32, 2, 128, ("indexed", 16)),
    (16, 16, 128, ("indexed", 1)),
    (12, 12, 64, ("indexed", 1)),
    (25, 25, 64, ("indexed", 1)),
    (32, 8, 64, ("repeated", 4)),     # a kv head is half a slab
    (4, 2, 64, ("repeated", 2)),
    (6, 2, 80, ("repeated", 3)),      # heads off the slab: transposed
    (8, 2, 256, ("indexed", 4)),      # a head two slabs wide
])
def test_kv_route_is_the_shape_of_the_heads(n_head, n_kv, d, want):
    assert fa.kv_route(n_head, n_kv, d) == want


def test_kv_route_refuses_kv_heads_that_do_not_divide():
    with pytest.raises(ValueError, match="do not divide"):
        fa.kv_route(28, 5, 128)


@pytest.mark.parametrize("q_lanes,k_lanes,v_lanes,n_head,why", [
    (256, 128, 128, 4, "no lane slab"),      # d = 64: half a slab
    (512, 256, 128, 4, "no kv heads"),       # k and v disagree
    (512, 192, 192, 4, "no kv heads"),       # not whole heads
    (512, 384, 384, 4, "do not divide"),     # 3 kv heads under 4
])
def test_the_entry_refuses_k_and_v_it_cannot_index(direct, q_lanes, k_lanes,
                                                   v_lanes, n_head, why):
    q, k, v = (jax.ShapeDtypeStruct((1, 128, n), jnp.bfloat16)
               for n in (q_lanes, k_lanes, v_lanes))
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(lambda *p: fa.flash_attention_projected(p, n_head),
                       q, k, v)


def test_the_backward_puts_a_groups_heads_on_an_axis_of_their_own():
    """dk and dv leave the kernels a QUERY head as (b, rep, s, kv
    lanes): head r of kv head g at [:, r, :, g*d:(g+1)*d], so the
    entry's sum runs over a major axis."""
    b, t, n_kv, rep, d = 1, 128, 2, 7, 128
    q, k, v, g = _operands(b, t, n_kv, rep, d, jnp.float32, seed=3)
    slabs, _ = fa._projected_slabs((q, k, v), n_kv * rep)
    assert slabs == fa._Slabs(n_kv * rep, 1, 128, (0, 0, 0), rep)
    args = (True, d ** -0.5, 64, 64)
    o, lse = fa._fa_forward_pallas(q, k, v, *args, interpret=True,
                                   slabs=slabs)
    dq, dk, dv = fa._fa_backward_pallas(q, k, v, o, lse, g, *args,
                                        interpret=True, slabs=slabs)
    assert dq.shape == q.shape
    assert dk.shape == dv.shape == (b, rep, t, n_kv * d)
    wide_slabs, _ = fa._projected_slabs((q,) * 3, n_kv * rep)
    want = fa._fa_backward_pallas(
        q, _repeated(k, n_kv, rep), _repeated(v, n_kv, rep), o, lse, g,
        *args, interpret=True, slabs=wide_slabs)
    np.testing.assert_array_equal(dq, want[0])
    for got, heads in ((dk, want[1]), (dv, want[2])):
        by_head = got.reshape(b, rep, t, n_kv, d).transpose(
            0, 2, 3, 1, 4).reshape(b, t, n_kv * rep * d)
        np.testing.assert_array_equal(by_head, heads)


# --------------------------------------- a group of heads a grid step


@pytest.fixture
def small_tiles(monkeypatch):
    """The cells' geometry at a sixteenth: tiles of 32, blocks of 64."""
    monkeypatch.setattr(fa, "_CAUSAL_TILE", 32)
    return 64


def _both_steps(q, k, v, n_head, block, heads, blocks):
    slabs, d = fa._projected_slabs((q, k, v), n_head)
    args = (q, k, v, True, d ** -0.5, block, block, True)
    return (fa._fa_forward_pallas(*args, slabs=slabs, route=("slab", 0)),
            fa._fa_forward_pallas(*args, slabs=slabs,
                                  route=("group", heads), blocks=blocks))


def _plain_o(q, k, v, n_kv, rep):
    """`_attention_reference` on the operands' values, in float32."""
    b, t, _ = k.shape
    d = k.shape[-1] // n_kv

    def heads(x, n):
        return x.astype(jnp.float32).reshape(b, t, n, d).transpose(
            0, 2, 1, 3)

    kk, vv = (jnp.repeat(heads(x, n_kv), rep, axis=1) for x in (k, v))
    return fa._attention_reference(
        heads(q, n_kv * rep), kk, vv, True, d ** -0.5).transpose(
            0, 2, 1, 3).reshape(q.shape)


# (kv heads, query heads a kv head, head size, heads a grid step):
# Laguna's 48 over 8, SmallThinker's 28 over 4, Nemotron's 32 over 2 (a
# group in four parts) and Qwen3-Next's 16 over 2 at heads of 256 (two
# parts); then what the rule gives the other head size
GROUP_STEPS = [(2, 6, 128, 6), (1, 7, 128, 7), (1, 16, 128, 4),
               (2, 8, 256, 4), (1, 8, 128, 4), (1, 6, 256, 3),
               (1, 16, 256, 4)]


@pytest.mark.parametrize("key_blocks", [2, 3])
@pytest.mark.parametrize("n_kv,rep,d,heads", GROUP_STEPS, ids=[
    "rep6", "rep7", "rep16_in_parts", "rep8_d256_in_parts", "rep8_in_parts",
    "rep6_d256_in_parts", "rep16_d256_in_parts"])
def test_the_group_step_is_the_slab_step_bit_for_bit(small_tiles, n_kv, rep,
                                                     d, heads, key_blocks):
    """At the shipped geometry (the slab step's own blocks) a row sees
    the same sequence of products, maxima and sums under either step: o
    and lse are equal, bfloat16 operands as every cell's; and o is the
    plain reference's to what bfloat16 probabilities cost."""
    block, t = small_tiles, small_tiles * key_blocks
    assert fa.forward_route(t, t, d, rep, block_k=block) == ("group", heads)
    q, k, v, _ = _operands(1, t, n_kv, rep, d, jnp.bfloat16, seed=rep)
    (want_o, want_lse), (o, lse) = _both_steps(
        q, k, v, n_kv * rep, block, heads, None)
    assert o.shape == q.shape and o.dtype == q.dtype
    assert lse.shape == (n_kv * rep, 1, t) and lse.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want_o, np.float32))
    np.testing.assert_array_equal(lse, want_lse)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               _plain_o(q, k, v, n_kv, rep), atol=2e-2)


def test_a_group_no_step_takes_keeps_the_slab_step_and_still_runs(
        small_tiles):
    """Seven heads of 256 are 1,792 lanes and seven has no smaller part:
    `forward_route` leaves the call on the slab step; `route=` (sweeps)
    still reaches the group step there, all seven a step."""
    block, t, n_kv, rep, d = small_tiles, 128, 1, 7, 256
    assert fa._group_heads(rep, d) == 1
    assert fa.forward_route(t, t, d, rep, block_k=block) == ("slab", 0)
    q, k, v, _ = _operands(1, t, n_kv, rep, d, jnp.bfloat16, seed=5)
    (want_o, want_lse), (o, lse) = _both_steps(q, k, v, rep, block, rep, None)
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(want_o, np.float32))
    np.testing.assert_array_equal(lse, want_lse)


# every (q rows, keys) the chip sweep timed (tools/perf_probe.py's
# `GROUP_BLOCKS`; the first is the rule's), at a sixteenth; `exact`: the
# key blocks and the tiles are the slab step's, so the sums are too
@pytest.mark.parametrize("blocks,exact", [
    ((64, 64), True), ((32, 64), True), ((64, 32), False),
    ((16, 64), False), ((32, 128), False), ((32, 32), False)],
    ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else None)
@pytest.mark.parametrize("n_kv,rep,heads,d", [(1, 6, 6, 128), (1, 16, 8, 128),
                                              (1, 4, 2, 256)],
                         ids=["rep6", "rep16_in_twos", "rep4_d256_in_twos"])
def test_every_swept_geometry_is_the_slab_step(small_tiles, n_kv, rep, heads,
                                               d, blocks, exact):
    """`blocks=` (sweeps and tests) against the slab step and the plain
    reference: another key block is another order of the same sums, to
    float32's and bfloat16's rounding."""
    block, t = small_tiles, 128
    q, k, v, _ = _operands(1, t, n_kv, rep, d, jnp.bfloat16, seed=3)
    (want_o, want_lse), (o, lse) = _both_steps(
        q, k, v, n_kv * rep, block, heads, blocks)
    o, want_o = (np.asarray(x, np.float32) for x in (o, want_o))
    if exact:
        np.testing.assert_array_equal(o, want_o)
        np.testing.assert_array_equal(lse, want_lse)
    np.testing.assert_allclose(o, want_o, rtol=2 ** -7, atol=2 ** -8)
    np.testing.assert_allclose(lse, want_lse, atol=1e-5)
    np.testing.assert_allclose(o, _plain_o(q, k, v, n_kv, rep), atol=2e-2)


def test_the_entry_differentiates_through_the_group_step(direct, small_tiles,
                                                         monkeypatch):
    """`flash_attention_projected` at a grouped causal shape of several
    key blocks runs `dwt_fa_grp_fwd` and hands the UNTOUCHED backward its
    o and its (b*h, 1, s) lse: float32 operands against grouped
    attention written out, value and gradients."""
    direct(small_tiles)
    b, t, n_kv, rep, d = 2, 192, 2, 3, 128
    q, k, v, g = _operands(b, t, n_kv, rep, d, jnp.float32, seed=4)
    names = []
    monkeypatch.setattr(fa.pl, "pallas_call", functools.partial(
        lambda call, *a, **kw: names.append(kw["name"]) or call(*a, **kw),
        fa.pl.pallas_call))

    def plain(q, k, v):
        o = _plain_o(q, k, v, n_kv, rep)
        return (o * g).sum(), o

    want, want_o = jax.grad(plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    o, got = _call_and_grads((q, k, v), n_kv * rep, g, None)
    assert sorted(set(names)) == ["dwt_fa_bwd_fused", "dwt_fa_grp_fwd"]
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=5e-4)


@pytest.mark.parametrize("why,sq,sk,d,rep,causal,window,want", [
    ("laguna_xs_2_33b_a3b.steady, a full layer", 16384, 16384, 128, 6,
     True, None, ("group", 6)),
    ("smallthinker_21b_a3b.steady, the global layer", 16384, 16384, 128, 7,
     True, None, ("group", 7)),
    ("nemotron3_nano_30b_a3b.steady", 8192, 8192, 128, 16, True, None,
     ("group", 4)),
    ("qwen3_next_80b_a3b.steady", 16384, 16384, 256, 8, True, None,
     ("group", 4)),
    ("two key blocks", 2048, 2048, 128, 4, True, None, ("group", 4)),
    ("a windowed layer", 16384, 16384, 128, 7, True, 4096, ("slab", 0)),
    ("a head of its own k and v", 4096, 4096, 128, 1, True, None,
     ("slab", 0)),
    ("one key block", 1024, 1024, 128, 7, True, None, ("slab", 0)),
    ("sq != sk", 1024, 4096, 128, 7, True, None, ("slab", 0)),
    ("not causal", 4096, 4096, 128, 7, False, None, ("slab", 0)),
    ("three key blocks of 512", 1536, 1536, 128, 7, True, None,
     ("group", 7)),
    ("a group of a prime over the most a step takes", 4096, 4096, 128, 11,
     True, None, ("slab", 0)),
    ("seven heads of 256: 1,792 lanes and no smaller part", 4096, 4096,
     256, 7, True, None, ("slab", 0)),
])
def test_forward_route_is_the_shape_of_the_call(why, sq, sk, d, rep, causal,
                                                window, want):
    assert fa.forward_route(sq, sk, d, rep, causal, window) == want


# ------------------------------------------ who repeats and who does not


@pytest.mark.parametrize("heads,kv,d,on_tpu,lanes", [
    (4, 2, 128, True, 256),    # a head a slab, direct: k and v as projected
    (4, 2, 64, True, 256),     # two heads a slab, direct: repeated
    (4, 4, 128, True, 512),    # nothing to repeat
    (4, 2, 128, False, None),  # off the TPU: cut to heads and repeated
], indirect=["on_tpu"])
def test_llama_attention_repeats_where_the_kernels_cannot_index(
        monkeypatch, heads, kv, d, on_tpu, lanes):
    """`LlamaAttention` hands the direct entry k and v of their own
    width where `kv_route` says "indexed" and (b, T, heads*d) where it
    says "repeated" (d = 64: a kv head is half a slab); every call that
    does not go direct sees (b, T, heads, d) after the repeat, as it
    always did."""
    from dlrover_wuqiong_tpu.models import attention as dispatch
    from dlrover_wuqiong_tpu.models.llama import (
        LlamaAttention,
        LlamaConfig,
        rope_freqs,
    )

    seen = {}

    def projected(proj, n_head, *a):
        seen["direct"] = [x.shape for x in proj]
        return jnp.zeros_like(proj[0])

    def transposed(q, k, v, **kw):
        seen["mha"] = [x.shape for x in (q, k, v)]
        return jnp.zeros_like(q)

    monkeypatch.setattr(dispatch, "flash_attention_projected", projected)
    monkeypatch.setattr(dispatch, "mha", transposed)
    cfg = LlamaConfig(hidden_size=256, num_heads=heads, num_kv_heads=kv,
                      attn_head_dim=d, max_seq_len=128)
    b, t = 2, 128
    x = jnp.ones((b, t, 256), cfg.dtype)
    cos, sin = rope_freqs(d, t, cfg.rope_theta)
    module = LlamaAttention(cfg)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x, cos, sin)
    jax.eval_shape(module.apply, params, x, cos, sin)
    if on_tpu:
        assert seen == {"direct": [(b, t, heads * d), (b, t, lanes),
                                   (b, t, lanes)]}
    else:
        assert seen == {"mha": [(b, t, heads, d)] * 3}
