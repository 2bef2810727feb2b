"""The traced program is a function of its arguments.

What `ops/flash_attention.py` traces — the pack width, the grid, the
backward's form, the streamed fallback — is decided from shapes alone;
the three framework keys and the warm spec hold arguments alone.  The
process environment is in none of it: the five names that once were
(`RETIRED`) change nothing when set.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import mosaic


def _pallas_calls(jaxpr):
    """[(kernel name, grid)] of every pallas_call under `jaxpr`."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        tuple(eqn.params["grid_mapping"].grid)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_calls(sub))
    return out


def _attention_grad_jaxpr(b, h, t, d, dtype=jnp.bfloat16):
    x = jax.ShapeDtypeStruct((b, h, t, d), dtype)
    return jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)


# ------------------------------------------- (A) shape -> program decisions


@pytest.mark.parametrize("bh,pack", [
    (288, 8), (100, 4), (80, 8), (24, 8), (12, 4), (6, 2), (7, 1), (1, 1)])
def test_pack_is_the_largest_of_8_4_2_1_dividing_the_heads(bh, pack):
    assert fa._fit_pack(bh) == pack


@pytest.mark.parametrize("causal", [True, False])  # ring steps are not
@pytest.mark.parametrize("sq,sk,block,grid", [
    (1024, 1024, 1024, ()),      # GPT-2's context: one block each way
    (512, 512, 1024, ()),        # a block is capped at the sequence
    (256, 256, 256, ()),
    (256, 512, 512, ()),         # one block each way, sq != sk
    (1024, 1024, 512, (2, 2)),   # key blocks, then query blocks
    (2048, 2048, 1024, (2, 2)),
    (1024, 2048, 1024, (2, 1)),  # one query block, two key blocks
    (2048, 1024, 1024, (1, 2)),
])
def test_backward_is_one_kernel_wherever_dq_fits(sq, sk, block, grid,
                                                 causal):
    """One block each way: the one-block kernel on a grid of heads alone.
    Several: the dk/dv sweep's grid with the packed heads' whole dq in
    VMEM (`backward_route`), or by `route=` the dq and dk/dv pair."""
    q = jax.ShapeDtypeStruct((2, sq, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((2, sk, 64), jnp.float32)
    lse = jax.ShapeDtypeStruct((2, 1, sq), jnp.float32)

    def calls(**kw):
        return sorted(_pallas_calls(jax.make_jaxpr(
            lambda q, k, v, o, l, g: fa._fa_backward_pallas(
                q, k, v, o, l, g, causal, 0.125, block, block, True, **kw))(
            q, k, k, q, lse, q).jaxpr))

    assert fa.backward_route(sq, sk, 64, 64, 0, 2, block, block, 4) == (
        "fused", 2)
    assert calls() == [("dwt_fa_bwd_fused", (1,) + grid)]
    if grid:
        assert calls(route=("split", 2)) == [
            ("dwt_fa_bwd_dkv", (1,) + grid),
            ("dwt_fa_bwd_dq", (1,) + grid[::-1])]
        assert calls(route=("fused", 1)) == [
            ("dwt_fa_bwd_fused", (2,) + grid)]


@pytest.mark.parametrize("sq,sk,streamed", [
    (2048, 2048, True), (1024, 4096, True),
    (2048, 2047, False), (1024, 1024, False)])
def test_streamed_fallback_switches_at_2048_squared(sq, sk, streamed):
    assert fa._use_streamed(sq, sk) is streamed


@pytest.mark.parametrize("heads,d,route", [
    (12, 64, ("direct", 2)),      # gpt2_124m.steady: two heads a slab
    (16, 128, ("direct", 1)),     # olmoe_1b_7b.steady: a head a slab
    (25, 64, ("transposed", 0)),  # gpt2_xl, per chip: 12.5 slabs
    (2, 64, ("direct", 2)), (1, 64, ("transposed", 0)),
    (8, 256, ("direct", 1)),      # a head two slabs wide
    (12, 80, ("transposed", 0)), (16, 96, ("transposed", 0)),
    (4, 32, ("transposed", 0)),   # h*d % 128 == 0 is not enough
])
def test_the_route_is_the_shape_of_the_heads(heads, d, route):
    assert fa.attention_route(heads, d) == route


@pytest.mark.parametrize("cell,heads,kv,d,want", [
    # a head a slab: k and v stay (b, T, kv*d), query slab s reads kv
    # slab s // rep and nothing is repeated
    ("smallthinker_21b_a3b.steady", 28, 4, 128, ("indexed", 7)),
    ("nemotron3_nano_30b_a3b.steady", 32, 2, 128, ("indexed", 16)),
    # two heads of 64 a slab: a kv head is HALF a slab, the caller repeats
    ("granite4_h_micro.steady", 32, 8, 64, ("repeated", 4)),
    # every head its own k and v: nothing to repeat on any route
    ("olmoe_1b_7b.steady", 16, 16, 128, ("indexed", 1)),
    ("gpt2_124m.steady", 12, 12, 64, ("indexed", 1)),
    ("gpt2_xl.fsdp4_steady", 25, 25, 64, ("indexed", 1)),
    ("kimi_vl_a3b.steady", 16, 16, 192, ("indexed", 1)),
])
def test_grouped_heads_are_indexed_where_a_head_is_a_slab(cell, heads, kv,
                                                          d, want):
    """`kv_route` at the seven cells' shapes — static, from shapes alone,
    the counter of whether a direct call's k and v are repeated."""
    assert fa.kv_route(heads, kv, d) == want


@pytest.mark.parametrize("cell,heads,kv,d,dv,t,window,want", [
    # grouped heads indexed on the direct route, causal over several key
    # blocks: a kv head's group of query heads a grid step, or a part
    ("laguna_xs_2_33b_a3b.steady full", 48, 8, 128, 128, 16384, None,
     ("group", 6)),
    ("smallthinker_21b_a3b.steady global", 28, 4, 128, 128, 16384, None,
     ("group", 7)),
    ("nemotron3_nano_30b_a3b.steady", 32, 2, 128, 128, 8192, None,
     ("group", 4)),
    ("qwen3_next_80b_a3b.steady", 16, 2, 256, 256, 16384, None,
     ("group", 4)),
    # every windowed call
    ("laguna_xs_2_33b_a3b.steady sliding", 64, 8, 128, 128, 16384, 512,
     ("slab", 0)),
    ("smallthinker_21b_a3b.steady windowed", 28, 4, 128, 128, 16384, 4096,
     ("slab", 0)),
    # a head of its own k and v
    ("olmoe_1b_7b.steady", 16, 16, 128, 128, 4096, None, ("slab", 0)),
    ("olmo_hybrid_7b.steady", 30, 30, 128, 128, 8192, None, ("slab", 0)),
    # two heads a slab: k and v repeated, `kv_rep` 1 at the kernel
    ("gpt2_124m.steady", 12, 12, 64, 64, 1024, None, ("slab", 0)),
    ("granite4_h_micro.steady", 32, 8, 64, 64, 8192, None, ("slab", 0)),
    ("lfm2_24b_a2b.steady", 32, 8, 64, 64, 16384, None, ("slab", 0)),
    # the transposed route
    ("gpt2_xl.fsdp4_steady", 25, 25, 64, 64, 1024, None, ("slab", 0)),
    ("kimi_vl_a3b.steady", 16, 16, 192, 128, 16384, None, ("slab", 0)),
    ("xing4_0_29b_a4b.steady", 32, 32, 192, 128, 8192, None, ("slab", 0)),
    # one key block
    ("grouped at T = 1,024", 28, 4, 128, 128, 1024, None, ("slab", 0)),
])
def test_the_forward_step_is_the_shape_of_the_call(cell, heads, kv, d, dv, t,
                                                   window, want):
    """`forward_route` at the cells' shapes, handed what
    `_fa_forward_pallas` hands it: `_Slabs.kv_rep` on the direct route
    where k and v are indexed, 1 everywhere else."""
    layout, slab_heads = fa.attention_route(heads, d, dv)
    how, rep = fa.kv_route(heads, kv, d)
    at_kernel = rep if layout == "direct" and how == "indexed" else 1
    window = fa._effective_window(window, True, t)
    assert fa.forward_route(t, t, d, at_kernel, True, window) == want
    assert fa.forward_route(t // 2, t, d, at_kernel, True, window) == (
        "slab", 0)  # sq != sk


@pytest.mark.parametrize("on_tpu,h,d,t,why", [
    (False, 12, 64, 1024, "off the TPU"),
    (True, 12, 64, 4099, "a sequence of 4099"),   # no block tiles it
    (True, 25, 64, 1024, "slab boundaries"),
], indirect=["on_tpu"])
def test_the_direct_entry_refuses_what_projected_ok_does(monkeypatch,
                                                         on_tpu, h, d, t,
                                                         why):
    """`flash_attention_projected` has the dispatcher's own guard: a
    call `projected_ok` does not take raises, and never reaches a kernel
    with no block or a TPU lowering off the TPU."""
    assert not fa.projected_ok(h, d, t)
    qkv = jax.ShapeDtypeStruct((2, t, 3 * h * d), jnp.bfloat16)
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(lambda x: fa.flash_attention_projected((x,), h),
                       qkv)
    monkeypatch.setattr(mosaic, "on_tpu", lambda: True)
    assert fa.projected_ok(12, 64, 1024) and fa.projected_ok(16, 128, 4096)


def _model_attention_jaxpr(b, h, t, d, form, kv=None):
    """The attention a model of this shape traces on one device: through
    `models/attention.attend_projected`, from the projections' own
    layout — `form` "qkv" as models/gpt.py hands it, "q,k,v" as
    models/llama.py does, k and v at `kv` heads' width where
    `kv_route` says they are indexed."""
    from dlrover_wuqiong_tpu.models.attention import attend_projected
    from dlrover_wuqiong_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(n_head=h, n_embd=h * d)
    n = 3 if form == "qkv" else 1
    x = jax.ShapeDtypeStruct((b, t, n * h * d), jnp.bfloat16)
    proj = (x,) * (4 - n)
    if kv:
        proj = (x,) + (jax.ShapeDtypeStruct((b, t, kv * d), jnp.bfloat16),) * 2
    return jax.make_jaxpr(jax.grad(
        lambda proj: attend_projected(proj, h, cfg).astype(
            jnp.float32).sum()))(proj)


def _primitives(jaxpr) -> set:
    """Every primitive traced outside the kernels' own bodies."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found |= _primitives(sub)
    return found


# pack and grid changed for the two direct cells in PR 29: a grid step
# is ONE lane slab of one batch row where it was 8 heads of the
# transposed array (124M: 24 x 6 slabs of two heads, was 288 / 8 groups;
# OLMoE: 5 x 16 slabs of one head, was 80 / 8), because a slab is what a
# BlockSpec can address in the projections' layout without a lane slice
# Nemotron's row hands k and v at their 2 kv heads' own width since PR
# 67, as its model does: the forward is the GROUP step, 4 of a kv head's
# 16 query heads a grid step (2 rows x 2 kv heads x 4 parts), the
# backward the slab sweep it was
@pytest.mark.parametrize("b,h,kv,t,d,form,route,groups,grid,tiles,forward", [
    (24, 12, None, 1024, 64, "qkv", "direct", 24 * 6, (1, 1),
     (3, 4), None),                                    # gpt2_124m.steady
    (4, 25, None, 1024, 64, "qkv", "transposed", 100 // 4, (1, 1),
     (3, 4), None),                                    # gpt2_xl, per chip
    (5, 16, None, 4096, 128, "q,k,v", "direct", 5 * 16, (4, 4),
     (36, 64), None),                                  # olmoe_1b_7b.steady
    (2, 32, 2, 8192, 128, "q,k,v", "direct", 2 * 32, (8, 8),
     (136, 256), ("dwt_fa_grp_fwd", (2 * 2 * 4, 8, 8))),
    #                                         nemotron3_nano_30b_a3b.steady
    (1, 32, None, 8192, 64, "q,k,v", "direct", 16, (8, 8),
     (136, 256), None),   # granite4_h_micro.steady: k and v repeated to 32
])
def test_the_cells_attention_plans(on_tpu, b, h, kv, t, d, form, route,
                                   groups, grid, tiles, forward):
    """PERF.md section 5's prose, pinned: what each benchmark cell's
    attention traces to on the chip, from its shape alone — the route,
    the kernels and their grids, and whether anything is split, cut to
    heads or transposed around them."""
    jaxpr = _model_attention_jaxpr(b, h, t, d, form, kv).jaxpr
    assert fa.attention_route(h, d)[0] == route
    # the backward is ONE kernel in every cell: on heads alone where the
    # sequence is one block, else the dk/dv sweep with dq resident
    assert fa.backward_route(t, t, d, d, fa.attention_route(h, d)[1],
                             b * h)[0] == "fused"
    assert sorted(_pallas_calls(jaxpr)) == sorted([
        forward or ("dwt_fa_fwd", (groups,) + grid),
        ("dwt_fa_bwd_fused", (groups,) + (grid if t > 1024 else ()))])
    assert fa.causal_tile_count(t, t) == tiles
    relaid = _primitives(jaxpr) & {"transpose", "split", "reshape",
                                   "slice"}
    if route == "transposed":
        assert fa._fit_pack(b * h) == b * h // groups
        assert relaid == {"transpose", "split", "reshape"}
    elif d == 64:
        # two heads a slab: delta is the kernel's too; all that is left
        # is the join of dq, dk and dv into c_attn's cotangent
        assert relaid == set()
        assert ("concatenate" in _primitives(jaxpr)) == (form == "qkv")
    else:
        # a head a slab: delta is one reduce of (b, t, h, d) over d,
        # turned to the kernels' (b*h, 1, t) as a (b, t, h) array
        assert relaid == {"reshape", "transpose"}
        assert "concatenate" not in _primitives(jaxpr)


def test_the_latent_cells_attention_plan(on_tpu):
    """`kimi_vl_a3b.steady`'s attention from its shape alone: 16 heads
    whose q and k are 192 wide and whose v is 128 lie on no slab
    boundary, so `attend` hands the kernels the transposed (b*h, T, d)
    arrays on 16 blocks of 1,024 a side, 8 heads a grid step forward and
    2 backward (two heads' whole dq, 64 MiB, is what fits the fused
    kernel's VMEM: `backward_route`), each operand at its own width and
    none padded."""
    from dlrover_wuqiong_tpu.models.attention import attend, goes_direct
    from dlrover_wuqiong_tpu.models.latent_attention import (
        LatentAttentionConfig,
    )

    cfg = LatentAttentionConfig()
    assert (cfg.num_heads, cfg.qk_head_dim, cfg.v_head_dim) == (16, 192, 128)
    assert fa.attention_route(16, 192, 128) == ("transposed", 0)
    assert not fa.projected_ok(16, 192, 16384, 128)
    assert not goes_direct(cfg, 16, 192, 16384)  # a slab and a half
    q = jax.ShapeDtypeStruct((2, 16384, 16, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 16384, 16, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: attend(q, k, v, cfg).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, q, v)
    assert [a.aval.shape for a in jaxpr.jaxpr.outvars] == [
        q.shape, q.shape, v.shape]
    assert fa._fit_pack(2 * 16) == 8
    assert fa.backward_route(16384, 16384, 192, 128, 0, 2 * 16) == (
        "fused", 2)
    assert sorted(_pallas_calls(jaxpr.jaxpr)) == [
        ("dwt_fa_bwd_fused", (16, 16, 16)), ("dwt_fa_fwd", (4, 16, 16))]
    assert "pad" not in _primitives(jaxpr.jaxpr)
    assert fa.kernel_lanes(192, 128) == 192 + 128
    assert fa.causal_tile_count(16384, 16384) == (528, 1024)


@pytest.mark.parametrize("window,names,sweep,tiles", [
    # the GLOBAL layer: the causal kernels over all 16 x 16 blocks, the
    # forward a kv head's seven query heads a grid step (PR 67)
    (0, ("dwt_fa_grp_fwd", "dwt_fa_bwd_fused"), 16, (528, 1024)),
    # a WINDOWED layer: kernels of another name on a grid narrowed to the
    # five key blocks a query block sees (backward: the five query
    # blocks a key block is seen by): a block below the window is no
    # grid step.  The backward is one kernel a layer, a slab's whole dq
    # resident
    (4096, ("dwt_fa_win_fwd", "dwt_fa_win_bwd_fused"), 5, (252, 1024)),
    # a window no shorter than the sequence is the causal call itself
    (16384, ("dwt_fa_grp_fwd", "dwt_fa_bwd_fused"), 16, (528, 1024)),
])
def test_the_windowed_cells_attention_plans(on_tpu, window, names, sweep,
                                            tiles):
    """`smallthinker_21b_a3b.steady`'s two kinds of attention layer, from
    their shape and `LlamaConfig.attn_window` alone: 28 heads of 128 are
    28 lane slabs, so both go DIRECT on 16 blocks of 1,024 a side, k
    and v the 4 kv heads' own 512 lanes (`kv_route`: query slab s reads
    kv slab s // 7; nothing is repeated)."""
    from dlrover_wuqiong_tpu.models.attention import (
        attend_projected,
        window_tiles,
    )
    from dlrover_wuqiong_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(hidden_size=2560, num_heads=28, num_kv_heads=4,
                      attn_head_dim=128, attn_window=window)
    assert fa.attention_route(28, 128) == ("direct", 1)
    assert fa.kv_route(28, 4, 128) == ("indexed", 7)
    x = jax.ShapeDtypeStruct((2, 16384, 28 * 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 16384, 4 * 128), jnp.bfloat16)
    grads = jax.grad(lambda proj: attend_projected(proj, 28, cfg).astype(
        jnp.float32).sum())
    jaxpr = jax.make_jaxpr(grads)((x, kv, kv)).jaxpr
    # a slab a grid step, but for the group forward: 2 rows x 4 kv heads
    assert sorted(_pallas_calls(jaxpr)) == sorted(
        (name, (2 * 4 if "grp" in name else 2 * 28, 16, sweep))
        for name in names)
    assert [g.shape for g in jax.eval_shape(grads, (x, kv, kv))] == [
        x.shape, kv.shape, kv.shape]
    # the repeated form (another caller's) has no group to step over:
    # the slab kernels, as before
    assert sorted(_pallas_calls(jax.make_jaxpr(grads)((x,) * 3).jaxpr)) == \
        sorted((name.replace("grp_", ""), (2 * 28, 16, sweep))
               for name in names)
    assert fa.causal_tile_count(16384, 16384, window=window or None) == tiles
    assert window_tiles(cfg, 2, 28, 16384) == (
        None if not window else (56 * tiles[0], 56 * 528))


@pytest.mark.parametrize("h,d,form", [
    (3, 64, "qkv"),      # an odd number of heads of 64
    (3, 64, "q,k,v"),
    (2, 80, "qkv"),      # h*d % 128 != 0
    (4, 32, "q,k,v"),    # h*d % 128 == 0, four heads a slab
])
def test_shapes_off_the_slab_fall_back_and_still_match(on_tpu, monkeypatch,
                                                       h, d, form):
    """`attend_projected` on a shape `attention_route` calls transposed:
    the kernels (interpret mode) on (b*h, t, d) arrays behind the split,
    the cut to heads and the transposes, against the plain reference."""
    import functools

    from dlrover_wuqiong_tpu.models.attention import attend_projected
    from dlrover_wuqiong_tpu.models.gpt import GPTConfig

    assert fa.attention_route(h, d)[0] == "transposed"
    for name in ("_fa_forward_pallas", "_fa_backward_pallas"):
        kernel = getattr(fa, name)
        monkeypatch.setattr(fa, name, functools.partial(
            lambda kernel, *a, **kw: kernel(
                *a, **{**kw, "interpret": True}), kernel))
    b, t = 2, 128
    keys = jax.random.split(jax.random.PRNGKey(h * d), 4)
    q, k, v, g = (jax.random.normal(kx, (b, t, h * d), jnp.float32)
                  for kx in keys)
    proj = (jnp.concatenate([q, k, v], -1),) if form == "qkv" else (q, k, v)
    cfg = GPTConfig(n_head=h, n_embd=h * d)

    def heads(x):
        return x.reshape(b, t, h, d).transpose(0, 2, 1, 3)

    def plain(proj):
        q, k, v = proj if len(proj) == 3 else jnp.split(proj[0], 3, -1)
        o = fa._attention_reference(heads(q), heads(k), heads(v), True,
                                    d ** -0.5)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * d)
        return (o * g).sum(), o

    def model(proj):
        o = attend_projected(proj, h, cfg)
        return (o * g).sum(), o

    packed = b * h // fa._fit_pack(b * h)
    assert sorted(_pallas_calls(jax.make_jaxpr(jax.grad(
        model, has_aux=True))(proj).jaxpr)) == [
            ("dwt_fa_bwd_fused", (packed,)), ("dwt_fa_fwd", (packed, 1, 1))]
    got, o = jax.grad(model, has_aux=True)(proj)
    want, want_o = jax.grad(plain, has_aux=True)(proj)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=5e-4)


def _rope_written_out(x, cos, sin):
    """RoPE as the formula reads, a head at a time on (b, s, h, d): the
    head's halves x1, x2 -> (x1 cos - x2 sin, x2 cos + x1 sin).  Written
    apart from `models/llama.apply_rope` (it is what that function was
    before PR 29)."""
    s = x.shape[1]
    c, si = cos[:s][None], sin[:s][None]
    heads = []
    for a in range(x.shape[2]):
        x1, x2 = jnp.split(x[:, :, a].astype(jnp.float32), 2, axis=-1)
        heads.append(jnp.concatenate(
            [x1 * c - x2 * si, x2 * c + x1 * si], axis=-1))
    return jnp.stack(heads, axis=2).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", ["heads", "flat"])
@pytest.mark.parametrize("h,d", [(16, 128), (3, 64), (2, 16)])
def test_rope_is_the_written_out_rotation_in_both_layouts(h, d, layout,
                                                          dtype):
    """The one `apply_rope` of models/llama.py, on (b, s, h, d) and on
    the projections' own (b, s, h*d), against the per-head formula: the
    same two products and one sum an element, so the same bits."""
    from dlrover_wuqiong_tpu.models.llama import apply_rope, rope_freqs

    b, s = 2, 24
    cos, sin = rope_freqs(d, 32, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(h + d), (b, s, h, d), dtype)
    want = _rope_written_out(x, cos, sin)
    got = apply_rope(x if layout == "heads" else x.reshape(b, s, h * d),
                     cos, sin)
    assert got.dtype == dtype
    assert got.shape == (x.shape if layout == "heads" else (b, s, h * d))
    np.testing.assert_array_equal(
        np.asarray(got.reshape(x.shape), np.float32),
        np.asarray(want, np.float32))
    # and it rotates: position 0 stays, every pair keeps its length
    np.testing.assert_array_equal(np.asarray(got[:, 0], np.float32),
                                  np.asarray(x.reshape(got.shape)[:, 0],
                                             np.float32))
    if dtype == jnp.float32:
        g = got.reshape(x.shape)
        np.testing.assert_allclose(
            g[..., :d // 2] ** 2 + g[..., d // 2:] ** 2,
            x[..., :d // 2] ** 2 + x[..., d // 2:] ** 2, rtol=1e-5,
            atol=1e-6)


def test_llama_off_the_direct_route_rotates_a_head_at_a_time(monkeypatch):
    """A Llama whose attention does not go direct (here: off the TPU, on
    a tp mesh that shards the heads) cuts q and k to (b, s, h, d) BEFORE
    the rotation, as it always did: the rolls run along one head's d,
    which no mesh axis shards, so the partitioner adds no collective
    over the written-out formula's program."""
    import optax

    from dlrover_wuqiong_tpu.analysis.hlo_budget import iter_collectives
    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models import llama

    def collectives():
        res = auto_accelerate(
            llama.Llama(llama.LlamaConfig.nano()),
            optimizer=optax.adamw(1e-2), materialize=False,
            strategy=[("fsdp", {}), ("tensor_parallel", {"size": 2})])
        ids = jax.ShapeDtypeStruct((8, 64), jnp.int32,
                                   sharding=res.batch_sharding_fn(2))
        text = res.train_step.lower(
            res.state, {"input_ids": ids, "labels": ids}).compile(
            ).as_text()
        return sorted(op for op, _, _ in iter_collectives(text))

    def split_and_join(x, cos, sin, mesh=None):  # as it was before PR 29
        c = cos[:x.shape[1]][None, :, None, :]
        si = sin[:x.shape[1]][None, :, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * si, x2 * c + x1 * si],
                               axis=-1).astype(x.dtype)

    def whole_row_then_cut(x, cos, sin, mesh=None, rope=llama.apply_rope):
        b, s, h, d = x.shape
        return rope(x.reshape(b, s, h * d), cos, sin).reshape(x.shape)

    monkeypatch.setenv("DWT_COMPILE_CACHE", "0")
    ours = collectives()
    monkeypatch.setattr(llama, "apply_rope", split_and_join)
    assert ours == collectives() and ours
    # what the order of cut and rotation saves: rolled along the whole
    # row, whose lanes tp shards, every roll trades halos (48 more)
    monkeypatch.setattr(llama, "apply_rope", whole_row_then_cut)
    assert collectives().count("collective-permute") \
        > ours.count("collective-permute")


@pytest.mark.parametrize("text,want", [
    # compiled HLO, or a lowered step that calls the kernels in line
    ('custom_call @tpu_custom_call dwt_fa_fwd\n' * 3, 3),
    # the direct route's lowered step: the wrapper behind `jax.jit` is
    # ONE private function, called a layer — through a remat body too
    ('func.func public @main() {\n call @_fa_fwd(%0)\n call @layer(%1)\n'
     ' call @layer(%2)\n}\n'
     'func.func private @layer() {\n call @_fa_fwd(%0)\n}\n'
     'func.func private @_fa_fwd() {\n custom_call @tpu_custom_call '
     'dwt_fa_fwd\n}\n'
     'func.func private @_fa_fwd_1() {\n custom_call @tpu_custom_call '
     'dwt_fa_fwd\n}\n', 3 + 1),
])
def test_the_smoke_counts_a_kernel_once_for_each_call_site(text, want):
    """`chip_smoke.kernel_counts`: what `--phase kernel` and `--phase
    train` hold to `>= n_layer` on both routes."""
    import chip_smoke

    assert chip_smoke.kernel_counts(text)["dwt_fa_fwd"] == want


# ------------------------------------------ the Mamba-2 scan's route


GRANITE = (64, 64, 1, 128, 256, 8192)    # granite4_h_micro.steady
NEMOTRON = (64, 64, 8, 128, 128, 8192)   # nemotron3_nano_30b_a3b.steady


@pytest.mark.parametrize("on_tpu,shape,route", [
    (True, GRANITE, ("kernel", 16)),     # ONE group: 16 of its 64 heads
    (True, NEMOTRON, ("kernel", 8)),     # a group of 8 a grid step
    (True, (8, 128, 2, 128, 128, 2048), ("kernel", 4)),   # a head a slab
    (True, (8, 32, 1, 128, 128, 1024), ("kernel", 8)),    # four a slab
    (False, GRANITE, ("plain", 0)),      # off the TPU
    (False, NEMOTRON, ("plain", 0)),
    (True, (64, 64, 1, 128, 64, 8192), ("plain", 0)),     # chunk < a lane
    (True, (64, 64, 1, 128, 192, 8448), ("plain", 0)),    # no multiple
    (True, (8, 16, 2, 8, 16, 64), ("plain", 0)),          # nano() sizes
    (True, (64, 48, 1, 128, 256, 8192), ("plain", 0)),    # heads off slabs
    (True, (64, 16, 1, 128, 256, 8192), ("plain", 0)),    # eight a slab
    (True, (6, 64, 2, 128, 128, 1024), ("plain", 0)),     # 3 heads a group
    (True, (64, 64, 1, 64, 256, 8192), ("plain", 0)),     # state < a lane
    (True, (64, 64, 1, 128, 256, 8000), ("plain", 0)),    # ragged: refused
    (True, (4096, 64, 1, 128, 256, 8192), ("plain", 0)),  # states over VMEM
], indirect=["on_tpu"])
def test_the_scans_route_is_its_shapes_and_the_backend(on_tpu, shape, route):
    """`ops/ssd.scan_route(h, p, g, n, chunk, t)`: the static counter of
    which scan calls run `dwt_ssd_fwd` / `dwt_ssd_bwd`, and with how
    many heads a grid step."""
    from dlrover_wuqiong_tpu.ops import ssd

    assert ssd.scan_route(*shape) == route


def _grad_jaxpr_of(mixer, tokens):
    """The jaxpr of a mixer's loss gradient on two rows of `tokens`
    tokens, 64 wide, bfloat16."""
    u = jax.ShapeDtypeStruct((2, tokens, 64), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros(u.shape, u.dtype))["params"])
    return jax.make_jaxpr(jax.grad(lambda p, u: mixer.apply(
        {"params": p}, u).astype(jnp.float32).sum()))(params, u)


def _mixer_grad_jaxpr(mesh):
    from dlrover_wuqiong_tpu.models.mamba2 import Mamba2Config, Mamba2Mixer

    cfg = Mamba2Config(hidden_size=64, num_heads=8, head_dim=64, n_groups=2,
                       state_size=128, chunk_size=128, mesh=mesh)
    return _grad_jaxpr_of(Mamba2Mixer(cfg), 512)


def test_a_mixer_on_one_tpu_device_scans_in_the_kernels(on_tpu):
    """One forward and one backward kernel a mixer, over (batch rows,
    chunks, blocks of heads): 2 x 4 x 2 at two groups of four heads;
    and since PR 59 the short convolution's pair in front of them, over
    (blocks of 256 of the 1,024 channels, batch rows, blocks of rows):
    4 x 2 x 1 at one row block of 512."""
    from dlrover_wuqiong_tpu.ops import short_conv, ssd

    assert ssd.scan_route(8, 64, 2, 128, 128, 512) == ("kernel", 4)
    assert short_conv.conv_route(512, 1024, 4, jnp.bfloat16) == "kernel"
    assert sorted(_pallas_calls(_mixer_grad_jaxpr(None).jaxpr)) == [
        ("dwt_conv_bwd", (4, 2, 1)), ("dwt_conv_fwd", (4, 2, 1)),
        ("dwt_ssd_bwd", (2, 4, 2)), ("dwt_ssd_fwd", (2, 4, 2))]


def test_a_mixer_on_a_mesh_of_several_devices_keeps_the_plain_scan(on_tpu):
    """A Mosaic kernel cannot be partitioned by GSPMD (PR 22): where the
    model config carries a mesh of more than one device `scan_route`,
    which the mixer hands it, says "plain" whatever the shapes — and so
    does the convolution's `conv_route`; a mesh of one device is one
    device (the scan's pair and the convolution's)."""
    from jax.sharding import Mesh

    from dlrover_wuqiong_tpu.ops import short_conv, ssd

    two = Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    one = Mesh(np.array(jax.devices()[:1]), ("fsdp",))
    shape = (8, 64, 2, 128, 128, 512)
    assert ssd.scan_route(*shape, two) == ("plain", 0)
    assert ssd.scan_route(*shape, one) == ("kernel", 4)
    assert ssd.scan_route(*shape) == ("kernel", 4)
    assert short_conv.conv_route(512, 1024, 4, jnp.bfloat16, two) == "plain"
    assert short_conv.conv_route(512, 1024, 4, jnp.bfloat16, one) == "kernel"
    assert _pallas_calls(_mixer_grad_jaxpr(two).jaxpr) == []
    assert len(_pallas_calls(_mixer_grad_jaxpr(one).jaxpr)) == 4


def _two_devices():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]), ("fsdp",))


@pytest.mark.parametrize("on_tpu,shape,mesh,route", [
    # olmo_hybrid_7b.steady: one sequence of 8,192 = 128 chunks of 64,
    # fifteen heads held, keys of 96, values of 192, one device
    (True, (8192, 64, 15, 96, 192), None, ("kernel", 5)),
    (True, (8192, 64, 15, 96, 192), _two_devices, "chunked"),
    (False, (8192, 64, 15, 96, 192), None, "chunked"),   # every CPU run
    (True, (8192 + 32, 64, 15, 96, 192), None, "sequential"),   # ragged
    (True, (16, 64, 15, 96, 192), None, "sequential"),   # a parameter draw
    (True, (8192, 64, 30, 96, 192), None, ("kernel", 5)),  # all the heads
    (True, (8192, 64, 7, 96, 192), None, ("kernel", 1)),
    (True, (64, 16, 3, 8, 24), None, "chunked"),         # the nano widths
    (True, (8192, 8, 15, 96, 192), None, "chunked"),     # chunk off a tile
    (True, (8160, 48, 15, 96, 192), None, "chunked"),    # no power of two
    (True, (8192, 64, 15, 320, 192), None, "chunked"),   # keys too wide
    (True, (8192, 2048, 15, 256, 256), None, "chunked"),  # tiles over VMEM
    # ling3_0_flash.steady: a decay a key CHANNEL (the shape's sixth
    # entry), sixteen heads held, keys and values of 128, one device
    (True, (8192, 64, 16, 128, 128, True), None, ("kernel", 4)),
    (True, (8192, 64, 16, 128, 128, True), _two_devices, "chunked"),
    (False, (8192, 64, 16, 128, 128, True), None, "chunked"),  # every CPU run
    (True, (8184, 24, 16, 128, 128, True), None, "sequential"),  # no whole
    (False, (8184, 24, 16, 128, 128, True), None, "sequential"),  # sub-blocks
    (True, (8184, 24, 16, 128, 128), None, "chunked"),   # (a head's: a tile)
    (True, (8192, 64, 32, 128, 128, True), None, ("kernel", 4)),  # all heads
    (True, (64, 16, 4, 16, 16, True), None, "chunked"),  # the nano widths
    (True, (8192, 256, 16, 256, 256, True), None, "chunked"),  # over VMEM
    (True, (8192, 256, 16, 256, 256), None, ("kernel", 4)),  # (a head's fit)
], indirect=["on_tpu"])
def test_the_delta_rules_route_is_its_shapes_the_backend_and_the_mesh(
        on_tpu, shape, mesh, route):
    """`ops/delta_rule.delta_route(t, chunk, heads, dk, dv, mesh,
    channel_decay)`: the static counter of which calls run `dwt_gdr_fwd` /
    `dwt_gdr_bwd` — a decay a channel's `dwt_kda_fwd` / `dwt_kda_bwd` —
    and with how many heads a grid step."""
    from dlrover_wuqiong_tpu.ops import delta_rule as dr

    assert dr.delta_route(*shape[:5], mesh and mesh(), *shape[5:]) == route


@pytest.mark.parametrize("cell,shape,route", [
    ("olmo_hybrid_7b.steady", (8192, 64, 15, 96, 192), ("kernel", 5)),
    ("ling3_0_flash.steady", (8192, 64, 16, 128, 128, True), ("kernel", 4)),
    ("qwen3_next_80b_a3b.steady", (16384, 64, 32, 128, 128), ("kernel", 4)),
])
def test_the_solves_rounds_are_the_chunks_at_the_three_delta_cells(
        on_tpu, cell, shape, route):
    """`ops/delta_rule.solve_rounds(chunk)`, which `_solve` reads, beside
    `delta_route` (unchanged): at the three cells' chunk of 64 the rounds
    that join blocks of 2 and of 4 steps run on the vector units (PR 69)
    and those of 8, 16 and 32 stay two float32 products each, 36 MXU
    passes of a tile's 60 before; a chunk of 16 (the nano models') keeps
    one round of products, and nothing but the chunk decides.  With the
    compiled step's count of `dwt_gdr_*` / `dwt_kda_*` calls (the cells'
    compile tests) this is the mechanism's counter: it engages on every
    kernel call."""
    from dlrover_wuqiong_tpu.ops import delta_rule as dr

    chunk = shape[1]
    assert dr.delta_route(*shape[:5], None, *shape[5:]) == route, cell
    assert dr.solve_rounds(chunk) == {"vector": (2, 4), "mxu": (8, 16, 32)}
    assert 12 * len(dr.solve_rounds(chunk)["mxu"]) == 36
    assert dr.solve_rounds(16) == {"vector": (2, 4), "mxu": (8,)}
    assert dr.solve_rounds(128)["mxu"] == (8, 16, 32, 64)


def _delta_mixer_grad_jaxpr(mesh):
    from dlrover_wuqiong_tpu.models.gated_delta import (
        GatedDeltaConfig, GatedDeltaMixer)

    cfg = GatedDeltaConfig(hidden_size=64, num_heads=6, key_dim=32,
                           value_dim=64, chunk_size=16, mesh=mesh)
    return _grad_jaxpr_of(GatedDeltaMixer(cfg), 256)


def test_a_delta_mixer_takes_the_kernels_on_one_tpu_device_only(
        monkeypatch):
    """One forward and one backward kernel a mixer, over (batch rows,
    blocks of heads, steps): 2 x 2 x 2 at six heads, three a step, and
    sixteen chunks of 16, eight side by side a step; on a
    mesh of two devices (`GatedDeltaConfig.mesh`, handed on by
    `OlmoHybridConfig.linear_config`) and off the TPU none."""
    from dlrover_wuqiong_tpu.models.olmo_hybrid import OlmoHybridConfig

    two = _two_devices()
    assert OlmoHybridConfig.nano(mesh=two).linear_config().mesh is two
    assert _pallas_calls(_delta_mixer_grad_jaxpr(None).jaxpr) == []
    monkeypatch.setattr(mosaic, "on_tpu", lambda: True)
    assert sorted(_pallas_calls(_delta_mixer_grad_jaxpr(None).jaxpr)) == [
        ("dwt_gdr_bwd", (2, 2, 2)), ("dwt_gdr_fwd", (2, 2, 2))]
    assert _pallas_calls(_delta_mixer_grad_jaxpr(two).jaxpr) == []


def _kda_mixer_grad_jaxpr(mesh):
    from dlrover_wuqiong_tpu.models.kda import KDAConfig, KDAMixer

    cfg = KDAConfig(hidden_size=64, num_heads=8, key_dim=32, value_dim=32,
                    chunk_size=16, mesh=mesh)
    return _grad_jaxpr_of(KDAMixer(cfg), 256)


def test_a_kda_mixer_takes_the_channel_pair_on_one_tpu_device_only(
        monkeypatch):
    """As the gated-delta mixer above, the decay a key channel: one
    `dwt_kda_fwd` and one `dwt_kda_bwd` a mixer over (2 batch rows, 2
    blocks of four of eight heads, 2 steps of eight chunks of 16), none
    of the scalar pair; on a mesh of two devices (`KDAConfig.mesh`,
    handed on by `BailingHybridConfig.linear_config`) and off the TPU
    none."""
    from dlrover_wuqiong_tpu.models.bailing_hybrid import BailingHybridConfig

    two = _two_devices()
    assert BailingHybridConfig.nano(mesh=two).linear_config().mesh is two
    assert _pallas_calls(_kda_mixer_grad_jaxpr(None).jaxpr) == []
    monkeypatch.setattr(mosaic, "on_tpu", lambda: True)
    assert sorted(_pallas_calls(_kda_mixer_grad_jaxpr(None).jaxpr)) == [
        ("dwt_kda_bwd", (2, 2, 2)), ("dwt_kda_fwd", (2, 2, 2))]
    assert _pallas_calls(_kda_mixer_grad_jaxpr(two).jaxpr) == []


def _qwen3_next_routes(mesh):
    """Every route `qwen3_next_80b_a3b.steady` asks, at the cell's
    shapes: one sequence of 16,384, 16 query heads over 2 kv heads of
    256 with 64 lanes rotated, 32 value heads over 16 key heads of 128 |
    128, a convolution over 2,048 | 2,048 | 4,096 channels, 16 of 512
    experts of 512 held, 10 a token."""
    from dlrover_wuqiong_tpu.models.attention import goes_direct
    from dlrover_wuqiong_tpu.models.qwen3_next import Qwen3NextConfig
    from dlrover_wuqiong_tpu.ops import delta_rule, grouped_matmul as gm
    from dlrover_wuqiong_tpu.ops import rope, short_conv

    cfg = Qwen3NextConfig(vocab_size=18992, num_layers=4, experts_held=16,
                          mesh=mesh)
    t, lin = 16384, cfg.linear_config()
    assert (lin.num_heads, lin.key_heads, lin.neg_eigval, lin.mesh) == \
        (32, 16, False, mesh)
    experts = [(16, 2048, 512)] * 2 + [(16, 512, 2048)]
    return {
        "attention_route": fa.attention_route(16, 256),
        "kv_route": fa.kv_route(16, 2, 256),
        "goes_direct": goes_direct(cfg.attention_config(), 16, 256, t),
        "backward_route": fa.backward_route(t, t, 256, 256, 1, 16)[0],
        "rope_route_q": rope.rope_route(16 * 256, 256, mesh, 64),
        "rope_route_k": rope.rope_route(2 * 256, 256, mesh, 64),
        "delta_route": delta_rule.delta_route(t, 64, 32, 128, 128, mesh),
        "conv_route_q_and_k": short_conv.conv_route(
            t, 2048, 4, jnp.bfloat16, mesh),
        "conv_route_v": short_conv.conv_route(t, 4096, 4, jnp.bfloat16,
                                              mesh),
        "experts_route": gm.experts_route(t * 10, experts, 512, mesh),
    }


@pytest.mark.parametrize("on_tpu,mesh,routes", [
    (True, None, {
        "attention_route": ("direct", 1), "kv_route": ("indexed", 8),
        "goes_direct": True, "backward_route": "fused",
        "rope_route_q": "plain", "rope_route_k": "plain",
        "delta_route": ("kernel", 4), "conv_route_q_and_k": "kernel",
        "conv_route_v": "kernel", "experts_route": "kernel"}),
    (True, _two_devices, {
        "attention_route": ("direct", 1), "kv_route": ("indexed", 8),
        "goes_direct": False, "backward_route": "fused",
        "rope_route_q": "plain", "rope_route_k": "plain",
        "delta_route": "chunked", "conv_route_q_and_k": "plain",
        "conv_route_v": "plain", "experts_route": "plain"}),
    (False, None, {   # every CPU run
        "attention_route": ("direct", 1), "kv_route": ("indexed", 8),
        "goes_direct": False, "backward_route": "fused",
        "rope_route_q": "plain", "rope_route_k": "plain",
        "delta_route": "chunked", "conv_route_q_and_k": "plain",
        "conv_route_v": "plain", "experts_route": "plain"}),
], indirect=["on_tpu"])
def test_the_qwen3_next_cells_routes_are_its_shapes_and_its_site(
        on_tpu, mesh, routes):
    """PR 66's cell from its shapes alone: heads of 256 go DIRECT (two
    slabs a head, eight query heads reading one kv head's), the rotation
    of 64 of 256 lanes keeps the formula, the recurrence runs the scalar
    pair at 128 | 128 four value heads a grid step, the convolution the
    Pallas pair (whole lane tiles, which the Olmo hybrid's 1,440 are
    not), the share of the experts the held-rows kernels — on one TPU
    device and nowhere else."""
    assert _qwen3_next_routes(mesh and mesh()) == routes


@pytest.mark.parametrize("dtype,sha", [
    (jnp.float32,
     "8a984bb94ddcdc4f436c9c961286e605a9f18602ff27cf5058797de82fab862f"),
    (jnp.bfloat16,
     "a1e5403d701f02775bb281de6bf864b02452b1d0981a9f831bcde84ea05f3c52"),
])
def test_a_mixer_off_the_tpu_lowers_to_the_plain_scans_text(dtype, sha):
    """The fallback is the `jax.numpy` body PR 31 wrote, word for word:
    a `Mamba2Mixer`'s loss and gradient lower on the CPU to the text
    they lowered to before the kernels were there (the sha256 of that
    text, taken from the parent commit's checkout, PR 34), and
    `ssd_scan` there is `ssd_scan_plain`."""
    import hashlib

    from dlrover_wuqiong_tpu.models.mamba2 import Mamba2Config, Mamba2Mixer
    from dlrover_wuqiong_tpu.ops import ssd

    cfg = Mamba2Config(hidden_size=48, num_heads=8, head_dim=16, n_groups=2,
                       state_size=8, chunk_size=16, dtype=dtype)
    mixer = Mamba2Mixer(cfg)
    u = jax.ShapeDtypeStruct((2, 64, 48), jnp.float32)
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(1), jnp.zeros(u.shape))["params"])

    def loss(p, u):
        return mixer.apply({"params": p}, u).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(params, u).as_text()
    assert "pallas" not in text and "custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == sha
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (2, 64, 8, 16), (2, 64, 8), (8,), (2, 64, 2, 8), (2, 64, 2, 8),
        (8,))]
    lowered = [jax.jit(lambda *a, fn=fn: fn(*a, chunk=16, dtype=dtype)
                       ).lower(*args).as_text()
               for fn in (ssd.ssd_scan, ssd.ssd_scan_plain)]
    assert lowered[0] == lowered[1]


# ----------------------------------- (B) the environment is in none of it

RETIRED = [("DWT_FA_PACK", "4"), ("DWT_FA_NO_FUSED", "1"),
           ("DWT_FA_STREAMED", "1"), ("DWT_FP8_DENSE", "1"),
           ("DWT_REMAT_POLICY", "dots")]


def _programs_and_keys(monkeypatch):
    from dlrover_wuqiong_tpu.auto.compile_cache import train_step_cache_key
    from dlrover_wuqiong_tpu.auto.warm_pool import WarmSpec
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
    from dlrover_wuqiong_tpu.serving.engine import (
        ServeSpec,
        serve_step_cache_key,
    )
    from dlrover_wuqiong_tpu.telemetry.perf import executable_key

    cfg = dataclasses.replace(GPTConfig.nano(), remat=True, fp8=False,
                              use_flash_attention=False)
    model = GPT(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    out = {
        # the reference path a CPU run takes, and the kernels' path
        "attention_cpu": str(_attention_grad_jaxpr(1, 8, 256, 64)),
        "model": str(jax.make_jaxpr(jax.grad(
            lambda p: model.apply(p, ids).astype(jnp.float32).sum()))(
            params)),
        "train_key": train_step_cache_key(
            {"fsdp": 8}, {"remat": True}, {"n_layer": 2}, True, 1,
            backend="cpu"),
        "executable_key": executable_key("fp", 8, "cpu"),
        "serve_key": serve_step_cache_key({"n_layer": 2}, ServeSpec(),
                                          backend="cpu"),
        "spec_key": WarmSpec(
            n_devices=8, strategy=[["fsdp", {}]],
            model={"kind": "gpt", "config": {"n_layer": 2}},
            batch_shape=[8, 32]).spec_key(),
    }
    with monkeypatch.context() as m:
        m.setattr(mosaic, "on_tpu", lambda: True)
        out["attention_tpu"] = str(_attention_grad_jaxpr(1, 8, 256, 64))
    return out


@pytest.mark.parametrize("name,value", RETIRED)
def test_a_retired_variable_changes_no_program_and_no_key(monkeypatch, name,
                                                          value):
    monkeypatch.delenv(name, raising=False)
    unset = _programs_and_keys(monkeypatch)
    monkeypatch.setenv(name, value)
    assert _programs_and_keys(monkeypatch) == unset


def _modules(package):
    import ast
    import pathlib

    import dlrover_wuqiong_tpu

    root = pathlib.Path(dlrover_wuqiong_tpu.__file__).parent / package
    return [(path, ast.parse(path.read_text()))
            for path in sorted(root.rglob("*.py"))]


@pytest.mark.parametrize("package", ["ops", "models", "parallel"])
def test_the_backend_is_read_in_one_place(package):
    """`ops/mosaic.py` holds the ONE reading of the backend and the one
    test for a `shard_map`, and everyone calls them through the module:
    no other module of `ops/`, `models/` or `parallel/` calls
    `jax.default_backend()`, defines a copy, or binds `on_tpu` by name
    at import — a patch of `mosaic.on_tpu` (tests/conftest.py) would
    not reach it.  And no kernel module stands on another's private
    names: what they share is the toolkit's."""
    import ast

    public = {"on_tpu", "inside_shard_map", "kernel_site"}
    copies = public | {"_" + name for name in public}
    found = []
    for path, tree in _modules(package):
        toolkit = (package, path.name) == ("ops", "mosaic.py")
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                names = {alias.name for alias in node.names}
                if "on_tpu" in names:
                    found.append((path.name, "imports on_tpu by name"))
                if package == "ops" and node.level == 1 and node.module \
                        not in (None, "mosaic") and any(
                            n.startswith("_") for n in names):
                    found.append((path.name, f"{node.module}'s privates"))
            if toolkit:
                continue
            if isinstance(node, ast.Attribute) \
                    and node.attr == "default_backend":
                found.append((path.name, "reads the backend"))
            if isinstance(node, ast.FunctionDef) and node.name in copies:
                found.append((path.name, f"defines {node.name}"))
    assert found == []
    if package == "ops":
        defined = {node.name for path, tree in _modules("ops")
                   if path.name == "mosaic.py" for node in tree.body
                   if isinstance(node, ast.FunctionDef)}
        assert public <= defined


# -------------------------- (C) fused, split and reference backward agree


@pytest.mark.parametrize("d,dv", [(64, 64), (128, 128), (192, 128)])
@pytest.mark.parametrize("sq,sk", [
    (128, 128), (256, 256), (128, 256), (256, 128)])
def test_fused_and_split_backward_agree_with_the_reference(sq, sk, d, dv):
    """The same arrays through both forms of the backward — one block
    each way, and the blocks halved — and through `jax.grad` of the plain
    reference (interpret mode); q and k `d` wide, v and dO `dv`."""
    keys = jax.random.split(jax.random.PRNGKey(sq + sk + d), 4)
    widths = {0: (sq, dv), 1: (sq, d), 2: (sk, d), 3: (sk, dv)}
    g, q, k, v = (jax.random.normal(kx, (2, *widths[i]), jnp.float32)
                  for i, kx in enumerate(keys))
    scale = d ** -0.5

    def loss(q, k, v):
        o, _ = fa._reference_with_lse(q[None], k[None], v[None], True, scale)
        return (o[0] * g).sum()

    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for block_q, block_k in ((sq, sk), (sq // 2, sk // 2)):
        o, lse = fa._fa_forward_pallas(q, k, v, True, scale, block_q,
                                       block_k, interpret=True)
        got = fa._fa_backward_pallas(q, k, v, o, lse, g, True, scale,
                                     block_q, block_k, interpret=True)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=5e-4)


# ------------------------------------------- (D) fused K and TrainingArgs


@pytest.fixture(scope="module")
def built():
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                              use_flash_attention=False, remat=False)
    return auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                           strategy=[("fsdp", {})], materialize=False,
                           seq_len=32)


def test_fused_train_step_of_one_is_train_step(built):
    assert built.fused_train_step(1) is built.train_step
    assert built.fused_train_step(0) is built.train_step
    assert built._fused_cache == {}


def test_fused_train_step_is_cached_by_k(built):
    four = built.fused_train_step(4)
    assert four is not built.train_step
    assert built.fused_train_step(4) is four
    assert built.fused_train_step(2) is not four
    assert sorted(built._fused_cache) == [2, 4]


def test_tune_variants_accepts_zero_only():
    from dlrover_wuqiong_tpu.trainer.trainer import TrainingArgs

    assert TrainingArgs(tune_variants=0).tune_variants == 0
    for bad in (1, -1, 3):
        with pytest.raises(ValueError, match="tune_variants"):
            TrainingArgs(tune_variants=bad)


def test_training_args_has_33_fields_and_no_tuner_knob():
    from dlrover_wuqiong_tpu.trainer.trainer import TrainingArgs

    names = [f.name for f in dataclasses.fields(TrainingArgs)]
    assert len(names) == 33
    assert [n for n in names if n.startswith("tune_")] == \
        ["tune_config_steps", "tune_variants"]
