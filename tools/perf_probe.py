"""On-chip step decomposition probe.

Times a GPT-2 step's components in isolation on the real TPU so kernel
work targets the measured-largest bucket instead of guesses.  Sync is a
host readback; iterations chain on carried values.
A device trace gives the same split per op (PERF.md, "Where the time
goes"); this probe predates one.

Usage: python tools/perf_probe.py [attn|attn_bwd|attn_sweep|attn_direct|attn_bd|head|
model|opt|step|lib|dispatch|rpc|gmm|rows_map|rope|moe_numbers|delta|delta_kda|sums|hc|conv|gate|sscan] ...  (no args = step/attn/head/model/opt).  One JSON line
per probe as it finishes, then ONE summary line
``{"probes": [...], "emitted": N}`` under the shared report-CLI contract
(common/report_cli.py; -h to stderr rc=0, unknown probe rc=1).
`dispatch` measures the fused-vs-unfused dispatch-overhead win of
the K-step driver (trainer/train_step.py) in THIS environment;
`rpc` streams per-round control-plane RPCs/s per verb class against a
per-frame-fsync and a group-commit master, rounds interleaved.
`attn` is GPT-2's attention forward and backward by the host's clock,
then `attn_bwd`: the backward alone at the six several-block cells'
shapes, fused at every pack that fits against the dq + dk/dv pair, by
device time.
`attn_direct` is GPT-2's layer on the two routes of `attention_route`
by the host's clock, then the causal forward ALONE under grouped heads
at the four grouped cells' shapes by device time: the slab step
(`dwt_fa_fwd`) against the group step (`dwt_fa_grp_fwd`) at six (q rows
x keys), with each one's distance from the slab step's o and lse;
`--shape b,heads,kv_heads,T,d [--blocks 1024x1024,512x1024] [--heads
6,3]` runs that sweep alone at one shape (PERF.md section 6, PR 67, has
the table).
`attn_bd` is the block-masked attention (`ops/block_attention.py`) at
SDAR's shape (or `--shape b,heads,kv,t,d`, t a copy's tokens, `--length`
the block length) by device time: `dwt_fa_bd_fwd` alone and
`dwt_fa_bd_bwd` alone under every plan of `--blocks 512x512,1024x512`
(block x tile) x `--heads 1,2,4` (query heads a grid step), in ms a call
and us a head-tile, with what runs beside the backward kernel (delta)
and each plan's distance from the first; lines kept under
`chiprun_out/attn_bd.jsonl` (PERF.md section 6, PR 71, has the table
`_STEPS`' comment quotes).
`gmm` reads, from a profiler trace, the device time of each grouped
product of a chip's share of an expert layer (98,304 rows of which
6,800 are held in 8 groups) as `ops/grouped_matmul.py`'s kernels and as
`lax.ragged_dot` run it: a stand-alone jit puts layout copies of its
own around either, so a host clock around the call measures those.
`rows_map` reads the same way what the elementwise passes between those
products cost, as the compiler's fusions over the whole T*k-row buffer
and as `dwt_rows_map_*` over the tiles that hold a held row, at both
share cells' shapes and held shares.
`rope` reads the same way one rotation of the projections' rows, forward
and backward, at the rotating cells' shapes (Laguna's half-rotated
heads among them): `models/llama.apply_rope`'s formula as the compiler
fuses it against `dwt_rope` (`ops/rope.py`) at three row tiles.
`moe_numbers` reads the same way the expert layer's bookkeeping at the
share cells' shapes: each line that indexed T*k single numbers (a
`bincount`, a gather through a sort or its inverse, `take_along_axis`
and its transpose) against the compare-and-sum, the sort operand or the
select `models/moe.py` runs in its place.
`sums` reads the same way the sum of a token's k expert rows at the
three share cells' shapes: the gather of all T*k rows through the sort's
inverse against the kernel route's loop over the held rows in assignment
order at three chunks a turn, and counts the entries that differ.
`delta` reads the same way the gated delta rule at its three cells'
shapes — the Olmo hybrid's (a decay a head, 15 heads of 96 | 192), Ling's
(a decay a key channel, 16 of 128 | 128) and Qwen3-Next's (a decay a
head, 32 of 128 | 128 over 16,384 steps, four a grid step) — forward and
forward + backward: first the tile solve alone in us a tile, then the
chunked `jax.numpy` form against the form's pair, `dwt_gdr_*` or
`dwt_kda_*` (`ops/delta_rule.py`), at several heads and one or two chunks
a grid step, with each one's distance from the chunked form and
`solve_rounds` (which rounds of the solve ran where); `delta_kda` is
Ling's half alone.
`hc` reads the same way one sublayer's hyper-connection at Xing's
stream (four lanes of 8,192 x 3,584): the plain route's fusions against
`dwt_hc_pre` / `_post` / `_post_bwd` / `_pre_bwd` (`ops/hc_mix.py`) at
three token tiles, with each kernel's GB/s over the bytes its pass moves.
`conv` reads the same way one layer's short convolution + silu at the
four cells' shapes that call it (the two Mamba-2 hybrids', one of Ling's
three calls, Olmo's two widths), forward and forward + gradient:
`models/mamba2.causal_conv_silu`'s plain lines as the compiler fuses
them against `dwt_conv_fwd` / `dwt_conv_bwd` (`ops/short_conv.py`) at
several rows a grid step, in ms and in GB/s over the passes the least
implementation moves (2 forward, 5 with the gradient: 7 a layer under
full recomputation); lines kept under `chiprun_out/`.
`gate` reads the same way one layer's head-wise output gate at Laguna's
two head counts (1 x 16,384 x 64 and x 48 heads of 128), forward and
forward + gradient: `models/llama.LlamaAttention`'s plain line, and the
same product with g spread by a one-hot matrix product, as the compiler
fuses them against `dwt_gate` / `dwt_gate_bwd` (`ops/head_gate.py`) at
several row tiles, in ms and in GB/s over the passes the least
implementation moves (2 forward, 5 with the gradient).
`sscan` reads the same way one call of the selective scan
(`ops/selective_scan.py`) at the Mamba-1 cell's shape (1 x 8,192 x 5,120
channels x 16 states), forward alone and forward + gradient:
`dwt_sscan_fwd` / `dwt_sscan_bwd` over channel block x chunk, in ms and
in ns a (token, 128-channel) step, with each plan's distance from the
first; lines kept under `chiprun_out/pr72/sscan_probe.jsonl`.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

B, H, T, D = 24, 12, 1024, 64
E = H * D
VOCAB = 50304


def _sync(x):
    leaf = jax.tree.leaves(x)[0]
    return float(jnp.float32(leaf.reshape(-1)[0]))


def _time(fn, arg, iters=20, warmup=3):
    """fn(arg) -> same-structured arg (chained); returns seconds/iter."""
    for _ in range(warmup):
        arg = fn(arg)
    _sync(arg)
    t0 = time.perf_counter()
    for _ in range(iters):
        arg = fn(arg)
    _sync(arg)
    return (time.perf_counter() - t0) / iters


_EMITTED: list = []  # per-probe records, folded into the summary line


def _emit_raw(obj):
    """One historical per-probe JSON line, recorded for the summary."""
    _EMITTED.append(obj)
    print(json.dumps(obj), flush=True)


def _emit(name, ms, **extra):
    _emit_raw({"probe": name, "ms": round(ms * 1e3, 3), **extra})


def _qkv(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, T, D), jnp.bfloat16)
    return q, k, v


INNER = 8  # dependent inner repeats per jit call: amortizes the fixed
# per-dispatch overhead that otherwise dominates short probes


def probe_attn(block_q=1024, block_k=1024, tag="attn"):
    from dlrover_wuqiong_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv()

    fa = functools.partial(flash_attention, causal=True, sm_scale=None,
                           block_q=block_q, block_k=block_k)

    @jax.jit
    def fwd(args):
        q, k, v = args
        for _ in range(INNER):
            q = fa(q, k, v)
        return (q, k, v)

    @jax.jit
    def fwdbwd(args):
        q, k, v = args

        def loss(q, k, v):
            return fa(q, k, v).astype(jnp.float32).sum()

        for _ in range(INNER):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            q, k, v = (dq.astype(q.dtype), dk.astype(k.dtype),
                       dv.astype(v.dtype))
        return (q, k, v)

    t_f = _time(fwd, (q, k, v), iters=5) / INNER
    t_fb = _time(fwdbwd, (q, k, v), iters=5) / INNER
    # ideal: fwd 2 matmuls, bwd 5 matmuls of 2*B*H*T*T*D flops each
    mm = 2 * B * H * T * T * D
    _emit(tag, t_fb, fwd_ms=round(t_f * 1e3, 3),
          blocks=[block_q, block_k],
          ideal_fwd_ms=round(2 * mm / 155e12 * 1e3, 2),
          ideal_fwdbwd_ms=round(7 * mm / 155e12 * 1e3, 2))


# the six several-block cells' attention: (cell, T, d_qk, d_v, heads,
# batch, window).  The route and layout follow from the shapes
# (`attention_route`, `backward_route`).  The last row is no cell's:
# Xing's heads at half its rows, where four units a grid step are two
# thirds of the VMEM they are at 8,192 (PR 52: code size or bytes)
BWD_CELLS = [
    ("olmoe_1b_7b", 4096, 128, 128, 16, 5, None),
    ("nemotron3_nano_30b_a3b", 8192, 128, 128, 32, 2, None),
    ("granite4_h_micro", 8192, 64, 64, 32, 1, None),
    ("smallthinker_21b_a3b", 16384, 128, 128, 28, 2, None),
    ("smallthinker_21b_a3b", 16384, 128, 128, 28, 2, 4096),
    ("kimi_vl_a3b", 16384, 192, 128, 16, 2, None),
    ("xing4_0_29b_a4b", 8192, 192, 128, 32, 1, None),
    ("32_heads_4096_transposed", 4096, 192, 128, 32, 1, None),
]


def probe_attn_bwd(cells=BWD_CELLS):
    """The backward ALONE at the several-block cells' attention shapes,
    as ONE fused kernel (`backward_route`'s answer, and on the
    transposed layout every pack of 8/4/2/1 whose resident set fits,
    the rule's or not) and as the dq + dk/dv pair: the device time of
    each kernel from a profiler trace, so that the route's rule rests on
    kernel times and not on a whole cell's.  A line names what was
    timed: the sweep's grid and `_fused_bwd_vmem`'s bytes at that
    pack."""
    from dlrover_wuqiong_tpu.ops import flash_attention as fa

    for cell, t, d, dv, heads, batch, window in cells:
        layout, slab_heads = fa.attention_route(heads, d, dv)
        bh = batch * heads
        ks = jax.random.split(jax.random.PRNGKey(t + d), 4)
        if layout == "direct":
            shapes = [(batch, t, heads * d)] * 4
            slabs = fa._projected_slabs(
                [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes[:3]],
                heads)[0]
        else:
            shapes = [(bh, t, d), (bh, t, d), (bh, t, dv), (bh, t, dv)]
            slabs = None
        q, k, v, do = (jax.random.normal(key, s, jnp.bfloat16)
                       for key, s in zip(ks, shapes))
        plan = dict(causal=True, sm_scale=d ** -0.5, block_q=1024,
                    block_k=1024, interpret=False, slabs=slabs,
                    window=window)
        o, lse = jax.jit(functools.partial(fa._fa_forward_pallas, **plan))(
            q, k, v)
        rule = fa.backward_route(t, t, d, dv, slab_heads, bh)
        most = 1 if slabs else fa._fit_pack(bh)
        blocks = t // 1024
        vmem = {p: fa._fused_bwd_vmem(
            p, t, 1024, 1024, fa._kernel_head_dim(d),
            fa._kernel_head_dim(dv), slab_heads or 1, 2)
            for p in (8, 4, 2, 1) if p <= most}
        routes = [("fused", p) for p, held in vmem.items()
                  if held <= fa._VMEM_LIMIT] + [("split", most)]
        swept = fa._window_plan(
            fa._effective_window(window, True, t), blocks, blocks, 1024,
            1024, 0).get("steps", blocks)
        for route in routes:
            fn = jax.jit(functools.partial(fa._fa_backward_pallas,
                                           route=route, **plan))
            ops = _device_ops_ms(fn, q, k, v, o, lse, do, top=6)
            kernels = {n: ms for n, ms in ops.items()
                       if n.startswith("dwt_fa_")}
            groups = batch * slabs.per_row if slabs else bh // route[1]
            _emit_raw({"probe": "attn_bwd", "cell": cell,
                       "shape": [bh, t, d, dv], "layout": layout,
                       "window": window, "route": list(route),
                       "the_rule": route == rule,
                       "grid": [groups, blocks, swept],
                       "fused_vmem_mib": round(vmem[route[1]] / 2 ** 20, 1)
                       if route[0] == "fused" else None,
                       "kernels_ms": round(sum(kernels.values()), 4),
                       "device_ops_ms": ops})


def probe_attn_cells():
    probe_attn()
    probe_attn_bwd()


def probe_attn_direct(shape=None, blocks=None, heads=None):
    """The same heads on the two routes of `attention_route`, a layer's
    attention as a model runs it, forward + backward: c_attn's
    (B, T, 3*H*D) output through `flash_attention_projected` (the
    kernels index it, two heads a lane slab), and through the split,
    the cut to heads and `mha`'s transposes (the transposed route).
    Then `probe_attn_grouped` at the four grouped cells' shapes — or,
    with `--shape b,heads,kv,T,d` (and `--blocks 512x1024,...`,
    `--heads 8,16`), that sweep alone at that shape."""
    if shape or blocks or heads:
        return probe_attn_grouped({"shape": shape} if shape else None,
                                  blocks, heads)
    from dlrover_wuqiong_tpu.ops.flash_attention import (
        flash_attention_projected,
        mha,
    )

    qkv = jax.random.normal(jax.random.PRNGKey(0), (B, T, 3 * E),
                            jnp.bfloat16)

    def direct(qkv):
        return flash_attention_projected((qkv,), H)

    def transposed(qkv):
        q, k, v = (x.reshape(B, T, H, D) for x in jnp.split(qkv, 3, -1))
        return mha(q, k, v).reshape(B, T, E)

    for tag, attn in (("attn_direct", direct),
                      ("attn_transposed", transposed)):
        @jax.jit
        def fwdbwd(qkv, attn=attn):
            for _ in range(INNER):
                qkv = jax.grad(lambda x: attn(x).astype(
                    jnp.float32).sum())(qkv)
            return qkv

        _emit(tag, _time(fwdbwd, qkv, iters=5) / INNER, heads=[H, D])
    probe_attn_grouped()


# (b, heads, kv heads, T, d) of the causal calls that take the group
# step (`fa.forward_route`): Laguna's two full layers, SmallThinker's
# global layer, Nemotron's attention layers, Qwen3-Next's
GROUPED_CELLS = {
    "laguna_xs_2_33b_a3b": (1, 48, 8, 16384, 128),
    "smallthinker_21b_a3b": (2, 28, 4, 16384, 128),
    "nemotron3_nano_30b_a3b": (2, 32, 2, 8192, 128),
    "qwen3_next_80b_a3b": (1, 16, 2, 16384, 256),
}
GROUP_BLOCKS = ((1024, 1024), (512, 1024), (1024, 512), (256, 1024),
                (512, 2048), (512, 512))


def probe_attn_grouped(shapes=None, blocks=None, heads=None):
    """The causal forward ALONE under grouped heads on the direct route,
    by device time from a profiler trace: the slab step (`dwt_fa_fwd`,
    one query head a grid step) against the group step
    (`dwt_fa_grp_fwd`) at every (q rows, keys) of `blocks` (default
    `GROUP_BLOCKS`, the rule's first) and every heads-a-step of `heads`
    (default: the rule's), with each one's
    distance from the slab step's o and lse.  `shapes`: {name: (b,
    heads, kv heads, T, d)}, default the four grouped cells'."""
    from dlrover_wuqiong_tpu.ops import flash_attention as fa

    for cell, (b, h, kv, t, d) in (shapes or GROUPED_CELLS).items():
        ks = jax.random.split(jax.random.PRNGKey(t + h), 3)
        q, k, v = (jax.random.normal(key, (b, t, n * d), jnp.bfloat16)
                   for key, n in zip(ks, (h, kv, kv)))
        slabs = fa._projected_slabs((q, k, v), h)[0]
        plan = dict(causal=True, sm_scale=d ** -0.5, block_q=1024,
                    block_k=1024, interpret=False, slabs=slabs)
        rep = h // kv
        rule = fa.forward_route(t, t, d, rep)
        tiles = b * h * fa.causal_tile_count(t, t)[0]
        routes = [(("slab", 0), None)] + [
            (("group", g), bb) for bb in blocks or GROUP_BLOCKS
            for g in (heads or [fa._group_heads(rep, d)])
            if t % bb[0] == 0 and t % bb[1] == 0 and rep % g == 0]
        want = None
        for route, bb in routes:
            fn = jax.jit(functools.partial(fa._fa_forward_pallas, route=route,
                                           blocks=bb, **plan))
            line = {"probe": "attn_grouped", "cell": cell,
                    "shape": [b, h, kv, t, d], "route": list(route),
                    "blocks": bb, "the_rule": route == rule and (
                        bb is None or tuple(bb) == GROUP_BLOCKS[0])}
            try:
                ops = _device_ops_ms(fn, q, k, v, top=3)
                got = fn(q, k, v)
            except Exception as e:  # noqa: BLE001 — a step Mosaic refuses
                _emit_raw(dict(line, error=repr(e)[:300]))
                continue
            want = want or got
            ms = sum(x for n, x in ops.items() if n.startswith("dwt_fa_"))
            _emit_raw(dict(
                line, kernel_ms=round(ms, 4),
                us_a_head_tile=round(ms * 1e3 / tiles, 4),
                o_max_diff=float(jnp.abs(got[0].astype(jnp.float32)
                                         - want[0].astype(jnp.float32)).max()),
                lse_max_diff=float(jnp.abs(got[1] - want[1]).max()),
                device_ops_ms=ops))


BD_SHAPE = (1, 32, 4, 8192, 128)  # `sdar_30b_a3b.steady`'s: b, heads, kv
# heads, a copy's tokens (2 x that many positions), d
BD_PLANS = ((512, 512), (1024, 512))  # (block, tile)


def probe_attn_bd(shape=None, blocks=None, heads=None, length=4,
                  interpret=False, out=None):
    """The block-masked attention (`ops/block_attention.py`) at one shape
    under several plans — (block, tile) of `blocks` x heads a step of
    `heads` — by device time from a profiler trace: the forward alone
    (`dwt_fa_bd_fwd`), then the backward alone over the forward's o and
    lse (`dwt_fa_bd_bwd`, and beside it every other op of the call:
    delta), each in ms a call and in us a head and (512 x 512) tile of
    the plan, with the largest distance of o and of each gradient from
    the first plan's.  A plan Mosaic refuses is a line with its error.
    Lines kept under `out` (default `chiprun_out/attn_bd.jsonl`).
    `--shape b,heads,kv,t,d` (t: a copy's tokens), `--blocks
    512x512,1024x512`, `--heads 1,2,4`, `--length L`; `interpret`: a
    rehearsal off the chip, whose times are no device's."""
    from dlrover_wuqiong_tpu.ops import block_attention as ba

    b, h, kv, t, d = shape or BD_SHAPE
    ks = jax.random.split(jax.random.PRNGKey(t + h), 4)
    q, k, v, g = (jax.random.normal(key, (b, 2 * t, n * d), jnp.bfloat16)
                  for key, n in zip(ks, (h, kv, kv, h)))
    kw = dict(n_head=h, n_kv=kv, block_length=length, scale=d ** -0.5,
              interpret=interpret)
    out = out or os.path.join("chiprun_out", "attn_bd.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    want = None
    for block, tile in blocks or BD_PLANS:
        tiles = b * h * ba.bd_tile_count(t, length, "kernel", block, tile)[0] \
            * (tile / ba.TILE) ** 2
        for heads_a_step in heads or (1, 2, 4):
            plan = dict(kw, block=block, tile=tile, heads=heads_a_step)
            line = {"probe": "attn_bd",
                    "device": jax.devices()[0].device_kind,
                    "shape": [b, h, kv, t, d],
                    "length": length, "block": block, "tile": tile,
                    "heads": heads_a_step}
            try:
                fwd = functools.partial(ba._forward_jit, **plan)
                o, lse = fwd(q, k, v)
                bwd = functools.partial(ba._backward_jit, **plan)
                f_ops = _device_ops_ms(fwd, q, k, v, top=8)
                b_ops = _device_ops_ms(bwd, q, k, v, o, lse, g, top=8)
                got = (o,) + tuple(bwd(q, k, v, o, lse, g))
            except Exception as e:  # noqa: BLE001 — a step Mosaic refuses
                line["error"] = repr(e)[:300]
            else:
                want = want or got
                f_ms, b_ms = f_ops.get("dwt_fa_bd_fwd", 0.0), b_ops.get(
                    "dwt_fa_bd_bwd", 0.0)
                line.update(
                    fwd_ms=f_ms, bwd_ms=b_ms,
                    bwd_beside_ms=round(sum(b_ops.values()) - b_ms, 4),
                    fwd_us_a_head_tile=round(f_ms * 1e3 / tiles, 4),
                    bwd_us_a_head_tile=round(b_ms * 1e3 / tiles, 4),
                    off_o_dq_dk_dv=[float(jnp.abs(
                        x.astype(jnp.float32) - y.astype(jnp.float32)).max())
                        for x, y in zip(got, want)],
                    bwd_device_ops_ms=b_ops)
            _emit_raw(line)
            with open(out, "a") as f_out:
                f_out.write(json.dumps(line) + "\n")


def probe_attn_sweep():
    for bq, bk in [(1024, 1024), (512, 1024), (512, 512), (256, 512),
                   (256, 256), (128, 128)]:
        probe_attn(bq, bk, tag=f"attn_{bq}x{bk}")
    probe_attn_direct()


def probe_lib():
    """jax's bundled TPU flash kernel at the same shape — reference point."""
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes,
            flash_attention as jax_fa,
        )
    except ImportError as e:
        print(json.dumps({"probe": "lib", "error": repr(e)}), flush=True)
        return
    q, k, v = _qkv()
    bs = BlockSizes(block_q=512, block_k_major=512, block_k=512,
                    block_b=1,
                    block_q_major_dkv=512, block_k_major_dkv=512,
                    block_k_dkv=512, block_q_dkv=512,
                    block_k_major_dq=512, block_k_dq=512, block_q_dq=512)

    @jax.jit
    def fwd(args):
        q, k, v = args
        for _ in range(INNER):
            q = jax_fa(q, k, v, causal=True, sm_scale=1.0,
                       block_sizes=bs).astype(q.dtype)
        return (q, k, v)

    @jax.jit
    def fwdbwd(args):
        q, k, v = args

        def loss(q, k, v):
            return jax_fa(q, k, v, causal=True, sm_scale=1.0,
                          block_sizes=bs).astype(jnp.float32).sum()

        for _ in range(INNER):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            q, k, v = (dq.astype(q.dtype), dk.astype(k.dtype),
                       dv.astype(v.dtype))
        return (q, k, v)

    try:
        t_f = _time(fwd, (q, k, v), iters=5) / INNER
        t_fb = _time(fwdbwd, (q, k, v), iters=5) / INNER
        _emit("lib_flash", t_fb, fwd_ms=round(t_f * 1e3, 3))
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"probe": "lib", "error": repr(e)[:300]}),
              flush=True)


def probe_dots():
    """Standalone TF/s for each distinct dot SHAPE inside the FA kernel
    (r5 verdict item 2): the kernel's 7 matmuls are 3 d=64-contractions
    (S=QK^T, recomputed S, dP=dO V^T), 2 plain seq-contractions (O=PV,
    dQ=dS K) and 2 transposed-operand seq-contractions (dV=P^T dO,
    dK=dS^T Q) — the two seq flavors measure ~35% apart, so the blended
    floor is 3*t_d + 2*t_seq + 2*t_seqT.  In-kernel fwd+bwd ms minus
    this floor = softmax/VPU/layout residual."""
    BH = B * H
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    a64 = jax.random.normal(ks[0], (BH, T, D), jnp.bfloat16)
    b64 = jax.random.normal(ks[1], (BH, T, D), jnp.bfloat16)
    p = jax.random.normal(ks[2], (BH, T, T), jnp.bfloat16)
    mm = 2 * BH * T * T * D

    def _probe(tag, spec, lhs, rhs, out_like):
        def _dep(x, out):
            # data-dependent epsilon chains iterations without letting
            # XLA fold the dependency away (0*x would be simplified)
            return x + (out.ravel()[0] * 1e-30).astype(x.dtype)

        @jax.jit
        def run(state):
            out, l, r = state
            for _ in range(INNER):
                out = jnp.einsum(spec, _dep(l, out), r).astype(out.dtype)
            return (out, l, r)

        t = _time(run, (out_like, lhs, rhs), iters=5) / INNER
        _emit(tag, t, tflops=round(mm / t / 1e12, 1))
        return t

    # d=64 contraction (S = Q K^T): output (BH, T, T)
    t_d = _probe("dot_qk_d64", "bqd,bkd->bqk", a64, b64, p)
    # seq contraction (O = P V): output (BH, T, D)
    t_s = _probe("dot_av_seq", "bqk,bkd->bqd", p, b64, a64)
    # seq contraction transposed operands (dK = dS^T Q): output (BH, T, D)
    t_t = _probe("dot_dk_seqT", "bqk,bqd->bkd", p, a64, a64)
    blended = 3 * t_d + 2 * t_s + 2 * t_t
    _emit("dots_blended_floor", blended,
          note="3x d64-contract + 2x seq + 2x seqT = the kernel's 7 dots "
               "at their standalone rates; in-kernel total minus this = "
               "softmax/VPU/layout residual")


def probe_head():
    """LM head + CE fwd+bwd: x (B,T,E) @ wte (V,E)^T -> ce."""
    from dlrover_wuqiong_tpu.models.gpt import cross_entropy_loss

    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, E), jnp.bfloat16)
    wte = jax.random.normal(jax.random.PRNGKey(1), (VOCAB, E), jnp.float32)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, VOCAB)

    @jax.jit
    def fwdbwd(args):
        x, wte = args

        def loss(x, wte):
            logits = jnp.einsum("bte,ve->btv", x, wte.astype(x.dtype))
            return cross_entropy_loss(logits, tgt)

        for _ in range(INNER):
            dx, dw = jax.grad(loss, argnums=(0, 1))(x, wte)
            x, wte = dx.astype(x.dtype), dw
        return (x, wte)

    t = _time(fwdbwd, (x, wte), iters=5) / INNER
    mm = 2 * B * T * E * VOCAB
    _emit("head_ce", t, ideal_ms=round(3 * mm / 155e12 * 1e3, 2))


def probe_model():
    """Full model fwd (no CE) and fwd+bwd with sum loss (no head)."""
    import dataclasses

    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = dataclasses.replace(GPTConfig.gpt2(), remat=False)
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0), batch=1, seq=T)
    idx = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                             cfg.vocab_size)

    @jax.jit
    def fwd(params):
        h = model.apply({"params": params}, idx, return_hidden=True)[1]
        # consume hidden so the head matmul isn't in this probe
        return jax.tree.map(
            lambda p: p + 0 * h.astype(jnp.float32).mean().astype(p.dtype)
            if p.ndim else p, params)

    @jax.jit
    def fwdbwd(params):
        def loss(p):
            h = model.apply({"params": p}, idx, return_hidden=True)[1]
            return h.astype(jnp.float32).sum()

        g = jax.grad(loss)(params)
        return g

    t_f = _time(fwd, params)
    t_fb = _time(fwdbwd, params)
    _emit("model_no_head", t_fb, fwd_ms=round(t_f * 1e3, 3))


def probe_opt():
    import optax

    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig.gpt2()
    params = GPT(cfg).init_params(jax.random.PRNGKey(0), batch=1, seq=8)
    opt = optax.adamw(3e-4)
    state = opt.init(params)

    @jax.jit
    def upd(args):
        params, state = args
        for _ in range(INNER):
            grads = jax.tree.map(lambda p: p * 1e-3, params)
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        return (params, state)

    t = _time(upd, (params, state), iters=5) / INNER
    _emit("optimizer", t)


def probe_step():
    import dataclasses

    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = dataclasses.replace(GPTConfig.gpt2(), remat=False)
    res = auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                          devices=jax.devices()[:1], strategy=[("fsdp", {})])
    data = jax.random.randint(jax.random.PRNGKey(0), (B, T + 1), 0,
                              cfg.vocab_size)
    b = res.place_batch({"input_ids": data[:, :-1], "labels": data[:, 1:]})

    def stepper(state):
        state, _ = res.train_step(state, b)
        return state

    t = _time(stepper, jax.tree.map(jnp.copy, res.state))
    _emit("full_step", t)


def probe_dispatch(k: int = 8, steps: int = 32):
    """Fused-vs-unfused dispatch overhead on the real train step.

    Drives the SAME compiled step once per dispatch (chained on state, one
    final readback) and as one K-step fused scan per dispatch
    (trainer/train_step.py), on one chip.  The per-step delta is the
    amortizable dispatch cost of THIS environment, and `auto_k` is what
    the trainer's auto-tuner would pick here (target <2% overhead)."""
    import dataclasses

    import numpy as np
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.common.util import measure_dispatch_overhead_s
    from dlrover_wuqiong_tpu.data.elastic_dataset import stack_batches
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
    from dlrover_wuqiong_tpu.trainer.train_step import auto_fused_steps

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = dataclasses.replace(GPTConfig.gpt2(), remat=False)
        bsz = B
    else:  # runnable anywhere: the CPU regime is dispatch-BOUND at nano
        cfg = dataclasses.replace(GPTConfig.nano(), use_flash_attention=False,
                                  remat=False)
        bsz = 8
    seq = cfg.block_size
    res = auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                          devices=jax.devices()[:1], strategy=[("fsdp", {})])
    x = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          (bsz, seq + 1), dtype=np.int32)
    hb = {"input_ids": x[:, :-1], "labels": x[:, 1:]}
    b = res.place_batch(dict(hb))

    st = jax.tree.map(jnp.copy, res.state)
    st, m = res.train_step(st, b)
    _sync(m["loss"])  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        st, m = res.train_step(st, b)
    _sync(m["loss"])  # steps chain on state; one readback syncs them all
    t_unfused = (time.perf_counter() - t0) / steps

    fused = res.fused_train_step(k)
    fb = res.place_fused_batch(stack_batches([hb] * k))
    st, m = fused(st, fb)
    _sync(m["loss"])  # compile + warm
    blocks = max(2, steps // k)
    t0 = time.perf_counter()
    for _ in range(blocks):
        st, m = fused(st, fb)
    _sync(m["loss"])  # one readback per K-step fusion
    t_fused = (time.perf_counter() - t0) / (blocks * k)

    overhead = measure_dispatch_overhead_s()
    # the STEP's own amortizable overhead, backed out of the measured
    # fused-vs-unfused delta (a K-fusion removes (K-1)/K of it) — the
    # scalar probe underestimates it badly for a many-leaf state
    step_overhead = max((t_unfused - t_fused) * k / (k - 1), 0.0)
    _emit("dispatch_fused_vs_unfused", t_unfused, k=k,
          fused_ms=round(t_fused * 1e3, 3),
          saved_ms_per_step=round((t_unfused - t_fused) * 1e3, 3),
          scalar_dispatch_overhead_ms=round(overhead * 1e3, 3),
          step_dispatch_overhead_ms=round(step_overhead * 1e3, 3),
          auto_k=auto_fused_steps(t_fused, overhead_s=step_overhead))


def probe_splash():
    """jax splash-attention (newer vmapped MQA-style kernel) — causal."""
    try:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
            splash_attention_mask as sm,
        )
    except ImportError as e:
        print(json.dumps({"probe": "splash", "error": repr(e)}), flush=True)
        return
    q, k, v = _qkv()
    mask = sm.MultiHeadMask(
        [sm.CausalMask((T, T)) for _ in range(H)])
    kernel = sk.make_splash_mha(mask, head_shards=1, q_seq_shards=1)

    @jax.jit
    def fwd(args):
        q, k, v = args
        for _ in range(INNER):
            q = jax.vmap(kernel)(q, k, v).astype(q.dtype)
        return (q, k, v)

    @jax.jit
    def fwdbwd(args):
        q, k, v = args

        def loss(q, k, v):
            return jax.vmap(kernel)(q, k, v).astype(jnp.float32).sum()

        for _ in range(INNER):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            q, k, v = (dq.astype(q.dtype), dk.astype(k.dtype),
                       dv.astype(v.dtype))
        return (q, k, v)

    try:
        t_f = _time(fwd, (q, k, v), iters=5) / INNER
        t_fb = _time(fwdbwd, (q, k, v), iters=5) / INNER
        _emit("splash", t_fb, fwd_ms=round(t_f * 1e3, 3))
    except Exception as e:  # noqa: BLE001
        _emit_raw({"probe": "splash", "error": repr(e)[:300]})


def probe_remat():
    """Step time + compiled HBM temp (activation) bytes per remat policy."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
    from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

    base = GPTConfig.gpt2()
    data = jax.random.randint(jax.random.PRNGKey(0), (B, T + 1), 0,
                              base.vocab_size)
    for policy in [None, "full", "dots", "offload_dots"]:
        strat = [("fsdp", {})]
        if policy is None:
            strat.append(("checkpoint", {"enabled": False}))
        else:
            strat.append(("checkpoint", {"policy": policy}))
        try:
            res = auto_accelerate(GPT(base), optimizer=optax.adamw(3e-4),
                                  devices=jax.devices()[:1], strategy=strat)
            b = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
            budget = compiled_memory(
                res.train_step.lower(res.state, b).compile())

            def stepper(state):
                state, _ = res.train_step(state, b)
                return state

            t = _time(stepper, jax.tree.map(jnp.copy, res.state),
                      iters=10, warmup=2)
            _emit(f"remat_{policy}", t,
                  temp_gb=round(budget.get("temp_bytes", 0) / 2**30, 3),
                  live_gb=round(budget.get("live_bytes", 0) / 2**30, 3))
            del res
        except Exception as e:  # noqa: BLE001
            _emit_raw({"probe": f"remat_{policy}",
                       "error": repr(e)[:200]})


def probe_rpc(rounds=2, clients=48, procs=4, duration_s=1.5,
              fsync_floor_ms=3.0):
    """Control-plane RPC throughput per verb class, streamed per round.

    Two masters stay up for the whole probe — per-frame-fsync baseline
    and group-commit — and rounds ALTERNATE between them (the same
    same-session interleave rule as the kernel A/B probes: host load
    drifts ±10% run to run, so paired rounds beat sequential blocks).
    Each round prints one line with journaled/buffered/polling RPCs/s,
    the aggregate p99 and the journal's frames-per-fsync gauge; the
    last line summarizes the journaled-verb speedup over the paired
    baseline rounds.  CPU-only (fleet_bench machinery — no accelerator
    anywhere); ``fsync_floor_ms`` emulates PD-class journal storage,
    pass 0 via DWT_RPC_PROBE_FSYNC_FLOOR_MS to measure bare local
    fsync."""
    from dlrover_wuqiong_tpu.fleet_bench import FleetMaster, run_fleet

    floor = float(os.environ.get("DWT_RPC_PROBE_FSYNC_FLOOR_MS",
                                 fsync_floor_ms))
    rates = {"perframe": [], "grouped": []}
    with FleetMaster(group_commit=False, fsync_floor_ms=floor) as base, \
            FleetMaster(group_commit=True, fsync_floor_ms=floor) as gc:
        for r in range(rounds):
            for mode, fm in (("perframe", base), ("grouped", gc)):
                got = run_fleet(fm.addr, clients=clients, procs=procs,
                                duration_s=duration_s)
                js = fm.journal_stats()
                rates[mode].append(got["journaled"]["rpc_per_s"])
                _emit_raw({
                    "probe": "rpc", "mode": mode, "round": r,
                    "clients": got["clients"],
                    "journaled_rpc_per_s": got["journaled"]["rpc_per_s"],
                    "buffered_rpc_per_s": got["buffered"]["rpc_per_s"],
                    "polling_rpc_per_s": got["polling"]["rpc_per_s"],
                    "rpc_per_s": got["rpc_per_s"],
                    "rpc_p99_ms": got["rpc_p99_ms"],
                    "rpc_errors": got["rpc_errors"],
                    "journal_batch_mean": js["batch_mean"],
                    "fsync_floor_ms": js["fsync_floor_ms"]})
    base_mean = sum(rates["perframe"]) / max(1, len(rates["perframe"]))
    gc_mean = sum(rates["grouped"]) / max(1, len(rates["grouped"]))
    _emit_raw({"probe": "rpc", "summary": True, "rounds": rounds,
               "journaled_rpc_per_s_perframe": round(base_mean, 1),
               "journaled_rpc_per_s_grouped": round(gc_mean, 1),
               "journaled_speedup":
                   round(gc_mean / base_mean, 2) if base_mean else 0.0})


def _device_ops_ms(fn, *args, iters=5, top=4):
    """{device op: ms a call} of `fn(*args)`, the `top` largest, from a
    profiler trace of `iters` calls (`utils/xplane.py` reads it: ops of
    one name, `copy.4` and `copy.5`, are summed)."""
    import tempfile

    from dlrover_wuqiong_tpu.utils.xplane import parse_trace_dir

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="perf_probe_") as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        profile = parse_trace_dir(trace_dir)
    return {o.name: round(o.total_s * 1e3 / iters, 4)
            for o in (profile.top(k=top) if profile else [])}


def probe_gmm(rows=98304, sizes=(1530, 400, 950, 700, 1100, 520, 900, 700)):
    """The hybrid cell's grouped products, forward and both backward
    forms at both shapes: `dwt_gmm` / `dwt_gmm_t` / `dwt_tgmm` against
    the compiler's `ragged-dot` kernels (PERF.md section 6, PR 36)."""
    from dlrover_wuqiong_tpu.ops import grouped_matmul as gm

    sizes = jnp.array(sizes, jnp.int32)
    plan = dict(tile=gm._ROW_TILE, columns=gm._COLUMN_TILE, interpret=False)

    def plain(l, r):
        return jax.lax.ragged_dot(l, r, sizes)

    def plain_bwd(which):
        return jax.jit(lambda l, r, d: jax.vjp(plain, l, r)[1](d)[which])

    for c, n in ((2688, 1856), (1856, 2688)):
        ks = jax.random.split(jax.random.PRNGKey(c), 3)
        lhs = jax.random.normal(ks[0], (rows, c), jnp.bfloat16)
        rhs = (0.02 * jax.random.normal(ks[1], (len(sizes), c, n))
               ).astype(jnp.bfloat16)
        d_out = jax.random.normal(ks[2], (rows, n), jnp.bfloat16)
        for name, fn, args in (
                ("ragged_dot", jax.jit(plain), (lhs, rhs)),
                ("ragged_dot_d_lhs", plain_bwd(0), (lhs, rhs, d_out)),
                ("ragged_dot_d_rhs", plain_bwd(1), (lhs, rhs, d_out)),
                ("dwt_gmm", lambda l, r: gm._gmm(
                    l, r, sizes, transposed=False, **plan), (lhs, rhs)),
                ("dwt_gmm_t", lambda d, r: gm._gmm(
                    d, r, sizes, transposed=True, **plan), (d_out, rhs)),
                ("dwt_tgmm", lambda l, d: gm._tgmm(
                    l, d, sizes, dtype=jnp.dtype(jnp.bfloat16), **plan),
                 (lhs, d_out))):
            _emit_raw({"probe": "gmm", "what": name, "shape": [c, n],
                       "held_rows": int(sizes.sum()), "rows": rows,
                       "device_ops_ms": _device_ops_ms(fn, *args)})


def probe_rows_map():
    """The elementwise passes of a share's expert layer at both cells'
    shapes and held shares (the hybrid's relu^2 over 98,304 x 1856 at
    7.2% held, SmallThinker's ReGLU over 196,608 x 768 at 29.7%; the sum
    of two row gradients and the combine's backward pair over the model
    width): `models/moe.py`'s `jax.numpy` lines under their masks, jitted
    alone, against `dwt_rows_map_*` (PERF.md section 6, PR 38)."""
    from dlrover_wuqiong_tpu.models import moe
    from dlrover_wuqiong_tpu.ops import grouped_matmul as gm

    def draw(seed, *shapes, dtype=jnp.bfloat16):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
        return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]

    for rows, d, f, held, gate_act in ((98304, 2688, 1856, 7050, None),
                                       (196608, 2560, 768, 58400,
                                        jax.nn.relu)):
        act = moe._activation(gate_act)
        n = 1 if gate_act is None else 2
        held_rows = jnp.int32(held)
        mask = (jnp.arange(rows) < held)[:, None]
        products = draw(0, *[(rows, f)] * n)
        d_h, = draw(1, (rows, f))
        ys, d_rows = draw(2, (rows, d), (rows, d))
        gates, = draw(3, (rows, 1), dtype=jnp.float32)

        def plain_act(*a):
            return act(*(jnp.where(mask, x, 0) for x in a))[0]

        def plain_weigh(ys, d_rows, gates):
            dots = (ys.astype(jnp.float32) * d_rows).sum(-1)
            return (d_rows * gates).astype(ys.dtype), dots

        def mapped(fn, alias=None):
            return lambda *a: gm.rows_map(fn, held_rows, *a, alias=alias)

        def grad_of(fn):
            return lambda *a: jax.vjp(fn, *a[:-1])[1](a[-1])

        for name, fn, args in (
                ("plain_act", plain_act, products),
                ("plain_act_bwd", grad_of(plain_act), (*products, d_h)),
                ("plain_sum_of_two", jnp.add, (ys, d_rows)),
                ("plain_weigh", plain_weigh, (ys, d_rows, gates)),
                ("map_act", mapped(act), products),
                ("map_act_bwd", grad_of(lambda *a: mapped(act)(*a)[0]),
                 (*products, d_h)),
                ("map_sum_of_two", mapped(gm._add), (ys, d_rows)),
                ("map_weigh", mapped(moe._weigh), (ys, d_rows, gates))):
            _emit_raw({"probe": "rows_map", "what": name,
                       "shape": [rows, d, f], "held_rows": held,
                       "device_ops_ms": _device_ops_ms(jax.jit(fn), *args)})


def probe_moe_numbers():
    """The expert layer's bookkeeping at the share cells' shapes
    (32,768 tokens x 6 of 64 experts, 16 held; 16,384 x 6 of 128, 8
    held): each `jax.numpy` line that indexed T*k single numbers,
    jitted alone, against the dense form `models/moe.py` runs now —
    the counts, the gates into expert order, the dots back by
    assignment, the scores at the chosen experts and its transpose
    (PERF.md section 6, PR 45)."""
    from dlrover_wuqiong_tpu.models import moe

    for tokens, k, num_experts, held in ((32768, 6, 64, 16),
                                         (16384, 6, 128, 8)):
        keys = jax.random.split(jax.random.PRNGKey(tokens), 4)
        probs = jax.nn.sigmoid(jax.random.normal(keys[0],
                                                 (tokens, num_experts)))
        gates, experts = jax.lax.top_k(probs, k)
        flat = experts.reshape(-1)
        flat = jnp.where(flat < held, flat, held)
        order = jnp.argsort(flat)
        inv = jnp.argsort(order).reshape(tokens, k).T
        dots = jax.random.normal(keys[1], (tokens * k,))
        d_gates = jax.random.normal(keys[2], (tokens, k))
        held_rows = (flat < held).sum()

        def gathered_gates(flat, gates):
            order = jnp.argsort(flat)
            return order, gates.reshape(-1)[order]

        def chosen(fn):
            return lambda probs, d: jax.vjp(fn, probs)[1](d)[0]

        def taken(probs):
            return jnp.take_along_axis(probs, experts, axis=-1)

        def selected(probs):
            return moe.route_top_k(probs, k, False)[0]

        for name, fn, args in (
                ("bincount_held", lambda f: jnp.bincount(f, length=held),
                 (flat,)),
                ("bincount_all", lambda e: jnp.bincount(
                    e.reshape(-1), length=num_experts), (experts,)),
                ("expert_counts", lambda e: moe.expert_counts(
                    e, num_experts), (experts,)),
                ("argsort_and_gather_of_gates", gathered_gates,
                 (flat, gates)),
                ("expert_order", moe._expert_order, (flat, gates)),
                ("dots_through_inv", lambda d, inv: jnp.where(
                    inv < held_rows, d[inv], 0.0).T, (dots, inv)),
                ("numbers_by_assignment", lambda d, o:
                 moe._numbers_by_assignment(d, o, held_rows, k),
                 (dots, order)),
                ("take_along_axis", taken, (probs,)),
                ("take_along_axis_bwd", chosen(taken), (probs, d_gates)),
                ("top_k_and_select", selected, (probs,)),
                ("select_bwd", chosen(selected), (probs, d_gates))):
            _emit_raw({"probe": "moe_numbers", "what": name,
                       "shape": [tokens, k, num_experts, held],
                       "device_ops_ms": _device_ops_ms(jax.jit(fn), *args)})


def probe_sums(chunks=(2048, 4096, 8192)):
    """The sum of a token's k expert rows at the three share cells'
    shapes and even routing (32,768 tokens x 6 of 64 experts, 16 or 8
    held; 16,384 x 6 of 128, 8 held), with gates and without: the gather
    of all T*k rows through the sort's inverse (`models/moe.py`'s plain
    route) against the loop over the held rows in assignment order and
    its one gather of T entries (the kernel route's `_sum_held`) at
    three chunks a turn, with how many entries of the result differ
    from the plain route's (PERF.md section 6, PR 50)."""
    from dlrover_wuqiong_tpu.models import moe

    shipped = moe._SUM_CHUNK
    try:
        for tokens, k, num_experts, held, width in (
                (32768, 6, 64, 16, 2560), (32768, 6, 64, 8, 2048),
                (16384, 6, 128, 8, 2688)):
            keys = jax.random.split(jax.random.PRNGKey(tokens + width), 2)
            probs = jax.nn.softmax(jax.random.normal(keys[0],
                                                     (tokens, num_experts)))
            gates, experts = jax.lax.top_k(probs, k)
            flat = jnp.where(experts.reshape(-1) < held, experts.reshape(-1),
                             held)
            order, flat_gates = moe._expert_order(flat, gates)
            held_rows = (flat < held).sum().astype(jnp.int32)
            rows = jax.random.normal(keys[1], (tokens * k, width),
                                     jnp.bfloat16)
            ways = {"plain": jnp.argsort(order).reshape(tokens, k).T}

            def summed(route, way, weighted):
                return jax.jit(lambda r, g: moe.combine(
                    r, g if weighted else None,
                    flat_gates if weighted else None,
                    order, way, held_rows, route))

            want = {w: summed("plain", ways["plain"], w)(rows, gates)
                    for w in (True, False)}
            for chunk in (None, *chunks):
                moe._SUM_CHUNK = chunk or shipped
                route = "kernel" if chunk else "plain"
                way = moe._held_by_token(order, flat_gates, flat.reshape(
                    tokens, k) < held) if chunk else ways["plain"]
                for weighted in (True, False):
                    fn = summed(route, way, weighted)
                    differ = int((fn(rows, gates).astype(jnp.float32)
                                  != want[weighted].astype(jnp.float32)).sum())
                    _emit_raw({"probe": "sums", "route": route, "chunk": chunk,
                               "gates": weighted, "held_rows": int(held_rows),
                               "shape": [tokens, k, num_experts, held, width],
                               "differs_from_plain": differ,
                               "device_ops_ms": _device_ops_ms(
                                   fn, rows, gates, top=8)})
    finally:
        moe._SUM_CHUNK = shipped


def probe_rope():
    """One rotation, forward and backward, at SmallThinker's q and k
    (2 x 16,384 x 3,584 and x 512, heads of 128), latent attention's q
    part (2 x 16,384 x 16 heads of 64), OLMoE's q (5 x 4,096 x 2,048)
    and Laguna's full layers' q and k (1 x 16,384 x 48 and x 8 heads of
    128 whose first 64 features turn): the formula of
    `models/llama.apply_rope` jitted alone against `dwt_rope` (PERF.md
    section 6, PR 44 and PR 56)."""
    from unittest import mock

    from dlrover_wuqiong_tpu.models.llama import apply_rope, rope_freqs
    from dlrover_wuqiong_tpu.ops import mosaic, rope

    def grad_of(fn):
        return lambda x, d_out: jax.vjp(fn, x)[1](d_out)[0]

    several = (256, 512, 1024)
    for b, t, lanes, d, rotated, tiles in (
            (2, 16384, 3584, 128, 128, several),
            (2, 16384, 512, 128, 128, (512,)),
            (2, 16384, 1024, 64, 64, (512,)),
            (5, 4096, 2048, 128, 128, (512,)),
            (1, 16384, 6144, 128, 64, several),
            (1, 16384, 1024, 128, 64, (512,))):
        cos, sin = rope_freqs(rotated, t, 10000.0)
        keys = jax.random.split(jax.random.PRNGKey(lanes), 2)
        x, d_out = (jax.random.normal(k, (b, t, lanes), jnp.bfloat16)
                    for k in keys)

        def plain(x):
            return apply_rope(x, cos, sin, head_dim=d)

        def kernel(tile):
            return lambda x: rope._rope_kernels(x, cos, sin, d, tile=tile)

        cases = [("plain", plain, None)] + [
            ("dwt_rope", kernel(tile), tile) for tile in tiles]
        for name, fn, tile in cases:
            # the formula is what a call off the TPU traces
            with mock.patch.object(mosaic, "on_tpu",
                                   lambda: name != "plain"):
                for what, f, args in ((name, jax.jit(fn), (x,)),
                                      (name + "_bwd", jax.jit(grad_of(fn)),
                                       (x, d_out))):
                    _emit_raw({"probe": "rope", "what": what,
                               "shape": [b, t, lanes], "head": d,
                               "rotated": rotated, "tile": tile,
                               "device_ops_ms": _device_ops_ms(f, *args)})


# the delta rule at its cells' shapes: ((heads, chunks) a grid step to
# try, (b, T, H, dk, dv, chunk), the decay a key CHANNEL's, the write
# gate's top)
DELTA_FORMS = {
    "head": (((5, 1), (1, 2), (3, 2), (5, 2), (15, 2)),
             (1, 8192, 15, 96, 192, 64), False, 2.0),   # olmo_hybrid_7b.steady
    "channel": (((4, 1), (1, 2), (2, 2), (4, 2)),
                (1, 8192, 16, 128, 128, 64), True, 1.0),  # ling3_0_flash.steady
    "grouped": (((4, 1), (1, 2), (2, 2), (4, 2)),   # qwen3_next_80b_a3b.steady
                (1, 16384, 32, 128, 128, 64), False, 1.0),
}


def probe_delta(forms=tuple(DELTA_FORMS), interpret=False, shapes=None):
    """The gated delta rule at its three cells' shapes (chunk 64, bfloat16
    products):

    | form | cell | b x T | heads of dk | dv | decay | write gate |
    | --- | --- | --- | --- | --- | --- | --- |
    | `head` | `olmo_hybrid_7b.steady` | 1 x 8,192 | 15 of 96 | 192 | a head | (0, 2) |
    | `channel` | `ling3_0_flash.steady` | 1 x 8,192 | 16 of 128 | 128 | a key channel in (-5, 0) | (0, 1) |
    | `grouped` | `qwen3_next_80b_a3b.steady` | 1 x 16,384 | 32 of 128 | 128 | a head | (0, 1) |

    forward and forward + backward: the chunked `jax.numpy` form against
    the form's kernel pair (`dwt_gdr_*` / `dwt_kda_*`) at several (heads,
    chunks) a grid step, each with the kernel route's largest relative
    distance from the chunked form; first the tile solve ALONE (`_solve`
    over the shape's head-tiles, one kernel), in us a tile.  Every line
    carries `solve_rounds(chunk)`: which of the solve's rounds ran on the
    vector units and which on the MXU (PERF.md section 6: PR 48, PR 58,
    PR 69)."""
    for form in forms:
        plans, shape, channel, beta_top = DELTA_FORMS[form]
        _probe_delta_form(form, plans, (shapes or {}).get(form, shape),
                          channel, beta_top, interpret)


def _probe_solve_alone(tiles, hb, chunk, interpret):
    """`ops/delta_rule._solve` over `tiles` (128 x 128) tiles of two
    chunks, `hb` a grid step as the pairs take a block of heads: the
    device's us a tile."""
    from jax.experimental import pallas as pl

    from dlrover_wuqiong_tpu.ops import delta_rule as dr

    size = dr._ROWS

    def kernel(low_ref, out_ref):
        _, strict, solve = dr._masks(chunk, size // chunk)
        for h in range(hb):
            out_ref[h] = dr._solve(jnp.where(strict, low_ref[h], 0.0), solve)

    block = pl.BlockSpec((hb, size, size), lambda i: (i, 0, 0))
    fn = jax.jit(lambda low: pl.pallas_call(
        kernel, grid=(tiles // hb,), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(low.shape, jnp.float32),
        interpret=interpret, name="dwt_solve_alone")(low))
    low = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (tiles, size, size))
    ms = _device_ops_ms(fn, low, top=1).get("dwt_solve_alone", 0.0)
    return round(1e3 * ms / tiles, 4)


def _probe_delta_form(form, plans, shape, channel, beta_top, interpret):
    from dlrover_wuqiong_tpu.ops import delta_rule as dr

    b, t, h, dk, dv, chunk = shape
    rounds = dr.solve_rounds(chunk)
    tiles, hb = b * h * t // dr._ROWS, dr._heads_block(h)
    _emit_raw({"probe": "delta", "form": form, "what": "solve_alone",
               "tiles": tiles, "heads_a_step": hb, "solve_rounds": rounds,
               "us_a_tile": _probe_solve_alone(tiles, hb, chunk, interpret)})
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv), jnp.bfloat16)
    if channel:  # the safe gate's range, most channels far from the bound
        g = -5.0 * jax.nn.sigmoid(
            jax.random.normal(ks[3], (b, t, h, dk)) - 2.0)
    else:
        g = -0.2 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = beta_top * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    d_out = jax.random.normal(ks[5], (b, t, h, dv))
    args = (q, k, v, g, beta)

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), *jax.vjp(fn, *a)[1](d_out)))

    def chunked(*a):
        return (dr._chunked_channel if channel else dr._chunked)(
            *a, chunk, jnp.bfloat16)

    want = both(chunked)(*args)
    name = "dwt_kda" if channel else "dwt_gdr"
    cases = [("chunked", chunked, None)] + [
        (name, functools.partial(
            lambda plan, *a: dr._chunk_kernels(
                *a, chunk, jnp.bfloat16, plan[0], interpret, plan[1]),
            plan), plan) for plan in plans]
    for what, fn, plan in cases:
        off = [float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)
                             ).max() / jnp.abs(y.astype(jnp.float32)).max())
               for x, y in zip(both(fn)(*args), want)]
        for label, f in ((what, jax.jit(fn)), (what + "_fwd_bwd", both(fn))):
            _emit_raw({"probe": "delta", "form": form, "what": label,
                       "heads_and_chunks_a_step": plan,
                       "solve_rounds": rounds if plan else None,
                       "off_o_dq_dk_dv_dg_dbeta": [round(x, 6) for x in off],
                       "device_ops_ms": _device_ops_ms(f, *args, top=6)})


def probe_hc(shape=(1, 4, 8192, 3584), tiles=(128, 256, 512)):
    """One sublayer's hyper-connection at `xing4_0_29b_a4b.steady`'s
    stream (1 x 4 lanes x 8,192 x 3,584, bfloat16), the branch a
    doubling: `models/hyper_connection.py`'s plain route (`coefficients`,
    `read`, `write`) against `ops/hc_mix.py`'s four kernels at several
    token tiles, forward alone and forward + backward, every device op's
    ms a call and each kernel's GB/s over the bytes its pass moves
    (PERF.md section 6, PR 54)."""
    from dlrover_wuqiong_tpu.models import hyper_connection as hc
    from dlrover_wuqiong_tpu.ops import hc_mix

    b, n, t, d = shape
    cfg = hc.HyperConnectionConfig(hidden_size=d, lanes=n, norm_eps=1e-5)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    leaves = hc.HyperConnection(cfg).init(keys[0])["params"]
    leaves = {**leaves, "phi": 0.02 * jax.random.normal(
        keys[1], leaves["phi"].shape)}
    x = jax.random.normal(keys[2], shape, jnp.bfloat16)
    vector = b * t * d * 2  # one hidden vector a token, bytes
    passes = {"dwt_hc_pre": n + 1, "dwt_hc_post": 2 * n + 1,
              "dwt_hc_post_bwd": 3 * n + 2, "dwt_hc_pre_bwd": 3 * n + 1}

    def plain(leaves, x):
        h_pre, h_post, h_res = hc.coefficients(leaves, x, cfg)
        return hc.write(h_res, h_post, x, 2 * hc.read(h_pre, x))

    def kernels(tile):
        plan = hc_mix.plan(t, tile)

        def fn(leaves, x):
            u, coef, x = hc_mix.mix_in(
                x, leaves["phi"], leaves["alpha"][0], leaves["b_pre"],
                cfg.norm_eps, plan)
            h_post, h_res = hc._post_res(leaves, coef, cfg)
            return hc_mix.mix_out(h_res, h_post, x, 2 * u, plan)
        return fn

    def both(fn):
        return lambda leaves, x, d_out: jax.vjp(fn, leaves, x)[1](d_out)

    want = jax.jit(both(plain))(leaves, x, x)
    for name, fn, tile in [("plain", plain, None)] + [
            ("dwt_hc", kernels(tile), tile) for tile in tiles]:
        got = jax.jit(both(fn))(leaves, x, x)
        off = [float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)
                             ).max() / jnp.abs(w.astype(jnp.float32)).max())
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        for what, f, args in ((name, jax.jit(fn), (leaves, x)),
                              (name + "_fwd_bwd", jax.jit(both(fn)),
                               (leaves, x, x))):
            ops = _device_ops_ms(f, *args, top=256)
            _emit_raw({
                "probe": "hc", "what": what, "shape": list(shape),
                "tile": tile, "all_ops_ms": round(sum(ops.values()), 4),
                "off_alpha_b_post_b_pre_b_res_phi_x": [
                    round(o, 6) for o in off],
                "gb_per_s": {k: round(passes[k] * vector / ms / 1e6, 1)
                             for k, ms in ops.items() if k in passes},
                "device_ops_ms": dict(list(ops.items())[:8])})


# (cell, (b, T, channels), a bias): what `causal_conv_silu` is handed
CONV_CALLS = (
    ("nemotron3_nano_30b_a3b", (2, 8192, 6144), True),
    ("granite4_h_micro", (1, 8192, 4352), True),
    ("ling3_0_flash_q_k_or_v", (1, 8192, 2048), False),
    ("olmo_hybrid_7b_q_or_k", (1, 8192, 1440), False),
    ("olmo_hybrid_7b_v", (1, 8192, 2880), False),
)


def probe_conv(calls=CONV_CALLS, rows=(1024, 2048, 4096), out=None):
    """One layer's short convolution + silu, forward alone and forward
    + gradient (x, filter, bias), at the cells' shapes in bfloat16: the
    plain lines of `models/mamba2.causal_conv_silu` against
    `ops/short_conv.py`'s pair wherever `conv_route` lets a shape in, at
    several rows a grid step; every device op's ms a call, their sum,
    and that sum as GB/s over the passes the least implementation moves
    (PERF.md section 6, PR 59).  The lines are kept in `out`
    (`chiprun_out/pr59/conv_probe.jsonl`)."""
    from unittest import mock

    from dlrover_wuqiong_tpu.models.mamba2 import causal_conv_silu
    from dlrover_wuqiong_tpu.ops import mosaic, short_conv

    out = out or os.path.join("chiprun_out", "pr59", "conv_probe.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    for cell, shape, has_bias in calls:
        t, channels = shape[1:]
        keys = jax.random.split(jax.random.PRNGKey(channels), 4)
        x, d_out = (jax.random.normal(k, shape, jnp.bfloat16)
                    for k in keys[:2])
        leaves = [jax.random.uniform(keys[2], (4, channels), jnp.float32,
                                     -0.5, 0.5)]
        if has_bias:
            leaves.append(jax.random.uniform(
                keys[3], (channels,), jnp.float32, -0.5, 0.5))

        def plain(x, *leaves):
            return causal_conv_silu(x, leaves[0], (leaves + (None,))[1],
                                    jnp.bfloat16)

        def kernel(rows):
            return lambda x, *leaves: short_conv._conv_kernels(
                x, leaves[0], (leaves + (None,))[1], jnp.bfloat16,
                rows=rows)

        def both(fn):  # y too, or the compiler drops the forward
            def run(d_out, x, *leaves):
                y, vjp = jax.vjp(fn, x, *leaves)
                return (y, *vjp(d_out))
            return run

        takes = short_conv.conv_route(t, channels, 4, jnp.bfloat16)
        cases = [("plain", plain, None)] + [
            ("dwt_conv", kernel(r), r) for r in rows
            if takes == "kernel" and t % r == 0]
        want = None
        for name, fn, r in cases:
            # the plain lines are what a call off the TPU traces
            with mock.patch.object(mosaic, "on_tpu", lambda: False):
                got = jax.jit(both(fn))(d_out, x, *leaves)[1:]
                want = want or got
                off = [float(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32)).max()
                             / jnp.abs(w.astype(jnp.float32)).max())
                       for g, w in zip(got, want)]
                for what, f, args, passes in (
                        (name, jax.jit(fn), (x, *leaves), 2),
                        (name + "_fwd_bwd", jax.jit(both(fn)),
                         (d_out, x, *leaves), 5)):
                    ops = _device_ops_ms(f, *args, top=256)
                    ms = sum(ops.values())
                    line = {
                        "probe": "conv", "cell": cell, "what": what,
                        "shape": list(shape), "route": takes,
                        "rows_a_step": r, "all_ops_ms": round(ms, 4),
                        "passes": passes, "gb_per_s": ms and round(
                            passes * x.size * 2 / ms / 1e6, 1),
                        "off_dx_dfilter_dbias": [round(o, 6) for o in off],
                        "device_ops_ms": dict(list(ops.items())[:8])}
                    _emit_raw(line)
                    with open(out, "a") as f_out:
                        f_out.write(json.dumps(line) + "\n")


def probe_sscan(shape=(1, 8192, 5120, 16),
                blocks=(256, 512, 1024), chunks=(64, 128, 256), out=None):
    """One call of the selective scan at the Mamba-1 cell's shape in
    bfloat16 (dt float32): `ops/selective_scan.py`'s pair at every
    (channel block, chunk) the kernels' VMEM takes, forward alone and
    forward + gradient (x, dt, A, B, C); every device op's ms a call,
    their sum, the kernels' own ns a (token, 128-channel) step, and the
    gradients' distance from the first plan's (PERF.md section 6,
    PR 72).  The lines are kept in `out`."""
    from dlrover_wuqiong_tpu.ops import selective_scan as ss

    out = out or os.path.join("chiprun_out", "pr72", "sscan_probe.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    bsz, t, d, n = shape
    keys = jax.random.split(jax.random.PRNGKey(d), 6)
    x, d_out = (jax.random.normal(k, (bsz, t, d), jnp.bfloat16)
                for k in keys[:2])
    dt = jax.nn.softplus(jax.random.normal(keys[2], (bsz, t, d)) - 4.0)
    a = -jnp.exp(jax.random.uniform(keys[3], (d, n), minval=0.0,
                                    maxval=2.7))
    bm, cm = (jax.random.normal(k, (bsz, t, n), jnp.bfloat16)
              for k in keys[4:])
    args = (x, dt, a, bm, cm)
    steps = bsz * t * d // 128
    want = None
    for block in blocks:
        for chunk in chunks:
            if d % block or t % chunk or ss._vmem_bytes(
                    n, block, chunk) > ss._VMEM_LIMIT:
                continue

            def fn(*args, block=block, chunk=chunk):
                return ss._scan_kernels(*args, chunk, block)

            def both(d_out, *args, fn=fn):  # y too, or the forward goes
                y, vjp = jax.vjp(fn, *args)
                return (y, *vjp(d_out.astype(jnp.float32)))

            got = jax.jit(both)(d_out, *args)
            want = want or got
            off = [float(jnp.abs(g.astype(jnp.float32)
                                 - w.astype(jnp.float32)).max()
                         / jnp.abs(w.astype(jnp.float32)).max())
                   for g, w in zip(got, want)]
            for what, f, call in (
                    ("fwd", jax.jit(fn), args),
                    ("fwd_bwd", jax.jit(both), (d_out, *args))):
                ops = _device_ops_ms(f, *call, top=256)
                kernels = {k: v for k, v in ops.items()
                           if k.startswith("dwt_sscan")}
                line = {
                    "probe": "sscan", "what": what, "shape": list(shape),
                    "block": block, "chunk": chunk,
                    "all_ops_ms": round(sum(ops.values()), 4),
                    "kernels_ms": kernels,
                    "kernel_ns_a_step": {
                        k: round(v * 1e6 / steps, 2)
                        for k, v in kernels.items()},
                    "off_y_dx_ddt_da_db_dc": [round(o, 7) for o in off],
                    "device_ops_ms": dict(list(ops.items())[:8])}
                _emit_raw(line)
                with open(out, "a") as f_out:
                    f_out.write(json.dumps(line) + "\n")


def _gate_plain(y, g):
    d = y.shape[-1] // g.shape[-1]
    return (y * jnp.repeat(g, d, axis=-1)).astype(y.dtype)


def _gate_one_hot(y, g):
    """g spread over its head's lanes by the matrix unit: exact at the
    highest precision (a one-hot column picks one g), six passes."""
    lanes, heads = y.shape[-1], g.shape[-1]
    spread = (jnp.arange(lanes)[None] // (lanes // heads)
              == jnp.arange(heads)[:, None]).astype(jnp.float32)
    return (y * jnp.einsum("bth,hl->btl", g, spread,
                           precision=jax.lax.Precision.HIGHEST)
            ).astype(y.dtype)


def probe_gate(heads=(64, 48), tiles=(64, 128, 256, 512), t=16384,
               interpret=False, out=None):
    """One layer's output gate at Laguna's sliding (64 heads) and full
    (48) layers, y (1, 16384, heads x 128) bfloat16 and g float32,
    forward alone and forward + gradient (y and g): the plain line and
    its one-hot form as the compiler fuses them, against
    `ops/head_gate.py`'s pair at several row tiles; every device op's ms
    a call, their sum, and that sum as GB/s over the passes the least
    implementation moves (PERF.md section 6, PR 61).  The lines are kept
    in `out` (`chiprun_out/pr61/gate_probe.jsonl`)."""
    from dlrover_wuqiong_tpu.ops import head_gate

    out = out or os.path.join("chiprun_out", "pr61", "gate_probe.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)

    def both(fn):  # y' too, or the compiler drops the forward
        def run(d_out, y, g):
            gated, vjp = jax.vjp(fn, y, g)
            return (gated, *vjp(d_out))
        return run

    for h in heads:
        shape = (1, t, h * 128)
        keys = jax.random.split(jax.random.PRNGKey(h), 3)
        y, d_out = (jax.random.normal(k, shape, jnp.bfloat16)
                    for k in keys[:2])
        g = jax.nn.sigmoid(jax.random.normal(keys[2], shape[:2] + (h,)))
        cases = [("plain", _gate_plain, None),
                 ("one_hot", _gate_one_hot, None)] + [
            ("dwt_gate", functools.partial(head_gate._gate_kernels, tile=tile,
                                           interpret=interpret), tile)
            for tile in tiles]
        want = jax.jit(both(_gate_plain))(d_out, y, g)
        for name, fn, tile in cases:
            got = jax.jit(both(fn))(d_out, y, g)
            off = [float(jnp.abs(a.astype(jnp.float32)
                                 - w.astype(jnp.float32)).max())
                   for a, w in zip(got, want)]
            for what, f, args, passes in (
                    (name, jax.jit(fn), (y, g), 2),
                    (name + "_fwd_bwd", jax.jit(both(fn)), (d_out, y, g),
                     5)):
                ops = _device_ops_ms(f, *args, top=256)
                ms = sum(ops.values())
                line = {"probe": "gate", "what": what, "shape": list(shape),
                        "heads": h, "tile": tile,
                        "all_ops_ms": round(ms, 4), "passes": passes,
                        "gb_per_s": ms and round(
                            passes * y.size * 2 / ms / 1e6, 1),
                        "off_y_dy_dg": [round(o, 8) for o in off],
                        "device_ops_ms": dict(list(ops.items())[:8])}
                _emit_raw(line)
                with open(out, "a") as f_out:
                    f_out.write(json.dumps(line) + "\n")


ALL = {"attn": probe_attn_cells, "attn_bwd": probe_attn_bwd,
       "attn_sweep": probe_attn_sweep,
       "attn_direct": probe_attn_direct, "attn_bd": probe_attn_bd,
       "lib": probe_lib,
       "remat": probe_remat,
       "splash": probe_splash, "dots": probe_dots,
       "head": probe_head, "model": probe_model, "opt": probe_opt,
       "step": probe_step, "dispatch": probe_dispatch,
       "rpc": probe_rpc, "gmm": probe_gmm, "rows_map": probe_rows_map,
       "rope": probe_rope, "moe_numbers": probe_moe_numbers,
       "delta": probe_delta,
       "delta_kda": functools.partial(probe_delta, forms=("channel",)),
       "sums": probe_sums, "hc": probe_hc, "conv": probe_conv,
       "gate": probe_gate, "sscan": probe_sscan}


def _sweep_of(name: str, sweep: dict) -> dict:
    """The sweep flags a probe takes: `attn_direct` and `attn_bd` theirs
    (`--length` is `attn_bd`'s alone), every other none."""
    if name == "attn_bd":
        return {k: v for k, v in sweep.items() if v is not None}
    if name == "attn_direct":
        return {k: v for k, v in sweep.items() if k != "length"}
    return {}


def main(argv=None) -> int:
    """Shared report-CLI contract (common/report_cli.py) around the
    historical per-probe lines: each probe still prints its own JSON line
    as it finishes (long sweeps stream progress), and the FINAL line is
    the machine-parseable summary — ``{"probes": [...], "emitted": N}``
    on success, ``{"error": ...}`` rc=1 on an unknown probe name."""
    argv = list(argv) if argv is not None else sys.argv[1:]
    from dlrover_wuqiong_tpu.common.report_cli import run_report

    def _ints(text, sep=","):
        return tuple(int(x) for x in text.split(sep))

    flags = {  # `attn_direct`'s and `attn_bd`'s sweeps
        "--shape": _ints, "--heads": _ints, "--length": int,
        "--blocks": lambda text: tuple(_ints(b, "x")
                                       for b in text.split(","))}

    def _offline(vals):
        given = {vals[f] for f in flags if f in vals}
        names = [a for a in argv if not a.startswith("-")
                 and a not in given] \
            or ["step", "attn", "head", "model", "opt"]
        sweep = {f[2:]: parse(vals[f]) if f in vals else None
                 for f, parse in flags.items()}
        unknown = [n for n in names if n not in ALL]
        if unknown:
            raise ValueError(
                f"unknown probe(s) {unknown}; have {sorted(ALL)}")
        del _EMITTED[:]
        for n in names:
            ALL[n](**_sweep_of(n, sweep))
        return {"probes": list(_EMITTED), "emitted": len(_EMITTED)}

    def _no_live(addr, vals):
        # unreachable: _offline always returns a report
        raise RuntimeError("perf_probe has no live-master mode")

    return run_report(
        argv, __doc__,
        offline=_offline,
        live=_no_live,
        no_addr_error="perf_probe runs on-device probes, not a master "
                      "RPC",
        value_flags=tuple(flags))


if __name__ == "__main__":
    sys.exit(main())
