"""`laguna_xs_2_33b_a3b.steady`'s step and its short-window attention
kernels, compiled by the TPU's own compiler for a DESCRIBED v5e (no chip
attached), as tests/test_tpu_compile.py does for the other cells — whose
helpers these tests use.  A file of its own so that another xdist worker
compiles this step while that file compiles the others.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_tpu_compile import (  # noqa: F401 — `topo` and the cache switch are fixtures
    _compile,
    _every_device_op_has_an_owner,
    _grouped_kernel_calls,
    _no_fusion_falls_to_the_root,
    _no_persistent_cache,
    _one_chip_step,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory


@pytest.fixture(scope="module")
def laguna_step(request):
    """`laguna_xs_2_33b_a3b.steady`'s step — published widths, the dense
    full-attention layer and one period (three sliding layers of 64
    heads, one full layer of 48), 32 of 256 SwiGLU experts held beside
    the shared one, an eighth of the vocabulary, the cell's one
    sequence of 16,384 tokens, full recomputation."""
    return _one_chip_step(request, "laguna_xs_2_33b_a3b.steady", "laguna")


def _live_gb(step) -> float:
    return compiled_memory(step)["live_bytes"] / 1e9


def test_laguna_step_fits_one_chip_by_the_rule_and_fills_it(laguna_step):
    """State + temporaries under 90% of the chip's 16 GB at rung (a),
    one sequence of 16,384 tokens (PR 26's rule; the described compile
    reads 13.60 GB live, of which 8.30 GB is donated state — 13.84 until
    PR 61, which is what the cell's file took the rung on: the gate's
    float32 spreads of y's size are gone; the step holds loops over the
    held rows' chunks and the compiler's statistics count the one
    (131072, 2048) bf16 row buffer that outlives them twice, PERF.md
    section 6, PR 42: 13.06 GB with it counted once) — under the rule's
    14.4 either way, and far over the 25% a cell has to fill."""
    cell, model, step = laguna_step
    assert model.config.num_params() == 691_623_936
    assert (cell["global_batch"], cell["seq_len"]) == (1, 16384)
    m = step.memory_analysis()
    live = _live_gb(step)
    assert live == pytest.approx(13.60, abs=0.06)
    once = live - 16384 * 8 * 2048 * 2 / 1e9
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.65 * 16 < once < live < 0.90 * 16
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()
    rung = cell["config"]["train"]["memory_rung"]
    assert rung["taken"] == "1 x 16384"
    assert live < rung["live_GB"]["1 x 16384"] == 13.84 < rung["limit_GB"]


def test_laguna_step_runs_two_kinds_of_attention_kernel_at_two_head_counts(
        laguna_step):
    """The two full layers run the causal kernels at 48 heads and the
    three sliding layers the windowed ones (`dwt_fa_win_*`) at 64, each
    forward, recomputed and backward — the backward ONE fused kernel a
    layer: 4 + 2 and 6 + 3 custom calls.  Heads of 128 are lane slabs:
    the kernels index the projections' own (1, 16384, 48 x 128) and
    (1, 16384, 64 x 128) and, for k and v, the 8 kv heads' own
    (1, 16384, 1024): query slab s reads kv slab s // 6 or s // 8
    (`fa.kv_route`), nothing is laid out by head or repeated.  A window
    of 512 under blocks of 1,024 is two grid steps a query block
    (`_window_plan`: d_max = 1, both crossed by a diagonal, none run
    whole) and 63 tiles of 512 in a 32 x 32 square: 4 a query block, 3
    on the diagonal's block and 1 on the one before it."""
    cell, _, step = laguna_step
    text = step.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_fa_\w+?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_grp_fwd": 4, "dwt_fa_bwd_fused": 2,
                     "dwt_fa_win_fwd": 6, "dwt_fa_win_bwd_fused": 3}
    # a full layer's forward takes a kv head's six query heads a grid
    # step (PR 67); a windowed layer's keeps the slab step
    assert fa.forward_route(16384, 16384, 128, 6) == ("group", 6)
    assert fa.forward_route(16384, 16384, 128, 8, window=512) == ("slab", 0)
    for heads, rep in ((48, 6), (64, 8)):
        assert fa.attention_route(heads, 128) == ("direct", 1)
        assert fa.kv_route(heads, 8, 128) == ("indexed", rep)
        assert fa.backward_route(16384, 16384, 128, 128, 1, heads)[0] \
            == "fused"
    kernels = collections.Counter(re.findall(
        r"%(dwt_fa_(?:win_)?)\w+?(?:\.\d+)? = .*operand_layout_constraints="
        r"\{(bf16\[[\d,]+\])\{2,1,0\}, (bf16\[[\d,]+\])\{2,1,0\}, "
        r"(bf16\[[\d,]+\])\{2,1,0\}", text))
    kv = "bf16[1,16384,1024]"
    assert kernels == {
        ("dwt_fa_", "bf16[1,16384,6144]", kv, kv): 6,
        ("dwt_fa_win_", "bf16[1,16384,8192]", kv, kv): 9}
    assert "bf16[64,16384,128]" not in text
    assert "bf16[48,16384,128]" not in text
    plan = fa._window_plan(512, 16, 16, 1024, 1024, 0)
    assert plan["steps"] == 2 and plan["whole"] is None
    assert plan["crossed"] == ((0, 0), (1, 1024))
    assert fa.causal_tile_count(16384, 16384, window=512) == (63, 1024)


def test_laguna_step_holds_its_scopes_a_gate_and_a_share_of_experts(
        laguna_step):
    """Every scope the cell's scopes file names is in the compiled step:
    the gate's product and its sigmoid-and-multiply, the full layers'
    partial rotation, the dense layer's SwiGLU, the shared expert, the
    assumed auxiliary term.  A share's three grouped products a layer
    run `ops/grouped_matmul.py`'s kernels — twelve a sparse layer — on
    the 32 held experts of 512, none on the published 256; no
    `ragged-dot`, no `conditional`."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = laguna_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("attention/g_proj", "attention/gate",
                 "attention/rope_partial", "attention/q_proj",
                 "attention/k_proj", "attention/v_proj", "attention/o_proj",
                 "feed_forward/gate_proj", "feed_forward/down_proj",
                 "feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "feed_forward/moe/shared", "feed_forward/moe/aux",
                 "input_norm", "post_attn_norm", "Laguna/head", "loss",
                 "optimizer", "attn_pairs", "attn_gate_mean"):
        assert any(part in s for s in scopes), part
    assert cell["config"]["assumed"]["router_aux_loss_coef"] == 0.01
    rows = 16384 * 8
    calls = _grouped_kernel_calls(text)
    assert len(calls) == 48 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in calls.values()), calls
    ours = collections.Counter(
        (re.sub(r"[.\d]+$", "", name), shapes[0])
        for name, (_, shapes) in calls.items())
    assert ours == {
        ("dwt_gmm", f"{rows},512"): 16, ("dwt_gmm", f"{rows},2048"): 8,
        ("dwt_gmm_t", f"{rows},512"): 4, ("dwt_gmm_t", f"{rows},2048"): 8,
        ("dwt_tgmm", "32,2048,512"): 8, ("dwt_tgmm", "32,512,2048"): 4}
    assert "[256,2048,512]" not in text and "[256,512,2048]" not in text
    assert "conditional(" not in text


def test_laguna_step_rotates_whole_heads_and_half_heads_by_the_kernel(
        laguna_step):
    """Every rotation of the step is `dwt_rope` on the projections' own
    rows, each forward, recomputed and backward: the three sliding
    layers' q and k whole (9 calls at 64 x 128 lanes, 9 at 8 x 128), the
    two full layers' q and k HALF a head (6 at 48 x 128, 6 more at
    8 x 128; the lanes behind the first 64 pass inside the kernel) — 30
    calls, and under `attention/rope_partial` nothing of the formula: no
    fusion, copy or broadcast of a row's size, only the kernels and the
    (16384, 128) float32 table they read."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import moved_bytes

    text = laguna_step[2].as_text()
    calls = collections.Counter(re.findall(
        r"%dwt_rope[.\d]* = bf16\[(\d+),16384,(\d+)\]", text))
    assert calls == {("1", "8192"): 9, ("1", "1024"): 15, ("1", "6144"): 6}
    partial = moved_bytes(text, "rope_partial")
    kernels = [op for op in partial if op.startswith("dwt_rope")]
    assert len(kernels) == 12, sorted(partial)
    # what else the scope holds makes and stages the table: no op of it
    # writes more than the table's 8 MB, where a row of q is 201
    table = 16384 * 128 * 4
    assert partial[kernels[0]]["written"] >= 16384 * 1024 * 2 > table
    assert {op: m["written"] for op, m in partial.items()
            if op not in kernels and m["written"] > table} == {}


def test_laguna_step_gates_on_the_kernels_own_rows(laguna_step):
    """Every layer's output gate is `ops/head_gate.py`'s pair on the
    rows the attention kernels wrote: five layers forward, recomputed
    (`dwt_gate`, 10 calls) and backward (`dwt_gate_bwd`, 5), three at
    64 x 128 lanes and two at 48 x 128, under the scope
    `attention/gate` that `step.attn_gate_ms` reads.  Nothing else
    under that scope writes an array of y's size — no broadcast of g
    over a head's lanes, no reshape or copy of one — nor a tenth of it:
    what stays is the sigmoid, its derivative and the staging of g,
    (16384, heads) float32."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import moved_bytes
    from dlrover_wuqiong_tpu.ops import head_gate

    text = laguna_step[2].as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_gate\w*?)[.\d]* = .*?bf16\[1,16384,(\d+)\]", text))
    assert calls == {("dwt_gate", "8192"): 6, ("dwt_gate", "6144"): 4,
                     ("dwt_gate_bwd", "8192"): 3, ("dwt_gate_bwd", "6144"): 2}
    for heads in (48, 64):
        assert head_gate.gate_route(heads * 128, 128) == "plain"  # off the TPU
    gate = moved_bytes(text, "gate")
    kernels = [op for op in gate if op.startswith("dwt_gate")]
    assert len(kernels) == 15, sorted(gate)
    assert all("attention/gate" in gate[op]["scope"] for op in kernels)
    y = 16384 * 48 * 128 * 2
    assert min(gate[op]["written"] for op in kernels) >= y
    assert {op: m["written"] for op, m in gate.items()
            if op not in kernels and m["written"] > y // 10} == {}
    assert not [op for op in gate if op not in kernels and re.match(
        r"(broadcast|reshape|copy)[.\d]*$", op)
        and gate[op]["written"] >= y], sorted(gate)


@pytest.mark.parametrize("heads,route,names", [
    (64, None, ("dwt_fa_win_bwd_fused",)),
    (48, None, ("dwt_fa_win_bwd_fused",)),
    (64, ("split", 1), ("dwt_fa_win_bwd_dq", "dwt_fa_win_bwd_dkv"))])
def test_short_window_kernels_compile_at_the_cells_shape(topo, heads, route,
                                                         names):
    """One sequence of 16,384 tokens, 64 (or 48) heads of 128 on their
    lane slabs over the 8 kv heads' own 1,024 lanes, blocks of 1,024 and
    a window of 512, SHORTER than a block: the forward and the backward
    on a grid of two steps a query block, each a block a diagonal
    crosses — the fused sweep, and the pair a longer sequence would
    take."""
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, 16384, heads * 128), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 1024), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((heads, 1, 16384), jnp.float32, sharding=one)
    slabs, _ = fa._projected_slabs((x, kv, kv), heads)
    assert slabs == (heads, 1, 128, (0, 0, 0), heads // 8)
    kw = dict(slabs=slabs, window=512)
    fwd = _compile(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, True, 128 ** -0.5, 1024, 1024, False, **kw), x, kv, kv)
    assert "dwt_fa_win_fwd" in fwd and "tpu_custom_call" in fwd
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, 128 ** -0.5, 1024, 1024, False,
        route=route, **kw), x, kv, kv, x, lse, x)
    assert f"bf16[1,{heads // 8},16384,1024]" in bwd
    assert sorted(set(re.findall(r"dwt_fa_win_bwd_[a-z]+", bwd))) == sorted(
        names)
    assert "dwt_fa_fwd" not in fwd + bwd and "dwt_fa_bwd" not in bwd


def test_every_device_op_of_the_step_has_an_owner(laguna_step):
    """As the other steps (tests/test_tpu_compile.py); the counters'
    copies are their scopes' (`attn_tiles`, `attn_pairs`,
    `attn_gate_mean`: `models/attention.collect_attention_stats`)."""
    _every_device_op_has_an_owner(laguna_step[2])


def test_no_fusion_of_the_step_falls_to_the_models_root(laguna_step):
    _no_fusion_falls_to_the_root(laguna_step[2], "Laguna")
