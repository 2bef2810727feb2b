"""`smallthinker_21b_a3b.steady`'s step and its windowed attention kernels,
compiled by the TPU's own compiler for a DESCRIBED v5e (no chip attached),
as tests/test_tpu_compile.py does for the other cells — whose helpers these
tests use.  A file of its own so that another xdist worker compiles this
step (about 80 s) while that file compiles the other four.
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
    _held_row_loops,
    _index_ops_of_numbers,
    _no_fusion_falls_to_the_root,
    _no_persistent_cache,
    _one_chip_step,
    _repeated_kv_ops,
    _row_buffer_walkers,
    _rows_map_calls,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory


# ------------------------------- SmallThinker-21B-A3B's step on one chip

@pytest.fixture(scope="module")
def smallthinker_step(request):
    """`smallthinker_21b_a3b.steady`'s step — published widths, one
    period (a global no-position layer and three windowed RoPE layers),
    16 of 64 ReGLU experts held, an eighth of the vocabulary, the cell's
    batch of 16,384-token sequences, full recomputation (about 50 s)."""
    return _one_chip_step(request, "smallthinker_21b_a3b.steady",
                          "smallthinker")


def test_smallthinker_step_fits_one_chip_by_the_rule_and_fills_it(
        smallthinker_step):
    """State + temporaries under 90% of the chip's 16 GB at the shipped
    batch (PR 26's rule; described compiles read 11.09 / 14.37 GB live at
    1 / 2 sequences; 14.26 since PR 38: the masks over the row buffer
    and the sum of two row gradients' temporaries went), of which 6.71
    GB is donated state; far over the 25% a cell has to fill.  Since
    PR 42 the step holds loops, and the compiler's statistics count a
    loop-carried buffer that outlives its loop TWICE (any `while`, plain
    XLA ops alone: +1.007 GB for a (196608, 2560) bf16 carry, where the
    program's heap holds it once and the chip reserves the parent's
    bytes to 16 KB: PERF.md section 6, PR 42): the one row buffer is
    taken off the reading before it is held to the pin.  13.85 since
    PR 44: the rotation's float32 copy of q and its tables tiled out to
    3,584 lanes went with the formula's fusions (`dwt_rope`).  13.46
    since PR 46: k and v repeated to 28 heads, (2, 16384, 3584) each,
    and their copies in the kernels' operand layout are no array of the
    step (`fa.kv_route`): 0.94 GB under the rule's 14.4."""
    cell, model, step = smallthinker_step
    assert model.config.num_params() == 559_290_880
    assert cell["seq_len"] == 16384
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    live -= cell["global_batch"] * 16384 * 6 * 2560 * 2  # counted twice
    want = {1: 11.09, 2: 13.46}[cell["global_batch"]]
    assert live / 1e9 == pytest.approx(want, abs=0.05)
    assert 0.25 * 16 * 2 ** 30 < 0.65 * 16e9 < live < 0.90 * 16e9, live / 1e9
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_smallthinker_step_runs_two_kinds_of_attention_kernel(
        smallthinker_step):
    """The global layer runs the causal kernels and the three windowed
    layers kernels of their own name (`dwt_fa_win_*`: inside
    `kernel.attn_ms` by prefix, alone in `kernel.attn_window_ms`), each
    forward, recomputed and backward — the backward ONE fused kernel a
    layer (`fa.backward_route`): 3 and 9 custom calls.  28 heads
    of 128 are lane slabs: the kernels index the projections' own
    (batch, 16384, 28 x 128) and, for k and v, the 4 kv heads' own
    (batch, 16384, 512) — query slab s reads kv slab s // 7
    (`fa.kv_route`) — nothing is laid out by head and nothing repeats k
    or v: no broadcast, no copy and no array of a repeated k's or v's
    size stands under `attention` outside the projections and the
    kernels (the parent's step held 40: 16 broadcasts, 16 copies into
    the kernels' operand layout, 8 re-layouts of dk and dv before their
    group sums); dk and dv leave the backward kernels a query head as
    (batch, 7, 16384, 512) and eight `reduce`s a step sum them to k's
    and v's own width."""
    cell, _, step = smallthinker_step
    text = step.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_fa_\w+?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_grp_fwd": 2, "dwt_fa_bwd_fused": 1,
                     "dwt_fa_win_fwd": 6, "dwt_fa_win_bwd_fused": 3}
    # the global layer's forward takes a kv head's seven query heads a
    # grid step (PR 67); the windowed layers' keep the slab step
    assert fa.forward_route(16384, 16384, 128, 7) == ("group", 7)
    assert fa.forward_route(16384, 16384, 128, 7, window=4096) == ("slab", 0)
    b = cell["global_batch"]
    assert fa.backward_route(16384, 16384, 128, 128, 1, b * 28) == (
        "fused", 1)
    assert fa.attention_route(28, 128) == ("direct", 1)
    assert fa.kv_route(28, 4, 128) == ("indexed", 7)
    kernels = re.findall(
        r"%dwt_fa_\w+?(?:\.\d+)? = .*operand_layout_constraints=\{"
        r"(bf16\[[\d,]+\])\{2,1,0\}, (bf16\[[\d,]+\])\{2,1,0\}, "
        r"(bf16\[[\d,]+\])\{2,1,0\}", text)
    assert kernels == [(f"bf16[{b},16384,3584]",) + (
        f"bf16[{b},16384,512]",) * 2] * 12
    assert f"bf16[{b * 28},16384,128]" not in text
    assert _repeated_kv_ops(text, b, 16384, 28, 128) == []
    sums = re.findall(
        rf"= bf16\[{b},16384,512\]\S* reduce\(.*attention/reduce_sum", text)
    assert len(sums) == 8
    assert len(re.findall(rf"bf16\[{b},7,16384,512\]\S*, "
                          rf"bf16\[{b},7,16384,512\]\S*\) custom-call",
                          text)) == 4
    assert fa.causal_tile_count(16384, 16384, window=4096) == (252, 1024)


def test_smallthinker_step_holds_its_scopes_and_a_share_of_reglu_experts(
        smallthinker_step):
    """Every scope the cell's scopes file names is in the compiled step,
    the router's product (which reads the block's input, from before the
    attention) under `moe/router`.  A share's three grouped products a
    layer run `ops/grouped_matmul.py`'s kernels — twelve a layer: three
    forward, three recomputed, six backward — every one under
    `feed_forward/moe/experts`, every weight operand the 16 held
    experts, none the published 64; no `ragged-dot`.  What holds other
    ops in the step is the twenty loops over the held rows' chunks —
    twelve gather them into expert order, eight sum them by assignment
    (`_held_row_loops`) — no `conditional`."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = smallthinker_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "attention/q_proj", "attention/k_proj", "attention/v_proj",
                 "attention/o_proj", "input_norm", "post_attn_norm",
                 "SmallThinker/head", "loss", "optimizer"):
        assert any(part in s for s in scopes), part
    # the file's assumed load-balancing term is sown, no shared expert is
    assert cell["config"]["assumed"]["router_aux_loss_coef"] == 0.01
    assert any("moe/aux" in s for s in scopes)
    assert not any("moe/shared" in s for s in scopes)
    rows = cell["global_batch"] * 16384 * 6
    calls = _grouped_kernel_calls(text)
    assert len(calls) == 48 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in calls.values()), calls
    ours = collections.Counter(
        (re.sub(r"[.\d]+$", "", name), shapes[0])
        for name, (_, shapes) in calls.items())
    assert ours == {
        ("dwt_gmm", f"{rows},768"): 16, ("dwt_gmm", f"{rows},2560"): 8,
        ("dwt_gmm_t", f"{rows},768"): 4, ("dwt_gmm_t", f"{rows},2560"): 8,
        ("dwt_tgmm", "16,2560,768"): 8, ("dwt_tgmm", "16,768,2560"): 4}
    assert "[64,2560,768]" not in text and "[64,768,2560]" not in text
    _held_row_loops(text, rows, 2560, layers=4)


def test_smallthinker_step_walks_its_row_buffer_in_gathers_alone(
        smallthinker_step):
    """The elementwise passes of a share's four expert layers are
    `dwt_rows_map_*` kernels over the tiles that hold a held row: ReGLU
    forward and recomputed (8) and its backward (4) over (T*k, 768), the
    sum of the two first products' row gradients (4) over (T*k, 2560),
    all under `moe/experts`, and the combine's backward pair (4) under
    `moe/combine` — and no fusion under either scope still has a (T*k,
    width) operand.  The twelve gathers INTO expert order are loops
    whose turn gathers (8192, 2560); the eight sums BY ASSIGNMENT are
    loops over the held rows too, a turn gathers (4096 + 16, 2560), and
    one gather of (T, 2560) behind each reads the tokens' sums (PR 50:
    were eight gathers of (k, T, 2560), T*k index entries each); no
    gather has a (T*k, 2560) or a (k, T, 2560) result."""
    cell, _, step = smallthinker_step
    text = step.as_text()
    rows = cell["global_batch"] * 16384 * 6
    assert _rows_map_calls(text) == {
        ("dwt_rows_map_gated_relu", f"{rows},768"): 8,
        ("dwt_rows_map_gated_relu_bwd", f"{rows},768"): 4,
        ("dwt_rows_map_add", f"{rows},2560"): 4,
        ("dwt_rows_map_weigh", f"{rows},2560"): 4}
    assert _row_buffer_walkers(text, rows) == []
    assert _held_row_loops(text, rows, 2560, layers=4) == {
        "bf16[8192,2560]": 12, "bf16[4112,2560]": 8,
        f"bf16[{rows // 6},2560]": 8}


def test_smallthinker_step_indexes_no_single_numbers(smallthinker_step):
    """The expert layers' bookkeeping holds no scatter and no gather of
    single numbers (`_index_ops_of_numbers`, PR 45): the count of all 64
    experts the auxiliary term reads and the groups' sizes are ONE
    compare-and-sum a layer call, the gates are a select of the scores
    (the transpose of `top_k`'s own was a nameless scatter into (T, 64)),
    and they reach expert order, as the dots do their assignments, as
    further operands of the sorts.  The row gathers are pinned above."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import instructions_of

    text = smallthinker_step[2].as_text()
    assert not instructions_of(text, "scatter", "moe")
    assert _index_ops_of_numbers(text, 2560) == []


def test_smallthinker_step_rotates_q_and_k_in_one_pass_each(
        smallthinker_step):
    """The three windowed layers rotate q and k, each forward,
    recomputed and backward (the backward the same kernel): 18
    `dwt_rope` calls on the projections' own (batch, 16384, 28 x 128)
    and (batch, 16384, 4 x 128), all under `attention`; the global layer
    carries no position.  And the formula's fusions are gone: no fusion
    of the step under `attention` that is neither a projection's nor a
    kernel's has a q- or k-shaped result (the parent's step had nine of
    each: the rolls' slices padded back under a select)."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import owners

    cell, _, step = smallthinker_step
    text = step.as_text()
    b = cell["global_batch"]
    calls = collections.Counter(re.findall(
        r"%dwt_rope[.\d]* = bf16\[(\d+),16384,(\d+)\]", text))
    assert calls == {(str(b), "3584"): 9, (str(b), "512"): 9}
    own = owners(text)
    scopes = collections.Counter(
        re.sub(r"^\w+/", "", own[name]["scope"])
        for name in re.findall(r"%(dwt_rope[.\d]*) = ", text))
    assert scopes == {"SmallThinker/layers/attention/dwt_rope": 18}
    rotated = [
        name for name, shape, op in re.findall(
            r"^\s*%([\w.\-]+) = (\w+\[[\d,]+\])\S* ([\w\-]+)\(", text,
            re.M)
        if op == "fusion" and re.search(rf"\[{b},16384,(3584|512)\]", shape)
        and name in own and "attention" in own[name]["scope"]
        and not re.search(r"[qkvo]_proj|dwt_", own[name]["scope"])]
    assert rotated == []


@pytest.mark.parametrize("window,route,names", [
    (4096, None, ("dwt_fa_win_bwd_fused",)),
    (4000, None, ("dwt_fa_win_bwd_fused",)),
    (4096, ("split", 1), ("dwt_fa_win_bwd_dq", "dwt_fa_win_bwd_dkv"))])
def test_windowed_kernels_compile_at_the_cells_shape(topo, window, route,
                                                     names):
    """One sequence of 16,384 tokens, 28 heads of 128 on their lane
    slabs over the 4 kv heads' own 512 lanes (query slab s reads kv slab
    s // 7), blocks of 1,024: the forward and the backward of a windowed
    call on its narrowed grid (index maps that clamp, a class a grid
    step), at the published window and at one off the block and the
    tile — the fused sweep with a slab's whole dq resident, and the pair
    a sequence that does not fit would take; dk and dv a query head,
    a group's heads on an axis of their own."""
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, 16384, 3584), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 512), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((28, 1, 16384), jnp.float32, sharding=one)
    slabs, _ = fa._projected_slabs((x, kv, kv), 28)
    assert slabs == (28, 1, 128, (0, 0, 0), 7)
    kw = dict(slabs=slabs, window=window)
    fwd = _compile(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, True, 128 ** -0.5, 1024, 1024, False, **kw), x, kv, kv)
    assert "dwt_fa_win_fwd" in fwd and "tpu_custom_call" in fwd
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, 128 ** -0.5, 1024, 1024, False,
        route=route, **kw), x, kv, kv, x, lse, x)
    assert "bf16[1,7,16384,512]" in bwd
    assert sorted(set(re.findall(r"dwt_fa_win_bwd_[a-z]+", bwd))) == sorted(
        names)
    assert "dwt_fa_fwd" not in fwd + bwd and "dwt_fa_bwd" not in bwd


def test_every_device_op_of_the_step_has_an_owner(smallthinker_step):
    """As the other four steps (tests/test_tpu_compile.py): what has no
    owner is a copy only the step's outputs read, the step counter and
    `moe_dropped`; the two tile counts' copies are the scope
    `attn_tiles`'s (`models/attention.collect_attention_stats`)."""
    _every_device_op_has_an_owner(smallthinker_step[2])


def test_no_fusion_of_the_step_falls_to_the_models_root(smallthinker_step):
    _no_fusion_falls_to_the_root(smallthinker_step[2], "SmallThinker")
