"""`kimi_vl_a3b.steady`'s step and its two-width attention kernels,
compiled by the TPU's own compiler for a DESCRIBED v5e (no chip
attached), as tests/test_tpu_compile.py does for the other cells — whose
helpers these tests use.  A file of its own so that another xdist worker
compiles this step (about 70 s) while those files compile the other
five.
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
    _row_buffer_walkers,
    _rows_map_calls,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory


@pytest.fixture(scope="module")
def kimi_vl_step(request):
    """`kimi_vl_a3b.steady`'s step — published widths, the leading dense
    layer and four expert layers, 8 of 64 SwiGLU experts held beside the
    shared one, an eighth of the vocabulary, the cell's batch of
    16,384-token sequences, full recomputation (about 70 s)."""
    return _one_chip_step(request, "kimi_vl_a3b.steady", "kimi_vl")


def test_kimi_vl_step_fits_one_chip_by_the_rule_and_fills_it(kimi_vl_step):
    """State + temporaries under 90% of the chip's 16 GB at the shipped
    batch (PR 26's rule; described compiles read 11.08 / 14.27 GB live at
    1 / 2 sequences at depth 5, 12.50 at the fallback's 1 at depth 6), of
    which 6.82 GB is donated state; far over the 25% a cell has to fill.
    The file's 14.27 is the reading of a step without loops.  Since
    PR 42 the step holds twelve (twenty since PR 50), and the
    compiler's statistics count a loop-carried buffer that outlives its
    loop twice (tests/test_smallthinker_compile.py says how that was
    found): 15.17 as read (15.16 since PR 50: the sums by assignment
    carry a (196608, 2048) buffer where the parent wrote the gathered
    (6, 32768, 2048)), 14.37 with the one (196608, 2048) bf16 buffer
    taken off, and on the chip the peak is the parent's
    (`device.peak_hbm_gib` 13.920 for 13.921: PERF.md section 6,
    PR 42)."""
    cell, model, step = kimi_vl_step
    assert model.config.num_params() == 568_484_608
    assert (cell["seq_len"], cell["global_batch"]) == (16384, 2)
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    live -= cell["global_batch"] * 16384 * 6 * 2048 * 2  # counted twice
    rung = cell["config"]["train"]["memory_rung"]
    assert rung["live_GB"]["2 x 16384 at depth 5"] == 14.27
    assert live / 1e9 == pytest.approx(14.37, abs=0.05)
    assert 0.25 * 16 * 2 ** 30 < 0.65 * 16e9 < live < 0.90 * 16e9, live / 1e9
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_kimi_vl_step_runs_the_kernels_at_192_and_128_unpadded(kimi_vl_step):
    """Five latent layers run the causal kernels forward, recomputed and
    backward: 10 forwards and 5 fused backwards (one sweep gives dq, dk
    and dv: `fa.backward_route`) under their `dwt_fa_*` names.  16
    heads of 192 | 128 lie on no slab boundary: the TRANSPOSED route on
    (batch x 16, 16384, d), q and k handed in 192 wide and v, o and dO
    128 wide; nothing is padded to 256 lanes or to the other's width."""
    cell, _, step = kimi_vl_step
    text = step.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_fa_\w+?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_fwd": 10, "dwt_fa_bwd_fused": 5}
    assert fa.attention_route(16, 192, 128) == ("transposed", 0)
    bh = cell["global_batch"] * 16
    assert fa.backward_route(16384, 16384, 192, 128, 0, bh) == ("fused", 2)
    shapes = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*%(dwt_fa_\w+?)(?:\.\d+)? = ", line)
        if m and "custom-call(" in line:
            operands = line.split("operand_layout_constraints={", 1)[1]
            shapes[m.group(1), tuple(re.findall(
                r"(?:bf16|f32)\[[\d,]+\]",
                operands.split("}, frontend", 1)[0]))] += 1
    wide, narrow = f"bf16[{bh},16384,192]", f"bf16[{bh},16384,128]"
    row = f"f32[{bh},1,16384]"
    ins = (wide, wide, narrow, narrow, row, row)
    assert shapes == {("dwt_fa_fwd", (wide, wide, narrow)): 10,
                      ("dwt_fa_bwd_fused", ins): 5}
    assert f"bf16[{bh},16384,256]" not in text


def test_kimi_vl_step_holds_its_scopes_and_a_share_of_swiglu_experts(
        kimi_vl_step):
    """Every scope the cell's scopes file names is in the compiled step.
    A share's three grouped products a layer run `ops/grouped_matmul.py`'s
    kernels — twelve a layer: three forward, three recomputed, six
    backward — every one under `feed_forward/moe/experts`, every weight
    operand the 8 held experts, none the published 64; no `ragged-dot`;
    the SwiGLU shared expert under `moe/shared`; layer 0's dense SwiGLU
    under `feed_forward` itself.  No auxiliary term is sown.  What holds
    other ops in the step is the twenty loops over the held rows' chunks
    — twelve gather them into expert order, eight sum them by assignment
    (`_held_row_loops`) — no `conditional`."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = kimi_vl_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("attention/q_proj", "attention/kv_a_proj",
                 "attention/kv_a_norm", "attention/kv_b_proj",
                 "attention/rope", "attention/assemble", "attention/o_proj",
                 "feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "feed_forward/moe/shared/shared_gate_proj",
                 "layers/feed_forward/gate_proj",
                 "layers/feed_forward/down_proj", "input_norm",
                 "post_attn_norm", "LatentMoE/head", "loss", "optimizer",
                 "attn_lanes"):
        assert any(part in s for s in scopes), part
    assert not any("moe/aux" in s for s in scopes)
    rows = cell["global_batch"] * 16384 * 6
    assert gm.experts_route(rows, [(8, 2048, 1408), (8, 2048, 1408),
                                   (8, 1408, 2048)], 64) == "plain"  # off TPU
    calls = _grouped_kernel_calls(text)
    assert len(calls) == 48 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in calls.values()), calls
    ours = collections.Counter(
        (re.sub(r"[.\d]+$", "", name), shapes[0])
        for name, (_, shapes) in calls.items())
    assert ours == {
        ("dwt_gmm", f"{rows},1408"): 16, ("dwt_gmm", f"{rows},2048"): 8,
        ("dwt_gmm_t", f"{rows},1408"): 4, ("dwt_gmm_t", f"{rows},2048"): 8,
        ("dwt_tgmm", "8,2048,1408"): 8, ("dwt_tgmm", "8,1408,2048"): 4}
    assert "[64,2048,1408]" not in text and "[64,1408,2048]" not in text
    _held_row_loops(text, rows, 2048, layers=4)


def test_kimi_vl_step_rotates_in_one_pass_under_its_scope(kimi_vl_step):
    """The five latent layers rotate the q heads' 64-wide parts side by
    side, (batch, 16384, 16 x 64), two heads a lane slab, and the ONE
    key part a token, 64 lanes padded to a slab: two `dwt_rope` calls
    forward, recomputed and backward a layer — 30, each under the scope
    `attention/rope`, which `step.attn_latent_ms` reads."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import owners

    cell, _, step = kimi_vl_step
    text = step.as_text()
    b = cell["global_batch"]
    calls = collections.Counter(re.findall(
        r"%dwt_rope[.\d]* = (\w+\[[\d,]+\])", text))
    assert calls == {f"bf16[{b},16384,1024]": 15, f"bf16[{b},16384,128]": 15}
    own = owners(text)
    assert collections.Counter(
        re.sub(r"^\w+/", "", own[name]["scope"])
        for name in re.findall(r"%(dwt_rope[.\d]*) = ", text)) == {
            "LatentMoE/layers/attention/rope/dwt_rope": 30}


def test_kimi_vl_step_walks_its_row_buffer_in_gathers_alone(kimi_vl_step):
    """The elementwise passes of a share's four expert layers are
    `dwt_rows_map_*` kernels over the tiles that hold a held row: SwiGLU
    forward and recomputed (8) and its backward (4) over (T*k, 1408), the
    sum of the two first products' row gradients (4) over (T*k, 2048),
    the combine's backward pair (4) — and no fusion under either scope
    still has a (T*k, width) operand.  The twelve gathers INTO expert
    order are loops whose turn gathers (8192, 2048); the eight sums BY
    ASSIGNMENT are loops over the held rows too, a turn gathers (4096 +
    16, 2048), and one gather of (T, 2048) behind each reads the tokens'
    sums (PR 50: were eight gathers of (k, T, 2048), T*k index entries
    each); no gather has a (T*k, 2048) or a (k, T, 2048) result."""
    cell, _, step = kimi_vl_step
    text = step.as_text()
    rows = cell["global_batch"] * 16384 * 6
    assert _rows_map_calls(text) == {
        ("dwt_rows_map_gated_silu", f"{rows},1408"): 8,
        ("dwt_rows_map_gated_silu_bwd", f"{rows},1408"): 4,
        ("dwt_rows_map_add", f"{rows},2048"): 4,
        ("dwt_rows_map_weigh", f"{rows},2048"): 4}
    assert _row_buffer_walkers(text, rows) == []
    assert _held_row_loops(text, rows, 2048, layers=4) == {
        "bf16[8192,2048]": 12, "bf16[4112,2048]": 8,
        f"bf16[{rows // 6},2048]": 8}


def test_kimi_vl_step_indexes_no_single_numbers(kimi_vl_step):
    """The expert layers' bookkeeping holds no scatter and no gather of
    single numbers (`_index_ops_of_numbers`, PR 45): the count of all 64
    experts the bias's rule reads and the groups' sizes are ONE
    compare-and-sum a layer call, the scores at the experts the biased
    choice names are a select (its transpose too: the parent's was a
    nameless scatter into (T, 64) two fusions down), the gates reach
    expert order and the dots their assignments as further operands of
    the sorts.  The row gathers are pinned above."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import instructions_of

    text = kimi_vl_step[2].as_text()
    assert not instructions_of(text, "scatter", "moe")
    assert _index_ops_of_numbers(text, 2048) == []


@pytest.mark.parametrize("seq,route,names", [
    # slow: the step above holds the same forward and fused backward at
    # 16,384 (its `dwt_fa_fwd` / `dwt_fa_bwd_fused` calls are pinned)
    pytest.param(16384, None, ("dwt_fa_bwd_fused",),
                 marks=pytest.mark.slow),
    (16384, ("split", 8), ("dwt_fa_bwd_dq", "dwt_fa_bwd_dkv")),
    (1024, None, ("dwt_fa_bwd_fused",))])
def test_two_width_kernels_compile_at_the_cells_shape(topo, seq, route,
                                                      names):
    """Two sequences' 32 heads, q and k 192 wide beside v 128, blocks of
    1,024: the forward, and the backward at the cell's 16,384 — fused,
    two heads' whole dq resident (64 MiB of the 100 the call states),
    and as the pair a longer sequence would take — and at one block each
    way."""
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((32, seq, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((32, seq, 128), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((32, 1, seq), jnp.float32, sharding=one)
    fwd = _compile(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, True, 192 ** -0.5, 1024, 1024, False), q, q, v)
    assert "dwt_fa_fwd" in fwd and "tpu_custom_call" in fwd
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, 192 ** -0.5, 1024, 1024, False,
        route=route), q, q, v, v, lse, v)
    assert sorted(set(re.findall(r"dwt_fa_bwd_[a-z]+", bwd))) == sorted(names)


def test_every_device_op_of_the_step_has_an_owner(kimi_vl_step):
    """As the other five steps (tests/test_tpu_compile.py); the lane
    counts' copies are the scope `attn_lanes`'s
    (`models/attention.collect_attention_stats`)."""
    _every_device_op_has_an_owner(kimi_vl_step[2])


def test_no_fusion_of_the_step_falls_to_the_models_root(kimi_vl_step):
    _no_fusion_falls_to_the_root(kimi_vl_step[2], "LatentMoE")
