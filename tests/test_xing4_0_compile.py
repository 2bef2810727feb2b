"""`xing4_0_29b_a4b.steady`'s step — four residual lanes mixed by
hyper-connections around latent attention with a q latent and a share
of the experts — compiled by the TPU's own compiler for a DESCRIBED v5e
(no chip attached), as tests/test_tpu_compile.py does for the other
cells — whose helpers these tests use, `_one_chip_step` among them: the
step compiles once a RUN (about 115 s), whichever workers are handed
these tests.  The multi-token-prediction module, which the cell's cut
leaves out, compiles here at published widths behind the dense layer.
"""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from test_program_from_arguments import _pallas_calls
from test_tpu_compile import (  # noqa: F401 — `topo`, the cache switch: fixtures
    _every_device_op_has_an_owner,
    _grouped_kernel_calls,
    _no_fusion_falls_to_the_root,
    _no_persistent_cache,
    _one_chip_step,
    _rows_map_calls,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory


@pytest.fixture(scope="module")
def xing_step(request):
    """`xing4_0_29b_a4b.steady`'s step — published widths, the leading
    dense layer and four expert layers, 8 of 64 SwiGLU experts held
    beside the shared one, an eighth of the vocabulary, one sequence of
    8,192 tokens in four lanes, full recomputation."""
    return _one_chip_step(request, "xing4_0_29b_a4b.steady", "xing4_0")


@pytest.fixture(scope="module")
def xing_mtp_step(request):
    """The dense layer and, behind it, the multi-token-prediction module
    (its joining product, one expert block, its norm, the shared table
    and head) at published widths and the cell's batch."""
    return _one_chip_step(request, "xing4_0_29b_a4b.steady", "xing4_0",
                          num_hidden_layers=1, num_nextn_predict_layers=1)


def test_xing_step_fits_one_chip_by_the_rule_and_fills_it(xing_step):
    """State + temporaries under 90% of the chip's 16 GB at 1 x 8192
    (PR 26's rule; the described compile read 13.86 GB live there and
    12.25 at 1 x 4096 when the rung was taken, and reads 13.74 since the
    mixes are kernels: three whole-stream cotangents a sublayer are no
    longer alive together), of which 9.11 GB is donated state; far over
    the 25% a cell has to fill."""
    cell, model, step = xing_step
    assert model.config.num_params() == 759_346_446
    assert (cell["seq_len"], cell["global_batch"]) == (8192, 1)
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    rung = cell["config"]["train"]["memory_rung"]
    assert rung["live_GB"]["1 x 8192"] == 13.86 < rung["limit_GB"] == 14.4
    assert rung["taken"] == "1 x 8192"
    assert 13.6 < live / 1e9 <= 13.86 + 0.05 < rung["limit_GB"], live / 1e9
    assert 0.25 * 16 * 2 ** 30 < 0.75 * 16e9 < live < 0.90 * 16e9, live / 1e9
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_xing_step_carries_its_lanes_without_padding(xing_step):
    """The stream is (1, 4, 8192, 3584): no array of the step has the
    four lanes on its last axis or before the hidden size on its last two
    (a bf16 tile would pad 4 to 16), and Sinkhorn's (1, 4, 4, 8192)
    coefficients lie in (4, 128) tiles of tokens, nothing padded — no
    (.., 4, 4) minor, a whole (8, 128) tile a token."""
    text = xing_step[2].as_text()
    assert not re.findall(r"(?:bf16|f32)\[[\d,]*\b4,3584\]", text)
    assert not re.findall(r"(?:bf16|f32)\[[\d,]*,4,4\]", text)
    assert "bf16[1,4,8192,3584]{3,2,1,0:T(8,128)(2,1)}" in text
    tiles = collections.Counter(re.findall(
        r"f32\[1,4,4,8192\]\{3,2,1,0:(T\(\d+,\d+\))", text))
    assert set(tiles) == {"T(4,128)"} and tiles["T(4,128)"] > 1000


def test_xing_step_runs_the_kernels_at_192_and_128_and_rotates_scaled(
        xing_step):
    """Five latent layers of 32 heads run the causal kernels forward,
    recomputed and backward on the transposed route, q and k 192 wide
    beside v 128, as Kimi's cell does at 16 heads; the rotations are
    `dwt_rope`'s, handed YaRN's tables: q's 32 x 64 lanes side by side
    and the one key part, forward, recomputed and backward a layer."""
    cell, _, step = xing_step
    text = step.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_fa_\w+?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_fwd": 10, "dwt_fa_bwd_fused": 5}
    assert fa.attention_route(32, 192, 128) == ("transposed", 0)
    assert "bf16[32,8192,192]" in text and "bf16[32,8192,256]" not in text
    # the backward sweeps two heads a grid step, not the four that fit
    # (23.4 ms a call on the chip at four, 13.5 at two: PR 52)
    bh = cell["global_batch"] * 32
    assert fa.backward_route(8192, 8192, 192, 128, 0, bh) == ("fused", 2)
    wide, narrow = ((bh, 8192, w) for w in (192, 128))
    grids = _pallas_calls(jax.make_jaxpr(functools.partial(
        fa._fa_backward_pallas, causal=True, sm_scale=192 ** -0.5,
        block_q=1024, block_k=1024, interpret=False))(*(
            jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for s in (wide, wide, narrow, narrow)),
        jax.ShapeDtypeStruct((bh, 1, 8192), jnp.float32),
        jax.ShapeDtypeStruct(narrow, jnp.bfloat16)).jaxpr)
    assert grids == [("dwt_fa_bwd_fused", (16, 8, 8))]
    assert collections.Counter(re.findall(
        r"%dwt_rope[.\d]* = (\w+\[[\d,]+\])", text)) == {
            "bf16[1,8192,2048]": 15, "bf16[1,8192,128]": 15}


def test_xing_step_holds_its_scopes_and_a_share_of_the_experts(xing_step):
    """Every scope the cell's scopes file names is in the compiled step:
    the four of the mixing and the stack's two ends, the q latent's
    three, Kimi's.  A share's grouped products run `dwt_gmm*` kernels
    under `moe/experts` over the 8 held experts of 3584 x 1024, 8,192 x
    4 rows; no `ragged-dot`, no `conditional`; the loops are the held
    rows' chunks' and Sinkhorn's rounds (forward, recomputed and
    backward of ten sublayers: thirty of twenty trips)."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = xing_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("layers/hc/coeff", "layers/hc/sinkhorn", "layers/hc/pre",
                 "layers/hc/post_res", "LatentMoE/hc/expand",
                 "LatentMoE/hc/read_out", "hc_stats",
                 "attention/q_a_proj", "attention/q_a_norm",
                 "attention/q_b_proj", "attention/kv_a_proj",
                 "attention/kv_a_norm", "attention/kv_b_proj",
                 "attention/rope", "attention/assemble", "attention/o_proj",
                 "feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "feed_forward/moe/shared/shared_gate_proj",
                 "layers/feed_forward/gate_proj", "input_norm",
                 "post_attn_norm", "LatentMoE/head", "loss", "optimizer",
                 "attn_lanes"):
        assert any(part in s for s in scopes), part
    for where in ("bwd/", "recompute/"):
        for part in ("hc/coeff", "hc/sinkhorn", "hc/pre", "hc/post_res"):
            assert any(s.startswith(where) and part in s for s in scopes), \
                (where, part)
    assert not any("attention/q_proj" in s or "mtp" in s for s in scopes)
    rows = cell["global_batch"] * 8192 * 4
    calls = _grouped_kernel_calls(text)
    assert len(calls) == 48 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in calls.values()), calls
    ours = collections.Counter(
        (re.sub(r"[.\d]+$", "", name), shapes[0])
        for name, (_, shapes) in calls.items())
    assert ours == {
        ("dwt_gmm", f"{rows},1024"): 16, ("dwt_gmm", f"{rows},3584"): 8,
        ("dwt_gmm_t", f"{rows},1024"): 4, ("dwt_gmm_t", f"{rows},3584"): 8,
        ("dwt_tgmm", "8,3584,1024"): 8, ("dwt_tgmm", "8,1024,3584"): 4}
    assert "[64,3584,1024]" not in text and "[64,1024,3584]" not in text
    assert _rows_map_calls(text) == {
        ("dwt_rows_map_gated_silu", f"{rows},1024"): 8,
        ("dwt_rows_map_gated_silu_bwd", f"{rows},1024"): 4,
        ("dwt_rows_map_add", f"{rows},3584"): 4,
        ("dwt_rows_map_weigh", f"{rows},3584"): 4}
    assert "conditional(" not in text
    loops = [line for line in text.splitlines() if " while(" in line]
    assert sum("hc/sinkhorn" in line for line in loops) == 30
    assert all("hc/sinkhorn" in line or "/moe/" in line for line in loops)


def test_xing_step_mixes_its_lanes_in_four_kernels_a_sublayer(
        xing_step, on_tpu):
    """The route and its static counter: ten sublayers run `dwt_hc_pre`
    and `dwt_hc_post` forward, again recomputed (the last sublayer of a
    block is recomputed for nobody: five, not ten) and `dwt_hc_post_bwd`
    and `dwt_hc_pre_bwd` backward, each under the scope of its mix, the
    stream's cotangent through H_res written into `dwt_hc_pre_bwd`'s
    output buffer.  The byte ledger (`hlo_scopes.moved_bytes`): nothing
    else between the stack's two ends reads or writes a whole (1, 4,
    8192, 3584) stream, and all of `hc/*` moves under 600 hidden vectors
    a token (the least is 560, `resmix_bytes_per_step`; the plain route
    moved 1,350)."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import moved_bytes
    from dlrover_wuqiong_tpu.ops import hc_mix

    cell, _, step = xing_step
    text = step.as_text()
    assert hc_mix.hc_route(4, cell["seq_len"], 3584) == "kernel"
    assert dict(hc_mix.plan(cell["seq_len"])) == {
        "tile": 128, "pre_tile": 512, "interpret": False}
    moved = moved_bytes(text, "hc")
    shapes = dict(re.findall(r"%(dwt_hc_[\w.]+) = (.*?) custom-call\(", text))
    calls = collections.Counter()
    for name, entry in moved.items():
        kernel = re.match(r"(dwt_hc_\w+?)(?:\.\d+)?$", name)
        if kernel:
            phase, *path = entry["scope"].split("/")
            calls[kernel.group(1), phase,
                  "/".join(path[path.index("hc"):][:2]),
                  " ".join(re.findall(r"\w+\[[\d,]+\]", shapes[name]))] += 1
    stream, vector, coef = ("bf16[1,4,8192,3584]", "bf16[1,8192,3584]",
                            "f32[1,32,8192]")
    assert calls == {
        ("dwt_hc_pre", "fwd", "hc/pre", f"{vector} {coef}"): 10,
        ("dwt_hc_pre", "recompute", "hc/pre", f"{vector} {coef}"): 10,
        ("dwt_hc_post", "fwd", "hc/post_res", stream): 10,
        ("dwt_hc_post", "recompute", "hc/post_res", stream): 5,
        ("dwt_hc_post_bwd", "bwd", "hc/post_res",
         f"{vector} {stream} f32[1,24,8192]"): 10,
        ("dwt_hc_pre_bwd", "bwd", "hc/pre",
         f"{stream} f32[4,32,3584] {coef}"): 10}
    assert text.count("output_to_operand_aliasing={{0}: (5, {})}") == 10
    one = cell["global_batch"] * cell["seq_len"] * 3584 * 2
    whole = {name for name, e in moved.items()
             if max(e["read"], e["written"]) >= 4 * one}
    ends = {name for name in whole if not name.startswith("dwt_hc_")}
    assert len(whole - ends) == 55
    assert sorted(moved[name]["scope"] for name in ends) == [
        "bwd/LatentMoE/hc/expand", "fwd/LatentMoE/hc/read_out"]
    total = sum(e["read"] + e["written"] for e in moved.values()) / one
    assert 550 < total < 600, total


def test_every_device_op_of_the_step_has_an_owner(xing_step):
    """As the other steps (tests/test_tpu_compile.py); the counter's max
    is the scope `hc_stats`'s (`hyper_connection.collect_residual_stats`)
    so that `step.unowned_ms` reads 0."""
    _every_device_op_has_an_owner(xing_step[2])


def test_no_fusion_of_the_step_falls_to_the_models_root(xing_step):
    _no_fusion_falls_to_the_root(xing_step[2], "LatentMoE")


def test_the_mtp_module_compiles_at_published_widths(xing_mtp_step):
    """Behind the dense layer: the 7168 x 3584 joining product, one more
    expert block with its own hyper-connections under `mtp_0/block`, the
    second logits through the SAME head (two products under `head`
    forward, not two matrices) and the second cross-entropy."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    _, model, step = xing_mtp_step
    assert (model.config.num_layers, model.config.mtp_layers) == (1, 1)
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("mtp_0/eh_proj", "mtp_0/block_0/hc/sinkhorn",
                 "mtp_0/block_0/hc/post_res",
                 "mtp_0/block_0/feed_forward/moe/experts",
                 "mtp_0/block_0/attention/q_b_proj", "LatentMoE/head",
                 "loss"):
        assert any(part in s for s in scopes), part
    assert "f32[7168,3584]" in text
    assert len(re.findall(r"%(dwt_fa_fwd)(?:\.\d+)? = ", text)) == 4
    params = 3 * 8  # m, v, the weights: in and out, of ONE head matrix
    assert text.count("f32[3584,16384]{") > params  # it is there
    m = step.memory_analysis()
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()
