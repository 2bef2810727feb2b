"""`qwen3_next_80b_a3b.steady`'s attention and its step, compiled by the
TPU's own compiler for a DESCRIBED v5e (no chip attached), as
tests/test_tpu_compile.py does for the other cells — whose helpers these
tests use.

Tier-1 compiles ONE attention call at the cell's shape — the first
heads of 256 (two lane slabs a head) on the direct kernels, eight query
heads reading one kv head's slabs — forward and backward (about ten
seconds).  The WHOLE step is `slow` (tier-2, `-m slow`): ONE
module-scoped fixture compiles it, once a run, and that takes the TPU
compiler a minute and three quarters.  Run
`python -m pytest tests/test_qwen3_next_compile.py -m slow` after a
change to `models/qwen3_next.py`, `models/gated_delta.py`,
`models/llama.py`'s attention, `models/moe.py`, `ops/delta_rule.py` or
the cell's file: it pins the memory rung.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_tpu_compile import (  # noqa: F401 — `topo` and the cache switch are fixtures
    _every_device_op_has_an_owner,
    _grouped_kernel_calls,
    _no_fusion_falls_to_the_root,
    _no_persistent_cache,
    _one_chip_step,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

B, T, H, KV, D = 1, 16384, 16, 2, 256


@pytest.fixture(scope="module")
def qwen_step(request):
    """`qwen3_next_80b_a3b.steady`'s step — published widths, one period
    of four blocks, 16 of 512 experts held, an eighth of the vocabulary,
    the cell's one sequence of 16,384 tokens, full recomputation."""
    return _one_chip_step(request, "qwen3_next_80b_a3b.steady",
                          "qwen3_next")


def test_heads_of_256_compile_on_the_direct_kernels_at_the_cells_shape(
        topo, on_tpu, _no_persistent_cache):
    """q (1, 16384, 16 x 256) over k and v (1, 16384, 2 x 256) in
    bfloat16 on one TPU device: `dwt_fa_grp_fwd` — four of a kv head's
    eight query heads a grid step, 1,024 lanes (`fa.forward_route`, PR
    67) — and ONE fused backward kernel on the projections' own layout,
    nothing repeated to sixteen heads, nothing laid out by head."""
    one = SingleDeviceSharding(topo.devices[0])
    assert fa.projected_ok(H, D, T)
    assert fa.kv_route(H, KV, D) == ("indexed", 8)
    assert fa.forward_route(T, T, D, 8) == ("group", 4)

    def shape(heads):
        return jax.ShapeDtypeStruct((B, T, heads * D), jnp.bfloat16,
                                    sharding=one)

    def loss(q, k, v):
        return fa.flash_attention_projected((q, k, v), H).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(H), shape(KV), shape(KV)).compile()
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_\w*?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_grp_fwd": 1, "dwt_fa_bwd_fused": 1}
    assert "bf16[1,16384,4096]" in text  # q as its projection left it
    assert "bf16[16,16384,256]" not in text
    assert " while(" not in text and " conditional(" not in text


LIVE_GB = 13.47  # the step's described reading at rung (c)


@pytest.mark.slow
def test_qwen_step_fits_one_chip_by_the_rule_and_fills_it(qwen_step):
    """State + temporaries under 90% of the chip's 16 GB at rung (c), 16
    experts held at one sequence of 16,384 tokens (PR 26's rule), of
    which 5.09 GB is donated state: 13.47 GB live, held here; the cell's
    file keeps every rung's reading, (a) refused by the compiler at 17.61
    and (b) over at 16.12.  Far over the 25% a cell has to fill."""
    cell, model, step = qwen_step
    assert model.config.num_params() == 424_340_544
    assert (cell["global_batch"], cell["seq_len"]) == (B, T)
    rung = cell["config"]["train"]["memory_rung"]
    live = compiled_memory(step)["live_bytes"] / 1e9
    assert rung["taken"] == "c"
    assert live == pytest.approx(LIVE_GB, abs=0.05)
    assert live == pytest.approx(rung["live_GB"]["c: 16 held, 1 x 16384"],
                                 abs=0.05)
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.65 * 16 < live < 0.90 * 16
    assert step.memory_analysis().alias_size_in_bytes >= \
        12 * model.config.num_params()


@pytest.mark.slow
def test_qwen_step_holds_its_scopes_kernels_and_a_share_of_experts(
        qwen_step):
    """Every scope the cell's scopes file names is in the compiled step;
    each of the three mixers runs the delta rule's forward kernel twice
    (forward, recomputed) and its backward once, and the convolution's
    pair on q, k and v; the attention block the direct kernels at heads
    of 256 and NO `dwt_rope` (a two-slab head rotated in part keeps the
    formula) and no head-wise gate kernel; a share's grouped products run
    `ops/grouped_matmul.py`'s kernels on the 16 held experts of 512, none
    on the published 512."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    text = qwen_step[2].as_text()
    scopes = set(scope_table(text).values())
    for part in (*(f"linear_attention/{s}" for s in (
            "q_proj", "k_proj", "v_proj", "g_proj", "gates", "conv",
            "delta", "gate_norm", "o_proj")),
                 "attention/q_proj", "attention/k_proj", "attention/v_proj",
                 "attention/qk_norm", "attention/rope_partial",
                 "attention/gate", "attention/o_proj",
                 "feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "feed_forward/moe/shared", "feed_forward/moe/aux",
                 "input_norm", "post_attn_norm", "Qwen3Next/head", "loss",
                 "optimizer"):
        assert any(part in s for s in scopes), part
    assert any("moe/shared/shared_expert_gate" in s for s in scopes)
    calls = collections.Counter(re.findall(
        r"%(dwt_(?:fa|gdr|conv|rope|gate)\w*?)(?:\.\d+)? = ", text))
    assert calls == {
        "dwt_gdr_fwd": 2 * 3, "dwt_gdr_bwd": 3,
        "dwt_conv_fwd": 2 * 3 * 3, "dwt_conv_bwd": 3 * 3,
        "dwt_fa_grp_fwd": 2, "dwt_fa_bwd_fused": 1}
    grouped = _grouped_kernel_calls(text)
    assert grouped and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in grouped.values()), grouped
    assert "[512,2048,512]" not in text and "[32,2048,512]" not in text
    assert " conditional(" not in text


@pytest.mark.slow
def test_every_device_op_of_the_step_has_an_owner(qwen_step):
    _every_device_op_has_an_owner(qwen_step[2])


@pytest.mark.slow
def test_no_fusion_of_the_step_falls_to_the_models_root(qwen_step):
    _no_fusion_falls_to_the_root(qwen_step[2], "Qwen3Next")
