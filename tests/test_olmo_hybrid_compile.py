"""`olmo_hybrid_7b.steady`'s step, compiled by the TPU's own compiler for
a DESCRIBED v5e (no chip attached), as tests/test_tpu_compile.py does for
the other cells — whose helpers these tests use.  A file of its own, as
tests/test_smallthinker_compile.py is, so that another xdist worker
compiles this step (about 50 s) while that file compiles the other four.
"""

import json
import os

import pytest
from test_tpu_compile import (  # noqa: F401 — `topo` and the cache switch are fixtures
    _every_device_op_has_an_owner,
    _no_fusion_falls_to_the_root,
    _no_persistent_cache,
    _one_chip_step,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

SUBSCOPES = ("q_proj", "k_proj", "v_proj", "g_proj", "gates", "conv",
             "delta", "gate_norm", "o_proj")


@pytest.fixture(scope="module")
def olmo_step(request):
    """`olmo_hybrid_7b.steady`'s step — published widths, one period
    (three gated delta-rule mixers at fifteen of thirty heads, chunk 64,
    one attention layer of thirty heads of 128, a SwiGLU of 11,008 behind
    each), the untied head on an eighth of the vocabulary, one
    8192-token sequence, full recomputation (about 50 s)."""
    return _one_chip_step(request, "olmo_hybrid_7b.steady", "olmo_hybrid")


def test_olmo_step_fits_one_chip_by_the_rule_and_fills_it(olmo_step):
    """State + temporaries under 90% of the chip's 16 GB (PR 26's rule)
    at the shipped sizes: 12.96 GB, of which 9.55 GB is donated state and
    3.41 GB temporaries — 1.20 GB under rung (a) of the configuration
    file, which was read with the chunked `jax.numpy` delta rule (14.16
    GB): the kernel pair keeps a chunk's tiles, U, W and their cotangents
    in VMEM.  With all thirty heads of the linear mixers held the state
    and its gradients alone would be 14.86 GB."""
    cell, model, step = olmo_step
    assert model.config.num_params() == 795_736_986
    assert (cell["global_batch"], cell["seq_len"], model.config.chunk_size,
            model.config.linear_heads) == (1, 8192, 64, 15)
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    rung = cell["config"]["train"]["memory_rung"]
    assert rung["taken"] == "a"
    assert live / 1e9 == pytest.approx(12.96, abs=0.05)
    assert live / 1e9 < rung["live_GB"]["a: 1 x 8192, chunk 64"] - 1.0
    assert 0.25 * 16 * 2 ** 30 < 0.75 * 16e9 < live < \
        rung["limit_GB"] * 1e9 == 0.90 * 16e9, live / 1e9
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_olmo_step_holds_its_scopes_and_no_op_that_holds_others(olmo_step):
    """Every scope the cell's scopes file names is in the compiled step,
    the untied head's product under `head`; every op under
    `linear_attention` belongs to the file's `linattn` part, the
    convolution's and the delta rule's to `linattn_scan`; and nothing in
    the step holds other ops (a `while`, a `conditional`), which a device
    trace would count beside the ops they ran — at three mixers of 128
    chunks each, forward, recomputed and backward: a kernel's sequential
    chunk axis is inside ONE custom call."""
    from benchmark import cells, program
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = olmo_step
    text = step.as_text()
    assert " while(" not in text and " conditional(" not in text
    table = scope_table(text)
    scopes = set(table.values())
    for part in (*(f"linear_attention/{s}" for s in SUBSCOPES),
                 "feed_forward/gate_proj", "feed_forward/up_proj",
                 "feed_forward/down_proj", "attention/q_proj",
                 "attention/k_proj", "attention/v_proj", "attention/o_proj",
                 "attention/qk_norm", "post_mixer_norm",
                 "post_feedforward_norm", "OlmoHybrid/head", "loss",
                 "optimizer"):
        assert any(part in s for s in scopes), part
    assert not any("mamba" in s or "moe" in s for s in scopes)
    with open(os.path.join(cells.HERE, "models", cell["config"][
            "model_class"] + ".scopes.json")) as f:
        rules = json.load(f)
    under = {s for s in scopes if "linear_attention" in s.split("/")}
    assert len(under) > 30
    for scope in under:
        assert program.part_of(scope, rules["parts"]) == "linattn", scope
        path = scope.split("/")
        sub = path[path.index("linear_attention") + 1:][:1]
        # (a fusion whose members span two of the nine is named by the
        # mixer alone)
        assert not sub or sub[0] in SUBSCOPES, scope
        want = "linattn_scan" if sub and sub[0] in ("conv", "delta") \
            else program.UNSCOPED
        assert program.part_of(scope, rules["linattn_parts"]) == want
    for phase in ("fwd", "recompute", "bwd"):
        assert any(s.startswith(phase) and "linear_attention/delta" in s
                   for s in scopes), phase
    # the attention layer's kernels, and the mixers' pair: three layers,
    # forward and recomputed, and three backward
    kernels = sorted(n.split(".")[0] for n in table if n.startswith("dwt_"))
    assert kernels == ["dwt_fa_bwd_fused", "dwt_fa_fwd", "dwt_fa_fwd"] \
        + ["dwt_gdr_bwd"] * 3 + ["dwt_gdr_fwd"] * 6


def test_olmo_step_keeps_the_plain_convolution(olmo_step, on_tpu):
    """The mixers' q, k and v are 1,440, 1,440 and 2,880 channels wide at
    fifteen heads: 11.25 and 22.5 lane tiles, so `conv_route` says
    "plain" on the chip too, the step holds no `dwt_conv_*` custom call,
    and `linear_attention/conv` is the compiler's fusions in all three
    phases (3.9 of `step.linattn_scan_ms`' 40.65 ms: ROADMAP S15)."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table
    from dlrover_wuqiong_tpu.ops import short_conv

    cell, model, step = olmo_step
    cfg = model.config.linear_config()
    widths = (cfg.num_heads * cfg.key_dim, cfg.num_heads * cfg.value_dim)
    assert widths == (1440, 2880)
    for channels in widths:
        assert short_conv.conv_route(cell["seq_len"], channels,
                                     cfg.conv_kernel, cfg.dtype) == "plain"
    assert short_conv.conv_route(cell["seq_len"], 1536, cfg.conv_kernel,
                                 cfg.dtype) == "kernel"
    text = step.as_text()
    assert "dwt_conv" not in text
    scopes = set(scope_table(text).values())
    for phase in ("fwd", "recompute", "bwd"):
        assert any(s.startswith(phase) and "linear_attention/conv" in s
                   for s in scopes), phase


def test_olmo_step_runs_the_delta_rule_in_its_kernels(olmo_step):
    """Every `dwt_gdr_*` custom call is owned by `linear_attention/delta`
    — two forward kernels and one backward a layer, in the forward, the
    recomputed and the backward phase (the `custom_vjp`'s backward is
    traced under the forward call's scopes), so their time is inside
    `step.linattn_scan_ms` — and nothing under that scope is a (dk x dk)
    transition of the carry or an array with two chunk-length axes: the
    chunk's tiles, the solve and the carried state are the kernels'."""
    import re

    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        owners, read_instruction)
    from dlrover_wuqiong_tpu.ops import delta_rule as dr

    text = olmo_step[2].as_text()
    table = owners(text)
    calls = {n: e for n, e in table.items() if n.startswith("dwt_gdr_")}
    by_phase = sorted((e["scope"].split("/")[0], n.split(".")[0])
                      for n, e in calls.items())
    assert by_phase == [("bwd", "dwt_gdr_bwd")] * 3 \
        + [("fwd", "dwt_gdr_fwd")] * 3 + [("recompute", "dwt_gdr_fwd")] * 3
    for name, entry in calls.items():
        assert "linear_attention/delta" in entry["scope"], (name, entry)
        assert entry["via"] != "none" and entry["kind"] == "compute"
    under = {n for n, e in table.items()
             if "linear_attention/delta" in e["scope"]}
    assert len(under) > 100
    seen = 0
    for line in text.splitlines():
        inst = read_instruction(line)
        if inst is None or inst["name"] not in under:
            continue
        seen += 1
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", inst["shape"]):
            dims = [int(d) for d in dims.split(",")]
            assert not (dtype == "f32" and dims[-2:] == [96, 96]), line
            assert dims.count(64) < 2, line
    assert seen >= len(under) - 5
    # the static counter of the same decision (the compile patches the
    # backend): five heads a grid step, no head padded
    assert dr._heads_block(15) == 5
    assert dr.product_lanes(96, 192) == (288, 288)


def test_olmo_step_runs_the_kernels_direct_at_thirty_heads_of_128(
        olmo_step):
    """The first un-grouped call at thirty heads through a model: a head
    is a lane slab, so the kernels index the projections' own
    (1, 8192, 3840) — the DIRECT route, ONE backward kernel — and nothing
    is laid out by head."""
    text = olmo_step[2].as_text()
    assert fa.attention_route(30, 128)[0] == "direct"
    assert fa.kv_route(30, 30, 128)[1] == 1
    assert fa.backward_route(8192, 8192, 128, 128, 1, 30)[0] == "fused"
    assert "dwt_fa_bwd_dq" not in text and "dwt_fa_bwd_dkv" not in text
    assert "bf16[30,8192,128]" not in text


def test_every_device_op_of_the_olmo_step_has_an_owner(olmo_step):
    _every_device_op_has_an_owner(olmo_step[2])


def test_no_fusion_of_the_olmo_step_falls_to_the_models_root(olmo_step):
    _no_fusion_falls_to_the_root(olmo_step[2], "OlmoHybrid")
