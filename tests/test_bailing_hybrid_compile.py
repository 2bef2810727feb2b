"""`ling3_0_flash.steady`'s recurrence and its step, compiled by the TPU's
own compiler for a DESCRIBED v5e (no chip attached), as
tests/test_tpu_compile.py does for the other cells — whose helpers these
tests use.

Tier-1 compiles the channel-decay recurrence's gradient alone at the
cell's shape (the kernel pair: about five seconds).  The WHOLE step is `slow` (tier-2,
`-m slow`): ONE module-scoped fixture compiles it, once a run, and that
takes the TPU compiler three to four minutes on every core of this
machine, more than the suite's margin under its 1,470 s limit (PR 57's
whole runs: 1,372 s without it; two others were cut by the machine's own
variance).  Run
`python -m pytest tests/test_bailing_hybrid_compile.py -m slow` after a
change to `ops/delta_rule.py`'s channel form, `models/kda.py`,
`models/bailing_hybrid.py` or the cell's file: it pins the memory rung.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
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

from dlrover_wuqiong_tpu.ops import delta_rule as dr
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory


@pytest.fixture(scope="module")
def ling_step(request):
    """`ling3_0_flash.steady`'s step — published widths, layers 0-6 (six
    KDA mixers, one gated latent attention; one dense SwiGLU, six expert
    layers), 16 of 32 heads and 8 of 512 experts held, an eighth of the
    vocabulary, the cell's one sequence of 8,192 tokens, full
    recomputation."""
    return _one_chip_step(request, "ling3_0_flash.steady", "bailing_hybrid")


def _elements(text):
    """The size of every float array the compiled text names."""
    return [int(np.prod([int(n) for n in dims.split(",")]))
            for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)]


def test_the_channel_decay_gradient_compiles_at_the_cells_shape(topo, on_tpu):
    """One KDA layer's recurrence, forward and backward, at (1, 8192, 16,
    128) on one TPU device: the channel pair compiles for the chip —
    one `dwt_kda_fwd` (the residuals' forward: the gradient alone needs
    no second one) and one `dwt_kda_bwd`, none of the scalar pair — with
    no `while` and no `conditional`; no array but the saved entering
    states, (1, 16, 128, 128, 128) float32 = 134 MB, is larger than the
    operands themselves (no transition matrix, no scaled column operand
    in HBM); and its temporaries stay under 0.443 GB (0.403 as compiled,
    + 10%; the chunked form's were 1.70)."""
    one = SingleDeviceSharding(topo.devices[0])
    t, h, d, chunk = 8192, 16, 128, 64
    assert dr.delta_route(t, chunk, h, d, d, None, True) == ("kernel", 4)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(q, k, v, g, beta, do):
        return jnp.sum(dr.gated_delta_rule(q, k, v, g, beta, chunk=chunk,
                                           dtype=jnp.bfloat16) * do)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape(1, t, h, d), shape(1, t, h, d),
        shape(1, t, h, d, dtype=jnp.bfloat16), shape(1, t, h, d),
        shape(1, t, h), shape(1, t, h, d)).compile()
    text = compiled.as_text()
    assert " while(" not in text and " conditional(" not in text
    assert collections.Counter(re.findall(
        r"%(dwt_\w+?)(?:\.\d+)? = ", text)) == {
            "dwt_kda_fwd": 1, "dwt_kda_bwd": 1}
    states, operand = (t // chunk) * h * d * d, t * h * d
    assert {n for n in _elements(text) if n > operand} == {states}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.443e9


def _live_gb(step) -> float:
    return compiled_memory(step)["live_bytes"] / 1e9


# the step's described reading with the channel pair, and since PR 59
# the convolutions' (11.93 before it)
LIVE_GB = 11.82


@pytest.mark.slow
def test_ling_step_fits_one_chip_by_the_rule_and_fills_it(ling_step):
    """State + temporaries under 90% of the chip's 16 GB at rung (b), one
    sequence of 8,192 tokens (PR 26's rule), of which 7.79 GB is donated
    state: 11.82 GB live with the two pairs, held here; the cell's file
    keeps the chunked form's readings (12.92 at (b), and (a), one sequence
    of 16,384, over at 15.71), an upper bound on this one.  Far over the
    25% a cell has to fill."""
    cell, model, step = ling_step
    assert model.config.num_params() == 648_853_344
    assert (cell["global_batch"], cell["seq_len"]) == (1, 8192)
    m = step.memory_analysis()
    live = _live_gb(step)
    rung = cell["config"]["train"]["memory_rung"]
    assert rung["taken"] == "b"
    assert live == pytest.approx(LIVE_GB, abs=0.05)
    assert live < rung["live_GB"]["b: 1 x 8192, chunk 64"] < rung["limit_GB"]
    assert rung["live_GB"]["a: 1 x 16384, chunk 64"] > rung["limit_GB"]
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.65 * 16 < live < 0.90 * 16
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


@pytest.mark.slow
def test_ling_step_runs_the_channel_decay_by_the_kernel_pair(ling_step):
    """The recurrence's route is the one `delta_route` says on one TPU
    device: every `dwt_kda_*` custom call is owned by
    `linear_attention/delta` — six layers' `dwt_kda_fwd` in the forward
    and in the recomputed phase, six `dwt_kda_bwd` in the backward — none
    of the scalar pair is in the step, and nothing under that scope is a
    (dk x dk) transition of the carry, a tile with two chunk-length axes
    or a copy of K a sub-block: the largest array there is the saved
    entering states, then the operands themselves."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        owners, read_instruction)

    cell, model, step = ling_step
    text = step.as_text()
    tokens, heads, dk, chunk = cell["seq_len"], 16, 128, 64
    assert model.config.chunk_size == chunk
    assert dr.delta_route(tokens, chunk, heads, dk, dk,
                          channel_decay=True) == "chunked"   # as the test runs
    assert "dwt_gdr" not in text
    table = owners(text)
    calls = {n: e for n, e in table.items() if n.startswith("dwt_kda_")}
    by_phase = sorted((e["scope"].split("/")[0], n.split(".")[0])
                      for n, e in calls.items())
    assert by_phase == [("bwd", "dwt_kda_bwd")] * 6 \
        + [("fwd", "dwt_kda_fwd")] * 6 + [("recompute", "dwt_kda_fwd")] * 6
    for name, entry in calls.items():
        assert "linear_attention/delta" in entry["scope"], (name, entry)
        assert entry["via"] != "none" and entry["kind"] == "compute"
    under = {n for n, e in table.items()
             if "linear_attention/delta" in e["scope"]}
    assert len(under) > 100
    states, operand, seen = tokens // chunk * heads * dk * dk, \
        tokens * heads * dk, 0
    for line in text.splitlines():
        inst = read_instruction(line)
        if inst is None or inst["name"] not in under:
            continue
        seen += 1
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", inst["shape"]):
            dims = [int(d) for d in dims.split(",")]
            assert int(np.prod(dims)) in (states, operand) \
                or int(np.prod(dims)) < operand, line
            assert dims.count(chunk) < 2, line
            if dims[-2:] == [dk, dk]:          # the states, by chunk
                assert dims == [1, heads, tokens // chunk, dk, dk], line
    assert seen >= len(under) - 5


@pytest.mark.slow
def test_ling_step_convolves_in_its_kernels(ling_step, on_tpu):
    """Every KDA mixer's three short convolutions (q, k and v, 2,048
    channels each at sixteen heads of 128) run `ops/short_conv.py`'s
    pair: eighteen `dwt_conv_fwd` in the forward pass, eighteen in its
    recomputation, eighteen `dwt_conv_bwd` in the backward pass, each
    under `linear_attention/conv`, which the cell's scopes file puts in
    `step.linattn_scan_ms`."""
    import json
    import os

    from benchmark import cells, program
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table
    from dlrover_wuqiong_tpu.ops import short_conv

    cell, _, step = ling_step
    assert short_conv.conv_route(cell["seq_len"], 16 * 128, 4,
                                 jnp.bfloat16) == "kernel"
    table = scope_table(step.as_text())
    calls = {n: s for n, s in table.items() if n.startswith("dwt_conv_")}
    assert collections.Counter(
        (n.split(".")[0], s.split("/")[0]) for n, s in calls.items()) == {
            ("dwt_conv_fwd", "fwd"): 18, ("dwt_conv_fwd", "recompute"): 18,
            ("dwt_conv_bwd", "bwd"): 18}
    with open(os.path.join(cells.HERE, "models", cell["config"][
            "model_class"] + ".scopes.json")) as f:
        rules = json.load(f)
    for name, scope in calls.items():
        assert "/linear_attention/conv/" in f"/{scope}/", (name, scope)
        assert program.part_of(scope, rules["linattn_parts"]) \
            == "linattn_scan"


@pytest.mark.slow
def test_ling_step_holds_its_scopes_kernels_and_a_share_of_experts(
        ling_step):
    """Every scope the cell's scopes file names is in the compiled step;
    the one latent layer runs the two-width attention kernels (forward,
    recomputed, one fused backward) and `dwt_rope`; a share's three
    grouped products a layer run `ops/grouped_matmul.py`'s kernels,
    twelve an expert layer, on the 8 held experts of 768, none on the
    published 512.  What holds other ops in the step is the loops over
    the held rows' chunks, five an expert layer (`models/moe.py`'s) — no
    `conditional`, and no `while` of the recurrence or the group limit."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = ling_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("linear_attention/q_proj", "linear_attention/k_proj",
                 "linear_attention/v_proj", "linear_attention/f_proj",
                 "linear_attention/decay", "linear_attention/gates",
                 "linear_attention/conv", "linear_attention/delta",
                 "linear_attention/g_proj", "linear_attention/gate",
                 "linear_attention/gate_norm", "linear_attention/o_proj",
                 "attention/q_proj", "attention/kv_a_proj",
                 "attention/kv_a_norm", "attention/kv_b_proj",
                 "attention/rope", "attention/assemble", "attention/g_proj",
                 "attention/gate", "attention/o_proj",
                 "feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "feed_forward/moe/shared/shared_gate_proj",
                 "layers/feed_forward/gate_proj", "input_norm",
                 "post_attn_norm", "BailingHybrid/head", "loss", "optimizer",
                 "attn_lanes", "delta_stats", "kda_stats"):
        assert any(part in s for s in scopes), part
    assert not any("moe/aux" in s for s in scopes)
    calls = collections.Counter(re.findall(
        r"%(dwt_(?:fa|rope)\w*?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_fwd": 2, "dwt_fa_bwd_fused": 1, "dwt_rope": 6}
    assert fa.attention_route(16, 192, 128) == ("transposed", 0)
    grouped = _grouped_kernel_calls(text)
    assert len(grouped) == 72 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in grouped.values()), grouped
    assert "[512,2560,768]" not in text and "[512,768,2560]" not in text
    # (`_held_row_loops` tells the loops' gathers by shape, and here a
    # turn's chunk IS the tokens, 8,192: the loops are counted by scope)
    assert " conditional(" not in text
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert len(loops) == 5 * 6
    assert all(scope.rsplit("moe/", 1)[1].split("/")[0]
               in ("dispatch", "combine") for scope in loops), loops


@pytest.mark.slow
def test_every_device_op_of_the_step_has_an_owner(ling_step):
    """As the other steps (tests/test_tpu_compile.py); the counters'
    copies are their scopes' (`delta_stats`, `kda_stats`,
    `attn_gate_mean`)."""
    _every_device_op_has_an_owner(ling_step[2])


@pytest.mark.slow
def test_no_fusion_of_the_step_falls_to_the_models_root(ling_step):
    _no_fusion_falls_to_the_root(ling_step[2], "BailingHybrid")
