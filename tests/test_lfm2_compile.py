"""`lfm2_24b_a2b.steady`'s gated short convolution and its step, compiled
by the TPU's own compiler for a DESCRIBED v5e (no chip attached), as
tests/test_tpu_compile.py does for the other cells — whose helpers these
tests use.

Tier-1 compiles ONE conv mixer's gradient at the cell's shape (a few
seconds).  The WHOLE step is `slow` (tier-2, `-m slow`): ONE
module-scoped fixture compiles it, once a run, and that takes the TPU
compiler about a minute on every core of this machine — more than the
suite's margin under its 1,470 s limit.  Run
`python -m pytest tests/test_lfm2_compile.py -m slow` after a change to
`models/lfm2.py`, `models/llama.py`'s attention, `models/moe.py` or the
cell's file: it pins the memory rung.
"""

import collections
import math
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

from dlrover_wuqiong_tpu.models.lfm2 import ShortConvMixer
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

TOKENS, HIDDEN = (4, 8192), 2048


@pytest.fixture(scope="module")
def lfm2_step(request):
    """`lfm2_24b_a2b.steady`'s step — published widths, blocks 1-5 (four
    gated short-convolution mixers, one QK-normed GQA layer; one dense
    SwiGLU, four expert layers), 8 of 64 experts held, an eighth of the
    tied table, the cell's four sequences of 8,192 tokens, full
    recomputation."""
    return _one_chip_step(request, "lfm2_24b_a2b.steady", "lfm2_moe")


def test_one_conv_mixers_gradient_compiles_at_the_cells_shape(topo):
    """One gated short-convolution mixer's gradient at (4, 8192, 2048)
    bfloat16 on one TPU device: five matrix products (h W_in; the
    cotangents of W_out, of what W_out reads, of W_in and of h) and no
    kernel of this repo's — the gates and the filter are the plain lines —
    nothing that holds other ops (no `while`, no `conditional`), no
    float32 array as large as the tokens x hidden written anywhere (the
    lines run in bfloat16; in float32 the compiler wrote the (32,768 x
    6,144) projection itself in float32, 0.8 GB), and temporaries under
    1.55 GB (1.34 as compiled, + 15%)."""
    one = SingleDeviceSharding(topo.devices[0])
    layer = ShortConvMixer(HIDDEN, 3, jnp.bfloat16)
    h = jax.ShapeDtypeStruct((*TOKENS, HIDDEN), jnp.bfloat16, sharding=one)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), h)["params"])

    def loss(p, h, dy):
        return jnp.sum(layer.apply({"params": p}, h).astype(jnp.float32)
                       * dy)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h, h).compile()
    text = compiled.as_text()
    assert " while(" not in text and " conditional(" not in text
    assert "dwt_" not in text and "custom-call" not in text
    assert len(re.findall(r" convolution\(", text)) == 5  # the products
    tokens = TOKENS[0] * TOKENS[1]
    # what the ENTRY computation's instructions write (a fusion's own
    # instructions write nothing to HBM)
    entry = re.search(r"ENTRY[^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    written = [re.split(r"\s[a-z][\w\-]*\(", line.split("=", 1)[1], 1)[0]
               for line in entry.splitlines() if "=" in line]
    assert any("bf16[4,8192,6144]" in shapes for shapes in written)
    wide = [dims for shapes in written
            for dims in re.findall(r"f32\[([\d,]+)\]", shapes)
            if math.prod(int(n) for n in dims.split(",")) >= tokens * HIDDEN]
    assert not wide, wide[:4]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.55e9


def _live_gb(step) -> float:
    return compiled_memory(step)["live_bytes"] / 1e9


LIVE_GB = 13.17  # the step's described reading at rung (a)


@pytest.mark.slow
def test_lfm2_step_fits_one_chip_by_the_rule_and_fills_it(lfm2_step):
    """State + temporaries under 90% of the chip's 16 GB at rung (a),
    four sequences of 8,192 tokens (PR 26's rule), of which 5.63 GB is
    donated state: 13.17 GB live, held here; the cell's file keeps every
    rung's reading, and five sequences over at 14.85.  Far over the 25% a
    cell has to fill."""
    cell, model, step = lfm2_step
    assert model.config.num_params() == 469_285_248
    assert (cell["global_batch"], cell["seq_len"]) == TOKENS
    rung = cell["config"]["train"]["memory_rung"]
    live = _live_gb(step)
    assert rung["taken"] == "a"
    assert live == pytest.approx(LIVE_GB, abs=0.05)
    assert live == pytest.approx(rung["live_GB"]["a: 4 x 8192"], abs=0.05)
    assert rung["live_GB"]["a: 4 x 8192"] < rung["limit_GB"] \
        < rung["live_GB"]["over: 5 x 8192"]
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.65 * 16 < live < 0.90 * 16
    assert step.memory_analysis().alias_size_in_bytes >= \
        12 * model.config.num_params()


@pytest.mark.slow
def test_lfm2_step_holds_its_scopes_kernels_and_a_share_of_experts(
        lfm2_step):
    """Every scope the cell's scopes file names is in the compiled step;
    the one attention layer runs the kernels at heads of 64 on the direct
    route (forward, recomputed, one fused backward) and `dwt_rope` on q
    and k; a share's three grouped products a layer run
    `ops/grouped_matmul.py`'s kernels on the 8 held experts of 1,536,
    none on the published 64; no kernel of `ops/short_conv.py` is in the
    step (the gated form runs the plain lines).  What holds other ops in
    the step is the loops over the held rows' chunks (`models/moe.py`'s)
    — no `conditional`."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = lfm2_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("short_conv/in_proj", "short_conv/gated",
                 "short_conv/out_proj", "attention/q_proj",
                 "attention/k_proj", "attention/v_proj", "attention/qk_norm",
                 "attention/o_proj", "feed_forward/moe/router",
                 "feed_forward/moe/dispatch", "feed_forward/moe/experts",
                 "feed_forward/moe/combine", "layers/feed_forward/gate_proj",
                 "operator_norm", "ffn_norm", "Lfm2/head", "loss",
                 "optimizer", "shortconv_calls"):
        assert any(part in s for s in scopes), part
    assert not any("moe/aux" in s or "moe/shared" in s for s in scopes)
    assert "dwt_conv" not in text
    calls = collections.Counter(re.findall(
        r"%(dwt_(?:fa|rope)\w*?)(?:\.\d+)? = ", text))
    assert calls == {"dwt_fa_fwd": 2, "dwt_fa_bwd_fused": 1, "dwt_rope": 6}
    assert fa.attention_route(32, 64) == ("direct", 2)  # two heads a slab
    grouped = _grouped_kernel_calls(text)
    assert len(grouped) == 12 * 4 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in grouped.values()), grouped
    assert "[64,2048,1536]" not in text and "[64,1536,2048]" not in text
    assert " conditional(" not in text
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert all(scope.rsplit("moe/", 1)[1].split("/")[0]
               in ("dispatch", "combine") for scope in loops), loops


@pytest.mark.slow
def test_every_device_op_of_the_step_has_an_owner(lfm2_step):
    """As the other steps (tests/test_tpu_compile.py); the counter's
    copies are its scope's (`shortconv_calls`)."""
    _every_device_op_has_an_owner(lfm2_step[2])


@pytest.mark.slow
def test_no_fusion_of_the_step_falls_to_the_models_root(lfm2_step):
    _no_fusion_falls_to_the_root(lfm2_step[2], "Lfm2")
