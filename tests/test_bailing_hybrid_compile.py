"""`ling3_0_flash.steady`'s recurrence and its step, compiled by the TPU's
own compiler for a DESCRIBED v5e (no chip attached), as
tests/test_tpu_compile.py does for the other cells — whose helpers these
tests use.

Tier-1 compiles the channel-decay recurrence's gradient alone at the
cell's shape (about twenty seconds).  The WHOLE step is `slow` (tier-2,
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


@pytest.fixture(scope="module")
def ling_step(request):
    """`ling3_0_flash.steady`'s step — published widths, layers 0-6 (six
    KDA mixers, one gated latent attention; one dense SwiGLU, six expert
    layers), 16 of 32 heads and 8 of 512 experts held, an eighth of the
    vocabulary, the cell's one sequence of 8,192 tokens, full
    recomputation."""
    return _one_chip_step(request, "ling3_0_flash.steady", "bailing_hybrid")


def test_the_channel_decay_gradient_compiles_at_the_cells_shape(topo):
    """One KDA layer's recurrence, forward and backward, at (1, 8192, 16,
    128): the chunked channel form compiles for the chip with no `while`
    and no `conditional` (the solve's rounds, the carry's scan and its
    reverse are unrolled), holds no (chunk x chunk x dk) tile a head and
    chunk — its largest array is the scaled column operand, four copies
    of K — and its temporaries stay under 2 GB (1.70 as compiled: the
    sub-blocks' operands are recomputed in the backward pass)."""
    one = SingleDeviceSharding(topo.devices[0])
    t, h, d, chunk = 8192, 16, 128, 64

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
    assert "dwt_gdr" not in text and "tpu_custom_call" not in text
    largest = max(
        int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text))
    assert t * h * d * 4 <= largest < t * h * d * chunk
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


def _live_gb(step) -> float:
    m = step.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9


@pytest.mark.slow
def test_ling_step_fits_one_chip_by_the_rule_and_fills_it(ling_step):
    """State + temporaries under 90% of the chip's 16 GB at rung (b), one
    sequence of 8,192 tokens (PR 26's rule), of which 7.79 GB is donated
    state; rung (a), one sequence of 16,384, read 15.54 GB and is over
    (the file keeps both readings).  Far over the 25% a cell has to
    fill."""
    cell, model, step = ling_step
    assert model.config.num_params() == 648_853_344
    assert (cell["global_batch"], cell["seq_len"]) == (1, 8192)
    m = step.memory_analysis()
    live = _live_gb(step)
    rung = cell["config"]["train"]["memory_rung"]
    assert rung["taken"] == "b"
    assert rung["live_GB"]["b: 1 x 8192, chunk 64"] == pytest.approx(
        live, abs=0.05)
    assert rung["live_GB"]["a: 1 x 16384, chunk 64"] > rung["limit_GB"]
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.65 * 16 < live < 0.90 * 16
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


@pytest.mark.slow
def test_ling_step_runs_the_channel_decay_by_the_chunked_form(ling_step):
    """The recurrence's route is the one `delta_route` says: the chunked
    `jax.numpy` form (no Pallas pair takes a decay a channel yet), so no
    `dwt_gdr_*` call is in the step; and no array of the step is a
    (chunk x chunk x dk) tile a head and chunk — the largest the form
    writes is the scaled column operand, four copies of K."""
    cell, model, step = ling_step
    text = step.as_text()
    assert dr.delta_route(cell["seq_len"], model.config.chunk_size, 16, 128,
                          128, channel_decay=True) == "chunked"
    assert dr.delta_route(cell["seq_len"], model.config.chunk_size, 16, 128,
                          128) == "chunked"  # off the TPU, the scalar one too
    assert "dwt_gdr" not in text
    tokens, heads, dk, chunk = 8192, 16, 128, 64
    largest = 0
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        largest = max(largest, n)
    assert tokens * heads * dk * 4 <= largest < tokens * heads * dk * chunk


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
