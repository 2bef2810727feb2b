"""`sdar_30b_a3b.steady`'s block-masked attention and its step, compiled
by the TPU's own compiler for a DESCRIBED v5e (no chip attached), as
tests/test_keye_compile.py does for Keye's — whose helpers these tests
use.

Tier-1 compiles ONE layer's attention — the two kernels of
`ops/block_attention.py`, the forward and the one backward sweep — at
the cell's shape
(under half a minute).  The WHOLE step is `slow` (tier-2, `-m slow`):
ONE module-scoped fixture compiles it, once a run.  Run `python -m
pytest tests/test_sdar_compile.py -m slow` after a change to
`models/sdar.py`, `models/llama.py`'s attention,
`ops/block_attention.py`, `models/moe.py` or the cell's file: it pins
the memory rung.
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

from dlrover_wuqiong_tpu.ops import block_attention as ba
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

B, T, H, KV, D, L = 1, 8192, 32, 4, 128, 4
KERNELS = ("dwt_fa_bd_fwd", "dwt_fa_bd_bwd")  # no `_dq`, no `_dkv`
LIVE_GB = 13.04  # the step's described reading at the rung taken


@pytest.fixture(scope="module")
def sdar_step(request):
    """`sdar_30b_a3b.steady`'s step — published widths, 6 blocks, 16 of
    128 experts held, an eighth of the vocabulary, the cell's one
    sequence of 8,192 data tokens (16,384 positions), full
    recomputation."""
    return _one_chip_step(request, "sdar_30b_a3b.steady", "sdar_moe")


def test_one_layers_block_attention_compiles_at_the_cells_shape(
        topo, on_tpu, _no_persistent_cache):
    """The attention of ONE layer over `[clean ; noised]`, forward and
    backward, at 2 x 8,192 positions x 32/4 heads of 128 in bfloat16 on
    one TPU device: each of the two kernels once — the forward and ONE
    backward sweep, neither of the pair it replaced — both over the
    plan's 80 steps of (1,024 x 1,024) a pair of heads, the forward's by
    queries, the backward's by keys with the pair's dq and the kv head's
    dk and dv whole in VMEM (79 of its 100 MiB reckoned); no (2T)^2
    array of any type, nothing that holds other ops, temporaries under
    0.6 GB (lse, delta and its product: dk and dv are summed inside the
    kernel, no partial sum leaves it)."""
    one = SingleDeviceSharding(topo.devices[0])
    assert ba.bd_route(T, L, H, KV, D) == "kernel"

    def shape(lanes):
        return jax.ShapeDtypeStruct((B, 2 * T, lanes), jnp.bfloat16,
                                    sharding=one)

    def loss(q, k, v):
        return ba.block_diffusion_attention(q, k, v, H, KV, L).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(H * D), shape(KV * D), shape(KV * D)).compile()
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_\w*?)(?:\.\d+)? = ", text))
    assert calls == dict.fromkeys(KERNELS, 1)
    assert " while(" not in text and " conditional(" not in text
    assert f"[{2 * T},{2 * T}]" not in text
    assert f"{2 * T},{2 * T}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
    assert ba._fit(T, L, None, None) == (1024, 512)
    assert ba._fit(T, L, None, None, "backward") == (1024, 512)
    assert ba.bd_plan(T, 1024)[1] == ba.bd_plan(T, 1024, True)[1] == 80
    assert ba._bwd_vmem(2, 2 * T, D, 2, 1024) == 83_099_648 \
        <= ba._VMEM_LIMIT < ba._bwd_vmem(4, 2 * T, D, 2, 1024)
    assert text.count("s32[400]") >= 2  # both plans: 5 x 80 prefetched


def _live_gb(step) -> float:
    return compiled_memory(step)["live_bytes"] / 1e9


@pytest.mark.slow
def test_sdar_step_fits_one_chip_by_the_rule_and_fills_it(sdar_step):
    """State + temporaries under 90% of the chip's 16 GB at the rung the
    cell's file takes (PR 26's rule), of which 7.75 GB is donated state;
    far over the 25% a cell has to fill."""
    cell, model, step = sdar_step
    assert model.config.num_params() == 645_623_296
    assert (cell["global_batch"], cell["seq_len"]) == (B, T)
    rung = cell["config"]["train"]["memory_rung"]
    live = _live_gb(step)
    assert rung["taken"] == "a"
    assert live == pytest.approx(LIVE_GB, abs=0.05)
    assert live == pytest.approx(rung["live_GB"]["a: depth 6, 1 x 8192"],
                                 abs=0.05)
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.60 * 16 < live < 0.90 * 16
    assert step.memory_analysis().alias_size_in_bytes >= \
        12 * model.config.num_params()


@pytest.mark.slow
def test_sdar_step_holds_its_scopes_kernels_and_a_share_of_experts(
        sdar_step):
    """Every scope the cell's scopes file names is in the compiled step;
    each of the six layers runs the block-masked forward twice (forward,
    recomputed) and the ONE backward kernel once, and `dwt_rope` on q
    and k; no kernel of `ops/flash_attention.py` or
    `ops/sparse_attention.py` is in the step and no (2T)^2 array; a
    share's grouped products run `ops/grouped_matmul.py`'s kernels on
    the 16 held experts of 768, none on the published 128."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = sdar_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("diffusion/noise", "attention/q_proj", "attention/k_proj",
                 "attention/v_proj", "attention/qk_norm",
                 "attention/o_proj", "feed_forward/moe/router",
                 "feed_forward/moe/dispatch", "feed_forward/moe/experts",
                 "feed_forward/moe/combine", "input_norm", "post_attn_norm",
                 "SDAR/head", "loss", "optimizer", "attn_bd"):
        assert any(part in s for s in scopes), part
    assert any("moe/aux" in s for s in scopes)  # the assumed balance term
    assert not any("moe/shared" in s for s in scopes)
    calls = collections.Counter(re.findall(
        r"%(dwt_(?:fa|idx|rope)\w*?)(?:\.\d+)? = ", text))
    layers = 6
    assert calls.pop("dwt_rope") >= 2 * 2 * layers
    assert calls == {"dwt_fa_bd_fwd": 2 * layers, "dwt_fa_bd_bwd": layers}
    assert f"{2 * T},{2 * T}]" not in text
    grouped = _grouped_kernel_calls(text)
    assert grouped and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in grouped.values()), grouped
    assert "[128,2048,768]" not in text and "[128,768,2048]" not in text
    assert " conditional(" not in text and "approx" not in text.lower()


@pytest.mark.slow
def test_every_device_op_of_the_step_has_an_owner(sdar_step):
    _every_device_op_has_an_owner(sdar_step[2])


@pytest.mark.slow
def test_no_fusion_of_the_step_falls_to_the_models_root(sdar_step):
    _no_fusion_falls_to_the_root(sdar_step[2], "SDAR")
