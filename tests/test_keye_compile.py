"""`keye_vl2_30b_a3b.steady`'s sparse attention and its step, compiled by
the TPU's own compiler for a DESCRIBED v5e (no chip attached), as
tests/test_tpu_compile.py does for the other cells — whose helpers these
tests use.

Tier-1 compiles ONE layer's sparse attention — the six kernels of
`ops/sparse_attention.py`, forward and backward — at the cell's shape
(about ten seconds).  The WHOLE step is `slow` (tier-2, `-m slow`): ONE
module-scoped fixture compiles it, once a run, and that takes the TPU
compiler a minute and a half.  Run
`python -m pytest tests/test_keye_compile.py -m slow` after a change to
`models/keye.py`, `models/llama.py`'s attention, `ops/sparse_attention.py`,
`models/moe.py` or the cell's file: it pins the memory rung.
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

from dlrover_wuqiong_tpu.ops import sparse_attention as sa
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

B, T, H, KV, D, HI, DI, TOPK = 1, 16384, 32, 4, 128, 16, 64, 2048
KERNELS = ("dwt_idx_scores", "dwt_idx_select", "dwt_fa_sp_fwd",
           "dwt_idx_kl", "dwt_fa_sp_bwd_fused", "dwt_idx_bwd")


@pytest.fixture(scope="module")
def keye_step(request):
    """`keye_vl2_30b_a3b.steady`'s step — published widths, 6 blocks, 16
    of 128 experts held, an eighth of the vocabulary, the cell's one
    sequence of 16,384 tokens, full recomputation."""
    return _one_chip_step(request, "keye_vl2_30b_a3b.steady", "keye_vl2")


def test_one_layers_sparse_attention_compiles_at_the_cells_shape(
        topo, on_tpu, _no_persistent_cache):
    """Scores, choice, attention over the choice and the index term of
    ONE layer, forward and backward, at (1, 16384) x 32/4 heads of 128
    and 16 indexer heads of 64 in bfloat16 on one TPU device: each of the
    six kernels once (the attention's backward ONE fused sweep), the
    (T x T) float32 scores' buffer reused for the index term's cotangent
    (no second one), nothing that holds other ops,
    and temporaries under 2.3 GB (2.10 as compiled: 1.07 of scores, 0.27 of mask, the operands' gradients)."""
    one = SingleDeviceSharding(topo.devices[0])
    assert sa.sparse_route(T, D, DI) == "kernel"

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(q, k, v, q_idx, k_idx, w):
        o, kl, _ = sa.sparse_attention(q, k, v, q_idx, k_idx, w, TOPK)
        return o.astype(jnp.float32).sum() + kl

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        shape((B, T, H, D)), shape((B, T, KV, D)), shape((B, T, KV, D)),
        shape((B, T, HI, DI)), shape((B, T, DI)),
        shape((B, T, HI), jnp.float32)).compile()
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"%(dwt_\w*?)(?:\.\d+)? = ", text))
    assert calls == dict.fromkeys(KERNELS, 1)
    assert " while(" not in text and " conditional(" not in text
    assert "approx" not in text.lower() and " sort(" not in text
    written = re.findall(r"= f32\[1,16384,16384\]\S* (\S+?)\(", text)
    assert sorted(set(written)) == ["custom-call", "get-tuple-element"]
    assert compiled.memory_analysis().temp_size_in_bytes < 2.3e9


def _live_gb(step) -> float:
    return compiled_memory(step)["live_bytes"] / 1e9


LIVE_GB = 13.74  # the step's described reading at rung (c)


@pytest.mark.slow
def test_keye_step_fits_one_chip_by_the_rule_and_fills_it(keye_step):
    """State + temporaries under 90% of the chip's 16 GB at rung (c),
    depth 6 at one sequence of 16,384 tokens (PR 26's rule), of which
    7.91 GB is donated state: 13.74 GB live, held here; the cell's file
    keeps every rung's reading, (a) and (b) over at 16.6 and 16.5.  Far
    over the 25% a cell has to fill."""
    cell, model, step = keye_step
    assert model.config.num_params() == 659_190_016
    assert (cell["global_batch"], cell["seq_len"]) == (B, T)
    rung = cell["config"]["train"]["memory_rung"]
    live = _live_gb(step)
    assert rung["taken"] == "c"
    assert live == pytest.approx(LIVE_GB, abs=0.05)
    assert live == pytest.approx(rung["live_GB"]["c: depth 6, 1 x 16384"],
                                 abs=0.05)
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.65 * 16 < live < 0.90 * 16
    assert step.memory_analysis().alias_size_in_bytes >= \
        12 * model.config.num_params()


@pytest.mark.slow
def test_keye_step_holds_its_scopes_kernels_and_a_share_of_experts(
        keye_step):
    """Every scope the cell's scopes file names is in the compiled step;
    each of the six layers runs the scores', the choice's and the
    attention's forward kernels twice (forward, recomputed), the KL
    term's twice, the two backward kernels once (the attention's ONE
    fused sweep, the scores'), and `dwt_rope` on q,
    k and the indexer's two; no kernel of `ops/flash_attention.py` is in
    the step; a share's grouped products run `ops/grouped_matmul.py`'s
    kernels on the 16 held experts of 768, none on the published 128."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = keye_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("sparse_attn/index/wq_idx", "sparse_attn/index/wk_idx",
                 "sparse_attn/index/w_proj", "sparse_attn/index/scores",
                 "sparse_attn/select", "sparse_attn/attend",
                 "sparse_attn/index_loss", "sparse_attn/index_loss/scores",
                 "sparse_attn/counters", "attention/q_proj",
                 "attention/k_proj", "attention/v_proj", "attention/qk_norm",
                 "attention/o_proj", "feed_forward/moe/router",
                 "feed_forward/moe/dispatch", "feed_forward/moe/experts",
                 "feed_forward/moe/combine", "input_norm", "post_attn_norm",
                 "Keye/head", "loss", "optimizer", "attn_sparse"):
        assert any(part in s for s in scopes), part
    assert any("moe/aux" in s for s in scopes)  # the assumed balance term
    assert not any("moe/shared" in s for s in scopes)
    calls = collections.Counter(re.findall(
        r"%(dwt_(?:fa|idx|rope)\w*?)(?:\.\d+)? = ", text))
    layers = 6
    # q, k, the indexer's heads and its key, forward and recomputed at
    # the least (the backward's depend on what takes a cotangent)
    assert calls.pop("dwt_rope") >= 4 * 2 * layers
    assert calls == {
        "dwt_idx_scores": 2 * layers, "dwt_idx_select": 2 * layers,
        "dwt_fa_sp_fwd": 2 * layers, "dwt_idx_kl": 2 * layers,
        "dwt_fa_sp_bwd_fused": layers, "dwt_idx_bwd": layers}
    grouped = _grouped_kernel_calls(text)
    assert grouped and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in grouped.values()), grouped
    assert "[128,2048,768]" not in text and "[128,768,2048]" not in text
    assert " conditional(" not in text and "approx" not in text.lower()


@pytest.mark.slow
def test_every_device_op_of_the_step_has_an_owner(keye_step):
    _every_device_op_has_an_owner(keye_step[2])


@pytest.mark.slow
def test_no_fusion_of_the_step_falls_to_the_models_root(keye_step):
    _no_fusion_falls_to_the_root(keye_step[2], "Keye")
