"""`phi4_mini_flash.steady`'s selective scan, its differential attention
and its step, compiled by the TPU's own compiler for a DESCRIBED v5e (no
chip attached), as tests/test_sdar_compile.py does for SDAR's — whose
helpers these tests use.

The scan's pair and one layer's attention at the cell's shape compile in
seconds; the WHOLE step in half a minute, once a run (a module-scoped
fixture): it pins the routes every mixer takes, that no array of T x
d_inner x d_state elements is an operand or a result of any op, that
nothing outside the kernels holds other ops, and the memory rung.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from test_tpu_compile import (  # noqa: F401 — `topo` and the cache switch are fixtures
    _every_device_op_has_an_owner,
    _no_fusion_falls_to_the_root,
    _no_persistent_cache,
    _one_chip_step,
    topo,
)

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import selective_scan as ss
from dlrover_wuqiong_tpu.ops import short_conv
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

B, T, HIDDEN, D_INNER, N, H, KV, D = 1, 16384, 2560, 5120, 16, 40, 20, 64
LIVE_GB = 13.88  # the step's described reading at the rung taken


def _calls(text, prefix="dwt_"):
    """{kernel: custom calls} of a compiled text (an instruction is
    named after its kernel, behind `jvp_` or `transpose_jvp_` where no
    jit of its own names it)."""
    names = (re.search(rf"({prefix}[a-z0-9_]*[a-z0-9])", line.split(" = ")[0])
             for line in text.splitlines() if "custom-call(" in line)
    return collections.Counter(m.group(1) for m in names if m)


def _no_state_by_time(text):
    """No array with T, d_inner AND d_state among its dimensions."""
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        sizes = dims.split(",")
        assert not {str(T), str(D_INNER), str(N)} <= set(sizes), dims


def test_the_scans_pair_compiles_at_the_cells_shape(topo, on_tpu,
                                                    _no_persistent_cache):
    """One layer's scan, forward and backward, at 1 x 16,384 x 5,120
    channels x 16 states on one TPU device: the forward kernel and the
    backward once each, over (512 channels x 256 steps) a grid step; the
    residual is the state entering each of the 64 chunks; B and C reach
    the kernels spread over a lane tile (T x 16 x 128); no T x 5,120 x
    16 array, nothing that holds other ops."""
    one = SingleDeviceSharding(topo.devices[0])
    assert ss.sscan_route(T, D_INNER, N) == ("kernel", 512)
    assert (ss._BLOCK, ss._CHUNK) == (512, 256)
    assert ss._vmem_bytes(N, 512, 256) < ss._VMEM_LIMIT

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(x, dt, a, b_mat, c_mat, d_skip):
        return ss.selective_scan(x, dt, a, b_mat, c_mat, d_skip).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        shape(B, T, D_INNER), shape(B, T, D_INNER, dtype=jnp.float32),
        shape(D_INNER, N, dtype=jnp.float32), shape(B, T, N),
        shape(B, T, N), shape(D_INNER, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert _calls(text) == {"dwt_sscan_fwd": 1, "dwt_sscan_bwd": 1}
    assert " while(" not in text and " conditional(" not in text
    _no_state_by_time(text)
    assert f"f32[{B},{T // 256},{N},{D_INNER}]" in text  # chunk boundaries
    assert f"f32[{B},{T},{N},128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


@pytest.mark.parametrize("window,kernels", [
    (512, {"dwt_fa_win_fwd": 1, "dwt_fa_win_bwd_fused": 1}),
    (None, {"dwt_fa_fwd": 1, "dwt_fa_bwd_fused": 1})],
    ids=["window_512", "full"])
def test_one_layers_two_maps_are_one_call_of_the_two_width_kernels(
        topo, on_tpu, _no_persistent_cache, window, kernels):
    """Differential attention's a1 and a2: ONE call of the kernels on 40
    heads of q and k at 64 and v = [v1 | v2] at 128, on the transposed
    layout (`attention_route`: another v width is always transposed), a
    fused backward; the windowed layer's kernels carry the window's
    name."""
    one = SingleDeviceSharding(topo.devices[0])
    assert fa.attention_route(H, D, 2 * D) == ("transposed", 0)
    assert fa.backward_route(T, T, D, 2 * D, 1, B * H)[0] == "fused"
    assert fa.kernel_lanes(D, 2 * D) == 3 * D  # nothing padded

    def shape(d):
        return jax.ShapeDtypeStruct((B, H, T, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, True, 0.125, window=window
                                  ).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(D), shape(D), shape(2 * D)).compile().as_text()
    assert _calls(text, "dwt_fa_") == kernels
    assert f"[{T},{T}]" not in text


@pytest.fixture(scope="module")
def phi4_step(request):
    """`phi4_mini_flash.steady`'s step — published widths, six of 32
    blocks, an eighth of the tied table, the cell's one sequence of
    16,384 tokens, full recomputation (about half a minute)."""
    return _one_chip_step(request, "phi4_mini_flash.steady", "phi4flash")


def test_phi4_step_fits_one_chip_by_the_rule_and_fills_it(phi4_step):
    """State + temporaries under 90% of the chip's 16 GB at the rung the
    cell's file takes (PR 26's rule), of which 8.37 GB is donated state;
    far over the 25% a cell has to fill."""
    cell, model, step = phi4_step
    assert model.config.num_params() == 697_094_272
    assert (cell["global_batch"], cell["seq_len"]) == (B, T)
    rung = cell["config"]["train"]["memory_rung"]
    live = compiled_memory(step)["live_bytes"] / 1e9
    assert rung["taken"] == "a: 1 x 16384"
    assert live == pytest.approx(LIVE_GB, abs=0.05)
    assert live == pytest.approx(rung["live_GB"][rung["taken"]], abs=0.05)
    assert 0.25 * 16 * 2 ** 30 / 1e9 < 0.60 * 16 < live < 0.90 * 16
    assert step.memory_analysis().alias_size_in_bytes >= \
        12 * model.config.num_params()


def test_phi4_step_takes_every_kernel_route_and_keeps_no_state_by_time(
        phi4_step, on_tpu):
    """Each Mamba layer runs the convolution's and the scan's forward
    kernel twice (forward, recomputed) and their backward once; each of
    the three attention layers ONE forward call a pass and one fused
    backward, the windowed layer's under the window's name; every scope
    the cell's scopes file names is in the step; no T x 5,120 x 16
    array; nothing outside the kernels holds other ops."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = phi4_step
    text = step.as_text()
    assert short_conv.conv_route(T, D_INNER, 4, jnp.bfloat16) == "kernel"
    assert _calls(text) == {
        "dwt_sscan_fwd": 4, "dwt_sscan_bwd": 2,
        "dwt_conv_fwd": 4, "dwt_conv_bwd": 2,
        "dwt_fa_win_fwd": 2, "dwt_fa_win_bwd_fused": 1,
        "dwt_fa_fwd": 4, "dwt_fa_bwd_fused": 2}
    assert " while(" not in text and " conditional(" not in text
    _no_state_by_time(text)
    table = scope_table(text)
    scopes = set(table.values())
    for part in ("mamba/in_proj", "mamba/conv", "mamba/x_proj",
                 "mamba/dt_proj", "mamba/sscan",
                 "mamba/out_proj", "gmu/in_proj", "gmu/out_proj",
                 "attention/qkv_proj", "attention/q_proj",
                 "attention/o_proj", "attention/diff",
                 "feed_forward/gate_proj", "feed_forward/down_proj",
                 "input_norm", "post_mixer_norm", "Phi4Flash/head", "loss",
                 "optimizer"):
        assert any(part in s for s in scopes), part
    for name, scope in table.items():
        if name.startswith("dwt_sscan"):
            assert "mamba/sscan" in scope, (name, scope)
        if name.startswith("dwt_conv"):
            assert "mamba/conv" in scope, (name, scope)
        if name.startswith("dwt_fa_"):
            assert "/attention" in scope, (name, scope)


def test_every_device_op_of_the_step_has_an_owner(phi4_step):
    _every_device_op_has_an_owner(phi4_step[2])


def test_no_fusion_of_the_step_falls_to_the_models_root(phi4_step):
    _no_fusion_falls_to_the_root(phi4_step[2], "Phi4Flash")
