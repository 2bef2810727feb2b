"""The selective scan's Pallas kernels (`ops/selective_scan.py`:
`dwt_sscan_fwd`, `dwt_sscan_bwd`) in interpret mode on the CPU, against
the plain `jax.numpy` route AND against the step-by-step recurrence, at
shrunk shapes: one channel block and several (dB and dC sum over a
chunk's blocks, dA over the chunks), two chunk lengths, more than two
chunks (the carried state, forward and in reverse), two batch rows,
bfloat16 operands.  What the described-`v5e` compiles cannot see
(results), as they see what this cannot (tiling, VMEM):
tests/test_phi4flash_compile.py."""

import functools

import jax
import jax.numpy as jnp
import pytest

from dlrover_wuqiong_tpu.ops import selective_scan as ss
from tests.test_selective_scan import (
    NAMES, inputs, off, step_by_step, value_and_grads)

SEQ = 48
# (channels, states, chunk, channels a grid step)
CASES = {
    "one_block_L16": (128, 16, 16, 128),
    "two_blocks_L16": (256, 16, 16, 128),
    "two_blocks_L8": (256, 16, 8, 128),
    "a_block_of_two_lane_tiles_L24": (512, 8, 24, 256),
    "one_chunk": (256, 16, 48, 256),
}


def _kernels(chunk, block):
    def fn(x, dt, a, b_mat, c_mat, d_skip):
        return ss._scan_kernels(x, dt, a, b_mat, c_mat, chunk, block,
                                interpret=True) \
            + d_skip * x.astype(jnp.float32)
    return fn


@functools.lru_cache(maxsize=None)
def _three_ways(case, dtype_name):
    d, n, chunk, block = CASES[case]
    args = inputs(t=SEQ, d=d, n=n, dtype=jnp.dtype(dtype_name))
    return {
        "kernel": value_and_grads(_kernels(chunk, block), args),
        "plain": value_and_grads(functools.partial(
            ss.selective_scan_plain, chunk=chunk), args),
        "sequential": value_and_grads(step_by_step, args)}


@pytest.mark.parametrize("oracle", ["plain", "sequential"])
@pytest.mark.parametrize("i", range(7), ids=NAMES)
@pytest.mark.parametrize("case", CASES)
def test_kernels_in_float32_are_the_scan(case, i, oracle):
    out = _three_ways(case, "float32")
    assert off(out["kernel"][i], out[oracle][i]) < 1e-5


@pytest.mark.parametrize("i", range(7), ids=NAMES)
@pytest.mark.parametrize("case", ["two_blocks_L16",
                                  "a_block_of_two_lane_tiles_L24"])
def test_kernels_read_bfloat16_operands_and_keep_a_float32_state(case, i):
    """x, B and C in bfloat16, read as they are; dx, dB and dC leave in
    bfloat16 (one rounding), everything else float32."""
    out = _three_ways(case, "bfloat16")
    assert out["kernel"][0].dtype == jnp.float32
    assert off(out["kernel"][i], out["sequential"][i]) < (
        1e-5 if i in (0, 2, 3, 6) else 1e-2)


def test_the_backward_keeps_chunk_boundary_states_only():
    """The residuals of the pair: the operands and the state ENTERING
    every chunk, (b, chunks, N, D) — nothing with a time axis AND the
    state axis at full length."""
    d, n, chunk, block = CASES["two_blocks_L16"]
    x, dt, a, b_mat, c_mat, _ = inputs(t=SEQ, d=d, n=n)
    plan = (("chunk", chunk), ("block", block), ("interpret", True))
    _, res = ss._chunks_fwd(x, dt, a.T, ss._spread(b_mat),
                            ss._spread(c_mat), plan)
    shapes = [r.shape for r in res]
    assert shapes[-1] == (2, SEQ // chunk, n, d)
    assert not any(len(s) >= 3 and SEQ in s and d in s and n in s
                   for s in shapes)
