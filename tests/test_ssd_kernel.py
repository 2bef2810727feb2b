"""The Mamba-2 scan's Pallas kernels (`ops/ssd.py`: `dwt_ssd_fwd`,
`dwt_ssd_bwd`) in interpret mode on the CPU, against the plain
`jax.numpy` form of the same chunked algorithm AND against the
sequential recurrence (`benchmark/reference_nemotron_h.recurrence`), at
shrunk shapes of both cells' kinds: ONE group of several heads (granite:
the blocks of a group share C B^T across grid steps) and several groups
of several heads (the other hybrid: a block a group), two chunk sizes,
more than two chunks (the carried state, forward and in reverse), two
batch rows.  What the described-`v5e` compiles cannot see (results), as
they see what this cannot (tiling, VMEM): tests/test_tpu_compile.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_nemotron_h as ref
from dlrover_wuqiong_tpu.ops import ssd

NAMES = ["y", "dx", "ddlt", "da", "dB", "dC", "dD"]
SEQ = 96

# (H, P, G, N, chunk, heads a grid step)
CASES = {
    "one_group_two_blocks_L16": (8, 64, 1, 16, 16, 4),
    "one_group_two_blocks_L32": (8, 64, 1, 16, 32, 4),
    "one_group_a_slab_a_block_L32": (4, 64, 1, 8, 32, 2),
    "two_groups_a_block_each_L16": (8, 64, 2, 16, 16, 4),
    "two_groups_a_block_each_L32": (8, 64, 2, 16, 32, 4),
    "two_groups_two_blocks_each_L16": (8, 64, 2, 8, 16, 2),
    "a_head_a_slab_L16": (4, 128, 2, 8, 16, 2),
    "a_head_a_slab_one_group_L32": (4, 128, 1, 8, 32, 2),
}


def _inputs(h, p, g, n, t=SEQ, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dlt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    b_mat = jax.random.normal(ks[3], (b, t, g, n))
    c_mat = jax.random.normal(ks[4], (b, t, g, n))
    return x, dlt, a, b_mat, c_mat, jax.random.normal(ks[5], (h,))


def _value_and_grads(fn, args):
    def scalar(*a):
        return jnp.sum(jnp.sin(fn(*a)))

    @jax.jit
    def both(*a):
        return (fn(*a),) + jax.grad(scalar, argnums=tuple(range(6)))(*a)
    return both(*args)


@functools.lru_cache(maxsize=None)
def _three_ways(case, dtype_name):
    h, p, g, n, chunk, hb = CASES[case]
    dtype = jnp.dtype(dtype_name)
    args = _inputs(h, p, g, n)
    with jax.default_matmul_precision("highest"):
        return {
            "kernel": _value_and_grads(
                lambda *a: ssd._scan_kernels(*a, chunk, dtype, hb,
                                             interpret=True), args),
            "plain": _value_and_grads(
                lambda *a: ssd.ssd_scan_plain(*a, chunk=chunk, dtype=dtype),
                args),
            "sequential": _value_and_grads(ref.recurrence, args),
        }


def _off(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.abs(want).max()) > 0
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("oracle", ["plain", "sequential"])
@pytest.mark.parametrize("i", range(7), ids=NAMES)
@pytest.mark.parametrize("case", CASES)
def test_kernels_in_float32_are_the_scan(case, i, oracle):
    """Values and every gradient, within the 1e-5 tests/test_nemotron_h.py
    holds the plain scan to; a gradient against the step-by-step
    recurrence within 3e-5, which is where the plain form reads too at
    the cases of two or four heads (a sum over 96 steps in float32 beside
    a small largest entry)."""
    out = _three_ways(case, "float32")
    limit = 3e-5 if oracle == "sequential" and i else 1e-5
    assert _off(out["kernel"][i], out[oracle][i]) < limit


@pytest.mark.parametrize("i", range(7), ids=NAMES)
@pytest.mark.parametrize("case", CASES)
def test_kernels_in_bfloat16_round_what_the_plain_form_rounds(case, i):
    """`dtype=bfloat16` against the plain form at the same dtype.  The
    values agree to a few roundings of an entering state (its float32
    bits differ: a running product against one (chunks x chunks)
    product); the gradients to bf16's rounding of the cotangents, which
    the kernels round before every product as the TPU's default
    precision does and the CPU's plain form does not.  Against the
    float32 recurrence the kernels are no further off than twice the
    plain form at bf16 is (sin' of y makes a gradient here sensitive);
    a decay or a state rounded to bf16 would be off by far more."""
    out = _three_ways(case, "bfloat16")
    assert _off(out["kernel"][i], out["plain"][i]) < (2e-4 if i == 0
                                                      else 2e-2)
    exact = _three_ways(case, "float32")["sequential"][i]
    assert _off(out["kernel"][i], exact) < \
        2 * _off(out["plain"][i], exact) + 1e-3


def test_route_takes_the_same_arguments_as_the_scan():
    """`ssd_scan` off the TPU is the plain form, bit for bit."""
    args = _inputs(8, 64, 2, 16)
    assert ssd.scan_route(8, 64, 2, 16, 16, SEQ) == ("plain", 0)
    np.testing.assert_array_equal(
        np.asarray(ssd.ssd_scan(*args, chunk=16)),
        np.asarray(ssd.ssd_scan_plain(*args, chunk=16)))


@pytest.mark.parametrize("fn", ["ssd_scan", "ssd_scan_plain"])
def test_both_entries_refuse_a_ragged_last_chunk(fn):
    args = _inputs(8, 64, 2, 16, t=40)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        getattr(ssd, fn)(*args, chunk=16)


def test_mask_is_applied_before_the_exp():
    """Decay rates under which exp(cum_s - cum_t) of a masked pair
    overflows float32: a mask applied after the exp would give inf * 0
    = NaN, forward and backward."""
    h, p, g, n, chunk, hb = CASES["one_group_two_blocks_L32"]
    x, dlt, a, b_mat, c_mat, d = _inputs(h, p, g, n)
    a = jnp.full_like(a, -40.0)         # cum falls ~5 a step: -160 a chunk
    args = (x, dlt + 0.1, a, b_mat, c_mat, d)
    assert float(jnp.exp(-jnp.cumsum((dlt + 0.1) * a[0], 1)[:, chunk - 1]
                         ).max()) == np.inf
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(lambda *v: ssd._scan_kernels(
            *v, chunk, jnp.float32, hb, interpret=True), args)
        want = _value_and_grads(ref.recurrence, args)
    assert _off(got[0], want[0]) < 1e-5
    # d(a) is a difference of near-equal sums at such rates: the plain
    # form reads 4e-4 off the recurrence there too
    for u, v in zip(got, want):
        assert bool(jnp.isfinite(u).all())
        assert _off(u, v) < 2e-3


def test_carried_state_is_float32_of_operands_rounded_once():
    """The roundings the configuration fixes, bit for bit on the one
    intermediate the forward kernel writes out: the state ENTERING the
    second chunk.  B is zero but at ONE step s0 of the first chunk, so
    every entry of that state is a single product — no sum whose order
    could move a bit: float32(bf16(B[s0, n])) * float32(bf16(dlt x *
    exp(cum_L - cum_s0))), the decay to the chunk's end in float32, the
    product rounded to bf16 ONCE, the state itself never rounded.  The
    third chunk's entering state is that times exp(total), in float32."""
    h, p, g, n, chunk, hb = 4, 64, 1, 8, 16, 2
    x, dlt, a, b_mat, c_mat, d = _inputs(h, p, g, n, t=48, b=1)
    s0 = 5
    b_mat = b_mat * (jnp.arange(48) == s0)[None, :, None, None]
    xs, dl_col, cc, cr, bm, cm, d_vec = ssd._kernel_operands(
        x, dlt, a, b_mat, c_mat, d, chunk, jnp.bfloat16, hb)
    _, states = ssd._ssd_forward_pallas(
        xs, dl_col, cc, cr, bm, ssd._transposed(bm, g), cm, d_vec,
        chunk=chunk, p=p, hb=hb, dtype=jnp.bfloat16, save=True,
        interpret=True)
    assert states.dtype == jnp.float32 and states.shape == (1, 3, n, h * p)
    assert not np.asarray(states[0, 0]).any()       # S_0 = 0
    cum = jnp.cumsum(dlt[0, :chunk] * a, 0)                     # (L, H)
    to_end = jnp.exp(cum[-1] - cum[s0])                         # (H,)
    w = (x[0, s0] * dlt[0, s0][:, None] * to_end[:, None]).astype(
        jnp.bfloat16).astype(jnp.float32)                       # (H, P)
    b_row = b_mat[0, s0, 0].astype(jnp.bfloat16).astype(jnp.float32)
    want = b_row[:, None] * w.reshape(1, h * p)                 # (N, H*P)
    np.testing.assert_array_equal(np.asarray(states[0, 1]),
                                  np.asarray(want))
    # a state rounded to bf16, or a bf16 decay, would differ in most bits
    assert float(jnp.abs(want.astype(jnp.bfloat16).astype(jnp.float32)
                         - want).max()) > 0
    total = jnp.cumsum(dlt[0, chunk:2 * chunk] * a, 0)[-1]      # (H,)
    np.testing.assert_array_equal(
        np.asarray(states[0, 2]),
        np.asarray(want * jnp.repeat(jnp.exp(total), p)[None, :]))


def test_masked_product_rounds_cb_times_decay_once():
    """One chunk, so nothing enters: y - D x is the masked product
    alone.  With x zero but at ONE step s0, y_t for t >= s0 is a single
    product a head: float32(bf16(C_t . B_s0 * exp(cum_t - cum_s0))) *
    float32(bf16(dlt_s0 x_s0)) — C B^T o decay formed in float32 and
    rounded ONCE, dlt x rounded once; C . B is a sum over N, so B and C
    hold small integers (exact in bf16, their dot exact in float32)."""
    h, p, g, n, chunk, hb = 4, 64, 2, 8, 32, 2
    x, dlt, a, b_mat, c_mat, d = _inputs(h, p, g, n, t=chunk, b=1)
    s0 = 7
    x = x * (jnp.arange(chunk) == s0)[None, :, None, None]
    b_mat, c_mat = jnp.round(2 * b_mat), jnp.round(2 * c_mat)
    y = ssd._scan_kernels(x, dlt, a, b_mat, c_mat, 0 * d, chunk,
                          jnp.bfloat16, hb, interpret=True)
    cum = jnp.cumsum(dlt[0] * a, 0)                             # (L, H)
    decay = jnp.exp(cum - cum[s0])                              # (L, H)
    cb = jnp.einsum("tgn,gn->tg", c_mat[0], b_mat[0, s0])       # exact
    m = (jnp.repeat(cb, h // g, axis=1) * decay).astype(jnp.bfloat16)
    xdt = (x[0, s0] * dlt[0, s0][:, None]).astype(jnp.bfloat16)
    want = m.astype(jnp.float32)[:, :, None] * xdt.astype(jnp.float32)
    want = want * (jnp.arange(chunk) >= s0)[:, None, None]
    np.testing.assert_array_equal(np.asarray(y[0]), np.asarray(want))
    assert float(jnp.abs(want).max()) > 0
