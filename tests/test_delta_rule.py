"""`ops/delta_rule.py`: the chunked gated delta rule against the
recurrence one step at a time — values and the gradient of every operand
— the triangular solve and the chunk-to-chunk carry on their own, and
the route.  CPU, float32, small sizes with dk != dv and a head count
that is no power of two.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import delta_rule as dr

B, T, H, DK, DV = 2, 64, 3, 6, 10
OPERANDS = ("q", "k", "v", "g", "beta")


def draw(seed, t=T, agree=0.0, beta_top=2.0):
    """Operands as the mixer hands them over: q and k of unit length (q
    scaled), a log-decay <= 0, a write gate in (0, beta_top).  `agree`
    mixes ONE direction into every key of a head (keys that agree are
    what makes the solve hard)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, t, H, DK))
    k = jax.random.normal(ks[1], (B, t, H, DK)) \
        + agree * jax.random.normal(ks[5], (B, 1, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(DK)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, t, H, DV))
    g = -0.5 * jax.nn.softplus(jax.random.normal(ks[3], (B, t, H)))
    beta = beta_top * jax.nn.sigmoid(jax.random.normal(ks[4], (B, t, H)))
    return q, k, v, g, beta


@pytest.fixture(scope="module")
def oracle():
    """The sequential recurrence's output and the gradients of a scalar
    of it, once."""
    ops = draw(0)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))
    with jax.default_matmul_precision("highest"):
        out = dr.gated_delta_rule_sequential(*ops)
        grads = jax.grad(
            lambda *a: jnp.sum(dr.gated_delta_rule_sequential(*a) * w),
            argnums=tuple(range(5)))(*ops)
    return ops, w, out, grads


@pytest.mark.parametrize("what", ("value",) + OPERANDS)
@pytest.mark.parametrize("chunk", (4, 16, 64))
def test_the_chunked_form_is_the_recurrence(oracle, chunk, what):
    """ONE test over chunk sizes {4, 16, 64} (T = 64: sixteen chunks, four,
    one) and over the output and each operand's gradient."""
    ops, w, out, grads = oracle
    assert dr.delta_route(T, chunk, H, DK, DV) == "chunked"
    with jax.default_matmul_precision("highest"):
        if what == "value":
            got, want = dr.gated_delta_rule(*ops, chunk=chunk), out
        else:
            i = OPERANDS.index(what)
            got = jax.grad(lambda *a: jnp.sum(
                dr.gated_delta_rule(*a, chunk=chunk) * w), argnums=i)(*ops)
            want = grads[i]
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("agree,beta_top", [(0.0, 1.0), (3.0, 2.0),
                                            (30.0, 2.0)])
def test_keys_that_agree_under_a_write_gate_near_two_stay_exact(agree,
                                                                beta_top):
    """Where a chunk's keys agree and beta nears 2, L's powers explode
    (the nilpotent product's factors) and the inverse stays bounded: the
    block form still is the recurrence, at one chunk of 64."""
    ops = draw(3, agree=agree, beta_top=beta_top)
    ops = ops[:3] + (ops[3] * 0.01, ops[4])  # next to no decay
    with jax.default_matmul_precision("highest"):
        got = dr.gated_delta_rule(*ops, chunk=64)
        want = dr.gated_delta_rule_sequential(*ops)
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_the_solve_is_the_inverse_and_its_cotangent():
    n = 16
    low = jnp.tril(jax.random.normal(jax.random.PRNGKey(1), (5, 3, n, n)),
                   -1)
    with jax.default_matmul_precision("highest"):
        inv = dr._unit_lower_inverse(low)
        want = jnp.linalg.inv(jnp.eye(n) + low)
        np.testing.assert_allclose(inv, want, rtol=1e-4, atol=1e-4)
        w = jax.random.normal(jax.random.PRNGKey(2), low.shape)
        got = jax.grad(lambda m: jnp.sum(dr._unit_lower_inverse(m) * w))(low)
        ref = jax.grad(lambda m: jnp.sum(
            jnp.linalg.inv(jnp.eye(n) + m) * w))(low)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-3)


def test_the_carry_is_the_loop_over_the_chunks_and_so_is_its_gradient():
    b, c, h, dk, dv = 2, 7, 3, 4, 5  # seven chunks: no power of two
    ka, kb, kw = jax.random.split(jax.random.PRNGKey(4), 3)
    a_mat = 0.5 * jax.random.normal(ka, (b, c, h, dk, dk))
    b_mat = jax.random.normal(kb, (b, c, h, dk, dv))
    w = jax.random.normal(kw, (b, c, h, dk, dv))

    def loop(a_mat, b_mat):
        state, out = jnp.zeros((b, h, dk, dv)), []
        for j in range(c):
            out.append(state)
            state = a_mat[:, j] @ state + b_mat[:, j]
        return jnp.stack(out, 1)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(dr._entering(a_mat, b_mat),
                                   loop(a_mat, b_mat), rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda *x: jnp.sum(dr._entering(*x) * w), (0, 1))(
            a_mat, b_mat)
        want = jax.grad(lambda *x: jnp.sum(loop(*x) * w), (0, 1))(
            a_mat, b_mat)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def test_the_route_is_the_shapes_and_a_ragged_sequence_still_runs():
    assert dr.delta_route(8192, 64, 15, 96, 192) == "chunked"  # the CPU
    assert dr.delta_route(64, 64, H, DK, DV) == "chunked"
    assert dr.delta_route(8192 + 32, 64, 15, 96, 192) == "sequential"
    assert dr.delta_route(16, 64, H, DK, DV) == "sequential"
    assert dr.product_lanes(96, 192) == (288, 288)
    ops = draw(5, t=24)  # no multiple of 16: one step at a time
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(
            dr.gated_delta_rule(*ops, chunk=16),
            dr.gated_delta_rule_sequential(*ops))


def test_the_chunked_form_compiles_to_no_op_that_holds_others():
    """No `while`, no `conditional`, forward or backward: the carry is an
    unrolled associative scan (a device trace counts an op that holds
    others beside them)."""
    ops = draw(6)
    text = jax.jit(jax.grad(
        lambda *a: jnp.sum(dr.gated_delta_rule(*a, chunk=4)),
        argnums=tuple(range(5)))).lower(*ops).compile().as_text()
    assert " while(" not in text and " conditional(" not in text


def test_the_statistics_stay_float32_under_a_bfloat16_dtype():
    """`dtype` rounds the operands of the nine within-chunk products and
    nothing else: every product accumulates in float32, the solve and
    the carry take float32 operands at the highest precision, and the
    output is float32.  Read off the lowered program (the CPU runs no
    bfloat16 product)."""
    import re

    ops = draw(7)
    fn = jax.jit(lambda *a: dr.gated_delta_rule(*a, chunk=16,
                                                dtype=jnp.bfloat16))
    assert jax.eval_shape(fn, *ops).dtype == jnp.float32
    dots = [line for line in fn.lower(*ops).as_text().splitlines()
            if "dot_general" in line]
    rounded = [d for d in dots if "xbf16>, tensor" in d]
    exact = [d for d in dots if d not in rounded]
    assert len(rounded) == 9 and len(exact) > 12
    for d in dots:
        assert re.search(r"-> tensor<[0-9x]*xf32>", d), d
    for d in rounded:
        assert d.count("xbf16>") == 2, d
    for d in exact:
        assert "precision = [HIGHEST, HIGHEST]" in d and "bf16" not in d, d
