"""The gated delta rule's Pallas kernels (`ops/delta_rule.py`: a decay
a head `dwt_gdr_fwd`, `dwt_gdr_bwd`; a decay a key CHANNEL `dwt_kda_fwd`,
`dwt_kda_bwd`) in interpret mode on the CPU, against the chunked
`jax.numpy` form of the same equations AND against the recurrence one
step at a time: the output and the gradient of every operand at the
cells' widths (keys of 96, values of 192; 128 | 128 for either form)
and at the nano model's (8 and 24), chunks of 64 and of 16, one chunk a
grid step and several side by side, more than two steps (the carried
state, forward and in reverse), one block of heads and several, two
batch rows.
What the described-`v5e` compile cannot see (results), as it sees what
this cannot (tiling, VMEM): tests/test_olmo_hybrid_compile.py and
tests/test_bailing_hybrid_compile.py.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from dlrover_wuqiong_tpu.ops import delta_rule as dr

NAMES = ["o", "dq", "dk", "dv", "dg", "dbeta"]

# (T, H, dk, dv, chunk, heads a grid step, chunks a grid step)
CASES = {
    "cell_widths_C64_three_steps_of_one": (192, 2, 96, 192, 64, 2, 1),
    "cell_widths_C64_two_steps_of_two": (256, 2, 96, 192, 64, 2, 2),
    "cell_widths_C16_three_blocks": (64, 3, 96, 192, 16, 1, 2),
    "nano_widths_C16_one_step_of_four": (64, 3, 8, 24, 16, 3, 4),
    "nano_widths_C64_two_blocks": (128, 4, 8, 24, 64, 2, 2),
    # 128 | 128 under one decay a head, four heads a grid step: what a
    # mixer of 32 value heads hands the scalar pair (PR 66)
    "grouped_cell_widths_C64_two_steps_of_two": (256, 4, 128, 128, 64, 4,
                                                 2),
    # a decay a key channel (the name says so: `_channel`)
    "channel_cell_widths_C64_three_steps_of_one": (192, 2, 128, 128, 64, 2,
                                                   1),
    "channel_cell_widths_C64_three_steps_of_two": (384, 2, 128, 128, 64, 1,
                                                   2),
    "channel_nano_widths_C16_two_steps_of_four": (128, 3, 8, 24, 16, 3, 4),
    "channel_nano_widths_C16_three_blocks": (64, 3, 8, 24, 16, 1, 2),
}
BOUND = -5.0  # Ling's `kda_lower_bound`


def _channel(case):
    return case.startswith("channel")


def draw(seed, t, h, dk, dv, b=2, agree=0.0, beta_top=2.0, channel=False):
    """Operands as the mixer hands them over (tests/test_delta_rule.py's
    draw, which that file's oracle pins to one shape, at any shape); with
    `channel` as the KDA mixer does: g (b, T, H, dk) in (`BOUND`, 0), the
    write gate in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk)) \
        + agree * jax.random.normal(ks[5], (b, 1, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    if channel:
        g = BOUND * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, dk)))
        beta_top = min(beta_top, 1.0)
    else:
        g = -0.5 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = beta_top * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _chunked_form(channel):
    return dr._chunked_channel if channel else dr._chunked


def _value_and_grads(fn, args):
    @jax.jit
    def both(*a):
        return (fn(*a),) + jax.grad(
            lambda *x: jnp.sum(jnp.sin(fn(*x))), argnums=tuple(range(5)))(*a)
    return both(*args)


def _kernels(chunk, dtype, hb, n=None):
    return lambda *a: dr._chunk_kernels(*a, chunk, dtype, hb,
                                        interpret=True, chunks_a_step=n)


@functools.lru_cache(maxsize=None)
def _three_ways(case):
    t, h, dk, dv, chunk, hb, n = CASES[case]
    args = draw(0, t, h, dk, dv, channel=_channel(case))
    chunked = _chunked_form(_channel(case))
    with jax.default_matmul_precision("highest"):
        return {
            "kernel": _value_and_grads(_kernels(chunk, jnp.float32, hb, n),
                                       args),
            "chunked": _value_and_grads(
                lambda *a: chunked(*a, chunk, jnp.float32), args),
            "sequential": _value_and_grads(dr.gated_delta_rule_sequential,
                                           args),
        }


def _off(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.abs(want).max()) > 0
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("what", NAMES)
@pytest.mark.parametrize("against", ("chunked", "sequential"))
@pytest.mark.parametrize("case", CASES)
def test_the_kernels_are_the_chunked_form_and_the_recurrence(case, against,
                                                             what):
    """ONE test over shapes, the two oracles, the output and each
    operand's gradient: float32 throughout, so what differs is the order
    of the sums."""
    ways = _three_ways(case)
    i = NAMES.index(what)
    assert _off(ways["kernel"][i], ways[against][i]) < 2e-5


@pytest.mark.parametrize("agree,beta_top,channel", [
    (0.0, 1.0, False), (3.0, 2.0, False), (30.0, 2.0, False),
    (3.0, 1.0, True), (30.0, 1.0, True)])
def test_keys_that_agree_under_a_write_gate_near_two_stay_exact(
        agree, beta_top, channel):
    """tests/test_delta_rule.py's case through the kernel: the solve in
    VMEM is the module's forward substitution in blocks, not the
    nilpotent product, at one chunk of 64 and at the second of two — the
    channel pair's too, under its gate's top of 1."""
    ops = draw(3, 128, 3, 6, 10, agree=agree, beta_top=beta_top,
               channel=channel)
    ops = ops[:3] + (ops[3] * 0.01, ops[4])  # next to no decay
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_kernels(64, jnp.float32, 3))(*ops)
        want = dr.gated_delta_rule_sequential(*ops)
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(want).max()))


def _tile(what, chunk, n):
    """A strictly lower (R x R) L of n chunks: keys on the unit sphere
    under a write gate in (0, 2); keys that all but AGREE under a gate of
    1.99; or nothing."""
    size = n * chunk
    ks = jax.random.split(jax.random.PRNGKey(chunk + n), 3)
    k = jax.random.normal(ks[0], (size, 24))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[1], (size, 1)))
    if what == "agree":
        k, beta = k + 30.0 * jax.random.normal(ks[2], (1, 24)), 1.99
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    rows, cols = np.arange(size)[:, None], np.arange(size)[None, :]
    strict = (rows > cols) & (rows // chunk == cols // chunk)
    with jax.default_matmul_precision("highest"):
        low = jnp.where(strict, beta * (k @ k.T), 0.0)
    return jnp.zeros_like(low) if what == "zero" else low


@pytest.mark.parametrize("what", ("random", "agree", "zero"))
@pytest.mark.parametrize("n", (1, 2), ids=("one_chunk", "two_chunks"))
@pytest.mark.parametrize("chunk", (16, 32, 64))
def test_a_tiles_solve_is_the_block_substitution_in_float32(chunk, n, what):
    """`_solve` by itself on an (R x R) tile, the rounds `solve_rounds`
    hands the vector units among them, against `_unit_lower_inverse` with
    float32 products: within a few float32 ulps of the largest entry at
    random keys (the same formula, the sums in another order) and exactly
    I at L = 0.  Where a chunk's keys agree under a gate near 2 the
    inverse is the badly conditioned one whose nilpotent form overflows
    (L^32 passes 1e27): there BOTH forms stand off the float64 inverse by
    tens of ulps, the tile's by no more than the oracle's does — the form
    is still the substitution, and no product of it runs below float32."""
    low = _tile(what, chunk, n)

    def kernel(low_ref, out_ref):
        out_ref[...] = dr._solve(low_ref[...], dr._masks(chunk, n)[2])

    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda x: pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True)(x))(low))
        want = np.asarray(dr._unit_lower_inverse(low))
    ulp = float(np.spacing(np.float32(np.abs(want).max())))
    if what == "agree":
        exact = np.linalg.inv(np.eye(n * chunk) + np.asarray(low, np.float64))
        assert np.abs(exact).max() < 2.0 and np.isfinite(got).all()
        assert np.abs(got - exact).max() \
            <= 3 * np.abs(want - exact).max() + 4 * ulp
    else:
        assert np.abs(got - want).max() <= (4 * ulp if what == "random" else 0)


@pytest.mark.parametrize("what", NAMES)
@pytest.mark.parametrize("g_step", [BOUND, dr.CHANNEL_DECAY_FLOOR])
def test_every_channel_at_the_bound_and_at_the_floor_stays_exact(g_step,
                                                                 what):
    """g AT the published bound on every channel and step, and at
    `CHANNEL_DECAY_FLOOR`: a chunk of 64 decays by e^-320 and e^-512, and
    the sub-blocks' MIDDLE reference keeps every exponent inside +-64, so
    the kernels' output and gradients are finite and the recurrence's
    (a reference at a block's first step loses small key entries to
    flushed denormals: PR 57; `_chunked_channel`'s dg is 3.1 and 2.0
    times further from the recurrence's here than the kernels')."""
    ways = _at_a_constant_decay(g_step)
    i = NAMES.index(what)
    got, want = ways["kernel"][i], ways["sequential"][i]
    assert bool(jnp.isfinite(got).all())
    # dg is what is left of k o dk's and q o dq's terms, which all but
    # cancel where nothing outlives a step: its error is theirs
    scale = jnp.abs(ways["sequential"][2 if what == "dg" else i]).max()
    assert float(jnp.abs(got - want).max() / scale) < 2e-5


@functools.lru_cache(maxsize=None)
def _at_a_constant_decay(g_step):
    q, k, v, g, beta = draw(5, 128, 2, 16, 24, b=1, channel=True)
    args = (q, k, v, jnp.full_like(g, g_step), beta)
    with jax.default_matmul_precision("highest"):
        return {"kernel": _value_and_grads(_kernels(64, jnp.float32, 2),
                                           args),
                "sequential": _value_and_grads(
                    dr.gated_delta_rule_sequential, args)}


@pytest.mark.parametrize("what", NAMES)
def test_channels_that_decay_alike_are_the_scalar_pairs_answer(what):
    """A channel form whose channels all carry one value IS the form a
    head: `dwt_kda_*` on g broadcast over dk against `dwt_gdr_*` on g,
    the output and every gradient (dg summed over a head's channels)."""
    i = NAMES.index(what)
    got, want = (x[i] for x in _alike())
    if what == "dg":
        got = got.sum(-1)
    assert _off(got, want) < 2e-5


@functools.lru_cache(maxsize=None)
def _alike():
    q, k, v, g, beta = draw(6, 128, 2, 32, 64)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    fn = _kernels(64, jnp.float32, 2)
    with jax.default_matmul_precision("highest"):
        return (_value_and_grads(fn, (q, k, v, wide, beta)),
                _value_and_grads(fn, (q, k, v, g, beta)))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    `pallas_call`'s kernel, a `pjit`'s body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def _dots(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"]


def _kernel_bodies(jaxpr):
    return [e.params["jaxpr"] for e in _eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("channel", (False, True),
                         ids=("a_head", "a_channel"))
@pytest.mark.parametrize("phase", ("forward", "backward"))
def test_the_statistics_stay_float32_under_a_bfloat16_dtype(phase, channel):
    """`dtype` rounds the operands the chunked form rounds — eight
    products of the forward kernel a head's decay, seven a channel's (one
    sub-block's product makes L and P both) — and nothing else: every
    product accumulates in float32, the solve's (two a round that
    `solve_rounds` leaves on the MXU; the other rounds are float32
    multiply-adds, no product) and its cotangent's take float32 operands
    at the highest precision, the carried state's scratch, the
    saved states and the output are float32."""
    chunk = 16
    exact_a_solve = 2 * len(dr.solve_rounds(chunk)["mxu"])
    ops = draw(7, 32, 2, 8, 24, channel=channel)

    def fn(*a):
        return dr._chunk_kernels(*a, chunk, jnp.bfloat16, 1, interpret=True,
                                 chunks_a_step=1)

    if phase == "forward":
        jaxpr = jax.make_jaxpr(fn)(*ops)
        assert jaxpr.out_avals[0].dtype == jnp.float32
        want_rounded, want_exact = 7 if channel else 8, exact_a_solve
    else:
        jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](
            jnp.ones(ops[2].shape, jnp.float32)))(*ops)
        # the forward kernel again (it saves the states), then the
        # backward: the rebuilt tiles' less P U and Kd^T U (and, a
        # channel's, Q S_in: its cotangent needs no product of it), 16 of
        # the cotangents' (a channel's: 12 and the sub-block's two); the
        # solve again and the two of -T^T dT T^T
        want_rounded = 7 + 4 + 14 if channel else 8 + 6 + 16
        want_exact = 2 * exact_a_solve + 2
    dots = _dots(jaxpr.jaxpr)
    rounded = [d for d in dots
               if all(v.aval.dtype == jnp.bfloat16 for v in d.invars)]
    exact = [d for d in dots if d not in rounded]
    assert (len(rounded), len(exact)) == (want_rounded, want_exact)
    for d in dots:
        assert d.outvars[0].aval.dtype == jnp.float32
    for d in exact:
        assert all(v.aval.dtype == jnp.float32 for v in d.invars)
        assert d.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
    # a kernel's last argument is the carried state's scratch (a
    # channel's is held transposed)
    scratch = [k.invars[-1].aval for k in _kernel_bodies(jaxpr.jaxpr)]
    assert len(scratch) == (1 if phase == "forward" else 2)
    for aval in scratch:
        assert aval.dtype == jnp.float32
        assert aval.shape == ((1, 24, 8) if channel else (1, 8, 24))


@pytest.mark.parametrize("channel", (False, True),
                         ids=("a_head", "a_channel"))
def test_the_rounded_operands_are_the_chunked_forms(channel):
    """Under a bfloat16 `dtype` the kernel route is the chunked form to
    bfloat16's rounding over several chunks (the carry is applied, where
    the chunked form composes (dk x dk) transitions), and at ONE chunk —
    the same operands rounded at the same places, no entering state, the
    sums in the same order — bit for bit.  (One batch row of three heads:
    a shape whose bfloat16 products the CPU runs.)"""
    ops = draw(8, 64, 3, 8, 24, b=1, channel=channel)
    for chunk, tol in ((16, 2.0 ** -7), (64, 0.0)):
        got = jax.jit(_kernels(chunk, jnp.bfloat16, 3))(*ops)
        want = jax.jit(lambda *a: _chunked_form(channel)(
            *a, chunk, jnp.bfloat16))(*ops)  # noqa: B023
        assert _off(got, want) <= tol, chunk


@pytest.mark.parametrize("heads,hb", [(15, 5), (30, 5), (3, 3), (4, 4),
                                      (7, 1), (16, 4)])
def test_a_block_of_heads_divides_the_heads_evenly(heads, hb):
    """The plan is the kernel's own, from shapes: a grid step takes the
    largest divisor of the heads up to five, so no block is ragged — a
    block that does not divide the heads is never asked of the kernels,
    and `_kernel_operands` could not lay one out."""
    assert dr._heads_block(heads) == hb and heads % hb == 0
    ops = draw(1, 32, 3, 8, 24, b=1)
    with pytest.raises(TypeError, match="reshape"):
        dr._chunk_kernels(*ops, 16, jnp.float32, 2, interpret=True)


def test_the_kernels_block_keys_and_values_at_their_own_widths():
    """Keys of 96 and values of 192 are not padded in HBM: the kernels'
    operands are the head-major arrays at dk and dv, the saved states
    (dk x dv), and `product_lanes` says so."""
    ops = draw(2, 128, 2, 96, 192, b=1)
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(_kernels(
        64, jnp.bfloat16, 2), *a))(*ops)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
              for v in (*eqn.invars, *eqn.outvars) if hasattr(v, "aval")}
    assert (1, 2, 128, 96) in shapes and (1, 2, 128, 192) in shapes
    assert (1, 2, 2, 96, 192) in shapes                  # entering states
    assert {s[-1] for s in shapes if s[:-1] == (1, 2, 128)} == {96, 192}
    assert dr.product_lanes(96, 192) == (288, 288)


def test_the_scalar_pairs_traced_gradient_is_the_one_olmos_cell_was_read_on():
    """The channel pair shares the scalar pair's helpers and wrappers
    (`_masks`, `_solve`, `_specs`, the jitted calls, the `custom_vjp`):
    the gradient of `_chunk_kernels` at `olmo_hybrid_7b.steady`'s shape,
    five heads a step, traces to ONE pinned text, so that cell's program
    does not move with a change meant for another pair's.  PR 57's text
    (4,816 lines, sha256 65f20916...a9f8) held through PR 58's channel
    pair; PR 69 moved it ON PURPOSE — `_solve` itself changed for all
    four kernels (blocks of 8 steps by substitution on the vector units,
    the rounds left on the MXU over the lower-half rows: 8,558 lines) —
    and the cell was read again on this text (PERF.md section 6, PR
    69)."""
    t, h, dk, dv = 8192, 15, 96, 192
    shapes = [jax.ShapeDtypeStruct(dims, dtype) for dims, dtype in (
        ((1, t, h, dk), jnp.float32), ((1, t, h, dk), jnp.float32),
        ((1, t, h, dv), jnp.bfloat16), ((1, t, h), jnp.float32),
        ((1, t, h), jnp.float32))]
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(dr._chunk_kernels(*a, 64, jnp.bfloat16, 5)),
        argnums=(0, 1, 2, 3, 4)))(*shapes))
    assert hashlib.sha256((text + "\n").encode()).hexdigest() == \
        "74036b8b3d39e50c6c81740675c359d93d0620c85da043980ef211165d36ca6e"
