"""Manifold-constrained hyper-connections (`models/hyper_connection.py`)
at a small size in float32: Sinkhorn's rounds, the coefficients against
the plain reference's (`benchmark/reference_xing4_0.py`), the two mixes
and their written-out backward rules against autodiff of the plain
formula, what the init is, the layout the stream is carried in, and the
counter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing4_0 as ref
from dlrover_wuqiong_tpu.models import hyper_connection as hc

N, D, T = 4, 32, 24
CFG = hc.HyperConnectionConfig(hidden_size=D, lanes=N)


def _init(cfg=CFG):
    return hc.HyperConnection(cfg).init(jax.random.PRNGKey(0))["params"]


def _leaves(seed=0):
    """The init's leaves with a drawn Phi and gains of 0.3: the init's
    own (Phi = 0, gains of 0.01) make every coefficient a constant."""
    leaves = _init()
    return {**leaves, "alpha": jnp.full_like(leaves["alpha"], 0.3),
            "phi": 0.5 * jax.random.normal(jax.random.PRNGKey(seed),
                                           leaves["phi"].shape)}


def _stream(seed=1, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, N, T, D), dtype)


def _lanes(x):
    return [x[:, i] for i in range(N)]


@pytest.mark.parametrize("iters,summed", [(20, True), (1, False)])
def test_h_res_is_doubly_stochastic_after_twenty_rounds_not_after_one(
        iters, summed):
    """Unit-variance logits: rows and columns sum to 1 within 1e-5 after
    20 rounds (what `sinkhorn_err` reads) and are far off after 1, where
    only the rows, normalised last, do."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, N, N, 96))
    h = hc.sinkhorn(logits, iters, 1e-6, (-30.0, 30.0))
    assert float(h.min()) > 0
    err = float(hc.sinkhorn_err(h))
    assert float(jnp.abs(h.sum(2) - 1).max()) < 1e-5  # rows, last
    if summed:
        assert 0 < err < 1e-5
    else:
        assert err > 1e-2


def test_the_clamp_holds_exp_finite():
    logits = jnp.full((1, N, N, 8), 1e4).at[:, 0, 0].set(-1e4)
    h = hc.sinkhorn(logits, 20, 1e-6, (-30.0, 30.0))
    assert bool(jnp.isfinite(h).all())
    assert float(hc.sinkhorn_err(h)) < 1e-4


def test_coefficients_match_the_reference():
    """h_pre, h_post and H_res of a drawn Phi against the reference's,
    which norms vec(X) and then multiplies; the tokens lie on the last
    axis here, behind the lanes."""
    leaves, x = _leaves(), _stream()
    h_pre, h_post, h_res = hc.coefficients(leaves, x, CFG)
    assert h_pre.shape == (2, N, T) and h_res.shape == (2, N, N, T)
    assert h_pre.dtype == h_res.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        w_pre, w_post, w_res = ref.mixing_coefficients(
            _lanes(x), leaves, iters=20, hc_eps=1e-6, res_clamp=(-30, 30),
            eps=1e-6)
    assert float(jnp.abs(w_pre - 0.25).max()) > 0.02  # a dynamic part
    np.testing.assert_allclose(h_pre, w_pre.transpose(0, 2, 1), atol=1e-5)
    np.testing.assert_allclose(h_post, w_post.transpose(0, 2, 1), atol=1e-5)
    np.testing.assert_allclose(h_res, w_res.transpose(0, 2, 3, 1),
                               atol=1e-5)


def _plain_read(h_pre, x):
    return jnp.einsum("bnt,bntd->btd", h_pre, x)


def _plain_write(h_res, h_post, x, y):
    return jnp.einsum("bijt,bjtd->bitd", h_res, x) \
        + h_post[..., None] * y[:, None]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_mixes_and_their_backward_rules_are_the_plain_formulas(dtype):
    """`read` and `write` carry custom rules (sums and stacks of lane
    slices, no pad of a cotangent): value and every gradient against
    autodiff of the einsum form; in bfloat16 the sums run in float32."""
    x = _stream(dtype=dtype)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, T, D), dtype)
    h_pre, h_post, h_res = hc.coefficients(_leaves(), x, CFG)

    def ours(h_pre, h_res, h_post, x, y):
        return jnp.sum(jnp.sin(hc.write(h_res, h_post, x, y)
                               .astype(jnp.float32))) \
            + jnp.sum(jnp.cos(hc.read(h_pre, x).astype(jnp.float32)))

    def plain(h_pre, h_res, h_post, x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.sum(jnp.sin(_plain_write(h_res, h_post, x, y))) \
            + jnp.sum(jnp.cos(_plain_read(h_pre, x)))

    args = (h_pre, h_res, h_post, x, y)
    got, got_g = jax.value_and_grad(ours, argnums=range(5))(*args)
    want, want_g = jax.value_and_grad(plain, argnums=range(5))(*args)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert float(got) == pytest.approx(float(want), rel=tol)
    for g, w in zip(got_g, want_g):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=tol,
            atol=tol * float(jnp.abs(w.astype(jnp.float32)).max()))


def test_a_block_starts_as_a_plain_residual_on_the_lanes_mean():
    """The papers' init (Phi = 0): h_pre = 1/n, h_post = 1, H_res doubly
    stochastic and near the identity — so the lanes' mean goes through
    as x + F(x) would."""
    leaves, x = _init(), _stream()
    assert float(jnp.abs(leaves["phi"]).max()) == 0
    np.testing.assert_allclose(leaves["alpha"], 0.01)
    h_pre, h_post, h_res = hc.coefficients(leaves, x, CFG)
    np.testing.assert_allclose(h_pre, 0.25, atol=1e-6)
    np.testing.assert_allclose(h_post, 1.0, atol=1e-6)
    assert float(h_res[0, 0, 0, 0]) == pytest.approx(0.711, abs=2e-3)
    assert float(h_res[0, 0, 1, 0]) == pytest.approx(0.096, abs=2e-3)
    y = jax.random.normal(jax.random.PRNGKey(3), (2, T, D))
    np.testing.assert_allclose(hc.read(h_pre, x), x.mean(1), atol=1e-6)
    out = hc.write(h_res, h_post, x, y)
    np.testing.assert_allclose(out.mean(1), x.mean(1) + y, atol=1e-5)


def test_the_leaves_are_counted_and_named():
    leaves = _leaves()
    assert {k: v.shape for k, v in leaves.items()} == {
        "phi": (N, D, 24), "alpha": (3,), "b_pre": (N,), "b_post": (N,),
        "b_res": (N, N)}
    assert sum(v.size for v in leaves.values()) == CFG.num_params() \
        == N * D * 24 + 27
    published = hc.HyperConnectionConfig()
    assert published.num_params() == 14336 * 24 + 27 == 344_091


def test_no_array_carries_the_lanes_on_one_of_its_last_two_axes():
    """The layout is part of the sizing: (.., n, d) as the last two axes
    pads n to a tile's 8 or 16 sublanes, and a (T, n, n) array with
    n x n minor to a whole (8, 128) tile a token.  No array of the
    forward and backward pass of one hyper-connection that holds the
    tokens has the lanes on its last axis, or on the one before it in
    front of the hidden size."""
    leaves, x = _leaves(), _stream()
    y = jnp.ones((2, T, D))

    def f(leaves, x, y):
        h_pre, h_post, h_res = hc.coefficients(leaves, x, CFG)
        return jnp.sum(hc.write(h_res, h_post, x, y * hc.read(h_pre, x)))

    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(leaves, x, y)

    def shapes(jaxpr):  # Sinkhorn's rounds are a scan's body
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield eqn.primitive, var.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = 0
    for primitive, shape in shapes(jaxpr.jaxpr):
        if T in shape:
            seen += 1
            assert shape[-1] != N and shape[-2:] != (N, D), (primitive,
                                                             shape)
    assert seen > 100


def test_the_counter_is_collected_once_a_model():
    assert hc.collect_residual_stats({}) == {}
    assert hc.collect_residual_stats(
        {"layers_0": {"attn_lanes": (jnp.ones(2),)}}) == {}
    stats = hc.collect_residual_stats({
        "layers_0": {"hc_sinkhorn_err": (jnp.float32(1e-6),
                                         jnp.float32(3e-6))},
        "layers_1": {"hc_sinkhorn_err": (jnp.float32(2e-6),)}})
    assert list(stats) == ["resmix_sinkhorn_err"]
    assert float(stats["resmix_sinkhorn_err"]) == pytest.approx(3e-6)


def test_expand_and_read_out_are_replicate_and_sum():
    e = jax.random.normal(jax.random.PRNGKey(0), (2, T, D))
    x = hc.expand(e, N)
    assert x.shape == (2, N, T, D)
    np.testing.assert_array_equal(x[:, 2], e)
    np.testing.assert_allclose(hc.read_out(x), N * e, rtol=1e-6)
