"""Manifold-constrained hyper-connections (`models/hyper_connection.py`)
at a small size in float32: Sinkhorn's rounds, the coefficients against
the plain reference's (`benchmark/reference_xing4_0.py`), the two mixes
and their written-out backward rules against autodiff of the plain
formula, what the init is, the layout the stream is carried in, and the
counter; and the kernel route (`ops/hc_mix.py`: four `dwt_hc_*` kernels,
here in interpret mode) against the plain one — values and every
gradient in both dtypes, a ragged last tile, two and four lanes, the
stack's own loss — and the table of which calls take it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing4_0 as ref
from dlrover_wuqiong_tpu.models import hyper_connection as hc
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import hc_mix

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


# ------------------------------------------------------- the kernel route

@pytest.fixture
def kernel_route(request, monkeypatch):
    """`mix_in` / `mix_out` take the kernels, in interpret mode, at the
    token tile the test names (`kernel_route(tile)`) — from the call on:
    the backend is said to be the TPU to everyone, so the attention of a
    whole stack takes its kernels too, interpreted as well."""
    def switch(tile=None):
        request.getfixturevalue("on_tpu")
        monkeypatch.setattr(hc_mix, "plan", functools.partial(
            hc_mix.plan, tile=tile, interpret=True))
        for name in ("_fa_forward_pallas", "_fa_backward_pallas"):
            monkeypatch.setattr(fa, name, functools.partial(
                lambda kernel, *a, **kw: kernel(
                    *a, **{**kw, "interpret": True}), getattr(fa, name)))
    return switch


def _drawn(cfg, seed=0):
    """Every leaf away from its init: the gains and biases too."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    leaves = _init(cfg)
    return {"phi": 0.5 * jax.random.normal(keys[0], leaves["phi"].shape),
            "alpha": jnp.array([0.3, 0.2, 0.4]),
            "b_pre": leaves["b_pre"] + 0.3 * jax.random.normal(
                keys[1], leaves["b_pre"].shape),
            "b_post": 0.3 * jax.random.normal(keys[2],
                                              leaves["b_post"].shape),
            "b_res": leaves["b_res"] + 0.3 * jax.random.normal(
                keys[3], leaves["b_res"].shape)}


def _sublayer(cfg, weight, leaves, x, y):
    """One sublayer around a branch with a matrix of its own, plus a
    second input `y` beside the branch's output: u, X' and a loss."""
    u, x, h_post, h_res = hc.mix_in(leaves, x, cfg)
    branch = jnp.tanh(u.astype(jnp.float32) @ weight).astype(u.dtype)
    out = hc.mix_out(h_res, h_post, x, branch + y)
    loss = jnp.sum(jnp.sin(out.astype(jnp.float32))) \
        + jnp.sum(jnp.cos(u.astype(jnp.float32)))
    return loss, (u, out)


def _close(got, want, tol, what):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * float(np.abs(want).max()),
                               err_msg=what)


# (lanes, tokens, hidden, dtype, token tile): one tile; a ragged last
# tile (160 = 128 + 32) at two widths of lane group; several whole tiles
KERNEL_CASES = [(2, 48, 128, jnp.float32, None),
                (4, 160, 256, jnp.float32, 128),
                (2, 160, 128, jnp.bfloat16, 128),
                (4, 256, 384, jnp.bfloat16, 128),
                (4, 32, 640, jnp.bfloat16, None)]


@pytest.mark.parametrize("n,t,d,dtype,tile", KERNEL_CASES)
def test_the_kernel_route_is_the_plain_route(kernel_route, n, t, d, dtype,
                                             tile):
    """u, X' and the gradient of X, y, Phi, the gains and the three
    biases through one sublayer: `dwt_hc_pre`, `_post`, `_post_bwd` and
    `_pre_bwd` against `coefficients`, `read` and `write`.  In bfloat16
    the kernels round the stream's cotangent once where the plain route
    adds up rounded parts, so the two agree to bfloat16's step."""
    cfg = hc.HyperConnectionConfig(hidden_size=d, lanes=n)
    keys = jax.random.split(jax.random.PRNGKey(n * t + d), 3)
    leaves = _drawn(cfg)
    x = jax.random.normal(keys[0], (2, n, t, d), dtype)
    y = jax.random.normal(keys[1], (2, t, d), dtype)
    weight = jax.random.normal(keys[2], (d, d)) / d ** 0.5
    fn = jax.value_and_grad(functools.partial(_sublayer, cfg, weight),
                            argnums=(0, 1, 2), has_aux=True)
    assert hc_mix.hc_route(n, t, d) == "plain"
    (want, want_out), want_g = fn(leaves, x, y)
    kernel_route(tile)
    assert hc_mix.hc_route(n, t, d) == "kernel"
    (got, got_out), got_g = fn(leaves, x, y)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(got) == pytest.approx(float(want), rel=tol)
    for name, g, w in zip(("u", "X'"), got_out, want_out):
        assert g.dtype == w.dtype == dtype
        _close(g, w, tol, name)
    assert set(got_g[0]) == {"phi", "alpha", "b_pre", "b_post", "b_res"}
    for name in got_g[0]:
        assert got_g[0][name].dtype == jnp.float32
        assert float(jnp.abs(want_g[0][name]).max()) > 0, name
        _close(got_g[0][name], want_g[0][name], tol, name)
    for name, g, w in zip(("dX", "dy"), got_g[1:], want_g[1:]):
        assert g.dtype == dtype
        _close(g, w, tol, name)


def test_the_stream_goes_through_mix_in_and_its_cotangent_comes_back(
        kernel_route):
    """`mix_in` returns the stream itself; what `mix_out` (or anything
    else) sends back along it is added inside `dwt_hc_pre_bwd`, and a
    stream nobody reads costs a cotangent of zeros, not an error.  Row
    n^2 + 2n of the coefficients is the norm's factor."""
    kernel_route()
    leaves, x = _leaves(), _stream()[:, :, :16]
    d = x.shape[-1] * 4
    x = jnp.tile(x, (1, 1, 1, 4))  # 128 wide
    cfg = hc.HyperConnectionConfig(hidden_size=d, lanes=N)
    leaves = {**leaves, "phi": jnp.tile(leaves["phi"], (1, 4, 1))}
    u, same, h_post, h_res = hc.mix_in(leaves, x, cfg)
    np.testing.assert_array_equal(same, x)
    plan = hc_mix.plan(16, interpret=True)
    coef = hc_mix.mix_in(x, leaves["phi"], leaves["alpha"][0],
                         leaves["b_pre"], cfg.norm_eps, plan)[1]
    assert coef.shape == (2, hc_mix.coef_rows(N), 16) == (2, 32, 16)
    rms = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=(1, 3)) + cfg.norm_eps)
    np.testing.assert_allclose(coef[:, N * (N + 2)], rms, rtol=1e-6)
    assert float(jnp.abs(coef[:, N * (N + 2) + 1:]).max()) == 0

    def only_u(x):
        return jnp.sum(hc.mix_in(leaves, x, cfg)[0] ** 2)

    def u_and_stream(x):
        u, x = hc.mix_in(leaves, x, cfg)[:2]
        return jnp.sum(u ** 2) + jnp.sum(jnp.sin(x))

    def plain_u(x):
        return jnp.sum(hc.read(hc.coefficients(leaves, x, cfg)[0], x) ** 2)

    want = jax.grad(plain_u)(x)
    _close(jax.grad(only_u)(x), want, 2e-5, "u alone")
    _close(jax.grad(u_and_stream)(x), want + jnp.cos(x), 2e-5, "u + stream")


@pytest.mark.parametrize("on_tpu,n,t,d,devices,route", [
    (True, 4, 8192, 3584, 1, "kernel"),
    (True, 2, 32, 128, 1, "kernel"),
    (True, 4, 8192, 3584, None, "kernel"),  # no mesh at all
    (False, 4, 8192, 3584, 1, "plain"),     # the CPU
    (True, 4, 8192, 3584, 4, "plain"),      # a mesh: GSPMD cannot cut it
    (True, 4, 8192, 200, 1, "plain"),       # no whole lane slabs
    (True, 4, 8200, 3584, 1, "plain"),      # no whole packed tiles
    (True, 1, 8192, 3584, 1, "plain"),      # one lane mixes nothing
], indirect=["on_tpu"])
def test_which_calls_take_the_kernels(on_tpu, n, t, d, devices, route):
    from jax.sharding import Mesh

    mesh = None if devices is None else Mesh(
        np.array(jax.devices()[:devices]), ("fsdp",))
    assert hc_mix.hc_route(n, t, d, mesh) == route


def test_a_plan_is_whole_lane_slabs_of_tokens_or_all_of_them():
    assert dict(hc_mix.plan(8192)) == {
        "tile": 128, "pre_tile": 512, "interpret": False}
    assert dict(hc_mix.plan(48)) == {
        "tile": 48, "pre_tile": 48, "interpret": False}
    assert dict(hc_mix.plan(400, tile=256, interpret=True)) == {
        "tile": 256, "pre_tile": 256, "interpret": True}
    with pytest.raises(AssertionError):
        hc_mix.plan(40)
    with pytest.raises(AssertionError):
        hc_mix.plan(8192, tile=96)
    assert (hc_mix.coef_rows(4), hc_mix.coef_rows(2)) == (32, 16)


def test_the_stack_on_the_kernel_route_is_the_stack_on_the_plain_route(
        kernel_route, lanes=4, remat=True, layers=2):
    """`LatentMoE` with four lanes, 128 wide, a dense and an expert
    block under full recomputation: the loss and every gradient leaf on
    the two routes — the rule's residuals live through `jax.checkpoint`,
    and a sublayer's stream is the X' of the one before."""
    from dlrover_wuqiong_tpu.models.latent_moe import (
        LatentMoE,
        LatentMoEConfig,
    )
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    cfg = LatentMoEConfig.nano(
        dtype=jnp.float32, hidden_size=128, num_layers=layers, remat=remat,
        residual_lanes=lanes, max_seq_len=32)
    model = LatentMoE(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq=32)

    def drawn(path, leaf):
        name = path[-1].key
        if name == "phi":
            return 0.3 * jax.random.normal(jax.random.PRNGKey(leaf.size),
                                           leaf.shape)
        return leaf * 30.0 if name == "alpha" else leaf

    params = jax.tree_util.tree_map_with_path(drawn, params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    fn = jax.value_and_grad(make_lm_loss(model.apply))
    want, want_g = fn(params, batch)
    kernel_route()
    got, got_g = fn(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    scale = max(float(jnp.abs(w).max()) for w in jax.tree.leaves(want_g))
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    for (path, g), w in zip(flat, jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4,
            atol=1e-5 * float(jnp.abs(w).max()) + 1e-6 * scale,
            err_msg=jax.tree_util.keystr(path))
