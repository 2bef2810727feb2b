"""`ops/sparse_attention.py`: the kernel route (interpret mode) against
the plain route and both against a gather-and-softmax written here — the
chosen set with ties, the forward, the KL term, the VJP of every operand;
the route's truth table; the counters; and that a model WITHOUT the new
`LlamaConfig` fields lowers to the text it lowered to before them.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import sparse_attention as sa

B, T, H, KV, D, HI, DI, TOPK = 2, 64, 4, 2, 128, 2, 16, 16
SCALE = 0.1


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    f = jnp.float32
    return dict(
        q=jax.random.normal(ks[0], (B, T, H, D), f) * 0.3,
        k=jax.random.normal(ks[1], (B, T, KV, D), f) * 0.3,
        v=jax.random.normal(ks[2], (B, T, KV, D), f),
        q_idx=jax.random.normal(ks[3], (B, T, HI, DI), f),
        k_idx=jax.random.normal(ks[4], (B, T, DI), f),
        w=jax.random.normal(ks[5], (B, T, HI), f),
        ct=jax.random.normal(ks[6], (B, T, H, D), f))


NAMES = ("q", "k", "v", "q_idx", "k_idx", "w")


def _sorted_sets(q_idx, k_idx, w, topk=TOPK):
    """Each query's top-k causal keys by a stable sort of its own scores:
    (indices (b, t, topk) padded with 0, which of them are real)."""
    scores = np.asarray((jnp.maximum(jnp.einsum(
        "bthd,bsd->bhts", q_idx, k_idx), 0.0)
        * w.transpose(0, 2, 1)[..., None]).sum(1))
    n_b, n_t = scores.shape[:2]
    idx = np.zeros((n_b, n_t, topk), np.int32)
    real = np.zeros((n_b, n_t, topk), bool)
    for b in range(n_b):
        for t in range(n_t):
            kept = np.sort(np.argsort(-scores[b, t, :t + 1],
                                      kind="stable")[:topk])
            idx[b, t, :len(kept)], real[b, t, :len(kept)] = kept, True
    return idx, real


def _gathered(q, k, v, q_idx, k_idx, w, ct, sets):
    """The equations over a GATHER of each query's kept keys: a softmax
    over TOPK gathered keys, never a (T x T) mask."""
    idx, real = sets
    n_b, n_t = q.shape[:2]
    rows = jnp.arange(n_b)[:, None, None]
    rep = q.shape[2] // k.shape[2]
    kk, vv = (jnp.repeat(a[rows, idx], rep, axis=3) for a in (k, v))
    att = jnp.einsum("bthd,btshd->bths", q, kk) * SCALE
    p = jax.nn.softmax(jnp.where(real[:, :, None], att, -jnp.inf), -1)
    out = jnp.einsum("bths,btshd->bthd", p, vv)
    scores = (jnp.maximum(jnp.einsum("bthd,btsd->bths", q_idx,
                                     k_idx[rows, idx]), 0.0)
              * w[..., None]).sum(2)
    logq = jax.nn.log_softmax(jnp.where(real, scores, -jnp.inf), -1)
    pbar = jax.lax.stop_gradient(p.mean(2))
    kl = jnp.where(real, pbar * (jnp.log(jnp.where(real, pbar, 1.0))
                                 - jnp.where(real, logq, 0.0)), 0.0).sum()
    return (out * ct).sum() + 3.0 * kl / (n_b * n_t), (
        out, kl / (n_b * n_t), None)


def _route(which, topk=TOPK, bwd=None):
    """`which`: "plain", "kernel" (the backward at `bwd_step`'s own
    step) or "kernel_other" (at the step `bwd`, by the wrapper's
    override)."""
    def run(q, k, v, q_idx, k_idx, w, ct):
        if which == "plain":
            o, kl, mask, _ = sa._sparse_plain(q, k, v, q_idx, k_idx, w, topk,
                                           SCALE)
        else:
            o, kl, mask, _ = sa._sparse_kernels(
                q, k, v, q_idx, k_idx, w, topk, SCALE, block=16, rows=8,
                interpret=True, bwd=bwd if which == "kernel_other" else None)
        return (o * ct).sum() + 3.0 * kl, (o, kl, mask)
    return run


ROUTES = ("plain", "kernel", "kernel_other")


def _all_routes(operands, topk, bwd):
    """{gathered | plain | kernel | kernel_other: ((value, (o, kl,
    mask)), the six operands' gradients), sets: `_sorted_sets`'}, each
    route one jitted program (op by op they take four times as long);
    `bwd` the OTHER step of the fused backward, (heads a unit, q rows,
    keys), than the one `bwd_step` gives the operands' shape."""
    rep = operands["q"].shape[2] // operands["k"].shape[2]
    assert bwd != sa.bwd_step(operands["q"].shape[1], D, rep, 4, 16)
    with jax.default_matmul_precision("highest"):
        sets = _sorted_sets(*(operands[n] for n in NAMES[3:]), topk)
        return {"sets": sets, **{name: jax.jit(jax.value_and_grad(
            fn, argnums=tuple(range(6)), has_aux=True))(*operands.values())
            for name, fn in (
                ("gathered", functools.partial(_gathered, sets=sets)),
                *((name, _route(name, topk, bwd)) for name in ROUTES))}}


# an indexer that prefers the RECENT keys: under it a late row keeps
# nothing in its leading key blocks, which is what the floor of the
# forward's running maximum is for
RECENT_TOPK = 8  # under one block of 16
RH, RKV = 2, 1   # one group of two heads: half the kernels to lower
LONG = 80  # five tiles of 16 and no whole number of 32 keys: the
#            forward's step is (16 x 16) there, (16 x 32) at T = 64; five
#            query blocks of the backward: dq carried across key blocks
ODD = 3  # heads on the one kv head of a group two do not divide


def _recent_operands(t, heads=RH):
    """One sequence of `t` whose indexer scores GROW with the key's
    position (numpy draws: nothing to compile)."""
    draw = np.random.default_rng(t).standard_normal
    f = np.float32
    ramp = (np.arange(t, dtype=f)[:, None] + 1.0) / t
    return {name: jnp.asarray(x, f) for name, x in dict(
        q=draw((1, t, heads, D)) * 0.3, k=draw((1, t, RKV, D)) * 0.3,
        v=draw((1, t, RKV, D)), q_idx=np.abs(draw((1, t, HI, DI))),
        k_idx=ramp[None] + 0.01 * draw((1, t, DI)),
        w=np.abs(draw((1, t, HI))) + 0.1, ct=draw((1, t, heads, D))).items()}


@pytest.fixture(scope="module", params=("scattered", "recent", "odd_group"))
def results(request, operands):
    """Every route's results: on the module's random operands (groups of
    two heads; the backward's own step there is one head at (32 x 64),
    the other the pair at single tiles), on one sequence of `LONG` under
    the indexer that prefers recent keys (its own: the pair at single
    tiles, five q blocks — dq carried across key blocks; the other: one
    head), and on one of T whose one group is `ODD` heads (no pair
    divides it: one head, at (32 x 64) or at single tiles)."""
    if request.param == "scattered":
        return _all_routes(operands, TOPK, (2, 16, 16))
    if request.param == "recent":
        return _all_routes(_recent_operands(LONG), RECENT_TOPK, (1, 16, 16))
    return _all_routes(_recent_operands(T, ODD), RECENT_TOPK, (1, 16, 16))


@pytest.mark.parametrize("route", ROUTES[:2])
def test_the_chosen_set_is_the_sorted_one(results, route):
    idx, real = results["sets"]
    mask = np.asarray(results[route][0][1][2]) != 0
    for b in range(mask.shape[0]):
        for t in range(mask.shape[1]):
            assert np.array_equal(np.flatnonzero(mask[b, t, :t + 1]),
                                  idx[b, t][real[b, t]]), (b, t)
    if mask.shape[1] == LONG:  # late rows keep nothing in the leading tiles
        assert not mask[0, 32:, :16].any()
        assert not mask[0, LONG - 1, :LONG - 16].any()


@pytest.mark.parametrize("route", ROUTES[:2])
def test_forward_and_kl_are_the_gathered_softmaxs(results, route):
    (_, (want_o, want_kl, _)), _ = results["gathered"]
    (_, (o, kl, _)), _ = results[route]
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-5)
    assert abs(float(kl) - float(want_kl)) < 1e-5 * float(want_kl)


@pytest.mark.parametrize("route", ROUTES)
def test_the_vjp_is_the_gathered_softmaxs(results, route):
    """Every operand's cotangent, of the output and of the KL; finite on
    a row that keeps no key in its leading blocks."""
    for operand, name in enumerate(NAMES):
        want = results["gathered"][1][operand]
        assert np.isfinite(results[route][1][operand]).all(), name
        np.testing.assert_allclose(
            results[route][1][operand], want, rtol=1e-3,
            atol=1e-5 * float(jnp.abs(want).max()) + 1e-7, err_msg=name)


def test_the_forwards_step_is_whole_blocks_of_the_sequence():
    assert sa._fwd_blocks(T, 16) == (16, 32)
    assert sa._fwd_blocks(LONG, 16) == (16, 16)
    assert sa._fwd_blocks(16384, sa._BLOCK) == tuple(
        n * sa._BLOCK for n in sa._FWD_TILES)
    assert sa._fwd_blocks(16384 + sa._BLOCK, sa._BLOCK) == (
        sa._BLOCK, sa._BLOCK)


@functools.lru_cache(maxsize=None)
def _plain_forward():
    """(q, k, v as rows, the choice, o, the kept scores' log-sums
    (b, KV, T, rep)) of recent keys at T by dense lines."""
    operands = _recent_operands(T)
    q, k, v = (operands[n] for n in NAMES[:3])
    with jax.default_matmul_precision("highest"):
        mask = sa._plain_select(sa._plain_scores(
            *(operands[n] for n in NAMES[3:])), RECENT_TOPK)
        o, _ = sa._plain_attend(q, k, v, mask, SCALE)
        s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, RH // RKV, 2))
    assert not mask[0, 32:, :16].any()
    lse = jax.scipy.special.logsumexp(
        jnp.where(mask[:, None], s * SCALE, -jnp.inf), axis=-1)
    return ([x.reshape(1, T, -1) for x in (q, k, v)],
            mask.astype(jnp.int8), o.reshape(1, T, -1),
            lse.reshape(1, RKV, RH // RKV, T).transpose(0, 1, 3, 2))


@pytest.mark.parametrize("blocks", (None, (32, 16), (32, 32), (64, 32),
                                    (16, 64)))
def test_every_block_geometry_of_the_forward_is_the_plain_attention(blocks):
    """The forward's (q rows x keys) a grid step (None: its own), whole
    tiles at or below the diagonal only, late rows' leading key blocks
    empty: o and the natural log-sum of the kept scores."""
    rows, mask, want_o, want_lse = _plain_forward()
    with jax.default_matmul_precision("highest"):
        o, lse = jax.jit(functools.partial(
            sa._fwd_pallas, scale=SCALE, n_kv=RKV, block=16, interpret=True,
            blocks=blocks))(*rows, mask)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5)


@pytest.mark.parametrize("units", (1, 2))
@pytest.mark.parametrize("blocks", ((16, 16), (16, 32), (32, 16), (32, 64)))
def test_every_step_of_the_backward_is_the_plain_attentions_vjp(
        blocks, units):
    """The fused backward at one and at two heads a unit and every shape
    of (q rows x keys) a grid step, whole tiles at or below the diagonal
    only, late rows' leading key blocks empty: dq, dk and dv of the dense
    lines."""
    (q, k, v), mask, _, _ = _plain_forward()
    do = jnp.asarray(np.random.default_rng(5).standard_normal(q.shape),
                     jnp.float32)

    def plain(q, k, v):
        o, _ = sa._plain_attend(*(x.reshape(1, T, -1, D) for x in (q, k, v)),
                                mask != 0, SCALE)
        return o.reshape(q.shape)

    plan = dict(scale=SCALE, n_kv=RKV, block=16, interpret=True)
    with jax.default_matmul_precision("highest"):
        o, vjp = jax.vjp(plain, q, k, v)
        _, lse = sa._fwd_pallas(q, k, v, mask, **plan)
        delta = (do * o).reshape(1, T, RKV, RH // RKV, D).sum(-1).transpose(
            0, 2, 1, 3)
        got = jax.jit(functools.partial(
            sa._bwd_pallas, step=(units, *blocks), **plan))(
                q, k, v, do, lse, delta, mask)
    for name, x, want in zip("qkv", got, vjp(do)):
        np.testing.assert_allclose(
            x, want, rtol=1e-4, atol=1e-5 * float(jnp.abs(want).max()),
            err_msg="d" + name)


@pytest.mark.parametrize("bq,bk,rel,bands", (
    (1024, 512, -512, [(512, 1024, 512)]),
    (1024, 512, 0, [(0, 1024, 512)]),
    (512, 1024, 0, [(0, 512, 512)]),
    (512, 1024, 512, [(0, 512, 1024)]),
    (1024, 1024, 0, [(0, 512, 512), (512, 1024, 1024)]),
    (1024, 1024, 1024, [(0, 1024, 1024)]),
    (512, 512, 0, [(0, 512, 512)])))
def test_a_block_the_diagonal_crosses_runs_the_tiles_at_or_below_it(
        bq, bk, rel, bands):
    assert sa._fwd_bands(bq, bk, rel, 512) == bands


def test_the_terms_reach_their_own_operands_alone(operands):
    """The output's cotangent reaches q, k, v and no indexer operand;
    the KL's the indexer's three and none of q, k, v."""
    args = [operands[n] for n in NAMES]
    for pick, reached in ((0, (0, 1, 2)), (1, (3, 4, 5))):
        def fn(*a):
            out = sa._sparse_kernels(*a, TOPK, SCALE, block=16, rows=8,
                                     interpret=True)
            return out[pick].sum()

        grads = jax.jit(jax.grad(fn, argnums=tuple(range(6))))(*args)
        for i, g in enumerate(grads):
            assert bool(np.any(np.asarray(g))) == (i in reached), NAMES[i]


@pytest.mark.parametrize("steps", (2.0, 1.0))
def test_the_search_cuts_a_run_of_equals_at_the_lowest_keys(steps):
    """Scores on a coarse grid (runs of equal values, -0.0 among them):
    the kernel's threshold search and its cut by position keep what
    `lax.top_k` keeps."""
    scores = jnp.round(jax.random.normal(
        jax.random.PRNGKey(3), (B, T, T)) * steps) / steps
    mask, logz, counts = sa._select(scores, topk=TOPK, rows=8, chunk=16,
                            interpret=True)
    want = np.asarray(sa._plain_select(scores, TOPK))
    tri = np.tril(np.ones((T, T), bool))
    assert np.array_equal((np.asarray(mask) != 0)[:, tri], want[:, tri])
    kept = np.where(want, np.asarray(scores), -np.inf)
    np.testing.assert_allclose(
        logz[..., 0], jax.scipy.special.logsumexp(kept, axis=-1), rtol=1e-5)
    # a run was cut somewhere: the test tests what it says
    last = np.asarray(scores[0, T - 1])
    thr = last[want[0, T - 1]].min()
    assert (last == thr).sum() > want[0, T - 1][last == thr].sum() > 0


def test_the_entry_counts_from_the_choice_itself(operands):
    args = [operands[n] for n in NAMES]
    _, _, stats = sa.sparse_attention(*args, TOPK, SCALE)
    kept, causal, live, tiles, run = (float(x) for x in stats)
    assert kept == B * sa.kept_pairs(T, TOPK)
    assert causal == B * T * (T + 1) // 2
    assert live == tiles == run == B  # T under a block: one tile
    mask = sa._plain_select(sa._plain_scores(*args[3:]), TOPK)
    kept16, live16, tiles16 = (float(x) for x in sa.tile_counts(
        sa.tiles_of(mask, 16)))
    # the kernel route's own count of the same tiles
    *_, tiles = sa._sparse_kernels(*args, TOPK, SCALE, block=16, rows=8,
                                   interpret=True)
    tri = np.tril(np.ones((4, 4), bool))
    assert np.array_equal(np.asarray(tiles)[:, tri],
                          np.asarray(sa.tiles_of(mask, 16))[:, tri])
    assert kept16 == kept and tiles16 == B * 4 * 5 // 2
    assert 0 < live16 <= tiles16


def test_the_route_is_the_shapes_and_the_site(on_tpu):
    assert sa.sparse_route(16384, 128, 64) == "kernel"
    assert sa.sparse_route(16384 + 8, 128, 64) == "plain"  # no whole block
    assert sa.sparse_route(16384, 64, 64) == "plain"  # half a slab a head
    assert sa.sparse_route(8, 128, 64) == "plain"  # a parameter draw


def test_the_backwards_step_is_the_shapes(on_tpu):
    """(heads of a group, q rows, keys) a grid step of the fused
    backward: the fastest measured step that divides the group and the
    sequence and whose whole-length sums fit VMEM; where one head's at
    one tile does not there is no kernel route at all."""
    assert sa.bwd_step(16384, 128, 8) == (1, 1024, 2048)  # the cell's shape
    assert sa._bwd_vmem(2, 16384, 128, 2, 1024, 2048) > sa._VMEM  # no pair
    assert sa.bwd_step(16384, 128, ODD) == (1, 1024, 2048)
    # an odd number of tiles: single tiles, and there the pair is faster
    assert sa.bwd_step(16384 + 512, 128, 8) == (2, 512, 512)
    assert sa.bwd_step(16384 + 512, 128, ODD) == (1, 512, 512)
    assert sa.bwd_step(16384 + 1024, 128, 8) == (1, 1024, 1024)
    assert sa.bwd_step(16384, 128, 8, itemsize=4) == (1, 1024, 1024)
    # the whole-length sums outgrow the wide steps, then every step
    assert sa.bwd_step(24576, 128, 8) == (1, 1024, 1024)
    assert sa.bwd_step(30720, 128, 8) == (1, 512, 512)
    assert sa.bwd_step(30720 + 512, 128, 8) is None
    assert sa._bwd_vmem(1, 30720 + 512, 128, 2, 512, 512) > sa._VMEM
    assert sa.sparse_route(30720, 128, 64) == "kernel"
    assert sa.sparse_route(30720 + 512, 128, 64) == "plain"
    assert sa.sparse_route(16384, 128, 64, itemsize=4) == "kernel"
    # the tests' own shapes
    assert sa.bwd_step(T, D, H // KV, 4, 16) == (1, 32, 64)
    assert sa.bwd_step(LONG, D, RH // RKV, 4, 16) == (2, 16, 16)
    assert sa.bwd_step(T, D, ODD, 4, 16) == (1, 32, 64)


def test_off_the_chip_the_route_is_plain():
    assert sa.sparse_route(16384, 128, 64) == "plain"


def test_a_model_without_the_fields_lowers_to_the_text_it_did():
    """`LlamaConfig`'s new fields default off: a Llama nano's loss and
    gradient (per-head QK norm on, the rest default) lower to the text of
    the commit before them (sha256 taken there)."""
    from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    cfg = dataclasses.replace(LlamaConfig.nano(), qk_head_norm=True)
    assert not cfg.attn_index_topk
    model = Llama(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = jax.jit(jax.value_and_grad(make_lm_loss(model.apply))).lower(
        params, {"input_ids": ids, "labels": ids}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "4b7e13c9b71a33015882e6eb9419fbba28302a326b7559711c183af459bb08d0"
