"""The Mamba-1 selective scan's plain route (`ops/selective_scan.py`:
chunks of a rematerialised `lax.scan`) against the recurrence written
step by step (one plain scan over time), values and every gradient: whole chunks, a
sequence that is no whole number of chunks (padded with steps that
change nothing), one chunk, a float32 state under bfloat16 operands, and
the route's decision off the TPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import selective_scan as ss

NAMES = ["y", "dx", "ddt", "dA", "dB", "dC", "dD"]


def inputs(b=2, t=48, d=256, n=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, d)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, d)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (d, n), minval=0.0, maxval=2.7))
    b_mat = jax.random.normal(ks[3], (b, t, n)).astype(dtype)
    c_mat = jax.random.normal(ks[4], (b, t, n)).astype(dtype)
    return x, dt, a, b_mat, c_mat, jax.random.normal(ks[5], (d,))


def step_by_step(x, dt, a, b_mat, c_mat, d_skip):
    """h_t = exp(dt_t (x) A) h_{t-1} + (dt_t x_t) (x) B_t; y_t = h_t C_t
    + D x_t: ONE `lax.scan` over time, a line a step, float32 — no chunk,
    no padding, nothing rematerialised."""
    x, b_mat, c_mat = (v.astype(jnp.float32) for v in (x, b_mat, c_mat))

    def step(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1) + d_skip * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros((x.shape[0], x.shape[2], a.shape[1])),
        tuple(v.swapaxes(0, 1) for v in (x, dt, b_mat, c_mat)))
    return y.swapaxes(0, 1)


def value_and_grads(fn, args):
    def scalar(*a):
        return jnp.sum(jnp.sin(fn(*a)))

    @jax.jit
    def both(*a):
        return (fn(*a),) + jax.grad(scalar, argnums=tuple(range(6)))(*a)
    with jax.default_matmul_precision("highest"):
        return both(*args)


def off(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.abs(want).max()) > 0
    return float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
                 .max() / jnp.abs(want.astype(jnp.float32)).max())


@functools.lru_cache(maxsize=None)
def _both_ways(t, chunk, dtype_name="float32"):
    args = inputs(t=t, dtype=jnp.dtype(dtype_name))
    return (value_and_grads(functools.partial(ss.selective_scan_plain,
                                              chunk=chunk), args),
            value_and_grads(step_by_step, args))


@pytest.mark.parametrize("i", range(7), ids=NAMES)
@pytest.mark.parametrize("t,chunk", [(48, 16), (45, 16), (16, 16), (7, 16)],
                         ids=["three_chunks", "a_ragged_last_chunk",
                              "one_chunk", "under_a_chunk"])
def test_plain_route_is_the_recurrence(t, chunk, i):
    """Whole chunks or not: a sequence that is no whole number of chunks
    is HANDLED — padded with steps of dt = 0, which leave the state as it
    is, and cut off again."""
    plain, exact = _both_ways(t, chunk)
    assert off(plain[i], exact[i]) < 1e-5


@pytest.mark.parametrize("i", range(7), ids=NAMES)
def test_the_state_is_float32_under_bfloat16_operands(i):
    """x, B and C in bfloat16: the route reads them as they are and
    carries dt, A, every exp and h in float32 — against the step-by-step
    recurrence on the same rounded operands it is float32's distance
    (a state in bfloat16 would be off by 1e-2 after 48 steps), and y
    leaves in float32."""
    plain, exact = _both_ways(48, 16, "bfloat16")
    assert plain[0].dtype == jnp.float32
    assert off(plain[i], exact[i]) < (1e-5 if i in (0, 2, 3, 6) else 1e-2)


def test_off_the_tpu_the_scan_is_the_plain_route_bit_for_bit():
    args = inputs(t=512)
    assert ss.sscan_route(512, 256, 16) == ("plain", 0)
    np.testing.assert_array_equal(
        ss.selective_scan(*args),
        ss.selective_scan_plain(*args, chunk=ss._CHUNK))


@pytest.mark.parametrize("t,d,n,want", [
    (8192, 5120, 16, ("kernel", 512)),      # the cell's shape
    (16384, 5120, 16, ("kernel", 512)),
    (512, 256, 16, ("kernel", 256)),        # fewer channels than a block
    (8192 + 128, 5120, 16, ("plain", 0)),   # no whole chunks
    (8192, 5120 + 64, 16, ("plain", 0)),    # channels off the lane tiles
    (8192, 5120, 12, ("plain", 0)),         # states off the sublane tiles
    (8, 5120, 16, ("plain", 0)),            # a parameter draw
])
def test_route_by_shape_on_one_tpu_device(on_tpu, t, d, n, want):
    assert ss.sscan_route(t, d, n) == want


@pytest.mark.parametrize("on_tpu", [False], indirect=True)
def test_route_off_the_tpu_is_plain(on_tpu):
    assert ss.sscan_route(8192, 5120, 16) == ("plain", 0)


def test_route_on_a_mesh_of_several_devices_is_plain(on_tpu):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    assert ss.sscan_route(8192, 5120, 16, mesh) == ("plain", 0)
