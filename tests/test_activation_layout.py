"""The residual stream's stated layout (`parallel/sharding.pin_activation`,
called from `models/gpt.py`) on the virtual CPU mesh: the same numbers as
one device under every sharded plan, and no op at all without a mesh.
What the pins do to a TPU step's collectives is `tests/test_tpu_compile.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.parallel.mesh import MeshPlan, build_mesh
from dlrover_wuqiong_tpu.parallel.sharding import (
    activation_spec,
    pin_activation,
)

U = P.UNCONSTRAINED
BATCH, SEQ = 8, 64


def _build(strategy, n_devices):
    return auto_accelerate(GPT(GPTConfig.nano()), optimizer=optax.sgd(0.1),
                           strategy=strategy,
                           devices=jax.devices()[:n_devices],
                           rng=jax.random.PRNGKey(0))


def _batch():
    data = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                              GPTConfig.nano().vocab_size)
    return {"input_ids": data[:, :-1], "labels": data[:, 1:]}


@pytest.fixture(scope="module")
def one_device():
    """Parameters, loss and gradients of the model no mesh was given to."""
    res = _build([("fsdp", {})], 1)
    assert res.model.config.mesh is None
    params = jax.device_get(res.state.params)
    loss, grads = jax.jit(jax.value_and_grad(res.loss_fn))(
        res.state.params, res.place_batch(_batch()))
    return params, float(loss), jax.device_get(grads)


PLANS = {
    "fsdp4": ([("fsdp", {})], 4),
    "fsdp2_tp2": ([("tensor_parallel", {"size": 2}), ("fsdp", {})], 4),
    "sp2": ([("sequence_parallel", {"size": 2, "impl": "gspmd"})], 2),
    "sp2_ring_fsdp2": ([("sequence_parallel", {"size": 2, "impl": "ring"}),
                        ("fsdp", {})], 4),
}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_sharded_plan_agrees_with_one_device(one_device, plan):
    params, loss1, grads1 = one_device
    res = _build(*PLANS[plan])
    assert res.model.config.mesh is res.mesh  # the pins are in this trace
    jaxpr = str(jax.make_jaxpr(res.loss_fn)(
        res.state.params, res.place_batch(_batch())))
    assert "sharding_constraint" in jaxpr
    loss, grads = jax.jit(jax.value_and_grad(res.loss_fn))(
        jax.device_put(params, res.state_shardings.params),
        res.place_batch(_batch()))
    # bf16 compute: the summation order across chips is all that differs
    np.testing.assert_allclose(float(loss), loss1, rtol=2e-3)
    flat1 = jax.tree_util.tree_leaves_with_path(grads1)
    flat = jax.tree_util.tree_leaves_with_path(jax.device_get(grads))
    assert len(flat) == len(flat1)
    for (path, g1), (_, g) in zip(flat1, flat):
        g1, g = np.asarray(g1, np.float32), np.asarray(g, np.float32)
        err = np.linalg.norm(g - g1) / (np.linalg.norm(g1) + 1e-12)
        assert err < 5e-2, (jax.tree_util.keystr(path), err)
    # and the whole step runs, its state coming back where it was put
    state, metrics = res.train_step(res.state, res.place_batch(_batch()))
    assert np.isfinite(float(metrics["loss"]))
    k = state.params["h_0"]["mlp"]["c_fc"]["kernel"]
    assert k.sharding == res.state_shardings.params["h_0"]["mlp"]["c_fc"][
        "kernel"]


def test_one_device_model_holds_no_constraint(one_device):
    """The bypass is structural: without a mesh the helper hands back its
    argument, so a one-device step is the program it was before."""
    x = jnp.ones((2, 4, 8))
    assert pin_activation(x, None) is x
    assert pin_activation(x, build_mesh(MeshPlan(), jax.devices()[:1])) is x
    params = one_device[0]
    model = GPT(GPTConfig.nano())
    idx = jnp.zeros((2, 16), jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, idx))(params))
    assert "sharding_constraint" not in jaxpr


@pytest.mark.parametrize("plan,shape,want", [
    (MeshPlan(fsdp=4), (8, 64, 128), P(("dp", "fsdp"), None, None)),
    (MeshPlan(dp=2, fsdp=2, tp=2), (8, 64, 128),
     P(("dp", "fsdp"), None, None)),
    (MeshPlan(fsdp=2, sp=2), (8, 64, 128), P(("dp", "fsdp"), "sp", None)),
    # a batch-of-one init: nothing to say about a dimension the axes do
    # not divide
    (MeshPlan(fsdp=4), (1, 8, 128), P(U, None, None)),
])
def test_activation_spec(plan, shape, want):
    mesh = build_mesh(plan, jax.devices()[:plan.num_devices])
    assert activation_spec(mesh, shape) == want


def test_activation_spec_of_nothing():
    assert activation_spec(None) is None
    assert activation_spec(build_mesh(MeshPlan(), jax.devices()[:1])) is None


def test_batch_is_left_to_an_enclosing_shard_map():
    """Inside a pipeline stage or a DiLoCo group the batch dimension is
    the enclosing map's; the pin states the rest."""
    mesh = build_mesh(MeshPlan(dp=2, fsdp=2), jax.devices()[:4])
    seen = []

    def body(x):
        seen.append(activation_spec(mesh, x.shape))
        return pin_activation(x, mesh)

    x = jnp.ones((8, 16, 32))
    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                                out_specs=P("dp"), axis_names={"dp"}))(x)
    assert seen == [P(U, None, None)]
    np.testing.assert_array_equal(out, x)
