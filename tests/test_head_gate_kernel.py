"""`ops/head_gate.py`'s pair `dwt_gate` / `dwt_gate_bwd` in interpret mode
(no chip): against `LlamaAttention`'s plain line and JAX's own
differentiation of it, a gated layer on either route, and the counter
it sows of the route (which calls `gate_route` hands to the pair is
tests/test_kernel_site.py's table).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.models.attention import collect_attention_stats
from dlrover_wuqiong_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    rope_freqs,
)
from dlrover_wuqiong_tpu.ops import head_gate


def _plain(y, g):
    """`LlamaAttention`'s line on the plain route."""
    return (y * jnp.repeat(g, y.shape[-1] // g.shape[-1], axis=-1)
            ).astype(y.dtype)


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("b,t,heads,d,tile", [
    (1, 256, 48, 128, 96),   # a full layer's heads; a last tile of 64 rows
    (2, 128, 64, 128, 64),   # a sliding layer's, two batch rows
    (2, 128, 64, 128, 48),   # the same with a ragged last tile
    (1, 40, 3, 256, 16),     # a head of two slabs
])
def test_the_pair_is_the_plain_line_and_its_gradient(b, t, heads, d, tile):
    """y' bit for bit (float32 product, rounded once), dy too (the same
    product of the cotangent), dg to a float32 sum's rounding — through
    `jax.checkpoint`, as a step under full recomputation runs it."""
    keys = jax.random.split(jax.random.PRNGKey(heads), 3)
    y, d_out = (jax.random.normal(k, (b, t, heads * d), jnp.bfloat16)
                for k in keys[:2])
    g = jax.nn.sigmoid(jax.random.normal(keys[2], (b, t, heads)))
    kernels = jax.checkpoint(functools.partial(
        head_gate._gate_kernels, tile=tile, interpret=True))
    got, vjp = jax.vjp(kernels, y, g)
    want, plain_vjp = jax.vjp(_plain, y, g)
    assert got.dtype == jnp.bfloat16 and got.shape == y.shape
    np.testing.assert_array_equal(_f32(got), _f32(want))
    (dy, dg), (want_dy, want_dg) = vjp(d_out), plain_vjp(d_out)
    assert dy.dtype == jnp.bfloat16 and dg.dtype == jnp.float32
    assert dg.shape == g.shape
    np.testing.assert_array_equal(_f32(dy), _f32(want_dy))
    np.testing.assert_allclose(dg, want_dg, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want_dg).max()))


def _gated_layer(d):
    cfg = LlamaConfig(hidden_size=64, num_heads=4, num_kv_heads=2,
                      attn_head_dim=d, dtype=jnp.float32, attn_gate=True,
                      use_flash_attention=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    cos, sin = rope_freqs(d, 32, 100.0)
    layer = LlamaAttention(cfg)
    params = layer.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def run(params, x):
        out, upd = layer.apply({"params": params}, x, cos, sin,
                               mutable=["intermediates"])
        return (out * w).sum(), upd["intermediates"]

    return run, params, x


def test_a_gated_layer_on_the_kernel_is_the_plain_routes(monkeypatch):
    """The gate's route alone said to be the kernel's (the attention
    before it stays the `jax.numpy` one, whose y is the same array on
    both): the layer's output and every gradient are the plain
    route's, and the layer sows which one it took."""
    run, params, x = _gated_layer(128)
    grad = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
    (want, sown), want_grads = grad(params, x)
    assert float(sown["attn_gate_kernel"][0]) == 0.0
    assert "dwt_gate" not in str(jax.make_jaxpr(run)(params, x))
    monkeypatch.setattr(head_gate, "gate_route", lambda *a: "kernel")
    monkeypatch.setattr(head_gate, "_gate_kernels", functools.partial(
        head_gate._gate_kernels, interpret=True))
    names = re.findall(r"name=(dwt_\w+)", str(jax.make_jaxpr(grad)(params, x)))
    assert sorted(names) == ["dwt_gate", "dwt_gate_bwd"]
    (got, sown), got_grads = grad(params, x)
    assert float(sown["attn_gate_kernel"][0]) == 1.0
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5
                                   * float(jnp.abs(b).max()),
                                   err_msg=str(path))


@pytest.mark.parametrize("d", [128, 16])
def test_the_share_of_gated_layers_on_the_kernel_is_none_off_the_tpu(d):
    """Off the TPU every gate is the plain line, whatever the head:
    `attn_gate_kernel_share` 0.0 beside `attn_gate_mean`; a model that
    gates nothing counts neither."""
    run, params, x = _gated_layer(d)
    stats = collect_attention_stats(run(params, x)[1])
    assert float(stats["attn_gate_kernel_share"]) == 0.0
    assert 0.0 < float(stats["attn_gate_mean"]) < 1.0
    assert collect_attention_stats({"layer": {"moe_rows_held": (1.0,)}}) == {}
