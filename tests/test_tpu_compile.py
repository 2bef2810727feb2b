"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
DESCRIBED v5e (no chip attached): what interpret mode cannot see — tiling
the compiler refuses, VMEM a kernel may not have, a kernel GSPMD cannot
partition — fails here, at no chip time.

The kernel wrappers are called directly (code that asks
`jax.default_backend()` sees the CPU here).  The persistent compile cache
is off around these: a described-topology entry is written but can never
be read back without a chip (on-chip-measurement guide §2).  Nothing
runs, so nothing here is a statement about results or speed.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import quantization as qz


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# (bh, T, d): GPT-2's heads at the default 8-head pack; a d=128 model's
# 4k context at pack 4 (the same kernel body, half the unroll — the
# pack-8 compile of this shape alone takes ~40 s)
SHAPES = [(8, 1024, 64), (4, 4096, 128)]


@pytest.mark.parametrize("bh,t,d", SHAPES)
def test_attention_forward_and_backward_compile(topo, bh, t, d):
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, sharding=one)
    blk = min(1024, t)
    sc = d ** -0.5
    fwd = _compile(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, True, sc, blk, blk, False), x, x, x)
    assert "dwt_fa_fwd" in fwd and "tpu_custom_call" in fwd
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, sc, blk, blk, False), x, x, x, x, lse, x)
    if t == blk:  # one block each way: the fused dq+dk+dv kernel
        assert "dwt_fa_bwd_fused" in bwd
    else:
        assert "dwt_fa_bwd_dq" in bwd and "dwt_fa_bwd_dkv" in bwd


def test_attention_split_backward_compiles_at_gpt2_shape(topo, monkeypatch):
    """DWT_FA_NO_FUSED — the tuner's `no-fused` candidate — takes the dq
    and dk/dv kernels where the default takes the fused one."""
    monkeypatch.setenv("DWT_FA_NO_FUSED", "1")
    monkeypatch.setenv("DWT_FA_PACK", "4")  # the tuner's `pack4`, and fast
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((8, 1024, 64), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((8, 1, 1024), jnp.float32, sharding=one)
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, 0.125, 1024, 1024, False),
        x, x, x, x, lse, x)
    assert "dwt_fa_bwd_dq" in bwd and "dwt_fa_bwd_dkv" in bwd


def test_int8_quantise_kernels_compile(topo, monkeypatch):
    monkeypatch.setattr(qz, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    shape = (768, 3072)
    rows = shape[0] * shape[1] // qz.BLOCK
    q = _compile(qz.quantize_int8_blockwise,
                 jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one))
    assert "dwt_int8_quant" in q
    dq = _compile(
        lambda qq, s: qz.dequantize_int8_blockwise(
            qq, s, shape[0] * shape[1], shape),
        jax.ShapeDtypeStruct((rows, qz.BLOCK), jnp.int8, sharding=one),
        jax.ShapeDtypeStruct((rows, 1), jnp.float32, sharding=one))
    assert "dwt_int8_dequant" in dq


def test_attention_on_a_four_chip_mesh_is_shard_mapped(topo, monkeypatch):
    """GSPMD refuses to partition a Mosaic kernel; on a multi-device mesh
    the model's attention must reach it through a shard_map, each chip
    running the kernel on its quarter of the batch."""
    import numpy as np
    from jax.sharding import Mesh

    from dlrover_wuqiong_tpu.models.attention import attend
    from dlrover_wuqiong_tpu.models.gpt import GPTConfig
    from dlrover_wuqiong_tpu.parallel.mesh import AXIS_ORDER

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    # models/attention.py bound the name at import
    monkeypatch.setattr("dlrover_wuqiong_tpu.models.attention._on_tpu",
                        lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4, 1, 1, 1), AXIS_ORDER)
    cfg = GPTConfig(n_head=2, n_embd=128, mesh=mesh)
    x = jax.ShapeDtypeStruct(
        (8, 256, cfg.n_head, cfg.head_dim), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))))

    def loss(q, k, v):
        return attend(q, k, v, cfg).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert "dwt_fa_fwd" in text and "dwt_fa_bwd_fused" in text
    # per-chip slab: (8 / 4) batch x 2 heads = bh 4
    assert "bf16[4,256,64]" in text

    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(lambda q, k, v: fa.mha(q, k, v), x, x, x)
