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

import collections
import contextlib
import json
import math
import os
import re
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dlrover_wuqiong_tpu.analysis.hlo_budget import iter_collectives
from dlrover_wuqiong_tpu.ops import flash_attention as fa
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.ops import mosaic
from dlrover_wuqiong_tpu.ops import quantization as qz
from dlrover_wuqiong_tpu.ops import short_conv, ssd
from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")


@contextlib.contextmanager
def _cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    with _cache_off():
        yield


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


class _Step:
    """What the tests read of a compiled step, as plain data: its text
    and the memory analysis' numbers."""

    _FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")

    def __init__(self, text: str, memory: dict):
        self._text, self._memory = text, memory

    @classmethod
    def of(cls, compiled):
        m = compiled.memory_analysis()
        return cls(compiled.as_text(), {k: getattr(m, k) for k in cls._FIELDS})

    def plain(self) -> list:
        return [self._text, self._memory]

    def as_text(self) -> str:
        return self._text

    def memory_analysis(self):
        return types.SimpleNamespace(**self._memory)


def _once_a_run(request, key: str, build):
    """`build(topo)`'s {name: _Step}, compiled ONCE A RUN however many
    xdist workers are handed tests of the file (`--dist load` sends them
    to whichever is free, and a module fixture is built a worker):
    pytest-xdist's recipe for a session's shared data — the first worker
    to take the lock beside the run's base directory builds and writes,
    the others read.  A whole step compiles for 25 to 180 s and its text
    reads back in well under one."""
    from filelock import FileLock

    base = request.getfixturevalue("tmp_path_factory").getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent  # the workers' own directories lie in the run's
    path = base / f"compiled_{key}.json"
    with FileLock(str(path) + ".lock"):
        if path.is_file():
            kept = json.loads(path.read_text())
        else:
            steps = build(request.getfixturevalue("topo"))
            kept = {name: step.plain() for name, step in steps.items()}
            path.write_text(json.dumps(kept))
    return {name: _Step(*data) for name, data in kept.items()}


# (bh, T, d, causal): GPT-2's heads at the default 8-head pack; GPT-2 XL's
# 100 heads a chip, which pack by 4; a d=128 model's 4k context at pack 4
# (the same kernel body, half the unroll — the pack-8 compile of this
# shape alone takes ~40 s); and ring attention's later steps, which are
# not causal.  The causal ones are cut into tiles below the diagonal
# (`fa._causal_bands`): static slices Mosaic has to take.
# The last column: 0 = the transposed (bh, t, d) arrays; else the heads
# of a batch row of the projections' own (b, t, h*d) layout, indexed
# where they lie — gpt2_124m.steady's whole batch of c_attn outputs
# (q, k and v in one array, two heads of 64 a lane slab, masks in the
# kernel) and olmoe_1b_7b.steady's (a head a slab, four blocks each way).
SHAPES = [(8, 1024, 64, True, 0), (4, 1024, 64, True, 0),
          (4, 4096, 128, True, 0), (4, 1024, 64, False, 0),
          (24 * 12, 1024, 64, True, 12), (5 * 16, 4096, 128, True, 16)]


@pytest.mark.parametrize("bh,t,d,causal,heads", SHAPES)
def test_attention_forward_and_backward_compile(topo, bh, t, d, causal,
                                                heads):
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((bh, 1, t), jnp.float32, sharding=one)
    blk = min(1024, t)
    sc = d ** -0.5
    assert fa.causal_tile_count(t, t) == {1024: (3, 4), 4096: (36, 64)}[t]
    kw = {}
    if heads:
        b, lanes = bh // heads, heads * d
        fused = d == 64  # GPT-2's c_attn; OLMoE projects q, k, v apart
        x = jax.ShapeDtypeStruct((b, t, lanes), jnp.bfloat16, sharding=one)
        qkv = jax.ShapeDtypeStruct((b, t, 3 * lanes), jnp.bfloat16,
                                   sharding=one)
        slabs, _ = fa._projected_slabs((qkv,) if fused else (x,) * 3, heads)
        assert slabs == ((6, 2, 128, (0, 6, 12), 1) if fused
                         else (16, 1, 128, (0, 0, 0), 1))
        kw = {"slabs": slabs}
    q = qkv if heads and fused else x
    fwd = _compile(lambda q, k, v: fa._fa_forward_pallas(
        q, k, v, causal, sc, blk, blk, False, **kw), q, q, q)
    assert "dwt_fa_fwd" in fwd and "tpu_custom_call" in fwd
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, causal, sc, blk, blk, False, **kw),
        q, q, q, x, lse, x)
    if heads:  # nothing is cut to heads or turned on the way in or out
        assert f"bf16[{bh},{t},{d}]" not in fwd + bwd
        assert f"bf16[{b},{t},{lanes}]" in bwd
    # one block each way, or several with a unit's whole dq resident:
    # ONE kernel gives dq, dk and dv (`fa.backward_route`)
    assert "dwt_fa_bwd_fused" in bwd
    assert "dwt_fa_bwd_dq" not in bwd and "dwt_fa_bwd_dkv" not in bwd
    if t > blk:  # the pair a sequence that does not fit would take
        pair = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
            q, k, v, o, l, do, causal, sc, blk, blk, False,
            route=("split", 1 if heads else fa._fit_pack(bh)), **kw),
            q, q, q, x, lse, x)
        assert "dwt_fa_bwd_dq" in pair and "dwt_fa_bwd_dkv" in pair


@pytest.mark.parametrize("route,names", [
    (None, ["dwt_fa_bwd_fused"]),
    (("split", 4), ["dwt_fa_bwd_dkv", "dwt_fa_bwd_dq"])])
def test_attention_several_block_backward_compiles_at_gpt2_shape(
        topo, route, names):
    """Blocks of 512 at T = 1024 are a 2 x 2 grid: the backward is the
    sweep that keeps four heads' whole dq in VMEM, or by `route=` the dq
    and dk/dv kernels."""
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((4, 1024, 64), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((4, 1, 1024), jnp.float32, sharding=one)
    bwd = _compile(lambda q, k, v, o, l, do: fa._fa_backward_pallas(
        q, k, v, o, l, do, True, 0.125, 512, 512, False, route=route),
        x, x, x, x, lse, x)
    assert sorted(set(re.findall(r"dwt_fa_bwd_[a-z]+", bwd))) == names


def test_int8_quantise_kernels_compile(topo, on_tpu):
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


def test_attention_on_a_four_chip_mesh_is_shard_mapped(topo, on_tpu):
    """GSPMD refuses to partition a Mosaic kernel; on a multi-device mesh
    the model's attention must reach it through a shard_map, each chip
    running the kernel on its quarter of the batch."""
    import numpy as np
    from jax.sharding import Mesh

    from dlrover_wuqiong_tpu.models.attention import attend
    from dlrover_wuqiong_tpu.models.gpt import GPTConfig
    from dlrover_wuqiong_tpu.parallel.mesh import AXIS_ORDER

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


# ------------------------------------------------- the sharded step's layout

XL_BATCH, XL_SEQ, XL_WIDTH = 16, 1024, 1600


@pytest.fixture(scope="module")
def xl_fsdp4_steps(request):
    """{depth: step} of `gpt2_xl.fsdp4_steady`'s step at 2 and 3
    layers: XL widths, batch 16 x 1024, full remat, Trainer's optimizer,
    fsdp over the described 2x2 (about 25 s a compile, once a run)."""
    steps = _once_a_run(request, "gpt2_xl.fsdp4", _xl_fsdp4_steps)
    return {int(depth): step for depth, step in steps.items()}


def _xl_fsdp4_steps(topo):
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    steps = {}
    with pytest.MonkeyPatch.context() as mp, _cache_off():
        mp.setenv("DWT_COMPILE_CACHE", "0")
        mp.setattr(mosaic, "on_tpu", lambda: True)
        for depth in (2, 3):
            cfg = GPTConfig(n_layer=depth, n_head=25, n_embd=XL_WIDTH,
                            remat=True, remat_policy="full")
            res = auto_accelerate(
                GPT(cfg), strategy=[("fsdp", {})], devices=topo.devices,
                optimizer=optax.chain(optax.clip_by_global_norm(1.0),
                                      optax.adamw(3e-4, weight_decay=0.1)),
                materialize=False, seq_len=XL_SEQ)
            assert res.model.config.mesh is res.mesh
            ids = jax.ShapeDtypeStruct((XL_BATCH, XL_SEQ), jnp.int32,
                                       sharding=res.batch_sharding_fn(2))
            steps[str(depth)] = _Step.of(res.train_step.lower(
                res.state, {"input_ids": ids, "labels": ids}).compile())
    return steps


@pytest.mark.parametrize("depth", [2, 3])
def test_fsdp4_attention_stays_on_the_transposed_route(xl_fsdp4_steps,
                                                       depth):
    """25 heads of 64 are 12.5 lane slabs: XL's attention keeps the
    (b*h, t, d) arrays inside its shard_map — 4 rows x 25 heads a chip —
    and the kernel calls it always had: a layer's forward, its
    recomputed forward and its fused backward."""
    assert fa.attention_route(25, 64) == ("transposed", 0)
    text = xl_fsdp4_steps[depth].as_text()
    calls = re.findall(r"= \(?([^=]*?)\)? custom-call\([^\n]*"
                       r"op_name=\"[^\"]*/(dwt_fa_\w+)/", text)
    names = [name for _, name in calls]
    assert names.count("dwt_fa_fwd") == 2 * depth
    assert names.count("dwt_fa_bwd_fused") == depth
    assert all("bf16[100,1024,64]" in shapes for shapes, _ in calls)


def test_fsdp4_all_to_alls_do_not_grow_with_depth(xl_fsdp4_steps):
    """The `wte` lookup and its scatter-add re-lay the embedding once a
    step; a block re-lays nothing (a residual stream left to the
    partitioner costs 8 all-to-alls a layer)."""
    counts = {depth: sum(op == "all-to-all" for op, _, _ in
                         iter_collectives(step.as_text()))
              for depth, step in xl_fsdp4_steps.items()}
    assert counts == {2: 2, 3: 2}


@pytest.mark.parametrize("depth", [2, 3])
def test_fsdp4_blocks_move_weights_not_activations(xl_fsdp4_steps, depth):
    found = list(iter_collectives(xl_fsdp4_steps[depth].as_text()))
    in_blocks = [c for c in found if re.search(r"/h_\d+/", c[2])]
    assert in_blocks
    kernel_dims = {XL_WIDTH, 3 * XL_WIDTH, 4 * XL_WIDTH}
    for op, shapes, name in in_blocks:
        # weight-shaped only: a kernel gathered where it is used, bias
        # gradients summed — never [batch, seq, *]
        assert op in ("all-gather", "all-reduce") and all(
            len(dims) <= 2 and set(dims) <= kernel_dims
            for _, dims in shapes), (op, shapes, name)
    # the compiler's unnamed collectives (gradient sums, padded gathers):
    # nothing of rank 3 or more beyond a chip's share of one activation
    share = XL_BATCH // 4 * XL_SEQ * XL_WIDTH
    for op, shapes, name in found:
        assert all(len(dims) < 3 or math.prod(dims) <= share
                   for _, dims in shapes), (op, shapes, name)


def test_fsdp4_step_temporaries_fit(xl_fsdp4_steps):
    """A step whose dense layers all-reduce full-batch activations holds
    2.92 GiB of temporaries at two layers."""
    temp = xl_fsdp4_steps[2].memory_analysis().temp_size_in_bytes
    assert temp < 1.2 * 2 ** 30, temp / 2 ** 30


# ------------------------------------- the control's step on one chip

def _one_chip_step(request, name, model_file, **config):
    """Cell `name`'s step as its configuration file builds it (`config`
    replaces top-level keys of the file), the cell's batch, Trainer's
    optimizer, compiled for ONE described v5e chip, once a run
    (`_once_a_run`): (cell, model, step) — the step as the tests read
    it, its text and its memory analysis."""
    from benchmark import cells

    cell = cells.load_cell(name)
    cell["config"].update(config)
    model = cells.load_module("models", model_file).build(cell["config"])
    key = "_".join([name, *(f"{k}={v}" for k, v in sorted(config.items()))])
    step = _once_a_run(request, key, lambda topo: {
        "step": _compile_one_chip_step(topo, cell, model)})["step"]
    return cell, model, step


def _compile_one_chip_step(topo, cell, model):
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    with pytest.MonkeyPatch.context() as mp, _cache_off():
        mp.setenv("DWT_COMPILE_CACHE", "0")
        # every kernel module at once, one added later too: the backend
        # is read in one place (`ops/mosaic.on_tpu`)
        mp.setattr(mosaic, "on_tpu", lambda: True)
        res = auto_accelerate(
            model, strategy=[("fsdp", {})], devices=topo.devices[:1],
            optimizer=optax.chain(optax.clip_by_global_norm(1.0),
                                  optax.adamw(3e-4, weight_decay=0.1)),
            materialize=False, seq_len=cell["seq_len"])
        ids = jax.ShapeDtypeStruct(
            (cell["global_batch"], cell["seq_len"]), jnp.int32,
            sharding=res.batch_sharding_fn(2))
        return _Step.of(res.train_step.lower(
            res.state, {"input_ids": ids, "labels": ids}).compile())


@pytest.fixture(scope="module")
def gpt2_124m_step(request):
    """`gpt2_124m.steady`'s step (about 30 s)."""
    return _one_chip_step(request, "gpt2_124m.steady", "gpt")


def test_124m_step_moves_nothing_around_its_attention_kernels(
        gpt2_124m_step):
    """The compiled program's own count of the splits, cuts to heads
    and transposes under the `attn` scope outside the dense layers and
    the kernels (`hlo_scopes.relayouts`): the transposed route had 192 a
    step (168 copies, 12 reshapes, 12 slicing fusions).  What stays is
    the join of dq, dk and dv into c_attn's cotangent: two
    dynamic-update-slice fusions a layer under `attn` (the second
    carries no name of its own and is its reader's, the first's: PR 35
    counts it), the third under `c_attn`."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import relayouts

    cell, _, step = gpt2_124m_step
    text = step.as_text()
    moved = relayouts(text, "attn", outside=("c_attn", "c_proj"))
    assert sorted(moved.values()) == ["fusion"] * 24, moved
    assert all("dynamic-update-slice" in name for name in moved)
    b, t = cell["global_batch"], cell["seq_len"]
    # the kernels read c_attn's output and write c_proj's input
    assert text.count("dwt_fa_fwd") and f"bf16[{b * 12},{t},64]" not in text
    assert f"operand_layout_constraints={{bf16[{b},{t},2304]" in text


# ------------------------------------------------- OLMoE's step on one chip

@pytest.fixture(scope="module")
def olmoe_step(request):
    """`olmoe_1b_7b.steady`'s step — published widths, depth 1, all 64
    experts, the cell's batch of 4096-token sequences (about 50 s)."""
    return _one_chip_step(request, "olmoe_1b_7b.steady", "olmoe")


def test_olmoe_step_fits_one_chip_and_fills_it(olmoe_step):
    """State + temporaries: under 90% of the chip's 16 GB (the batch is
    the largest that is), over 75% (a smaller cell leaves the chip
    empty)."""
    cell, model, step = olmoe_step
    assert model.config.num_params() == 625_616_896
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    assert 0.75 * 16e9 < live < 0.90 * 16e9, live / 1e9
    # the state is donated: 12 B a parameter in, the same out
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_olmoe_step_runs_the_kernels_at_d128_t4096(olmoe_step):
    cell, _, step = olmoe_step
    text = step.as_text()
    for kernel in ("dwt_fa_fwd", "dwt_fa_bwd_fused"):
        assert kernel in text, kernel
    assert "dwt_fa_bwd_dq" not in text and "dwt_fa_bwd_dkv" not in text
    # a head is a lane slab: the kernels index the projections' own
    # (batch, 4096, 16 x 128), nothing is laid out by head
    b = cell["global_batch"]
    assert fa.attention_route(16, 128) == ("direct", 1)
    assert f"operand_layout_constraints={{bf16[{b},4096,2048]" in text
    assert f"bf16[{b * 16},4096,128]" not in text


def test_olmoe_step_moves_little_around_its_attention_kernels(olmoe_step):
    """`hlo_scopes.relayouts` under `attention` outside the projections
    and QK-norm.  What stays: delta's turn to (b*h, 1, t) (a copy and a
    reshape of a (b, t, h) array) and, nameless between two bitcasts of
    the backward pass and so its reader's since PR 35, a copy of a
    (b*t/8, 8, h, d) float32 array.  RoPE's half-swap on the (b, t, h*d)
    rows moved twelve more until PR 44 (the two lane rolls of q and of
    k: four slicing fusions and two copies forward, six slices
    backward); it is `ops/rope.py`'s kernel now, one call for q and one
    for k, forward and backward."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import relayouts

    text = olmoe_step[2].as_text()
    moved = relayouts(text, "attention", outside=(
        "q_proj", "k_proj", "v_proj", "o_proj", "qk_norm"))
    assert sorted(moved.values()) == ["copy"] * 2 + ["reshape"], moved
    b = olmoe_step[0]["global_batch"]
    assert collections.Counter(re.findall(
        r"%dwt_rope[.\d]* = (\w+\[[\d,]+\])", text)) == {
            f"bf16[{b},4096,2048]": 4}


def test_olmoe_step_keeps_its_scopes_and_names_the_grouped_matmuls(
        olmoe_step):
    """The TPU compiler puts kernels of its own in place of
    `lax.ragged_dot` and writes its name over the traced one; the scope
    table calls them `ragged_dot`, everything else keeps the program's
    scopes."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    table = scope_table(olmoe_step[2].as_text())
    ragged = [n for n in table if n.startswith("ragged-dot-none")]
    assert len(ragged) == 9  # three forward, their six transposes
    assert {table[n] for n in ragged} == {"ragged_dot"}
    scopes = set(table.values())
    for part in ("feed_forward/moe/router", "feed_forward/moe/dispatch",
                 "feed_forward/moe/experts", "feed_forward/moe/combine",
                 "feed_forward/moe/aux", "attention/qk_norm/q_norm",
                 "attention/q_proj", "Llama/head", "loss", "optimizer"):
        assert any(part in s for s in scopes), part


# ------------------------------------- Nemotron-3-Nano's step on one chip

@pytest.fixture(scope="module")
def nemotron_step(request):
    """`nemotron3_nano_30b_a3b.steady`'s step — published widths, nine
    layers (4 Mamba-2, 4 expert, 1 attention), 8 of 128 experts held, the
    cell's batch of 8192-token sequences, full recomputation (about
    50 s)."""
    return _one_chip_step(request, "nemotron3_nano_30b_a3b.steady",
                          "nemotron_h")


def test_nemotron_step_fits_one_chip_and_fills_it(nemotron_step):
    """State + temporaries: under 90% of the chip's 16 GB (the batch is
    the largest that is: 12.4 / 13.7 / 15.0 GB live at 1 / 2 / 3
    sequences), far over the 25% a cell has to fill."""
    cell, model, step = nemotron_step
    assert model.config.num_params() == 666_963_456
    assert cell["global_batch"] == 2 and cell["seq_len"] == 8192
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    assert 0.25 * 16 * 2 ** 30 < 0.80 * 16e9 < live < 0.90 * 16e9, live / 1e9
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_nemotron_step_runs_the_kernels_at_d128_t8192(nemotron_step):
    cell, _, step = nemotron_step
    text = step.as_text()
    # the forward a kv head's sixteen query heads in four grid steps of
    # four (`fa.forward_route`, PR 67), the backward the slab sweep
    assert fa.forward_route(8192, 8192, 128, 16) == ("group", 4)
    for kernel in ("dwt_fa_grp_fwd", "dwt_fa_bwd_fused"):
        assert kernel in text, kernel
    assert "dwt_fa_fwd" not in text
    assert "dwt_fa_bwd_dq" not in text and "dwt_fa_bwd_dkv" not in text
    # 32 heads of 128 on a hidden size of 2688: a head is a lane slab, the
    # kernels index the projections' own (batch, 8192, 32 x 128) and, for
    # k and v, the 2 kv heads' own (batch, 8192, 256): query slab s reads
    # kv slab s // 16 and nothing repeats them (`fa.kv_route`)
    b = cell["global_batch"]
    assert fa.attention_route(32, 128) == ("direct", 1)
    assert fa.kv_route(32, 2, 128) == ("indexed", 16)
    assert (f"operand_layout_constraints={{bf16[{b},8192,4096]{{2,1,0}}, "
            f"bf16[{b},8192,256]{{2,1,0}}, bf16[{b},8192,256]{{2,1,0}}"
            in text)
    assert f"bf16[{b * 32},8192,128]" not in text
    assert _repeated_kv_ops(text, b, 8192, 32, 128) == []


def _repeated_kv_ops(text, b, t, heads, d):
    """Device ops under `attention`, outside its projections and its
    kernels, with a bfloat16 result the size of a k or v repeated to
    the query heads, whatever axes it is cut into ((b, t, heads*d),
    (b, t, kv, rep, d), ...): what GQA's repeat leaves in a step — the
    broadcasts, the copies of the repeated arrays into the kernels'
    operand layout and the re-layouts of dk and dv before their group
    sums.  Empty where `fa.kv_route` says "indexed": the kernels read
    the kv heads' own arrays, and dk and dv a query head are the
    kernels' own results, summed by a `reduce` to the kv heads' width."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import owners

    own = owners(text)
    found = []
    for name, dims, op in re.findall(
            r"^\s*%([\w.\-]+) = bf16\[([\d,]+)\]\S* ([\w\-]+)\(", text,
            re.M):
        scope = own.get(name, {}).get("scope", "")
        if op in ("bitcast", "get-tuple-element", "parameter") \
                or "attention" not in scope \
                or re.search(r"[qkvo]_proj|dwt_", scope):
            continue
        if math.prod(map(int, dims.split(","))) == b * t * heads * d:
            found.append((op, dims))
    return sorted(found)


def _grouped_kernel_calls(text, kernels="dwt_gmm|dwt_tgmm"):
    """{custom call: (scope, the bf16 shapes of its result and operands)}
    of the `dwt_gmm*` / `dwt_tgmm*` kernels (or of `kernels`) in a
    compiled step's text."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    table = scope_table(text)
    calls = {}
    for line in text.splitlines():
        m = re.match(rf"\s*(?:ROOT )?%((?:{kernels})[\w.]*) = ", line)
        if m and "custom-call(" in line:
            calls[m.group(1)] = (table[m.group(1)],
                                 re.findall(r"bf16\[([\d,]+)\]", line))
    return calls


def _rows_map_calls(text):
    """{(kernel, its first result's shape): custom calls} of the
    `dwt_rows_map_*` kernels in a compiled step's text, every one under
    `moe/experts` or `moe/combine`."""
    calls = _grouped_kernel_calls(text, "dwt_rows_map")
    assert all("/moe/experts/" in scope or "/moe/combine/" in scope
               for scope, _ in calls.values()), calls
    return collections.Counter(
        (re.sub(r"\.\d+$", "", name), shapes[0])
        for name, (_, shapes) in calls.items())


def _row_buffer_walkers(text, rows):
    """The device ops a compiled step still runs over a whole (rows,
    width) buffer under `moe/experts` or `moe/combine`, the row gathers
    left out (a fusion that holds a `gather`; per-row arrays (rows, 1)
    are no buffer), the kernels too (their grids follow the held rows)
    and the loop that fills the cotangent's buffer by chunks of the held
    rows (the `while` holds its body's ops, and the body's
    `dynamic-update-slice`, alone or in a fusion, writes a chunk in
    place): what an elementwise pass of `jax.numpy` over the T*k-row
    buffer compiles to."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        owners, parse_computations)

    comps = parse_computations(text)
    ins = {i["name"]: i for body in comps.values() for i in body}
    buffer = re.compile(rf"\[{rows},(?!1\])\d+\]")
    found = []
    for name, entry in owners(text).items():
        i = ins.get(name)
        if i is None or i["opcode"] in (
                "custom-call", "parameter", "bitcast", "get-tuple-element",
                "tuple", "while", "dynamic-update-slice") or not any(
                    s in entry["scope"]
                    for s in ("moe/experts", "moe/combine")):
            continue
        shapes = [i["shape"]] + [ins[o]["shape"] for o in i["operands"]
                                 if o in ins]
        if not any(buffer.search(s) for s in shapes):
            continue
        if any(m["opcode"] in ("gather", "dynamic-update-slice")
               for m in comps.get(i["calls"], [])):
            continue
        found.append((name, i["opcode"], entry["scope"]))
    return found


def _held_row_loops(text, rows, width, layers, chunk=8192, sum_chunk=4096,
                    k=6):
    """The ops of a compiled step that hold other ops: none but the
    `while`s of `models/moe.py` on the kernel route, five an expert
    layer.  Three are `dispatch`'s — forward and recomputed under
    `moe/dispatch`, the cotangent's under `moe/combine` — and a turn of
    theirs gathers (chunk, width); two are the sums by assignment over
    the held rows (`_sum_held`) — `combine`'s under `moe/combine` in
    the forward pass, `dispatch`'s backward under `moe/dispatch` in the
    backward pass (a recomputed forward pass stops at the rows) — and a
    turn of theirs gathers a chunk of their own size and its halo.
    Each is over a buffer that starts unwritten (`dwt_rows_unwritten`)
    and is carried, (rows, width), with no copy of it at the loop's
    entry or exit, and a turn writes its chunk in place.  Behind a sum's loop ONE gather of
    (rows / k, width) reads each token's sum.  No gather of the step
    has a (rows, width) or a (k, rows / k, width) result: none has an
    index list of T*k entries.  What is left per T*k entry is the
    sorts: two a forward pass, recomputed too (the assignments into
    expert order, the held rows into assignment order), and one more in
    the backward pass (the dots back by assignment: its key is `order`
    itself, where the parent folded it into the sort that inverted
    `order`).  Returns the row gathers' result shapes, counted."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        instructions_of, parse_computations, scope_of)
    from dlrover_wuqiong_tpu.models.moe import _halo

    comps = parse_computations(text)
    every = [i for body in comps.values() for i in body]
    assert " conditional(" not in text
    loops = [i for i in every if i["opcode"] == "while"]
    assert collections.Counter(
        scope_of(i["op_name"]).split("/")[0] + "/"
        + scope_of(i["op_name"]).rsplit("moe/", 1)[1]
        for i in loops) == {"fwd/dispatch": layers,
                            "recompute/dispatch": layers,
                            "bwd/combine": layers,
                            "fwd/combine": layers,
                            "bwd/dispatch": layers}
    assert collections.Counter(
        scope_of(i["op_name"]).split("/")[0] for i in every
        if i["opcode"] == "sort" and f"s32[{rows}]" in i["shape"]) == {
            "fwd": 2 * layers, "recompute": 2 * layers, "bwd": layers}
    buffer, turn = f"bf16[{rows},{width}]", f"bf16[{chunk},{width}]"
    summed = f"bf16[{sum_chunk + _halo(k)},{width}]"
    placed = f"bf16[{rows // k},{width}]"
    assert all(buffer in i["shape"] for i in loops)
    assert len([i for i in every if i["opcode"] == "custom-call"
                and i["name"].startswith("dwt_rows_unwritten")
                and i["shape"].startswith(buffer)]) == 5 * layers
    assert not [i["name"] for i in every if i["shape"].startswith(buffer)
                and i["opcode"] in ("copy", "copy-start")]
    bodies = {name for line in text.splitlines() if " while(" in line
              for name in re.findall(r"body=%?([\w.\-]+)", line)}
    assert len(bodies) == 5 * layers
    in_place = [i for name in bodies for i in comps[name]
                if i["shape"].startswith(buffer)
                and (i["opcode"] == "dynamic-update-slice" or any(
                    m["opcode"] == "dynamic-update-slice"
                    for m in comps.get(i["calls"], [])))]
    assert len(in_place) == 5 * layers
    found = instructions_of(text, "gather", "moe")
    gathers = collections.Counter(
        shape.split("{")[0] for shape in found.values()
        if shape.startswith("bf16["))
    assert buffer not in gathers
    assert f"bf16[{k},{rows // k},{width}]" not in gathers
    assert gathers == {turn: 3 * layers, summed: 2 * layers,
                       placed: 2 * layers}
    # the chunks' gathers stand in the loops' bodies and nowhere else,
    # the tokens' behind them
    home = {i["name"]: name for name, body in comps.items() for i in body}
    called = {i["calls"]: home[i["name"]] for i in every if i["calls"]}
    for name, shape in found.items():
        if shape.startswith("bf16["):
            inside = called.get(home[name], home[name]) in bodies
            assert inside == (not shape.startswith(placed)), name
    return gathers


def _index_ops_of_numbers(text, width):
    """The gathers and scatters a compiled step runs under `moe` that
    move single NUMBERS — (opcode, result shape, scope) of each, a
    device op of its own or a member of a fusion however deep (the
    compiler nests them and drops their names there: the transpose of
    `take_along_axis` was a nameless scatter two fusions down, which
    `instructions_of` cannot see), every one but the row gathers, whose
    result's last dimension is the model's `width`.  On the TPU such an
    op costs the length of its index list whatever an entry weighs; the
    expert layer's bookkeeping holds none (PERF.md section 6, PR 45):
    it counts by compare-and-sum, carries the gates and the dots in its
    sorts and reads the scores by a select."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        owners, parse_computations)

    comps = parse_computations(text)
    ins = {i["name"]: i for body in comps.values() for i in body}
    found = []

    def walk(i, scope):
        shape = i["shape"].split("{")[0]
        if i["opcode"] == "scatter" or (
                i["opcode"] == "gather"
                and not shape.endswith(f",{width}]")):
            found.append((i["opcode"], shape, scope))
        for member in comps.get(i["calls"], []):
            walk(member, scope)

    for name, entry in owners(text).items():
        if "/moe/" in f"/{entry['scope']}/" and name in ins:
            walk(ins[name], entry["scope"])
    return found


def test_nemotron_step_keeps_its_scopes_and_holds_eight_experts(
        nemotron_step):
    """Every scope the per-layer metrics read is in the compiled step.
    The expert layers run the whole layer's path over the sorted T*k =
    98,304-row buffer on 8 groups, and since a share's buffer is mostly
    empty its grouped products are the `dwt_gmm` / `dwt_gmm_t` /
    `dwt_tgmm` kernels of `ops/grouped_matmul.py` (eight a layer: two
    forward, two recomputed, four backward), every one under
    `feed_forward/moe/experts`, none of the compiler's `ragged-dot`
    kernels: every weight operand holds the 8 held experts, none the
    published 128, and the group sizes are 8 numbers — an assignment to
    an absent expert has no group.  What holds other ops in the step
    (a `while`, which a device trace counts beside the ops it ran) is
    the twenty loops over the held rows' chunks, no `conditional`."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, _, step = nemotron_step
    text = step.as_text()
    scopes = set(scope_table(text).values())
    for part in ("mamba/in_proj", "mamba/conv", "mamba/ssd",
                 "mamba/gate_norm", "mamba/out_proj",
                 "feed_forward/moe/shared", "feed_forward/moe/router",
                 "feed_forward/moe/dispatch", "feed_forward/moe/experts",
                 "feed_forward/moe/combine", "attention/q_proj",
                 "attention/o_proj", "NemotronH/head", "loss", "optimizer"):
        assert any(part in s for s in scopes), part
    assert not any("moe/aux" in s for s in scopes)  # no auxiliary loss
    rows = cell["global_batch"] * 8192 * 6
    assert gm.gmm_route((rows, 2688), (8, 2688, 1856), 128) == "plain"  # CPU
    calls = _grouped_kernel_calls(text)
    assert len(calls) == 32 and "ragged-dot" not in text
    assert all("feed_forward/moe/experts/dwt_" in scope
               for scope, _ in calls.values()), calls
    shapes = {s for _, operands in calls.values() for s in operands}
    assert shapes == {f"{rows},1856", f"{rows},2688", "8,2688,1856",
                      "8,1856,2688"}
    assert "[128,2688,1856]" not in text and "[128,1856,2688]" not in text
    # 128 numbers appear where the bias's rule counts every expert's
    # load (dispatch) and steps the bias (out_of_band), nowhere else
    assert "s32[8]" in text
    wide = [ln for ln in text.splitlines() if "s32[128]" in ln]
    assert wide and all("moe/dispatch" in ln or "out_of_band" in ln
                        for ln in wide if "op_name=" in ln)
    assert any("out_of_band" in s for s in scopes)
    _held_row_loops(text, rows, 2688, layers=4)


def test_nemotron_step_walks_its_row_buffer_in_gathers_alone(nemotron_step):
    """The elementwise passes of a share's four expert layers are
    `dwt_rows_map_*` kernels over the tiles that hold a held row: relu^2
    forward and recomputed (8) and its backward (4) over (T*k, 1856)
    under `moe/experts`, the combine's backward pair (4) over (T*k,
    2688) under `moe/combine` — and no fusion under either scope still
    has a (T*k, width) operand.  The gathers INTO expert order walk the
    held rows' chunks: twelve loops whose turn gathers (8192, 2688) —
    where the parent ran twelve gathers of (T*k, 2688), four of them
    (the cotangent's) from an 88 MB source staged in VMEM (`S(1)`,
    PERF.md section 6, PR 38) — and a turn's source or its result is
    still staged there.  The sums BY ASSIGNMENT walk them too (PR 50):
    eight loops whose turn gathers (4096 + 16, 2688) and one gather of
    (T, 2688) behind each, where eight gathers of (k, T, 2688) stood;
    a turn's rows in float32, the chunk its k - 1 shifted adds read,
    are staged in VMEM."""
    cell, _, step = nemotron_step
    text = step.as_text()
    rows = cell["global_batch"] * 8192 * 6
    assert _rows_map_calls(text) == {
        ("dwt_rows_map_relu2", f"{rows},1856"): 8,
        ("dwt_rows_map_relu2_bwd", f"{rows},1856"): 4,
        ("dwt_rows_map_weigh", f"{rows},2688"): 4}
    assert _row_buffer_walkers(text, rows) == []
    gathers = _held_row_loops(text, rows, 2688, layers=4)
    assert gathers == {"bf16[8192,2688]": 12, "bf16[4112,2688]": 8,
                       f"bf16[{rows // 6},2688]": 8}
    # a map reserves the VMEM it holds and says what it costs at most
    # (PR 38), so the compiler still stages in VMEM (`S(1)`) what a turn
    # reads or what it writes: the (T, 2688) source in the loop's carry,
    # or the gathered chunk
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import parse_computations

    comps = parse_computations(text)
    staged = summed = 0
    for line in text.splitlines():
        if " while(" not in line:
            continue
        body = comps[re.search(r"body=%?([\w.\-]+)", line).group(1)]
        source = re.search(rf"bf16\[{rows // 6},2688\]\{{[^}}]*\}}", line)
        if source is None:  # a sum by assignment carries no tokens
            weighed = [i["shape"] for i in body
                       if i["shape"].startswith("f32[4112,2688]")]
            summed += bool(weighed) and all("S(1)" in s for s in weighed)
            continue
        chunks = [i["shape"] for i in body
                  if i["shape"].startswith("bf16[8192,2688]")]
        staged += "S(1)" in source.group(0) or all(
            "S(1)" in shape for shape in chunks)
    assert (staged, summed) == (12, 8)


# --------------------------------- granite-4.0-h-micro's step on one chip

@pytest.fixture(scope="module")
def granite_step(request):
    """`granite4_h_micro.steady`'s step — published widths, one period
    (nine Mamba-2 layers at ONE state group and chunk 256, one attention
    layer, a SwiGLU of 8192 behind each), the tied head on an eighth of
    the table, one 8192-token sequence, full recomputation (about
    60 s)."""
    return _one_chip_step(request, "granite4_h_micro.steady", "granite_hybrid")


def test_granite_step_fits_one_chip_by_the_rule_and_fills_it(granite_step):
    """State + temporaries under 90% of the chip's 16 GB (PR 26's rule)
    at the shipped sizes: 11.92 GB, of which 9.27 GB is donated state —
    0.55 GB under the reading the configuration file records for its
    memory rung (a), 12.47 GB, taken on the plain scan (PR 33): the
    kernels keep a layer's 537 MB decay tensor out of HBM and save 67 MB
    of entering states, and since PR 59 the convolution's pair reads
    xBC where it lies in the projection's output and holds no padded
    copy of it (12.11 GB before it).  A second sequence doubles the
    2.7 GB of temporaries: over."""
    cell, model, step = granite_step
    assert model.config.num_params() == 772_160_448
    assert (cell["global_batch"], cell["seq_len"],
            model.config.chunk_size, model.config.n_groups) == \
        (1, 8192, 256, 1)
    m = step.memory_analysis()
    live = compiled_memory(step)["live_bytes"]
    rung = cell["config"]["train"]["memory_rung"]
    assert live / 1e9 == pytest.approx(11.92, abs=0.05)
    assert live / 1e9 < rung["live_GB"]["a: 1 x 8192, chunk 256"] - 0.3
    assert 0.25 * 16 * 2 ** 30 < 0.70 * 16e9 < live < \
        rung["limit_GB"] * 1e9 == 0.90 * 16e9, live / 1e9
    assert live + m.temp_size_in_bytes > rung["limit_GB"] * 1e9
    assert m.alias_size_in_bytes >= 12 * model.config.num_params()


def test_granite_step_holds_its_scopes_and_no_op_that_holds_others(
        granite_step):
    """Every scope the cell's scopes file names is in the compiled step,
    the tied head's product under `head`; and nothing in it holds other
    ops (a `while`, a `conditional`), which a device trace would count
    beside the ops they ran — at nine scans of 32 chunks each."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    text = granite_step[2].as_text()
    scopes = set(scope_table(text).values())
    for part in ("mamba/in_proj", "mamba/conv", "mamba/ssd",
                 "mamba/gate_norm", "mamba/out_proj",
                 "feed_forward/gate_proj", "feed_forward/up_proj",
                 "feed_forward/down_proj", "attention/q_proj",
                 "attention/k_proj", "attention/v_proj", "attention/o_proj",
                 "input_norm", "post_mixer_norm", "GraniteHybrid/head",
                 "loss", "optimizer"):
        assert any(part in s for s in scopes), part
    assert not any("lm_head" in s or "moe" in s for s in scopes)
    assert " while(" not in text and " conditional(" not in text


def test_granite_step_runs_the_kernels_direct_at_32_heads_of_64(
        granite_step):
    """The first grouped-query call at d = 64 through a model: 32 query
    heads are 16 lane slabs of two, so after the four-fold repeat of k
    and v the kernels index the projections' own (1, 8192, 32 x 64) —
    the DIRECT route, over 8 x 8 blocks (ONE backward kernel, a slab's
    whole dq resident: `fa.backward_route`), nothing laid
    out by head.  Recorded, not required (either route computes the
    same): what moves data under `attention` outside the projections is
    six (1, 8192, 2048) copies — the repeats of k and v in the forward
    pass and in its recomputation, and a re-layout of dk and dv (as the
    kernel wrote them for 32 heads) before their sum over each group of
    four (ROADMAP M3)."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import relayouts

    text = granite_step[2].as_text()
    assert fa.attention_route(32, 64) == ("direct", 2)
    # a kv head of 64 is HALF a slab: not indexed, the repeat stays
    assert fa.kv_route(32, 8, 64) == ("repeated", 4)
    for kernel in ("dwt_fa_fwd", "dwt_fa_bwd_fused"):
        assert kernel in text, kernel
    assert "dwt_fa_bwd_dq" not in text and "dwt_fa_bwd_dkv" not in text
    assert fa.backward_route(8192, 8192, 64, 64, 2, 32) == ("fused", 1)
    assert "operand_layout_constraints={bf16[1,8192,2048]" in text
    assert "bf16[32,8192,64]" not in text
    moved = relayouts(text, "attention", outside=(
        "q_proj", "k_proj", "v_proj", "o_proj"))
    assert sorted(moved.values()) == ["copy"] * 6, moved


def test_granite_on_four_chips_is_handed_its_mesh_and_compiles(
        topo, on_tpu, monkeypatch):
    """ROADMAP D19: `GraniteHybridConfig` declares `mesh` and no
    `attn_impl`, so the parent's `auto_accelerate` left it `None` on
    four chips and the model traced its Mosaic kernels outside any
    shard_map.  Handed the plan's mesh, a mamba and an attention layer
    at the published widths compile under `fsdp` for the described 2x2:
    the attention's kernels sit in a shard_map, a chip's row of 32 heads
    each (the transposed route; off one device nothing goes direct), and
    the scan is the plain one (no `dwt_ssd` kernel, which GSPMD could
    not partition)."""
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.granite_hybrid import (
        GraniteHybrid, GraniteHybridConfig)

    monkeypatch.setenv("DWT_COMPILE_CACHE", "0")
    cfg = GraniteHybridConfig(layer_types=("mamba", "attention"))
    res = auto_accelerate(
        GraniteHybrid(cfg), strategy=[("fsdp", {})], devices=topo.devices,
        optimizer=optax.adamw(3e-4), materialize=False, seq_len=512)
    assert res.mesh.size == 4 and res.model.config.mesh is res.mesh
    ids = jax.ShapeDtypeStruct((4, 512), jnp.int32,
                               sharding=res.batch_sharding_fn(2))
    text = res.train_step.lower(
        res.state, {"input_ids": ids, "labels": ids}).compile().as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*?'
                         r'op_name="([^"]*)/pallas_call"', text)
    assert len(kernels) == 3 and all(
        "/layers_1/attention/shard_map/dwt_fa_" in k for k in kernels), kernels
    assert "bf16[32,512,64]" in text and "dwt_ssd" not in text


# --------------------------- who owns the device ops of the four steps
#
# (and of the windowed MoE's, the fifth: tests/test_smallthinker_compile.py,
# which calls `_every_device_op_has_an_owner` and `_no_fusion_falls_to_the_root`)

_STEPS = [("gpt2_124m_step", "GPT"), ("olmoe_step", "Llama"),
          ("nemotron_step", "NemotronH"), ("granite_step", "GraniteHybrid")]


def _owned(step):
    """(owners, {name: instruction}) of a compiled step's text."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        owners, parse_computations)

    text = step.as_text()
    return owners(text), {i["name"]: i for body in
                          parse_computations(text).values() for i in body}


@pytest.mark.parametrize("fixture,root", _STEPS)
def test_every_device_op_of_the_step_has_an_owner(request, fixture, root):
    """`hlo_scopes.owners` leaves no instruction that can run as a device
    op (`copy*`, `slice-*`, fusions, `reduce-window`, custom calls) with
    `via: none` — but the copies of what nothing names and only the
    step's outputs read: the step counter, a constant it returns."""
    _every_device_op_has_an_owner(request.getfixturevalue(fixture)[2])


def _every_device_op_has_an_owner(step):
    table, ins = _owned(step)
    unowned = [n for n, e in table.items() if e["via"] == "none"
               and (ins[n]["opcode"] in ("fusion", "reduce-window",
                                         "custom-call")
                    or ins[n]["opcode"].startswith(("copy", "slice-")))]
    assert len(unowned) <= 2, unowned

    def origin(name):
        # (a share's step copies its zero once for the loops' counters
        # too, and returns a copy of that copy)
        while ins[name]["opcode"] == "copy":
            name = ins[name]["operands"][0]
        return ins[name]["opcode"]

    for name in unowned:
        assert ins[name]["opcode"] == "copy", name
        assert {origin(o) for o in ins[name]["operands"]} <= {
            "parameter", "constant"}, name
    assert sum(e["via"] in ("consumer", "producer")
               for e in table.values()) > 500


@pytest.mark.parametrize("fixture,root", _STEPS)
def test_no_fusion_of_the_step_falls_to_the_models_root(request, fixture,
                                                        root):
    """A fusion whose members agree on the model's name alone takes its
    root instruction's scope or its members'; recomputed and backward
    instructions of one module agree on the module (no scope holds the
    root twice)."""
    _no_fusion_falls_to_the_root(request.getfixturevalue(fixture)[2], root)


def _no_fusion_falls_to_the_root(step, root):
    table, ins = _owned(step)
    fusions = {n: e for n, e in table.items()
               if ins[n]["opcode"] == "fusion"}
    assert len(fusions) > 100
    for name, entry in fusions.items():
        assert entry["scope"].split("/")[-1] != root, (name, entry)
    assert not [e["scope"] for e in table.values()
                if f"{root}/{root}" in e["scope"]]


# ------------------------- the dropless path's row movements, both cells

@pytest.mark.parametrize("fixture,layers,passes", [
    ("olmoe_step", 1, 1),       # one expert layer, no recomputation
    ("nemotron_step", 4, 2),    # four, each forward pass run twice
])
def test_moe_step_moves_its_rows_by_gathers_only(request, fixture, layers,
                                                 passes):
    """The static counter of `models/moe.dispatch` / `combine` and of
    the layer's bookkeeping: the compiled step holds no `scatter` under
    `moe` at all (PR 32 took the two a layer that moved rows, the
    combine's `segment_sum` and the transpose of the gather into expert
    order; PR 45 the integer scatter-adds that counted group sizes and
    expert loads, and the nameless one behind the gates' choice) and no
    gather of single numbers (`_index_ops_of_numbers`: the gates into
    expert order, the dots back by assignment, the scores at the chosen
    experts).  The rows move by four gathers a layer —
    into expert order (T*k, d) and back by assignment (k, T, d), forward
    and backward — and the two only the backward passes emit carry the
    scope of the call they are the backward of, as every such gather in
    the step does: none is left without a scope.  Where a layer holds a
    share (the kernel route) a gather into expert order is a loop over
    the held rows' chunks (8192, d), its body's gather under the same
    scope, and a sum by assignment is one too (PR 50: a turn gathers a
    chunk and its halo, (4096 + 16, d), and one gather of (T, d) behind
    the loop reads the tokens' sums — no index list of T*k entries is
    left); a whole layer keeps the one gather of (T*k, d) and the one
    of (k, T, d) and holds no op that holds others.  The grouped
    matmuls see the same buffers as before."""
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        instructions_of, scope_table)

    cell, _, step = request.getfixturevalue(fixture)
    text = step.as_text()
    width, k = (cell["config"][key] for key in
                ("hidden_size", "num_experts_per_tok"))
    tokens = cell["global_batch"] * cell["seq_len"]
    in_order = f"bf16[{tokens * k},{width}]"
    by_assignment = f"bf16[{k},{tokens},{width}]"

    def shapes(opcode, under):
        return sorted(s.split("{")[0] for s in
                      instructions_of(text, opcode, under).values())

    assert not instructions_of(text, "scatter", "moe")
    assert _index_ops_of_numbers(text, width) == []

    back = [by_assignment]
    if fixture == "nemotron_step":
        in_order = f"bf16[8192,{width}]"  # a turn of `dispatch`'s loop
        # and of a sum by assignment's, with its halo, and the one
        # gather of the tokens' sums behind that loop
        back = [f"bf16[{4096 + 16},{width}]", f"bf16[{tokens},{width}]"]

    def row_gathers(under):
        return [s for s in shapes("gather", under)
                if s in (in_order, by_assignment, *back)]

    assert row_gathers("moe/dispatch") == sorted(
        [in_order] * layers * passes + back * layers)
    # (a recomputed forward pass stops at the rows: nothing in the
    # backward pass reads the sum it would make of them)
    assert row_gathers("moe/combine") == sorted(
        back * layers + [in_order] * layers)
    assert len(row_gathers("moe")) == layers * (passes + 1 + 2 * len(back))
    # (the one other gather of (T, d) in the hybrid's step is the
    # embedding's lookup)
    assert len(row_gathers("")) == len(row_gathers("moe")) + (
        fixture == "nemotron_step")

    # the grouped matmuls, by the buffer each writes: the compiler's
    # kernels where the layer holds every expert (9 a step at OLMoE, 11
    # ops with their metadata), `ops/grouped_matmul.py`'s where it holds
    # a share (32 a step at the hybrid, and no `ragged-dot` at all)
    rows = tokens * k
    kernels = collections.Counter(re.findall(
        r"%ragged-dot-none[.\d]* = bf16\[([\d,]+)\]", text))
    ragged = [n for n in scope_table(text) if n.startswith("ragged-dot")]
    ours = collections.Counter(
        (re.sub(r"[.\d]+$", "", name), shapes[0])  # the result's comes first
        for name, (_, shapes) in _grouped_kernel_calls(text).items())
    if fixture == "olmoe_step":
        assert kernels == {f"{rows},1024": 3, f"{rows},2048": 3,
                           "64,2048,1024": 2, "64,1024,2048": 1}
        assert len(ragged) == 11 and not ours
        # a whole layer fills its buffer: no map, the compiler's fusions,
        # one gather of every row
        assert "dwt_rows_map" not in text and "dwt_rows_unwritten" not in text
        assert " while(" not in text and " conditional(" not in text
    else:
        assert not kernels and not ragged
        assert ours == {
            ("dwt_gmm", f"{rows},1856"): 8, ("dwt_gmm", f"{rows},2688"): 8,
            ("dwt_gmm_t", f"{rows},1856"): 4,
            ("dwt_gmm_t", f"{rows},2688"): 4,
            ("dwt_tgmm", "8,2688,1856"): 4, ("dwt_tgmm", "8,1856,2688"): 4}
        _held_row_loops(text, rows, width, layers)


# ------------------------------ the scan's kernels in both hybrids' steps

def _scan_arguments(topo, b, t, h, p, g, n):
    """`ssd_scan`'s six arguments as bf16 / float32 shapes on one
    described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((b, t, h, p), jnp.bfloat16), ((b, t, h), jnp.float32),
        ((h,), jnp.float32), ((b, t, g, n), jnp.bfloat16),
        ((b, t, g, n), jnp.bfloat16), ((h,), jnp.float32))]


def _square_tiles(text, under, side):
    """Lines of the instructions scoped `under` that name an array whose
    two minor axes are both `side` long."""
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if not name or under not in name.group(1):
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]",
                               line.split(" metadata=")[0]):
            if dims.split(",")[-2:] == [str(side)] * 2:
                found.append(line.strip()[:160])
                break
    return found


@pytest.mark.parametrize("fixture,layers,heads_block", [
    ("granite_step", 9, 16),    # ONE group of 64 heads, chunk 256
    ("nemotron_step", 4, 8),    # 8 groups of 8, chunk 128
])
def test_hybrid_step_scans_in_its_kernels_and_holds_no_decay_tensor(
        request, topo, on_tpu, fixture, layers, heads_block):
    """The static counters of the scan's route.  Every scan of the step
    runs the kernels: three custom calls a layer — `dwt_ssd_fwd` in the
    forward pass, again in its recomputation, `dwt_ssd_bwd` in the
    backward pass — 27 at granite's nine layers, 12 at the other
    hybrid's four.  Each carries the scope `mamba/ssd` in all three
    phases, so `benchmark/program.part_of` puts it in `ssm_scan` by the
    class's `ssm_parts`: `step.ssm_scan_ms` holds the kernels, and
    `kernel.ssd_roofline` (a count from shapes over that time) cannot
    pass 100% for work that fell to `step.unscoped_ms`.  No instruction
    under `mamba/ssd` names an array with two chunk-length minor axes:
    the (L x L) decay tensor, C B^T and their product never reach HBM
    (the plain form, compiled alone for the same chip, names them).
    None of the kernels is named `dwt_fa_*` (`kernel.attn_ms` sums
    those): the attention layer's own four are all there are.  Nothing
    of the scan holds other ops: its carry between chunks is a grid
    axis of the kernels, no loop of the step."""
    import json

    from benchmark import cells, program
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table

    cell, model, step = request.getfixturevalue(fixture)
    text = step.as_text()
    cfg = model.config.mamba_config()
    assert ssd.scan_route(cfg.num_heads, cfg.head_dim, cfg.n_groups,
                          cfg.state_size, cfg.chunk_size,
                          cell["seq_len"]) == ("kernel", heads_block)
    table = scope_table(text)
    calls = {n: s for n, s in table.items() if n.startswith("dwt_ssd_")}
    by_kernel = collections.Counter(
        (n.split(".")[0], s.split("/")[0]) for n, s in calls.items())
    assert by_kernel == {("dwt_ssd_fwd", "fwd"): layers,
                         ("dwt_ssd_fwd", "recompute"): layers,
                         ("dwt_ssd_bwd", "bwd"): layers}
    assert len(calls) == 3 * layers == {9: 27, 4: 12}[layers]
    assert len(re.findall(r"custom-call\([^\n]*dwt_ssd_", text)) \
        == len(calls)
    with open(os.path.join(cells.HERE, "models", cell["config"][
            "model_class"] + ".scopes.json")) as f:
        rules = json.load(f)
    for name, scope in calls.items():
        assert "/mamba/ssd/" in f"/{scope}/", (name, scope)
        assert program.part_of(scope, rules["ssm_parts"]) == "ssm_scan"
        assert program.part_of(scope, rules["parts"]) == "ssm"
    scopes = set(table.values())
    assert any("mamba/conv" in s for s in scopes)
    assert any("mamba/ssd" in s for s in scopes)

    assert _square_tiles(text, "mamba/ssd", cfg.chunk_size) == []
    shapes = _scan_arguments(
        topo, cell["global_batch"], cell["seq_len"], cfg.num_heads,
        cfg.head_dim, cfg.n_groups, cfg.state_size)
    plain = _compile(lambda *a: ssd.ssd_scan_plain(
        *a, chunk=cfg.chunk_size, dtype=jnp.bfloat16), *shapes)
    assert _square_tiles(plain, "ssd", cfg.chunk_size)

    attention = [n for n in table if n.startswith("dwt_fa_")]
    # granite repeats k and v (d = 64): the slab step; the other hybrid
    # indexes them: a group of query heads a grid step
    forward = "dwt_fa_fwd" if fixture == "granite_step" else "dwt_fa_grp_fwd"
    assert sorted(n.split(".")[0] for n in attention) == sorted(
        ["dwt_fa_bwd_fused", forward, forward])
    # (what the other hybrid's step holds of loops is its expert
    # layers': `_held_row_loops`)
    assert " conditional(" not in text and not [
        line for line in text.splitlines()
        if " while(" in line and "/moe/" not in line]


@pytest.mark.parametrize("b,t,h,p,g,n,chunk", [
    (1, 8192, 64, 64, 1, 128, 256),     # granite4_h_micro.steady
    (2, 8192, 64, 64, 8, 128, 128),     # nemotron3_nano_30b_a3b.steady
    (1, 2048, 8, 128, 2, 128, 128),     # a head a lane slab
    (1, 1024, 8, 32, 1, 128, 128),      # four heads a slab
])
def test_scan_kernels_compile_at_the_cells_shapes(topo, b, t, h, p, g, n,
                                                  chunk):
    """`dwt_ssd_fwd` and `dwt_ssd_bwd` alone, a few seconds a shape: what
    the interpret-mode tests (tests/test_ssd_kernel.py) cannot see."""
    shapes = _scan_arguments(topo, b, t, h, p, g, n)
    hb = ssd._heads_block(h // g, p)

    def scan(*a):
        return ssd._scan_kernels(*a, chunk, jnp.bfloat16, hb)

    assert "dwt_ssd_fwd" in _compile(scan, *shapes)
    text = _compile(jax.grad(lambda *a: scan(*a).sum(),
                             argnums=tuple(range(6))), *shapes)
    assert "dwt_ssd_fwd" in text and "dwt_ssd_bwd" in text
    assert _square_tiles(text, "", chunk) == []


# ------------------- the short convolution's pair in the hybrids' steps

@pytest.mark.parametrize("fixture,layers", [
    ("granite_step", 9),     # 4,352 channels: 17 pairs of lane tiles
    ("nemotron_step", 4),    # 6,144 channels, two sequences
])
def test_hybrid_step_convolves_in_its_kernels(request, on_tpu, fixture,
                                              layers):
    """The static counters of the convolution's route.  Every Mamba-2
    layer's short convolution runs the pair: three custom calls a layer
    — `dwt_conv_fwd` in the forward pass, again in its recomputation,
    `dwt_conv_bwd` in the backward pass — 27 at granite's nine layers,
    12 at the other hybrid's four, each under `mamba/conv` in all three
    phases, so `step.ssm_scan_ms` reads the same work through the
    untouched scopes files.  Nothing else under that scope is as large
    as the rows: the shifted products' fusions and the padded copy are
    gone, what stays is the (8, channels) coefficients."""
    import json

    from benchmark import cells, program
    from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
        read_instruction, scope_table)

    cell, model, step = request.getfixturevalue(fixture)
    text = step.as_text()
    cfg = model.config.mamba_config()
    rows = cell["global_batch"] * cell["seq_len"] * cfg.conv_dim
    assert short_conv.conv_route(cell["seq_len"], cfg.conv_dim,
                                 cfg.conv_kernel, cfg.dtype) == "kernel"
    table = scope_table(text)
    calls = {n: s for n, s in table.items() if n.startswith("dwt_conv_")}
    by_kernel = collections.Counter(
        (n.split(".")[0], s.split("/")[0]) for n, s in calls.items())
    assert by_kernel == {("dwt_conv_fwd", "fwd"): layers,
                         ("dwt_conv_fwd", "recompute"): layers,
                         ("dwt_conv_bwd", "bwd"): layers}
    assert len(calls) == 3 * layers == {9: 27, 4: 12}[layers]
    assert len(re.findall(r"custom-call\([^\n]*dwt_conv_", text)) \
        == len(calls)
    with open(os.path.join(cells.HERE, "models", cell["config"][
            "model_class"] + ".scopes.json")) as f:
        rules = json.load(f)
    for name, scope in calls.items():
        assert "/mamba/conv/" in f"/{scope}/", (name, scope)
        assert program.part_of(scope, rules["ssm_parts"]) == "ssm_scan"
        assert program.part_of(scope, rules["parts"]) == "ssm"
    under = {n for n, s in table.items()
             if "mamba/conv" in s and n not in calls}
    for line in text.splitlines():
        inst = read_instruction(line)
        if inst is None or inst["name"] not in under \
                or "get-tuple-element(" in line:
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", inst["shape"]):
            assert math.prod(int(d) for d in dims.split(",")) \
                < rows // 100, line.strip()[:200]


@pytest.mark.parametrize("b,t,channels,bias,dtype", [
    (2, 8192, 6144, True, jnp.bfloat16),    # nemotron3_nano_30b_a3b.steady
    (1, 8192, 4352, True, jnp.bfloat16),    # granite4_h_micro.steady
    (1, 8192, 2048, False, jnp.bfloat16),   # ling3_0_flash.steady: q, k, v
    (1, 1536, 384, True, jnp.float32),      # one lane tile a step, float32
])
def test_conv_kernels_compile_at_the_cells_shapes(topo, b, t, channels,
                                                  bias, dtype):
    """`dwt_conv_fwd` and `dwt_conv_bwd` alone, a second or two a shape:
    what the interpret-mode tests (tests/test_short_conv_kernel.py)
    cannot see — the sublane rolls, the halo views at a packed tile's
    grain, the blocks inside VMEM."""
    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((b, t, channels), dtype), ((4, channels), jnp.float32),
        ((channels,), jnp.float32))]

    def conv(x, kernel, bias_):
        return short_conv._conv_kernels(x, kernel, bias_ if bias else None,
                                        dtype)

    assert "dwt_conv_fwd" in _compile(conv, *shapes)
    text = _compile(lambda x, *a: jax.vjp(conv, x, *a)[1](x), *shapes)
    assert "dwt_conv_bwd" in text and "dwt_conv_fwd" not in text


# ----------------------- the grouped products of a share of the experts

@pytest.mark.parametrize("c,n", [(2688, 1856), (1856, 2688)])
def test_grouped_kernels_compile_at_the_cells_shapes(topo, c, n):
    """`dwt_gmm`, `dwt_gmm_t` and `dwt_tgmm` alone at the hybrid cell's
    four shapes — 98,304 rows x (8, 2688, 1856) and x (8, 1856, 2688),
    their transposed-weight forms and the two weight gradients — a few
    seconds a shape: what the interpret-mode tests
    (tests/test_grouped_matmul.py) cannot see.  1856 columns are taken
    whole, 2688 in tiles of 896; the blocks fit the kernels' VMEM."""
    one = SingleDeviceSharding(topo.devices[0])
    rows = 2 * 8192 * 6
    lhs, rhs, sizes = (jax.ShapeDtypeStruct(s, d, sharding=one)
                       for s, d in (((rows, c), jnp.bfloat16),
                                    ((8, c, n), jnp.bfloat16),
                                    ((8,), jnp.int32)))
    assert gm._column_tile(1856) == 1856 and gm._column_tile(2688) == 896
    assert gm._vmem_bytes(c, n) < gm._VMEM_LIMIT
    text = _compile(jax.value_and_grad(
        lambda l, r, s: gm._grouped_kernels(l, r, s).astype(
            jnp.float32).sum(), argnums=(0, 1)), lhs, rhs, sizes)
    for kernel in ("dwt_gmm.", "dwt_gmm_t", "dwt_tgmm"):
        assert kernel in text, kernel
    assert "ragged-dot" not in text
    assert " while(" not in text and " conditional(" not in text
