"""What every Pallas (Mosaic) kernel module under `ops/` stands on.

WHERE A CALL RUNS: `on_tpu()` is the one reading of the backend and
`kernel_site(mesh)` the one classification of a call's place.  A kernel
module states ONCE, beside its VMEM request, the sites its kernels run
on (`_SITES`); its route predicate asks `kernel_site(mesh) in _SITES`
and keeps only what is its own (its shapes, its VMEM reckoning).  That
`ssd`, `grouped_matmul` and `delta_rule` refuse "manual" — they have
never run inside a `shard_map` — is one constant each, not six wordings.
Everyone calls `on_tpu` THROUGH this module, never by a name bound at
import: a test or a probe that says "the backend is the TPU" patches one
name (tests/conftest.py's `on_tpu`) and reaches every caller, a kernel
module added later too.

WHAT A KERNEL BODY IS WRITTEN WITH: the widths, `slab_heads`, the products,
the output struct; a kernel's VMEM request stays beside it, in its program.

Parity: none — the reference has no Pallas kernels.
"""

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128     # the minor axis of a vector register, of an HBM tile
SUBLANES = 16   # a packed bfloat16 tile's rows; a float32 tile's 8 divides it


# ------------------------------------------------------- where a call runs

def on_tpu() -> bool:
    # a backend that fails to initialise raises from here: training on
    # the jnp reference because the chip did not come up is not a mode
    return jax.default_backend() == "tpu"


def inside_shard_map() -> bool:
    """Whether the trace runs inside a `shard_map` over every axis of
    its mesh: the one place a kernel runs on a mesh of several devices."""
    mesh = jax.sharding.get_abstract_mesh()
    return bool(mesh.axis_names) and set(mesh.manual_axes) == set(
        mesh.axis_names)


def kernel_site(mesh=None) -> str:
    """Where the call being traced runs, as far as a kernel can care:
    "off", the backend is not the TPU; "device", `mesh` (the model
    config's) is None or of one device; "manual", inside a `shard_map`
    over every axis of a mesh of several (the operands are one chip's
    shards, a Mosaic call an ordinary per-device op); "mesh", several
    devices and GSPMD's to partition, which no Mosaic call can be."""
    if not on_tpu():
        return "off"
    if mesh is None or mesh.size == 1:
        return "device"
    return "manual" if inside_shard_map() else "mesh"


def slab_heads(d: int) -> int:
    """Heads of width `d` on one 128-lane slab of a (b, T, H*d) layout: 1
    where a head is a slab or several, 128 // d where that is whole and
    d >= 32, else 0.  Which answers a kernel is written for is its own."""
    if d % LANES == 0:
        return 1
    return LANES // d if LANES % d == 0 and d >= 32 else 0


# ------------------------------------- what a kernel body is written with

def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _compiler_params(*semantics, vmem_limit=None):
    # a limit is past the 16 MiB default scoped one (the fused attention
    # kernels hold every head's blocks), inside v5e's 128 MiB of VMEM
    kw = {} if vmem_limit is None else {"vmem_limit_bytes": vmem_limit}
    return pltpu.CompilerParams(dimension_semantics=semantics, **kw)


def _reckoned_vmem(held: int) -> int:
    """A quarter over what a kernel holds, the compiler's default at least."""
    return max(held * 5 // 4, 16 * 1024 * 1024)


def _einsum(spec, *operands, dtype):
    """The `jax.numpy` routes': operands rounded to `dtype`, f32 sums."""
    return jnp.einsum(spec, *(o.astype(dtype) for o in operands),
                      preferred_element_type=jnp.float32)


def _out_struct(shape, dtype, like):
    """Kernel output struct.  Inside a shard_map (the only way a Mosaic
    kernel runs on a multi-device mesh) the outputs vary over the same
    manual axes as the operands; outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _dot(a, b):
    """a @ b with native-dtype (bf16) MXU multiply, f32 accumulation."""
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a @ b.T with native-dtype MXU multiply, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_c0(a, b):
    """Contract dim 0 of both: (K, M) x (K, N) -> (M, N), f32 accumulate."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _row_dot(a, b):
    """(rows, lanes) . (rows, lanes) -> (rows, 1): the product's sum over
    the lanes, in float32 whatever the two hold.  A head's cotangent of a
    gate on its lanes (`ops/head_gate.py`); attention's delta = rowsum(dO
    . O) of a head's slabs is the same sum."""
    return (a.astype(jnp.float32) * b.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _put(acc, index, i, v):
    """acc with column (or row) i set to the broadcast of v."""
    return jnp.where(index == i, v, acc)
