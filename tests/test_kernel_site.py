"""One truth table of "does this call take a kernel".

Every route predicate under `ops/` (and `models/attention.goes_direct`,
which finishes the attention's) at a shape of each benchmark cell that
calls it, over the five places a call can be traced:

  off      the backend is not the TPU
  device   the TPU, no mesh in the config
  one      the TPU, a mesh of one device
  mesh     the TPU, a mesh of four devices, outside a `shard_map`
  manual   the TPU, inside a `shard_map` over every axis of that mesh

The answers are PR 54's: they were read from its checkout by running its
own functions over these rows and sites (`scan_route`'s, which had no
`mesh`, as `models/mamba2.scans_on_one_device(cfg) and scan_route(...)`;
`projected_ok`'s without one, as it still may be called) and are
written here as literals.  PR 55 put the question of the site in one
place (`ops/mosaic.kernel_site`) and each kernel module's answer in one
constant (`_SITES`): a predicate whose answer moves fails here, and S9 /
M1 (ROADMAP), which flip a constant, edit the column they mean to.
PR 56 gave `rope_route` the rotated width (Laguna's full layers turn 64
of a head's 128 lanes): the rows of the calls the program now makes.
PR 59 added `conv_route` (`ops/short_conv.py`), a predicate the parent
did not have: its rows are what the mixers' convolutions are handed.
PR 61 added `gate_route` (`ops/head_gate.py`), new too: its rows are the
rows of y a gated `LlamaAttention` multiplies.
PR 66 added the rows of `qwen3_next`'s calls (heads of 256, 64 of them
rotated; 32 value heads of 128 | 128 under one decay a head; the
convolution's 2,048 and 4,096 channels): no predicate changed.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from dlrover_wuqiong_tpu.models import attention
from dlrover_wuqiong_tpu.ops import delta_rule, flash_attention as fa
from dlrover_wuqiong_tpu.ops import grouped_matmul as gm
from dlrover_wuqiong_tpu.ops import (
    hc_mix,
    head_gate,
    mosaic,
    rope,
    short_conv,
    ssd,
)

SITES = ("off", "device", "one", "mesh", "manual")

# the columns most rows share: (off, device, one, mesh, manual)
_NOWHERE = ("plain",) * 5
_ONE_DEVICE = ("plain", "kernel", "kernel", "plain", "plain")
_OR_A_SHARD_MAP = ("plain", "kernel", "kernel", "plain", "kernel")


def _scan(hb):
    return (("plain", 0), ("kernel", hb), ("kernel", hb), ("plain", 0),
            ("plain", 0))


HELD = 16  # qwen3_next's experts held (its memory rung's)


def _swiglu(held, d, f):
    return [(held, d, f)] * 2 + [(held, f, d)]


# predicate -> [(cell or case, arguments before `mesh`, the five answers)]
TABLE = {
    # (heads, q's and k's width[, v's]): the shape alone, at every site
    "attention_route": [
        ("gpt2_124m", (12, 64), (("direct", 2),) * 5),
        ("gpt2_xl", (25, 64), (("transposed", 0),) * 5),
        ("olmoe", (16, 128), (("direct", 1),) * 5),
        ("nemotron", (32, 128), (("direct", 1),) * 5),
        ("granite", (32, 64), (("direct", 2),) * 5),
        ("smallthinker", (28, 128), (("direct", 1),) * 5),
        ("kimi_vl", (16, 192, 128), (("transposed", 0),) * 5),
        ("olmo_hybrid", (30, 128), (("direct", 1),) * 5),
        ("xing4_0", (32, 192, 128), (("transposed", 0),) * 5),
        ("laguna_full", (48, 128), (("direct", 1),) * 5),
        ("laguna_sliding", (64, 128), (("direct", 1),) * 5),
        ("qwen3_next", (16, 256), (("direct", 1),) * 5),
    ],
    # (heads, kv heads, width)
    "kv_route": [
        ("gpt2_124m", (12, 12, 64), (("indexed", 1),) * 5),
        ("gpt2_xl", (25, 25, 64), (("indexed", 1),) * 5),
        ("olmoe", (16, 16, 128), (("indexed", 1),) * 5),
        ("nemotron", (32, 2, 128), (("indexed", 16),) * 5),
        ("granite", (32, 8, 64), (("repeated", 4),) * 5),
        ("smallthinker", (28, 4, 128), (("indexed", 7),) * 5),
        ("kimi_vl", (16, 16, 192), (("indexed", 1),) * 5),
        ("olmo_hybrid", (30, 30, 128), (("indexed", 1),) * 5),
        ("xing4_0", (32, 32, 192), (("indexed", 1),) * 5),
        ("laguna_full", (48, 8, 128), (("indexed", 6),) * 5),
        ("laguna_sliding", (64, 8, 128), (("indexed", 8),) * 5),
        ("qwen3_next", (16, 2, 256), (("indexed", 8),) * 5),
    ],
    # (heads, width, sequence[, v's width]) and NO mesh: the entry's own
    # guard, which the backend alone moves
    "projected_ok": [
        ("gpt2_124m", (12, 64, 1024), (False, True, True, True, True)),
        ("gpt2_xl", (25, 64, 1024), (False,) * 5),
        ("olmoe", (16, 128, 4096), (False, True, True, True, True)),
        ("nemotron", (32, 128, 8192), (False, True, True, True, True)),
        ("granite", (32, 64, 8192), (False, True, True, True, True)),
        ("smallthinker", (28, 128, 16384), (False, True, True, True, True)),
        ("kimi_vl", (16, 192, 16384, 128), (False,) * 5),
        ("olmo_hybrid", (30, 128, 8192), (False, True, True, True, True)),
        ("xing4_0", (32, 192, 8192, 128), (False,) * 5),
        ("laguna_full", (48, 128, 16384), (False, True, True, True, True)),
        ("laguna_sliding", (64, 128, 16384),
         (False, True, True, True, True)),
        ("qwen3_next", (16, 256, 16384), (False, True, True, True, True)),
        ("a_sequence_no_block_tiles", (12, 64, 4099), (False,) * 5),
    ],
    # (the config's attn_impl, heads, width, sequence): the config's mesh
    # is the site's
    "goes_direct": [
        ("gpt2_124m", ("flash", 12, 64, 1024),
         (False, True, True, False, False)),
        ("gpt2_xl", ("flash", 25, 64, 1024), (False,) * 5),
        ("olmoe", ("flash", 16, 128, 4096),
         (False, True, True, False, False)),
        ("nemotron", ("flash", 32, 128, 8192),
         (False, True, True, False, False)),
        ("granite", ("flash", 32, 64, 8192),
         (False, True, True, False, False)),
        ("smallthinker", ("flash", 28, 128, 16384),
         (False, True, True, False, False)),
        ("olmo_hybrid", ("flash", 30, 128, 8192),
         (False, True, True, False, False)),
        ("laguna_full", ("flash", 48, 128, 16384),
         (False, True, True, False, False)),
        ("laguna_sliding", ("flash", 64, 128, 16384),
         (False, True, True, False, False)),
        ("qwen3_next", ("flash", 16, 256, 16384),
         (False, True, True, False, False)),
        # ring and Ulysses are read only where there is a mesh to run on
        ("olmoe_over_ring", ("ring", 16, 128, 4096),
         (False, True, False, False, False)),
        ("olmoe_over_ulysses", ("ulysses", 16, 128, 4096),
         (False, True, False, False, False)),
    ],
    # (heads, head size, groups, state size, chunk, sequence)
    "scan_route": [
        ("granite", (64, 64, 1, 128, 256, 8192), _scan(16)),
        ("nemotron", (64, 64, 8, 128, 128, 8192), _scan(8)),
        ("nano", (8, 16, 2, 8, 16, 64), (("plain", 0),) * 5),
    ],
    # (lanes of a row, head size[, its first lanes that turn: the tables'
    # width, where it is not the head's]): q's rows and k's
    "rope_route": [
        ("olmoe_q_and_k", (2048, 128), _OR_A_SHARD_MAP),
        ("smallthinker_q", (3584, 128), _OR_A_SHARD_MAP),
        ("smallthinker_k", (512, 128), _OR_A_SHARD_MAP),
        ("kimi_vl_q", (1024, 64), _OR_A_SHARD_MAP),
        ("kimi_vl_k_and_xing4_0_k", (64, 64), _OR_A_SHARD_MAP),
        ("xing4_0_q", (2048, 64), _OR_A_SHARD_MAP),
        ("olmo_hybrid_q_and_k", (3840, 128), _OR_A_SHARD_MAP),
        ("laguna_sliding_q", (8192, 128), _OR_A_SHARD_MAP),
        ("laguna_sliding_k", (1024, 128), _OR_A_SHARD_MAP),
        ("laguna_full_q_half_a_head", (6144, 128, 64), _OR_A_SHARD_MAP),
        ("laguna_full_k_half_a_head", (1024, 128, 64), _OR_A_SHARD_MAP),
        ("a_quarter_of_a_head", (2048, 128, 32), _OR_A_SHARD_MAP),
        ("an_eighth_of_a_head", (2048, 128, 16), _NOWHERE),
        ("three_quarters_of_a_head", (2048, 128, 96), _NOWHERE),
        ("seven_heads_of_64", (448, 64), _NOWHERE),
        ("heads_of_32", (1024, 32), _NOWHERE),
        ("heads_of_256", (1024, 256), _NOWHERE),
        # a head of two slabs turning its first 64 lanes: the formula
        ("qwen3_next_q_64_of_256", (4096, 256, 64), _NOWHERE),
        ("qwen3_next_k_64_of_256", (512, 256, 64), _NOWHERE),
    ],
    # (sequence, chunk, heads, key width, value width)
    "delta_route": [
        ("olmo_hybrid", (8192, 64, 15, 96, 192),
         ("chunked", ("kernel", 5), ("kernel", 5), "chunked", "chunked")),
        ("ragged", (8200, 64, 15, 96, 192), ("sequential",) * 5),
        ("nano", (64, 16, 3, 8, 24), ("chunked",) * 5),
        # a sixth entry: the decay is a key CHANNEL's
        ("ling3_0_flash", (8192, 64, 16, 128, 128, True),
         ("chunked", ("kernel", 4), ("kernel", 4), "chunked", "chunked")),
        ("no_whole_sub_blocks", (8184, 24, 16, 128, 128, True),
         ("sequential",) * 5),
        # 32 value heads (their 16 key heads' q and k repeated to them)
        ("qwen3_next", (16384, 64, 32, 128, 128),
         ("chunked", ("kernel", 4), ("kernel", 4), "chunked", "chunked")),
    ],
    # (lanes of the stream, tokens, hidden size)
    "hc_route": [
        ("xing4_0", (4, 8192, 3584), _OR_A_SHARD_MAP),
        ("no_whole_slabs", (4, 8192, 200), _NOWHERE),
    ],
    # (sequence, channels, taps, the rows' dtype)
    "conv_route": [
        ("nemotron", (8192, 6144, 4, jnp.bfloat16), _ONE_DEVICE),
        ("granite", (8192, 4352, 4, jnp.bfloat16), _ONE_DEVICE),
        ("ling3_0_flash_q_k_and_v", (8192, 2048, 4, jnp.bfloat16),
         _ONE_DEVICE),
        ("olmo_hybrid_q_and_k", (8192, 1440, 4, jnp.bfloat16), _NOWHERE),
        ("olmo_hybrid_v", (8192, 2880, 4, jnp.bfloat16), _NOWHERE),
        ("qwen3_next_q_and_k", (16384, 2048, 4, jnp.bfloat16), _ONE_DEVICE),
        ("qwen3_next_v", (16384, 4096, 4, jnp.bfloat16), _ONE_DEVICE),
        ("no_whole_row_block", (8200, 6144, 4, jnp.bfloat16), _NOWHERE),
        ("nano", (64, 320, 4, jnp.float32), _NOWHERE),
    ],
    # (lanes of a row of y, head size)
    "gate_route": [
        ("laguna_sliding", (8192, 128), _OR_A_SHARD_MAP),
        ("laguna_full", (6144, 128), _OR_A_SHARD_MAP),
        ("heads_of_two_slabs", (512, 256), _OR_A_SHARD_MAP),
        ("heads_of_64", (4096, 64), _NOWHERE),
        ("heads_of_96", (6144, 96), _NOWHERE),
        ("a_row_that_cuts_a_head", (6144 + 64, 128), _NOWHERE),
        ("a_row_that_cuts_a_head_of_two_slabs", (640, 256), _NOWHERE),
    ],
    # (lhs (T*k, c), rhs (held, c, n), experts the router names)
    "gmm_route": [
        ("nemotron_in", ((98304, 2688), (8, 2688, 1856), 128), _ONE_DEVICE),
        ("nemotron_out", ((98304, 1856), (8, 1856, 2688), 128),
         _ONE_DEVICE),
        ("smallthinker_in", ((196608, 2560), (16, 2560, 768), 64),
         _ONE_DEVICE),
        ("kimi_vl_in", ((196608, 2048), (8, 2048, 1408), 64), _ONE_DEVICE),
        ("xing4_0_in", ((32768, 3584), (8, 3584, 1024), 64), _ONE_DEVICE),
        ("laguna_in", ((131072, 2048), (32, 2048, 512), 256), _ONE_DEVICE),
        ("qwen3_next_in", ((163840, 2048), (HELD, 2048, 512), 512),
         _ONE_DEVICE),
        ("olmoe_whole_layer", ((163840, 2048), (64, 2048, 1024), 64),
         _NOWHERE),
    ],
    # (T*k, the layer's weights, experts the router names)
    "experts_route": [
        ("nemotron", (98304, [(8, 2688, 1856), (8, 1856, 2688)], 128),
         _ONE_DEVICE),
        ("smallthinker", (196608, _swiglu(16, 2560, 768), 64), _ONE_DEVICE),
        ("kimi_vl", (196608, _swiglu(8, 2048, 1408), 64), _ONE_DEVICE),
        ("xing4_0", (32768, _swiglu(8, 3584, 1024), 64), _ONE_DEVICE),
        ("laguna", (131072, _swiglu(32, 2048, 512), 256), _ONE_DEVICE),
        ("qwen3_next", (163840, _swiglu(HELD, 2048, 512), 512),
         _ONE_DEVICE),
        ("olmoe_whole_layer", (163840, _swiglu(64, 2048, 1024), 64),
         _NOWHERE),
        ("maps_over_vmem", (98304, _swiglu(8, 8192, 1024), 128), _NOWHERE),
    ],
}


def _ask(predicate, args, mesh):
    if predicate in ("attention_route", "kv_route", "projected_ok"):
        return getattr(fa, predicate)(*args)
    if predicate == "goes_direct":
        impl, *shape = args
        return attention.goes_direct(
            types.SimpleNamespace(mesh=mesh, attn_impl=impl), *shape)
    module = {"scan_route": ssd, "rope_route": rope, "hc_route": hc_mix,
              "delta_route": delta_rule, "gmm_route": gm,
              "experts_route": gm, "conv_route": short_conv,
              "gate_route": head_gate}[predicate]
    # the mesh sits before the rotated part, and before the decay's form
    at = {"rope_route": 2, "delta_route": 5}.get(predicate, len(args))
    return getattr(module, predicate)(*args[:at], mesh, *args[at:])


def _inside_a_shard_map(mesh, ask, over, **kw):
    """`ask(mesh)`'s answer, traced inside a `shard_map` over axis `over`."""
    seen = []

    def shard(x):
        seen.append(ask(mesh))
        return x

    jax.eval_shape(jax.shard_map(shard, mesh=mesh, in_specs=P(over),
                                 out_specs=P(over), **kw), jnp.zeros((8, 4)))
    return seen[0]


def _at(site, ask):
    """`ask(mesh)`'s answer, traced at `site`."""
    devices = {"one": 1, "mesh": 4, "manual": 4}.get(site)
    mesh = devices and Mesh(np.array(jax.devices()[:devices]), ("fsdp",))
    if site == "manual":
        return _inside_a_shard_map(mesh, ask, "fsdp")
    return ask(mesh)


@pytest.mark.parametrize("site,on_tpu", [(s, s != "off") for s in SITES],
                         indirect=["on_tpu"], ids=SITES)
@pytest.mark.parametrize("predicate,case,args,want", [
    pytest.param(predicate, case, args, want, id=f"{predicate}-{case}")
    for predicate, rows in TABLE.items() for case, args, want in rows])
def test_a_predicate_answers_as_it_did(on_tpu, site, predicate, case, args,
                                       want):
    got = _at(site, lambda mesh: _ask(predicate, args, mesh))
    assert got == want[SITES.index(site)]


@pytest.mark.parametrize("site,on_tpu,want", [
    ("off", False, "off"), ("device", True, "device"),
    ("one", True, "device"), ("mesh", True, "mesh"),
    ("manual", True, "manual")], indirect=["on_tpu"])
def test_the_five_sites_are_kernel_sites_four_answers(on_tpu, site, want):
    """`kernel_site` itself; and a `shard_map` over SOME of a mesh's axes
    leaves the others GSPMD's: that is "mesh"."""
    assert _at(site, mosaic.kernel_site) == want
    if site == "manual":
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "fsdp"))
        assert _inside_a_shard_map(mesh, mosaic.kernel_site, "dp",
                                   axis_names={"dp"}) == "mesh"


@pytest.mark.parametrize("d,heads", [
    (128, 1), (256, 1), (64, 2), (32, 4), (16, 0), (96, 0), (80, 0),
    (192, 0)])
def test_heads_on_a_slab(d, heads):
    """The one rule (`mosaic.slab_heads`) the attention's, the rotation's
    and the scan's routes are written over; each keeps its own supported
    set (the table above)."""
    assert mosaic.slab_heads(d) == heads


def test_every_kernel_module_states_its_sites_once():
    """`_SITES` beside the VMEM request: a subset of `kernel_site`'s
    answers, "off" in none."""
    stated = {m.__name__.rsplit(".", 1)[1]: set(m._SITES)
              for m in (ssd, rope, hc_mix, delta_rule, gm, short_conv)}
    stated["flash_attention (direct)"] = set(fa._DIRECT_SITES)
    assert stated == {
        "ssd": {"device"}, "grouped_matmul": {"device"},
        "delta_rule": {"device"}, "flash_attention (direct)": {"device"},
        "short_conv": {"device"},
        "rope": {"device", "manual"}, "hc_mix": {"device", "manual"}}
