"""Block-diffusion's mask and its kernels (`ops/block_attention.py`): the
rule pair by pair against a brute-force loop, the plan against the rule
tile by tile, the counter against both, and the two kernels — the
forward and the ONE backward sweep — in interpret mode against the dense
`jax.numpy` route — forward and every gradient — at L = 4 and two other
block lengths, at copies of one, two and three blocks, with blocks cut
into tiles and not, groups of two and of four heads taken whole and in
parts (dk and dv then sums over a kv head's units); the route's VMEM
reckoning against the shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.ops import block_attention as ba
from dlrover_wuqiong_tpu.ops import flash_attention as fa

H, KV, D = 4, 2, 16


def brute_force(t: int, length: int) -> np.ndarray:
    """The definition, one (i, j) at a time."""
    kept = np.zeros((2 * t, 2 * t), bool)
    for i in range(2 * t):
        for j in range(2 * t):
            bi, bj = (i % t) // length, (j % t) // length
            if i < t:  # a clean query: clean keys, block-causal
                kept[i, j] = j < t and bj <= bi
            else:  # a noised query: clean keys before its block, its own
                kept[i, j] = (j < t and bj < bi) or (j >= t and bj == bi)
    return kept


@pytest.mark.parametrize("t,length", [(8, 4), (16, 2), (32, 8), (24, 4),
                                      (12, 1), (16, 16)])
def test_the_mask_is_the_definition_pair_by_pair(t, length):
    kept = np.asarray(ba.kept_mask_bd(t, length))
    want = brute_force(t, length)
    assert (kept == want).all()
    # no clean query sees a noised key; a noised query sees its own block
    # both ways and no other noised block; every row sees a key
    assert not kept[:t, t:].any()
    assert (kept[t:, t:] == kept[t:, t:].T).all()
    assert kept.any(axis=1).all()
    assert kept.sum() == t * t + t * length == ba.bd_tile_count(
        t, length, "plain")[2]


@pytest.mark.parametrize("t,length,block,tile", [
    (32, 4, 16, 16), (48, 4, 16, 16), (64, 8, 32, 16), (96, 2, 32, 16),
    (64, 4, 64, 16), (16, 4, 16, 16)])
def test_the_plan_runs_the_live_tiles_and_no_other(t, length, block, tile):
    """Every piece of every step holds a kept pair, every kept pair lies
    in exactly one piece; by queries and by keys the same pieces; the
    counter's live tiles are the rule's, counted tile by tile."""
    want = brute_force(t, length)
    for by_keys in (False, True):
        table, n = ba.bd_plan(t, block, by_keys)
        rows, cols, variants, first, last = table.reshape(5, n)
        covered = np.zeros_like(want, int)
        for q, k, v in zip(rows, cols, variants):
            for (q0, k0), crossed in ba._pieces(int(v), block, tile).items():
                r, c = q * block + q0, k * block + k0
                piece = want[r:r + tile, c:c + tile]
                assert piece.any(), (q, k, v, q0, k0)
                assert crossed == (not piece.all())
                covered[r:r + tile, c:c + tile] += 1
        assert (covered[want] == 1).all() and covered.max() == 1
        rests = cols if by_keys else rows
        assert first.sum() == last.sum() == 2 * t // block
        # a block's steps are consecutive: its output rests in VMEM
        assert (np.diff(rests) != 0).sum() == 2 * t // block - 1
        # the work lists hold the same pieces, neighbours joined
        for v in (ba.WHOLE, ba.CLEAN, ba.NOISED, ba.SAME):
            held = {(p, b) if by_keys else (b, p)
                    for b0, b1, pieces in ba._work(v, block, tile, by_keys)
                    for b in range(b0, b1, tile)
                    for lo, hi, _ in pieces for p in range(lo, hi, tile)}
            assert held == set(ba._pieces(v, block, tile)), v
    live = sum(want[r:r + tile, c:c + tile].any()
               for r in range(0, 2 * t, tile) for c in range(0, 2 * t, tile))
    run, counted, kept, computed = ba.bd_tile_count(
        t, length, "kernel", block, tile)
    assert run == counted == live
    assert kept == want.sum() and computed == run * tile * tile
    dense = ba.bd_tile_count(t, length, "plain", block, tile)
    assert dense == ((2 * t // tile) ** 2, live, kept, 4 * t * t)


def test_the_cells_plan_is_the_issues_arithmetic():
    """T = 8,192 in tiles of 512: 136 clean-to-clean + 136 noised-to-
    clean + 16 noised-to-noised = 288 of 1,024 tiles — 80 grid steps of
    1,024 x 1,024 a pair of heads, the forward's by queries and the
    backward's by keys; a causal call over the 16,384 positions runs
    528."""
    assert ba.bd_tile_count(8192, 4) == (288, 288, 67_141_632,
                                         288 * 512 * 512)
    assert ba.bd_plan(8192, 1024)[1] == ba.bd_plan(8192, 1024, True)[1] == 80
    assert ba.bd_plan(8192, 512)[1] == 288
    assert fa.causal_tile_count(16384, 16384)[0] == 528
    assert ba._fit(8192, 4, None, None) == (1024, 512)
    assert ba._fit(8192, 4, None, None, "backward") == (1024, 512)
    assert ba.bd_plan(8192, 512, True)[1] == 288
    q, k = (jax.ShapeDtypeStruct((1, 16384, n * 128), jnp.bfloat16)
            for n in (32, 4))
    assert ba._geometry(q, k, 32, 4, 4, None, None, None, "backward") == (
        1, 16384, 128, 8, 1024, 512, 2)
    assert ba._fit(1536, 4, None, None) == (512, 512)
    assert ba._fit(8192, 1024, None, None) is None  # L over the tile
    assert ba._fit(1000, 4, None, None) is None


def test_the_route_is_what_the_call_can_observe(on_tpu):
    assert ba.bd_route(8192, 4, 32, 4, 128) == "kernel"
    assert ba.bd_route(8192, 4, 32, 4, 64) == "plain"     # no slab a head
    assert ba.bd_route(8192, 3, 32, 4, 128) == "plain"    # no power of two
    assert ba.bd_route(8200, 4, 32, 4, 128) == "plain"    # no whole blocks
    assert ba.bd_route(8, 4, 32, 4, 128) == "plain"       # a parameter draw
    assert ba.bd_route(16384, 4, 32, 4, 128) == "plain"   # no head's dq fits


@pytest.mark.parametrize("on_tpu", [False], indirect=True)
def test_off_the_tpu_every_call_is_plain(on_tpu):
    assert ba.bd_route(8192, 4, 32, 4, 128) == "plain"


@pytest.mark.parametrize("t,d,itemsize,route", [
    (8192, 128, 2, "kernel"), (14336, 128, 2, "kernel"),
    (15360, 128, 2, "plain"), (4096, 256, 2, "kernel"),
    (8192, 256, 2, "plain"), (8192, 128, 4, "kernel"),
    (10240, 128, 4, "plain"), (1024, 1024, 2, "kernel"),
    (2048, 1024, 2, "plain")])
def test_the_route_is_plain_exactly_where_one_heads_dq_does_not_fit(
        on_tpu, t, d, itemsize, route):
    """The backward keeps ONE head's dq and its kv head's dk and dv whole
    in VMEM: a float32 sum and two output buffers each over the 2t rows,
    beside a step's double-buffered operands (q and do, k and v, lse's
    and delta's rows on 8 sublanes) and three float32 score blocks."""
    assert ba.bd_route(t, 4, 32, 4, d, itemsize=itemsize) == route
    block = ba._fit(t, 4, None, None, "backward")[0]
    held = ba._bwd_vmem(1, 2 * t, d, itemsize, block)
    sums = 3 * 2 * t * d * (4 + 2 * itemsize)
    operands = 2 * (2 * 2 * block * d * itemsize + 2 * 8 * block * 4)
    assert held == sums + operands + 3 * block * block * 4
    assert (held <= ba._VMEM_LIMIT) == (route == "kernel")
    # a head more a step holds its dq and its operands more
    assert ba._bwd_vmem(2, 2 * t, d, itemsize, block) - held == \
        2 * t * d * (4 + 2 * itemsize) + 2 * 2 * block * d * itemsize \
        + 2 * 2 * 8 * block * 4


def test_a_step_takes_the_heads_whose_sums_fit(on_tpu):
    """Two heads a step at the cell's 2 x 8,192; one where two heads'
    dq would pass the limit (2 x 12,288: 96 MiB of sums alone)."""
    for t, heads in ((8192, 2), (12288, 1)):
        q, k = (jax.ShapeDtypeStruct((1, 2 * t, n * 128), jnp.bfloat16)
                for n in (32, 4))
        assert ba.bd_route(t, 4, 32, 4, 128) == "kernel"
        assert ba._geometry(q, k, 32, 4, 4, None, None, None,
                            "backward")[-1] == heads
        assert ba._geometry(q, k, 32, 4, 4, None, None, None,
                            "forward")[-1] == 2


def _operands(t, seed=0, batch=2, kv=KV):
    keys = jax.random.split(jax.random.key(seed), 4)
    return tuple(jax.random.normal(k, (batch, 2 * t, n * D), jnp.float32)
                 for k, n in zip(keys, (H, kv, kv, H)))


# (L, block, tile, blocks a copy, heads a step, kv heads of the 4 heads)
CASES = [(4, 16, 16, 1, 2, 2), (4, 16, 16, 2, 2, 2), (4, 16, 16, 3, 1, 2),
         (8, 32, 16, 1, 2, 2), (8, 32, 16, 2, 1, 2), (2, 32, 16, 3, 2, 2),
         (4, 32, 32, 2, 2, 2), (16, 32, 16, 3, 2, 2),
         # a unit smaller than the group of FOUR: dk and dv are sums over
         # four units of one head, or two of two
         (4, 16, 16, 2, 1, 1), (4, 32, 16, 3, 2, 1), (8, 32, 16, 2, 1, 1),
         (2, 32, 32, 2, 2, 1),
         # a copy of ONE block: the plan is three steps, each key block's
         # first is the sweep's first or its last
         (4, 16, 16, 1, 1, 1), (4, 32, 16, 1, 2, 1), (4, 32, 16, 1, 1, 2),
         # three and four blocks a copy: a query block's dq rests while
         # the sweep walks other key blocks' steps and comes back to it
         (4, 16, 16, 4, 2, 1), (8, 16, 16, 3, 4, 1), (4, 16, 16, 4, 1, 2)]


@pytest.mark.parametrize("length,block,tile,blocks,heads,kv", CASES)
def test_the_kernels_are_the_plain_route(length, block, tile, blocks, heads,
                                         kv):
    """o, dq, dk and dv of the interpreted kernels — the forward and the
    one backward sweep — against the dense lines under `kept_mask_bd`,
    float32 on both sides."""
    t = block * blocks
    q, k, v, g = _operands(t, seed=length + blocks, kv=kv)
    scale = D ** -0.5
    plan = (block, tile, heads, True)

    def plain(q, k, v):
        return ba._plain(q, k, v, H, kv, length, scale)

    def kernels(q, k, v):
        return ba._kernels(q, k, v, H, kv, length, scale, plan)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(plain, q, k, v)
        got, pull_k = jax.vjp(kernels, q, k, v)
        assert float(jnp.abs(got - want).max()) < 2e-6
        for name, a, b in zip("qkv", pull_k(g), pull(g)):
            assert float(jnp.abs(a - b).max()) < 1e-5, name


def test_a_noised_token_moves_no_clean_row_and_no_other_block():
    """What the mask is for, through the kernels: a change to one noised
    key reaches its own block's noised queries and nothing else."""
    length, block, t = 4, 16, 32
    q, k, v, _ = _operands(t, seed=3, batch=1)
    plan = (block, 16, 2, True)
    base = ba._kernels(q, k, v, H, KV, length, D ** -0.5, plan)
    at = t + 9  # noised token 9: block 2, rows t + 8 .. t + 11
    moved = ba._kernels(q, k.at[:, at].add(1.0), v.at[:, at].add(1.0), H,
                        KV, length, D ** -0.5, plan)
    rows = np.asarray(jnp.abs(moved - base).max(axis=(0, 2)) > 0)
    assert rows[t + 8:t + 12].all() and rows.sum() == 4


def test_the_entry_refuses_what_is_no_two_copies():
    q, k, v, _ = _operands(6, batch=1)
    with pytest.raises(ValueError, match="two copies"):
        ba.block_diffusion_attention(q, k, v, H, KV, 4)
    with pytest.raises(ValueError, match="no block"):
        ba._forward(q[:, :8], k[:, :8], v[:, :8], H, KV, 4, 1.0,
                    block=16, tile=16, interpret=True)


def test_the_probe_walks_its_plans_off_the_chip(tmp_path, capsys):
    """`tools/perf_probe.py attn_bd`, rehearsed: a line a plan with each
    plan's distance from the first, no device time claimed off the chip
    (no op of the trace bears a kernel's name), the lines kept."""
    import json

    from test_report_cli import _perf_probe_tool

    probe = _perf_probe_tool()
    out = tmp_path / "attn_bd.jsonl"
    probe.probe_attn_bd(shape=(1, 4, 1, 32, 16), blocks=((16, 16), (32, 16)),
                        heads=(1, 2), interpret=True, out=str(out))
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [json.loads(x) for x in
            capsys.readouterr().out.splitlines()] == lines
    assert [(x["block"], x["heads"]) for x in lines] == [
        (16, 1), (16, 2), (32, 1), (32, 2)]
    for line in lines:
        assert "error" not in line, line
        assert line["fwd_ms"] == line["bwd_ms"] == 0.0
        assert max(line["off_o_dq_dk_dv"]) < 0.1  # bfloat16 operands
    assert lines[0]["off_o_dq_dk_dv"] == [0.0] * 4
