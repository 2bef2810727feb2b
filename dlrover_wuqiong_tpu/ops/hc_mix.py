"""A hyper-connection's two mixes of the n-lane residual stream
(`models/hyper_connection.py`: the formulas, and the plain route) as
four streaming kernels: the stream is read once and written once a mix
a direction, and everything that is a few numbers a token rides the
pass that holds the token's four lanes in VMEM.

    dwt_hc_pre       X, Phi          -> u, coef      5 V   (V: one hidden
    dwt_hc_post      C, X, y         -> X'           9 V    vector a token)
    dwt_hc_post_bwd  C, dX', X, y    -> dy, pX, dC  14 V
    dwt_hc_pre_bwd   coef, dcoef, du, X, pX, Phi
                                     -> dX, dPhi, da 13 V  (pX aliased to dX)

`dwt_hc_pre` sums x^2 over the lanes and the hidden size, multiplies
(X Phi) on the MXU (the stream's dtype x itself into float32, as the
`einsum` of the plain route), scales by the norm's factor r, takes
h_pre = sigmoid(alpha_pre raw + b_pre) and writes u = sum_i h_pre[i]
X[i], float32 sums rounded once.  `coef` (b, Kp, T) float32 holds the
n^2 + 2n normalised raw coefficients and, in row n^2 + 2n, r: the
tokens on the lane axis, as Sinkhorn wants them.  `dwt_hc_post` writes
all n output lanes from one read of the n + 1 inputs; C (b, Cp, T) is
H_res's n^2 rows and h_post's n.  `dwt_hc_post_bwd` makes dy, the
stream's cotangent through H_res (pX) and the n^2 + n dot products over
the hidden size from one read.  `dwt_hc_pre_bwd` completes the
cotangent of the raw coefficients on the tile (dh_pre[i] = du . X[i]
joins what Sinkhorn's backward handed in), and writes

    dX[i] = pX[i] + h_pre[i] du + (d_raw r) Phi[i]^T - (r^2 c / (n d)) X[i]

(c = sum_k d_raw[k] raw[k], the statistic's term) summed in float32 and
rounded ONCE, accumulating dPhi[i] = (d_raw r)^T X[i] over the token
tiles on the MXU.

A grid step is (a batch row, a tile of tokens); inside it the VPU's
passes go a chunk of `_ROWS` tokens and a group of `_GROUP` 128-lane
slabs at a time, so that a turn's operands are a few registers each,
and what is a few numbers a token lives as COLUMNS of a (tile, Kp)
float32 array (tokens on the sublanes, a column broadcasts along the
hidden size) — the kernels transpose the small arrays between that and
HBM's (Kp, tile).

ONE differentiation rule a mix: `mix_in` hands the stream through as an
output, `mix_out` takes it from there, so the stream's cotangent
arrives at `mix_in`'s rule as one array and leaves it as one — no join,
no add of stream-sized arrays in XLA.

Which calls take the kernels is what a call can observe (`hc_route`): on
one of `_SITES`, the hidden size a whole number of 128-lane slabs, the
tokens a whole number of packed bfloat16 tiles (16); a last tile may be
ragged.  Everything else keeps `models/hyper_connection.py`'s formulas.

What a v5e trace showed: PERF.md section 6, PR 54 (`tools/perf_probe.py
hc`).

Parity: none — the reference trains Llama/GLM-class stacks only.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mosaic
from .mosaic import (
    LANES, _compiler_params, _dot, _dot_t, _out_struct, _reckoned_vmem,
    _round_up)

_ROWS = 16  # tokens a chunk: one packed bfloat16 tile of sublanes
_GROUP = 4  # 128-lane slabs of the hidden size worked on at a time
_TOKEN_TILE = 128  # tokens a grid step: a bf16 lane's block is 0.9 MB
_PRE_TILE = 512  # `dwt_hc_pre`'s: five blocks a step, not nine to fourteen
_SITES = frozenset({"device", "manual"})  # a token at a time: a shard is one
_F32 = jnp.float32


def hc_route(lanes: int, tokens: int, d: int, mesh=None) -> str:
    """Which route a hyper-connection over `lanes` lanes of `tokens` x
    `d` takes: "kernel" (`dwt_hc_*`) when the hidden size is a whole
    number of 128-lane slabs, the tokens a whole number of 16, and the
    call runs on one of `_SITES` (`mesh` is the model config's); else
    "plain", the formulas of
    `models/hyper_connection.py`.  The static counter of the decision,
    with the compiled step's count of `dwt_hc_*` custom calls."""
    if lanes < 2 or d % LANES or tokens % _ROWS \
            or mosaic.kernel_site(mesh) not in _SITES:
        return "plain"
    return "kernel"


def coef_rows(n: int) -> int:
    """Rows of `coef`: the n^2 + 2n raw coefficients, the norm's factor,
    padded to a packed bfloat16 tile (Phi's rows on the MXU)."""
    return _round_up(n * (n + 2) + 1, 16)


def _mix_rows(n: int) -> int:
    """Rows of C: H_res's n^2 and h_post's n, padded to a float32 tile."""
    return _round_up(n * n + n, 8)


def _each_group(d: int, body, carry=0, unroll=False):
    """`body(lanes, carry) -> carry` over the hidden size, `_GROUP`
    slabs at a time (fewer where they do not divide it): a loop whose
    body is traced once — as Python's own loop, seven copies of every
    pass's equations, the four kernels cost the step's set-up 15 s
    (PERF.md section 6, PR 54).  `unroll`: the loop's copies are made
    when the kernel is lowered, for a body of so few operations that the
    loop's own cost shows (`dwt_hc_pre` ran 0.65 ms rolled, 0.44 so)."""
    slabs = d // LANES
    width = max(g for g in range(1, _GROUP + 1) if slabs % g == 0) * LANES

    def step(g, carry):
        return body(pl.ds(pl.multiple_of(g * width, width), width), carry)

    return jax.lax.fori_loop(0, d // width, step, carry, unroll=unroll)


def _each_chunk(tile: int, body):
    """`body(rows)` over the tile's tokens, `_ROWS` at a time."""
    def step(c, carry):
        body(pl.ds(pl.multiple_of(c * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, tile // _ROWS, step, 0)


def _slab_sum(v):
    """(rows, lanes) -> (rows, 128): the 128-lane slabs added up."""
    return sum(v[:, s:s + LANES] for s in range(0, v.shape[1], LANES))


def _columns(parts, width: int):
    """[(rows, 128) partial sums] -> (rows, width): part k summed over
    its lanes into column k, zeros beside."""
    col = jax.lax.broadcasted_iota(jnp.int32, (parts[0].shape[0], width), 1)
    out = jnp.zeros(col.shape, _F32)
    for k, part in enumerate(parts):
        out = jnp.where(col == k, jnp.sum(part, axis=-1, keepdims=True), out)
    return out


def _zeros(count: int):
    return (jnp.zeros((_ROWS, LANES), _F32),) * count


# --------------------------------------------------------------- forward

def _pre_kernel(x_ref, phi_ref, gb_ref, u_ref, coef_ref, stat_ref, w_ref, *,
                n, eps):
    """x (1, n, tile, d), phi (n, Kp, d), gb (2, Kp): the gains and
    biases of h_pre in columns < n -> u (1, tile, d), coef (1, Kp,
    tile)."""
    tile, d = x_ref.shape[2:]
    k = n * (n + 2)
    raw = sum(_dot_t(x_ref[0, i], phi_ref[i]) for i in range(n))

    def statistic(rows):
        def squares(lanes, acc):
            for i in range(n):
                x = x_ref[0, i, rows, lanes].astype(_F32)
                acc = acc + _slab_sum(x * x)
            return acc

        stat_ref[rows, :] = _each_group(d, squares, _zeros(1)[0], True)

    _each_chunk(tile, statistic)
    r = jax.lax.rsqrt(jnp.sum(stat_ref[...], axis=-1, keepdims=True)
                      / (n * d) + eps)
    col = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 1)
    coef = jnp.where(col == k, r, raw * r)  # Phi's rows past k are zeros
    coef_ref[0] = coef.T
    w_ref[...] = jax.nn.sigmoid(gb_ref[0:1, :] * coef + gb_ref[1:2, :])

    def mix(rows):
        w = w_ref[rows, :]

        def lanes_mixed(lanes, carry):
            u = sum(w[:, i:i + 1] * x_ref[0, i, rows, lanes].astype(_F32)
                    for i in range(n))
            u_ref[0, rows, lanes] = u.astype(u_ref.dtype)
            return carry

        _each_group(d, lanes_mixed, 0, True)

    _each_chunk(tile, mix)


def _post_kernel(c_ref, x_ref, y_ref, o_ref, ct_ref, *, n):
    """C (1, Cp, tile), x (1, n, tile, d), y (1, tile, d) -> X'."""
    tile, d = y_ref.shape[1:]
    ct_ref[...] = c_ref[0].T

    def mix(rows):
        w = ct_ref[rows, :]
        cols = [w[:, c:c + 1] for c in range(n * n + n)]

        def lanes_mixed(lanes, carry):
            xs = [x_ref[0, j, rows, lanes].astype(_F32) for j in range(n)]
            y = y_ref[0, rows, lanes].astype(_F32)
            for i in range(n):
                out = sum(cols[i * n + j] * xs[j] for j in range(n)) \
                    + cols[n * n + i] * y
                o_ref[0, i, rows, lanes] = out.astype(o_ref.dtype)
            return carry

        _each_group(d, lanes_mixed)

    _each_chunk(tile, mix)


# -------------------------------------------------------------- backward

def _post_bwd_kernel(c_ref, g_ref, x_ref, y_ref, dy_ref, px_ref, dc_ref,
                     ct_ref, dt_ref, *, n):
    """C, dX' (1, n, tile, d), x, y -> dy, pX = H_res^T dX', dC (1, Cp,
    tile): dX'[i] . X[j] in row i n + j, dX'[i] . y in row n^2 + i."""
    tile, d = y_ref.shape[1:]
    ct_ref[...] = c_ref[0].T

    def mix(rows):
        w = ct_ref[rows, :]
        cols = [w[:, c:c + 1] for c in range(n * n + n)]

        def lanes_mixed(lanes, dots):
            gs = [g_ref[0, i, rows, lanes].astype(_F32) for i in range(n)]
            xs = [x_ref[0, j, rows, lanes].astype(_F32) for j in range(n)]
            y = y_ref[0, rows, lanes].astype(_F32)
            for j in range(n):
                px = sum(cols[i * n + j] * gs[i] for i in range(n))
                px_ref[0, j, rows, lanes] = px.astype(px_ref.dtype)
            dy = sum(cols[n * n + i] * gs[i] for i in range(n))
            dy_ref[0, rows, lanes] = dy.astype(dy_ref.dtype)
            pairs = [gs[i] * xs[j] for i in range(n) for j in range(n)] \
                + [gs[i] * y for i in range(n)]
            return tuple(dot + _slab_sum(pair)
                         for dot, pair in zip(dots, pairs))

        dt_ref[rows, :] = _columns(
            _each_group(d, lanes_mixed, _zeros(n * n + n)), dt_ref.shape[1])

    _each_chunk(tile, mix)
    dc_ref[0] = dt_ref[...].T


def _pre_bwd_kernel(coef_ref, dcoef_ref, gb_ref, du_ref, x_ref, px_ref,
                    phi_ref, dx_ref, dphi_ref, da_ref, dh_ref, w_ref, p_ref,
                    *, n, tokens):
    """coef and its cotangent (1, Kp, tile), du (1, tile, d), x, pX ->
    dX (pX's buffer), dPhi (n, Kp, d) float32 summed over the grid, da
    (1, Kp, tile): a_pre's cotangent in rows < n."""
    tile, d = du_ref.shape[1:]
    k, kp = n * (n + 2), phi_ref.shape[1]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, _F32)

    def dots(rows):
        def lanes_dotted(lanes, acc):
            du = du_ref[0, rows, lanes].astype(_F32)
            return tuple(a + _slab_sum(
                du * x_ref[0, i, rows, lanes].astype(_F32))
                for i, a in enumerate(acc))

        dh_ref[rows, :] = _columns(
            _each_group(d, lanes_dotted, _zeros(n), True), kp)

    _each_chunk(tile, dots)
    coef = coef_ref[0].T
    col = jax.lax.broadcasted_iota(jnp.int32, coef.shape, 1)
    r = jnp.sum(jnp.where(col == k, coef, 0.0), axis=-1, keepdims=True)
    h = jax.nn.sigmoid(gb_ref[0:1, :] * coef + gb_ref[1:2, :])
    da = dh_ref[...] * h * (1 - h)  # dh is zero past column n
    da_ref[0] = da.T
    d_raw = jnp.where(col < k, dcoef_ref[0].T, 0.0) + gb_ref[0:1, :] * da
    c = jnp.sum(jnp.where(col < k, d_raw * coef, 0.0), axis=-1,
                keepdims=True)
    w_ref[...] = jnp.where(col == n, -(r * r) * c / (n * d), h)
    d_raw = d_raw * r
    ragged = tokens % tile != 0
    if ragged:  # what a last tile holds behind T must not reach dPhi
        valid = pl.program_id(1) * tile + jax.lax.broadcasted_iota(
            jnp.int32, (tile, 1), 0) < tokens
        d_raw = jnp.where(valid, d_raw, 0.0)
    d_raw_t = d_raw.T.astype(x_ref.dtype)
    d_raw = d_raw.astype(x_ref.dtype)

    def lane(i, carry):
        x = x_ref[0, i]
        if ragged:
            x = jnp.where(valid, x, jnp.zeros_like(x))
        dphi_ref[i] = dphi_ref[i] + _dot(d_raw_t, x)
        p_ref[...] = _dot(d_raw, phi_ref[i])
        col = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, kp), 1)

        def total(rows):
            w = w_ref[rows, :]
            h_i = jnp.sum(jnp.where(col == i, w, 0.0), axis=-1,
                          keepdims=True)
            q = w[:, n:n + 1]

            def lanes_summed(lanes, carry):
                dx = px_ref[0, i, rows, lanes].astype(_F32) \
                    + h_i * du_ref[0, rows, lanes].astype(_F32) \
                    + q * x_ref[0, i, rows, lanes].astype(_F32) \
                    + p_ref[rows, lanes]
                dx_ref[0, i, rows, lanes] = dx.astype(dx_ref.dtype)
                return carry

            _each_group(d, lanes_summed, 0, True)

        _each_chunk(tile, total)
        return carry

    jax.lax.fori_loop(0, n, lane, 0, unroll=True)


# ----------------------------------------------------------------- calls

def _stream(n, tile, d):
    return pl.BlockSpec((1, n, tile, d), lambda b, i: (b, 0, i, 0))


def _branch(tile, d):
    return pl.BlockSpec((1, tile, d), lambda b, i: (b, i, 0))


def _small(rows, tile):
    return pl.BlockSpec((1, rows, tile), lambda b, i: (b, 0, i))


def _whole(shape):
    return pl.BlockSpec(shape, lambda b, i: (0,) * len(shape))


def _params(block_bytes: int, scratch_bytes: int):
    # every block double-buffered, the scratch, and room for a chunk's
    # temporaries and the small arrays' two layouts
    vmem = 2 * block_bytes + scratch_bytes + 4 * 1024 * 1024
    return _compiler_params("arbitrary", "arbitrary",
                            vmem_limit=_reckoned_vmem(vmem))


def _pre_pallas(x, phi_t, gb, *, eps, tile, interpret):
    b, n, t, d = x.shape
    kp, size = phi_t.shape[1], jnp.dtype(x.dtype).itemsize
    lane = tile * d * size
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, eps=eps),
        grid=(b, pl.cdiv(t, tile)),
        in_specs=[_stream(n, tile, d), _whole(phi_t.shape), _whole(gb.shape)],
        out_specs=(_branch(tile, d), _small(kp, tile)),
        out_shape=(_out_struct((b, t, d), x.dtype, x),
                   _out_struct((b, kp, t), _F32, x)),
        scratch_shapes=[pltpu.VMEM((tile, LANES), _F32),
                        pltpu.VMEM((tile, kp), _F32)],
        compiler_params=_params((n + 1) * lane + phi_t.size * size,
                                2 * tile * LANES * 4),
        cost_estimate=pl.CostEstimate(
            flops=(2 * kp + 4) * x.size, transcendentals=b * t * kp,
            bytes_accessed=(n + 1) * b * t * d * size + phi_t.size * size
            + b * kp * t * 4),
        interpret=interpret,
        name="dwt_hc_pre",
    )(x, phi_t, gb)


def _post_pallas(c, x, y, *, tile, interpret):
    b, n, t, d = x.shape
    cp, size = c.shape[1], jnp.dtype(x.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(b, pl.cdiv(t, tile)),
        in_specs=[_small(cp, tile), _stream(n, tile, d), _branch(tile, d)],
        out_specs=_stream(n, tile, d),
        out_shape=_out_struct(x.shape, x.dtype, x),
        scratch_shapes=[pltpu.VMEM((tile, cp), _F32)],
        compiler_params=_params((2 * n + 1) * tile * d * size,
                                tile * LANES * 4),
        cost_estimate=pl.CostEstimate(
            flops=2 * (n + 1) * x.size, transcendentals=0,
            bytes_accessed=(2 * n + 1) * b * t * d * size + c.size * 4),
        interpret=interpret,
        name="dwt_hc_post",
    )(c, x, y)


def _post_bwd_pallas(c, g, x, y, *, tile, interpret):
    b, n, t, d = x.shape
    cp, size = c.shape[1], jnp.dtype(x.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        grid=(b, pl.cdiv(t, tile)),
        in_specs=[_small(cp, tile), _stream(n, tile, d), _stream(n, tile, d),
                  _branch(tile, d)],
        out_specs=(_branch(tile, d), _stream(n, tile, d), _small(cp, tile)),
        out_shape=(_out_struct(y.shape, y.dtype, x),
                   _out_struct(x.shape, x.dtype, x),
                   _out_struct(c.shape, _F32, x)),
        scratch_shapes=[pltpu.VMEM((tile, cp), _F32),
                        pltpu.VMEM((tile, cp), _F32)],
        compiler_params=_params((3 * n + 2) * tile * d * size,
                                2 * tile * LANES * 4),
        cost_estimate=pl.CostEstimate(
            flops=4 * (n + 1) * x.size, transcendentals=0,
            bytes_accessed=(3 * n + 2) * b * t * d * size + 2 * c.size * 4),
        interpret=interpret,
        name="dwt_hc_post_bwd",
    )(c, g, x, y)


def _pre_bwd_pallas(coef, d_coef, gb, du, x, px, phi_t, *, tile, interpret):
    b, n, t, d = x.shape
    kp, size = phi_t.shape[1], jnp.dtype(x.dtype).itemsize
    lane = tile * d * size
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, tokens=t),
        grid=(b, pl.cdiv(t, tile)),
        in_specs=[_small(kp, tile), _small(kp, tile), _whole(gb.shape),
                  _branch(tile, d), _stream(n, tile, d), _stream(n, tile, d),
                  _whole(phi_t.shape)],
        out_specs=(_stream(n, tile, d), _whole(phi_t.shape),
                   _small(kp, tile)),
        out_shape=(_out_struct(x.shape, x.dtype, x),
                   _out_struct(phi_t.shape, _F32, x),
                   _out_struct(coef.shape, _F32, x)),
        scratch_shapes=[pltpu.VMEM((tile, kp), _F32),
                        pltpu.VMEM((tile, kp), _F32),
                        pltpu.VMEM((tile, d), _F32)],
        input_output_aliases={5: 0},
        compiler_params=_params(
            (3 * n + 1) * lane + phi_t.size * (size + 4),
            2 * tile * LANES * 4 + tile * d * 4),
        cost_estimate=pl.CostEstimate(
            flops=(4 * kp + 6) * x.size, transcendentals=b * t * kp,
            bytes_accessed=(3 * n + 1) * b * t * d * size
            + phi_t.size * (size + 4) + 3 * coef.size * 4),
        interpret=interpret,
        name="dwt_hc_pre_bwd",
    )(coef, d_coef, gb, du, x, px, phi_t)


# behind `jax.jit` a kernel is traced and lowered to Mosaic once a
# shape, not once a sublayer
_pre = jax.jit(_pre_pallas, static_argnames=("eps", "tile", "interpret"))
_post = jax.jit(_post_pallas, static_argnames=("tile", "interpret"))
_post_bwd = jax.jit(_post_bwd_pallas, static_argnames=("tile", "interpret"))
_pre_bwd = jax.jit(_pre_bwd_pallas, static_argnames=("tile", "interpret"))


def plan(tokens: int, tile=None, interpret: bool = False) -> tuple:
    """The static arguments of one call's kernels: all of a short
    sequence in one tile, else tiles of whole 128-token lane slabs (the
    small arrays carry the tokens on their lanes) — `tile` for all four
    (tests, the probe), else each kernel's own (PERF.md section 6, PR
    54: `dwt_hc_pre` 0.349 ms a call at 512 tokens, 0.443 at 128; the
    three others fastest at 128)."""
    tiles = (tile or _TOKEN_TILE, tile or _PRE_TILE)
    assert tokens % _ROWS == 0 and not any(t % LANES for t in tiles), (
        tokens, tiles)
    return (("tile", min(tokens, tiles[0])),
            ("pre_tile", min(tokens, tiles[1])), ("interpret", interpret))


def _stream_plan(plan: tuple) -> dict:
    """The three kernels' that hold the stream twice or more a step."""
    return {k: v for k, v in plan if k != "pre_tile"}


@contextlib.contextmanager
def _scoped(name):
    """`hc/<name>`, opened by the rules themselves: a custom rule's
    backward is traced outside the forward's scopes."""
    with jax.named_scope("hc"), jax.named_scope(name):
        yield


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def mix_in(x, phi, alpha_pre, b_pre, eps, plan):
    """x (b, n, T, d), Phi (n, d, n^2 + 2n), h_pre's gain and biases (n)
    -> u (b, T, d), coef (b, Kp, T) float32 (rows < n^2 + 2n: the raw
    coefficients of the NORMED stream, z Phi; row n^2 + 2n: the norm's
    factor, no cotangent taken), and x itself: `mix_out` takes the
    stream from here, so that its cotangent comes back to this rule."""
    return _mix_in_fwd(x, phi, alpha_pre, b_pre, eps, plan)[0]


def _mix_in_fwd(x, phi, alpha_pre, b_pre, eps, plan):
    n, kp = x.shape[1], coef_rows(x.shape[1])
    with _scoped("coeff"):
        phi_t = jnp.pad(phi.astype(x.dtype).transpose(0, 2, 1),
                        ((0, 0), (0, kp - phi.shape[2]), (0, 0)))
        gb = jnp.pad(jnp.stack([jnp.broadcast_to(alpha_pre, (n,)), b_pre])
                     .astype(_F32), ((0, 0), (0, kp - n)))
    with _scoped("pre"):
        own = dict(plan)
        u, coef = _pre(x, phi_t, gb, eps=eps, tile=own["pre_tile"],
                       interpret=own["interpret"])
    return (u, coef, x), (x, phi_t, gb, coef)


def _mix_in_bwd(eps, plan, res, cts):
    x, phi_t, gb, coef = res
    du, d_coef, px = cts
    n = x.shape[1]
    k = n * (n + 2)
    with _scoped("pre"):
        dx, dphi_t, da = _pre_bwd(coef, d_coef, gb, du, x, px, phi_t,
                                  **_stream_plan(plan))
    with _scoped("coeff"):
        da = da[:, :n]
        return (dx, dphi_t[:, :k].transpose(0, 2, 1),
                jnp.sum(da * coef[:, :n]), da.sum((0, 2)))


mix_in.defvjp(_mix_in_fwd, _mix_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def mix_out(h_res, h_post, x, y, plan):
    """X'[i] = sum_j H_res[i, j] X[j] + h_post[i] y: (b, n, n, T), (b,
    n, T) float32, (b, n, T, d), (b, T, d) -> (b, n, T, d)."""
    return _mix_out_fwd(h_res, h_post, x, y, plan)[0]


def _mix_out_fwd(h_res, h_post, x, y, plan):
    b, n, t = h_post.shape
    with _scoped("post_res"):
        c = jnp.concatenate([h_res.reshape(b, n * n, t), h_post], axis=1)
        c = jnp.pad(c, ((0, 0), (0, _mix_rows(n) - c.shape[1]), (0, 0)))
        return _post(c, x, y, **_stream_plan(plan)), (c, x, y)


def _mix_out_bwd(plan, res, d_out):
    c, x, y = res
    b, n, t = x.shape[:3]
    with _scoped("post_res"):
        dy, px, dc = _post_bwd(c, d_out, x, y, **_stream_plan(plan))
        return (dc[:, :n * n].reshape(b, n, n, t), dc[:, n * n:n * n + n],
                px, dy)


mix_out.defvjp(_mix_out_fwd, _mix_out_bwd)
