"""Pipeline parallelism over the mesh `pp` axis — GPipe / interleaved /
1F1B schedules, SPMD.

Parity: reference pipe compiler (`atorch/atorch/modules/distributed_modules/
compilers/pipe_compiler/PipelineStage.py:115,922` — PiPPy stage split +
1F1B/interleaved schedule over torch RPC) and `StageInterleaver.py`, plus
`auto/opt_lib/pipeline_parallel_optimization.py:56`.

TPU redesign: no RPC driver and no stage processes.  The layer stack is
stacked into one pytree with a leading layer axis sharded `P("pp")`, and the
schedule is a `lax.scan` over pipeline ticks inside `shard_map` restricted to
the `pp` axis (`axis_names={"pp"}`): each tick every stage applies its local
layer slice and hands its activation to the next stage with
`jax.lax.ppermute` (ICI neighbor link).  All other mesh axes (dp/fsdp/tp/sp)
stay in GSPMD "auto" mode inside the body, so pipeline composes with the rest
of the strategy space.

Three schedules (lockstep-SPMD analysis — all stages tick together, so the
torch 1F1B's *async* throughput win does not exist here; what transfers is):

- "gpipe": forward scan, autodiff replays it backward (fill-drain).  Bubble
  fraction (pp-1)/(M+pp-1).  Activation residuals: one per tick — O(M)
  stage-inputs live through the backward.
- "interleaved": Megatron-style interleaved virtual stages, expressed as the
  circular schedule — each device owns `v` non-contiguous layer chunks and
  microbatches wrap around the ring `v` times.  Bubble fraction shrinks to
  (pp-1)/(M*v+pp-1): the fill/drain cost is per *chunk* (1/v of a stage).
  Autodiff again yields the mirrored backward.
- "1f1b": manual one-forward-one-backward schedule.  Each tick a stage runs
  one microbatch forward AND one backward (with on-the-fly recompute from the
  stashed stage *input*), so the live stash is min(M, 2pp-1) microbatch
  inputs — O(pp), independent of M — vs GPipe's O(M).  Same-tick head
  coupling on the last stage starts each microbatch's backward immediately
  after its forward, exactly the 1F1B dependency pattern.  Tick count is
  M + 2(pp-1) combined fwd+bwd ticks (GPipe: M+pp-1 of each), so throughput
  is within (M+pp-1)/(M+2pp-2) of GPipe while memory scales with pp, not M —
  use it to raise M (and thereby shrink the bubble) under a fixed HBM budget.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..common.log import get_logger

logger = get_logger("pipeline")


def _pvary_pp(tree):
    """Mark a scan carry as pp-varying for VMA-tracked (nested) contexts.

    Under check_vma=True the scan carry must enter with the same varying-
    axes type it leaves with (ppermute/axis_index make it {V:pp}); outside
    VMA tracking pvary is a no-op."""
    try:
        return jax.tree.map(
            lambda x: jax.lax.pcast(x, ("pp",), to="varying"), tree)
    except Exception:  # noqa: BLE001 — no VMA tracking here: a no-op
        return tree


def _pp_shard_map(f, mesh, in_specs, out_specs):
    """shard_map manual over ONLY the pp axis; other axes stay GSPMD.

    When NESTED inside another manual body (the DiLoCo dp step), the mesh
    must be the context AbstractMesh and VMA tracking must be ON — the
    pp x ring-SP closure showed that an inner shard_map's transpose
    silently corrupts gradients without it (tests pin grad exactness)."""
    from .mesh import context_mesh

    ctx = context_mesh(mesh)
    nested = ctx is not mesh
    return jax.shard_map(f, mesh=ctx, in_specs=in_specs,
                         out_specs=out_specs, axis_names={"pp"},
                         check_vma=nested)


def schedule_ticks(schedule: str, num_microbatches: int, pp: int,
                   virtual_stages: int = 1) -> Tuple[int, float]:
    """(tick count, bubble fraction) of a schedule's forward pass.

    Per-tick work is one layer-*chunk* (a full per-device stage for
    gpipe/1f1b, 1/v of it for interleaved), so bubble fractions are directly
    comparable across schedules."""
    M, v = num_microbatches, virtual_stages
    if schedule == "interleaved":
        ticks = M * v + pp - 1
        return ticks, (pp - 1) / ticks
    ticks = M + pp - 1
    return ticks, (pp - 1) / ticks


def default_pp_microbatches(accum_steps: int, pp: int) -> int:
    """The microbatch-count policy shared by auto_accelerate (what gets
    built) and the strategy engine's bubble estimate (what gets scored) —
    one definition so they cannot silently diverge."""
    return max(accum_steps, 2 * pp)


def circular_layer_order(n_layer: int, pp: int, v: int) -> List[int]:
    """Layer permutation for the interleaved (circular) schedule.

    Chunk c (layers [c*Lc, (c+1)*Lc)) lives on device `c % pp` at local
    position `c // pp`; this order makes each device's `P("pp")` slice of the
    stacked layer axis exactly its v chunks, concatenated."""
    if n_layer % (pp * v):
        raise ValueError(f"layers ({n_layer}) must divide by pp*v="
                         f"{pp * v} for the interleaved schedule")
    lc = n_layer // (pp * v)
    order = []
    for d in range(pp):
        for j in range(v):
            c = d + j * pp
            order.extend(range(c * lc, (c + 1) * lc))
    return order


def _apply_block(block_fn, pl, h):
    """block_fn may return h or (h, aux_scalar) — MoE blocks surface their
    load-balancing aux loss this way (sown intermediates cannot cross the
    shard_map/scan boundary)."""
    out = block_fn(pl, h)
    if isinstance(out, tuple):
        h2, aux = out
        return h2, aux.astype(jnp.float32)
    return out, jnp.zeros((), jnp.float32)


def _scan_blocks(block_fn, h, layer_params):
    """Sequentially apply stacked layers, accumulating aux: the ONE
    aux-carry implementation shared by every schedule."""
    def _layer(carry, pl):
        h, a = carry
        h2, a2 = _apply_block(block_fn, pl, h)
        return (h2, a + a2), None

    (h, aux), _ = jax.lax.scan(_layer, (h, jnp.zeros((), jnp.float32)),
                               layer_params)
    return h, aux


def pipeline_apply(block_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params: Any, x: jax.Array, mesh: Mesh,
                   num_microbatches: int, schedule: str = "gpipe",
                   virtual_stages: int = 1, with_aux: bool = False):
    """Run a stacked layer pytree as a `pp`-stage pipeline over `x`.

    Args:
        block_fn: (one_layer_params, x) -> x  OR  -> (x, aux_scalar)
            (MoE load-balance loss), applied per layer.
        stacked_params: pytree whose leaves have a leading layer axis L
            (sharded P("pp") — L must divide evenly by pp).  For
            schedule="interleaved" the layer axis must already be in
            `circular_layer_order`.
        x: (B, T, C) activations, replicated over pp.
        num_microbatches: M; must divide B.
        schedule: "gpipe" | "interleaved" ("1f1b" is a training schedule —
            see `pipeline_1f1b`; its forward alone is gpipe).
        virtual_stages: v chunks per device for "interleaved".
        with_aux: also return the mean-over-microbatches aux loss
            (replicated over pp, differentiable).
    Returns (B, T, C) replicated over pp — or ((B, T, C), aux) with_aux.
    """
    pp = mesh.shape.get("pp", 1)
    if pp == 1:
        out, aux = _scan_blocks(block_fn, x, stacked_params)
        return (out, aux) if with_aux else out

    B = x.shape[0]
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    xm = x.reshape(M, B // M, *x.shape[1:])
    if schedule == "interleaved" and virtual_stages > 1:
        out, aux = _interleaved_apply(block_fn, stacked_params, xm, mesh,
                                      virtual_stages)
        out = out.reshape(B, *x.shape[1:])
        return (out, aux) if with_aux else out

    def _stage_body(sp_local, xm_full):
        # sp_local leaves: (L/pp, ...) — this stage's layer slice
        # xm_full: (M, b, T, C) — replicated over pp
        stage = jax.lax.axis_index("pp")
        n_ticks = M + pp - 1
        fwd_perm = [(i, i + 1) for i in range(pp - 1)]

        def _tick(carry, t):
            buf, outs, aux_acc = carry
            mb_in = jnp.clip(t, 0, M - 1)
            h_in = jnp.where(stage == 0, xm_full[mb_in], buf)
            y, aux_t = _scan_blocks(block_fn, h_in, sp_local)
            # fill/drain ticks compute on garbage: only count aux for this
            # stage's valid microbatch (m = t - stage)
            valid = (t - stage >= 0) & (t - stage < M)
            aux_acc = aux_acc + jnp.where(valid, aux_t, 0.0)
            # hand activation to the next stage (no wraparound)
            buf_next = jax.lax.ppermute(y, "pp", fwd_perm)
            # last stage finished microbatch t-(pp-1) at this tick
            out_idx = t - (pp - 1)
            write = (stage == pp - 1) & (out_idx >= 0)
            outs_upd = outs.at[jnp.clip(out_idx, 0, M - 1)].set(y)
            outs = jnp.where(write, outs_upd, outs)
            return (buf_next, outs, aux_acc), None

        buf0 = jnp.zeros_like(xm_full[0])
        outs0 = jnp.zeros_like(xm_full)
        (_, outs, aux_acc), _ = jax.lax.scan(
            _tick, _pvary_pp((buf0, outs0, jnp.zeros((), jnp.float32))),
            jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast over pp so the
        # head computes identically (and cheaply) on every stage
        outs = jax.lax.psum(
            jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs)), "pp")
        # per-stage aux sums over pp; /M = mean over microbatches (matches
        # the dense model's single whole-batch aux)
        aux = jax.lax.psum(aux_acc, "pp") / M
        return outs, aux

    out, aux = _pp_shard_map(
        _stage_body, mesh,
        in_specs=(P("pp"), P()), out_specs=(P(), P()))(stacked_params, xm)
    out = out.reshape(B, *x.shape[1:])
    return (out, aux) if with_aux else out


def _interleaved_apply(block_fn, stacked_params, xm, mesh, v):
    """Circular (interleaved virtual-stage) schedule forward.

    Event (microbatch m, chunk c) runs at tick `c + (m % pp) + pp*v*(m // pp)`
    on device `c % pp` — gap-1 chains (activations hop exactly one tick via a
    wraparound ppermute), no per-device tick collisions, and M*v + pp - 1
    total ticks: the fill/drain bubble costs chunks (1/v stages), not stages.
    Requires M % pp == 0.
    """
    pp = mesh.shape["pp"]
    M = xm.shape[0]
    if M % pp:
        raise ValueError(f"interleaved schedule needs microbatches ({M}) "
                         f"divisible by pp={pp}")

    def _stage_body(sp_local, xm_full):
        stage = jax.lax.axis_index("pp")
        l_loc = jax.tree.leaves(sp_local)[0].shape[0]
        if l_loc % v:
            raise ValueError(f"per-device layers ({l_loc}) not divisible by "
                             f"virtual_stages={v}")
        lc = l_loc // v
        n_ticks = M * v + pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def _apply_chunk(j, h):
            chunk = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, j * lc, lc, 0),
                sp_local)
            return _scan_blocks(block_fn, h, chunk)

        def _tick(carry, t):
            buf, outs, aux_acc = carry
            u = t - stage
            r = jnp.mod(u, pp)            # m % pp
            k = jnp.floor_divide(u, pp)   # j + v * (m // pp)
            j = jnp.clip(jnp.mod(k, v), 0, v - 1)
            q = jnp.floor_divide(k, v)    # m // pp
            valid = (u >= 0) & (q >= 0) & (q < M // pp)
            m = jnp.clip(r + pp * q, 0, M - 1)
            first = (stage == 0) & (j == 0)
            h_in = jnp.where(first, xm_full[m], buf)
            y, aux_t = _apply_chunk(j, h_in)
            aux_acc = aux_acc + jnp.where(valid, aux_t, 0.0)
            is_out = valid & (stage == pp - 1) & (j == v - 1)
            outs = jnp.where(is_out, outs.at[m].set(y), outs)
            return (jax.lax.ppermute(y, "pp", perm), outs, aux_acc), None

        buf0 = jnp.zeros_like(xm_full[0])
        outs0 = jnp.zeros_like(xm_full)
        (_, outs, aux_acc), _ = jax.lax.scan(
            _tick, _pvary_pp((buf0, outs0, jnp.zeros((), jnp.float32))),
            jnp.arange(n_ticks))
        outs = jax.lax.psum(
            jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs)), "pp")
        aux = jax.lax.psum(aux_acc, "pp") / M
        return outs, aux

    return _pp_shard_map(
        _stage_body, mesh,
        in_specs=(P("pp"), P()), out_specs=(P(), P()))(stacked_params, xm)


# ------------------------------------------------------------ 1F1B training


def _tree_zeros_like(t):
    return jax.tree.map(jnp.zeros_like, t)


def pipeline_1f1b(block_fn: Callable[[Any, jax.Array], jax.Array],
                  head_loss_fn: Callable[[Any, jax.Array, jax.Array],
                                         jax.Array],
                  stacked_params: Any, head_params: Any, xm: jax.Array,
                  aux: jax.Array, mesh: Mesh
                  ) -> Tuple[jax.Array, Any, Any, jax.Array]:
    """One-forward-one-backward pipeline training schedule.

    Per tick, every stage runs one microbatch forward and one backward.  The
    backward recomputes the stage from its stashed *input* (activation
    rematerialization), so the live stash is min(M, 2pp-1) microbatch inputs
    per stage — independent of M — where GPipe-through-autodiff keeps
    M + pp - 1 tick residuals alive.  The last stage folds the head+loss
    vjp into its forward slot, seeding each microbatch's backward in the same
    tick (the 1F1B dependency pattern; ref PipelineStage.py:922
    StageInterleaver's fwd/bwd queues).

    Schedule (device d, tick t): forward of microbatch `t - d`; backward of
    microbatch `t - 2(pp-1) + d`.  Both chains hop exactly one tick, so one
    forward ppermute and one backward ppermute per tick suffice.

    Args:
        block_fn: (layer_params, h) -> h OR (h, aux_scalar) — MoE blocks
            surface the router balance loss as aux; its value folds into
            the reported loss and its 1/M cotangent is seeded in each
            backward slot, so MoE composes with 1f1b.
        head_loss_fn: (head_params, h, aux_mb) -> scalar mean loss for one
            microbatch (runs on the last stage only).
        stacked_params: (L, ...) leaves, sharded P("pp").
        head_params: pytree, replicated over pp.
        xm: (M, b, T, C) embedded microbatches.
        aux: (M, b, ...) per-microbatch labels/extras for head_loss_fn.
    Returns:
        (loss, d_stacked, d_head, d_xm) — loss/d_head/d_xm replicated over
        pp, d_stacked sharded P("pp").  All grads are d(mean-over-M loss).
    """
    M = xm.shape[0]
    pp = mesh.shape.get("pp", 1)
    if pp == 1:
        def _total(sp, hp, xm_):
            def _mb(carry, mx):
                x_mb, aux_mb = mx
                h, a = _scan_blocks(block_fn, x_mb, sp)
                return carry + head_loss_fn(hp, h, aux_mb) + a, None
            total, _ = jax.lax.scan(_mb, jnp.zeros((), jnp.float32),
                                    (xm_, aux))
            return total / M
        loss, (d_sp, d_hp, d_xm) = jax.value_and_grad(
            _total, argnums=(0, 1, 2))(stacked_params, head_params, xm)
        return loss, d_sp, d_hp, d_xm

    S = min(M, 2 * pp - 1)          # stash ring size — the memory headline
    n_ticks = M + 2 * (pp - 1)
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    bwd_perm = [(i, i - 1) for i in range(1, pp)]

    def _stage_body(sp_local, hp, xm_full, aux_full):
        stage = jax.lax.axis_index("pp")
        zero_h = jnp.zeros_like(xm_full[0])

        def _apply_stage(p, h):
            # (y, aux_scalar): MoE blocks surface the router balance loss;
            # dense blocks get aux = 0 and a zero cotangent — one uniform
            # code path instead of a rejected composition
            return _scan_blocks(block_fn, h, p)

        def _tick(carry, t):
            # Every slot computes unconditionally and masks its results:
            # tp/fsdp collectives live inside the stage/head bodies, and a
            # collective under a pp-varying `lax.cond` deadlocks the
            # cross-device rendezvous (different pp ranks would execute
            # different collective sequences).  Fill/drain waste is bounded:
            # per device the head runs (M+2pp-2)/M times GPipe's head work.
            fwd_buf, bwd_buf, stash, d_sp, d_hp, d_xm, loss = carry

            # ---- forward slot
            m_f = t - stage
            fwd_valid = (m_f >= 0) & (m_f < M)
            m_fc = jnp.clip(m_f, 0, M - 1)
            h_in = jnp.where(stage == 0, xm_full[m_fc], fwd_buf)
            y, aux_t = _apply_stage(sp_local, h_in)
            stash = jnp.where(fwd_valid, stash.at[m_fc % S].set(h_in),
                              stash)
            # the aux VALUE accumulates on the computing stage per valid
            # forward; its psum over pp lands in the reported loss below
            loss = loss + jnp.where(fwd_valid,
                                    aux_t.astype(jnp.float32) / M, 0.0)

            # head + loss, kept on the last stage by masking (cotangent 1/M
            # folds the mean-over-microbatches into every downstream grad)
            lm, head_vjp = jax.vjp(
                lambda hp_, h_: head_loss_fn(hp_, h_, aux_full[m_fc]),
                hp, y)
            d_hp_m, dh_seed = head_vjp(jnp.ones((), lm.dtype) / M)
            is_last_f = fwd_valid & (stage == pp - 1)
            loss = loss + jnp.where(is_last_f,
                                    lm.astype(jnp.float32) / M, 0.0)
            d_hp = jax.tree.map(
                lambda acc, g: acc + jnp.where(is_last_f, g,
                                               jnp.zeros_like(g)),
                d_hp, d_hp_m)

            # ---- backward slot (recompute-from-stash vjp)
            m_b = t - 2 * (pp - 1) + stage
            bwd_valid = (m_b >= 0) & (m_b < M)
            m_bc = jnp.clip(m_b, 0, M - 1)
            dy = jnp.where(stage == pp - 1, dh_seed, bwd_buf)
            h_s = stash[m_bc % S]
            _, stage_vjp = jax.vjp(_apply_stage, sp_local, h_s)
            # seed BOTH outputs: dL/dy from downstream, dL/daux = 1/M (the
            # mean-over-microbatches weight of the router balance loss) —
            # this is the cotangent whose absence forced the old
            # MoE x 1f1b rejection
            d_p_m, dh_prev = stage_vjp(
                (dy.astype(h_s.dtype),
                 jnp.ones((), jnp.float32) / M))
            d_sp = jax.tree.map(
                lambda acc, g: acc + jnp.where(bwd_valid, g,
                                               jnp.zeros_like(g)),
                d_sp, d_p_m)
            dh_prev = jnp.where(bwd_valid, dh_prev, zero_h)
            d_xm = jnp.where(bwd_valid & (stage == 0),
                             d_xm.at[m_bc].set(dh_prev), d_xm)

            # ---- ring hops (unconditional; invalid slots carry zeros that
            # land in equally-invalid slots next tick)
            fwd_buf = jax.lax.ppermute(y, "pp", fwd_perm)
            bwd_buf = jax.lax.ppermute(dh_prev, "pp", bwd_perm)
            return (fwd_buf, bwd_buf, stash, d_sp, d_hp, d_xm, loss), None

        carry0 = _pvary_pp(
            (zero_h, zero_h,
             jnp.zeros((S,) + xm_full[0].shape, xm_full.dtype),
             _tree_zeros_like(sp_local), _tree_zeros_like(hp),
             jnp.zeros_like(xm_full), jnp.zeros((), jnp.float32)))
        (_, _, _, d_sp, d_hp, d_xm, loss), _ = jax.lax.scan(
            _tick, carry0, jnp.arange(n_ticks))

        # loss: CE lives on the last stage only (masked at accumulation);
        # per-stage aux sums live everywhere — psum folds both
        loss = jax.lax.psum(loss, "pp")
        d_hp = jax.tree.map(
            lambda g: jax.lax.psum(
                jnp.where(stage == pp - 1, g, jnp.zeros_like(g)), "pp"),
            d_hp)
        d_xm = jax.lax.psum(
            jnp.where(stage == 0, d_xm, jnp.zeros_like(d_xm)), "pp")
        return loss, d_sp, d_hp, d_xm

    return _pp_shard_map(
        _stage_body, mesh,
        in_specs=(P("pp"), P(), P(), P()),
        out_specs=(P(), P("pp"), P(), P()))(
            stacked_params, head_params, xm, aux)


# --------------------------------------------------------- model integration


_LAYER_RE = re.compile(r"^(h|layers)_(\d+)$")


def split_layer_params(params: Dict) -> Tuple[Dict, List[Dict], str]:
    """Split a flax param dict into (non_layer, [layer_0..layer_{L-1}], key
    prefix).  Layers are the `h_<i>` / `layers_<i>` subtrees."""
    non_layer, layers = {}, {}
    prefix = None
    for k, v in params.items():
        m = _LAYER_RE.match(k)
        if m:
            prefix = m.group(1)
            layers[int(m.group(2))] = v
        else:
            non_layer[k] = v
    ordered = [layers[i] for i in range(len(layers))]
    if not ordered:
        raise ValueError("model has no h_<i>/layers_<i> blocks to pipeline")
    return non_layer, ordered, prefix or "h"


def stack_layer_params(layers: List[Dict]) -> Dict:
    """[per-layer pytree] -> one pytree with leading layer axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layer_params(stacked: Dict, n: int) -> List[Dict]:
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


@dataclasses.dataclass
class PipelinedLM:
    """Wraps a block-structured LM (GPT/Llama family) for pp execution.

    Looks like a model to the rest of the stack: has `.config`, `.apply`,
    `.init_params`.  Params restructure to {non-layer..., "blocks": stacked}.

    `schedule`: "gpipe" | "interleaved" | "1f1b".  Interleaved stores the
    stacked layer axis in `circular_layer_order` (undone by
    `to_flat_params`).  "1f1b" applies to training via `value_and_grad`;
    its plain forward is gpipe.

    Arbitrary layer-stack models (anything `split_layer_params` can split)
    plug in via the `embed_fn` / `block_builder` / `head_fn` adapter hooks;
    the GPT/Llama adapters below are the defaults.
    """

    inner: Any  # the wrapped flax module
    mesh: Mesh
    num_microbatches: int
    schedule: str = "gpipe"
    virtual_stages: int = 1
    embed_fn: Optional[Callable] = None      # (params, idx) -> (B,T,C)
    block_builder: Optional[Callable] = None  # (params, idx, det) -> block_fn
    head_fn: Optional[Callable] = None       # (head_params, h) -> logits
    embed_keys: Optional[Tuple[str, ...]] = None
    head_keys: Optional[Tuple[str, ...]] = None
    # custom PER-MICROBATCH head loss for the 1f1b schedule:
    # (head_params, h (b,T,C), labels (b,T)) -> scalar mean loss.  This is
    # the shape 1f1b can honor (its backward seeds per-microbatch head
    # vjps in-schedule); a whole-batch (params, batch) loss_fn cannot be
    # decomposed that way and stays rejected.
    head_loss_fn: Optional[Callable] = None
    # does block_fn return (h, aux)?  None = derive: MoE configs using the
    # built-in adapters do; custom block_builders must say so explicitly
    # (a silent zero aux would hide a dropped balance loss)
    block_returns_aux: Optional[bool] = None

    def __post_init__(self):
        self.config = self.inner.config
        self._n_layer = getattr(self.config, "n_layer",
                                getattr(self.config, "num_layers", 0))
        handed = getattr(self.config, "handed_on", ())
        if handed:
            # a stage takes x and returns x: a boundary behind the layer
            # that makes them would have to carry these to every later
            # stage, and their cotangents back (ROADMAP, Reach)
            raise ValueError(
                f"{type(self.inner).__name__}'s blocks hand on "
                f"{', '.join(handed)} beside x (models/stack.layers' "
                f"`handed`): a pipeline stage carries x alone")
        if self.head_loss_fn is not None and self.schedule != "1f1b":
            raise ValueError(
                "head_loss_fn only applies to schedule='1f1b' — gpipe/"
                "interleaved train through a whole-batch loss_fn and "
                "would silently ignore it")
        if getattr(self.config, "moe_experts", 0) and \
                self.block_builder is not None and \
                self.block_returns_aux is None:
            # fail HERE, before any (possibly many-GB) param init —
            # guessing either way silently drops or fabricates the
            # router balance loss
            raise ValueError(
                "MoE config with a custom block_builder: set "
                "block_returns_aux=True if the builder's block_fn returns "
                "(h, aux), False if the aux loss is handled elsewhere")
        pp = self.mesh.shape.get("pp", 1)
        if self.schedule == "interleaved":
            self._order = circular_layer_order(self._n_layer, pp,
                                               self.virtual_stages)
        else:
            self._order = list(range(self._n_layer))

    # -- param plumbing

    def init_params(self, rng, **kw):
        return self.from_flat_params(self.inner.init_params(rng, **kw))

    def from_flat_params(self, flat: Dict) -> Dict:
        """The inner model's layout -> pipelined layout (ckpt import)."""
        non_layer, layers, self._prefix = split_layer_params(dict(flat))
        out = dict(non_layer)
        out["blocks"] = stack_layer_params([layers[i] for i in self._order])
        return out

    def to_flat_params(self, params: Dict) -> Dict:
        """Pipelined layout -> the inner model's layout (for export)."""
        out = {k: v for k, v in params.items() if k != "blocks"}
        stacked = unstack_layer_params(params["blocks"], self._n_layer)
        for pos, layer_idx in enumerate(self._order):
            out[f"{getattr(self, '_prefix', 'h')}_{layer_idx}"] = \
                stacked[pos]
        return out

    # -- forward

    def apply(self, variables, idx, deterministic: bool = True,
              mutable: Any = None):
        params = variables["params"]
        x = self._embed(params, idx)
        block_fn = self._block_fn(params, idx, deterministic)
        # MoE + custom builder without block_returns_aux was rejected in
        # __post_init__, so the derive below is unambiguous
        want_aux = (self.block_returns_aux
                    if self.block_returns_aux is not None
                    else bool(getattr(self.config, "moe_experts", 0)))
        res = pipeline_apply(block_fn, params["blocks"], x, self.mesh,
                             self.num_microbatches, schedule=self.schedule,
                             virtual_stages=self.virtual_stages,
                             with_aux=want_aux)
        if want_aux:
            x, aux = res
        else:
            x = res
        logits = self._head(params, x)
        if mutable:
            # surface what the blocks add to the loss the way flax sow
            # would, under the MoE layers' name: make_lm_loss collects it
            inter = ({"intermediates": {"moe_aux_loss": (aux,)}}
                     if want_aux else {})
            return logits, inter
        return logits

    # -- 1F1B training path

    def _embed_head_keys(self, params) -> Tuple[Tuple[str, ...],
                                                Tuple[str, ...]]:
        if self.embed_keys or self.head_keys:
            if not (self.embed_keys and self.head_keys):
                raise ValueError("embed_keys and head_keys must be supplied "
                                 "together for adapter-hook models")
            return self.embed_keys, self.head_keys
        if "wte" in params:   # GPT: tied wte appears in BOTH (grads sum)
            return ("wte", "wpe"), ("ln_f", "wte")
        return ("embed_tokens",), ("norm", "lm_head")

    def value_and_grad(self, params: Dict, batch: Dict
                       ) -> Tuple[jax.Array, Dict]:
        """(loss, grads) via the 1F1B schedule — used by make_train_step in
        place of jax.value_and_grad when schedule == "1f1b".  The head
        loss is `head_loss_fn` when supplied, else token cross-entropy."""
        from ..models.gpt import cross_entropy_loss

        idx, labels = batch["input_ids"], batch["labels"]
        M = self.num_microbatches
        B, T = idx.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by microbatches {M}")
        e_keys, h_keys = self._embed_head_keys(params)
        ep = {k: params[k] for k in e_keys}
        hp = {k: params[k] for k in h_keys}
        x, embed_vjp = jax.vjp(lambda e: self._embed(e, idx), ep)
        xm = x.reshape(M, B // M, T, x.shape[-1])
        lm = labels.reshape(M, B // M, T)
        block_fn = self._block_fn(params, idx, True)

        if self.head_loss_fn is not None:
            head_loss = self.head_loss_fn
        else:
            def head_loss(hparams, h, lbl):
                return cross_entropy_loss(self._head(hparams, h), lbl)

        loss, d_blocks, d_head, d_xm = pipeline_1f1b(
            block_fn, head_loss, params["blocks"], hp, xm, lm, self.mesh)
        (d_embed,) = embed_vjp(d_xm.reshape(B, T, -1).astype(x.dtype))
        grads: Dict = {"blocks": d_blocks}
        for k in e_keys:
            grads[k] = d_embed[k]
        for k in h_keys:
            grads[k] = (jax.tree.map(jnp.add, grads[k], d_head[k])
                        if k in grads else d_head[k])
        return loss, grads

    def __call__(self, *a, **kw):  # pragma: no cover - convenience
        return self.apply(*a, **kw)

    # -- model-family adapters (embed / block / head built from the same
    #    flax modules the inner model uses, so numerics match exactly)

    def _embed(self, params, idx):
        if self.embed_fn is not None:
            return self.embed_fn(params, idx)
        import flax.linen as nn

        cfg = self.config
        T = idx.shape[1]
        if "wte" in params:  # GPT family (models/gpt.py)
            tok = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype).apply(
                {"params": params["wte"]}, idx)
            pos = nn.Embed(cfg.block_size, cfg.n_embd, dtype=cfg.dtype).apply(
                {"params": params["wpe"]}, jnp.arange(T)[None, :])
            return tok + pos
        # Llama family (models/llama.py)
        return nn.Embed(cfg.vocab_size, cfg.hidden_size,
                        dtype=cfg.dtype).apply(
            {"params": params["embed_tokens"]}, idx)

    def _block_fn(self, params, idx, deterministic):
        if self.block_builder is not None:
            return self.block_builder(params, idx, deterministic)
        cfg = self.config
        if getattr(cfg, "moe_experts", 0) and "wte" in params:
            # MoE blocks: capture what the block sowed for the loss (the
            # load-balance aux term) and carry it through the pipeline as
            # an explicit scalar
            from ..models.gpt import Block
            from ..models.sown import collect

            def fn(pl, h):
                h2, upd = Block(cfg).apply(
                    {"params": pl}, h, deterministic,
                    mutable=["intermediates"])
                return h2, collect(upd.get("intermediates", {}), None,
                                   jnp.zeros((), jnp.float32))[0]
        elif "wte" in params:
            from ..models.gpt import Block

            fn = lambda pl, h: Block(cfg).apply(  # noqa: E731
                {"params": pl}, h, deterministic)
        else:
            from ..models.llama import LlamaBlock, rope_freqs

            T = idx.shape[1]
            cos, sin = rope_freqs(cfg.head_dim, T, cfg.rope_theta)
            fn = lambda pl, h: LlamaBlock(cfg).apply(  # noqa: E731
                {"params": pl}, h, cos, sin)
        if getattr(cfg, "remat", False):
            fn = jax.checkpoint(fn, prevent_cse=False)
        return fn

    def _head(self, params, x):
        if self.head_fn is not None:
            return self.head_fn(params, x)
        import flax.linen as nn

        cfg = self.config
        if "wte" in params:
            x = nn.LayerNorm(dtype=cfg.dtype).apply(
                {"params": params["ln_f"]}, x)
            wte = params["wte"]["embedding"]
            return jnp.einsum("bte,ve->btv", x, wte.astype(cfg.dtype))
        from ..models.llama import RMSNorm

        x = RMSNorm(cfg.rms_eps, cfg.dtype).apply(
            {"params": params["norm"]}, x)
        return nn.Dense(cfg.vocab_size, use_bias=False,
                        dtype=cfg.dtype).apply(
            {"params": params["lm_head"]}, x)


class PipelineShardingPlanner:
    """Decorates a ShardingPlanner: `blocks/...` leaves get P("pp", *inner).

    The stacked leading layer axis shards over pp; the remaining dims reuse
    the transformer TP/FSDP rules evaluated against the same path.
    """

    def __init__(self, base):
        self._base = base
        self.mesh = base.mesh
        self.rules = base.rules

    def __getattr__(self, name):
        return getattr(self._base, name)

    def param_specs(self, params: Any) -> Any:
        from .sharding import _add_fsdp, path_of, spec_for_path

        def _spec(key_path, leaf):
            path = path_of(key_path)
            if path.startswith("blocks/"):
                inner = spec_for_path(path, self.rules, ndim=leaf.ndim - 1)
                inner = _add_fsdp(inner, tuple(leaf.shape[1:]), self.mesh,
                                  self._base.fsdp_min_size)
                return P("pp", *tuple(inner) + (None,) * (
                    leaf.ndim - 1 - len(tuple(inner))))
            spec = spec_for_path(path, self.rules, ndim=leaf.ndim)
            return _add_fsdp(spec, tuple(leaf.shape), self.mesh,
                             self._base.fsdp_min_size)

        return jax.tree_util.tree_map_with_path(_spec, params)

    def param_shardings(self, params: Any) -> Any:
        from jax.sharding import NamedSharding

        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.param_specs(params),
                            is_leaf=lambda x: isinstance(x, P))

    def batch_sharding(self, *a, **kw):
        return self._base.batch_sharding(*a, **kw)

    def replicated(self):
        return self._base.replicated()
