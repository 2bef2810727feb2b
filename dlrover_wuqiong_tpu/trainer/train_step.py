"""Sharded training step builder: the hot loop, compiled once under jit.

Parity: reference training hot loop after `auto_accelerate` (SURVEY.md §3.4
tail — FSDP/TP modules with per-layer NCCL collectives).  TPU redesign: one
jit'd step over the global mesh; GSPMD inserts all collectives from the
in/out shardings.  Gradient accumulation (reference ElasticTrainer's fixed
global batch) is a `lax.scan` over microbatches inside the step.

Fused multi-step dispatch (`fused_steps=K`): a second `lax.scan` level
wraps the whole step over K pre-staged batches, so ONE dispatch drives K
optimizer updates and ONE host readback per fusion syncs all K metrics.
The fixed per-dispatch cost then amortizes to <2% of a fusion instead of
dominating small steps —
`auto_fused_steps` picks K from measured step time vs. measured dispatch
overhead, clamped so the trainer's hook cadences (checkpoint/logging/eval)
stay exactly reachable at fusion boundaries.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from ..common.log import get_logger
from ..parallel.sharding import ShardingPlanner, path_of

logger = get_logger("train_step")


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params, optimizer: optax.GradientTransformation):
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=optimizer.init(params))


def leave_untouched(optimizer: optax.GradientTransformation,
                    patterns: Tuple[str, ...]) -> optax.GradientTransformation:
    """`optimizer` with a zero update for every parameter leaf whose WHOLE
    path matches one of `patterns` (regular expressions over
    `layers_1/feed_forward/selection_bias`, as the sharding rules are):
    neither a step nor weight decay reaches it.  For a model's
    `untrained_params`: a variable that rides in the parameter tree,
    because the train state carries no other collection, and is set by a
    rule of its own, or by none.  The optimizer's state keeps its shape,
    so every sharding rule that reads it as parameter-shaped still does."""
    compiled = [re.compile(p) for p in patterns]

    def update(grads, state, params=None):
        updates, state = optimizer.update(grads, state, params)
        updates = jax.tree_util.tree_map_with_path(
            lambda path, u: jnp.zeros_like(u)
            if any(c.fullmatch(path_of(path)) for c in compiled) else u,
            updates)
        return updates, state

    return optax.GradientTransformation(optimizer.init, update)


def _add_steps(params, steps):
    """`params` with `steps` (a tree over some of its leaves) added."""
    if not isinstance(steps, dict):
        return params + steps.astype(params.dtype)
    return {**params, **{k: _add_steps(params[k], v)
                         for k, v in steps.items()}}


@jax.named_scope("accum")  # names the scan in the compiled step
def accumulate_grads(grad_fn, params, batch, accum_steps: int):
    """Mean loss + mean grads over the leading microbatch axis of `batch`.

    `grad_fn(micro) -> (loss, grads)`; f32 accumulators shaped like
    `params`.  Shared by the plain train step and the DiLoCo inner step so
    the accumulation semantics cannot diverge."""
    def body(carry, micro):
        loss_sum, grads_sum = carry
        loss, grads = grad_fn(micro)
        return (loss_sum + loss,
                jax.tree.map(jnp.add, grads_sum, grads)), ()

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero), batch)
    return (loss_sum / accum_steps,
            jax.tree.map(lambda g: g / accum_steps, grads))


def make_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    planner: Optional[ShardingPlanner] = None,
    accum_steps: int = 1,
    donate: bool = True,
    value_and_grad_fn: Optional[Callable] = None,
    opt_host_shardings: Any = None,
    opt_device_shardings: Any = None,
    fused_steps: int = 1,
    state_shardings: Any = None,
):
    """Returns jit'd `step(state, batch) -> (state, metrics)`.

    `state_shardings` (the tree the state was initialised on) pins the
    returned state to the layout it came in with.  Left to itself the
    partitioner returns some replicated leaves sharded (a LayerNorm
    scale updated from reduce-scattered grads stays scattered), so the
    second call sees new input shardings: a second compile, donation
    that cannot alias, and a warm-pool entry — compiled for the init
    layout — that the worker's steady state never hits.

    `batch` leaves have a leading microbatch axis of size `accum_steps` when
    accumulation is on: shape (accum, per_device_batch * data_axes, ...).
    `value_and_grad_fn(params, batch) -> (loss, grads)` overrides the default
    autodiff path (used by the manual 1F1B pipeline schedule).
    `opt_host_shardings`/`opt_device_shardings` (both or neither): the
    optimizer state lives in host memory between steps (optimizer_offload
    strategy) — the step hops it to device for the update and back.

    `fused_steps=K > 1` returns the fused driver `step(state, batches) ->
    (state, metrics)` instead: `lax.scan` of the SAME per-step math over K
    pre-staged batches (leaves carry a leading fused axis of size K) inside
    ONE jit — one dispatch per K optimizer steps instead of K, which
    amortizes the fixed per-dispatch overhead that otherwise caps
    small-step throughput.  Metrics
    accumulate ON DEVICE in the scan outputs: `metrics["losses"]` /
    `metrics["grad_norms"]` are per-step arrays of shape (K,) and
    `metrics["loss"]` / `metrics["grad_norm"]` are the LAST step's values,
    so one host readback per fusion syncs the whole block — no per-step
    `float(...)` sync survives on the hot path.  Donation semantics are
    unchanged: the carried state is donated exactly as in the K=1 case
    (and still rejected under optimizer_offload below).
    """

    def _grads(params, batch):
        """(loss, grads, stats): a loss that also counts (make_lm_loss:
        what the model's layers sowed) hands its counters out beside the
        loss, and they ride in the step's metrics.  `stats["param_steps"]`,
        where the loss gives one, is no counter: a tree shaped like part
        of `params`, added to them after the optimizer's update (a rule
        run out of band on variables the optimizer leaves alone)."""
        if value_and_grad_fn is not None:
            return (*value_and_grad_fn(params, batch), {})
        with_stats = getattr(loss_fn, "with_stats", None)
        if with_stats is None:
            return (*jax.value_and_grad(loss_fn)(params, batch), {})
        (loss, stats), grads = jax.value_and_grad(
            with_stats, has_aux=True)(params, batch)
        return loss, grads, stats

    def train_step(state: TrainState, batch):
        if accum_steps == 1:
            loss, grads, stats = _grads(state.params, batch)
        else:  # counters of the last microbatch alone would mislead: none
            stats = {}
            loss, grads = accumulate_grads(
                lambda micro: _grads(state.params, micro)[:2],
                state.params, batch, accum_steps)
        # outside any flax module: the scope names the clip, the update
        # and the norm in the compiled step (analysis/hlo_scopes.py)
        with jax.named_scope("optimizer"):
            opt_in = state.opt_state
            if opt_host_shardings is not None:
                opt_in = jax.device_put(opt_in, opt_device_shardings)
            updates, opt_state = optimizer.update(grads, opt_in,
                                                  state.params)
            if opt_host_shardings is not None:
                opt_state = jax.device_put(opt_state, opt_host_shardings)
            params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        steps = stats.pop("param_steps", None)
        if steps:
            with jax.named_scope("out_of_band"):
                params = _add_steps(params, steps)
        new_state = TrainState(state.step + 1, params, opt_state)
        return new_state, {"loss": loss, "grad_norm": gnorm, **stats}

    # offloaded opt states: donation would let XLA alias a pinned_host
    # input buffer onto a device-memory output (same shape/dtype) and the
    # runtime rejects the memory-kind mismatch.  Silently disabling the
    # flag hid the conflict from callers; now it is an explicit resolve-
    # time error (graftlint donation-alias — auto_accelerate resolves
    # donate=None to the right value before calling here).
    if donate and opt_host_shardings is not None:
        raise ValueError(
            "graftlint[donation-alias]: donate=True with host-offloaded "
            "optimizer state — XLA would alias a pinned_host input onto a "
            "device-memory output and the runtime rejects the memory-kind "
            "mismatch; pass donate=False (auto_accelerate's donate=None "
            "resolves this automatically)")
    jit_kw: dict = {"donate_argnums": (0,) if donate else ()}
    if state_shardings is not None:
        jit_kw["out_shardings"] = (state_shardings, None)
    if fused_steps <= 1:
        return jax.jit(train_step, **jit_kw)

    def fused_train_step(state: TrainState, batches):
        def body(st, b):
            st, m = train_step(st, b)
            return st, m

        state, stacked = jax.lax.scan(body, state, batches,
                                      length=fused_steps)
        metrics = {
            "loss": stacked["loss"][-1],
            "grad_norm": stacked["grad_norm"][-1],
            "losses": stacked["loss"],
            "grad_norms": stacked["grad_norm"],
            **{k: v[-1] for k, v in stacked.items()
               if k not in ("loss", "grad_norm")},
        }
        return state, metrics

    return jax.jit(fused_train_step, **jit_kw)


def auto_fused_steps(step_time_s: float, overhead_s: Optional[float] = None,
                     target_overhead: float = 0.02, cap: int = 64,
                     cadence: int = 0) -> int:
    """Pick K so the per-dispatch overhead is < `target_overhead` of a
    K-step fusion: K >= overhead / (target * step_time).

    `cap` bounds staging memory (K batches live on device at once) and the
    reaction latency of fusion-boundary hooks.  `cadence` (the gcd of the
    trainer's active step cadences — logging/save/eval/tune) clamps K to
    its largest divisor so checkpoint cadence stays exactly reachable:
    hooks fire only at fusion boundaries, and the preempt-table goodput
    curve (chaos.py) is meaningful only if the chosen ckpt interval is a
    boundary."""
    import math

    if overhead_s is None:
        from ..common.util import measure_dispatch_overhead_s

        overhead_s = measure_dispatch_overhead_s()
    if step_time_s <= 0:
        k = cap
    else:
        k = math.ceil(overhead_s / (target_overhead * step_time_s))
    k = max(1, min(k, cap))
    if cadence > 0:
        k = min(k, cadence)
        while cadence % k:
            k -= 1
    return k


def shard_train_state(state: TrainState, planner: ShardingPlanner
                      ) -> Tuple[TrainState, Any]:
    """Place params/opt-state on the mesh; returns (state, state_shardings).

    Prefer `train_state_shardings` + jit-with-out_shardings init (see
    auto/accelerate.py) for new code: this entry materializes the full
    unsharded tree first, which an 8B-class model cannot afford."""
    state_sh = train_state_shardings(state, planner)
    placed = jax.device_put(state, state_sh)
    return placed, state_sh


def train_state_shardings(state_like: TrainState, planner: ShardingPlanner,
                          offload_opt: bool = False) -> TrainState:
    """Shardings for a TrainState, from a concrete OR abstract
    (jax.eval_shape) instance — never touches leaf values, so the full
    tree need not exist (sharded-by-construction init, parity
    atorch/utils/meta_model_utils.py:759 deferred materialization).

    offload_opt=True places the param-shaped optimizer moments in HOST
    memory (pinned_host memory kind): at 8B-class scale Adam states
    dominate the HBM budget (parity: reference adam_offload.py:87
    PartitionAdam).  XLA streams them device<->host around the update."""
    state = state_like
    param_sh = planner.param_shardings(state.params)
    repl = planner.replicated()
    opt_moment_sh = param_sh
    if offload_opt:
        from jax.sharding import NamedSharding

        opt_moment_sh = jax.tree.map(
            lambda sh: NamedSharding(sh.mesh, sh.spec,
                                     memory_kind="pinned_host"),
            param_sh,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    # optimizer moments (adam mu/nu, etc.) mirror the param pytree: any
    # opt_state subtree whose structure equals the param tree gets the param
    # shardings leaf-for-leaf; everything else (counts, scalars) replicates.
    # Matching by position, not shape — two same-shaped params can carry
    # different PartitionSpecs (e.g. P('fsdp','tp') vs P('tp','fsdp')).
    param_treedef = jax.tree.structure(state.params)
    param_shapes = [getattr(p, "shape", None)
                    for p in jax.tree.leaves(state.params)]

    def _is_param_shaped(sub):
        # structure alone is not enough: adafactor's v_row/v_col subtrees
        # mirror the param treedef with reduced leaf shapes
        try:
            if jax.tree.structure(sub) != param_treedef:
                return False
            return [getattr(x, "shape", None)
                    for x in jax.tree.leaves(sub)] == param_shapes
        except Exception:  # noqa: BLE001
            return False

    opt_sh = jax.tree.map(
        lambda sub: (opt_moment_sh if _is_param_shaped(sub)
                     else jax.tree.map(lambda _: repl, sub)),
        state.opt_state, is_leaf=_is_param_shaped)
    return TrainState(step=repl, params=param_sh, opt_state=opt_sh)


def make_lm_loss(model_apply: Callable) -> Callable:
    """Standard causal-LM loss over a batch dict {input_ids, labels}.

    Where the model sowed an objective of its own (targets and weights:
    `models/sown.objective`), that scalar stands where the cross-entropy
    against `batch["labels"]` stands.  To it are added every term the
    model's layers sowed for the loss, and `loss_fn.with_stats(params, batch) -> (loss, stats)` is
    the same loss with what the layers counted beside it ({} for a model
    that sows nothing): `models/sown.collect` is the one place that is
    asked, and the file that sows a value says there what it is.
    `make_train_step` differentiates `with_stats` and returns the
    counters in the step's metrics."""
    from ..models.gpt import cross_entropy_loss
    from ..models.sown import collect, objective_of

    def with_stats(params, batch):
        logits, updates = model_apply(
            {"params": params}, batch["input_ids"],
            mutable=["intermediates"])
        inter = updates.get("intermediates", {})
        ce = objective_of(inter, batch, logits) if inter else None
        if ce is None:
            ce = cross_entropy_loss(logits, batch["labels"])
        return collect(inter, batch, ce) if inter else (ce, {})

    def loss_fn(params, batch):
        return with_stats(params, batch)[0]

    loss_fn.with_stats = with_stats
    return loss_fn
