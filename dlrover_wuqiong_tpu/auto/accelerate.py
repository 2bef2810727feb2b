"""`auto_accelerate` — one-call training acceleration (strategy → GSPMD).

Parity: reference `atorch/atorch/auto/accelerate.py:406` (`auto_accelerate`,
`model_transform` :34, strategy handling :246-305) and the opt_lib registry
(`auto/opt_lib/optimization_library.py`).

TPU redesign (SURVEY.md §7 design stance): atorch's optimization strategies
(fsdp/zero/tensor_parallel/sequence_parallel/amp/checkpoint/...) collapse into
a *strategy compiler* that emits a mesh plan + PartitionSpecs + kernel flags.
`auto_accelerate` analyses the model, resolves the strategy (given or auto),
builds the mesh/planner, shards the train state, and returns a compiled train
step — the moral equivalent of (model, optim, dataloader) transforms, without
module wrapping.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from ..common.log import get_logger
from ..parallel.mesh import (
    MeshPlan,
    auto_plan,
    build_mesh,
    detect_hbm_per_device,
)
from ..analysis.jaxpr_engine import (
    assert_no_host_out_shardings,
    resolve_donation,
)
from .compile_cache import (
    enable_persistent_cache,
    note_train_step_served,
    train_step_cache_key,
)
from ..telemetry import spans as tspans
from ..parallel.sharding import ShardingPlanner, activation_spec
from ..trainer.train_step import (
    TrainState,
    leave_untouched,
    make_lm_loss,
    make_train_step,
    train_state_shardings,
)

logger = get_logger("accelerate")

# strategy registry: name -> handler(plan, kwargs, context)
_STRATEGY_REGISTRY: Dict[str, Callable] = {}


def register_strategy(name: str):
    def deco(fn):
        _STRATEGY_REGISTRY[name] = fn
        return fn
    return deco


@dataclasses.dataclass
class StrategyContext:
    plan: MeshPlan
    accum_steps: int = 1
    # tri-state: None = keep the model's own config; True/False = override
    amp: Optional[bool] = None  # bf16 compute
    remat: Optional[bool] = None
    flash_attention: Optional[bool] = None
    extra: Dict = dataclasses.field(default_factory=dict)

    def model_overrides(self, model) -> Dict[str, Any]:
        """Map the flags onto the model config's field names (only fields the
        config actually has — foreign models pass through untouched)."""
        fields = _config_fields(model)
        if not fields:
            return {}
        cfg = model.config
        out: Dict[str, Any] = {}
        if self.amp is not None and "dtype" in fields:
            out["dtype"] = jnp.bfloat16 if self.amp else jnp.float32
        if self.remat is not None and "remat" in fields:
            out["remat"] = self.remat
        if self.extra.get("remat_policy") and "remat_policy" in fields:
            out["remat_policy"] = self.extra["remat_policy"]
        if self.extra.get("remat_names") and "remat_names" in fields:
            out["remat_names"] = self.extra["remat_names"]
        if self.flash_attention is not None and \
                "use_flash_attention" in fields:
            out["use_flash_attention"] = self.flash_attention
        if self.extra.get("fp8") and "fp8" in fields:
            out["fp8"] = True
            if self.extra.get("fp8_filter") and "fp8_filter" in fields:
                out["fp8_filter"] = self.extra["fp8_filter"]
        return {k: v for k, v in out.items() if getattr(cfg, k) != v}


@register_strategy("fsdp")
@register_strategy("zero2")
@register_strategy("zero3")
def _s_fsdp(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """Parameters, gradients and optimizer state sharded over "fsdp"
    (parallel/sharding._add_fsdp); the batch over ("dp", "fsdp").  The
    residual stream is guaranteed to stay in that batch layout
    (parallel/sharding.activation_spec, stated by the model at each block's
    entry), so a dense layer gathers its kernel and multiplies the chip's own
    tokens: no activation crosses chips inside a block."""
    ctx.plan.fsdp = cfg.get("size", 0) or 0  # 0 → fill remaining


@register_strategy("data_parallel")
@register_strategy("ddp")
def _s_dp(ctx: StrategyContext, cfg: Dict, num_devices: int):
    ctx.plan.dp = cfg.get("size", 0) or 0


@register_strategy("tensor_parallel")
def _s_tp(ctx: StrategyContext, cfg: Dict, num_devices: int):
    ctx.plan.tp = cfg.get("size", 1)


@register_strategy("sequence_parallel")
def _s_sp(ctx: StrategyContext, cfg: Dict, num_devices: int):
    ctx.plan.sp = cfg.get("size", 1)
    # "ulysses" (all-to-all head scatter) | "ring" (ppermute KV rotation,
    # O(S/sp) memory — long context) | "gspmd" (let XLA all-gather KV)
    ctx.extra["sp_impl"] = cfg.get("impl", "ulysses")


@register_strategy("expert_parallel")
def _s_ep(ctx: StrategyContext, cfg: Dict, num_devices: int):
    ctx.plan.ep = cfg.get("size", 1)


@register_strategy("multi_slice")
def _s_multi_slice(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """Multi-slice (DCN-connected) topology: dp spans the slices, fsdp/tp/
    sp stay INSIDE a slice so the heavy per-layer collectives ride ICI and
    only the dp grad all-reduce crosses DCN (SURVEY §2.5 TPU row; parity:
    reference node groups, dist_job_manager.py:88).  `dp` is the
    OUTERMOST mesh axis, so each slice's devices form one contiguous dp
    group — pass `devices` ordered slice-major (slice 0's chips first).
    cfg: slices (required), devices_per_slice (default: evenly divided),
    tp, sp."""
    from ..parallel.mesh import hybrid_slice_plan

    slices = int(cfg.get("slices", 2))
    if slices < 2:
        raise ValueError("multi_slice needs slices >= 2")
    per = int(cfg.get("devices_per_slice") or num_devices // slices)
    if slices * per != num_devices:
        raise ValueError(
            f"multi_slice: {slices} slices x {per} devices/slice != "
            f"{num_devices} devices")
    tp, sp = int(cfg.get("tp", 1)), int(cfg.get("sp", 1))
    if per % (tp * sp):
        raise ValueError(
            f"multi_slice: tp={tp} x sp={sp} must divide the "
            f"{per} devices of a slice (fsdp fills the quotient)")
    ctx.plan = hybrid_slice_plan(slices, per, tp=tp, sp=sp)


@register_strategy("pipeline_parallel")
def _s_pp(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """cfg: size, microbatches, schedule ("gpipe" | "interleaved" | "1f1b"),
    virtual_stages (interleaved chunk count per device), head_loss (1f1b
    only: per-microbatch (head_params, h, labels) -> scalar loss)."""
    ctx.plan.pp = cfg.get("size", 1)
    ctx.extra["pp_microbatches"] = cfg.get("microbatches")
    schedule = cfg.get("schedule", "gpipe")
    if schedule not in ("gpipe", "interleaved", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} — expected "
                         "'gpipe', 'interleaved' or '1f1b'")
    virtual = cfg.get("virtual_stages", 2 if schedule == "interleaved" else 1)
    if schedule == "interleaved" and virtual < 2:
        raise ValueError("interleaved schedule needs virtual_stages >= 2 — "
                         "with 1 chunk per device it degenerates to gpipe")
    if schedule != "interleaved" and virtual > 1:
        raise ValueError(f"virtual_stages={virtual} only applies to "
                         "schedule='interleaved'")
    ctx.extra["pp_schedule"] = schedule
    ctx.extra["pp_virtual_stages"] = virtual
    if cfg.get("head_loss") is not None:
        if schedule != "1f1b":
            raise ValueError(
                "head_loss only applies to schedule='1f1b' (gpipe/"
                "interleaved take a whole-batch loss_fn instead)")
        if ctx.plan.pp <= 1:
            raise ValueError(
                "head_loss needs ('pipeline_parallel', {'size': >= 2, "
                "...}) — with pp=1 no pipeline is built and the custom "
                "objective would silently fall back to cross-entropy")
        ctx.extra["pp_head_loss"] = cfg["head_loss"]


@register_strategy("local_sgd")
@register_strategy("hsdp")
def _s_local_sgd(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """DiLoCo two-level training over the dp axis (parallel/local_sgd.py).
    cfg: sync_every/outer_lr/outer_momentum/nesterov/reduce."""
    ctx.extra["local_sgd"] = dict(cfg)


@register_strategy("amp")
@register_strategy("amp_native")
@register_strategy("half")
def _s_amp(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """bf16 compute; with {"fp8": True} additionally routes the name-filtered
    projections through Fp8Dense (parity: reference Fp8Optimization module
    filter, amp_optimization.py:197-260)."""
    ctx.amp = cfg.get("enabled", True)
    if cfg.get("fp8"):
        ctx.extra["fp8"] = True
        if cfg.get("filter"):
            ctx.extra["fp8_filter"] = tuple(cfg["filter"])


@register_strategy("checkpoint")
def _s_ckpt(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """cfg: enabled, policy ("full" | "dots" | "offload_dots" |
    "save_names" | "offload_names"), names (checkpoint_name anchors for
    the *_names policies).  Parity: reference selective_offloading_
    checkpoint.py + activation_checkpointing.py; policies resolved in
    ops/remat.py."""
    ctx.remat = cfg.get("enabled", True)
    if cfg.get("policy") is not None:
        from ..ops.remat import resolve_remat_policy

        resolve_remat_policy(cfg["policy"])  # fail fast on a bad name
        ctx.extra["remat_policy"] = cfg["policy"]
    if cfg.get("names"):
        ctx.extra["remat_names"] = tuple(cfg["names"])


@register_strategy("module_replace")
def _s_module_replace(ctx: StrategyContext, cfg: Dict, num_devices: int):
    ctx.flash_attention = cfg.get("enabled", True)


@register_strategy("stable_bf16")
@register_strategy("bf16_optimizer")
def _s_stable_bf16(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """bf16 params trained stably — Kahan compensation (default) or f32
    master weights ({"master": True}).  Parity: reference
    bf16_optimizer.py:46; impl optimizers/bf16_stable.py."""
    ctx.extra["stable_bf16"] = {"master": bool(cfg.get("master", False))}


@register_strategy("optimizer_offload")
def _s_opt_offload(ctx: StrategyContext, cfg: Dict, num_devices: int):
    """Optimizer moments in host memory (pinned_host) — parity: reference
    adam_offload.py:87 PartitionAdam host-offloaded states."""
    ctx.extra["optimizer_offload"] = cfg.get("enabled", True)


@register_strategy("grad_accum")
def _s_accum(ctx: StrategyContext, cfg: Dict, num_devices: int):
    ctx.accum_steps = cfg.get("steps", 1)


def resolve_strategy(strategy: Optional[Sequence], num_devices: int,
                     num_params: Optional[int] = None,
                     seq_len: int = 0,
                     hbm_per_device: Optional[int] = None) -> StrategyContext:
    """Given-strategy path (parity get_strategy :246 + adjust_strategy :305)
    or auto path (parity the engine search — heuristic here)."""
    ctx = StrategyContext(plan=MeshPlan())
    if not strategy:
        ctx.plan = auto_plan(
            num_devices, num_params, seq_len=seq_len,
            hbm_per_device=hbm_per_device or detect_hbm_per_device())
        return ctx
    for item in strategy:
        name, cfg = item if isinstance(item, (tuple, list)) else (item, {})
        handler = _STRATEGY_REGISTRY.get(name)
        if handler is None:
            raise ValueError(f"unknown optimization strategy: {name!r}; "
                             f"known: {sorted(_STRATEGY_REGISTRY)}")
        handler(ctx, cfg or {}, num_devices)
    # fill the unset data dim with remaining devices (domination rule)
    fixed = (ctx.plan.tp * ctx.plan.sp * ctx.plan.pp * ctx.plan.ep)
    remaining = num_devices // fixed
    if ctx.plan.fsdp == 0 and ctx.plan.dp == 0:
        ctx.plan.fsdp, ctx.plan.dp = remaining, 1
    elif ctx.plan.fsdp == 0:
        ctx.plan.fsdp = max(1, remaining // max(1, ctx.plan.dp))
    elif ctx.plan.dp == 0:
        ctx.plan.dp = max(1, remaining // max(1, ctx.plan.fsdp))
    ctx.plan.validate(num_devices)
    return ctx


@dataclasses.dataclass
class AccelerateResult:
    """Parity: reference AutoAccelerateResult (model/optim/dataloader/...)."""

    train_step: Callable
    state: TrainState
    state_shardings: Any
    mesh: Any
    planner: ShardingPlanner
    strategy: StrategyContext
    loss_fn: Callable
    batch_sharding_fn: Callable  # (ndim, seq_axis) -> NamedSharding
    model: Any = None  # the (possibly strategy-rebuilt) model
    # warm-restart bookkeeping (auto/compile_cache.py): the framework key
    # this build registered, whether a prior process already compiled the
    # same topology (→ the XLA disk cache will serve the step), and the
    # JSON-able strategy the warm pool can replay (None when the strategy
    # carries non-serializable payloads, e.g. a head_loss callable)
    cache_key: str = ""
    cache_warm: bool = False
    strategy_spec: Optional[list] = None
    # fused multi-step dispatch (trainer/train_step.py): K the main
    # `train_step` was built with, plus the lazy factory behind
    # `fused_train_step(k)` — the trainer auto-tunes K from MEASURED step
    # time, which only exists after the K=1 step is live, so fused
    # variants compile on demand, each registering its own cache key
    fused_steps: int = 1
    _fused_factory: Any = None   # k -> jitted fused step (None: local_sgd)
    _fused_key_fn: Any = None    # k -> framework cache key
    _fused_cache: Dict[int, Callable] = dataclasses.field(
        default_factory=dict)
    _cache_dir: Optional[str] = None

    def fused_train_step(self, fused_steps: int) -> Callable:
        """The K-step fused driver `step(state, batches)` for this build.

        `batches` leaves carry a leading fused axis of size K (stack K
        per-step batches with `data.elastic_dataset.stack_batches`, place
        with `place_fused_batch`).  Built lazily and cached per K: each K
        is a distinct compile (K changes the HLO — auto/compile_cache.py);
        K = 1 IS `train_step`."""
        k = int(fused_steps)
        if k <= 1:
            return self.train_step
        if self._fused_factory is None:
            raise ValueError(
                "fused_steps > 1 does not compose with local_sgd — the "
                "DiLoCo step's outer sync counts dispatches, and a K-step "
                "fusion would scan across sync boundaries; run unfused "
                "(fused_steps=1)")
        fn = self._fused_cache.get(k)
        if fn is None:
            fn = self._fused_factory(k)
            self._fused_cache[k] = fn
            if self._fused_key_fn is not None:
                key = self._fused_key_fn(k)
                note_train_step_served(
                    self._cache_dir, key,
                    meta={"mesh": self.strategy.plan.describe(),
                          "fused_steps": k})
        return fn

    def place_fused_batch(self, batch):
        """Shard a fused K-step host batch onto the mesh data axes.

        Leaves carry a leading fused-step axis (and the microbatch scan
        axis after it when accum_steps > 1) before the global batch dim;
        both scan axes replicate, the batch dim shards as usual."""
        batch_axis = 1 + (1 if self.strategy.accum_steps > 1 else 0)
        return self.place_batch(batch, batch_axis=batch_axis)

    def place_batch(self, batch, seq_axis: Optional[int] = None,
                    batch_axis: int = 0):
        """Shard a host batch pytree onto the mesh data axes.

        With grad accumulation the leading axis is the microbatch scan axis
        (replicated); pass batch_axis=1 (done automatically when the strategy
        has accum_steps > 1 and batch_axis is untouched).
        """
        if batch_axis == 0 and self.strategy.accum_steps > 1:
            batch_axis = 1
        if seq_axis is None:
            seq_axis = batch_axis + 1

        def _put(x):
            if x.ndim > batch_axis:
                sh = self.batch_sharding_fn(
                    x.ndim, seq_axis if x.ndim > seq_axis else None,
                    batch_axis)
            else:
                sh = self.planner.replicated()
            return jax.device_put(x, sh)

        return jax.tree.map(_put, batch)


def _warn_slow_offload_link(ctx, devices, num_params) -> None:
    """Resolve-time H2D probe for host-offload strategies.

    optimizer_offload and the offload_* remat policies stream state or
    activations across the host link every step.  On a slow host link
    they silently multiply step time instead of saving memory for free —
    so measure once, log the rate, and warn with the estimated per-step
    cost when the traffic cannot be hidden.  DWT_H2D_GBPS pins/overrides
    the probe."""
    offload_opt = bool(ctx.extra.get("optimizer_offload"))
    offload_acts = str(ctx.extra.get("remat_policy", "")).startswith(
        "offload")
    if not (offload_opt or offload_acts):
        return
    try:
        from ..common.util import measure_h2d_gbps

        gbps = measure_h2d_gbps(devices[0])
    except Exception:  # noqa: BLE001 — a failed probe must not break
        logger.debug("h2d probe failed", exc_info=True)
        return
    what = " + ".join(filter(None, [
        "optimizer_offload" if offload_opt else "",
        f"remat {ctx.extra.get('remat_policy')}" if offload_acts else ""]))
    est = None
    if offload_opt and num_params:
        # adam moments f32 both ways, sharded over the state axes
        shards = max(1, ctx.plan.tp * ctx.plan.fsdp)
        est = 2 * 8 * num_params / shards / (gbps * 1e9)
    if gbps < 1.0 or (est is not None and est > 1.0):
        logger.warning(
            "%s selected on a slow host link: measured H2D %.3f GB/s%s — "
            "expect the offload traffic to DOMINATE step time (the same "
            "link measured offload_dots at 3.4x step time).  Set "
            "DWT_H2D_GBPS to override the probe.", what, gbps,
            f", est. {est:.1f}s/step moment traffic per device"
            if est is not None else "")
    else:
        logger.info("%s: measured H2D %.1f GB/s%s", what, gbps,
                    f", est. {est * 1e3:.0f}ms/step moment traffic"
                    if est is not None else "")


def _config_fields(model) -> set:
    """The names of the model config's fields; none for a foreign model."""
    cfg = getattr(model, "config", None)
    return {f.name for f in dataclasses.fields(cfg)} \
        if dataclasses.is_dataclass(cfg) else set()


def _with_config(model, **changes):
    """The model rebuilt on its config with `changes` applied."""
    new_cfg = dataclasses.replace(model.config, **changes)
    return model.clone(config=new_cfg) if hasattr(model, "clone") \
        else type(model)(new_cfg)


def auto_accelerate(
    model,  # flax module with .apply / .init_params
    optimizer: Optional[optax.GradientTransformation] = None,
    sample_batch: Optional[Dict] = None,
    strategy: Optional[Sequence] = None,
    devices: Optional[Sequence] = None,
    loss_fn: Optional[Callable] = None,
    accum_steps: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    num_params_hint: Optional[int] = None,
    seq_len: int = 0,
    materialize: bool = True,
    donate: Optional[bool] = None,
    fused_steps: int = 1,
) -> AccelerateResult:
    """Analyse → resolve strategy → build mesh → shard state → compile step.

    `donate=None` (default) resolves automatically: the train step donates
    its input state unless the strategy forbids it (optimizer_offload
    would alias a pinned_host input onto a device output — CLAUDE.md).
    An explicit `donate=True` that conflicts with the resolved strategy
    raises `ValueError` here, before any parameter init (graftlint
    donation-alias check, analysis/jaxpr_engine.py).

    `materialize=False` returns ABSTRACT state: every leaf a
    ShapeDtypeStruct carrying its NamedSharding, nothing allocated.  The
    caller can AOT-lower the train step (`result.train_step.lower(
    result.state, abstract_batch).compile()`) and read
    `memory_analysis()` — the scale-proof path (8B+ fit checks without an
    8B machine; parity: reference meta_model_utils.py:1-759 meta-device
    init for 65B-class models).

    `fused_steps=K > 1` builds `result.train_step` as the fused K-step
    driver (trainer/train_step.py): `step(state, batches)` with a leading
    fused axis of size K on every batch leaf — one dispatch per K
    optimizer steps.  Any K (the auto-tuned one included) is also
    available lazily via `result.fused_train_step(k)` without rebuilding.
    """
    # two spans, because a seeded or resumed run throws the init away
    # and keeps the plan: `accelerate:plan`, then `accelerate:init_state`
    # leaves the model keeps in its parameter tree and the optimizer must
    # leave alone; read before a strategy wraps the model
    untrained = tuple(getattr(model, "untrained_params", ()))
    with tspans.span("accelerate:plan"):
        devices = list(devices if devices is not None else _all_devices())
        # Level-1 warm restarts: every build compiles through the persistent
        # XLA cache, so a restart on the same topology deserializes from disk
        # instead of recompiling (idempotent; DWT_COMPILE_CACHE=0 disables)
        cache_dir = enable_persistent_cache()
        num_params = num_params_hint
        if num_params is None and hasattr(model, "config") and \
                hasattr(model.config, "num_params"):
            num_params = model.config.num_params()
        ctx = resolve_strategy(strategy, len(devices), num_params, seq_len,
                               hbm_per_device=detect_hbm_per_device(devices))
        if accum_steps:
            ctx.accum_steps = accum_steps
        # resolve-time lint gate: an impossible donation request fails HERE,
        # before model init burns work on a doomed config (strategy-matrix
        # convention; graftlint donation-alias)
        donate = resolve_donation(ctx.extra, donate)
        if fused_steps > 1 and ctx.extra.get("local_sgd") is not None:
            # strategy-matrix convention: incompatibilities error at resolve
            # time, before any parameter init
            raise ValueError(
                "fused_steps > 1 does not compose with local_sgd — the "
                "DiLoCo step's outer sync counts dispatches, and a K-step "
                "fusion would scan across sync boundaries; run unfused "
                "(fused_steps=1)")
        overrides = ctx.model_overrides(model)
        if overrides:
            # rebuild the model with the strategy's amp/remat/flash flags
            model = _with_config(model, **overrides)
            logger.info("strategy overrides model config: %s",
                        {k: getattr(v, "__name__", v)
                         for k, v in overrides.items()})
        _warn_slow_offload_link(ctx, devices, num_params)
        mesh = build_mesh(ctx.plan, devices)
        planner = ShardingPlanner(mesh)
        if ctx.plan.ep > 1:
            planner.with_moe()
        sp_impl = ctx.extra.get("sp_impl", "ulysses")
        # as `model_overrides`: only fields the config actually has
        cfg_fields = _config_fields(model)
        if mesh.size > 1 and "mesh" in cfg_fields:
            # every multi-device plan hands the model its mesh: the Pallas
            # kernels (attention, the Mamba-2 scan, the grouped products)
            # cannot be partitioned by GSPMD, and each dispatch reads the
            # mesh to run under a shard_map over it or take its plain
            # form (models/attention.py, mamba2.py, ops/grouped_matmul.py)
            model = _with_config(model, mesh=mesh)
        if ctx.plan.sp > 1 and sp_impl != "gspmd" and \
                "attn_impl" in cfg_fields:
            # context-parallel attention: ring (ppermute) or Ulysses
            # (all-to-all)
            heads = getattr(model.config, "n_head",
                            getattr(model.config, "num_heads", None))
            if sp_impl == "ulysses" and heads and heads % ctx.plan.sp:
                raise ValueError(
                    f"ulysses sequence parallel needs heads ({heads}) "
                    f"divisible by sp={ctx.plan.sp}; use impl='ring' or "
                    f"adjust sp")
            model = _with_config(model, attn_impl=sp_impl)
            logger.info("sequence parallel: %s attention over sp=%d", sp_impl,
                        ctx.plan.sp)

        # the trace-defining model config, captured before pipeline wrapping
        # hides it (PipelinedLM's stage slicing is keyed via ctx.extra)
        cfg_for_key = getattr(model, "config", None)

        if ctx.plan.pp > 1:
            # stage-sliced GPipe pipeline over the pp axis
            # (parallel/pipeline.py)
            from ..parallel.pipeline import (
                PipelinedLM,
                PipelineShardingPlanner,
            )

            # pp x ring/ulysses SP composes: the attention's inner shard_map
            # nests inside the pipeline's manual-pp body via the context
            # AbstractMesh with VMA tracking (parallel/long_context.py
            # _context_mesh) — the long-context 70B configuration's layout
            # (MoE composes with every schedule incl. 1f1b — the manual
            # backward seeds the router aux cotangent, parallel/pipeline.py)
            n_layer = getattr(model.config, "n_layer",
                              getattr(model.config, "num_layers", None))
            if n_layer is None or n_layer % ctx.plan.pp:
                raise ValueError(
                    f"pipeline_parallel needs layers ({n_layer}) divisible by "
                    f"pp={ctx.plan.pp}")
            from ..parallel.pipeline import default_pp_microbatches

            microbatches = ctx.extra.get("pp_microbatches") or \
                default_pp_microbatches(ctx.accum_steps, ctx.plan.pp)
            pp_schedule = ctx.extra.get("pp_schedule", "gpipe")
            pp_virtual = ctx.extra.get("pp_virtual_stages", 1)
            if pp_schedule == "1f1b" and loss_fn is not None:
                raise ValueError(
                    "pipeline schedule '1f1b' cannot honor a whole-batch "
                    "(params, batch) loss_fn — its backward seeds PER-"
                    "MICROBATCH head vjps in-schedule.  Pass a per-microbatch "
                    "head loss instead: ('pipeline_parallel', {'head_loss': "
                    "fn(head_params, h, labels) -> scalar}), or use "
                    "schedule='gpipe'/'interleaved'")
            if ctx.extra.get("local_sgd") is not None:
                # reject HERE, before PipelinedLM wrapping and the (possibly
                # many-GB) init_params below burn work on a doomed config
                raise ValueError(
                    "local_sgd does not compose with pipeline_parallel — the "
                    "pipeline's PARTIALLY-manual shard_map ({pp} with other "
                    "axes GSPMD) cannot nest under the DiLoCo dp-manual body: "
                    "the partitioner rejects re-binding the parent's dp axis "
                    "(ring/ulysses SP nests fine because it goes FULLY manual "
                    "inside)")
            model = PipelinedLM(model, mesh, microbatches,
                                schedule=pp_schedule,
                                virtual_stages=pp_virtual,
                                head_loss_fn=ctx.extra.get("pp_head_loss"))
            planner = PipelineShardingPlanner(planner)
            logger.info("pipeline parallel: %d stages x %d layers, %d "
                        "microbatches, schedule=%s%s", ctx.plan.pp,
                        n_layer // ctx.plan.pp, microbatches, pp_schedule,
                        f" v={pp_virtual}" if pp_virtual > 1 else "")

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    optimizer = optimizer or optax.adamw(3e-4)
    if untrained:
        optimizer = leave_untouched(optimizer, untrained)
    stable_bf16_cfg = ctx.extra.get("stable_bf16")
    if stable_bf16_cfg is not None:
        from ..optimizers.bf16_stable import stable_bf16

        optimizer = stable_bf16(optimizer,
                                master=stable_bf16_cfg["master"])
    loss = loss_fn or make_lm_loss(model.apply)

    if ctx.extra.get("local_sgd") is not None:
        if not materialize:
            raise ValueError("materialize=False (AOT scale-proof) does not "
                             "support local_sgd — its state builder derives "
                             "trees from materialized params")
        # params sharded by construction (same mechanism as below); the
        # DiLoCo state builder then derives its outer/inner trees from them
        def _init_params(r):
            params = model.init_params(r)
            if ctx.extra.get("stable_bf16") is not None:
                # bf16 params x DiLoCo: the inner optimizer is already
                # stable_bf16-wrapped; the outer sync re-anchors its
                # comp state (reset hook below)
                params = jax.tree.map(
                    lambda p: p.astype(jnp.bfloat16)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    params)
            return params

        p_abs = jax.eval_shape(_init_params, rng)
        p_sh = planner.param_shardings(p_abs)
        assert_no_host_out_shardings(p_sh, where="local_sgd param init")
        with tspans.span("accelerate:init_state"):
            # as in the other two branches: the span waits for the device
            params = jax.block_until_ready(
                jax.jit(_init_params, out_shardings=p_sh)(rng))
        # DiLoCo two-level training (parallel/local_sgd.py): the dp axis
        # becomes the replica-group axis that only syncs every H steps
        from ..parallel.local_sgd import (
            LocalSGDConfig,
            init_diloco_state,
            make_diloco_train_step,
        )

        ls_cfg = LocalSGDConfig(**ctx.extra["local_sgd"])
        if ctx.plan.dp < 2:
            raise ValueError(
                "local_sgd needs ('data_parallel', {'size': R>=2}) — the "
                "dp axis carries the locally-training replica groups")
        # (local_sgd x pipeline is rejected earlier, in the pp branch,
        # before any parameter initialization)
        offload_opt = bool(ctx.extra.get("optimizer_offload"))
        state = init_diloco_state(params, optimizer, mesh, planner, ls_cfg,
                                  offload_opt=offload_opt)
        reset_hook = None
        if stable_bf16_cfg is not None:
            from ..optimizers.bf16_stable import reset_compensation

            def reset_hook(o, p, _m=stable_bf16_cfg["master"]):
                return reset_compensation(o, p, master=_m)
        opt_host_sh = opt_dev_sh = None
        if offload_opt:
            opt_host_sh = jax.tree.map(lambda x: x.sharding,
                                       state.inner_opt_state)
            from jax.sharding import NamedSharding as _NS

            opt_dev_sh = jax.tree.map(
                lambda sh: _NS(sh.mesh, sh.spec), opt_host_sh,
                is_leaf=lambda x: isinstance(x, _NS))
        step = make_diloco_train_step(loss, optimizer, mesh, planner,
                                      ls_cfg, accum_steps=ctx.accum_steps,
                                      reset_opt_on_sync=reset_hook,
                                      opt_host_shardings=opt_host_sh,
                                      opt_device_shardings=opt_dev_sh)
        state_sh = jax.tree.map(lambda x: x.sharding, state)
        _step_factory = None  # DiLoCo: no fused driver (sync cadence)
        logger.info("local_sgd (DiLoCo): dp=%d groups, sync every %d steps,"
                    " reduce=%s%s%s", ctx.plan.dp, ls_cfg.sync_every,
                    ls_cfg.reduce,
                    ", stable_bf16" if stable_bf16_cfg is not None else "",
                    ", optimizer_offload" if offload_opt else "")
    else:
        # Sharded-by-construction init (parity: reference meta-device init
        # + deferred materialization, atorch/utils/meta_model_utils.py:759
        # and fsdp_init_util.py:502): eval_shape infers the full train-state
        # tree WITHOUT allocating it, the planner maps shardings onto the
        # abstract tree, and jit-with-out_shardings materializes each
        # parameter/optimizer shard directly on its owner device.  No
        # process ever holds the unsharded 8B tree the old eager
        # `model.init_params(rng)` + device_put path required.
        def _create_state(r):
            params = model.init_params(r)
            if stable_bf16_cfg is not None:
                # bf16 PARAMS (not just compute dtype): halves param HBM
                # and FSDP all-gather bytes; stable_bf16 keeps updates
                # from vanishing below the bf16 ulp
                params = jax.tree.map(
                    lambda p: p.astype(jnp.bfloat16)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    params)
            return TrainState.create(params, optimizer)

        abstract = jax.eval_shape(_create_state, rng)
        offload_opt = bool(ctx.extra.get("optimizer_offload"))
        state_sh = train_state_shardings(abstract, planner,
                                         offload_opt=offload_opt)
        dev_sh = (train_state_shardings(abstract, planner) if offload_opt
                  else None)
        if not materialize:
            state = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                abstract, state_sh)
        elif offload_opt:
            # jit-init cannot emit host-memory outputs under SPMD (the
            # device-placement annotation defeats the partitioner), so
            # init lands on device shardings and the moments hop to
            # pinned_host right after — a one-time transfer at init.
            # graftlint enforces the invariant: the tree handed to jit
            # must be device-kind (host-kind-out-shardings check).
            assert_no_host_out_shardings(dev_sh, where="offload state init")
            with tspans.span("accelerate:init_state"):
                state = jax.jit(_create_state, out_shardings=dev_sh)(rng)
                state = jax.block_until_ready(
                    jax.device_put(state, state_sh))
        else:
            assert_no_host_out_shardings(state_sh, where="state init")
            with tspans.span("accelerate:init_state"):
                # dispatch is asynchronous: the span ends when the state
                # is there, or the init's time lands on whoever waits
                # next — in every branch that opens this span
                state = jax.block_until_ready(
                    jax.jit(_create_state, out_shardings=state_sh)(rng))
        vg_fn = None
        if ctx.plan.pp > 1 and ctx.extra.get("pp_schedule") == "1f1b":
            # manual fwd/bwd interleave replaces autodiff-through-apply
            vg_fn = model.value_and_grad
        def _step_factory(k: int):
            return make_train_step(
                loss, optimizer, mesh, planner,
                accum_steps=ctx.accum_steps,
                donate=donate,
                value_and_grad_fn=vg_fn,
                opt_host_shardings=(state_sh.opt_state if offload_opt
                                    else None),
                opt_device_shardings=(dev_sh.opt_state if offload_opt
                                      else None),
                fused_steps=k,
                # host-kind out_shardings trip the SPMD partitioner
                # (CLAUDE.md): the offload path keeps its in-step hops
                state_shardings=None if offload_opt else state_sh)
        step = _step_factory(fused_steps)
    # framework cache key: everything the trace depends on — mesh shape,
    # the RESOLVED strategy context (not the caller's spelling of it),
    # the final post-override model config, donation, the fused-step
    # count, and the trace-time env toggles folded in by
    # train_step_cache_key itself
    def _key_for(k: int) -> str:
        return train_step_cache_key(
            ctx.plan.sizes(),
            {"extra": ctx.extra, "amp": ctx.amp, "remat": ctx.remat,
             "flash_attention": ctx.flash_attention},
            cfg_for_key,
            donate=donate,
            accum_steps=ctx.accum_steps,
            fused_steps=k)

    cache_key = _key_for(fused_steps)
    # the activation layout the model states in its trace
    # (parallel/sharding.activation_spec); None: one device, nothing stated
    act = str(activation_spec(getattr(cfg_for_key, "mesh", None)))
    cache_warm = note_train_step_served(
        cache_dir, cache_key,
        meta={"mesh": ctx.plan.describe(), "n_devices": len(devices),
              "act": act, "fused_steps": fused_steps})
    strategy_spec = _jsonable_strategy(strategy, ctx)
    if sample_batch is not None and strategy_spec is not None and \
            cache_dir is not None:
        # let the agent derive degraded-mesh warm specs without knowing
        # the model (auto/warm_pool.py; explicit publishing for callers
        # without a sample_batch: ElasticContext.enable_warm_restarts)
        _publish_warm_spec(cache_dir, model, strategy_spec, devices,
                           sample_batch, ctx.accum_steps, fused_steps)
    logger.info("auto_accelerate: mesh=%s act=%s params=%s accum=%d "
                "cache_key=%s%s", ctx.plan.describe(), act,
                f"{num_params:,}" if num_params else "?", ctx.accum_steps,
                cache_key, " (warm)" if cache_warm else "")
    return AccelerateResult(
        train_step=step, state=state, state_shardings=state_sh, mesh=mesh,
        planner=planner, strategy=ctx, loss_fn=loss,
        batch_sharding_fn=planner.batch_sharding, model=model,
        cache_key=cache_key, cache_warm=cache_warm,
        strategy_spec=strategy_spec,
        fused_steps=fused_steps, _fused_factory=_step_factory,
        _fused_key_fn=_key_for, _cache_dir=cache_dir)


def _jsonable_strategy(strategy: Optional[Sequence],
                       ctx: StrategyContext) -> Optional[list]:
    """The strategy in warm-spec (JSON) form; for the auto path the
    resolved plan is spelled back as explicit axis strategies so a warm
    child reproduces the exact mesh without re-running auto_plan."""
    import json as _json

    if not strategy:
        plan = ctx.plan
        out = []
        if plan.tp > 1:
            out.append(["tensor_parallel", {"size": plan.tp}])
        if plan.sp > 1:
            out.append(["sequence_parallel", {"size": plan.sp}])
        if plan.ep > 1:
            out.append(["expert_parallel", {"size": plan.ep}])
        if plan.dp > 1:
            out.append(["data_parallel", {"size": plan.dp}])
        out.append(["fsdp", {"size": plan.fsdp}])
        return out
    out = []
    for item in strategy:
        name, cfg = item if isinstance(item, (tuple, list)) else (item, {})
        cfg = dict(cfg or {})
        try:
            _json.dumps(cfg)
        except (TypeError, ValueError):
            return None
        out.append([name, cfg])
    return out


def _publish_warm_spec(cache_dir: str, model, strategy_spec: list,
                       devices: Sequence, sample_batch: Dict,
                       accum_steps: int, fused_steps: int = 1) -> None:
    import jax as _jax

    from .warm_pool import WarmSpec, model_spec, publish_current_spec

    mspec = model_spec(model)
    ids = sample_batch.get("input_ids")
    if mspec is None or ids is None or getattr(ids, "ndim", 0) < 2:
        return
    shape = list(ids.shape[-2:])  # global [batch, seq]
    publish_current_spec(cache_dir, WarmSpec(
        n_devices=len(devices), strategy=strategy_spec, model=mspec,
        batch_shape=[int(s) for s in shape], accum_steps=accum_steps,
        platform=_jax.default_backend(), fused_steps=max(1, fused_steps)))


def _all_devices():
    """`jax.devices()` as the program's own first touch of the devices,
    where the caller made none: the runtime's client start, under
    `backend:attach`.  (Down here so that no line above moves: the
    persistent cache's key holds the source lines of a kernel's call
    path, ROADMAP S3(b).)"""
    with tspans.backend_attach("devices"):
        return jax.devices()
