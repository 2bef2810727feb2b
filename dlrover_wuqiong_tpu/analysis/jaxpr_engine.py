"""graftlint Engine A — jaxpr-level checks on traced (never executed) steps.

Parity: reference runtime diagnosis (`dlrover/python/diagnosis/
inferencechain/inference_chain.py:1`, error_monitor.py:1) observes NCCL
hangs and OOMs AFTER they fire; on TPU the same bug classes are visible
in the jaxpr before any chip is touched.  Each checker encodes one
CLAUDE.md hard-won rule:

- ``collective-in-cond`` — a collective (psum/all_gather/ppermute/...)
  reachable inside a ``lax.cond`` branch whose predicate VARIES over a
  shard_map manual axis: shards disagree on the branch, the collective
  rendezvous never completes → deadlock.  The fix is to compute
  unconditionally and mask with ``jnp.where`` (all pipeline schedules
  do, parallel/pipeline.py).  Detection is a varying-axes dataflow over
  the jaxpr: shard_map inputs start varying per their in_names, psum-like
  reductions cancel varyingness over their axes, ``axis_index``
  introduces it, and a cond whose predicate still varies over a manual
  axis with a collective in either branch is flagged.
- ``remat-noop`` — ``remat(..., prevent_cse=False)`` outside a
  ``lax.scan``/``while`` body: XLA CSE merges the recompute against the
  forward and silently undoes the rematerialization (identical time AND
  temps, CLAUDE.md).  Under scan the loop body is a separate computation
  and prevent_cse=False is exactly right; unrolled python layer loops
  are the trap (models use prevent_cse=True).
- ``donation-alias`` — donated argnums must be OFF when the resolved
  strategy carries ``optimizer_offload``: XLA would alias a pinned_host
  input onto a device output and the runtime rejects the memory-kind
  mismatch (trainer/train_step.py:102).
- ``host-kind-out-shardings`` — jit ``out_shardings`` carrying a host
  memory kind trips the SPMD partitioner ("Side-effect HLO must have
  sharding"): init on device shardings, then ``jax.device_put`` to the
  host-kind tree (auto/accelerate.py:607).

Everything here works on abstract values (``jax.make_jaxpr`` /
``materialize=False`` state) — no device computation is ever dispatched.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, FrozenSet, List, Optional, Sequence

from .findings import Finding

# collectives that rendezvous across shards (deadlock candidates inside a
# divergent cond) — name -> whether the result is INVARIANT over the
# collective's axes afterwards (psum of x over 'x' is the same on every
# 'x' shard; ppermute stays varying)
_COLLECTIVES: Dict[str, bool] = {
    "psum": True, "psum2": True, "pmax": True, "pmin": True,
    "all_gather": True, "all_to_all": False, "reduce_scatter": False,
    "ppermute": False, "pbroadcast": False, "pgather": False,
}

_HOST_MEMORY_KINDS = ("pinned_host", "unpinned_host", "host")


def _collective_axes(eqn) -> FrozenSet[str]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    try:
        return frozenset(a for a in axes if isinstance(a, str))
    except TypeError:
        return frozenset()


def _source_line(eqn) -> str:
    """file:line of the python frame that emitted this eqn, best-effort."""
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(eqn.source_info)
    except Exception:  # noqa: BLE001 — private API; cosmetic only
        return ""


def _sub_jaxprs(eqn):
    """(sub_jaxpr, invars_for_binders) pairs for eqns that nest jaxprs."""
    import jax.extend.core as core

    name = eqn.primitive.name
    if name == "cond":
        for br in eqn.params.get("branches", ()):
            yield br.jaxpr if hasattr(br, "jaxpr") else br, eqn.invars[1:]
        return
    if name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        carry = eqn.invars[cn + bn:]
        yield eqn.params["cond_jaxpr"].jaxpr, eqn.invars[:cn] + carry
        yield eqn.params["body_jaxpr"].jaxpr, \
            eqn.invars[cn:cn + bn] + carry
        return
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        body = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        if not isinstance(body, core.Jaxpr):
            continue
        yield body, eqn.invars


def _closed(fn_or_jaxpr, args):
    import jax

    if hasattr(fn_or_jaxpr, "jaxpr") or hasattr(fn_or_jaxpr, "eqns"):
        return fn_or_jaxpr
    return jax.make_jaxpr(fn_or_jaxpr)(*args)


def _find_collectives(jaxpr, manual_axes: FrozenSet[str]) -> List:
    """All collective eqns over any manual axis, recursively."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVES and \
                _collective_axes(eqn) & manual_axes:
            out.append(eqn)
        for sub, _ in _sub_jaxprs(eqn):
            out.extend(_find_collectives(sub, manual_axes))
    return out


# ------------------------------------------------- collective-in-cond


def check_collective_in_cond(fn_or_jaxpr, *args) -> List[Finding]:
    """Deadlock scan: cond with a shard-varying predicate guarding a
    collective.  Pass a callable plus example (abstract ok) args, or a
    jaxpr from ``jax.make_jaxpr``."""
    closed = _closed(fn_or_jaxpr, args)
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    findings: List[Finding] = []
    _walk_varying(jaxpr, {v: frozenset() for v in jaxpr.invars},
                  frozenset(), findings)
    return findings


def _walk_varying(jaxpr, varying: Dict, manual_axes: FrozenSet[str],
                  findings: List[Finding]) -> None:
    import jax.extend.core as core

    def axes_of(v) -> FrozenSet[str]:
        if isinstance(v, core.Literal):
            return frozenset()
        return varying.get(v, frozenset())

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_axes = frozenset().union(*(axes_of(v) for v in eqn.invars)) \
            if eqn.invars else frozenset()

        if name == "shard_map":
            manual = frozenset(eqn.params["manual_axes"]) | manual_axes
            body = eqn.params["jaxpr"]
            body = body.jaxpr if hasattr(body, "jaxpr") else body
            in_specs = eqn.params["in_specs"]
            sub_env: Dict = {}
            for bv, spec in zip(body.invars, in_specs):
                # a PartitionSpec entry is None, an axis name, or a
                # tuple of axis names
                named = frozenset(
                    a for entry in spec if entry is not None
                    for a in (entry if isinstance(entry, tuple)
                              else (entry,)))
                sub_env[bv] = (in_axes | named) & manual
            _walk_varying(body, sub_env, manual, findings)
            out_axes = manual  # conservative: shard outputs vary
            for ov in eqn.outvars:
                varying[ov] = out_axes
            continue

        if name == "cond":
            pred_axes = axes_of(eqn.invars[0]) & manual_axes
            if pred_axes:
                for br in eqn.params.get("branches", ()):
                    body = br.jaxpr if hasattr(br, "jaxpr") else br
                    for coll in _find_collectives(body, manual_axes):
                        where = _source_line(coll)
                        findings.append(Finding(
                            "collective-in-cond",
                            f"`{coll.primitive.name}` over axis "
                            f"{sorted(_collective_axes(coll))} inside a "
                            f"cond branch whose predicate varies over "
                            f"manual axis {sorted(pred_axes)} — shards "
                            f"that take different branches deadlock the "
                            f"collective rendezvous; compute "
                            f"unconditionally and mask with jnp.where"
                            + (f" (at {where})" if where else ""),
                            rule="collectives inside lax.cond with a "
                                 "shard-varying predicate deadlock"))

        if name in _COLLECTIVES:
            axes = _collective_axes(eqn)
            out = in_axes | (axes if name == "axis_index" else frozenset())
            if _COLLECTIVES[name]:
                out = out - axes
            for ov in eqn.outvars:
                varying[ov] = out
            continue
        if name == "axis_index":
            ax = eqn.params.get("axis_name", ())
            ax = (ax,) if isinstance(ax, str) else tuple(ax)
            for ov in eqn.outvars:
                varying[ov] = in_axes | frozenset(
                    a for a in ax if isinstance(a, str))
            continue

        for sub, binder_args in _sub_jaxprs(eqn):
            if len(sub.invars) == len(binder_args):
                sub_env = {bv: axes_of(av)
                           for bv, av in zip(sub.invars, binder_args)}
            else:  # unknown calling convention: every binder inherits all
                sub_env = {bv: in_axes for bv in sub.invars}
            _walk_varying(sub, sub_env, manual_axes, findings)

        for ov in eqn.outvars:
            varying[ov] = in_axes


# ------------------------------------------------------------ remat-noop


def check_remat_noop(fn_or_jaxpr, *args) -> List[Finding]:
    """remat(prevent_cse=False) outside a scan/while body: XLA CSE undoes
    the recompute (the python-layer-loop trap, CLAUDE.md)."""
    closed = _closed(fn_or_jaxpr, args)
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    findings: List[Finding] = []
    _walk_remat(jaxpr, in_loop_body=False, findings=findings)
    return findings


def _walk_remat(jaxpr, in_loop_body: bool,
                findings: List[Finding]) -> None:
    unsafe = [e for e in jaxpr.eqns
              if e.primitive.name in ("remat2", "remat")
              and not e.params.get("prevent_cse", True)]
    if not in_loop_body and unsafe:
        # group structurally identical instances: an unrolled layer loop
        # shows up as N clones side by side
        sig = {}
        for e in unsafe:
            body = e.params.get("jaxpr")
            key = tuple(se.primitive.name
                        for se in getattr(body, "eqns", ()))
            sig.setdefault(key, []).append(e)
        for eqns in sig.values():
            e = eqns[0]
            where = _source_line(e)
            n = len(eqns)
            findings.append(Finding(
                "remat-noop",
                f"remat with prevent_cse=False outside a scan/while body"
                + (f" ({n} identical instances — an unrolled python "
                   f"layer loop)" if n > 1 else "")
                + " — XLA CSE merges the recompute against the forward "
                  "and silently undoes rematerialization; use "
                  "prevent_cse=True (models do) or move the loop into "
                  "lax.scan"
                + (f" (at {where})" if where else ""),
                rule="prevent_cse=False under a python layer loop is "
                     "silently undone by XLA CSE"))
    for eqn in jaxpr.eqns:
        is_loop = eqn.primitive.name in ("scan", "while")
        for sub, _ in _sub_jaxprs(eqn):
            _walk_remat(sub, in_loop_body or is_loop, findings)


# -------------------------------------------------------- donation-alias


def check_donation_alias(strategy_extra: Dict[str, Any],
                         donate: Optional[bool]) -> List[Finding]:
    """Donation requested while the strategy offloads optimizer state."""
    if donate and strategy_extra.get("optimizer_offload"):
        return [Finding(
            "donation-alias",
            "donate=True with the 'optimizer_offload' strategy — XLA "
            "would alias a pinned_host input buffer onto a device-memory "
            "output and the runtime rejects the memory-kind mismatch; "
            "donation must stay off (auto_accelerate resolves this "
            "automatically when donate is unset)",
            rule="with ('optimizer_offload', ...) donation is OFF")]
    return []


def resolve_donation(strategy_extra: Dict[str, Any],
                     donate: Optional[bool]) -> bool:
    """The donation flag a train step may actually use.

    ``donate=None`` auto-resolves (off under optimizer_offload); an
    explicit ``donate=True`` that conflicts raises ``ValueError`` at
    resolve time, before any parameter init — the repo's strategy-matrix
    convention for impossible combinations.
    """
    findings = check_donation_alias(strategy_extra, donate)
    if findings:
        raise ValueError(f"graftlint[donation-alias]: "
                         f"{findings[0].message}")
    if donate is None:
        return not strategy_extra.get("optimizer_offload")
    return bool(donate)


# ----------------------------------------------- host-kind-out-shardings


def _is_explicit_host_kind(sharding, kind: Optional[str]) -> bool:
    """True when `kind` means 'deliberately placed off-device'.

    pinned_host is always explicit (the optimizer_offload trees).  On
    the CPU backend the DEFAULT memory kind is literally
    'unpinned_host', so that name only counts as host placement on a
    non-CPU platform.  Deliberately judged from `device.platform` alone:
    querying the memories API (`default_memory()`/`addressable_
    memories()`) on a fresh CPU backend pins its memory-space list
    before pinned_host is registered and every later pinned_host
    NamedSharding construction in the process fails — the checker must
    not perturb what it checks.
    """
    if kind == "pinned_host":
        return True
    if kind in _HOST_MEMORY_KINDS:
        try:
            platform = next(iter(sharding.device_set)).platform
        except Exception:  # noqa: BLE001 — fakes/abstract shardings
            return False
        return platform != "cpu"
    return False


def check_host_out_shardings(tree: Any) -> List[Finding]:
    """Shardings destined for jit out_shardings must be device-kind.

    A leaf is flagged when its memory kind is an explicit host placement
    (see `_is_explicit_host_kind`) — the optimizer_offload pinned_host
    trees, not plain CPU defaults.
    """
    import jax

    findings: List[Finding] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: hasattr(x, "memory_kind"))[0]:
        kind = getattr(leaf, "memory_kind", None)
        if _is_explicit_host_kind(leaf, kind):
            findings.append(Finding(
                "host-kind-out-shardings",
                f"out_shardings leaf {jax.tree_util.keystr(path)} carries "
                f"memory_kind={kind!r} — jit-init onto host memory trips "
                f"the SPMD partitioner ('Side-effect HLO must have "
                f"sharding'); init on device shardings, then "
                f"jax.device_put to the host-kind tree",
                rule="jit out_shardings with a host memory kind trips "
                     "the SPMD partitioner"))
    return findings


def assert_no_host_out_shardings(tree: Any, where: str = "jit init"
                                 ) -> None:
    findings = check_host_out_shardings(tree)
    if findings:
        raise ValueError(
            f"graftlint[host-kind-out-shardings] at {where}: "
            f"{findings[0].message}")


# ---------------------------------------------------------- step audits


def audit_step(fn: Callable, *abstract_args) -> List[Finding]:
    """Trace `fn` (abstract args ok — ShapeDtypeStructs) and run both
    jaxpr checkers.  Never dispatches device computation."""
    import jax

    closed = jax.make_jaxpr(fn)(*abstract_args)
    return (check_collective_in_cond(closed)
            + check_remat_noop(closed))


def self_audit(n_devices: int = 8) -> List[Finding]:
    """Trace the repo's own canonical train steps and lint the jaxprs.

    Covers the strategy corners where the deadlock/remat rules actually
    bite: ring-SP (ppermute inside shard_map, where-masked — must be
    clean), pipeline gpipe (masked schedule collectives), and the remat'd
    fsdp+tp step.  Uses materialize=False abstract state: tracing only.
    """
    import jax
    import jax.numpy as jnp

    from ..auto.accelerate import auto_accelerate
    from ..models.gpt import GPT, GPTConfig

    devices = list(jax.devices("cpu"))[:n_devices]
    if len(devices) < 4:
        return [Finding(
            "self-audit",
            f"need >= 4 cpu devices for the audit meshes, have "
            f"{len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")]
    cfg = GPTConfig(vocab_size=256, n_layer=2, n_head=4, n_embd=64,
                    block_size=32, dtype=jnp.float32)
    cases = [
        ("fsdp-tp-remat", cfg,
         [("tensor_parallel", {"size": 2}), ("fsdp", {}),
          ("checkpoint", {"policy": "dots"})], 1),
        ("ring-sp", cfg,
         [("sequence_parallel", {"size": 2, "impl": "ring"}),
          ("fsdp", {})], 1),
        ("accum", cfg, [("fsdp", {}), ("grad_accum", {"steps": 2})], 2),
    ]
    import dataclasses as _dc

    pp_cfg = _dc.replace(cfg, n_layer=2)
    cases.append(("pp-gpipe", pp_cfg,
                  [("pipeline_parallel", {"size": 2, "microbatches": 2}),
                   ("fsdp", {})], 1))
    findings: List[Finding] = []
    skipped: List[str] = []
    for tag, mcfg, strategy, accum in cases:
        try:
            res = auto_accelerate(GPT(mcfg), strategy=strategy,
                                  devices=devices, materialize=False)
            shape = (4, mcfg.block_size) if accum == 1 else \
                (accum, 4, mcfg.block_size)
            batch = {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32),
                     "labels": jax.ShapeDtypeStruct(shape, jnp.int32)}
            case = audit_step(res.train_step, res.state, batch)
        except RuntimeError as e:
            # a case this environment cannot build — report the skip
            # loudly rather than claiming coverage
            skipped.append(f"{tag}: {e}")
            continue
        for f in case:
            f.message = f"[{tag}] {f.message}"
            findings.append(f)
    if skipped:
        from ..common.log import get_logger

        get_logger("graftlint").warning(
            "self-audit skipped %d case(s): %s", len(skipped),
            "; ".join(skipped))
    return findings
