"""graftlint HLO budget engine: collective-communication regression gate.

Parity: no reference counterpart — reference dlrover treats communication
volume as a runtime observable (profiler dashboards); a sneaked-in extra
all-gather shows up as a throughput dip nobody attributes.  Here the
classic silent GSPMD regression — a model/step change that makes the
partitioner insert an extra collective or re-replicate a sharded tensor —
is caught at lint time: the engine lowers the repo's REAL
`make_train_step` per strategy on the self-provisioned CPU mesh (the
jaxpr engine's self-audit harness, same tiny GPTConfig), compiles it,
counts the collective ops and their payload bytes in the optimized HLO,
and compares against the checked-in analytic budgets below.  ROADMAP
item 5's perf-gap work gets a gate: a strategy exceeding its budget is a
`collective-budget` finding.

Backend note: XLA:CPU's SPMD expansion lowers all-gather/reduce-scatter
into all-reduce-based patterns, so the op MIX here is backend-specific —
budgets are keyed to this harness (same jax, same mesh, same model) and
are exact-count pins, not TPU predictions.  What IS transferable: the
count deltas.  An edit that adds one all-gather per layer on TPU adds
the same +N ops here.  Bytes budgets carry ~5% headroom (layout padding
may shift with XLA point releases); counts are pinned exactly.

Budget provenance (GPTConfig vocab=256, n_layer=2, n_head=4, n_embd=64,
block=32, 118,528 params, f32, 8 virtual CPU devices):

- ``fsdp`` (mesh fsdp8): the model pins its residual stream to the batch
  layout (parallel/sharding.pin_activation), so the partitioner gathers
  the kernels (19 all-gather, 0.86 MB) and sums weight gradients and the
  loss/grad-norm scalars (3 all-reduce, 0.47 MB); the `wte` lookup and
  its scatter-add are the 2 all-to-all — ~1.35 MB/step measured.  An
  all-to-all count that grows with depth, or an all-reduce of
  [batch, seq, *], is the residual stream leaving that layout.
- ``dp-tp`` (mesh dp4xtp2): grads all-reduce over dp + tp activation
  reductions + scalar reductions = 15 all-reduce (0.47 MB); the tp=2
  attention/mlp boundary contributes 8 collective-permutes and 8
  all-to-alls (CPU's expansion of the tp re-layouts), ~0.70 MB/step
  total measured.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .findings import Finding
from .hlo_scopes import read_instruction

#: collective op names counted in the optimized HLO (async `-start`
#: halves count once; `-done` is ignored).
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute", "all-to-all")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def iter_collectives(hlo_text: str):
    """(op, [(dtype, dims), ...], op_name) of every collective instruction
    in an optimized-HLO dump, one output shape per tuple element; async
    `-start` halves count once, `-done` is ignored.  Line by line
    through `hlo_scopes.read_instruction`, whose shape is the text before
    the opcode: a TPU layout's parentheses (`{0:T(1024)(128)}`) inside a
    tuple do not end it."""
    for ins in filter(None, map(read_instruction, hlo_text.splitlines())):
        op = ins["opcode"].removesuffix("-start")
        if op not in COLLECTIVE_OPS:
            continue
        shapes = [(dt, tuple(int(d) for d in dims.split(",") if d))
                  for dt, dims in _SHAPE_RE.findall(ins["shape"])]
        yield op, shapes, ins["op_name"]


def count_collectives(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """{op: {"count": n, "bytes": b}} over an optimized-HLO dump.

    Bytes are the op's OUTPUT payload (tuple outputs summed) — a proxy
    for wire traffic that is exact for all-reduce/permute and a lower
    bound for gathers.
    """
    out: Dict[str, Dict[str, int]] = {}
    for op, shapes, _ in iter_collectives(hlo_text):
        ent = out.setdefault(op, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += sum(math.prod(dims) * _DTYPE_BYTES.get(dt, 4)
                            for dt, dims in shapes)
    return out


#: checked-in analytic budgets (see module docstring for provenance).
#: "max_count" is an exact pin of the measured lowering; "max_bytes"
#: carries ~5% layout-padding headroom.  An op kind that appears in the
#: lowering but not in the budget is ALWAYS a finding (an unexpected
#: collective kind is exactly the regression this gate exists for).
BUDGETS: Dict[str, Dict] = {
    "fsdp": {
        "strategy": [("fsdp", {})],
        "accum": 1,
        "ops": {
            "all-reduce": {"max_count": 3, "max_bytes": 498_000},
            # the kernels, gathered where they are used; and the step
            # returns the state on the shardings it came in with
            # (trainer/train_step.py)
            "all-gather": {"max_count": 19, "max_bytes": 905_000},
            # the wte gather and its scatter-add: the count must not
            # grow with depth
            "all-to-all": {"max_count": 2, "max_bytes": 17_300},
        },
    },
    "dp-tp": {
        "strategy": [("data_parallel", {"size": 4}),
                     ("tensor_parallel", {"size": 2})],
        "accum": 1,
        "ops": {
            "all-reduce": {"max_count": 15, "max_bytes": 497_000},
            "collective-permute": {"max_count": 8, "max_bytes": 138_000},
            # activations re-laid out between the dp and tp shardings
            "all-to-all": {"max_count": 8, "max_bytes": 103_500},
        },
    },
}


def lower_case_hlo(strategy: Sequence, accum: int,
                   n_devices: int = 8) -> str:
    """Optimized HLO text of the repo's real train step for `strategy`.

    Mirrors jaxpr_engine.self_audit: tiny GPTConfig, materialize=False
    (abstract ShapeDtypeStruct state — AOT lower+compile only, no
    parameter materialization, no dispatch)."""
    import jax
    import jax.numpy as jnp

    from ..auto.accelerate import auto_accelerate
    from ..models.gpt import GPT, GPTConfig

    devices = list(jax.devices("cpu"))[:n_devices]
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} cpu devices for the budget meshes, have "
            f"{len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    cfg = GPTConfig(vocab_size=256, n_layer=2, n_head=4, n_embd=64,
                    block_size=32, dtype=jnp.float32)
    res = auto_accelerate(GPT(cfg), strategy=list(strategy),
                          devices=devices, materialize=False)
    shape = (8, cfg.block_size) if accum == 1 else \
        (accum, 8, cfg.block_size)
    batch = {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32),
             "labels": jax.ShapeDtypeStruct(shape, jnp.int32)}
    return res.train_step.lower(res.state, batch).compile().as_text()


def check_budget(tag: str, counts: Dict[str, Dict[str, int]],
                 budget: Dict) -> List[Finding]:
    """Compare measured collective counts/bytes against one budget."""
    findings: List[Finding] = []
    ops = budget["ops"]
    for op, got in sorted(counts.items()):
        allowed = ops.get(op)
        if allowed is None:
            findings.append(Finding(
                "collective-budget",
                f"[{tag}] unexpected collective kind {op} x{got['count']} "
                f"({got['bytes']} B) — not in the checked-in budget; a "
                f"code change made the partitioner insert new "
                f"communication",
                path="hlo:" + tag))
            continue
        if got["count"] > allowed["max_count"]:
            findings.append(Finding(
                "collective-budget",
                f"[{tag}] {op} count {got['count']} exceeds budget "
                f"{allowed['max_count']} — an extra collective sneaked "
                f"into the lowered step (bytes {got['bytes']})",
                path="hlo:" + tag))
        if got["bytes"] > allowed["max_bytes"]:
            findings.append(Finding(
                "collective-budget",
                f"[{tag}] {op} payload {got['bytes']} B exceeds budget "
                f"{allowed['max_bytes']} B at count {got['count']} — "
                f"same op count moving more data usually means a "
                f"re-replicated operand",
                path="hlo:" + tag))
    return findings


def budget_audit(n_devices: int = 8,
                 budgets: Optional[Dict[str, Dict]] = None
                 ) -> Tuple[List[Finding], Dict[str, Dict]]:
    """Lower+compile every budgeted strategy and gate on the budgets.

    Returns (findings, measured) — `measured` maps tag -> per-op counts
    so the CLI can surface the numbers even when the gate passes.
    An environment that cannot build a case (e.g. too few devices)
    yields a `budget-coverage` WARNING, not silent skippage.
    """
    budgets = BUDGETS if budgets is None else budgets
    findings: List[Finding] = []
    measured: Dict[str, Dict] = {}
    for tag, budget in sorted(budgets.items()):
        try:
            text = lower_case_hlo(budget["strategy"], budget.get(
                "accum", 1), n_devices=n_devices)
        except RuntimeError as e:
            findings.append(Finding(
                "budget-coverage",
                f"[{tag}] budget not checked: {e}",
                path="hlo:" + tag))
            continue
        counts = count_collectives(text)
        measured[tag] = counts
        findings.extend(check_budget(tag, counts, budget))
    return findings, measured
