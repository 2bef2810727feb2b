"""graftlint — static analysis enforcing the repo's hard-won SPMD rules.

Parity: reference `dlrover/python/diagnosis/` + `elastic_agent/monitor/`
(error_monitor.py:1, node_check.py:1) diagnose distributed failures at
RUNTIME; graftlint moves the TPU-costly bug classes to a pre-execution
contract.  Six engines share one finding model + rule catalog
(findings.RULE_CATALOG):

- `ast_engine` scans source text: trace-time ``DWT_*`` env reads
  missing from the compile-cache key, donated-buffer reuse,
  control-plane pickle/fork hygiene, module docstring citations.
- `protocol_engine` checks interprocedural control-plane invariants
  over a per-module call graph: journal-before-ack, idem keys,
  commit ordering, atomic publishes, lock leaks.
- `concurrency_engine` checks lock discipline on the same call-graph
  machinery: blocking-under-lock, lock-order cycles, unguarded
  shared state across threads, thread lifecycles.
- `schema_engine` extracts the full wire surface (message dataclasses,
  ADD-ONLY registries, verb classes, journal kinds vs replay branches,
  snapshot export/restore keys) and diffs it against the committed
  `analysis/schema.lock.json` — removals/renames/default changes are
  errors; additions require ``--update-lock``.
- `jaxpr_engine` inspects traced train steps without executing them:
  collective-in-cond deadlocks, CSE-undone remat, donation vs
  optimizer_offload aliasing, host-kind out_shardings.
- `hlo_budget` AOT-lowers the real train step per strategy and audits
  collective-op counts against checked-in analytic budgets.

CLI: ``python -m dlrover_wuqiong_tpu.analysis [--engine
jaxpr|ast|protocol|concurrency|schema|hlo|all] [--format json|sarif]
[--update-lock] [path...]`` — single-line JSON (or SARIF) summary on
stdout (one-line report contract), file:line findings on stderr, exit 1 on
any non-warning finding.  This module and the
ast/protocol/concurrency/schema engines import no jax so
``__graft_entry__.py`` can pre-flight them before any backend
initialization; jaxpr/hlo are imported lazily.
"""

from .ast_engine import run_paths as run_ast_engine  # noqa: F401
from .findings import Finding, render_report, summarize  # noqa: F401


def run_jaxpr_engine(n_devices: int = 8):
    """Lazy Engine A entry — imports jax on first use."""
    from .jaxpr_engine import self_audit

    return self_audit(n_devices)
