"""Finding model and rule catalog shared by all graftlint engines.

Parity: reference `dlrover/python/diagnosis/common/diagnosis_action.py`
style typed results (the runtime diagnosis stack reports observations as
structured objects, `diagnosis/diagnostician.py:1` here) — graftlint moves
the same idea BEFORE execution: each hard-won SPMD rule from CLAUDE.md
becomes a checker that emits `Finding`s from a trace or an AST instead of
from a crashed job.  Dependency-free on purpose: the AST and protocol
engines must be importable without initializing jax
(`__graft_entry__.py` pre-flight).

v2 additions: severity levels (``error`` gates, ``warning`` reports),
the machine-readable RULE_CATALOG (one entry per rule id — the README
rule-catalog section and ``--catalog`` both render from it), and the
suppression grammar: an inline ``# graftlint: disable=<ids> -- <reason>``
must carry a reason string after ``--`` or the suppression itself is a
finding (`suppression-no-reason`).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Set

SEVERITIES = ("error", "warning")


@dataclasses.dataclass
class Finding:
    """One rule violation, anchored to a file:line when known."""

    checker: str          # e.g. "env-at-trace"
    message: str          # human-readable, names the offending symbol
    path: str = ""        # repo-relative when possible
    line: int = 0         # 1-based; 0 = not file-anchored (jaxpr findings)
    rule: str = ""        # the CLAUDE.md rule this enforces, one line
    severity: str = ""    # "error" | "warning"; "" = look up the catalog

    def __post_init__(self):
        if not self.severity:
            entry = RULE_CATALOG.get(self.checker)
            self.severity = entry["severity"] if entry else "error"

    def location(self) -> str:
        if self.path and self.line:
            return f"{self.path}:{self.line}"
        return self.path or "<trace>"

    def format(self) -> str:
        return (f"{self.location()}: {self.severity}: "
                f"[{self.checker}] {self.message}")


def summarize(findings: List[Finding]) -> Dict[str, int]:
    """Per-checker counts for the single-line JSON summary."""
    out: Dict[str, int] = {}
    for f in findings:
        out[f.checker] = out.get(f.checker, 0) + 1
    return dict(sorted(out.items()))


def summarize_severity(findings: List[Finding]) -> Dict[str, int]:
    """Per-severity counts ({"error": n, "warning": m}) for the JSON line."""
    out: Dict[str, int] = {}
    for f in findings:
        sev = f.severity if f.severity in SEVERITIES else "error"
        out[sev] = out.get(sev, 0) + 1
    return dict(sorted(out.items()))


def render_report(findings: List[Finding],
                  limit: Optional[int] = None) -> str:
    lines = [f.format() for f in findings[:limit]]
    if limit is not None and len(findings) > limit:
        lines.append(f"... and {len(findings) - limit} more")
    return "\n".join(lines)


# ------------------------------------------------------------ suppressions

#: ``# graftlint: disable=rule-a,rule-b -- why this is sanctioned here``
#: The reason after ``--`` is REQUIRED: a reason-less disable still
#: suppresses (so the fix is additive) but emits `suppression-no-reason`.
DISABLE_RE = re.compile(
    r"graftlint:\s*disable=([\w,-]+)(?:\s*--\s*(\S.*))?")


def suppressed_checkers(line_text: str) -> Set[str]:
    """Rule ids disabled by an inline comment on `line_text` ('' = none)."""
    m = DISABLE_RE.search(line_text)
    if not m:
        return set()
    return {c.strip() for c in m.group(1).split(",") if c.strip()}


def is_suppressed(source_lines: Sequence[str], line: int,
                  checker: str) -> bool:
    """True when the 1-based `line` carries a disable for `checker`."""
    if not (1 <= line <= len(source_lines)):
        return False
    return checker in suppressed_checkers(source_lines[line - 1])


def check_suppression_reasons(path: str,
                              source_lines: Sequence[str]) -> List[Finding]:
    """Every inline disable must carry a ``-- reason`` tail.

    Run by the AST engine only (one pass per file) so `--engine all`
    does not double-report files both engines scan.
    """
    findings: List[Finding] = []
    for i, text in enumerate(source_lines, start=1):
        m = DISABLE_RE.search(text)
        if m and not m.group(2):
            findings.append(Finding(
                "suppression-no-reason",
                f"inline suppression of {m.group(1)!r} has no reason — "
                f"write '# graftlint: disable={m.group(1)} -- <why this "
                f"is sanctioned here>'",
                path=path, line=i,
                rule=RULE_CATALOG["suppression-no-reason"]["rationale"]))
    return findings


# ------------------------------------------------------------ rule catalog

#: id -> {engine, severity, rationale}.  The single source of truth the
#: README catalog, ``--catalog`` and Finding.severity defaults render
#: from; tests assert README and catalog stay in sync.
RULE_CATALOG: Dict[str, Dict[str, str]] = {
    # ---- ast engine (intra-file pattern rules, jax-free)
    "env-at-trace": {
        "engine": "ast", "severity": "error",
        "rationale": "an env read inside a compute-path function changes "
                     "the HLO behind an unchanged call; the traced program "
                     "is a function of its arguments — no name is exempt",
    },
    "donated-reuse": {
        "engine": "ast", "severity": "error",
        "rationale": "train_step/apply_sparse_update DONATE their inputs — "
                     "reusing an argument you passed in reads freed memory",
    },
    "blocking-readback": {
        "engine": "ast", "severity": "error",
        "rationale": "unconditional float()/np.asarray() on step outputs in "
                     "a train loop defeats fused dispatch — sync once per "
                     "fusion via the metrics readback",
    },
    "raw-rpc-call": {
        "engine": "ast", "severity": "error",
        "rationale": "every control-plane socket touch routes through "
                     "retry_call (ONE retry policy); raw dials outside "
                     "common/comm.py bypass backoff, jitter and deadlines",
    },
    "fork-after-jax": {
        "engine": "ast", "severity": "error",
        "rationale": "fork from a JAX-initialized process deadlocks XLA "
                     "runtime threads; spawn, never fork",
    },
    "unverified-restore": {
        "engine": "ast", "severity": "error",
        "rationale": "restore paths must digest-verify storage/shm/replica "
                     "bytes before device_put/restore_pytree — the "
                     "sanctioned route is engine.load",
    },
    "suppression-no-reason": {
        "engine": "ast", "severity": "error",
        "rationale": "inline disables must record WHY the rule is "
                     "sanctioned at that line, or the suppression outlives "
                     "its justification",
    },
    "control-plane-hygiene": {
        "engine": "ast", "severity": "error",
        "rationale": "typed JSON frames only on the agent-master path (no "
                     "pickle), and spawn, never fork, from JAX-initialized "
                     "processes",
    },
    "docstring-citation": {
        "engine": "ast", "severity": "error",
        "rationale": "every package module docstring cites the reference "
                     "file:line it matches so behavior parity stays "
                     "auditable",
    },
    "wall-clock-duration": {
        "engine": "ast", "severity": "warning",
        "rationale": "time.time() in elapsed/deadline arithmetic drifts "
                     "under NTP slew and host suspend — duration math runs "
                     "on time.monotonic(); wall clock is only for "
                     "persisted or cross-process timestamps",
    },
    # ---- protocol engine (interprocedural, per-module call graph)
    "journal-before-ack": {
        "engine": "protocol", "severity": "error",
        "rationale": "a mutating servicer verb acked before its journal "
                     "append is a mutation a master restart silently loses; "
                     "append must dominate the success return",
    },
    "idem-key-required": {
        "engine": "protocol", "severity": "error",
        "rationale": "mutating client verbs retried across a master restart "
                     "re-apply unless an idempotency key rides the frame "
                     "end to end (client call AND servicer journal)",
    },
    "commit-order": {
        "engine": "protocol", "severity": "error",
        "rationale": "checkpoint commit is atomic BY ORDER (done-files -> "
                     "manifest -> marker -> tracker); a marker/tracker "
                     "write with no preceding manifest publish (or commit "
                     "evidence) publishes an unverifiable generation",
    },
    "atomic-publish": {
        "engine": "protocol", "severity": "error",
        "rationale": "published control files (manifest/tracker/marker/"
                     "spec/...) must go through write-tmp+fsync+rename "
                     "(storage.write); a raw open(path, 'w') can tear",
    },
    "lock-leak": {
        "engine": "protocol", "severity": "error",
        "rationale": "a SharedLock acquire whose release is not in a "
                     "finally wedges the next worker generation for the "
                     "full timeout when this process dies mid-section",
    },
    # ---- concurrency engine (lock discipline + shared-state races)
    "blocking-under-lock": {
        "engine": "concurrency", "severity": "error",
        "rationale": "a socket dial/RPC/retry_call/fsync/sleep/subprocess "
                     "spawn reachable inside a lock-held span turns a slow "
                     "or dead peer into a wedge for every waiter — the PR 1 "
                     "(SIGKILLed SharedLock holder, 600s SAVE_TIMEOUT "
                     "stall) and PR 4 (replica dial-under-lock, 150s RPC "
                     "floor) outage shape; copy under the lock, send after "
                     "release",
    },
    "lock-order-cycle": {
        "engine": "concurrency", "severity": "error",
        "rationale": "lock A held while acquiring B adds ordering edge "
                     "A->B; a cycle in the per-module edge graph means two "
                     "threads entering from opposite ends deadlock — "
                     "impose one global acquisition order",
    },
    "unguarded-shared-state": {
        "engine": "concurrency", "severity": "error",
        "rationale": "a self.X mutated in a Thread(target=self._run) "
                     "worker and also written elsewhere with no common "
                     "lock (or read under a lock the worker write does "
                     "not hold) is a data race the GIL does not save you "
                     "from",
    },
    "thread-lifecycle": {
        "engine": "concurrency", "severity": "warning",
        "rationale": "a non-daemon Thread started with no join() on any "
                     "shutdown path hangs process exit — exactly how a "
                     "'finished' job keeps its pod alive; mark it daemon "
                     "or join it from stop()",
    },
    # ---- schema engine (wire-schema compatibility vs the lockfile)
    "schema-removed": {
        "engine": "schema", "severity": "error",
        "rationale": "a wire message/field/registry member/verb/replayed "
                     "journal kind present in schema.lock.json is gone — "
                     "old-generation peers still send it and old journals "
                     "still hold it; ADD-ONLY schemas never remove",
    },
    "schema-renamed": {
        "engine": "schema", "severity": "error",
        "rationale": "a locked name was replaced by a new one at the "
                     "same ordinal slot — a rename is a remove+add on "
                     "the wire; add the new name alongside and keep the "
                     "old one decoding",
    },
    "schema-default-changed": {
        "engine": "schema", "severity": "error",
        "rationale": "frames from old peers OMIT defaulted fields — "
                     "changing the default silently changes what those "
                     "frames mean on decode (sentinels like 0/-1/'' are "
                     "part of the wire contract)",
    },
    "schema-field-no-sentinel": {
        "engine": "schema", "severity": "error",
        "rationale": "the codec drops unknown fields on decode, so "
                     "mixed-generation decode only works when every "
                     "message field has a no-change default; a "
                     "sentinel-less field breaks rolling upgrades",
    },
    "schema-lock-stale": {
        "engine": "schema", "severity": "error",
        "rationale": "the extracted wire surface differs from the "
                     "committed schema.lock.json — additions are legal "
                     "but must be locked in the same PR (--update-lock) "
                     "so the schema delta is a reviewed diff",
    },
    "schema-lock-corrupt": {
        "engine": "schema", "severity": "warning",
        "rationale": "schema.lock.json is unreadable — the engine "
                     "re-extracts and skips the diff rather than "
                     "failing the gate on a torn artifact; regenerate "
                     "with --update-lock",
    },
    "journal-kind-unreplayed": {
        "engine": "schema", "severity": "error",
        "rationale": "a journal kind the servicer/master appends with "
                     "no replay branch in _apply_entry is silent state "
                     "loss at the next failover — every acked mutation "
                     "of that kind vanishes on restart",
    },
    "snapshot-asymmetric": {
        "engine": "schema", "severity": "warning",
        "rationale": "a snapshot key exported by _journal_state but "
                     "never read by _restore_snapshot (or vice versa) "
                     "means compaction silently drops state — the "
                     "export/restore key sets must stay symmetric",
    },
    # ---- jaxpr engine (trace-level)
    "collective-in-cond": {
        "engine": "jaxpr", "severity": "error",
        "rationale": "collectives under lax.cond with a shard-varying "
                     "predicate deadlock the rendezvous; compute "
                     "unconditionally and mask with jnp.where",
    },
    "remat-noop": {
        "engine": "jaxpr", "severity": "error",
        "rationale": "remat with prevent_cse=False under a python layer "
                     "loop is silently undone by XLA CSE",
    },
    "donation-alias": {
        "engine": "jaxpr", "severity": "error",
        "rationale": "donating a pinned_host input onto a device output is "
                     "rejected by the runtime; optimizer_offload must "
                     "disable donation",
    },
    "host-kind-out-shardings": {
        "engine": "jaxpr", "severity": "error",
        "rationale": "jit out_shardings with a host memory kind trips the "
                     "SPMD partitioner; init on device then device_put",
    },
    "self-audit": {
        "engine": "jaxpr", "severity": "warning",
        "rationale": "the self-audit harness could not build its meshes — "
                     "coverage gap, not a rule violation",
    },
    # ---- hlo budget engine (lowered-HLO communication budgets)
    "collective-budget": {
        "engine": "hlo", "severity": "error",
        "rationale": "an extra all-gather/reduce-scatter/all-reduce/"
                     "collective-permute in the lowered step vs the "
                     "checked-in analytic budget is the classic silent "
                     "GSPMD perf regression (ROADMAP item 5 gate)",
    },
    "budget-coverage": {
        "engine": "hlo", "severity": "warning",
        "rationale": "a budgeted strategy could not be lowered in this "
                     "environment — the budget was not checked, which is "
                     "a coverage gap, not a regression",
    },
}


def catalog_json() -> Dict[str, Dict[str, str]]:
    """Stable-ordered catalog for ``--catalog`` and the schema test."""
    return {k: dict(RULE_CATALOG[k]) for k in sorted(RULE_CATALOG)}


# ------------------------------------------------------------------ sarif


def to_sarif(findings: List[Finding]) -> Dict:
    """Serialize findings as a SARIF 2.1.0 document (``--format sarif``).

    Rules render from RULE_CATALOG (the same single source of truth as
    ``--catalog``/README) so CI annotations carry the rationale; findings
    with no file anchor (jaxpr trace findings) omit the location.  Only
    rules that actually fired are listed, keeping the document — and the
    one-line stdout contract — small.
    """
    fired = sorted({f.checker for f in findings})
    rules = []
    for rid in fired:
        entry = RULE_CATALOG.get(rid, {})
        rules.append({
            "id": rid,
            "shortDescription": {"text": entry.get("rationale", rid)},
            "properties": {"engine": entry.get("engine", "unknown")},
            "defaultConfiguration": {
                "level": entry.get("severity", "error")},
        })
    results = []
    for f in findings:
        res = {
            "ruleId": f.checker,
            "level": f.severity if f.severity in SEVERITIES else "error",
            "message": {"text": f.message},
        }
        if f.path:
            region = {"startLine": f.line} if f.line else {}
            loc = {"physicalLocation": {
                "artifactLocation": {"uri": f.path.replace("\\", "/")}}}
            if region:
                loc["physicalLocation"]["region"] = region
            res["locations"] = [loc]
        results.append(res)
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftlint",
                "informationUri":
                    "https://github.com/intelligent-machine-learning/"
                    "dlrover",
                "rules": rules,
            }},
            "results": results,
        }],
    }
