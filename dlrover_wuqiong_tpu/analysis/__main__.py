"""graftlint CLI — ``python -m dlrover_wuqiong_tpu.analysis``.

Parity: reference `dlrover/python/elastic_agent/diagnosis/
diagnosis_agent.py:1` runs its checks inside the agent loop; here the
same contract is a standalone gate: ONE JSON line
on stdout (machine-readable for CI/driver), human findings on stderr,
exit code 1 when any rule is violated.

Engine selection: ``--engine ast`` / ``--engine protocol`` /
``--engine concurrency`` / ``--engine schema`` need no jax at all (the
`__graft_entry__.py` pre-flight runs all four); ``--engine jaxpr`` /
``--engine hlo`` self-provision a virtual CPU platform (the
audit/budget meshes need 8 devices) BEFORE jax initializes any
backend, so running them on a machine with a TPU never touches a
chip.  ``--changed`` restricts the file-scanning engines to
the git diff (fast CI mode; the whole-program jaxpr/hlo engines are
skipped — schema still runs: its fixed-file extraction is pure AST and
cheap).  ``--catalog`` prints the rule catalog as the one JSON line
and exits 0.  ``--format sarif`` swaps the stdout line for a SARIF
2.1.0 document (still exactly one line) so CI annotates findings in
place; exit code semantics are unchanged.  ``--update-lock``
regenerates ``analysis/schema.lock.json`` from the extracted wire
surface (forces the schema engine on) instead of diffing against it —
internal-consistency errors still gate.

The JSON schema is a compatibility contract (tests/test_analysis.py
pins it): keys are only ever ADDED to the ``graftlint`` object.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import List, Optional


def _default_paths() -> List[str]:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.dirname(pkg)
    cand = [pkg] + [os.path.join(root, p)
                    for p in ("tests", "examples", "tools",
                              "__graft_entry__.py")]
    return [p for p in cand if os.path.exists(p)]


def _changed_paths() -> List[str]:
    """Python files touched in the working tree (diff vs HEAD plus
    untracked) — the ``--changed`` fast mode's scan set."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out: List[str] = []
    for args in (("git", "diff", "--name-only", "HEAD"),
                 ("git", "ls-files", "--others", "--exclude-standard")):
        try:
            text = subprocess.run(
                args, cwd=root, capture_output=True, text=True,
                timeout=30, check=False).stdout
        except (OSError, subprocess.TimeoutExpired):
            continue
        for rel in text.splitlines():
            if rel.endswith(".py"):
                p = os.path.join(root, rel)
                if os.path.exists(p):
                    out.append(p)
    return sorted(set(out))


def _provision_cpu(n_devices: int) -> None:
    """Force a CPU backend with enough virtual devices, pre-init.

    Mirrors tests/conftest.py: the env vars must be set before the
    backend exists; the explicit config update also covers a caller that
    imported jax before us.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m dlrover_wuqiong_tpu.analysis",
        description="graftlint: static SPMD-correctness and "
                    "control-plane-protocol checks")
    parser.add_argument("--engine",
                        choices=("jaxpr", "ast", "protocol", "concurrency",
                                 "schema", "hlo", "all"),
                        default="all")
    parser.add_argument("--format", choices=("json", "sarif"),
                        default="json",
                        help="stdout format: the graftlint JSON line "
                             "(default) or a SARIF 2.1.0 document for CI "
                             "annotation (still one line)")
    parser.add_argument("--devices", type=int, default=8,
                        help="virtual CPU devices for the jaxpr/hlo "
                             "audits")
    parser.add_argument("--max-report", type=int, default=50,
                        help="cap on stderr finding lines")
    parser.add_argument("--changed", action="store_true",
                        help="fast mode: scan only git-diff'd .py files "
                             "with the ast+protocol+concurrency+schema "
                             "engines (jaxpr/hlo are whole-program and "
                             "are skipped)")
    parser.add_argument("--update-lock", action="store_true",
                        help="regenerate analysis/schema.lock.json from "
                             "the extracted wire surface (forces the "
                             "schema engine; deterministic sorted-keys "
                             "JSON, atomic tmp+rename) instead of "
                             "diffing against it")
    parser.add_argument("--catalog", action="store_true",
                        help="print the rule catalog as the one JSON "
                             "line and exit")
    parser.add_argument("paths", nargs="*",
                        help="files/dirs for the ast/protocol engines "
                             "(default: the repo)")
    args = parser.parse_args(argv)

    from .findings import (catalog_json, render_report, summarize,
                           summarize_severity, to_sarif)

    if args.catalog:
        print(json.dumps({"graftlint_catalog": catalog_json()}))
        return 0

    t0 = time.monotonic()
    findings = []
    engines = []
    files_scanned = 0
    hlo_measured = {}
    if args.changed:
        scan_paths = args.paths or _changed_paths()
        run_file_engines = bool(scan_paths)
        run_trace_engines = False
    else:
        scan_paths = args.paths or _default_paths()
        run_file_engines = True
        run_trace_engines = True
    if args.engine in ("ast", "all") and run_file_engines:
        from .ast_engine import run_paths

        ast_findings, files_scanned = run_paths(scan_paths)
        findings.extend(ast_findings)
        engines.append("ast")
    if args.engine in ("protocol", "all") and run_file_engines:
        from .protocol_engine import run_paths as run_protocol

        proto_findings, n_files = run_protocol(scan_paths)
        files_scanned = max(files_scanned, n_files)
        findings.extend(proto_findings)
        engines.append("protocol")
    if args.engine in ("concurrency", "all") and run_file_engines:
        from .concurrency_engine import run_paths as run_concurrency

        conc_findings, n_files = run_concurrency(scan_paths)
        files_scanned = max(files_scanned, n_files)
        findings.extend(conc_findings)
        engines.append("concurrency")
    schema_summary = None
    if (args.engine in ("schema", "all") and run_file_engines) \
            or args.update_lock:
        from .schema_engine import run_schema

        schema_findings, schema_summary = run_schema(
            update_lock=args.update_lock)
        findings.extend(schema_findings)
        engines.append("schema")
    if args.engine in ("jaxpr", "all") and run_trace_engines:
        _provision_cpu(args.devices)
        from .jaxpr_engine import self_audit

        findings.extend(self_audit(args.devices))
        engines.append("jaxpr")
    if args.engine in ("hlo", "all") and run_trace_engines:
        _provision_cpu(args.devices)
        from .hlo_budget import budget_audit

        hlo_findings, hlo_measured = budget_audit(args.devices)
        findings.extend(hlo_findings)
        engines.append("hlo")

    if findings:
        print(render_report(findings, limit=args.max_report),
              file=sys.stderr)
    gating = [f for f in findings if f.severity != "warning"]
    if args.format == "sarif":
        # one-line SARIF 2.1.0 document instead of the graftlint object;
        # same exit-code semantics so CI gates identically.
        print(json.dumps(to_sarif(findings)))
        return 1 if gating else 0
    # report contract: exactly one JSON line on stdout.  Schema
    # evolution is ADD-ONLY (tests/test_analysis.py pins it); the
    # ``schema`` section only appears when the schema engine ran.
    record = {
        "engines": engines,
        "files_scanned": files_scanned,
        "findings": len(findings),
        "by_checker": summarize(findings),
        "by_severity": summarize_severity(findings),
        "hlo_collectives": {
            tag: {op: dict(v) for op, v in sorted(ops.items())}
            for tag, ops in sorted(hlo_measured.items())},
        "elapsed_s": round(time.monotonic() - t0, 2),
        "ok": not gating,
    }
    if schema_summary is not None:
        record["schema"] = schema_summary
    print(json.dumps({"graftlint": record}))
    return 1 if gating else 0


if __name__ == "__main__":
    sys.exit(main())
