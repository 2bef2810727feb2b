"""graftlint Engine B — Python-AST checks over the package and tests.

Parity: reference `dlrover/python/diagnosis/inferencechain/` precheck
operators (node_check.py:1, error_monitor.py:1 run AFTER a failure);
redesign: the four costliest TPU bug classes in this codebase are visible
in the source text, so they are enforced BEFORE a chip is touched:

- ``env-at-trace``    — an env read inside a function of a
  compute-path module changes the emitted HLO at TRACE time behind an
  unchanged Python call: the traced program is a function of its
  arguments, so there is no such read, of any name (CLAUDE.md).
- ``donated-reuse``   — ``train_step`` / ``apply_sparse_update`` DONATE
  their state inputs; code that reads the same variable after passing it
  in observes a dead buffer (CLAUDE.md: copy first in tests).
- ``control-plane-hygiene`` — the agent↔master frame path
  (common/comm.py, messages.py, serialize.py) is typed JSON, never
  pickle; and JAX-initialized processes must spawn, never fork
  (data/shm_loader.py:127).
- ``docstring-citation`` — every package module docstring cites the
  reference files it matches (``file:line``) or carries a ``Parity:``
  note, the repo's documented convention.
- ``blocking-readback`` — an UNCONDITIONAL ``float(...)`` /
  ``np.asarray(...)`` / ``device_get`` on a train-step output inside a
  training loop forces one host sync PER STEP: the device drains before
  the next dispatch, and it defeats the fused K-step driver's
  one-readback-per-fusion contract (trainer/train_step.py).
  Cadence-gated readbacks
  (under an ``if`` — e.g. logging every N steps) are fine.
- ``unverified-restore`` — raw checkpoint bytes (shm ``load_state_dict``
  / ``iter_shards``, shard-file ``np.frombuffer``) feeding a restore
  sink (``restore_pytree`` / ``jax.device_put``) in a function that
  never calls the verification API (checkpoint/integrity.py): the
  checkpoint trust boundary digests every shard at save, and a decode
  path that skips the check hands a flipped bit straight to the device.
- ``raw-rpc-call``     — a control-plane socket dial
  (``socket.create_connection``, ``*sock*.connect``) or frame-level IO
  (``_send_frame``/``_recv_frame``) outside the retry wrapper: every
  such invocation must run inside a function that routes through
  ``retry_call`` (common/util.py) or live in common/comm.py itself —
  the one place the policy is implemented.  A bare dial raises on the
  first ConnectionError, which is exactly how the control plane used
  to die with the master (ISSUE 4); the shared policy gives bounded
  exponential backoff + reconnect everywhere.

This module is import-light on purpose: NO jax, NO package siblings —
``__graft_entry__.py`` runs it as a pre-flight gate before any backend
initialization.  Suppressions: a line containing ``graftlint:
disable=<checker>`` silences that checker for that line (the in-tree
self-lint must pass with suppressions reserved for intentional,
documented cases — e.g. a measured per-step driver, whose whole point
is the per-step sync the rule exists to catch).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding

# package subtrees whose functions run under jit/trace: an env read there
# is a trace-time input (ops/flash_attention.py kernel picks are the
# canonical case).  trainer/ is split: train_step.py is traced, the
# Trainer loop around it is host-side orchestration (reads DWT_JOB_NAME
# etc. legitimately).
COMPUTE_DIRS = ("ops", "models", "parallel", "optimizers", "embedding")
COMPUTE_FILES = ("trainer/train_step.py",)

# control-plane modules whose wire format must stay typed JSON
FRAME_MODULES = ("comm.py", "messages.py", "serialize.py")

# callee name -> (donated positional indices, donated keyword names);
# positions follow the public signatures (trainer/train_step.py:84,
# embedding/sparse_optim.py:133)
DONATING_CALLS: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {
    "train_step": ((0,), ("state",)),
    "apply_sparse_update": ((1, 2), ("table", "state")),
}

_CITE_RE = re.compile(r"[\w/\.-]+\.(?:py|cc|h|proto|md):\d+|\bparity\b",
                      re.IGNORECASE)

# v2 suppression grammar lives in findings.py (shared with the protocol
# engine); reason-less disables are themselves findings — see
# check_suppression_reasons, run once per file below.
from .findings import is_suppressed as _suppressed  # noqa: E402


def _dotted(node: ast.AST) -> Optional[str]:
    """'res.state' for simple Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _env_var_read(node: ast.AST) -> Optional[str]:
    """The env-var name (or "<computed>") when `node` is os.getenv(...),
    os.environ.get(...) or os.environ[...]; None for anything else."""
    name = None
    if isinstance(node, ast.Call):
        func = node.func
        fn = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if (fn == "getenv" or (
                fn == "get" and isinstance(func, ast.Attribute)
                and _dotted(func.value) in ("os.environ", "environ"))) \
                and node.args:
            name = node.args[0]
    elif isinstance(node, ast.Subscript) and \
            _dotted(node.value) in ("os.environ", "environ"):
        name = node.slice
    if name is None:
        return None
    if isinstance(name, ast.Constant) and isinstance(name.value, str):
        return name.value
    return "<computed>"


# --------------------------------------------------------- env-at-trace


def check_env_at_trace(path: str, tree: ast.Module,
                       source_lines: Sequence[str]) -> List[Finding]:
    """No env read inside a function of a compute-path module: it would
    be a trace-time HLO input that no argument, and so no key, shows."""
    posix = path.replace(os.sep, "/")
    parts = posix.split("/")
    in_compute = (any(d in parts[:-1] for d in COMPUTE_DIRS)
                  or any(posix.endswith(f) for f in COMPUTE_FILES))
    if not in_compute or "tests" in parts:
        return []
    findings: List[Finding] = []

    def visit(node: ast.AST, in_func: bool) -> None:
        for child in ast.iter_child_nodes(node):
            child_in_func = in_func or isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef))
            var = _env_var_read(child)
            if var and child_in_func \
                    and not _suppressed(source_lines, child.lineno,
                                        "env-at-trace"):
                findings.append(Finding(
                    "env-at-trace",
                    f"{var} read from the environment inside a "
                    f"compute-path function — the traced program would "
                    f"change behind an unchanged call; decide from the "
                    f"arguments (shapes, dtypes, config, mesh, backend)",
                    path, child.lineno,
                    rule="the traced program is a function of its "
                         "arguments"))
            visit(child, child_in_func)

    visit(tree, in_func=False)
    return findings


# -------------------------------------------------------- donated-reuse


class _Scope:
    """Per-function bookkeeping for the donated-reuse dataflow."""

    def __init__(self) -> None:
        self.stores: Dict[str, List[int]] = {}   # root name -> linenos
        self.loads: Dict[str, List[int]] = {}    # dotted path -> linenos


def _collect_scope(fn: ast.AST) -> Tuple[_Scope, List[Tuple[ast.Call, str,
                                                            List[ast.AST]]]]:
    scope = _Scope()
    donating: List[Tuple[ast.Call, str, List[ast.AST]]] = []

    def record_store(name: str, line: int) -> None:
        scope.stores.setdefault(name, []).append(line)

    def visit(node: ast.AST, loops: List[ast.AST]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and child is not fn:
                continue  # nested scopes tracked separately
            if isinstance(child, ast.Name):
                if isinstance(child.ctx, (ast.Store, ast.Del)):
                    record_store(child.id, child.lineno)
                else:
                    scope.loads.setdefault(child.id, []).append(child.lineno)
            elif isinstance(child, ast.Attribute):
                dotted = _dotted(child)
                if dotted and "." in dotted:
                    if isinstance(child.ctx, (ast.Store, ast.Del)):
                        # `self.state, m = ...` rebinds the attribute: a
                        # kill for the dotted path (but not its root)
                        record_store(dotted, child.lineno)
                    else:
                        scope.loads.setdefault(dotted,
                                               []).append(child.lineno)
            elif isinstance(child, ast.Call):
                func = child.func
                callee = func.attr if isinstance(func, ast.Attribute) else (
                    func.id if isinstance(func, ast.Name) else "")
                if callee in DONATING_CALLS:
                    donating.append((child, callee, list(loops)))
            child_loops = loops + [child] if isinstance(
                child, (ast.For, ast.While, ast.AsyncFor)) else loops
            visit(child, child_loops)

    visit(fn, [])
    return scope, donating


def _donated_args(call: ast.Call, callee: str) -> List[ast.AST]:
    pos, kw = DONATING_CALLS[callee]
    out = [call.args[i] for i in pos if i < len(call.args)]
    out += [k.value for k in call.keywords if k.arg in kw]
    return out


def check_donated_reuse(path: str, tree: ast.Module,
                        source_lines: Sequence[str]) -> List[Finding]:
    """A variable passed to a donating jit must not be read afterwards."""
    findings: List[Finding] = []
    # the module body is a scope too — example scripts donate at top level
    fns: List[ast.AST] = [tree]
    fns += [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fns:
        scope, donating = _collect_scope(fn)
        for call, callee, loops in donating:
            if _suppressed(source_lines, call.lineno, "donated-reuse"):
                continue
            for arg in _donated_args(call, callee):
                dotted = _dotted(arg)
                if dotted is None:
                    continue  # fresh expression (jnp.copy(x), literal, ...)
                root = dotted.split(".")[0]
                kill_lines = scope.stores.get(root, []) + \
                    scope.stores.get(dotted, [])
                call_end = getattr(call, "end_lineno", call.lineno) \
                    or call.lineno
                # (a) read after the donating call with no reassignment
                for load_line in scope.loads.get(dotted, []):
                    if load_line <= call_end:
                        continue
                    if any(call.lineno <= k <= load_line
                           for k in kill_lines):
                        continue
                    if _suppressed(source_lines, load_line,
                                   "donated-reuse"):
                        continue
                    findings.append(Finding(
                        "donated-reuse",
                        f"`{dotted}` is read at line {load_line} after "
                        f"being DONATED to {callee}() — the buffer is dead"
                        f"; copy first (jnp.copy) or rebind the name",
                        path, load_line,
                        rule="train_step/apply_sparse_update donate their "
                             "inputs"))
                    break  # one finding per donated arg is enough
                # (b) re-donated on the next loop iteration unchanged
                if loops:
                    loop = loops[-1]
                    end = max((getattr(n, "lineno", loop.lineno)
                               for n in ast.walk(loop)),
                              default=loop.lineno)
                    if not any(loop.lineno <= k <= end for k in kill_lines):
                        findings.append(Finding(
                            "donated-reuse",
                            f"`{dotted}` is donated to {callee}() inside a "
                            f"loop but never reassigned in the loop body — "
                            f"the next iteration passes a dead buffer",
                            path, call.lineno,
                            rule="train_step/apply_sparse_update donate "
                                 "their inputs"))
    return findings


# ----------------------------------------------- blocking-readback

# callee names that advance the training hot loop; assignments fed by a
# call to one of these mark their targets as step outputs (device values)
STEP_ADVANCING_CALLS = ("train_step", "fused_train_step")
# callee names that force a blocking host readback of their argument
READBACK_CALLS = ("float", "asarray", "device_get")


def _terminal_callee(func: ast.AST) -> str:
    """Terminal name of a call target, through immediately-invoked
    factories: `res.train_step(...)`, `res.fused_train_step(k)(...)`."""
    if isinstance(func, ast.Call):  # factory(...)(args) — look inside
        return _terminal_callee(func.func)
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _assign_targets(node: ast.AST) -> List[str]:
    """Dotted/plain names stored by an assignment target tree."""
    out: List[str] = []
    for t in ast.walk(node):
        if isinstance(t, ast.Name) and isinstance(t.ctx, ast.Store):
            out.append(t.id)
        elif isinstance(t, ast.Attribute) and isinstance(t.ctx, ast.Store):
            dotted = _dotted(t)
            if dotted:
                out.append(dotted)
    return out


def _reads_step_output(expr: ast.AST, outputs: Set[str]) -> bool:
    plain = {o for o in outputs if "." not in o}
    dotted = {o for o in outputs if "." in o}
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in plain:
            return True
        if isinstance(n, ast.Attribute):
            d = _dotted(n)
            if d and any(d == o or d.startswith(o + ".") for o in dotted):
                return True
    return False


def check_blocking_readback(path: str, tree: ast.Module,
                            source_lines: Sequence[str]) -> List[Finding]:
    """Unconditional host readbacks of step outputs inside a train loop.

    A loop qualifies when its body calls a step-advancing function
    (STEP_ADVANCING_CALLS).  A readback qualifies when it executes on
    EVERY iteration — i.e. not nested under an ``if`` within the loop
    (cadence-gated logging is the sanctioned pattern) — and its argument
    derives from a variable assigned from the step call.  Tests are
    exempt: convergence tests read the loss back per step on purpose.
    """
    parts = path.replace(os.sep, "/").split("/")
    if "tests" in parts or parts[-1].startswith("test_"):
        return []
    findings: List[Finding] = []

    loops = [n for n in ast.walk(tree)
             if isinstance(n, (ast.For, ast.While, ast.AsyncFor))]
    for loop in loops:
        # collect step-output names assigned anywhere in this loop body
        outputs: Set[str] = set()
        step_callee = ""
        for n in ast.walk(loop):
            if isinstance(n, (ast.Assign, ast.AnnAssign, ast.AugAssign)) \
                    and n.value is not None:
                calls = [c for c in ast.walk(n.value)
                         if isinstance(c, ast.Call)
                         and _terminal_callee(c.func)
                         in STEP_ADVANCING_CALLS]
                if calls:
                    step_callee = _terminal_callee(calls[0].func)
                    targets = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    for t in targets:
                        outputs.update(_assign_targets(t))
        if not outputs:
            continue

        # walk the loop body tracking conditional nesting; stop at nested
        # loops' own step calls (they get their own pass) is unnecessary —
        # an inner loop's unconditional readback is still per-step
        def visit(node: ast.AST, conditional: bool) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue  # deferred execution: not per-iteration
                child_cond = conditional or isinstance(
                    child, (ast.If, ast.IfExp, ast.Try, ast.ExceptHandler))
                if isinstance(child, ast.Call) and not child_cond:
                    callee = _terminal_callee(child.func)
                    if callee in READBACK_CALLS and child.args and \
                            _reads_step_output(child.args[0], outputs) and \
                            not _suppressed(source_lines, child.lineno,
                                            "blocking-readback"):
                        findings.append(Finding(
                            "blocking-readback",
                            f"`{callee}(...)` on a {step_callee}() output "
                            f"runs UNCONDITIONALLY inside the training "
                            f"loop — one blocking host sync per step "
                            f"(the device drains before the next "
                            f"dispatch); "
                            f"gate it on a cadence or read back once per "
                            f"fused block",
                            path, child.lineno,
                            rule="no per-step host readbacks on the "
                                 "training hot path"))
                visit(child, child_cond)

        visit(loop, conditional=False)
    return findings


# --------------------------------------------------------- raw-rpc-call

# the module that IS the retry wrapper — raw socket IO is its job
RPC_WRAPPER_FILES = ("common/comm.py",)
# frame-level helpers that imply hand-rolled RPC when called elsewhere
FRAME_IO_CALLS = ("_send_frame", "_recv_frame")


def _function_spans(tree: ast.Module):
    """[(start, end, contains_retry_call)] for every function in the file."""
    spans = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        end = max((getattr(n, "end_lineno", None) or
                   getattr(n, "lineno", fn.lineno)
                   for n in ast.walk(fn)), default=fn.lineno)
        has_retry = any(
            isinstance(n, ast.Call)
            and _terminal_callee(n.func) == "retry_call"
            for n in ast.walk(fn))
        spans.append((fn.lineno, end, has_retry))
    return spans


def check_raw_rpc_call(path: str, tree: ast.Module,
                       source_lines: Sequence[str]) -> List[Finding]:
    """Socket dials / frame IO outside the shared retry wrapper.

    A call site is sanctioned when ANY enclosing function also routes
    through ``retry_call`` (the dial being the retried attempt — the
    multi_process IPC client and the checkpoint-replica fetch are the
    in-tree shapes), or when the file is common/comm.py.  Tests are
    exempt: fault-injection tests open raw sockets on purpose.
    """
    posix = path.replace(os.sep, "/")
    parts = posix.split("/")
    if "tests" in parts or parts[-1].startswith("test_"):
        return []
    if any(posix.endswith(f) for f in RPC_WRAPPER_FILES):
        return []
    findings: List[Finding] = []
    spans = _function_spans(tree)

    def sanctioned(line: int) -> bool:
        return any(s <= line <= e and has_retry
                   for s, e, has_retry in spans)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        dotted = _dotted(func) or ""
        callee = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        is_dial = (dotted in ("socket.create_connection",
                              "create_connection")
                   or (callee == "connect" and isinstance(
                       func, ast.Attribute)
                       and "sock" in (_dotted(func.value) or "").lower()))
        is_frame_io = callee in FRAME_IO_CALLS
        if not (is_dial or is_frame_io):
            continue
        line = node.lineno
        if sanctioned(line) or _suppressed(source_lines, line,
                                           "raw-rpc-call"):
            continue
        what = ("frame-level RPC IO" if is_frame_io
                else "control-plane socket dial")
        findings.append(Finding(
            "raw-rpc-call",
            f"{what} `{dotted or callee}(...)` outside the shared retry "
            f"wrapper — route the attempt through retry_call "
            f"(common/util.py) so it gets bounded backoff + reconnect "
            f"instead of dying on the first ConnectionError",
            path, line,
            rule="control-plane sockets go through retry_call"))
    return findings


# --------------------------------------------------- unverified-restore

# device-bound restore sinks: these hand bytes to the accelerator (or to
# the pytree rebuild that feeds device_put)
RESTORE_SINKS = ("restore_pytree", "device_put")
# raw checkpoint byte sources: shm segment reads and shard-file decodes —
# bytes from storage/shm/replica that carry digests which MUST be checked
RAW_RESTORE_SOURCES = ("load_state_dict", "iter_shards", "frombuffer")
# the verification API (checkpoint/integrity.py + the engine's verified
# readers): any of these in the same function sanctions the flow
RESTORE_VERIFY_CALLS = (
    "verify", "verify_segment_entries", "verify_segment_blob",
    "verify_rank_bytes", "verify_meta_bytes", "verify_storage_step",
    "_load_verified_shm", "_read_verified_step",
)


def check_unverified_restore(path: str, tree: ast.Module,
                             source_lines: Sequence[str]) -> List[Finding]:
    """Raw checkpoint bytes reaching a restore sink without verification.

    The checkpoint trust boundary (checkpoint/integrity.py) digests every
    shard at save; a code path that reads raw bytes (shm
    ``load_state_dict``/``iter_shards``, shard-file ``np.frombuffer``)
    AND feeds a restore sink (``restore_pytree``/``jax.device_put``) in
    the same function, without calling the verification API, would hand
    a flipped bit or torn persist straight to the device — exactly the
    silent-restore class the boundary exists to kill.  The sanctioned
    shape is the engine's: verify in the same function that decodes
    (``_read_verified_step``), or go through ``engine.load`` which does.
    Tests are exempt (fault-injection tests read raw bytes on purpose).
    """
    parts = path.replace(os.sep, "/").split("/")
    if "tests" in parts or parts[-1].startswith("test_"):
        return []
    findings: List[Finding] = []

    def scope_calls(fn: ast.AST) -> List[ast.Call]:
        """Calls lexically in `fn`'s own scope (nested defs excluded —
        they are separate scopes walked on their own)."""
        out: List[ast.Call] = []

        def visit(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    continue
                if isinstance(child, ast.Call):
                    out.append(child)
                visit(child)

        visit(fn)
        return out

    fns: List[ast.AST] = [tree]
    fns += [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fns:
        sinks: List[ast.Call] = []
        has_source = has_verify = False
        for node in scope_calls(fn):
            callee = _terminal_callee(node.func)
            if callee in RESTORE_SINKS:
                sinks.append(node)
            elif callee in RAW_RESTORE_SOURCES:
                has_source = True
            elif callee in RESTORE_VERIFY_CALLS:
                has_verify = True
        if not (sinks and has_source) or has_verify:
            continue
        for call in sinks:
            if _suppressed(source_lines, call.lineno,
                           "unverified-restore"):
                continue
            callee = _terminal_callee(call.func)
            findings.append(Finding(
                "unverified-restore",
                f"`{callee}(...)` in a function that also decodes raw "
                f"checkpoint bytes "
                f"({'/'.join(RAW_RESTORE_SOURCES)}) with no call into "
                f"the verification API (checkpoint/integrity.py) — a "
                f"flipped bit or torn persist would reach the device "
                f"silently; verify digests first or route through "
                f"engine.load",
                path, call.lineno,
                rule="checkpoint bytes are verified before device_put"))
    return findings


# ----------------------------------------------- control-plane-hygiene


def check_control_plane_hygiene(path: str, tree: ast.Module,
                                source_lines: Sequence[str]
                                ) -> List[Finding]:
    """No pickle on the typed-JSON frame path; spawn, never fork."""
    findings: List[Finding] = []
    parts = path.replace(os.sep, "/").split("/")
    frame_path = parts[-1] in FRAME_MODULES and "common" in parts
    imports_jax = any(
        (isinstance(n, ast.Import)
         and any(a.name.split(".")[0] == "jax" for a in n.names))
        or (isinstance(n, ast.ImportFrom) and n.module
            and n.module.split(".")[0] == "jax")
        for n in ast.walk(tree))

    for node in ast.walk(tree):
        line = getattr(node, "lineno", 0)
        if _suppressed(source_lines, line, "control-plane-hygiene"):
            continue
        if frame_path and isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = [a.name for a in node.names] if isinstance(
                node, ast.Import) else [node.module or ""]
            for mod in mods:
                if mod.split(".")[0] in ("pickle", "cloudpickle", "dill"):
                    findings.append(Finding(
                        "control-plane-hygiene",
                        f"`{mod}` imported on the control-plane frame path "
                        f"({parts[-1]}) — the wire format is typed JSON "
                        f"frames, never pickle",
                        path, line,
                        rule="control plane is typed JSON frames"))
        if isinstance(node, ast.Call):
            func = node.func
            callee = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else "")
            first = node.args[0].value if node.args and isinstance(
                node.args[0], ast.Constant) else None
            if callee in ("get_context", "set_start_method") and \
                    first == "fork":
                findings.append(Finding(
                    "control-plane-hygiene",
                    f"{callee}('fork') — fork from a JAX-initialized "
                    f"(multithreaded) process deadlocks; use 'spawn' "
                    f"(data/shm_loader.py)",
                    path, line, rule="spawn, never fork"))
            elif callee == "fork" and isinstance(func, ast.Attribute) and \
                    _dotted(func.value) == "os":
                findings.append(Finding(
                    "control-plane-hygiene",
                    "os.fork() — fork from a JAX-initialized process "
                    "deadlocks; use a spawn context",
                    path, line, rule="spawn, never fork"))
            elif callee in ("Process", "Pool") and imports_jax and \
                    isinstance(func, ast.Attribute) and \
                    _dotted(func.value) in ("multiprocessing", "mp"):
                findings.append(Finding(
                    "control-plane-hygiene",
                    f"bare multiprocessing.{callee}() in a jax-importing "
                    f"module defaults to fork on Linux — use "
                    f"get_context('spawn').{callee}",
                    path, line, rule="spawn, never fork"))
    return findings


# ------------------------------------------------- docstring-citation


def check_docstring_citation(path: str, tree: ast.Module,
                             source_lines: Sequence[str],
                             in_package: Optional[bool] = None
                             ) -> List[Finding]:
    """Package modules with code must cite their reference (`file:line`).

    Scoped to files living inside a python package (a dir with
    __init__.py) — tools/ scripts document themselves freely.
    """
    parts = path.replace(os.sep, "/").split("/")
    if parts[-1] == "__init__.py" or "tests" in parts:
        return []
    if in_package is None:
        in_package = os.path.isfile(os.path.join(
            os.path.dirname(os.path.abspath(path)), "__init__.py"))
    if not in_package:
        return []
    has_code = any(isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)) for n in tree.body)
    if not has_code:
        return []
    if _suppressed(source_lines, 1, "docstring-citation"):
        return []
    doc = ast.get_docstring(tree) or ""
    if _CITE_RE.search(doc):
        return []
    what = "has no module docstring" if not doc else \
        "docstring cites no reference file:line (and carries no Parity note)"
    return [Finding(
        "docstring-citation",
        f"module {what} — the repo convention is to cite the matched "
        f"reference files and explain the TPU redesign",
        path, 1, rule="every module docstring cites its reference")]


# ------------------------------------------------ wall-clock-duration

#: arithmetic against a file timestamp is wall-to-wall by necessity
#: (mtimes are wall clock) — exempt, the comparison is correct as is
_WALL_EXEMPT_CALLEES = ("getmtime", "getctime", "getatime",
                        "st_mtime", "st_ctime", "st_atime")


def _is_wall_clock_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _dotted(node.func) in ("time.time", "_time.time"))


def _touches_file_timestamp(node: ast.AST) -> bool:
    for n in ast.walk(node):
        name = ""
        if isinstance(n, ast.Attribute):
            name = n.attr
        elif isinstance(n, ast.Name):
            name = n.id
        if name in _WALL_EXEMPT_CALLEES:
            return True
    return False


def check_wall_clock_duration(path: str, tree: ast.Module,
                              source_lines: Sequence[str]
                              ) -> List[Finding]:
    """``time.time()`` inside elapsed-time / deadline arithmetic.

    Wall clock steps under NTP slew and host suspend; a deadline computed
    as ``time.time() + timeout`` or an interval as ``time.time() - t0``
    can fire early, late, or negative.  Duration math belongs on
    ``time.monotonic()``.  ``time.time()`` stays correct for PERSISTED /
    cross-process timestamps (journal entries, manifest ``ts`` fields,
    file-mtime comparisons) — those sites carry a suppression with the
    reason, or compare against a file timestamp (auto-exempt).
    """
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.BinOp) or \
                not isinstance(node.op, (ast.Add, ast.Sub)):
            continue
        line = getattr(node, "lineno", 0)
        if _suppressed(source_lines, line, "wall-clock-duration"):
            continue
        sides = (node.left, node.right)
        if not any(_is_wall_clock_call(s) for s in sides):
            continue
        if any(_touches_file_timestamp(s) for s in sides):
            continue
        op = "+" if isinstance(node.op, ast.Add) else "-"
        findings.append(Finding(
            "wall-clock-duration",
            f"time.time() used in `{op}` arithmetic — elapsed/deadline "
            f"math on the wall clock drifts under NTP slew; use "
            f"time.monotonic() (keep time.time() only for persisted or "
            f"cross-process timestamps, with a suppression reason)",
            path, line,
            rule="duration math runs on the monotonic clock"))
    return findings


# ------------------------------------------------------------- driver


def iter_python_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                out.extend(os.path.join(root, f)
                           for f in sorted(files) if f.endswith(".py"))
    return sorted(set(out))


def run_paths(paths: Sequence[str],
              checkers: Optional[Sequence[str]] = None
              ) -> Tuple[List[Finding], int]:
    """Run the AST engine over files/dirs; returns (findings, files_scanned).

    `checkers` filters by name.
    """
    files = iter_python_files(paths)
    findings: List[Finding] = []
    for path in files:
        try:
            source = open(path).read()
            tree = ast.parse(source)
        except (OSError, SyntaxError) as e:
            findings.append(Finding("parse-error", str(e), path, 0))
            continue
        lines = source.splitlines()
        rel = os.path.relpath(path)
        if not checkers or "env-at-trace" in checkers:
            findings.extend(check_env_at_trace(rel, tree, lines))
        if not checkers or "donated-reuse" in checkers:
            findings.extend(check_donated_reuse(rel, tree, lines))
        if not checkers or "blocking-readback" in checkers:
            findings.extend(check_blocking_readback(rel, tree, lines))
        if not checkers or "raw-rpc-call" in checkers:
            findings.extend(check_raw_rpc_call(rel, tree, lines))
        if not checkers or "unverified-restore" in checkers:
            findings.extend(check_unverified_restore(rel, tree, lines))
        if not checkers or "control-plane-hygiene" in checkers:
            findings.extend(
                check_control_plane_hygiene(rel, tree, lines))
        if not checkers or "docstring-citation" in checkers:
            findings.extend(check_docstring_citation(rel, tree, lines))
        if not checkers or "wall-clock-duration" in checkers:
            findings.extend(check_wall_clock_duration(rel, tree, lines))
        if not checkers or "suppression-no-reason" in checkers:
            from .findings import check_suppression_reasons

            findings.extend(check_suppression_reasons(rel, lines))
    return findings, len(files)
