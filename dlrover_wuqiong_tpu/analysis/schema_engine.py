"""graftlint schema engine: wire-schema compatibility vs a committed lock.

Parity: no single reference counterpart — reference dlrover's wire
compatibility lives in `proto/elastic_training.proto:14-29` (protobuf's
field numbering makes removals/renames structurally visible at build
time); this repo's typed-JSON codec (`common/serialize.py:1`) has no such
artifact, so every ADD-ONLY contract was enforced by hand-written pin
tests scattered across six suites.  This engine is the TPU redesign of
the proto file: it EXTRACTS the full wire surface from the AST and diffs
it against a committed lockfile (`analysis/schema.lock.json`), making a
PR's schema delta reviewable in its diff and removals a build-time error.

Like the ast/protocol/concurrency engines this imports no jax — it runs
in the `__graft_entry__.py` pre-flight before any backend exists.

The extracted surface (canonical sorted-keys JSON, field order
preserved inside lists):

- ``messages``: every ``@message`` dataclass in `common/messages.py` —
  field names IN DECLARATION ORDER, each with its default's canonical
  repr and sentinel-ness.  The codec decodes with unknown-field
  filtering (`serialize._decode_value`), so mixed-generation decode
  works iff every field has a default — a new field without one is
  `schema-field-no-sentinel`.
- ``registries``: the ADD-ONLY tuples (`LEDGER_STATES`,
  `SERVE_STATES`/`SERVE_COUNTERS`, `PERF_SNAPSHOT_KEYS`/
  `PERF_EVENT_KEYS`, `TIMELINE_EVENT_KEYS`).
- ``verbs``: the protocol engine's JOURNALED/IDEM sets plus the client
  verb classes recovered from `_call_buffered`/`_call_polling` call
  sites (`agent/master_client.py`).
- ``journal_kinds``: kinds WRITTEN (`self._journal("k", ...)` in the
  servicer, `*.journal.append("k", ...)` in the master,
  `self.append("k", ...)` in journal.py) vs kinds REPLAYED
  (`kind == "k"` comparisons in `_apply_entry` + journal.py's
  ``frame.get("kind") == "k"``).  A written kind with no replay branch
  is `journal-kind-unreplayed` — silent state loss at the next
  failover; a replayed kind removed from the lock is `schema-removed` —
  old journals become undecodable.
- ``snapshot_keys``: `_journal_state()`'s export dict literal vs the
  keys `_restore_snapshot` actually reads — `snapshot-asymmetric`
  (warning) when they drift.

Lockfile lifecycle: additions are legal but require ``--update-lock``
(deterministic sorted-keys JSON, atomic tmp+rename) so the delta is a
reviewed diff; a MISSING lock is the fresh-repo bootstrap (no finding);
a CORRUPT lock re-extracts with `schema-lock-corrupt` (warning), never
fatal; any other drift without ``--update-lock`` is `schema-lock-stale`
(error).  Removal/rename/default-change against the lock are errors —
an old peer or journal can no longer decode.
"""

from __future__ import annotations

import ast
import json
import os
import tempfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .findings import Finding, is_suppressed
from .protocol_engine import _dotted, _terminal

SURFACE_SCHEMA_VERSION = 1

#: package-relative source of the @message dataclasses.
MESSAGES_FILE = "common/messages.py"

#: package-relative file -> ADD-ONLY registry tuple names to extract.
REGISTRY_SPECS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("telemetry/ledger.py", ("LEDGER_STATES",)),
    ("telemetry/serving.py", ("SERVE_STATES", "SERVE_COUNTERS")),
    ("telemetry/perf.py", ("PERF_SNAPSHOT_KEYS", "PERF_EVENT_KEYS")),
    ("telemetry/timeline.py", ("TIMELINE_EVENT_KEYS",)),
)

#: where the journaled/idem verb-class sets live (set literals).
VERB_SETS_FILE = "analysis/protocol_engine.py"
VERB_SET_NAMES = ("JOURNALED_VERBS", "IDEM_VERBS")

#: the typed client facade — buffered/polling classes recovered from
#: `_call_buffered(msg.X(...), ...)` / `_call_polling(verb, msg.X(...))`.
CLIENT_FILE = "agent/master_client.py"

#: files scanned for journal-kind WRITE sites and REPLAY branches.
JOURNAL_WRITE_FILES = ("master/servicer.py", "master/master.py",
                       "master/journal.py")
JOURNAL_REPLAY_FILES = ("master/master.py", "master/journal.py")

#: the snapshot export/restore pair.
SNAPSHOT_FILE = "master/master.py"
SNAPSHOT_EXPORT_FUNC = "_journal_state"
SNAPSHOT_RESTORE_FUNC = "_restore_snapshot"

LOCK_BASENAME = "schema.lock.json"


def default_pkg_root() -> str:
    """The dlrover_wuqiong_tpu package directory."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_lock_path(pkg_root: Optional[str] = None) -> str:
    root = pkg_root or default_pkg_root()
    return os.path.join(root, "analysis", LOCK_BASENAME)


# ------------------------------------------------------------- extraction


class _Source:
    """One parsed source file: tree + lines + display path."""

    __slots__ = ("rel", "path", "tree", "lines")

    def __init__(self, rel: str, path: str, tree: ast.Module,
                 lines: List[str]):
        self.rel = rel
        self.path = path
        self.tree = tree
        self.lines = lines


def _load_sources(pkg_root: str,
                  rels: Sequence[str]) -> Dict[str, _Source]:
    """Parse the spec'd files that exist; missing files are skipped so
    fixture mini-packages (tests) extract partial surfaces."""
    out: Dict[str, _Source] = {}
    for rel in rels:
        path = os.path.join(pkg_root, rel)
        if not os.path.exists(path):
            continue
        try:
            source = open(path).read()
            tree = ast.parse(source)
        except (OSError, SyntaxError):
            continue
        try:
            disp = os.path.relpath(path)
        except ValueError:  # different drive (windows)
            disp = path
        out[rel] = _Source(rel, disp, tree, source.splitlines())
    return out


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _default_repr(node: Optional[ast.AST]) -> Optional[str]:
    """Canonical string for a field default (None = no default)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant):
        return repr(node.value)
    if isinstance(node, ast.Call) and _terminal(node.func) == "field":
        for kw in node.keywords:
            if kw.arg == "default_factory":
                name = _terminal(kw.value) or ast.unparse(kw.value)
                return f"factory:{name}"
            if kw.arg == "default":
                return _default_repr(kw.value)
        return "field:?"
    return ast.unparse(node)


def _extract_messages(src: _Source,
                      anchors: Dict[Tuple, Tuple[str, int]]) -> Dict:
    """@message dataclasses -> {name: {"fields": [{name, default,
    sentinel}...]}} with declaration order preserved."""
    messages: Dict[str, Dict] = {}
    for node in src.tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        if not any(_terminal(d) == "message" for d in node.decorator_list):
            continue
        fields: List[Dict[str, Any]] = []
        for stmt in node.body:
            if not (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)):
                continue
            default = _default_repr(stmt.value)
            fields.append({"name": stmt.target.id, "default": default,
                           "sentinel": default is not None})
            anchors[("field", node.name, stmt.target.id)] = (
                src.rel, stmt.lineno)
        messages[node.name] = {"fields": fields}
        anchors[("message", node.name)] = (src.rel, node.lineno)
    return messages


def _extract_registries(sources: Dict[str, _Source],
                        anchors: Dict[Tuple, Tuple[str, int]]) -> Dict:
    registries: Dict[str, List[str]] = {}
    for rel, names in REGISTRY_SPECS:
        src = sources.get(rel)
        if src is None:
            continue
        for node in src.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                if not (isinstance(target, ast.Name)
                        and target.id in names):
                    continue
                if isinstance(node.value, (ast.Tuple, ast.List)):
                    members = [m for m in
                               (_const_str(e) for e in node.value.elts)
                               if m is not None]
                    registries[target.id] = members
                    anchors[("registry", target.id)] = (rel, node.lineno)
    return registries


def _extract_verb_sets(src: Optional[_Source]) -> Dict[str, List[str]]:
    found: Dict[str, List[str]] = {}
    if src is not None:
        for node in src.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                if isinstance(target, ast.Name) and \
                        target.id in VERB_SET_NAMES and \
                        isinstance(node.value, (ast.Set, ast.Tuple,
                                                ast.List)):
                    found[target.id] = sorted(
                        m for m in (_const_str(e)
                                    for e in node.value.elts)
                        if m is not None)
    return {"journaled": found.get("JOURNALED_VERBS", []),
            "idem": found.get("IDEM_VERBS", [])}


def _msg_constructors(node: ast.AST) -> List[str]:
    """Message type names constructed under `node` (msg.X(...) calls)."""
    out: List[str] = []
    for child in ast.walk(node):
        if isinstance(child, ast.Call) and \
                isinstance(child.func, ast.Attribute) and \
                isinstance(child.func.value, ast.Name) and \
                child.func.value.id == "msg":
            out.append(child.func.attr)
    return out


def _extract_client_verbs(src: Optional[_Source]) -> Dict[str, List[str]]:
    buffered: set = set()
    polling: set = set()
    if src is not None:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            term = _terminal(node.func)
            if term == "_call_buffered" and node.args:
                buffered.update(_msg_constructors(node.args[0]))
            elif term == "_call_polling" and len(node.args) > 1:
                polling.update(_msg_constructors(node.args[1]))
    return {"buffered": sorted(buffered), "polling": sorted(polling)}


def _extract_journal_kinds(sources: Dict[str, _Source],
                           anchors: Dict[Tuple, Tuple[str, int]]) -> Dict:
    written: Dict[str, None] = {}
    for rel in JOURNAL_WRITE_FILES:
        src = sources.get(rel)
        if src is None:
            continue
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            term = _terminal(node.func)
            dotted = _dotted(node.func) or ""
            kind = _const_str(node.args[0])
            if kind is None:
                continue
            is_write = (term == "_journal"
                        or (term == "append"
                            and ("journal" in dotted
                                 or dotted == "self.append")))
            if is_write:
                written.setdefault(kind)
                anchors.setdefault(("written", kind), (rel, node.lineno))
    replayed: Dict[str, None] = {}
    for rel in JOURNAL_REPLAY_FILES:
        src = sources.get(rel)
        if src is None:
            continue
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Compare)
                    and len(node.ops) == 1
                    and isinstance(node.ops[0], ast.Eq)
                    and len(node.comparators) == 1):
                continue
            for a, b in ((node.left, node.comparators[0]),
                         (node.comparators[0], node.left)):
                if _is_kind_expr(a):
                    kind = _const_str(b)
                    if kind is not None:
                        replayed.setdefault(kind)
                        anchors.setdefault(("replayed", kind),
                                           (rel, node.lineno))
    return {"written": sorted(written), "replayed": sorted(replayed)}


def _is_kind_expr(node: ast.AST) -> bool:
    """`kind` name or `<x>.get("kind")` — a replay-dispatch discriminant."""
    if isinstance(node, ast.Name) and node.id == "kind":
        return True
    return (isinstance(node, ast.Call)
            and _terminal(node.func) == "get"
            and bool(node.args)
            and _const_str(node.args[0]) == "kind")


def _extract_snapshot_keys(src: Optional[_Source],
                           anchors: Dict[Tuple, Tuple[str, int]]) -> Dict:
    exported: List[str] = []
    restored: Dict[str, None] = {}
    if src is not None:
        for node in ast.walk(src.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name == SNAPSHOT_EXPORT_FUNC:
                anchors[("exported",)] = (src.rel, node.lineno)
                for child in ast.walk(node):
                    if isinstance(child, ast.Return) and \
                            isinstance(child.value, ast.Dict):
                        for k in child.value.keys:
                            key = _const_str(k) if k is not None else None
                            if key is not None and key not in exported:
                                exported.append(key)
            elif node.name == SNAPSHOT_RESTORE_FUNC:
                anchors[("restored",)] = (src.rel, node.lineno)
                state_arg = ""
                args = node.args.args
                if len(args) > 1:
                    state_arg = args[1].arg   # (self, state)
                elif args:
                    state_arg = args[0].arg
                for child in ast.walk(node):
                    key = None
                    if isinstance(child, ast.Call) and \
                            _terminal(child.func) == "get" and \
                            isinstance(child.func, ast.Attribute) and \
                            isinstance(child.func.value, ast.Name) and \
                            child.func.value.id == state_arg and \
                            child.args:
                        key = _const_str(child.args[0])
                    elif isinstance(child, ast.Subscript) and \
                            isinstance(child.value, ast.Name) and \
                            child.value.id == state_arg:
                        key = _const_str(child.slice)
                    if key is not None:
                        restored.setdefault(key)
    return {"exported": exported, "restored": sorted(restored)}


def extract_surface(pkg_root: Optional[str] = None
                    ) -> Tuple[Dict, Dict[Tuple, Tuple[str, int]],
                               Dict[str, _Source]]:
    """(surface, anchors, sources) — the canonical wire projection plus
    file:line anchors for findings and parsed sources for suppression
    checks."""
    root = pkg_root or default_pkg_root()
    rels = ([MESSAGES_FILE, VERB_SETS_FILE, CLIENT_FILE, SNAPSHOT_FILE]
            + [rel for rel, _ in REGISTRY_SPECS]
            + list(JOURNAL_WRITE_FILES) + list(JOURNAL_REPLAY_FILES))
    sources = _load_sources(root, sorted(set(rels)))
    anchors: Dict[Tuple, Tuple[str, int]] = {}
    msgs_src = sources.get(MESSAGES_FILE)
    surface = {
        "schema": SURFACE_SCHEMA_VERSION,
        "messages": (_extract_messages(msgs_src, anchors)
                     if msgs_src else {}),
        "registries": _extract_registries(sources, anchors),
        "verbs": {**_extract_verb_sets(sources.get(VERB_SETS_FILE)),
                  **_extract_client_verbs(sources.get(CLIENT_FILE))},
        "journal_kinds": _extract_journal_kinds(sources, anchors),
        "snapshot_keys": _extract_snapshot_keys(
            sources.get(SNAPSHOT_FILE), anchors),
    }
    return surface, anchors, sources


# --------------------------------------------------------------- lockfile


def canonical_json(surface: Dict) -> str:
    """Deterministic lock serialization: sorted keys, stable indent,
    trailing newline — `--update-lock` is byte-identical on a clean
    tree."""
    return json.dumps(surface, sort_keys=True, indent=2) + "\n"


def load_lock(path: str) -> Tuple[Optional[Dict], str]:
    """(lock, status): status is "ok" | "missing" | "corrupt"."""
    if not os.path.exists(path):
        return None, "missing"
    try:
        with open(path) as f:
            lock = json.load(f)
        if not isinstance(lock, dict):
            return None, "corrupt"
        return lock, "ok"
    except (OSError, ValueError):
        return None, "corrupt"


def write_lock(path: str, surface: Dict) -> None:
    """Atomic tmp+rename publish (the commit-file discipline — a torn
    lockfile would read as corrupt and silently skip the diff)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".schema.lock.", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(canonical_json(surface))
            f.flush()
            os.fsync(f.fileno())
        os.chmod(tmp, 0o644)  # mkstemp's 0600 is wrong for a committed file
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


# ------------------------------------------------------------------ rules


def _anchored(findings: List[Finding], sources: Dict[str, _Source],
              anchors: Dict[Tuple, Tuple[str, int]], key: Tuple,
              checker: str, message: str,
              fallback: Tuple[str, int] = ("", 0)) -> None:
    """Append a finding at its anchor unless an inline disable covers
    that line (the v2 suppression grammar applies to every engine)."""
    rel, line = anchors.get(key, fallback)
    src = sources.get(rel)
    path = src.path if src else rel
    if src is not None and line and is_suppressed(src.lines, line,
                                                  checker):
        return
    findings.append(Finding(checker, message, path, line))


def check_internal(surface: Dict,
                   anchors: Dict[Tuple, Tuple[str, int]],
                   sources: Dict[str, _Source]) -> List[Finding]:
    """Lock-independent consistency rules over the live surface."""
    findings: List[Finding] = []
    for name, spec in surface["messages"].items():
        for f in spec["fields"]:
            if not f["sentinel"]:
                _anchored(
                    findings, sources, anchors,
                    ("field", name, f["name"]), "schema-field-no-sentinel",
                    f"message field {name}.{f['name']} has no default — "
                    f"the codec drops unknown fields on decode, so a "
                    f"sentinel-less field breaks mixed-generation decode "
                    f"(give it a no-change default like 0/-1/'')")
    kinds = surface["journal_kinds"]
    for kind in kinds["written"]:
        if kind not in kinds["replayed"]:
            _anchored(
                findings, sources, anchors, ("written", kind),
                "journal-kind-unreplayed",
                f"journal kind {kind!r} is written but has no replay "
                f"branch in _apply_entry — every frame of it is silent "
                f"state loss at the next master failover")
    snap = surface["snapshot_keys"]
    for key in snap["exported"]:
        if key not in snap["restored"]:
            _anchored(
                findings, sources, anchors, ("exported",),
                "snapshot-asymmetric",
                f"snapshot key {key!r} is exported by "
                f"{SNAPSHOT_EXPORT_FUNC} but never read by "
                f"{SNAPSHOT_RESTORE_FUNC} — the state it carries "
                f"silently vanishes on restore")
    for key in snap["restored"]:
        if key not in snap["exported"]:
            _anchored(
                findings, sources, anchors, ("restored",),
                "snapshot-asymmetric",
                f"snapshot key {key!r} is read by "
                f"{SNAPSHOT_RESTORE_FUNC} but never exported by "
                f"{SNAPSHOT_EXPORT_FUNC} — the restore branch is dead "
                f"code (or the export was dropped)")
    return findings


def _diff_ordered(findings: List[Finding], sources: Dict[str, _Source],
                  anchors: Dict[Tuple, Tuple[str, int]],
                  anchor_key: Tuple, what: str,
                  locked: Sequence[str], live: Sequence[str]) -> None:
    """Removal/rename findings for an ordered name list (registry
    members, message field names).  A locked name missing from the live
    list whose ordinal slot now holds a NEW name is a rename; otherwise
    a removal."""
    live_set = set(live)
    locked_set = set(locked)
    for i, name in enumerate(locked):
        if name in live_set:
            continue
        if i < len(live) and live[i] not in locked_set:
            _anchored(
                findings, sources, anchors, anchor_key, "schema-renamed",
                f"{what} {name!r} was renamed to {live[i]!r} — old peers "
                f"and journals still send/hold the old name; add the new "
                f"name alongside instead (ADD-ONLY)")
        else:
            _anchored(
                findings, sources, anchors, anchor_key, "schema-removed",
                f"{what} {name!r} was removed — an old-generation peer "
                f"or journal that carries it can no longer decode "
                f"(ADD-ONLY: removals are never legal)")


def diff_lock(surface: Dict, lock: Dict,
              anchors: Dict[Tuple, Tuple[str, int]],
              sources: Dict[str, _Source],
              lock_display: str) -> List[Finding]:
    """Compatibility diff: lock (old generation) vs surface (this tree)."""
    findings: List[Finding] = []
    live_msgs = surface["messages"]
    for name, locked_spec in (lock.get("messages") or {}).items():
        if name not in live_msgs:
            _anchored(
                findings, sources, anchors, ("message", name),
                "schema-removed",
                f"wire message {name} was removed — old peers still "
                f"send it and old journals still hold it",
                fallback=(MESSAGES_FILE, 0))
            continue
        locked_fields = locked_spec.get("fields") or []
        live_fields = live_msgs[name]["fields"]
        _diff_ordered(findings, sources, anchors, ("message", name),
                      f"{name} field", [f["name"] for f in locked_fields],
                      [f["name"] for f in live_fields])
        live_by_name = {f["name"]: f for f in live_fields}
        for lf in locked_fields:
            cur = live_by_name.get(lf["name"])
            if cur is None or not cur["sentinel"]:
                continue  # removal/rename or no-sentinel already fired
            if lf.get("sentinel") and lf.get("default") != cur["default"]:
                _anchored(
                    findings, sources, anchors,
                    ("field", name, lf["name"]), "schema-default-changed",
                    f"default of {name}.{lf['name']} changed "
                    f"{lf.get('default')} -> {cur['default']} — frames "
                    f"from old peers omit the field and now decode to a "
                    f"DIFFERENT value than they meant")
    live_regs = surface["registries"]
    for reg, locked_members in (lock.get("registries") or {}).items():
        if reg not in live_regs:
            _anchored(findings, sources, anchors, ("registry", reg),
                      "schema-removed",
                      f"ADD-ONLY registry {reg} was removed entirely",
                      fallback=("", 0))
            continue
        _diff_ordered(findings, sources, anchors, ("registry", reg),
                      f"{reg} member", locked_members, live_regs[reg])
    live_verbs = surface["verbs"]
    for cls, locked_members in (lock.get("verbs") or {}).items():
        live = live_verbs.get(cls, [])
        for verb in locked_members:
            if verb not in live:
                _anchored(
                    findings, sources, anchors, ("verb", cls, verb),
                    "schema-removed",
                    f"verb {verb} left the {cls!r} class — its durability"
                    f"/retry contract (journaling, idem keys, buffering) "
                    f"changed under old peers",
                    fallback=(VERB_SETS_FILE
                              if cls in ("journaled", "idem")
                              else CLIENT_FILE, 0))
    live_replayed = surface["journal_kinds"]["replayed"]
    for kind in (lock.get("journal_kinds") or {}).get("replayed", []):
        if kind not in live_replayed:
            _anchored(
                findings, sources, anchors, ("replayed", kind),
                "schema-removed",
                f"journal kind {kind!r} lost its replay branch — "
                f"existing journals hold frames of it that a new master "
                f"can no longer apply",
                fallback=(SNAPSHOT_FILE, 0))
    live_restored = surface["snapshot_keys"]["restored"]
    for key in (lock.get("snapshot_keys") or {}).get("restored", []):
        if key not in live_restored:
            _anchored(
                findings, sources, anchors, ("restored",),
                "schema-removed",
                f"snapshot key {key!r} lost its restore branch — "
                f"existing journal snapshots carry state a new master "
                f"silently drops",
                fallback=(SNAPSHOT_FILE, 0))
    if canonical_json(surface) != canonical_json(lock):
        findings.append(Finding(
            "schema-lock-stale",
            f"extracted wire surface differs from {lock_display} — "
            f"additions are legal but must be locked in the same PR: "
            f"run `python -m dlrover_wuqiong_tpu.analysis --engine "
            f"schema --update-lock` and commit the lockfile diff",
            lock_display, 0))
    return findings


# ------------------------------------------------------------ entry point


def surface_counts(surface: Dict) -> Dict:
    """Add-only summary block for the CLI JSON line."""
    return {
        "messages": len(surface["messages"]),
        "fields": sum(len(m["fields"])
                      for m in surface["messages"].values()),
        "registries": len(surface["registries"]),
        "registry_members": sum(len(v)
                                for v in surface["registries"].values()),
        "verbs": {cls: len(v) for cls, v in surface["verbs"].items()},
        "journal_kinds_written": len(surface["journal_kinds"]["written"]),
        "journal_kinds_replayed": len(
            surface["journal_kinds"]["replayed"]),
        "snapshot_exported": len(surface["snapshot_keys"]["exported"]),
        "snapshot_restored": len(surface["snapshot_keys"]["restored"]),
    }


def run_schema(pkg_root: Optional[str] = None,
               update_lock: bool = False,
               lock_path: Optional[str] = None
               ) -> Tuple[List[Finding], Dict]:
    """Run the schema engine; (findings, summary).

    summary = {"surface": <counts>, "lock": "ok" | "missing" |
    "corrupt" | "stale" | "updated"} — rides the CLI JSON line's
    add-only ``schema`` section.
    """
    root = pkg_root or default_pkg_root()
    path = lock_path or default_lock_path(root)
    surface, anchors, sources = extract_surface(root)
    findings = check_internal(surface, anchors, sources)
    try:
        lock_display = os.path.relpath(path)
    except ValueError:
        lock_display = path
    if update_lock:
        # regenerate instead of diffing: the delta becomes the lockfile's
        # own git diff (reviewed), and internal-consistency errors above
        # still gate — --update-lock never launders a broken surface.
        write_lock(path, surface)
        return findings, {"surface": surface_counts(surface),
                          "lock": "updated"}
    lock, status = load_lock(path)
    if status == "corrupt":
        findings.append(Finding(
            "schema-lock-corrupt",
            f"{lock_display} is unreadable — diff skipped this run "
            f"(re-extracted surface stands alone); regenerate with "
            f"--update-lock",
            lock_display, 0))
    elif status == "ok" and lock is not None:
        diff = diff_lock(surface, lock, anchors, sources, lock_display)
        if diff:
            status = "stale"
            findings.extend(diff)
    # status "missing" is the fresh-repo bootstrap: no finding — the
    # first --update-lock commit creates the contract.
    return findings, {"surface": surface_counts(surface), "lock": status}
