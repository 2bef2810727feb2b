"""graftlint schema engine (Engine F) — wire-surface lockfile tests.

Parity: reference `dlrover/python/common/grpc.py:1` evolves its
message set by convention only; here the convention (ADD-ONLY wire
surface, CLAUDE.md) is enforced by extraction + a committed lockfile.
These tests drive the engine against seeded-mutation FIXTURE packages
(a minimal mirror of the repo's wire-bearing files) so every rule is
proven to fire on the exact shape it guards, plus lockfile-lifecycle
contracts: bootstrap, --update-lock determinism, corrupt-lock
degradation, suppression grammar, and the CLI/SARIF rc mapping.

The fixtures are parsed, never imported — the engine is pure AST, so
the mini-package needs no runnable code.
"""

import json
import os
import textwrap

import pytest

from dlrover_wuqiong_tpu.analysis.schema_engine import (
    canonical_json, default_lock_path, diff_lock, extract_surface,
    load_lock, run_schema, surface_counts, write_lock)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ------------------------------------------------------------ fixtures

MESSAGES_SRC = textwrap.dedent('''\
    """fixture wire messages."""
    from dataclasses import dataclass, field


    def message(cls):
        return dataclass(cls)


    @message
    class PolicyDecision:
        verb: str = ""
        cadence: int = 0
        replica_count: int = -1
        tags: list = field(default_factory=list)


    @message
    class HeartBeat:
        ts: float = 0.0
        node_id: str = ""
    ''')

LEDGER_SRC = textwrap.dedent('''\
    """fixture ledger registry."""
    LEDGER_STATES = (
        "productive",
        "rework",
        "degraded",
    )
    ''')

PROTOCOL_SRC = textwrap.dedent('''\
    """fixture verb classes."""
    JOURNALED_VERBS = {"PolicyDecisionReport", "TaskResultReport"}
    IDEM_VERBS = {"PolicyDecisionReport"}
    ''')

CLIENT_SRC = textwrap.dedent('''\
    """fixture master client."""


    class Client:
        def report(self):
            self._call_buffered(msg.HeartBeat(ts=0.0))

        def poll(self):
            return self._call_polling(5.0, msg.PolicyStateRequest())
    ''')

SERVICER_SRC = textwrap.dedent('''\
    """fixture servicer — journal write sites."""


    class Servicer:
        def handle(self, req):
            self._journal("policy", req)
            self._journal("task_result", req)
    ''')

MASTER_SRC = textwrap.dedent('''\
    """fixture master — replay dispatch + snapshot pair."""


    class Master:
        def _apply_entry(self, kind, data):
            if kind == "policy":
                pass
            elif kind == "task_result":
                pass

        def _journal_state(self):
            return {"kv": 1, "policy": 2}

        def _restore_snapshot(self, state):
            self.kv = state.get("kv")
            self.policy = state["policy"]
    ''')

FIXTURE_FILES = {
    "common/messages.py": MESSAGES_SRC,
    "telemetry/ledger.py": LEDGER_SRC,
    "analysis/protocol_engine.py": PROTOCOL_SRC,
    "agent/master_client.py": CLIENT_SRC,
    "master/servicer.py": SERVICER_SRC,
    "master/master.py": MASTER_SRC,
}


def make_pkg(root, overrides=None):
    """Write the fixture mini-package; overrides replace whole files."""
    files = dict(FIXTURE_FILES)
    files.update(overrides or {})
    for rel, text in files.items():
        path = os.path.join(str(root), rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    return str(root)


@pytest.fixture
def locked_pkg(tmp_path):
    """Fixture package with a committed (freshly generated) lockfile."""
    root = make_pkg(tmp_path / "pkg")
    findings, summary = run_schema(pkg_root=root, update_lock=True)
    assert findings == [] and summary["lock"] == "updated"
    return root


def checkers(findings):
    return sorted({f.checker for f in findings})


def mutate(root, rel, old, new):
    path = os.path.join(root, rel)
    text = open(path).read()
    assert old in text, f"fixture drift: {old!r} not in {rel}"
    with open(path, "w") as f:
        f.write(text.replace(old, new))


# ------------------------------------------------------ extraction


class TestExtraction:
    def test_fixture_surface_shape(self, tmp_path):
        root = make_pkg(tmp_path)
        surface, anchors, _ = extract_surface(root)
        assert set(surface["messages"]) == {"PolicyDecision", "HeartBeat"}
        pd = surface["messages"]["PolicyDecision"]["fields"]
        assert [f["name"] for f in pd] == ["verb", "cadence",
                                           "replica_count", "tags"]
        assert [f["default"] for f in pd] == ["''", "0", "-1",
                                              "factory:list"]
        assert all(f["sentinel"] for f in pd)
        assert surface["registries"]["LEDGER_STATES"] == [
            "productive", "rework", "degraded"]
        assert surface["verbs"] == {
            "journaled": ["PolicyDecisionReport", "TaskResultReport"],
            "idem": ["PolicyDecisionReport"],
            "buffered": ["HeartBeat"],
            "polling": ["PolicyStateRequest"]}
        assert surface["journal_kinds"] == {
            "written": ["policy", "task_result"],
            "replayed": ["policy", "task_result"]}
        assert surface["snapshot_keys"] == {
            "exported": ["kv", "policy"],
            "restored": ["kv", "policy"]}
        assert ("field", "PolicyDecision", "verb") in anchors

    def test_missing_files_are_partial_not_fatal(self, tmp_path):
        # a fixture (or a future repo layout change) missing a surface
        # file extracts what exists — never crashes the lint run
        root = make_pkg(tmp_path, overrides={})
        os.unlink(os.path.join(root, "agent/master_client.py"))
        surface, _, _ = extract_surface(root)
        assert surface["verbs"]["buffered"] == []
        assert surface["messages"]  # rest of the surface intact

    def test_real_repo_surface_is_populated(self):
        surface, _, _ = extract_surface()
        counts = surface_counts(surface)
        assert counts["messages"] >= 68
        assert counts["fields"] >= 211
        assert counts["registries"] >= 6
        assert counts["verbs"]["journaled"] >= 13
        assert counts["journal_kinds_written"] >= 16
        assert counts["snapshot_exported"] >= 8


# ------------------------------------------------- lockfile lifecycle


class TestLockfileLifecycle:
    def test_bootstrap_missing_lock_is_silent(self, tmp_path):
        root = make_pkg(tmp_path)
        findings, summary = run_schema(pkg_root=root)
        assert findings == []
        assert summary["lock"] == "missing"

    def test_update_lock_is_byte_identical(self, locked_pkg):
        lock_path = default_lock_path(locked_pkg)
        first = open(lock_path, "rb").read()
        findings, summary = run_schema(pkg_root=locked_pkg,
                                       update_lock=True)
        assert findings == [] and summary["lock"] == "updated"
        assert open(lock_path, "rb").read() == first
        # deterministic canonical form: sorted keys + trailing newline
        surface, _, _ = extract_surface(locked_pkg)
        assert first.decode() == canonical_json(surface)
        assert first.endswith(b"\n")

    def test_lockfile_is_world_readable(self, locked_pkg):
        # a committed artifact must not carry mkstemp's 0600
        mode = os.stat(default_lock_path(locked_pkg)).st_mode & 0o777
        assert mode == 0o644

    def test_clean_tree_diffs_clean(self, locked_pkg):
        findings, summary = run_schema(pkg_root=locked_pkg)
        assert findings == []
        assert summary["lock"] == "ok"

    def test_corrupt_lock_warns_never_fatal(self, locked_pkg):
        with open(default_lock_path(locked_pkg), "w") as f:
            f.write("{torn")
        findings, summary = run_schema(pkg_root=locked_pkg)
        assert checkers(findings) == ["schema-lock-corrupt"]
        assert all(f.severity == "warning" for f in findings)
        assert summary["lock"] == "corrupt"
        # --update-lock recovers
        findings, summary = run_schema(pkg_root=locked_pkg,
                                       update_lock=True)
        assert findings == [] and summary["lock"] == "updated"

    def test_non_dict_lock_is_corrupt(self, locked_pkg):
        with open(default_lock_path(locked_pkg), "w") as f:
            f.write("[1, 2]\n")
        lock, status = load_lock(default_lock_path(locked_pkg))
        assert lock is None and status == "corrupt"

    def test_write_lock_atomic_no_tmp_residue(self, tmp_path):
        root = make_pkg(tmp_path / "pkg")
        surface, _, _ = extract_surface(root)
        path = default_lock_path(root)
        write_lock(path, surface)
        residue = [n for n in os.listdir(os.path.dirname(path))
                   if n.startswith(".schema.lock.")]
        assert residue == []

    def test_addition_is_stale_until_update(self, locked_pkg):
        # ADD-ONLY means additions are legal — but the lock must be
        # regenerated so the delta shows up as a reviewed git diff
        mutate(locked_pkg, "telemetry/ledger.py",
               '"degraded",\n', '"degraded",\n    "compile",\n')
        findings, summary = run_schema(pkg_root=locked_pkg)
        assert checkers(findings) == ["schema-lock-stale"]
        assert summary["lock"] == "stale"
        findings, _ = run_schema(pkg_root=locked_pkg, update_lock=True)
        assert findings == []
        findings, summary = run_schema(pkg_root=locked_pkg)
        assert findings == [] and summary["lock"] == "ok"


# --------------------------------------------------- seeded mutations


class TestSeededMutations:
    def test_removed_message_field(self, locked_pkg):
        mutate(locked_pkg, "common/messages.py",
               "    replica_count: int = -1\n", "")
        findings, summary = run_schema(pkg_root=locked_pkg)
        assert "schema-removed" in checkers(findings)
        assert summary["lock"] == "stale"
        hit = [f for f in findings if f.checker == "schema-removed"]
        assert any("replica_count" in f.message for f in hit)
        assert all(f.severity == "error" for f in hit)

    def test_removed_message(self, locked_pkg):
        mutate(locked_pkg, "common/messages.py",
               "@message\nclass HeartBeat:\n    ts: float = 0.0\n"
               "    node_id: str = \"\"\n", "")
        findings, _ = run_schema(pkg_root=locked_pkg)
        hit = [f for f in findings if f.checker == "schema-removed"]
        assert any("HeartBeat" in f.message for f in hit)

    def test_renamed_field_same_ordinal(self, locked_pkg):
        mutate(locked_pkg, "common/messages.py",
               "replica_count: int = -1", "replicas: int = -1")
        findings, _ = run_schema(pkg_root=locked_pkg)
        hit = [f for f in findings if f.checker == "schema-renamed"]
        assert len(hit) == 1
        assert "replica_count" in hit[0].message
        assert "replicas" in hit[0].message

    def test_default_changed(self, locked_pkg):
        mutate(locked_pkg, "common/messages.py",
               "replica_count: int = -1", "replica_count: int = 0")
        findings, _ = run_schema(pkg_root=locked_pkg)
        assert "schema-default-changed" in checkers(findings)

    def test_stripped_sentinel_default(self, locked_pkg):
        mutate(locked_pkg, "common/messages.py",
               "replica_count: int = -1", "replica_count: int")
        findings, _ = run_schema(pkg_root=locked_pkg)
        assert "schema-field-no-sentinel" in checkers(findings)
        hit = [f for f in findings
               if f.checker == "schema-field-no-sentinel"]
        assert all(f.severity == "error" for f in hit)
        # internal rule: fires even with no lock at all
        os.unlink(default_lock_path(locked_pkg))
        findings, summary = run_schema(pkg_root=locked_pkg)
        assert checkers(findings) == ["schema-field-no-sentinel"]
        assert summary["lock"] == "missing"

    def test_removed_registry_member(self, locked_pkg):
        mutate(locked_pkg, "telemetry/ledger.py", '    "rework",\n', "")
        findings, _ = run_schema(pkg_root=locked_pkg)
        hit = [f for f in findings if f.checker == "schema-removed"]
        assert any("rework" in f.message and "LEDGER_STATES" in f.message
                   for f in hit)

    def test_dropped_replay_branch(self, locked_pkg):
        mutate(locked_pkg, "master/master.py",
               'elif kind == "task_result":', 'elif kind == "zzz":')
        findings, _ = run_schema(pkg_root=locked_pkg)
        got = checkers(findings)
        assert "journal-kind-unreplayed" in got   # written w/o replay
        assert "schema-removed" in got            # replayed set shrank

    def test_unreplayed_kind_fires_without_lock(self, tmp_path):
        # journal-kind-unreplayed is internal consistency, not a diff
        root = make_pkg(tmp_path, overrides={
            "master/master.py": MASTER_SRC.replace(
                'elif kind == "task_result":\n            pass\n', "")})
        findings, _ = run_schema(pkg_root=root)
        hit = [f for f in findings
               if f.checker == "journal-kind-unreplayed"]
        assert len(hit) == 1 and "task_result" in hit[0].message
        assert hit[0].severity == "error"

    def test_snapshot_asymmetric_both_directions(self, tmp_path):
        # exported-not-restored
        root = make_pkg(tmp_path / "a", overrides={
            "master/master.py": MASTER_SRC.replace(
                '        self.policy = state["policy"]\n', "")})
        findings, _ = run_schema(pkg_root=root)
        hit = [f for f in findings if f.checker == "snapshot-asymmetric"]
        assert len(hit) == 1 and "policy" in hit[0].message
        assert hit[0].severity == "warning"
        # restored-not-exported
        root = make_pkg(tmp_path / "b", overrides={
            "master/master.py": MASTER_SRC.replace(
                '"policy": 2', "")})
        findings, _ = run_schema(pkg_root=root)
        hit = [f for f in findings if f.checker == "snapshot-asymmetric"]
        assert len(hit) == 1 and "policy" in hit[0].message

    def test_restored_snapshot_key_removal_is_error(self, locked_pkg):
        # dropping a restore read regresses crash-recovery coverage:
        # both the asymmetry warning and the lock diff must fire
        mutate(locked_pkg, "master/master.py",
               '        self.policy = state["policy"]\n', "")
        findings, _ = run_schema(pkg_root=locked_pkg)
        got = checkers(findings)
        assert "snapshot-asymmetric" in got
        assert "schema-removed" in got

    def test_suppression_grammar_honored(self, tmp_path):
        root = make_pkg(tmp_path, overrides={
            "common/messages.py": MESSAGES_SRC.replace(
                "        node_id: str = \"\"\n",
                "        node_id: str  # graftlint: "
                "disable=schema-field-no-sentinel -- fixture probe\n")})
        findings, _ = run_schema(pkg_root=root)
        assert "schema-field-no-sentinel" not in checkers(findings)

    def test_diff_lock_verb_demotion(self, locked_pkg):
        # dropping a verb from JOURNALED_VERBS is a removal, not churn
        mutate(locked_pkg, "analysis/protocol_engine.py",
               '{"PolicyDecisionReport", "TaskResultReport"}',
               '{"PolicyDecisionReport"}')
        findings, _ = run_schema(pkg_root=locked_pkg)
        hit = [f for f in findings if f.checker == "schema-removed"]
        assert any("TaskResultReport" in f.message for f in hit)

    def test_diff_lock_pure_function(self, locked_pkg):
        surface, anchors, sources = extract_surface(locked_pkg)
        lock, status = load_lock(default_lock_path(locked_pkg))
        assert status == "ok"
        assert diff_lock(surface, lock, anchors, sources, "lock") == []


# ------------------------------------------------------- CLI surface


class TestSchemaCli:
    def _point_at(self, monkeypatch, root):
        from dlrover_wuqiong_tpu.analysis import schema_engine

        monkeypatch.setattr(schema_engine, "default_pkg_root",
                            lambda: root)

    def test_mutation_flips_rc1(self, locked_pkg, monkeypatch, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        self._point_at(monkeypatch, locked_pkg)
        assert main(["--engine", "schema"]) == 0
        capsys.readouterr()
        mutate(locked_pkg, "common/messages.py",
               "replica_count: int = -1", "replica_count: int")
        rc = main(["--engine", "schema"])
        cap = capsys.readouterr()
        assert rc == 1
        rec = json.loads(cap.out.strip())["graftlint"]
        assert rec["ok"] is False
        assert "schema-field-no-sentinel" in rec["by_checker"]
        assert rec["schema"]["lock"] == "stale"
        assert "schema-field-no-sentinel" in cap.err

    def test_corrupt_lock_rc0(self, locked_pkg, monkeypatch, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        self._point_at(monkeypatch, locked_pkg)
        with open(default_lock_path(locked_pkg), "w") as f:
            f.write("{torn")
        rc = main(["--engine", "schema"])
        cap = capsys.readouterr()
        assert rc == 0   # warning-only: degraded, never fatal
        rec = json.loads(cap.out.strip())["graftlint"]
        assert rec["by_severity"] == {"warning": 1}
        assert rec["schema"]["lock"] == "corrupt"

    def test_update_lock_flag_forces_schema(self, locked_pkg,
                                            monkeypatch, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        self._point_at(monkeypatch, locked_pkg)
        mutate(locked_pkg, "telemetry/ledger.py",
               '"degraded",\n', '"degraded",\n    "compile",\n')
        # --update-lock without --engine schema still runs the engine
        rc = main(["--engine", "ast", "--update-lock",
                   os.path.join(locked_pkg, "common")])
        cap = capsys.readouterr()
        assert rc == 0
        rec = json.loads(cap.out.strip())["graftlint"]
        assert "schema" in rec["engines"]
        assert rec["schema"]["lock"] == "updated"
        rc = main(["--engine", "schema"])
        capsys.readouterr()
        assert rc == 0

    def test_sarif_contract_over_schema_rules(self, locked_pkg,
                                              monkeypatch, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        self._point_at(monkeypatch, locked_pkg)
        mutate(locked_pkg, "common/messages.py",
               "replica_count: int = -1", "replicas: int = -1")
        rc = main(["--engine", "schema", "--format", "sarif"])
        cap = capsys.readouterr()
        assert rc == 1
        lines = cap.out.strip().splitlines()
        assert len(lines) == 1   # still exactly one stdout line
        sarif = json.loads(lines[0])
        assert sarif["version"] == "2.1.0"
        run = sarif["runs"][0]
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        result_ids = {r["ruleId"] for r in run["results"]}
        assert "schema-renamed" in result_ids
        assert result_ids <= rule_ids
        for res in run["results"]:
            if res["ruleId"] == "schema-renamed":
                loc = res["locations"][0]["physicalLocation"]
                assert loc["artifactLocation"]["uri"]
                assert res["level"] == "error"


# ------------------------------------------------ repo self-lint (t1)


class TestSchemaSelfLint:
    def test_repo_surface_matches_committed_lock(self):
        """The committed lockfile is in sync with the live tree — the
        same gate __graft_entry__'s preflight runs before every dryrun."""
        findings, summary = run_schema()
        assert findings == [], "\n".join(f.format() for f in findings)
        assert summary["lock"] == "ok"

    def test_committed_lock_is_canonical_bytes(self):
        """git's copy byte-equals the canonical serialization — a hand
        edit or non-canonical writer would silently defeat the
        byte-level determinism contract."""
        surface, _, _ = extract_surface()
        with open(default_lock_path(), "rb") as f:
            assert f.read().decode() == canonical_json(surface)
