"""graftlint v2 protocol engine (analysis/protocol_engine.py).

One good + one bad fixture per interprocedural rule (journal-before-ack,
idem-key-required, commit-order, atomic-publish, lock-leak), the
suppression-reason grammar, the v2 CLI surface (--catalog, --changed,
JSON schema stability — downstream parsers of the one-line output must
never break silently), and the tier-1 repo self-lint: the protocol
engine over this tree must come back clean.  Pure AST work — no jax
device computation anywhere in this file.
"""

import json
import os
import subprocess
import sys
import textwrap

from dlrover_wuqiong_tpu.analysis.findings import (
    Finding,
    RULE_CATALOG,
    check_suppression_reasons,
    render_report,
    summarize_severity,
)
from dlrover_wuqiong_tpu.analysis.protocol_engine import run_paths

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan(tmp_path, relpath, source, **kw):
    """Write one fixture file and run the protocol engine over it."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings, _ = run_paths([str(tmp_path)], **kw)
    return findings


# ------------------------------------------------- journal-before-ack


_SERVICER_PREAMBLE = """\
    class Servicer:
        def _journal(self, kind, data, idem=None, resp=None):
            journal = self.m.journal
            if journal is None:
                return
            journal.append(kind, data)

"""


class TestJournalBeforeAck:
    def test_unjournaled_mutating_verb_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.TaskResult):
                self.m.task_manager.report_dataset_task(
                    node_id, payload.dataset_name, payload.task_id)
                return msg.OkResponse()
            return None
""")
        assert [f.checker for f in found] == ["journal-before-ack"]
        assert "TaskResult" in found[0].message

    def test_ack_before_append_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.KVStoreSetRequest):
                self.m.kv_store.set(payload.key, payload.value)
                return msg.OkResponse()
                self._journal("kv_set", {"key": payload.key})
            return None
""")
        assert any(f.checker == "journal-before-ack"
                   and "BEFORE its journal append" in f.message
                   for f in found)

    def test_journal_then_ack_clean(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.TaskResult):
                self.m.task_manager.report_dataset_task(
                    node_id, payload.dataset_name, payload.task_id)
                resp = msg.OkResponse()
                self._journal("task_result", {"task_id": payload.task_id},
                              idem=idem, resp=resp)
                return resp
            return None
""")
        assert found == []

    def test_conditional_journal_before_final_return_clean(self, tmp_path):
        # the in-tree DatasetShardParams shape: a no-op mutation need
        # not journal, so the append may sit under `if created:`
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.DatasetShardParams):
                created = self.m.task_manager.new_dataset(payload.name)
                if created:
                    self._journal("dataset", {"name": payload.name})
                return msg.OkResponse()
            return None
""")
        assert found == []

    def test_non_servicer_module_ignored(self, tmp_path):
        # no _journal method => not a servicer class, rule stays quiet
        found = _scan(tmp_path, "other.py", """\
            class Helper:
                def dispatch(self, payload):
                    if isinstance(payload, msg.TaskResult):
                        return handle(payload)
        """)
        assert found == []


# ------------------------------------------- group-commit batched shape


_GC_PREAMBLE = """\
    class Servicer:
        def _journal(self, kind, data, idem=None, resp=None):
            journal = self.m.journal
            if journal is None:
                return
            seq = journal.append_nowait(kind, data)
            journal.wait_durable(seq)

"""


class TestGroupCommitShape:
    """The batched journal-before-ack shape: an ack gated on the durable
    watermark (append_nowait + wait_durable) counts as journal-append
    reaching the ack; an async enqueue with NO watermark gate is the new
    bad shape (the ack would race the batch leader's fsync)."""

    def test_batched_journal_helper_clean(self, tmp_path):
        # the in-tree MasterServicer._journal shape after group commit
        found = _scan(tmp_path, "servicer.py", _GC_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.TaskResult):
                self.m.task_manager.report_dataset_task(
                    node_id, payload.dataset_name, payload.task_id)
                resp = msg.OkResponse()
                self._journal("task_result", {"task_id": payload.task_id},
                              idem=idem, resp=resp)
                return resp
            return None
""")
        assert found == []

    def test_async_append_without_durable_wait_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _GC_PREAMBLE + """\
        def _enqueue_only(self, kind, data, idem=None):
            self.m.journal.append_nowait(kind, data)

        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.KVStoreSetRequest):
                self.m.kv_store.set(payload.key, payload.value)
                self._enqueue_only("kv_set", {"key": payload.key})
                return msg.OkResponse()
            return None
""")
        assert [f.checker for f in found] == ["journal-before-ack"]
        assert "wait_durable" in found[0].message

    def test_split_shape_assembled_in_branch_clean(self, tmp_path):
        # enqueue and watermark gate via SEPARATE helpers, paired in the
        # branch before the ack — a legal decomposition of group commit
        found = _scan(tmp_path, "servicer.py", _GC_PREAMBLE + """\
        def _enqueue(self, kind, data, idem=None):
            return self.m.journal.append_nowait(kind, data)

        def _gate(self, seq):
            self.m.journal.wait_durable(seq)

        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.KVStoreSetRequest):
                self.m.kv_store.set(payload.key, payload.value)
                seq = self._enqueue("kv_set", {"key": payload.key})
                self._gate(seq)
                return msg.OkResponse()
            return None
""")
        assert found == []

    def test_idem_key_rides_the_async_half(self, tmp_path):
        # idem-key-required must see idem= on the enqueue call even when
        # the durability gate is a separate helper
        found = _scan(tmp_path, "servicer.py", _GC_PREAMBLE + """\
        def _enqueue(self, kind, data, idem=None):
            return self.m.journal.append_nowait(kind, data)

        def _gate(self, seq):
            self.m.journal.wait_durable(seq)

        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.TaskResult):
                resp = msg.OkResponse()
                seq = self._enqueue("task_result", {"id": payload.task_id},
                                    idem=idem)
                self._gate(seq)
                return resp
            return None
""")
        assert found == []

    def test_idem_missing_on_batched_shape_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _GC_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.TaskResult):
                resp = msg.OkResponse()
                self._journal("task_result", {"id": payload.task_id})
                return resp
            return None
""")
        assert [f.checker for f in found] == ["idem-key-required"]


class TestPolicyVerbs:
    """PolicyDecisionReport sits in JOURNALED_VERBS + IDEM_VERBS: an
    adaptive decision that vanishes across a master restart would leave
    trainers on knobs the replayed master never heard of."""

    def test_policy_ack_without_journal_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.PolicyDecisionReport):
                decision = self.m.admit_policy_decision(payload.decision)
                return msg.PolicyDecisionAck(
                    decision_id=decision.decision_id)
            return None
""")
        assert [f.checker for f in found] == ["journal-before-ack"]
        assert "PolicyDecisionReport" in found[0].message

    def test_policy_journal_without_idem_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.PolicyDecisionReport):
                decision = self.m.admit_policy_decision(payload.decision)
                resp = msg.PolicyDecisionAck(
                    decision_id=decision.decision_id)
                self._journal("policy", {"decision": decision})
                return resp
            return None
""")
        assert [f.checker for f in found] == ["idem-key-required"]
        assert "PolicyDecisionReport" in found[0].message

    def test_policy_journal_before_ack_with_idem_clean(self, tmp_path):
        # the in-tree servicer shape: journal carries idem + resp in ONE
        # frame (a separate frame could tear between them)
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.PolicyDecisionReport):
                decision = self.m.admit_policy_decision(payload.decision)
                resp = msg.PolicyDecisionAck(
                    decision_id=decision.decision_id)
                self._journal("policy", {"decision": decision},
                              idem=idem, resp=resp)
                return resp
            return None
""")
        assert found == []

    def test_policy_client_send_without_idem_flagged(self, tmp_path):
        found = _scan(tmp_path, "client.py", """\
            class Client:
                def report_policy_decision(self, decision):
                    req = msg.PolicyDecisionReport(decision=decision)
                    return self._call_critical("report", req)
        """)
        assert [f.checker for f in found] == ["idem-key-required"]


class TestServeVerbs:
    """The serving verb family (ServeSubmitRequest / ServeLeaseRequest /
    ServeResultReport) sits in JOURNALED_VERBS + IDEM_VERBS: a lease or
    result that vanishes across a master restart would double-decode or
    drop an in-flight inference request — the exact property `chaos
    serve-drain` pins end to end."""

    def test_serve_submit_ack_without_journal_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.ServeSubmitRequest):
                accepted = self.m.serve_queue.submit(payload.requests)
                return msg.ServeSubmitAck(accepted=accepted)
            return None
""")
        assert [f.checker for f in found] == ["journal-before-ack"]
        assert "ServeSubmitRequest" in found[0].message

    def test_serve_result_journal_without_idem_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.ServeResultReport):
                self.m.serve_queue.complete(payload.results)
                resp = msg.OkResponse()
                self._journal("serve_result", {"node_id": node_id})
                return resp
            return None
""")
        assert [f.checker for f in found] == ["idem-key-required"]
        assert "ServeResultReport" in found[0].message

    def test_serve_lease_journal_before_ack_with_idem_clean(self, tmp_path):
        # the in-tree servicer shape: the leased request ids are the
        # journal payload (replay re-assigns the EXACT set), idem + resp
        # ride the same frame
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _get(self, node_id, payload, idem=None):
            if isinstance(payload, msg.ServeLeaseRequest):
                leased = self.m.serve_queue.lease(
                    payload.node_id, payload.max_requests)
                resp = msg.ServeLease(requests=leased)
                self._journal("serve_lease",
                              {"node_id": payload.node_id,
                               "request_ids": [r.request_id
                                               for r in leased]},
                              idem=idem, resp=resp)
                return resp
            return None
""")
        assert found == []

    def test_serve_client_send_without_idem_flagged(self, tmp_path):
        found = _scan(tmp_path, "client.py", """\
            class Client:
                def submit_serve_requests(self, requests):
                    req = msg.ServeSubmitRequest(requests=requests)
                    return self._call_critical("report", req)
        """)
        assert [f.checker for f in found] == ["idem-key-required"]


# ------------------------------------------------- idem-key-required


class TestIdemKeyRequired:
    def test_servicer_journal_without_idem_flagged(self, tmp_path):
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.KVStoreAddRequest):
                num = self.m.kv_store.add(payload.key, payload.amount)
                resp = msg.KVStoreResponse(num=num)
                self._journal("kv_add", {"key": payload.key})
                return resp
            return None
""")
        assert [f.checker for f in found] == ["idem-key-required"]
        assert "KVStoreAddRequest" in found[0].message

    def test_client_send_without_idem_flagged(self, tmp_path):
        found = _scan(tmp_path, "client.py", """\
            class Client:
                def report_task_result(self, dataset, task_id):
                    req = msg.TaskResult(dataset_name=dataset,
                                         task_id=task_id)
                    return self._call_critical("report", req)
        """)
        assert [f.checker for f in found] == ["idem-key-required"]
        assert "idem=self._next_idem()" in found[0].message

    def test_threaded_end_to_end_clean(self, tmp_path):
        found = _scan(tmp_path, "client.py", """\
            class Client:
                def report_task_result(self, dataset, task_id):
                    req = msg.TaskResult(dataset_name=dataset,
                                         task_id=task_id)
                    return self._call_critical("report", req,
                                               idem=self._next_idem())
        """)
        found += _scan(tmp_path, "servicer.py",
                       _SERVICER_PREAMBLE + """\
        def _report(self, node_id, payload, idem=None):
            if isinstance(payload, msg.TaskResult):
                self.m.task_manager.report_dataset_task(node_id,
                                                        payload.task_id)
                resp = msg.OkResponse()
                self._journal("task_result", {"id": payload.task_id},
                              idem=idem, resp=resp)
                return resp
            return None
""")
        assert found == []


# --------------------------------------------- failover-frame durability


class TestFailoverDurability:
    """ISSUE 20: the promotion fence is only real if the ``failover``
    frame is DURABLE before the new epoch opens — an async append that
    never gates on the watermark could vanish in a crash and revive a
    corpse at an unfenced epoch."""

    def test_async_failover_append_flagged(self, tmp_path):
        found = _scan(tmp_path, "master.py", """\
            class JobMaster:
                def promote_to_leader(self):
                    self.journal.append_nowait(
                        "failover", {"new_epoch": self.epoch + 2})
                    self.epoch = self.journal.open_epoch()
        """)
        assert [f.checker for f in found] == ["journal-before-ack"]
        assert "failover" in found[0].message
        assert "wait_durable" in found[0].message

    def test_sync_failover_append_clean(self, tmp_path):
        found = _scan(tmp_path, "master.py", """\
            class JobMaster:
                def promote_to_leader(self):
                    self.journal.append(
                        "failover", {"new_epoch": self.epoch + 2})
                    self.epoch = self.journal.open_epoch()
        """)
        assert found == []

    def test_nowait_gated_on_watermark_clean(self, tmp_path):
        found = _scan(tmp_path, "master.py", """\
            class JobMaster:
                def promote_to_leader(self):
                    seq = self.journal.append_nowait(
                        "failover", {"new_epoch": self.epoch + 2})
                    self.journal.wait_durable(seq)
                    self.epoch = self.journal.open_epoch()
        """)
        assert found == []

    def test_fetch_journal_polling_never_journaled(self, tmp_path):
        """The shipping pull is POLLING class: a servicer branch that
        answers FetchJournalRequest WITHOUT journaling is the sanctioned
        shape (a fetch that journaled would feed the journal it ships —
        the verb is deliberately absent from JOURNALED_VERBS)."""
        from dlrover_wuqiong_tpu.analysis.protocol_engine import (
            IDEM_VERBS,
            JOURNALED_VERBS,
        )

        assert "FetchJournalRequest" not in JOURNALED_VERBS
        assert "FetchJournalRequest" not in IDEM_VERBS
        found = _scan(tmp_path, "servicer.py", _SERVICER_PREAMBLE + """\
        def _get(self, node_id, payload):
            if isinstance(payload, msg.FetchJournalRequest):
                snap, sseq, frames, durable = self.m.journal.fetch_batch(
                    payload.from_seq, payload.max_frames)
                return msg.FetchJournalResponse(frames=frames,
                                                durable_seq=durable)
            return None
""")
        assert found == []


# ------------------------------------------------------- commit-order


class TestCommitOrder:
    def test_marker_without_manifest_flagged(self, tmp_path):
        found = _scan(tmp_path, "saver.py", """\
            import os

            def commit(storage, step, sdir):
                storage.write(str(step), os.path.join(
                    sdir, CheckpointConstant.COMMIT_MARKER))
        """)
        assert [f.checker for f in found] == ["commit-order"]
        assert ".commit marker" in found[0].message

    def test_tracker_without_evidence_flagged(self, tmp_path):
        found = _scan(tmp_path, "saver.py", """\
            import os

            def publish(storage, step, path):
                storage.write(str(step), os.path.join(
                    path, CheckpointConstant.TRACKER_FILE))
        """)
        assert [f.checker for f in found] == ["commit-order"]
        assert "tracker" in found[0].message

    def test_full_commit_order_clean(self, tmp_path):
        found = _scan(tmp_path, "saver.py", """\
            import os

            def _write_step_manifest(storage, step, sdir):
                write_manifest(storage, sdir, {"step": step})

            def commit(storage, step, sdir, path):
                _write_step_manifest(storage, step, sdir)
                storage.write(str(step), os.path.join(
                    sdir, CheckpointConstant.COMMIT_MARKER))
                storage.write(str(step), os.path.join(
                    path, CheckpointConstant.TRACKER_FILE))
        """)
        assert found == []

    def test_tracker_repoint_after_verify_clean(self, tmp_path):
        # the engine.py self-heal shape: repointing the tracker at a
        # generation whose manifest was just read and verified is legal
        found = _scan(tmp_path, "engine.py", """\
            import os

            def repoint(storage, step, path):
                manifest = read_manifest(storage, step_dir(path, step))
                if manifest is None:
                    return
                storage.write(str(step), os.path.join(
                    path, CheckpointConstant.TRACKER_FILE))
        """)
        assert found == []


# ----------------------------------------------------- atomic-publish


class TestAtomicPublish:
    def test_raw_open_on_manifest_flagged(self, tmp_path):
        found = _scan(tmp_path, "saver.py", """\
            import os

            def publish(sdir, blob):
                with open(os.path.join(sdir, "manifest.json"), "w") as f:
                    f.write(blob)
        """)
        assert [f.checker for f in found] == ["atomic-publish"]

    def test_resolved_assignment_flagged(self, tmp_path):
        # the warm_pool.py shape this rule caught in-tree: the hint
        # lives in an assignment, not the open() call itself
        found = _scan(tmp_path, "pool.py", """\
            import os

            def publish(pool, key, blob):
                spec_path = os.path.join(pool, f"{key}.spec.json")
                with open(spec_path, "w") as f:
                    f.write(blob)
        """)
        assert [f.checker for f in found] == ["atomic-publish"]

    def test_write_tmp_then_rename_clean(self, tmp_path):
        found = _scan(tmp_path, "saver.py", """\
            import os

            def publish(sdir, blob):
                target = os.path.join(sdir, "manifest.json")
                tmp = f"{target}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    f.write(blob)
                os.replace(tmp, target)
        """)
        assert found == []

    def test_storage_write_helper_clean(self, tmp_path):
        found = _scan(tmp_path, "saver.py", """\
            import os

            def publish(storage, sdir, blob):
                storage.write(blob, os.path.join(sdir, "manifest.json"))
        """)
        assert found == []

    def test_unpublished_file_ignored(self, tmp_path):
        found = _scan(tmp_path, "notes.py", """\
            def dump(path, blob):
                with open(path, "w") as f:
                    f.write(blob)
        """)
        assert found == []


# ---------------------------------------------------------- lock-leak


class TestLockLeak:
    def test_release_outside_finally_flagged(self, tmp_path):
        found = _scan(tmp_path, "stage.py", """\
            def stage(shm_lock, payload):
                shm_lock.acquire(timeout=60)
                write(payload)
                shm_lock.release()
        """)
        assert [f.checker for f in found] == ["lock-leak"]
        assert "finally" in found[0].message

    def test_release_in_finally_clean(self, tmp_path):
        found = _scan(tmp_path, "stage.py", """\
            def stage(shm_lock, payload):
                acquired = shm_lock.acquire(timeout=60)
                try:
                    write(payload)
                finally:
                    if acquired:
                        shm_lock.release()
        """)
        assert found == []

    def test_non_lock_acquire_ignored(self, tmp_path):
        found = _scan(tmp_path, "pool.py", """\
            def take(semaphore):
                semaphore.acquire()
                return semaphore
        """)
        assert found == []

    def test_suppression_with_reason_honored(self, tmp_path):
        found = _scan(tmp_path, "drill.py", """\
            def die_holding(lock):
                lock.acquire(timeout=5)  # graftlint: disable=lock-leak -- drill: the leak is the scenario
                raise SystemExit(9)
        """)
        assert found == []


# ------------------------------------------------ suppression grammar


class TestSuppressionReasons:
    def test_reasonless_disable_flagged(self):
        # literal split so THIS file's raw-line scan doesn't match it
        lines = ["x = 1  # graftlint: " + "disable=lock-leak"]
        found = check_suppression_reasons("a.py", lines)
        assert [f.checker for f in found] == ["suppression-no-reason"]
        assert found[0].line == 1

    def test_reasoned_disable_clean(self):
        lines = ["x = 1  # graftlint: disable=lock-leak -- drill needs it"]
        assert check_suppression_reasons("a.py", lines) == []

    def test_reasonless_disable_still_suppresses(self, tmp_path):
        # additive migration: the old syntax keeps suppressing (the AST
        # engine reports the missing reason separately) so turning the
        # rule on cannot flip previously-suppressed findings back on.
        # The fixture's disable is assembled at runtime so this file's
        # own raw-line scan doesn't see a reason-less literal.
        found = _scan(tmp_path, "stage.py", (
            "def stage(lock):\n"
            "    lock.acquire()  # graftlint: " + "disable=lock-leak\n"))
        assert found == []


# ------------------------------------------------------- rule catalog


class TestRuleCatalog:
    def test_every_emitted_checker_is_cataloged(self):
        # engines may only emit rule ids the catalog documents
        for rule_id, entry in RULE_CATALOG.items():
            assert entry["engine"] in ("ast", "protocol", "concurrency",
                                       "schema", "jaxpr", "hlo")
            assert entry["severity"] in ("error", "warning")
            assert len(entry["rationale"]) > 20

    def test_finding_severity_defaults_from_catalog(self):
        f = Finding("budget-coverage", "msg")
        assert f.severity == "warning"
        g = Finding("lock-leak", "msg")
        assert g.severity == "error"
        assert summarize_severity([f, g]) == {"error": 1, "warning": 1}
        assert "warning" in f.format() and "error" in g.format()

    def test_readme_catalog_in_sync(self):
        # the README rule-catalog section must list every rule id
        readme = open(os.path.join(REPO_ROOT, "README.md")).read()
        for rule_id in RULE_CATALOG:
            assert f"`{rule_id}`" in readme, (
                f"README graftlint catalog is missing {rule_id}")


# ------------------------------------------------------- CLI surface


class TestCliV2:
    def test_json_schema_stable(self, tmp_path, capsys):
        """Downstream parsers pin this schema; keys are ADD-only."""
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--engine", "protocol", str(tmp_path)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1
        rec = json.loads(out[0])["graftlint"]
        assert set(rec) == {"engines", "files_scanned", "findings",
                            "by_checker", "by_severity",
                            "hlo_collectives", "elapsed_s", "ok"}
        assert isinstance(rec["engines"], list)
        assert isinstance(rec["files_scanned"], int)
        assert isinstance(rec["findings"], int)
        assert isinstance(rec["by_checker"], dict)
        assert isinstance(rec["by_severity"], dict)
        assert isinstance(rec["hlo_collectives"], dict)
        assert isinstance(rec["elapsed_s"], float)
        assert isinstance(rec["ok"], bool)

    def test_json_schema_section_when_schema_engine_runs(self, capsys):
        """ADD-only evolution: the ``schema`` key appears exactly when
        the schema engine ran, on top of the pinned base key set."""
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        rc = main(["--engine", "schema"])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1
        rec = json.loads(out[0])["graftlint"]
        assert set(rec) == {"engines", "files_scanned", "findings",
                            "by_checker", "by_severity",
                            "hlo_collectives", "elapsed_s", "ok",
                            "schema"}
        assert rec["engines"] == ["schema"]
        assert set(rec["schema"]) == {"surface", "lock"}
        assert rec["schema"]["lock"] == "ok"
        counts = rec["schema"]["surface"]
        assert counts["messages"] > 0 and counts["fields"] > 0
        assert set(counts["verbs"]) == {"journaled", "idem",
                                        "buffered", "polling"}

    def test_protocol_violation_rc1(self, tmp_path, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "stage.py").write_text(textwrap.dedent("""\
            def stage(lock):
                lock.acquire()
                lock.release()
            """))
        rc = main(["--engine", "protocol", str(tmp_path)])
        cap = capsys.readouterr()
        assert rc == 1
        rec = json.loads(cap.out.strip())["graftlint"]
        assert rec["by_checker"] == {"lock-leak": 1}
        assert rec["by_severity"] == {"error": 1}
        assert "stage.py:2" in cap.err

    def test_catalog_flag_single_json_line(self, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        rc = main(["--catalog"])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1
        cat = json.loads(out[0])["graftlint_catalog"]
        assert set(cat) == set(RULE_CATALOG)

    def test_changed_mode_skips_trace_engines(self, tmp_path, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--changed", str(tmp_path)])
        out = capsys.readouterr().out.strip()
        rec = json.loads(out)["graftlint"]
        assert rc == 0
        assert rec["engines"] == ["ast", "protocol", "concurrency",
                                  "schema"]  # no jaxpr/hlo

    def test_changed_paths_smoke(self):
        from dlrover_wuqiong_tpu.analysis.__main__ import _changed_paths

        got = _changed_paths()
        assert isinstance(got, list)
        assert all(p.endswith(".py") and os.path.exists(p) for p in got)

    def test_lint_wrapper_changed_mode(self):
        """tools/lint.py forwards --changed (the CI fast path)."""
        out = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "lint.py"),
             "--changed", "--engine", "protocol",
             os.path.join(REPO_ROOT, "tools", "lint.py")],
            capture_output=True, text=True, timeout=120)
        lines = out.stdout.strip().splitlines()
        assert len(lines) == 1
        rec = json.loads(lines[0])["graftlint"]
        assert rec["engines"] == ["protocol"]
        assert out.returncode == 0


# -------------------------------------------------- repo self-lint (t1)


class TestProtocolSelfLint:
    def test_protocol_engine_repo_clean(self):
        paths = [os.path.join(REPO_ROOT, p)
                 for p in ("dlrover_wuqiong_tpu", "tests", "examples",
                           "tools", "__graft_entry__.py")]
        findings, n_files = run_paths([p for p in paths
                                       if os.path.exists(p)])
        assert n_files > 100
        assert findings == [], "\n" + render_report(findings)
