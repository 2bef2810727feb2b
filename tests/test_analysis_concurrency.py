"""graftlint v3 concurrency engine (analysis/concurrency_engine.py).

One good + one bad fixture per rule (blocking-under-lock,
lock-order-cycle, unguarded-shared-state, thread-lifecycle), the two
historical-wedge regression fixtures (PR 1 sleep-under-SharedLock, PR 4
replica dial-under-lock — moving the dial back inside the lock span must
fail lint), the suppression grammar against the new rules, the SARIF
output contract, the catalog rows, and the tier-1 repo self-lint: the
concurrency engine over this tree must come back clean.  Pure AST work —
no jax device computation anywhere in this file.
"""

import json
import os
import textwrap

from dlrover_wuqiong_tpu.analysis.concurrency_engine import run_paths
from dlrover_wuqiong_tpu.analysis.findings import (
    RULE_CATALOG,
    check_suppression_reasons,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan(tmp_path, relpath, source, **kw):
    """Write one fixture file and run the concurrency engine over it."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings, _ = run_paths([str(tmp_path)], **kw)
    return findings


# ------------------------------------------------- blocking-under-lock


class TestBlockingUnderLock:
    def test_sleep_in_with_lock_flagged(self, tmp_path):
        # the PR 1 wedge shape: a wait inside a lock-held span means a
        # SIGKILLed holder wedges every waiter for the full timeout
        found = _scan(tmp_path, "stage.py", """\
            import time

            class Stager:
                def stage(self):
                    with self.shm_lock:
                        time.sleep(600)
            """)
        assert [f.checker for f in found] == ["blocking-under-lock"]
        assert "time.sleep" in found[0].message
        assert found[0].line == 6

    def test_rpc_in_acquire_span_flagged(self, tmp_path):
        found = _scan(tmp_path, "stage.py", """\
            class Stager:
                def stage(self):
                    ok = self.shm_lock.acquire(timeout=5)
                    try:
                        body = retry_call(self._dial)
                    finally:
                        if ok:
                            self.shm_lock.release()
                    return body
            """)
        assert [f.checker for f in found] == ["blocking-under-lock"]
        assert "retry_call" in found[0].message

    def test_blocking_after_release_clean(self, tmp_path):
        # copy under the lock, send after release — the sanctioned shape
        found = _scan(tmp_path, "stage.py", """\
            class Stager:
                def stage(self):
                    ok = self.shm_lock.acquire(timeout=5)
                    try:
                        payload = bytes(self._buf)
                    finally:
                        if ok:
                            self.shm_lock.release()
                    return retry_call(lambda: self._send(payload))
            """)
        assert found == []

    def test_transitive_dial_under_lock_flagged(self, tmp_path):
        # PR 4 regression fixture: checkpoint/replica.py's _segment_bytes
        # holds _seg_lock over the memory copy ONLY and backup() dials
        # AFTER release; moving the dial back inside the span must fail —
        # each call to a dead peer burned the full 150s RPC floor with
        # the staging lock held.
        found = _scan(tmp_path, "replica.py", """\
            import socket
            import threading

            class ReplicaManager:
                def __init__(self):
                    self._seg_lock = threading.Lock()

                def _rpc(self, addr, payload):
                    def dial():
                        return socket.create_connection(addr, timeout=5)
                    return retry_call(dial)

                def _segment_bytes(self):
                    ok = self._seg_lock.acquire(timeout=5)
                    try:
                        payload = bytes(self._buf)
                        return self._rpc(("peer", 1), payload)
                    finally:
                        if ok:
                            self._seg_lock.release()
            """)
        assert "blocking-under-lock" in [f.checker for f in found]
        msg = [f for f in found
               if f.checker == "blocking-under-lock"][0].message
        assert "_rpc" in msg and "_seg_lock" in msg

    def test_pr4_fixed_shape_clean(self, tmp_path):
        # the shipped replica.py shape: lock covers the copy, the dial
        # happens after — lint-clean by construction
        found = _scan(tmp_path, "replica.py", """\
            import socket
            import threading

            class ReplicaManager:
                def __init__(self):
                    self._seg_lock = threading.Lock()

                def _rpc(self, addr, payload):
                    def dial():
                        return socket.create_connection(addr, timeout=5)
                    return retry_call(dial)

                def _segment_bytes(self):
                    ok = self._seg_lock.acquire(timeout=5)
                    try:
                        return bytes(self._buf)
                    finally:
                        if ok:
                            self._seg_lock.release()

                def backup(self, addr):
                    payload = self._segment_bytes()
                    return self._rpc(addr, payload)
            """)
        assert found == []

    def test_subprocess_under_lock_flagged(self, tmp_path):
        found = _scan(tmp_path, "build.py", """\
            import subprocess

            def build(build_lock):
                with build_lock:
                    subprocess.run(["make"], check=True)
            """)
        assert [f.checker for f in found] == ["blocking-under-lock"]
        assert "subprocess" in found[0].message

    def test_lock_typed_attr_resolved_without_lock_name(self, tmp_path):
        # `self._meta = threading.Lock()` makes self._meta a lock even
        # though its name never says so (the SharedLock._meta shape)
        found = _scan(tmp_path, "svc.py", """\
            import threading
            import time

            class Svc:
                def __init__(self):
                    self._meta = threading.Lock()

                def poll(self):
                    with self._meta:
                        time.sleep(1)
            """)
        assert [f.checker for f in found] == ["blocking-under-lock"]
        assert "Svc._meta" in found[0].message


# --------------------------------------------------- lock-order-cycle


class TestLockOrderCycle:
    def test_abba_cycle_flagged(self, tmp_path):
        found = _scan(tmp_path, "mgr.py", """\
            import threading

            class Mgr:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def two(self):
                    with self._b_lock:
                        with self._a_lock:
                            pass
            """)
        assert [f.checker for f in found] == ["lock-order-cycle"]
        assert "Mgr._a_lock" in found[0].message
        assert "Mgr._b_lock" in found[0].message

    def test_consistent_order_clean(self, tmp_path):
        found = _scan(tmp_path, "mgr.py", """\
            import threading

            class Mgr:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def two(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass
            """)
        assert found == []

    def test_transitive_cycle_through_helper_flagged(self, tmp_path):
        # A held while calling a helper that takes B, plus a direct B->A
        # path elsewhere: the cycle spans functions, like the real code
        found = _scan(tmp_path, "mgr.py", """\
            import threading

            class Mgr:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        self._under_b()

                def _under_b(self):
                    with self._b_lock:
                        pass

                def two(self):
                    with self._b_lock:
                        with self._a_lock:
                            pass
            """)
        assert [f.checker for f in found] == ["lock-order-cycle"]

    def test_same_lock_reentry_not_an_edge(self, tmp_path):
        # self-edges are out of scope (RLock re-entry is legal); only
        # cycles between DISTINCT locks are ordering deadlocks
        found = _scan(tmp_path, "mgr.py", """\
            import threading

            class Mgr:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """)
        assert found == []


# ---------------------------------------------- unguarded-shared-state


class TestUnguardedSharedState:
    def test_write_write_race_flagged(self, tmp_path):
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def __init__(self):
                    self._count = 0
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)

                def _run(self):
                    self._count += 1

                def reset(self):
                    self._count = 0
            """)
        assert [f.checker for f in found] == ["unguarded-shared-state"]
        assert "self._count" in found[0].message
        assert "reset" in found[0].message

    def test_inconsistent_guard_flagged(self, tmp_path):
        # the reader holds a lock the worker write ignores — the lock
        # protects nothing
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._state = {}
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)

                def _run(self):
                    self._state = {"fresh": True}

                def snapshot(self):
                    with self._lock:
                        return dict(self._state)
            """)
        assert [f.checker for f in found] == ["unguarded-shared-state"]
        assert "does not hold" in found[0].message

    def test_both_sites_guarded_clean(self, tmp_path):
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._count = 0
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)

                def _run(self):
                    with self._lock:
                        self._count += 1

                def reset(self):
                    with self._lock:
                        self._count = 0
            """)
        assert found == []

    def test_worker_confined_private_helper_clean(self, tmp_path):
        # a private method called only from the worker runs on the
        # worker thread — its writes are same-thread (the ckpt_saver
        # _sync_shm_to_storage -> _update_shard_num shape)
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def __init__(self):
                    self._num = 0
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)

                def _run(self):
                    self._num = 1
                    self._apply(2)

                def _apply(self, n):
                    self._num = n
            """)
        assert found == []

    def test_join_synchronized_handoff_clean(self, tmp_path):
        # the engine._wait_drain shape: the reader joins the worker
        # before touching the handoff attribute — happens-before, not a
        # race
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def __init__(self):
                    self._err = None
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)

                def _run(self):
                    self._err = ValueError("boom")

                def wait(self):
                    self._t.join()
                    if self._err is not None:
                        err, self._err = self._err, None
                        raise err
            """)
        assert found == []


# --------------------------------------------------- thread-lifecycle


class TestThreadLifecycle:
    def test_fire_and_forget_nondaemon_flagged(self, tmp_path):
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def start(self):
                    threading.Thread(target=self._run).start()

                def _run(self):
                    pass
            """)
        assert [f.checker for f in found] == ["thread-lifecycle"]
        assert found[0].severity == "warning"

    def test_daemon_kwarg_clean(self, tmp_path):
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def start(self):
                    threading.Thread(target=self._run, daemon=True).start()

                def _run(self):
                    pass
            """)
        assert found == []

    def test_joined_on_stop_clean(self, tmp_path):
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def stop(self):
                    self._t.join(timeout=10)

                def _run(self):
                    pass
            """)
        assert found == []

    def test_daemon_attr_assign_clean(self, tmp_path):
        found = _scan(tmp_path, "svc.py", """\
            import threading

            class Svc:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.daemon = True
                    self._t.start()

                def _run(self):
                    pass
            """)
        assert found == []


# ------------------------------------------------ suppression grammar


class TestSuppressions:
    def test_reasoned_disable_silences(self, tmp_path):
        found = _scan(tmp_path, "drill.py", """\
            import time

            def drill(shm_lock):
                with shm_lock:
                    time.sleep(5)  # graftlint: disable=blocking-under-lock -- chaos lock-death drill: the wedge IS the fixture
            """)
        assert found == []

    def test_reasonless_disable_still_suppresses_but_reported(self,
                                                              tmp_path):
        # additive migration contract shared with the other engines: a
        # reason-less disable keeps suppressing, and the AST engine's
        # suppression-reason pass reports the missing reason.  The
        # fixture's disable is assembled at runtime so this file's own
        # raw-line scan doesn't see a reason-less literal.
        src = ("import time\n"
               "def drill(shm_lock):\n"
               "    with shm_lock:\n"
               "        time.sleep(5)  # graftlint: "
               + "disable=blocking-under-lock\n")
        path = tmp_path / "drill.py"
        path.write_text(src)
        found, _ = run_paths([str(tmp_path)])
        assert found == []
        reasons = check_suppression_reasons("drill.py", src.splitlines())
        assert [f.checker for f in reasons] == ["suppression-no-reason"]

    def test_unrelated_disable_does_not_silence(self, tmp_path):
        found = _scan(tmp_path, "drill.py", """\
            import time

            def drill(shm_lock):
                with shm_lock:
                    time.sleep(5)  # graftlint: disable=lock-leak -- wrong rule id on purpose
            """)
        assert [f.checker for f in found] == ["blocking-under-lock"]


# ------------------------------------------------- catalog + CLI + sarif


class TestCatalogAndCli:
    CONCURRENCY_RULES = ("blocking-under-lock", "lock-order-cycle",
                         "unguarded-shared-state", "thread-lifecycle")

    def test_four_rules_cataloged(self):
        for rid in self.CONCURRENCY_RULES:
            entry = RULE_CATALOG[rid]
            assert entry["engine"] == "concurrency"
            assert entry["severity"] in ("error", "warning")
            assert len(entry["rationale"]) > 20

    def test_readme_documents_engine_and_wedges(self):
        readme = open(os.path.join(REPO_ROOT, "README.md")).read()
        for rid in self.CONCURRENCY_RULES:
            assert f"`{rid}`" in readme
        assert "Concurrency discipline" in readme
        # the two motivating historical wedges must stay named
        assert "SAVE_TIMEOUT" in readme
        assert "dial" in readme.lower()

    def test_cli_engine_concurrency(self, tmp_path, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--engine", "concurrency", str(tmp_path)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1
        rec = json.loads(out[0])["graftlint"]
        assert rec["engines"] == ["concurrency"]
        assert rec["ok"] is True

    def test_cli_violation_rc1(self, tmp_path, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "bad.py").write_text(textwrap.dedent("""\
            import time

            def drill(shm_lock):
                with shm_lock:
                    time.sleep(5)
            """))
        rc = main(["--engine", "concurrency", str(tmp_path)])
        cap = capsys.readouterr()
        assert rc == 1
        rec = json.loads(cap.out.strip())["graftlint"]
        assert rec["by_checker"] == {"blocking-under-lock": 1}
        assert "bad.py:5" in cap.err


class TestSarifOutput:
    def test_sarif_contract(self, tmp_path, capsys):
        """--format sarif: one line, SARIF 2.1.0, findings as results."""
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "bad.py").write_text(textwrap.dedent("""\
            import time

            def drill(shm_lock):
                with shm_lock:
                    time.sleep(5)
            """))
        rc = main(["--engine", "concurrency", "--format", "sarif",
                   str(tmp_path)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 1 and len(out) == 1
        doc = json.loads(out[0])
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "graftlint"
        rules = {r["id"]: r for r in run["tool"]["driver"]["rules"]}
        assert rules["blocking-under-lock"][
            "defaultConfiguration"]["level"] == "error"
        res = run["results"][0]
        assert res["ruleId"] == "blocking-under-lock"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("bad.py")
        assert loc["region"]["startLine"] == 5

    def test_sarif_clean_run(self, tmp_path, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--engine", "concurrency", "--format", "sarif",
                   str(tmp_path)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0 and len(out) == 1
        doc = json.loads(out[0])
        assert doc["runs"][0]["results"] == []
        assert doc["runs"][0]["tool"]["driver"]["rules"] == []


# -------------------------------------------------- repo self-lint (t1)


class TestConcurrencySelfLint:
    def test_concurrency_engine_repo_clean(self):
        paths = [os.path.join(REPO_ROOT, p)
                 for p in ("dlrover_wuqiong_tpu", "tests", "examples",
                           "tools", "__graft_entry__.py")]
        findings, n_files = run_paths([p for p in paths
                                       if os.path.exists(p)])
        assert findings == [], "\n".join(f.format() for f in findings)
        assert n_files > 100
