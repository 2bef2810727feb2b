"""Perf observatory (telemetry/perf.py): executable keying, baseline
store durability, sentinel firing discipline, observatory self-limiting,
the BUFFERED latest-SENT-wins PerfSnapshotReport verb end to end
(master aggregation + /metrics gauges + the ONE op-profile source of
truth in diagnosis), policy decision-effect attribution, the flight
recorder embed, and the ADD-ONLY schema pins for every new surface.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from dlrover_wuqiong_tpu.common import messages as msg
from dlrover_wuqiong_tpu.telemetry import reset_ledger, reset_recorder
from dlrover_wuqiong_tpu.telemetry.perf import (
    PERF_EVENT_KEYS,
    PERF_SCHEMA,
    PERF_SNAPSHOT_KEYS,
    BaselineStore,
    PerfObservatory,
    RegressionSentinel,
    executable_key,
    latest_snapshot,
    reset_observatory,
    set_observatory,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    reset_ledger()
    reset_recorder()
    reset_observatory()
    yield
    reset_ledger()
    reset_recorder()
    reset_observatory()


def _windows(sentinel, store, key, values, coll_frac=0.3, start=0):
    """Drive observe+update the way the observatory does (beyond-bound
    windows stay out of the baseline); returns fired events."""
    fired = []
    for i, v in enumerate(values):
        cats = {"matmul": v * (1 - coll_frac), "collective": v * coll_frac}
        beyond, event = sentinel.observe(key, v, cats, step=start + i)
        if not beyond:
            store.update(key, v, cats)
        if event is not None:
            fired.append(event)
    return fired


# ----------------------------------------------------------- executable key


class TestExecutableKey:
    @pytest.mark.parametrize("other", [
        ("fp2", 8, "cpu"), ("fp", 4, "cpu"), ("fp", 8, "tpu")])
    def test_folds_identity(self, other):
        """Each of the three arguments is in the key; nothing else is."""
        base = executable_key("fp", 8, "cpu")
        assert base == executable_key("fp", 8, "cpu")  # deterministic
        assert executable_key(*other) != base
        assert executable_key(*other) == executable_key(*other)


# ------------------------------------------------------------ baseline store


class TestBaselineStore:
    def test_rolling_window_trims(self):
        st = BaselineStore()  # memory-only
        for i in range(100):
            st.update("k", float(i), {"matmul": float(i)})
        assert st.stats("k")["n"] == 64  # max_samples default
        # the oldest samples fell off: median over the surviving tail
        assert st.stats("k")["median"] > 60
        assert st.category_medians("k")["matmul"] > 60
        assert st.publish() is False  # no path → memory-only contract

    def test_atomic_publish_and_reload(self, tmp_path):
        path = str(tmp_path / "perf" / "baseline.json")
        st = BaselineStore(path)
        for v in (0.1, 0.11, 0.09):
            st.update("k", v, {"collective": v / 2})
        assert st.publish() is True
        assert not [n for n in os.listdir(tmp_path / "perf")
                    if ".tmp." in n], "tmp file leaked past os.replace"
        st2 = BaselineStore(path)
        assert st2.stats("k") == st.stats("k")
        assert st2.category_medians("k") == st.category_medians("k")

    def test_corrupt_baseline_relearned_not_fatal(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        with open(path, "w") as f:
            f.write('{"schema": 1, "keys": TORN')
        st = BaselineStore(path)
        assert st.stats("k") is None  # fresh, no crash
        st.update("k", 0.1)
        assert st.publish() is True
        assert json.load(open(path))["keys"]["k"]["step_s"] == [0.1]


# -------------------------------------------------------- regression sentinel


class TestRegressionSentinel:
    def test_quiet_noise_never_fires(self):
        import random

        st = BaselineStore()
        sen = RegressionSentinel(st, m_consecutive=3)
        rng = random.Random(7)
        vals = [0.1 * (1 + 0.1 * (rng.random() * 2 - 1))
                for _ in range(40)]  # the documented ±10% chip drift
        assert _windows(sen, st, "k", vals) == []

    def test_fires_exactly_once_at_m_consecutive(self):
        st = BaselineStore()
        sen = RegressionSentinel(st, m_consecutive=3)
        _windows(sen, st, "k", [0.1] * 8)
        fired = _windows(sen, st, "k", [0.16] * 7, coll_frac=0.6,
                         start=100)
        assert len(fired) == 1
        ev = fired[0]
        assert ev["kind"] == "perf-regression"
        assert ev["consecutive"] == 3
        assert ev["step"] == 102  # third beyond-bound window
        assert tuple(sorted(ev)) == tuple(sorted(PERF_EVENT_KEYS))

    def test_streak_resets_on_recovery(self):
        st = BaselineStore()
        sen = RegressionSentinel(st, m_consecutive=3)
        _windows(sen, st, "k", [0.1] * 8)
        # two slow, one normal, two slow: never 3 consecutive → no fire
        fired = _windows(sen, st, "k", [0.2, 0.2, 0.1, 0.2, 0.2],
                         start=100)
        assert fired == []

    def test_needs_min_baseline(self):
        st = BaselineStore()
        sen = RegressionSentinel(st, m_consecutive=1, min_baseline=5)
        # 4 samples then an excursion: below min_baseline → silent
        _windows(sen, st, "k", [0.1] * 4)
        assert _windows(sen, st, "k", [9.9], start=50) == []

    def test_attributes_the_moved_category(self):
        st = BaselineStore()
        sen = RegressionSentinel(st, m_consecutive=1)
        for _ in range(8):
            st.update("k", 0.1, {"matmul": 0.07, "collective": 0.03})
        _, ev = sen.observe(
            "k", 0.16, {"matmul": 0.07, "collective": 0.09}, step=9)
        assert ev is not None
        assert ev["category"] == "collective"
        assert ev["category_delta_s"] == pytest.approx(0.06)

    def test_regression_does_not_poison_baseline(self):
        st = BaselineStore()
        sen = RegressionSentinel(st, m_consecutive=3)
        _windows(sen, st, "k", [0.1] * 8)
        med_before = st.stats("k")["median"]
        _windows(sen, st, "k", [0.2] * 10, start=100)
        # sustained-slow windows are beyond bound → excluded: the old
        # normal survives and the NEXT excursion still measures against it
        assert st.stats("k")["median"] == med_before


# ------------------------------------------------------------- observatory


class TestPerfObservatory:
    def test_cadence_and_self_limit(self, tmp_path):
        obs = PerfObservatory(key="k", ckpt_dir=str(tmp_path), every=3)
        obs._t_start -= 1e6  # long-running job: overhead fully amortized
        opened = []
        for step in range(0, 90, 10):
            win = obs.maybe_open(step, 1)
            if win is not None:
                obs.close(win)
                opened.append(step)
        assert opened == [0, 30, 60]  # every 3rd eligible boundary
        # overhead beyond budget: next eligible boundary is SKIPPED and
        # accounted, not silently dropped
        obs._overhead_s = 1e9
        assert obs.maybe_open(90, 1) is None
        assert obs.snapshot()["windows"] == 3
        snap_skips = obs._skipped
        assert snap_skips == 1

    def test_snapshot_shape_and_ledger_credit(self, tmp_path):
        from dlrover_wuqiong_tpu.telemetry import get_ledger

        get_ledger().start()
        obs = PerfObservatory(key="k", ckpt_dir=str(tmp_path), every=1)
        win = obs.maybe_open(8, 4)
        assert win is not None
        snap = obs.close(win)
        assert tuple(sorted(snap)) == tuple(sorted(PERF_SNAPSHOT_KEYS))
        assert snap["schema"] == PERF_SCHEMA
        assert snap["fused_k"] == 4 and snap["step"] == 8
        # wire surface an older master still reads; nothing tunes
        assert snap["tuned_variant"] == ""
        assert snap["windows"] == 1
        # window overhead is ledger-credited to the "profile" state
        assert get_ledger().snapshot()["states"]["profile"] > 0.0
        # baseline landed on disk atomically
        assert os.path.isfile(
            os.path.join(str(tmp_path), "perf", "baseline.json"))
        assert latest_snapshot() is None  # singleton not set here
        set_observatory(obs)
        assert latest_snapshot() is snap

    def test_retrace_event_from_cache_miss_growth(self, tmp_path):
        from dlrover_wuqiong_tpu.auto.compile_cache import counters

        events = []
        obs = PerfObservatory(key="k", ckpt_dir=str(tmp_path), every=1,
                              on_event=events.append)
        obs._t_start -= 1e6  # long-running job: overhead fully amortized
        w = obs.maybe_open(0, 1)
        obs.close(w)  # first window: seeds the counter snapshot, no event
        assert events == []
        before = counters.misses
        try:
            counters.misses += 2  # a steady-state retrace storm
            w = obs.maybe_open(8, 1)
            obs.close(w)
        finally:
            counters.misses = before
        kinds = [e["kind"] for e in events]
        assert kinds == ["retrace"]
        assert events[0]["consecutive"] == 2  # miss delta
        assert events[0]["category"] == "compile"
        assert tuple(sorted(events[0])) == tuple(sorted(PERF_EVENT_KEYS))
        assert obs.snapshot()["retraces"] == 2

    def test_on_event_failure_never_propagates(self):
        def boom(event):
            raise RuntimeError("operator wiring bug")

        obs = PerfObservatory(key="k", every=1, on_event=boom)
        event = {k: 0 for k in PERF_EVENT_KEYS}
        event["kind"] = "perf-regression"
        obs._fire(dict(event))  # must not raise through the fire path
        assert obs._last_event["kind"] == "perf-regression"


# ----------------------------------------------- master round-trip + policy


class TestPerfVerbRoundTrip:
    def test_report_to_summary_metrics_and_diagnosis(self):
        """report_perf_snapshot → servicer → latest-SENT-wins aggregation
        → PerfSummary + dwt_perf_* gauges + the diagnosis op-profile
        store (ONE source of truth for the op-category split)."""
        from dlrover_wuqiong_tpu.agent.master_client import MasterClient
        from dlrover_wuqiong_tpu.master.master import JobMaster

        master = JobMaster(min_nodes=1, max_nodes=1)
        master.prepare()
        try:
            mc = MasterClient(master.addr, node_id=0)
            snap = {"schema": PERF_SCHEMA, "key": "k", "step": 80,
                    "step_time_s": 0.12, "baseline_median_s": 0.1,
                    "overhead_frac": 0.004, "regressions": 1,
                    "retraces": 2,
                    "categories": {"matmul": 0.08, "collective": 0.04},
                    "captured_at": time.time()}
            mc.report_perf_snapshot(snap)
            summary = mc.get_perf_summary()
            assert summary.nodes == 1
            assert summary.regressions == 1 and summary.retraces == 2
            assert summary.snapshots["0"]["step_time_s"] == \
                pytest.approx(0.12)
            rendered = master.metric_collector.reg.render()
            assert "dwt_perf_step_seconds" in rendered
            assert "dwt_perf_baseline_median_seconds" in rendered
            assert "dwt_perf_overhead_fraction" in rendered
            # satellite: the snapshot's category split IS the op-profile
            # evidence hang resolution reads — no second source of truth
            prof = master.diagnosis_manager.data.node_op_profile(0)
            assert prof is not None
            evidence = json.loads(prof)
            assert evidence["source"] == "perf_snapshot"
            assert evidence["categories"]["collective"] == \
                pytest.approx(0.04)
            assert tuple(sorted(evidence)) == tuple(sorted(
                master.diagnosis_manager.data.PERF_EVIDENCE_KEYS))
            mc.close()
        finally:
            master.stop()

    def test_latest_sent_wins_not_latest_received(self):
        """A delayed buffered flush must never clobber a fresher snapshot
        (the drain-ordering hazard every buffered verb shares)."""
        from dlrover_wuqiong_tpu.master.master import JobMaster

        master = JobMaster(min_nodes=1, max_nodes=1)
        # no prepare(): collect_perf is exercised in-process
        fresh = msg.PerfSnapshotReport(
            node_id=0, snapshot={"step": 100, "step_time_s": 0.1},
            sent_at=200.0)
        stale = msg.PerfSnapshotReport(
            node_id=0, snapshot={"step": 50, "step_time_s": 0.5},
            sent_at=100.0)
        master.collect_perf(fresh)
        master.collect_perf(stale)  # arrives later, SENT earlier
        assert master.perf_summary().snapshots["0"]["step"] == 100

    def test_policy_tick_feeds_observe_perf(self):
        """The master's policy loop hands the perf aggregation to the
        engine; decision_effect exposes measured before/after."""
        from dlrover_wuqiong_tpu.brain.policy import (
            PolicyConfig,
            PolicyEngine,
        )

        eng = PolicyEngine(PolicyConfig())
        eng.observe_perf({"step_time_s": {"0": 0.10}, "regressions": 0,
                          "retraces": 0, "nodes": 1})
        assert eng.decision_effect() == {}  # no decision yet
        d = eng.maybe_decide()
        assert d is not None
        assert eng.decision_effect() == {}  # before frozen, no after yet
        eng.observe_perf({"step_time_s": {"0": 0.16}, "regressions": 1,
                          "retraces": 0, "nodes": 1})
        effect = eng.decision_effect()
        assert effect["decision_id"] == d.decision_id
        assert effect["before"]["step_time_s"]["0"] == 0.10
        assert effect["after"]["regressions"] == 1

    def test_note_emitted_replay_does_not_double_freeze(self):
        """Journal replay routes the SAME decision through note_emitted;
        the before-side frozen at maybe_decide must survive."""
        from dlrover_wuqiong_tpu.brain.policy import (
            PolicyConfig,
            PolicyEngine,
        )

        eng = PolicyEngine(PolicyConfig())
        eng.observe_perf({"nodes": 1, "tag": "before"})
        d = eng.maybe_decide()
        eng.observe_perf({"nodes": 1, "tag": "after"})
        eng.note_emitted(d)  # master's _apply_policy path: same object
        assert eng.decision_effect()["before"]["tag"] == "before"


# -------------------------------------------------------- recorder + CLI


class TestFlightEmbedAndReportCli:
    def test_flight_dump_embeds_latest_snapshot(self, tmp_path):
        from dlrover_wuqiong_tpu.telemetry import (
            get_recorder,
            load_flight_dumps,
        )

        obs = PerfObservatory(key="k", every=1)
        obs._snapshot = {"schema": PERF_SCHEMA, "key": "k", "step": 8,
                         "step_time_s": 0.1}
        set_observatory(obs)
        get_recorder().record("mark", "m", {})
        assert get_recorder().flush(str(tmp_path), "test") is not None
        dumps = load_flight_dumps(str(tmp_path))
        assert dumps and dumps[0]["perf"]["step"] == 8

    def test_perf_report_baseline_and_rc_contract(self, tmp_path):
        st = BaselineStore(str(tmp_path / "perf" / "baseline.json"))
        for v in (0.1, 0.11, 0.09):
            st.update("kk", v, {"matmul": v})
        assert st.publish()
        cli = os.path.join(REPO, "tools", "perf_report.py")
        env = {k: v for k, v in os.environ.items()
               if k != "DWT_MASTER_ADDR"}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run(
            [sys.executable, cli, "--baseline", str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=120)
        assert p.returncode == 0, p.stdout + p.stderr
        lines = p.stdout.strip().splitlines()
        assert len(lines) == 1
        report = json.loads(lines[0])
        assert report["source"] == "baseline"
        assert report["keys"]["kk"]["n"] == 3
        assert report["keys"]["kk"]["median_s"] == pytest.approx(0.1)
        # live query with no address: rc=2 + error line
        p = subprocess.run([sys.executable, cli], capture_output=True,
                           text=True, env=env, timeout=120)
        assert p.returncode == 2
        assert "error" in json.loads(p.stdout)

    def test_perf_report_flight_mode(self, tmp_path):
        from dlrover_wuqiong_tpu.telemetry import get_recorder

        obs = PerfObservatory(key="k", every=1)
        obs._snapshot = {"schema": PERF_SCHEMA, "key": "k", "step": 8,
                         "step_time_s": 0.1, "regressions": 2,
                         "retraces": 1}
        set_observatory(obs)
        get_recorder().flush(str(tmp_path), "test")
        cli = os.path.join(REPO, "tools", "perf_report.py")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run(
            [sys.executable, cli, "--flight", str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=120)
        assert p.returncode == 0, p.stdout + p.stderr
        report = json.loads(p.stdout)
        assert report["source"] == "flight"
        assert report["nodes"] == 1
        assert report["regressions"] == 2 and report["retraces"] == 1
        (snap,) = report["snapshots"].values()
        assert snap["step"] == 8


# ------------------------------------------------------- compile counters


class TestCompileCacheMetricsExport:
    def test_listener_mirrors_into_registry(self):
        """Satellite: the XLA cache listeners export
        dwt_compile_cache_hits/misses through the shared MetricRegistry —
        the same stream counters.snapshot() feeds the retrace watcher."""
        import dlrover_wuqiong_tpu.auto.compile_cache as cc
        from dlrover_wuqiong_tpu.master.metrics import get_registry

        # reach the installed listeners exactly as jax monitoring does
        # (idempotent install — never register a duplicate pair, which
        # would double-count for the rest of the process)
        try:
            from jax._src import monitoring
        except ImportError:
            pytest.skip("jax monitoring API unavailable")
        before_h, before_m = cc.counters.snapshot()
        cc._install_listeners()
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/compile_time_saved_sec", 1.5)
        assert cc.counters.snapshot() == (before_h + 1, before_m + 1)
        assert cc.counters.time_saved_s >= 1.5
        rendered = get_registry().render()
        assert "dwt_compile_cache_hits" in rendered
        assert "dwt_compile_cache_misses" in rendered
        assert "dwt_compile_cache_time_saved_seconds" in rendered


# ------------------------------------------------------------ schema pins


class TestAddOnlySchemas:
    # ADD-ONLY: every consumer (flight dumps, PerfSnapshotReport,
    # tools/perf_report.py, incident timeline) keys into these dicts —
    # extend the tuples, never rename or remove members.  Pin source of
    # truth: the committed wire-surface lockfile (analysis/
    # schema.lock.json, gated by graftlint's schema engine) — only the
    # canaries are hand-pinned.  PERF_EVIDENCE_KEYS is a diagnosis-
    # internal surface (not on the wire), so it stays fully hand-pinned.
    PINNED_EVIDENCE = {"source", "step", "key", "step_time_s",
                       "categories"}

    def test_snapshot_keys_add_only(self, schema_lock):
        locked = set(schema_lock["registries"]["PERF_SNAPSHOT_KEYS"])
        assert locked.issubset(set(PERF_SNAPSHOT_KEYS))
        assert "step_time_s" in PERF_SNAPSHOT_KEYS   # hand-pinned canary

    def test_event_keys_add_only(self, schema_lock):
        locked = set(schema_lock["registries"]["PERF_EVENT_KEYS"])
        assert locked.issubset(set(PERF_EVENT_KEYS))
        assert "deviation" in PERF_EVENT_KEYS   # hand-pinned canary

    def test_diagnosis_evidence_keys_add_only(self):
        from dlrover_wuqiong_tpu.diagnosis.manager import (
            DiagnosisDataManager,
        )

        assert self.PINNED_EVIDENCE.issubset(
            set(DiagnosisDataManager.PERF_EVIDENCE_KEYS))

    def test_message_family_add_only(self):
        import dataclasses

        assert {"node_id", "snapshot", "sent_at"}.issubset(
            {f.name for f in dataclasses.fields(msg.PerfSnapshotReport)})
        assert {"snapshots", "regressions", "retraces", "nodes"}.issubset(
            {f.name for f in dataclasses.fields(msg.PerfSummary)})
        # PerfQuery stays constructible with no arguments forever
        msg.PerfQuery()

    def test_perf_verbs_buffered_never_journaled(self):
        """Protocol invariant: PerfSnapshotReport is pure telemetry —
        lossy by design, so it must stay OUT of the journaled/idempotent
        verb sets (a journaled perf stream would bloat replay)."""
        from dlrover_wuqiong_tpu.analysis.protocol_engine import (
            IDEM_VERBS,
            JOURNALED_VERBS,
        )

        assert "PerfSnapshotReport" not in JOURNALED_VERBS
        assert "PerfSnapshotReport" not in IDEM_VERBS

    def test_profile_state_in_ledger(self):
        from dlrover_wuqiong_tpu.telemetry import LEDGER_STATES

        assert "profile" in LEDGER_STATES


# ------------------------------------------------------ trainer integration


class TestTrainerWindows:
    def test_train_loop_opens_windows_and_publishes_baseline(
            self, tmp_path):
        """End to end on the real Trainer: windows open at logging
        boundaries (the boundary that carries the ONE readback), the
        snapshot folds the executable key, and the baseline store lands
        under $ckpt_dir/perf/."""
        import dataclasses

        import jax.numpy as jnp
        import numpy as np

        from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
        from dlrover_wuqiong_tpu.telemetry import get_ledger
        from dlrover_wuqiong_tpu.telemetry.perf import get_observatory
        from dlrover_wuqiong_tpu.trainer.trainer import (
            Trainer,
            TrainingArgs,
        )

        def data(step, batch=8, seq=32, vocab=512):
            rng = np.random.default_rng(step % 4)
            x = rng.integers(0, vocab, (batch, seq + 1))
            return {"input_ids": x[:, :-1], "labels": x[:, 1:]}

        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=6, seq_len=32,
            global_batch_size=8, warmup_steps=1, logging_steps=2,
            save_steps=0, save_on_exit=False, fused_steps=1,
            strategy=[("fsdp", {})], perf_window_every=1)
        model = GPT(dataclasses.replace(
            GPTConfig.nano(), dtype=jnp.float32,
            use_flash_attention=False, remat=False))
        tr = Trainer(model, args, data)
        try:
            tr.train()
        finally:
            tr.ckpt.close()
        obs = get_observatory()
        assert obs is tr._perf
        snap = obs.snapshot()
        assert snap is not None and snap["windows"] >= 1
        assert len(snap["key"]) == 16  # executable_key digest
        assert snap["fused_k"] == 1
        assert snap["step_time_s"] > 0.0
        assert os.path.isfile(os.path.join(
            str(tmp_path), "checkpoints", "perf", "baseline.json"))
        # window overhead was ledger-credited, never a new readback
        assert get_ledger().snapshot()["states"]["profile"] > 0.0
