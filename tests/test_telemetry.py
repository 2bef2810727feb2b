"""Unified telemetry: goodput ledger, trace spans, flight recorder.

Pins the ADD-ONLY schemas (LEDGER_STATES, ledger snapshot keys, flight
dump envelope keys), the attribution-total invariant (states + other ==
wall), cross-process trace propagation over the real RPC path, the
master-side goodput aggregation (report → servicer → summary →
/metrics), and the tools/goodput_report.py offline CLI.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dlrover_wuqiong_tpu.telemetry import (
    FLIGHT_SCHEMA_VERSION,
    LEDGER_SCHEMA_VERSION,
    LEDGER_STATES,
    SPAN_SCHEMA_VERSION,
    FlightRecorder,
    GoodputLedger,
    get_ledger,
    get_recorder,
    load_flight_dumps,
    reset_ledger,
    reset_recorder,
)
from dlrover_wuqiong_tpu.telemetry import spans as tspans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Singletons are process-global — every test gets clean ones."""
    reset_ledger()
    reset_recorder()
    tspans.clear_spans()
    yield
    reset_ledger()
    reset_recorder()
    tspans.clear_spans()


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------------ ledger


class TestGoodputLedger:
    # ADD-ONLY: every locked name must stay forever (master aggregation,
    # /metrics labels, goodput_report and the chaos drills key on them);
    # new states append, never rename.  The pin source of truth is the
    # committed wire-surface lockfile (analysis/schema.lock.json, gated
    # by graftlint's schema engine) — only the canary is hand-pinned.
    def test_states_schema_add_only(self, schema_lock):
        locked = schema_lock["registries"]["LEDGER_STATES"]
        missing = set(locked) - set(LEDGER_STATES)
        assert not missing, f"removed ledger state(s) {missing}"
        assert "productive" in LEDGER_STATES   # hand-pinned canary
        assert LEDGER_SCHEMA_VERSION >= 1

    def test_snapshot_keys_add_only(self):
        led = GoodputLedger()
        snap = led.snapshot()
        for key in ("schema", "wall_s", "states", "other_s",
                    "goodput_fraction", "started_wall"):
            assert key in snap, f"removed snapshot key {key!r}"
        assert set(snap["states"]) == set(LEDGER_STATES)

    def test_attribution_is_total(self):
        clk = _FakeClock()
        led = GoodputLedger(clock=clk)
        led.start()
        with led.window("productive"):
            clk.t += 6.0
        with led.window("compile"):
            clk.t += 3.0
        clk.t += 1.0  # uncredited second -> residual
        snap = led.snapshot()
        assert snap["wall_s"] == pytest.approx(10.0)
        assert snap["states"]["productive"] == pytest.approx(6.0)
        assert snap["states"]["compile"] == pytest.approx(3.0)
        # states + other == wall BY CONSTRUCTION (other is computed)
        assert snap["other_s"] == pytest.approx(1.0)
        assert sum(snap["states"].values()) + snap["other_s"] == \
            pytest.approx(snap["wall_s"])
        assert snap["goodput_fraction"] == pytest.approx(0.6)

    def test_overcredit_never_goes_negative(self):
        # concurrent windows (saver thread + train loop) can credit more
        # than wall — the residual clamps at 0 and the fraction uses the
        # larger of (wall, credited) so it stays <= 1
        clk = _FakeClock()
        led = GoodputLedger(clock=clk)
        led.start()
        led.account("productive", 5.0)
        led.account("ckpt_persist", 5.0)
        clk.t += 4.0
        snap = led.snapshot()
        assert snap["other_s"] == 0.0
        assert 0.0 <= snap["goodput_fraction"] <= 1.0

    def test_unknown_state_raises(self):
        led = GoodputLedger()
        with pytest.raises(ValueError, match="add-only"):
            led.account("coffee_break", 1.0)

    def test_nonpositive_credit_ignored(self):
        led = GoodputLedger()
        led.account("productive", 0.0)
        led.account("productive", -3.0)
        assert led.snapshot()["states"]["productive"] == 0.0

    def test_start_idempotent_and_singleton_reset(self):
        led = get_ledger()
        assert led is get_ledger()
        led.start()
        w0 = led.snapshot()["started_wall"]
        time.sleep(0.01)
        led.start()  # first call wins
        assert led.snapshot()["started_wall"] == w0
        assert reset_ledger() is not led

    def test_thread_safety_under_concurrent_credits(self):
        led = GoodputLedger()

        def credit():
            for _ in range(500):
                led.account("productive", 0.001)

        threads = [threading.Thread(target=credit) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert led.snapshot()["states"]["productive"] == \
            pytest.approx(2.0, rel=1e-6)


# ------------------------------------------------------------------- spans


class TestSpans:
    def test_nesting_links_parent_child(self):
        with tspans.span("outer") as outer:
            with tspans.span("inner") as inner:
                pass
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_span"] == outer["span_id"]
        assert outer["parent_span"] == ""
        names = [s["name"] for s in tspans.spans_snapshot()]
        assert names[-2:] == ["inner", "outer"]  # closed innermost-first
        assert outer["schema"] == SPAN_SCHEMA_VERSION
        assert outer["dur_s"] >= inner["dur_s"] >= 0.0

    def test_error_status_on_raise(self):
        with pytest.raises(RuntimeError):
            with tspans.span("boom"):
                raise RuntimeError("x")
        rec = tspans.spans_snapshot()[-1]
        assert rec["name"] == "boom" and rec["status"] == "error"

    def test_extract_adopts_incoming_frame_context(self):
        incoming = {"trace_id": "t" * 16, "span_id": "s" * 16}
        with tspans.extract(incoming):
            with tspans.span("serve:op") as rec:
                pass
        assert rec["trace_id"] == incoming["trace_id"]
        assert rec["parent_span"] == incoming["span_id"]
        # stack restored: a new span outside starts a fresh trace
        with tspans.span("fresh") as rec2:
            pass
        assert rec2["trace_id"] != incoming["trace_id"]

    def test_env_context_propagates_to_spawned_child(self, monkeypatch):
        with tspans.span("parent") as parent:
            with tspans.env_context() as env:
                assert env["DWT_TRACE_ID"] == parent["trace_id"]
                assert env["DWT_TRACE_PARENT"] == parent["span_id"]
                child_env = dict(env)
        # simulate the spawned child: fresh thread (fresh TLS stack)
        # with the inherited env — its first span joins the trace
        monkeypatch.setenv("DWT_TRACE_ID", child_env["DWT_TRACE_ID"])
        monkeypatch.setenv("DWT_TRACE_PARENT",
                           child_env["DWT_TRACE_PARENT"])
        out = {}

        def child():
            with tspans.span("child-op") as rec:
                out.update(rec)

        t = threading.Thread(target=child)
        t.start()
        t.join()
        assert out["trace_id"] == parent["trace_id"]
        assert out["parent_span"] == parent["span_id"]

    def test_spans_are_flight_recorder_events(self):
        tspans.span_event("mark", {"k": 1})
        kinds = [(e["kind"], e["name"])
                 for e in get_recorder().snapshot()]
        assert ("span", "mark") in kinds

    def test_chrome_trace_dump(self, tmp_path):
        with tspans.span("a"):
            tspans.span_event("b")
        path = str(tmp_path / "trace.json")
        n = tspans.dump_chrome_trace(path)
        assert n >= 2
        data = json.loads(open(path).read())
        evt = data["traceEvents"][0]
        for key in ("name", "cat", "ph", "ts", "dur", "pid", "args"):
            assert key in evt


class TestHotSpans:
    """The per-step ring (PR 24): light records on the monotonic clock
    that never reach the flight recorder and evict no full span."""

    def test_every_span_carries_a_monotonic_start(self):
        t0 = time.monotonic()
        with tspans.span("full") as rec:
            with tspans.hot_span("light"):
                pass
        t1 = time.monotonic()
        assert t0 <= rec["t_mono"] <= t1 and rec["t_wall"] > 0
        (hot,) = tspans.hot_spans_snapshot()
        assert set(hot) == {"name", "t_mono", "dur_s", "span_id",
                            "parent_span", "trace_id", "tid"}
        assert rec["t_mono"] <= hot["t_mono"] <= t1
        assert hot["t_mono"] + hot["dur_s"] <= \
            rec["t_mono"] + rec["dur_s"]

    def test_annotation_is_harmless_with_no_profiler_running(self):
        import jax  # noqa: F401 — imported: the annotation path is live

        with tspans.hot_span("outer"):
            with tspans.span("inner"):
                tspans.span_event("mark")
        with pytest.raises(KeyError):
            with tspans.hot_span("raises"):
                raise KeyError("x")
        # the stack unwound: a later span starts a fresh trace
        assert tspans.current_trace() is None
        assert [s["name"] for s in tspans.hot_spans_snapshot()] == \
            ["outer", "raises"]

    def test_no_jax_is_imported_for_an_annotation(self):
        """The agent and the master record spans and never load JAX."""
        code = ("import sys\n"
                "from dlrover_wuqiong_tpu.telemetry import spans\n"
                "with spans.span('a'):\n"
                "    with spans.hot_span('b'):\n"
                "        pass\n"
                "assert 'jax' not in sys.modules, 'jax was imported'\n"
                "print(len(spans.spans_snapshot()),"
                " len(spans.hot_spans_snapshot()))\n")
        out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["1", "1"]

    def test_ten_thousand_iterations_evict_nothing_else(self):
        with tspans.span("trainer:build"):
            tspans.span_event("accelerate:init_state")
        with tspans.span("rpc:report_heartbeat"):
            pass
        full_before = [s["span_id"] for s in tspans.spans_snapshot()]
        ring_before = len(get_recorder())
        for _ in range(10_000):
            with tspans.hot_span("trainer:iteration"):
                with tspans.hot_span("trainer:data"):
                    pass
                with tspans.hot_span("trainer:dispatch"):
                    pass
        assert [s["span_id"] for s in tspans.spans_snapshot()] == \
            full_before
        # not one of them went through the flight recorder's ring
        assert len(get_recorder()) == ring_before
        assert not any(e["name"].startswith("trainer:iter")
                       for e in get_recorder().snapshot())
        hot = tspans.hot_spans_snapshot()
        assert 0 < len(hot) <= tspans._MAX_HOT_SPANS  # bounded
        assert hot[-1]["name"] == "trainer:iteration"

    def test_parent_ids_cross_the_pump_thread(self):
        """What the Trainer does at a logging boundary: the job carries
        the submitting span's context, the pump adopts it."""
        seen = {}

        def pump(trace):
            with tspans.extract(trace):
                with tspans.hot_span("pump:readback"):
                    with tspans.span("perf:window") as rec:
                        seen.update(rec)

        with tspans.hot_span("trainer:iteration"):
            with tspans.hot_span("trainer:log_submit"):
                t = threading.Thread(target=pump,
                                     args=(tspans.current_trace(),))
                t.start()
                t.join()
        by_name = {s["name"]: s for s in tspans.hot_spans_snapshot()}
        it, sub, rb = (by_name[n] for n in (
            "trainer:iteration", "trainer:log_submit", "pump:readback"))
        assert sub["parent_span"] == it["span_id"]
        assert rb["parent_span"] == sub["span_id"]
        assert rb["tid"] != it["tid"]
        assert it["trace_id"] == sub["trace_id"] == rb["trace_id"] == \
            seen["trace_id"]
        assert seen["parent_span"] == rb["span_id"]  # full under hot

    def test_chrome_dump_carries_the_per_step_ring(self, tmp_path):
        with tspans.hot_span("trainer:iteration"):
            pass
        path = str(tmp_path / "trace.json")
        assert tspans.dump_chrome_trace(path) == 1
        (evt,) = json.loads(open(path).read())["traceEvents"]
        assert evt["name"] == "trainer:iteration"
        assert evt["ts"] / 1e6 == pytest.approx(time.time(), abs=60)  # wall
        assert tspans.dump_chrome_trace(path, include_buffer=False) == 0


# ---------------------------------------------------------------- recorder


class TestFlightRecorder:
    def test_ring_is_bounded_drop_oldest(self):
        rec = FlightRecorder(max_events=4)
        for i in range(10):
            rec.record("mark", f"e{i}")
        events = rec.snapshot()
        assert len(events) == 4
        assert [e["name"] for e in events] == ["e6", "e7", "e8", "e9"]

    def test_flush_and_load_roundtrip(self, tmp_path):
        get_ledger().account("productive", 1.5)
        rec = get_recorder()
        rec.record("mark", "hello", {"x": 1})
        path = rec.flush(str(tmp_path), "fault")
        assert path and os.path.exists(path)
        dumps = load_flight_dumps(str(tmp_path))
        assert len(dumps) == 1
        dump = dumps[0]
        # ADD-ONLY envelope (tools/goodput_report.py --flight keys on it)
        for key in ("schema", "role", "pid", "reason", "flushed_at",
                    "ledger", "events"):
            assert key in dump, f"removed flight-dump key {key!r}"
        assert dump["schema"] == FLIGHT_SCHEMA_VERSION
        assert dump["reason"] == "fault"
        assert dump["pid"] == os.getpid()
        assert dump["ledger"]["states"]["productive"] == \
            pytest.approx(1.5)
        evt = [e for e in dump["events"] if e["name"] == "hello"][0]
        for key in ("t_wall", "kind", "name", "data"):
            assert key in evt
        assert evt["data"] == {"x": 1}

    def test_flush_sequence_keeps_all_dumps(self, tmp_path):
        rec = get_recorder()
        rec.record("mark", "a")
        p1 = rec.flush(str(tmp_path), "fault")
        p2 = rec.flush(str(tmp_path), "sigterm")
        assert p1 != p2
        reasons = [d["reason"] for d in load_flight_dumps(str(tmp_path))]
        assert reasons == ["fault", "sigterm"]

    def test_flush_never_raises(self, tmp_path):
        assert get_recorder().flush("", "fault") is None
        blocker = tmp_path / "f"
        blocker.write_text("not a dir")
        # flight dir creation fails (parent is a file) -> swallowed
        assert get_recorder().flush(str(blocker), "fault") is None


# -------------------------------------------- rpc trace + goodput flow


class TestRpcTraceAndGoodput:
    def test_goodput_report_to_summary_and_metrics(self):
        """report_goodput_ledger → servicer → latest-wins aggregation →
        GoodputSummary + dwt_goodput_* gauges on the master registry."""
        from dlrover_wuqiong_tpu.agent.master_client import MasterClient
        from dlrover_wuqiong_tpu.master.master import JobMaster

        master = JobMaster(min_nodes=1, max_nodes=1)
        master.prepare()
        try:
            mc = MasterClient(master.addr, node_id=0)
            led = reset_ledger()
            led.account("productive", 8.0)
            led.account("compile", 2.0)
            mc.report_goodput_ledger(led.snapshot())
            # cumulative resend: latest-wins, NOT double counted
            led.account("productive", 2.0)
            mc.report_goodput_ledger(led.snapshot())
            summary = mc.get_goodput_summary()
            assert summary.nodes == 1
            assert summary.states["productive"] == pytest.approx(10.0)
            assert summary.states["compile"] == pytest.approx(2.0)
            assert 0.0 < summary.goodput_fraction <= 1.0
            rendered = master.metric_collector.reg.render()
            assert "dwt_goodput_seconds" in rendered
            assert 'state="productive"' in rendered
            assert "dwt_goodput_fraction" in rendered
            mc.close()
        finally:
            master.stop()

    def test_trace_tree_spans_client_and_servicer(self):
        """One client operation under a root span produces rpc:<verb>
        (client thread) and serve:<verb> (servicer thread) spans sharing
        ONE trace_id, with serve parented under rpc — the cross-process
        propagation path, exercised over a real socket."""
        from dlrover_wuqiong_tpu.agent.master_client import MasterClient
        from dlrover_wuqiong_tpu.master.master import JobMaster

        master = JobMaster(min_nodes=1, max_nodes=1)
        master.prepare()
        try:
            mc = MasterClient(master.addr, node_id=0)
            with tspans.span("restore:drill") as root:
                mc.kv_store_set("tk", b"tv")
            assert mc.kv_store_get("tk") == b"tv"
            mc.close()
        finally:
            master.stop()
        spans = tspans.spans_snapshot()
        rpc = [s for s in spans if s["name"].startswith("rpc:")
               and s["trace_id"] == root["trace_id"]]
        assert rpc, [s["name"] for s in spans]
        assert rpc[0]["parent_span"] == root["span_id"]
        serve = [s for s in spans if s["name"].startswith("serve:")
                 and s["trace_id"] == root["trace_id"]]
        assert serve, [s["name"] for s in spans]
        rpc_ids = {s["span_id"] for s in rpc}
        assert serve[0]["parent_span"] in rpc_ids

    def test_goodput_report_cli_flight_mode(self, tmp_path):
        """tools/goodput_report.py --flight: one JSON line summarizing
        the dumps' latest-per-process ledgers and span counts."""
        led = get_ledger()
        led.account("productive", 4.0)
        led.account("restore_storage", 1.0)
        with tspans.span("ckpt:restore"):
            pass
        get_recorder().flush(str(tmp_path), "fault")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "goodput_report.py"),
             "--flight", str(tmp_path)],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        assert len(lines) == 1
        report = json.loads(lines[0])
        assert report["source"] == "flight"
        assert report["dumps"] == 1 and report["nodes"] == 1
        assert report["states"]["productive"] == pytest.approx(4.0)
        assert report["states"]["restore_storage"] == pytest.approx(1.0)
        assert 0.0 < report["goodput_fraction"] < 1.0
        assert report["spans"] >= 1 and report["traces"] >= 1

    def test_goodput_report_cli_no_address_fails_cleanly(self):
        env = dict(os.environ)
        env.pop("DWT_MASTER_ADDR", None)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "goodput_report.py")],
            capture_output=True, text=True, timeout=60, cwd=REPO,
            env=env)
        assert proc.returncode == 2
        assert "error" in json.loads(proc.stdout.strip())
