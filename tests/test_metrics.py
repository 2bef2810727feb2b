"""Observability tests: metric registry, Prometheus endpoint, collector,
step profiler wiring, agent resource monitor.

Mirrors reference `master/stats` tests + the xpu_timer Prometheus intent.
"""

import re
import sys
import types
import urllib.error
import urllib.request

import pytest

from dlrover_wuqiong_tpu.master.metrics import (
    JobMetricCollector,
    MetricRegistry,
    PrometheusExporter,
)
from dlrover_wuqiong_tpu.utils.profiler import StepProfiler


class TestMetricRegistry:
    def test_gauge_counter_histogram(self):
        reg = MetricRegistry()
        reg.gauge("g", 1.5, {"job": "j"})
        reg.inc("c", 2.0)
        reg.inc("c", 3.0)
        for v in (1.0, 2.0, 3.0, 4.0):
            reg.observe("h", v)
        assert reg.get_gauge("g", {"job": "j"}) == 1.5
        assert reg.get_counter("c") == 5.0
        text = reg.render()
        assert 'g{job="j"} 1.5' in text
        assert "c_total 5.0" in text
        assert "h_count 4" in text
        assert 'le="+Inf"' in text

    def test_label_value_escaping(self):
        # exposition format: backslash first, then quote, then newline —
        # a scraper must get one parseable line per series
        reg = MetricRegistry()
        reg.gauge("g", 1.0, {"path": 'C:\\tmp', "msg": 'say "hi"\nbye'})
        text = reg.render()
        assert 'path="C:\\\\tmp"' in text
        assert 'msg="say \\"hi\\"\\nbye"' in text
        line = [ln for ln in text.splitlines() if ln.startswith("g{")][0]
        assert "\n" not in line  # the newline is escaped, not emitted

    def test_counter_is_monotonic(self):
        reg = MetricRegistry()
        vals = []
        for _ in range(5):
            reg.inc("c", 1.0, {"job": "j"})
            vals.append(reg.get_counter("c", {"job": "j"}))
        assert vals == sorted(vals) and vals[-1] == 5.0
        # negative increments would break scrape-side rate(): the
        # registry exposes inc() only, so going down requires a caller
        # bug — pin that counters never render a lower value than before
        before = reg.render()
        reg.inc("c", 0.0, {"job": "j"})
        assert reg.get_counter("c", {"job": "j"}) == 5.0
        assert 'c_total{job="j"} 5.0' in before

    def test_histogram_buckets_cumulative_and_closed(self):
        reg = MetricRegistry()
        for v in (0.004, 0.004, 0.02, 0.2, 100.0):
            reg.observe("h", v, buckets=(0.005, 0.05, 0.5))
        text = reg.render()
        counts = [int(m) for m in re.findall(
            r'h_bucket\{le="[^"]*"\} (\d+)', text)]
        # one count per bound + the mandatory +Inf closure
        assert len(counts) == 4
        assert counts == sorted(counts), "buckets must be cumulative"
        assert counts == [2, 3, 4, 5]
        assert 'h_bucket{le="+Inf"} 5' in text
        assert "h_count 5" in text
        # le label values parse as floats (repr, not locale-formatted)
        for le in re.findall(r'h_bucket\{le="([^"]*)"\}', text):
            assert le == "+Inf" or float(le) > 0

    def test_collector_surfaces(self):
        reg = MetricRegistry()
        col = JobMetricCollector("jobx", registry=reg)
        col.collect_global_step(42)
        col.collect_speed(1.25, tokens_per_sec=1e5)
        col.collect_node_resource(0, cpu=2.0, memory_mb=512)
        col.collect_ckpt_timing("blocking", 0.05)
        col.collect_node_event("relaunch")
        text = reg.render()
        assert 'dwt_job_global_step{job="jobx"} 42' in text
        assert "dwt_job_tokens_per_second" in text
        assert "dwt_node_memory_mb" in text
        assert "dwt_ckpt_seconds" in text
        assert "dwt_node_events_total" in text


class TestPrometheusExporter:
    def test_http_scrape(self):
        reg = MetricRegistry()
        reg.gauge("dwt_up", 1.0)
        exp = PrometheusExporter(port=0, registry=reg)
        exp.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics", timeout=5
            ).read().decode()
            assert "dwt_up 1.0" in body
        finally:
            exp.stop()

    def test_scrape_carries_escaped_labels_and_types(self):
        reg = MetricRegistry()
        reg.gauge("dwt_g", 2.0, {"node": 'a"b'})
        reg.inc("dwt_c", 3.0)
        reg.observe("dwt_h", 0.01)
        exp = PrometheusExporter(port=0, registry=reg)
        exp.start()
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics", timeout=5)
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
            assert 'dwt_g{node="a\\"b"} 2.0' in body
            assert "# TYPE dwt_c counter" in body
            assert "dwt_c_total 3.0" in body
            assert "# TYPE dwt_h histogram" in body
            assert 'dwt_h_bucket{le="+Inf"} 1' in body
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{exp.port}/nope", timeout=5)
            assert ei.value.code == 404
        finally:
            exp.stop()


class TestStepProfiler:
    def test_steps_without_a_window_trace_nothing(self):
        prof = StepProfiler()
        for step in range(3):
            with prof.step(step):
                assert not prof.closes_at(step)
        assert not prof._tracing and prof.last_profile is None

    def test_trace_window(self, tmp_path):
        # trace start/stop around the window without error (CPU backend)
        prof = StepProfiler(trace_dir=str(tmp_path), start_step=1,
                            end_step=2)
        for step in range(4):
            with prof.step(step):
                pass
        prof.close()
        assert not prof._tracing


class TestResourceMonitorPriming:
    """agent/monitor.py: psutil cpu_percent needs a primed baseline."""

    @pytest.fixture()
    def fake_psutil(self, monkeypatch):
        from dlrover_wuqiong_tpu.agent import monitor as mon

        calls = {"created": 0, "cpu": 0}

        class FakeProcess:
            def __init__(self, pid=None):
                import os
                calls["created"] += 1
                self.pid = pid if pid is not None else os.getpid()
                self._primed = False

            def cpu_percent(self, interval=None):
                calls["cpu"] += 1
                # real psutil semantics: no baseline on the first call
                if not self._primed:
                    self._primed = True
                    return 0.0
                return 37.5

            def memory_info(self):
                return types.SimpleNamespace(rss=256 << 20)

        fake = types.ModuleType("psutil")
        fake.Process = FakeProcess
        monkeypatch.setitem(sys.modules, "psutil", fake)
        monkeypatch.setattr(mon, "_PROC", None)
        return mon, calls, FakeProcess

    def test_first_report_is_primed(self, fake_psutil):
        mon, calls, _ = fake_psutil
        stats = mon.get_process_resource()
        # without priming this would be the 0.0 baseline sample — the
        # regression the cached-Process fix exists for
        assert stats["cpu_percent"] == 37.5
        assert stats["memory_mb"] == 256.0
        assert calls == {"created": 1, "cpu": 2}  # prime + measure

    def test_process_object_is_reused(self, fake_psutil):
        mon, calls, _ = fake_psutil
        mon.get_process_resource()
        mon.get_process_resource()
        assert calls["created"] == 1
        assert calls["cpu"] == 3  # prime once, then one per report

    def test_reprime_after_pid_change(self, fake_psutil):
        mon, calls, FakeProcess = fake_psutil
        mon.get_process_resource()
        # simulate a spawned child inheriting the module global: the
        # cached Process carries the PARENT's pid and baseline
        mon._PROC = FakeProcess(pid=-1)
        stats = mon.get_process_resource()
        assert stats["cpu_percent"] == 37.5  # re-primed, not 0.0 baseline
        assert mon._PROC.pid != -1

    def test_no_psutil_falls_back(self, monkeypatch):
        from dlrover_wuqiong_tpu.agent import monitor as mon

        monkeypatch.setattr(mon, "_PROC", None)
        import builtins

        real_import = builtins.__import__

        def no_psutil(name, *a, **k):
            if name == "psutil":
                raise ImportError("nope")
            return real_import(name, *a, **k)

        monkeypatch.setattr(builtins, "__import__", no_psutil)
        stats = mon.get_process_resource()
        assert stats["cpu_percent"] == 0.0
        assert stats["memory_mb"] > 0.0  # resource.getrusage fallback
