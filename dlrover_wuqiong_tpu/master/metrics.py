"""Metrics collection + Prometheus-style export.

Parity: reference `master/stats/job_collector.py` (JobMetricCollector),
`master/stats/reporter.py` (StatsReporter local/Brain) and the xpu_timer
Prometheus endpoint intent (`atorch/dev/xpu_timer/common/manager.cc` — bvar/
brpc exporter of kernel/collective timings).

One process-wide `MetricRegistry` (gauges + counters + bounded histograms)
that any subsystem writes into (SpeedMonitor throughput, agent resource
reports, checkpoint timings, relaunch counts); a `PrometheusExporter`
serves it as text/plain exposition format over HTTP so standard scrapers
work against the master.
"""

from __future__ import annotations

import http.server
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..common.log import get_logger

logger = get_logger("metrics")

_LabelKey = Tuple[Tuple[str, str], ...]


def _labels_key(labels: Optional[Dict[str, str]]) -> _LabelKey:
    return tuple(sorted((labels or {}).items()))


def _escape_label_value(v: str) -> str:
    """Exposition-format label escaping: backslash, quote, newline."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(key: _LabelKey) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in key)
    return "{" + inner + "}"


#: default histogram bucket upper bounds (seconds-oriented, exponential);
#: rendered cumulatively with a trailing +Inf per the exposition format
DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                   30.0, 60.0, 300.0, 600.0)


class MetricRegistry:
    """Thread-safe gauges/counters/histograms with labels."""

    def __init__(self):
        self._lock = threading.Lock()
        self._gauges: Dict[str, Dict[_LabelKey, float]] = {}
        self._counters: Dict[str, Dict[_LabelKey, float]] = {}
        self._hists: Dict[str, Dict[_LabelKey, List[float]]] = {}
        self._buckets: Dict[str, Tuple[float, ...]] = {}
        self._help: Dict[str, str] = {}

    def gauge(self, name: str, value: float,
              labels: Optional[Dict[str, str]] = None, help: str = ""):
        with self._lock:
            self._gauges.setdefault(name, {})[_labels_key(labels)] = value
            if help:
                self._help[name] = help

    def inc(self, name: str, value: float = 1.0,
            labels: Optional[Dict[str, str]] = None, help: str = ""):
        with self._lock:
            d = self._counters.setdefault(name, {})
            k = _labels_key(labels)
            d[k] = d.get(k, 0.0) + value
            if help:
                self._help[name] = help

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, str]] = None, help: str = "",
                max_samples: int = 1000,
                buckets: Optional[Tuple[float, ...]] = None):
        with self._lock:
            d = self._hists.setdefault(name, {})
            k = _labels_key(labels)
            samples = d.setdefault(k, [])
            samples.append(value)
            if len(samples) > max_samples:
                del samples[:len(samples) - max_samples]
            if buckets is not None:
                self._buckets[name] = tuple(sorted(buckets))
            if help:
                self._help[name] = help

    def get_gauge(self, name: str,
                  labels: Optional[Dict[str, str]] = None
                  ) -> Optional[float]:
        with self._lock:
            return self._gauges.get(name, {}).get(_labels_key(labels))

    def get_counter(self, name: str,
                    labels: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return self._counters.get(name, {}).get(_labels_key(labels), 0.0)

    def render(self) -> str:
        """Prometheus text exposition format."""
        out = []
        with self._lock:
            for name, series in sorted(self._gauges.items()):
                if name in self._help:
                    out.append(f"# HELP {name} {self._help[name]}")
                out.append(f"# TYPE {name} gauge")
                for k, v in series.items():
                    out.append(f"{name}{_fmt_labels(k)} {v}")
            for name, series in sorted(self._counters.items()):
                if name in self._help:
                    out.append(f"# HELP {name} {self._help[name]}")
                out.append(f"# TYPE {name} counter")
                for k, v in series.items():
                    out.append(f"{name}_total{_fmt_labels(k)} {v}")
            for name, series in sorted(self._hists.items()):
                if name in self._help:
                    out.append(f"# HELP {name} {self._help[name]}")
                out.append(f"# TYPE {name} histogram")
                bounds = self._buckets.get(name, DEFAULT_BUCKETS)
                for k, samples in series.items():
                    if not samples:
                        continue
                    s = sorted(samples)
                    # cumulative bucket counts, non-decreasing by
                    # construction, closed by the mandatory +Inf bucket
                    cum = 0
                    i = 0
                    for le in bounds:
                        while i < len(s) and s[i] <= le:
                            i += 1
                        cum = i
                        bk = k + (("le", repr(float(le))),)
                        out.append(
                            f"{name}_bucket"
                            f"{_fmt_labels(tuple(sorted(bk)))} {cum}")
                    bk = k + (("le", "+Inf"),)
                    out.append(f"{name}_bucket"
                               f"{_fmt_labels(tuple(sorted(bk)))} {len(s)}")
                    out.append(f"{name}_count{_fmt_labels(k)} {len(s)}")
                    out.append(f"{name}_sum{_fmt_labels(k)} {sum(s)}")
        return "\n".join(out) + "\n"


_REGISTRY: Optional[MetricRegistry] = None
_REG_LOCK = threading.Lock()


def get_registry() -> MetricRegistry:
    global _REGISTRY
    with _REG_LOCK:
        if _REGISTRY is None:
            _REGISTRY = MetricRegistry()
        return _REGISTRY


class JobMetricCollector:
    """Master-side collector wiring job state into the registry.

    Parity: reference JobMetricCollector (stats/job_collector.py:185) —
    collects step/speed/node-resource/ckpt metrics for reporting.
    """

    def __init__(self, job_name: str = "dwt",
                 registry: Optional[MetricRegistry] = None):
        self.job = job_name
        self.reg = registry or get_registry()

    def collect_global_step(self, step: int, timestamp: float = 0.0):
        self.reg.gauge("dwt_job_global_step", step, {"job": self.job},
                       help="latest reported global step")

    def collect_speed(self, steps_per_sec: float, tokens_per_sec: float = 0):
        self.reg.gauge("dwt_job_steps_per_second", steps_per_sec,
                       {"job": self.job}, help="training throughput")
        if tokens_per_sec:
            self.reg.gauge("dwt_job_tokens_per_second", tokens_per_sec,
                           {"job": self.job})

    def collect_node_resource(self, node_id: int, cpu: float,
                              memory_mb: float):
        labels = {"job": self.job, "node": str(node_id)}
        self.reg.gauge("dwt_node_cpu_cores", cpu, labels)
        self.reg.gauge("dwt_node_memory_mb", memory_mb, labels)

    def collect_ckpt_timing(self, kind: str, seconds: float):
        """kind: 'blocking' | 'persist' | 'restore'."""
        self.reg.observe("dwt_ckpt_seconds", seconds,
                         {"job": self.job, "kind": kind},
                         help="checkpoint stage timings")

    def collect_node_event(self, event: str):
        """event: 'relaunch' | 'failure' | 'scale_up' | 'scale_down'."""
        self.reg.inc("dwt_node_events", 1.0,
                     {"job": self.job, "event": event},
                     help="node lifecycle events")


class PrometheusExporter:
    """Minimal /metrics HTTP endpoint (no deps)."""

    def __init__(self, port: int = 0,
                 registry: Optional[MetricRegistry] = None):
        self.registry = registry or get_registry()
        reg = self.registry

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                if self.path not in ("/metrics", "/"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = reg.render().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # silence per-request logging
                pass

        self._server = http.server.ThreadingHTTPServer(("0.0.0.0", port),
                                                       Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True, name="dwt-prometheus")
        self._thread.start()
        logger.info("prometheus exporter on :%d/metrics", self.port)

    def stop(self):
        self._server.shutdown()
        self._server.server_close()
