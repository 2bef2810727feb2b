"""Unified runtime telemetry: goodput ledger, trace spans, flight recorder.

Parity: reference `dlrover/python/master/monitor/speed_monitor.py` (the
master's only live training signal) + the xpu_timer always-on timing
intent (`atorch/dev/xpu_timer/common/manager.cc` — runtime metrics
exported continuously, not just inside benchmarks).

TPU redesign: the reference stack measures speed from reported steps and
leaves downtime attribution to offline log spelunking.  Here every second
of trainer wall time lands in exactly one ledger state (telemetry/
ledger.py), control-plane and checkpoint work is traced with
cross-process spans riding the typed JSON frames (telemetry/spans.py),
and each process keeps a bounded flight-recorder ring flushed to
``$ckpt_dir/flight/`` on faults (telemetry/recorder.py) — the measurement
substrate the Brain's adaptive policies read from instead of chaos-drill
ad-hoc timers.

The incident timeline (telemetry/timeline.py) merges all of the above
plus the master journal into ONE causally-ordered event stream — live
via the TimelineQuery verb, offline via tools/incident_report.py,
byte-equal either way.

The perf observatory (telemetry/perf.py) adds the device-side signal the
ledger cannot see: sampled in-train profiling windows keyed by
executable identity, a median+MAD baseline store under
``$ckpt_dir/perf/``, and a regression/retrace sentinel feeding node
events, the policy loop and tools/perf_report.py.

Schemas are ADD-ONLY: ``LEDGER_STATES``, the ledger snapshot keys, the
flight-dump envelope keys (tests/test_telemetry.py), the timeline
event envelope (tests/test_timeline.py) and the PerfSnapshot /
perf-event keys (tests/test_perf.py) — extend, never rename.
"""

from .ledger import (  # noqa: F401
    LEDGER_SCHEMA_VERSION,
    LEDGER_STATES,
    GoodputLedger,
    get_ledger,
    reset_ledger,
)
from .serving import (  # noqa: F401
    SERVE_COUNTERS,
    SERVE_SCHEMA_VERSION,
    SERVE_STATES,
    ServeLedger,
    get_serve_ledger,
    reset_serve_ledger,
)
from .perf import (  # noqa: F401
    PERF_EVENT_KEYS,
    PERF_SCHEMA,
    PERF_SNAPSHOT_KEYS,
    BaselineStore,
    PerfObservatory,
    RegressionSentinel,
    executable_key,
    get_observatory,
    keep_step_executable,
    latest_snapshot,
    reset_observatory,
    set_observatory,
    step_executables,
    step_memory,
)
from .recorder import (  # noqa: F401
    FLIGHT_SCHEMA_VERSION,
    FlightRecorder,
    flight_dir,
    get_recorder,
    load_flight_dumps,
    reset_recorder,
)
from .timeline import (  # noqa: F401
    TIMELINE_EVENT_KEYS,
    TIMELINE_SCHEMA_VERSION,
    assemble_incident,
    build_narrative,
    export_perfetto,
    incident_json,
    incident_sha256,
    trace_tree,
)
from .spans import (  # noqa: F401
    SPAN_SCHEMA_VERSION,
    clear_spans,
    current_trace,
    dump_chrome_trace,
    env_context,
    extract,
    hot_span,
    hot_spans_snapshot,
    inject,
    set_process_role,
    span,
    span_event,
    spans_snapshot,
)
