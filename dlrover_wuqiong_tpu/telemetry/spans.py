"""Cross-process trace spans riding the typed JSON control-plane frames.

Parity: reference `dlrover/python/common/grpc.py` (the envelope every
agent-master exchange rides) + the xpu_timer timeline-dump intent
(`atorch/dev/xpu_timer/common/manager.cc` — host-side timing exported for
offline viewing).  The reference has no distributed tracing: a restore or
re-mesh is reconstructed by grepping three processes' logs.

TPU redesign: the frame envelope (common/comm.py) carries
``trace_id``/``span_id``/``parent_span``; `retry_call`, RpcClient verb
calls, servicer handling, checkpoint save/restore tiers, rendezvous
rounds and warm-pool hydration open spans into a process-local bounded
buffer.  One restore then reconstructs end-to-end across
agent → master → saver processes from the flight dumps (recorder.py) or
a Chrome trace-event JSON (`dump_chrome_trace`, chrome://tracing /
Perfetto format).

Clocks: span *durations* are ``time.monotonic`` intervals; span *start
timestamps* are ``time.time`` so spans from different processes align on
one timeline (the one sanctioned cross-process use of wall clock), with
the monotonic start beside it (``t_mono``) for readers inside the
process that cut spans to a window stamped on that clock.

The profiler's clock: in a process that has imported JAX every span also
opens a ``jax.profiler.TraceAnnotation`` of its name, so it lands on the
host plane of whichever profiler trace is running (the benchmark's,
`StepProfiler`'s, the perf observatory's) beside ``XLA Ops``.  A process
that never imported JAX (agent, master, launcher) has no profiler and
must not load one; there the annotation is skipped.

Two rings.  `span()` writes the full record into the bounded buffer and
the flight recorder: control plane, checkpoint and set-up spans, a few
per boundary at most.  `hot_span()` is for what runs every optimizer
step (`trainer:iteration` and its children, the metrics pump): a tuple
in a ring of its own that never reaches the flight recorder, so ten
thousand iterations push out neither a set-up span nor a control-plane
one.  Both kinds nest in one per-thread stack, so a full span opened
under a hot one names it as its parent.

Child processes spawned mid-span inherit the active context through
``DWT_TRACE_ID`` / ``DWT_TRACE_PARENT`` (see `env_context`); the spawned
side picks them up lazily on its first span.

The start of the process: `process_start()` is when the kernel started
this process, brought onto both clocks, and `past_span()` writes a full
record for a stretch that is already over.  Together they let the first
span of a process (`proc:boot`, written by `boot_span()`) begin where
the process did — interpreter, imports and the backend's start included
— so a restart reads as one chain from the agent's `Popen` to the
worker's first step.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, Iterator, List, Optional, Tuple

from .recorder import get_recorder

SPAN_SCHEMA_VERSION = 1

#: bounded process-local span buffer (drop-oldest)
_MAX_SPANS = 2048

_BUFFER: "deque[Dict]" = deque(maxlen=_MAX_SPANS)
_BUFFER_LOCK = threading.Lock()

#: ring of per-step records (drop-oldest): (name, t_mono, dur_s, span_id,
#: parent_span, trace_id, thread id).  Appends are atomic under the GIL.
_MAX_HOT_SPANS = 32768
_HOT: "deque[tuple]" = deque(maxlen=_MAX_HOT_SPANS)
_HOT_IDS = itertools.count(1)

_TLS = threading.local()

_ROLE = os.getenv("DWT_PROC_ROLE", "")

#: this module's import, the stand-in for the process's start where the
#: kernel's own stamp cannot be read
_IMPORTED = (time.monotonic(), time.time())
_PROCESS_START: Optional[Tuple[float, float]] = None
_BOOT_WRITTEN = False


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


def set_process_role(role: str):
    """Name this process in span/flight dumps (agent/master/saver/...)."""
    global _ROLE
    _ROLE = role


def process_role() -> str:
    return _ROLE or "proc"


def _stack() -> List[Dict]:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = []
        # a spawned child joins the parent's trace lazily: the env
        # context seeds the root of this thread's stack once
        tid = os.getenv("DWT_TRACE_ID", "")
        if tid:
            stack.append({"trace_id": tid,
                          "span_id": os.getenv("DWT_TRACE_PARENT", "")})
        _TLS.stack = stack
    return stack


def current_trace() -> Optional[Dict[str, str]]:
    """Active {"trace_id", "span_id"} or None outside any span."""
    stack = _stack()
    if not stack:
        return None
    top = stack[-1]
    return {"trace_id": top["trace_id"], "span_id": top.get("span_id", "")}


def inject() -> Optional[Dict[str, str]]:
    """Trace fields for an outgoing frame envelope (None = untraced)."""
    return current_trace()


@contextlib.contextmanager
def extract(trace: Optional[Dict]):
    """Adopt an incoming frame's trace context for the handling scope."""
    if not trace or not trace.get("trace_id"):
        yield
        return
    stack = _stack()
    stack.append({"trace_id": str(trace["trace_id"]),
                  "span_id": str(trace.get("span_id", ""))})
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def env_context():
    """Env vars propagating the active context to a spawned child."""
    ctx = current_trace()
    env = {}
    if ctx:
        env["DWT_TRACE_ID"] = ctx["trace_id"]
        env["DWT_TRACE_PARENT"] = ctx["span_id"]
    yield env


def _annotation(name: str):
    """An entered `jax.profiler.TraceAnnotation`, or None in a process
    that has not imported JAX (importing it here would hand the agent
    and the master a runtime they must stay clear of)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


def _record(rec: Dict):
    with _BUFFER_LOCK:
        _BUFFER.append(rec)
    # spans are flight-recorder events too: a fault dump carries the
    # recent trace tree without a separate flush path
    get_recorder().record("span", rec["name"], rec)


def _new_record(name: str, attrs: Optional[Dict], parent: Optional[Dict],
                t_wall: float, t_mono: float) -> Dict:
    return {
        "schema": SPAN_SCHEMA_VERSION,
        "name": name,
        "trace_id": parent["trace_id"] if parent else _new_id(),
        "span_id": _new_id(),
        "parent_span": parent.get("span_id", "") if parent else "",
        "role": process_role(),
        "pid": os.getpid(),
        "t_wall": t_wall,
        "t_mono": t_mono,
        "dur_s": 0.0,
        "attrs": dict(attrs or {}),
        "status": "ok",
    }


@contextlib.contextmanager
def span(name: str, attrs: Optional[Dict] = None):
    """Open a span; nests under the active one, propagates via frames."""
    stack = _stack()
    rec = _new_record(name, attrs, stack[-1] if stack else None,
                      time.time(), time.monotonic())
    stack.append({"trace_id": rec["trace_id"], "span_id": rec["span_id"]})
    ann = _annotation(name)
    try:
        yield rec
    except BaseException:
        rec["status"] = "error"
        raise
    finally:
        rec["dur_s"] = time.monotonic() - rec["t_mono"]
        if ann is not None:
            ann.__exit__(None, None, None)
        stack.pop()
        _record(rec)


def past_span(name: str, t0: float, t1: float,
              attrs: Optional[Dict] = None,
              beside: Optional[Dict] = None) -> Dict:
    """Write a span for a stretch that is over: `t0` .. `t1` on
    `time.monotonic()`.  The same record as `span()`'s, into the same
    buffer and the flight recorder; no profiler annotation, since there
    is nothing left to annotate.  It hangs under the thread's active
    span — or, given `beside`, under that record's parent and in its
    trace: the stretch that ended where `beside` began."""
    if beside is not None:
        parent = {"trace_id": beside["trace_id"],
                  "span_id": beside["parent_span"]}
    else:
        stack = _stack()
        parent = stack[-1] if stack else None
    to_wall = time.time() - time.monotonic()  # graftlint: disable=wall-clock-duration -- the wall/monotonic anchor of a past start, not elapsed-time math
    rec = _new_record(name, attrs, parent, t0 + to_wall, t0)
    rec["dur_s"] = t1 - t0
    _record(rec)
    return rec


def process_start() -> Tuple[float, float]:
    """(t_mono, t_wall) of this process's start as the kernel has it:
    `/proc/self/stat`'s start time (field 22, clock ticks since boot)
    against `CLOCK_BOOTTIME`, brought onto `time.monotonic()` and the
    wall.  Where that cannot be read (no `/proc`), this module's import.
    Read once."""
    global _PROCESS_START
    if _PROCESS_START is None:
        start = _IMPORTED
        try:
            with open("/proc/self/stat") as f:
                # the command (field 2) may hold spaces and brackets
                fields = f.read().rpartition(")")[2].split()
            age = time.clock_gettime(time.CLOCK_BOOTTIME) \
                - int(fields[19]) / os.sysconf("SC_CLK_TCK")
            now_mono, now_wall = time.monotonic(), time.time()
            # the tick is 10 ms: a start "after" the import is rounding
            start = (min(now_mono - age, _IMPORTED[0]),
                     min(now_wall - age, _IMPORTED[1]))
        except (OSError, ValueError, IndexError, AttributeError):
            pass
        _PROCESS_START = start
    return _PROCESS_START


def backend_attached() -> bool:
    """Whether a JAX backend stands in this process.  Asked only where
    JAX is imported already (the agent and the master never load it);
    `xla_bridge` is private, as `compile_cache`'s listeners are."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def boot_span(beside: Dict) -> Optional[Dict]:
    """`proc:boot`, once a process: from `process_start()` to where
    `beside` (the first span of the program proper, `trainer:build`)
    began — the interpreter, the imports, the caller's own preparation
    and the backend's start by whoever made it, which
    `backend_attached_by` names: the `caller` if a backend stands
    already, else the `program` (its own `backend:attach` follows)."""
    global _BOOT_WRITTEN
    if _BOOT_WRITTEN:
        return None
    _BOOT_WRITTEN = True
    by = "caller" if backend_attached() else "program"
    return past_span("proc:boot", process_start()[0], beside["t_mono"],
                     {"backend_attached_by": by}, beside=beside)


@contextlib.contextmanager
def backend_attach(via: str) -> Iterator[Optional[Dict]]:
    """`backend:attach` around the program's own first touch of the
    devices (`via` says which call); no span, and None, where a backend
    stands."""
    if backend_attached():
        yield None
        return
    with span("backend:attach", {"via": via}) as rec:
        yield rec


class hot_span:
    """`with hot_span(name):` — the light span of the per-step path.

    Same nesting, same ids on the thread's stack and the same profiler
    annotation as `span()`, but the record is one tuple in `_HOT`: no
    dict, no uuid, no lock, no flight-recorder event."""

    __slots__ = ("name", "_ann", "_t0", "_ctx", "_parent")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _stack()
        parent = stack[-1] if stack else None
        sid = f"h{next(_HOT_IDS):x}"
        self._parent = parent.get("span_id", "") if parent else ""
        self._ctx = {"trace_id": parent["trace_id"] if parent else sid,
                     "span_id": sid}
        stack.append(self._ctx)
        self._ann = _annotation(self.name)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        dur = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _stack().pop()
        _HOT.append((self.name, self._t0, dur, self._ctx["span_id"],
                     self._parent, self._ctx["trace_id"],
                     threading.get_ident()))
        return False


def hot_spans_snapshot() -> List[Dict]:
    """The per-step ring as dicts, oldest first: name, t_mono, dur_s,
    span_id, parent_span, trace_id, tid."""
    keys = ("name", "t_mono", "dur_s", "span_id", "parent_span",
            "trace_id", "tid")
    return [dict(zip(keys, rec)) for rec in list(_HOT)]


def span_event(name: str, attrs: Optional[Dict] = None):
    """Zero-duration span for point-in-time marks (world formed, ...)."""
    with span(name, attrs):
        pass


def spans_snapshot() -> List[Dict]:
    """Copy of the bounded buffer, oldest first."""
    with _BUFFER_LOCK:
        return list(_BUFFER)


def clear_spans():
    with _BUFFER_LOCK:
        _BUFFER.clear()
    _HOT.clear()


def dump_chrome_trace(path: str, extra_spans: Optional[List[Dict]] = None,
                      instant_events: Optional[List[Dict]] = None,
                      process_names: Optional[Dict[int, str]] = None,
                      include_buffer: bool = True):
    """Write the buffer (plus `extra_spans`, e.g. merged flight dumps) as
    Chrome trace-event JSON — load in chrome://tracing or Perfetto.

    Multi-process (add-only, telemetry/timeline.py export_perfetto):
    `process_names` emits one process_name metadata row per pid so each
    process gets a labelled track; `instant_events`
    (``{"name", "t_wall", "pid", "args"}``) become instant marks (journal
    frames, flight flushes); `include_buffer=False` exports ONLY the
    supplied events — a whole-incident export must not mix in whatever
    the exporting process's own span buffer happens to hold."""
    import json

    events = []
    for pid, pname in sorted((process_names or {}).items()):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": pid, "args": {"name": str(pname)}})
    for inst in instant_events or []:
        events.append({
            "name": inst.get("name", ""),
            "cat": "instant",
            "ph": "i", "s": "p",
            "ts": float(inst.get("t_wall", 0.0)) * 1e6,
            "pid": inst.get("pid", 0),
            "tid": inst.get("pid", 0),
            "args": dict(inst.get("args") or {}),
        })
    buffered = spans_snapshot() if include_buffer else []
    if include_buffer:
        # the per-step ring keeps the monotonic clock only: anchor it to
        # the wall here, as a flight dump's envelope does
        to_wall = time.time() - time.monotonic()  # graftlint: disable=wall-clock-duration -- the wall/monotonic anchor of an export, not elapsed-time math
        buffered += [{**rec, "t_wall": rec["t_mono"] + to_wall,
                      "role": process_role(), "pid": os.getpid()}
                     for rec in hot_spans_snapshot()]
    for rec in (extra_spans or []) + buffered:
        events.append({
            "name": rec["name"],
            "cat": rec.get("role", "proc"),
            "ph": "X",
            "ts": rec["t_wall"] * 1e6,
            "dur": max(rec.get("dur_s", 0.0), 0.0) * 1e6,
            "pid": rec.get("pid", 0),
            "tid": rec.get("pid", 0),
            "args": {
                "trace_id": rec.get("trace_id", ""),
                "span_id": rec.get("span_id", ""),
                "parent_span": rec.get("parent_span", ""),
                "status": rec.get("status", "ok"),
                **rec.get("attrs", {}),
            },
        })
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": events}, f)
    os.replace(tmp, path)
    return len(events)
