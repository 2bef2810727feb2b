"""XPlane trace parsing → per-op-category runtime latencies.

Parity: reference xpu_timer (`atorch/dev/xpu_timer/common/manager.cc` +
`nvidia/hook.cc`) — an LD_PRELOAD shim that times every GEMM/NCCL launch and
exports per-op latency gauges to Prometheus.

TPU redesign: device kernels are not host-visible calls, so instead of
hooking launches we parse the XPlane protobuf that `jax.profiler` drops for
a traced step window and aggregate device-op durations by category (matmul,
collective, transfer, data-movement (on-device dynamic-slice/gather/...),
fused, sync, other).  The profile feeds the shared
MetricRegistry (→ PrometheusExporter) and the diagnosis evidence chain
(top-k slowest collectives), giving the same observability surface without
a preload shim.

The protobuf wire reader below is self-contained (stdlib only): XSpace is a
stable, public schema (tensorflow/tsl/profiler/protobuf/xplane.proto) and
we only need a thin slice of it — planes → lines → events + the two
metadata maps.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, Iterator, List, Optional, Tuple

from ..common.log import get_logger

logger = get_logger("xplane")


# ------------------------------------------------------- protobuf wire layer


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over a message buffer."""
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:        # varint
            val, pos = _varint(buf, pos)
        elif wt == 2:      # length-delimited
            ln, pos = _varint(buf, pos)
            if pos + ln > end:  # slicing would silently return short
                raise ValueError("truncated length-delimited field")
            val = buf[pos:pos + ln]
            pos += ln
        elif wt == 5:      # fixed32
            if pos + 4 > end:
                raise ValueError("truncated fixed32")
            val = buf[pos:pos + 4]
            pos += 4
        elif wt == 1:      # fixed64
            if pos + 8 > end:
                raise ValueError("truncated fixed64")
            val = buf[pos:pos + 8]
            pos += 8
        else:              # groups — not used by xplane.proto
            raise ValueError(f"unsupported wire type {wt}")
        yield fnum, wt, val


@dataclasses.dataclass
class _Event:
    metadata_id: int = 0
    duration_ps: int = 0
    num_occurrences: int = 1
    stats: List[Tuple[int, object]] = dataclasses.field(default_factory=list)


def _parse_stat(buf: bytes) -> Tuple[int, object]:
    mid, val = 0, None
    for fnum, wt, v in _fields(buf):
        if fnum == 1:
            mid = v
        elif fnum == 5:            # str_value
            val = v.decode("utf-8", "replace")
        elif fnum in (3, 4, 7):    # uint64/int64/ref
            val = v
    return mid, val


def _parse_event(buf: bytes) -> _Event:
    ev = _Event()
    for fnum, wt, v in _fields(buf):
        if fnum == 1:
            ev.metadata_id = v
        elif fnum == 3:
            ev.duration_ps = v
        elif fnum == 5:
            ev.num_occurrences = v
        elif fnum == 4:
            ev.stats.append(_parse_stat(v))
    return ev


def _parse_map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for fnum, wt, v in _fields(buf):
        if fnum == 1:
            key = v
        elif fnum == 2:
            val = v
    return key, val


def _metadata_name(buf: bytes) -> str:
    for fnum, wt, v in _fields(buf):
        if fnum == 2:
            return v.decode("utf-8", "replace")
    return ""


@dataclasses.dataclass
class _Line:
    name: str = ""
    events: List[_Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Plane:
    name: str = ""
    lines: List[_Line] = dataclasses.field(default_factory=list)
    event_names: Dict[int, str] = dataclasses.field(default_factory=dict)
    stat_names: Dict[int, str] = dataclasses.field(default_factory=dict)


def _parse_line(buf: bytes) -> _Line:
    line = _Line()
    for fnum, wt, v in _fields(buf):
        if fnum == 2:
            line.name = v.decode("utf-8", "replace")
        elif fnum == 4:
            line.events.append(_parse_event(v))
    return line


def _parse_plane(buf: bytes) -> _Plane:
    plane = _Plane()
    for fnum, wt, v in _fields(buf):
        if fnum == 2:
            plane.name = v.decode("utf-8", "replace")
        elif fnum == 3:
            plane.lines.append(_parse_line(v))
        elif fnum == 4:
            k, mv = _parse_map_entry(v)
            plane.event_names[k] = _metadata_name(mv)
        elif fnum == 5:
            k, mv = _parse_map_entry(v)
            plane.stat_names[k] = _metadata_name(mv)
    return plane


def parse_xspace(path: str) -> List[_Plane]:
    with open(path, "rb") as f:
        buf = f.read()
    return [_parse_plane(v) for fnum, wt, v in _fields(buf) if fnum == 1]


# ------------------------------------------------------------- categorizer


# HLO-name prefixes → category (checked on the lowercased, wrapped_/suffix-
# stripped event name).  hlo_category stats, when present (TPU), win.
_PREFIX_CATEGORIES = (
    # the Pallas kernels carry stable names (ops/flash_attention.py)
    ("attention", ("dwt_fa_",)),
    ("collective", ("all-reduce", "all-gather", "all-to-all",
                    "reduce-scatter", "collective-permute",
                    "collective-broadcast", "ragged-all-to-all")),
    ("matmul", ("dot", "convolution", "ragged-dot", "cublas", "gemm")),
    # dynamic-(update-)slice is ON-DEVICE data movement, heavily emitted by
    # the scan-based pipeline schedules — bucketing it under "transfer"
    # would inflate the host<->device gauge for every pipelined job
    ("transfer", ("copy", "infeed", "outfeed", "send", "recv")),
    ("data-movement", ("dynamic-update-slice", "dynamic-slice", "gather",
                       "scatter", "reshape", "transpose")),
    ("sync", ("rendezvous", "wait")),
    ("fused", ("fusion", "loop_", "input_", "output_")),
)

_HLO_CATEGORY_MAP = (
    ("collective", ("all-reduce", "all-gather", "all-to-all",
                    "reduce-scatter", "collective", "permute")),
    ("matmul", ("convolution", "dot", "gemm", "matmul")),
    ("transfer", ("copy", "infeed", "outfeed", "data formatting",
                  "host send", "host recv")),
)


def _instruction_name(name: str) -> str:
    """A TPU device trace names each op event by its whole HLO
    instruction (`%fusion.12 = f32[8]{0} fusion(...)`); the part before
    ` = ` is the instruction's name, which is all the other backends
    give."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def _normalize(name: str) -> str:
    n = _instruction_name(name).lower()
    if n.startswith("wrapped_"):
        n = n[len("wrapped_"):]
    n = n.split(".")[0].split("%")[-1].strip()
    return n


def categorize(name: str, hlo_category: str = "") -> Optional[str]:
    """Category of a device op, or None for host noise."""
    if hlo_category:
        hc = hlo_category.lower()
        for cat, keys in _HLO_CATEGORY_MAP:
            if any(k in hc for k in keys):
                return cat
        return "fused" if "fusion" in hc else "other"
    name = _instruction_name(name)
    if not name or name.startswith("$") or "(" in name or ":" in name:
        return None  # host-side python / runtime artifacts
    n = _normalize(name)
    for cat, prefixes in _PREFIX_CATEGORIES:
        if any(n.startswith(p) for p in prefixes):
            return cat
    if n.endswith("fusion"):  # multiply_reduce_fusion, ...
        return "fused"
    # bare HLO instruction names are [a-z0-9-_]; anything else is host noise
    if not n or not all(c.isalnum() or c in "-_" for c in n):
        return None
    return "other"


# --------------------------------------------------------------- aggregation


@dataclasses.dataclass
class OpEntry:
    name: str
    category: str
    total_s: float
    count: int


@dataclasses.dataclass
class OpProfile:
    """Per-category and per-op device time for one trace window."""

    categories: Dict[str, float] = dataclasses.field(default_factory=dict)
    ops: List[OpEntry] = dataclasses.field(default_factory=list)

    def top(self, category: Optional[str] = None, k: int = 10
            ) -> List[OpEntry]:
        sel = [o for o in self.ops if category in (None, o.category)]
        return sel[:k]

    def collective_evidence(self, k: int = 5) -> str:
        """JSON evidence string for diagnosis: the k slowest collectives."""
        tops = self.top("collective", k)
        if not tops:
            return ""
        return json.dumps([
            {"op": o.name, "seconds": round(o.total_s, 6), "count": o.count}
            for o in tops])


def summarize_planes(planes: List[_Plane]) -> OpProfile:
    device_planes = [p for p in planes if "/device:" in p.name]
    use = device_planes or planes
    agg: Dict[Tuple[str, str], List[float]] = {}
    seen = 0
    for plane in use:
        hlo_stat_ids = {i for i, n in plane.stat_names.items()
                        if n == "hlo_category"}
        # a TPU device plane lays the SAME device time out several times
        # (Steps, XLA Modules, XLA Ops, Async XLA Ops, TC Overlay): only
        # the per-op line is summed, or every second counts twice
        op_lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        for line in op_lines or plane.lines:
            if line.name == "python":
                continue
            seen += len(line.events)
            for ev in line.events:
                name = plane.event_names.get(ev.metadata_id, "")
                hlo_cat = next(
                    (str(v) for mid, v in ev.stats
                     if mid in hlo_stat_ids and isinstance(v, str)), "")
                cat = categorize(name, hlo_cat)
                if cat is None:
                    continue
                key = (_normalize(name), cat)
                tot = agg.setdefault(key, [0.0, 0])
                tot[0] += ev.duration_ps * 1e-12
                tot[1] += max(1, ev.num_occurrences)
    prof = OpProfile()
    for (name, cat), (sec, cnt) in agg.items():
        prof.categories[cat] = prof.categories.get(cat, 0.0) + sec
        prof.ops.append(OpEntry(name, cat, sec, cnt))
    prof.ops.sort(key=lambda o: -o.total_s)
    if seen and not prof.ops:
        logger.error("xplane: %d events on %d plane(s) and not one "
                     "recognised as a device op — the op split of this "
                     "window is EMPTY, not zero", seen, len(use))
    return prof


def parse_trace_dir(trace_dir: str) -> Optional[OpProfile]:
    """Parse the newest profiler run under `trace_dir` (all hosts merged)."""
    runs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not runs:
        return None
    planes: List[_Plane] = []
    for pb in sorted(glob.glob(os.path.join(runs[-1], "*.xplane.pb"))):
        try:
            planes.extend(parse_xspace(pb))
        except Exception:  # noqa: BLE001 — torn/foreign file: skip, not fail
            logger.error("unparseable xplane file %s", pb, exc_info=True)
    if not planes:
        return None
    return summarize_planes(planes)
