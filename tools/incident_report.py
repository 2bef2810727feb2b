"""Incident-timeline report: ONE JSON line for the driver/operator.

Two sources, ONE byte-identical timeline (telemetry/timeline.py):

    python tools/incident_report.py [--addr HOST:PORT] [--ckpt DIR]
    python tools/incident_report.py --journal DIR[,DIR2] [--flight CKPT_DIR]

Live mode asks the master (TimelineQuery, POLLING class) to assemble
the incident timeline from its own journal directory plus the flight
dumps under ``--ckpt`` (falls back to ``--flight`` when only that is
given), and folds the journal-shipping gauges (shipped_seq,
standby_lag_frames, lease_epoch — get_journal_stats) into the summary
line.  Offline mode runs the SAME assembler over disk artifacts
alone — a post-mortem needs no process alive.  Because the assembler
is a pure function of the artifacts, the two sources produce
byte-equal canonical JSON; ``timeline_sha256`` in the summary line is
the proof handle (the chaos drills diff it across live/offline).

``--journal`` accepts a comma-separated dir list for warm-standby
failover post-mortems (old primary's dir + promoted standby's): both
journals merge in (epoch, seq) order with byte-identical shipped
frames deduped.  Pass the SAME ordered list to live mode (the
answering master's own dir sorts first either way) and the two
timelines stay byte-equal across the failover.

Optional sinks (paths, both write full artifacts next to the 1-line
summary): ``--events-out FILE`` writes the canonical incident JSON;
``--perfetto FILE`` writes a chrome://tracing / Perfetto trace of the
whole incident (spans from every process + journal instants).

The operator's reader of ONE restart, as text and not as a JSON line:

    python tools/incident_report.py --restart-table CKPT_DIR

prints, for every worker generation in the flight dumps under
``CKPT_DIR/flight/`` whose exit the agent saw with a code other than 0,
the restart as one indented table — span, process role, start relative
to ``agent:worker_exit``, seconds — from the agent's handling of the
exit (``agent:failure_save``, the ``rpc:report`` of the failure,
``agent:stop_worker``) through the next generation's rendezvous and
``agent:launch_worker`` down to the worker's ``proc:boot``,
``trainer:build``, ``ckpt:restore:*`` and ``trainer:first_step`` (with
the seconds JAX spent inside it by kind).  The agent writes its half at
exit (``agent-exit``), a restarted worker its own once its first step is
dispatched (``resumed``).  rc=1 and a message on stderr where the dumps
hold no such generation.

The operator's reader of a job's device memory, as text too:

    python tools/incident_report.py --memory CKPT_DIR

prints, for every process with a memory record in the flight dumps under
``CKPT_DIR/flight/`` (a trainer's ``fault``, ``sigterm`` and ``resumed``
dumps), the step's compiled budget by fusion width
(``trainer:first_step``: arguments, outputs, what they alias, the
temporaries, the code, and what is live at once) and the timeline of the
device's readings up to the dump — ``trainer:build``'s ``hbm``,
``trainer:train``'s ``hbm_at_entry``, one ``trainer:memory`` a logging
boundary, ``ckpt:snapshot``'s ``hbm_before`` / ``hbm_after`` — of the
fullest device, in GiB, with the headroom (``bytes_limit`` less in use
and reserved) and the largest free block beside each.  rc=1 and a
message on stderr where the dumps hold no such record.

Summary fields: source bookkeeping, event/span/trace/epoch/process
counts, incidents with per-incident lost seconds, goodput_fraction,
and timeline_sha256.  Exit/error contract matches the other report
tools (common/report_cli.py): one JSON line ALWAYS, rc=2 missing
address, rc=1 failure, rc=0 success.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _summarize(content: str, src: dict) -> dict:
    from dlrover_wuqiong_tpu.telemetry import incident_sha256

    report = json.loads(content)
    counts = report.get("counts", {})
    narr = report.get("narrative", {})
    incidents = narr.get("incidents", [])
    line = dict(src)
    line.update({
        "schema": report.get("schema"),
        "events": counts.get("events", 0),
        "journal_events": counts.get("journal_events", 0),
        "flight_events": counts.get("flight_events", 0),
        "spans": counts.get("spans", 0),
        "traces": counts.get("traces", 0),
        "epochs": len(counts.get("epochs", [])),
        "processes": len(counts.get("processes", [])),
        "incidents": len(incidents),
        "failovers": sum(1 for i in incidents
                         if i.get("kind") == "failover"),
        "lost_s": round(sum(float(i.get("lost_s", 0.0))
                            for i in incidents), 3),
        "goodput_fraction": narr.get("goodput_fraction"),
        "policy_decisions": narr.get("policy_decisions", 0),
        "timeline_sha256": incident_sha256(content),
    })
    return line


def _sinks(content: str, vals: dict) -> None:
    from dlrover_wuqiong_tpu.telemetry import export_perfetto

    out = vals.get("--events-out")
    if out:
        with open(out, "w", encoding="utf-8") as f:
            f.write(content)
    perf = vals.get("--perfetto")
    if perf:
        export_perfetto(json.loads(content), perf)


def _journal_dirs(vals: dict) -> list:
    return [d.strip() for d in (vals.get("--journal") or "").split(",")
            if d.strip()]


def _from_disk(vals: dict) -> dict:
    from dlrover_wuqiong_tpu.telemetry import assemble_incident, incident_json

    dirs = _journal_dirs(vals)
    flight = vals.get("--flight") or ""
    for d in dirs:
        if not os.path.isdir(d):
            raise FileNotFoundError(
                f"--journal: {d!r} is not a directory")
    if flight and not os.path.isdir(flight):
        raise FileNotFoundError(
            f"--flight: {flight!r} is not a directory")
    content = incident_json(assemble_incident(
        journal_dir=dirs[0] if dirs else "", ckpt_dir=flight,
        journal_dirs=dirs[1:]))
    _sinks(content, vals)
    return _summarize(content, {"source": "disk",
                                "journal_dir": ",".join(dirs),
                                "ckpt_dir": flight})


def _from_master(addr: str, vals: dict) -> dict:
    from dlrover_wuqiong_tpu.agent.master_client import MasterClient

    ckpt = vals.get("--ckpt") or vals.get("--flight") or ""
    mc = MasterClient(addr, node_id=-1)
    try:
        resp = mc.get_timeline(ckpt_dir=ckpt,
                               journal_dirs=_journal_dirs(vals))
        try:
            stats = mc.get_journal_stats()
            gauges = {"shipped_seq": stats.shipped_seq,
                      "standby_lag_frames": stats.standby_lag_frames,
                      "lease_epoch": stats.lease_epoch,
                      "is_leader": stats.is_leader}
        except Exception:  # noqa: BLE001 — gauges are best-effort garnish;
            # the timeline answer is the deliverable
            gauges = {}
    finally:
        mc.close()
    _sinks(resp.content, vals)
    return _summarize(resp.content, {"source": "master", "addr": addr,
                                     "ckpt_dir": ckpt, **gauges})


# ------------------------------------------------- one restart as a table


def _flight_spans(dumps: list) -> dict:
    """{span_id: record} over every dump (`load_flight_dumps`), each
    with `start` on the shared wall (its own monotonic start through
    the dump's anchor)."""
    from dlrover_wuqiong_tpu.telemetry.timeline import anchored_wall

    spans = {}
    for dump in dumps:
        for evt in dump.get("events") or []:
            rec = evt.get("data") or {}
            if evt.get("kind") != "span" or rec.get("span_id") in spans:
                continue
            # the record's own clocks: the span's START (the event's are
            # when it was written)
            spans[rec["span_id"]] = {**rec, "children": [],
                                     "start": anchored_wall(dump, rec)}
    return spans


def _link(spans: dict) -> None:
    """Hang every span under its parent (`children`).  A span
    whose parent is in no dump (a `trainer:train` still open when its
    worker flushed, a per-step span of the hot ring) goes to the
    narrowest span of its process that holds its start, else to the
    `agent:launch_worker` that started its process."""
    for rec in sorted(spans.values(), key=lambda r: r["start"]):
        parent = spans.get(rec.get("parent_span") or "")
        if parent is None and rec.get("parent_span"):
            around = [s for s in spans.values() if s is not rec
                      and s.get("pid") == rec.get("pid")
                      and s["start"] <= rec["start"]
                      <= s["start"] + s.get("dur_s", 0.0)]
            parent = min(around, key=lambda s: s.get("dur_s", 0.0),
                         default=None) or next(
                (s for s in spans.values()
                 if s["name"] == "agent:launch_worker"
                 and s.get("attrs", {}).get("worker_pid")
                 == rec.get("pid")), None)
        if parent is not None:
            parent["children"].append(rec)


def _rows(rec: dict, depth: int, lo: float, hi: float) -> list:
    """(depth, record) of `rec` and of what lies under it and starts in
    `lo` .. `hi`.  A call made over and over under one parent (the
    monitor's poll of the master) keeps its first row; the rest are
    counted on it (`repeats`)."""
    out = []
    if depth == 0 or lo <= rec["start"] <= hi:
        out.append((depth, rec))
    seen = {}
    for child in rec["children"]:
        kind = (child["name"], child.get("attrs", {}).get("msg"))
        if kind[1] is not None and kind in seen:
            if lo <= child["start"] <= hi:
                seen[kind]["repeats"] = seen[kind].get("repeats", 0) + 1
            continue
        rows = _rows(child, depth + 1, lo, hi)
        if rows and kind[1] is not None:
            seen[kind] = rows[0][1]
        out += rows
    return out


def restart_table(ckpt_dir: str) -> str:
    from dlrover_wuqiong_tpu.telemetry import load_flight_dumps

    spans = _flight_spans(load_flight_dumps(ckpt_dir))
    _link(spans)
    gens = sorted((s for s in spans.values()
                   if s["name"] == "agent:generation"),
                  key=lambda s: s["start"])
    blocks = []
    for i, gen in enumerate(gens):
        exits = [c for c in gen["children"]
                 if c["name"] == "agent:worker_exit"
                 and c.get("attrs", {}).get("exit_code")]
        if not exits:
            continue
        t0 = exits[0]["start"]
        rows = _rows(gen, 0, t0, float("inf"))
        if i + 1 < len(gens):
            nxt = gens[i + 1]
            firsts = [s["start"] + s["dur_s"] for s in spans.values()
                      if s["name"] == "trainer:first_step"
                      and s["start"] >= nxt["start"]]
            rows += _rows(nxt, 0, t0, min(firsts, default=float("inf")))
        lines = [f"restart {len(blocks) + 1}: generation "
                 f"{gen['attrs'].get('restart_count')} left with exit code "
                 f"{exits[0]['attrs']['exit_code']} (seen by a poll every "
                 f"{exits[0]['attrs'].get('poll_interval_s')} s); starts "
                 f"are seconds from agent:worker_exit",
                 f"{'span':<44} {'role':<8} {'start_s':>9} {'seconds':>9}"
                 f"  attrs"]
        for depth, rec in rows:
            # a memory reading (a dict) has `--memory`'s table
            attrs = " ".join(
                f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in rec.get("attrs", {}).items()
                if not isinstance(v, dict))
            if rec.get("status", "ok") != "ok":
                attrs = f"status={rec['status']} {attrs}"
            if rec.get("repeats"):
                attrs += f" (+{rec['repeats']} more)"
            name = "  " * depth + rec["name"]
            lines.append(f"{name:<44} {rec.get('role', ''):<8} "
                         f"{rec['start'] - t0:>9.3f} "
                         f"{rec.get('dur_s', 0.0):>9.3f}  {attrs}".rstrip())
        blocks.append("\n".join(lines))
    if not blocks:
        raise LookupError(
            f"no agent:generation with a failed worker in the flight "
            f"dumps under {ckpt_dir!r}")
    return "\n\n".join(blocks)


# ---------------------------------------------- a job's device memory

_GIB = 2 ** 30
_BUDGET = ("argument", "output", "alias", "temp", "generated_code", "live")
#: (span, attr holding the reading or None for the span's own attrs,
#: whether the reading was taken at the span's end)
_READINGS = (("trainer:build", "hbm", True),
             ("trainer:train", "hbm_at_entry", False),
             ("trainer:memory", None, False),
             ("ckpt:snapshot", "hbm_before", False),
             ("ckpt:snapshot", "hbm_after", True))


def _memory_rows(spans: list) -> list:
    """(wall instant, label, step, reading) of one process's spans."""
    rows = []
    for rec in spans:
        attrs = rec.get("attrs", {})
        for name, key, at_end in _READINGS:
            hbm = attrs if key is None else attrs.get(key)
            if rec["name"] != name or not hbm or "bytes_in_use" not in hbm:
                continue
            rows.append((rec["start"] + (rec.get("dur_s", 0.0) if at_end
                                         else 0.0),
                         name if key is None else f"{name} {key}",
                         attrs.get("step", ""), hbm))
    return sorted(rows, key=lambda r: r[0])


def memory_table(ckpt_dir: str) -> str:
    from dlrover_wuqiong_tpu.telemetry import load_flight_dumps
    from dlrover_wuqiong_tpu.telemetry.memory import (
        headroom_bytes,
        held_bytes,
    )

    dumps = load_flight_dumps(ckpt_dir)  # oldest first
    by_pid = {}
    for rec in _flight_spans(dumps).values():
        by_pid.setdefault(rec.get("pid"), []).append(rec)
    blocks = []
    for pid in dict.fromkeys(d.get("pid") for d in dumps):
        mine = [d for d in dumps if d.get("pid") == pid]
        role, t_end = mine[-1].get("role", ""), mine[-1]["flushed_at"]
        reasons = [d.get("reason") for d in mine]
        spans = by_pid.get(pid, [])
        budgets = sorted((s["attrs"] for s in spans
                          if s["name"] == "trainer:first_step"
                          and "live_bytes" in s.get("attrs", {})),
                         key=lambda a: a["k"])
        rows = _memory_rows(spans)
        if not budgets and not rows:
            continue
        lines = [f"{role} pid {pid} (dumps: {', '.join(reasons)}): GiB of "
                 f"the fullest device; at_s is seconds before the last dump"]
        if budgets:
            lines.append("the step's compiled budget by fusion width "
                         "(live = argument + temp + output - alias)")
            lines.append(f"{'K':>4} " + " ".join(f"{c:>14}"
                                                 for c in _BUDGET))
            lines += [f"{a['k']:>4} " + " ".join(
                f"{a[c + '_bytes'] / _GIB:>14.3f}" for c in _BUDGET)
                for a in budgets]
        if rows:
            cols = ("in_use", "reserved", "sum", "peak_in_use",
                    "peak_reserved", "headroom", "largest_free",
                    "least_in_use")
            lines.append(f"{'at_s':>10} {'record':<26} {'step':>7} "
                         + " ".join(f"{c:>13}" for c in cols) + "  device")
            for at, label, step, hbm in rows:
                vals = (hbm["bytes_in_use"], hbm["bytes_reserved"],
                        held_bytes(hbm),
                        hbm["peak_bytes_in_use"],
                        hbm["peak_bytes_reserved"], headroom_bytes(hbm),
                        hbm["largest_free_block_bytes"],
                        hbm["least_bytes_in_use"])
                lines.append(
                    f"{at - t_end:>10.3f} {label:<26} {step!s:>7} "
                    + " ".join(f"{v / _GIB:>13.3f}" for v in vals)
                    + f"  {hbm.get('device', '')} of "
                      f"{hbm.get('devices', '')}")
        blocks.append("\n".join(lines))
    if not blocks:
        raise LookupError(
            f"no memory record (trainer:first_step's budget, hbm, "
            f"trainer:memory) in the flight dumps under {ckpt_dir!r}")
    return "\n\n".join(blocks)


_TABLES = {"--restart-table": restart_table, "--memory": memory_table}


def main(argv=None) -> int:
    from dlrover_wuqiong_tpu.common.report_cli import run_report

    args = list(sys.argv[1:] if argv is None else argv)
    for flag, table in _TABLES.items():
        if flag not in args:
            continue
        try:
            print(table(args[args.index(flag) + 1]))
        except (IndexError, LookupError, OSError) as e:
            print(f"incident_report: {e!r}", file=sys.stderr)
            return 1
        return 0

    return run_report(
        argv, __doc__,
        offline=lambda v: (_from_disk(v)
                           if (v.get("--journal") or v.get("--flight"))
                           else None),
        live=_from_master,
        no_addr_error="no master address: pass --addr, set "
                      "DWT_MASTER_ADDR, or use --journal DIR "
                      "[--flight CKPT_DIR]",
        value_flags=("--journal", "--flight", "--ckpt",
                     "--perfetto", "--events-out"))


if __name__ == "__main__":
    sys.exit(main())
