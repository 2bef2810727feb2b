"""Direct unit tests for common/report_cli.py — the shared one-line-JSON
contract every tools/ report CLI rides (goodput/policy/serve/incident/
perf/warm/perf_probe).  Pins the rc semantics and the exactly-one-stdout-
line invariant in EVERY path, so a tool migration can't silently bend
the driver-facing contract.
"""

import json
import os

import pytest

from dlrover_wuqiong_tpu.common.report_cli import (
    parse_value_flags,
    run_report,
)

DOC = "tool docstring for -h"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lines(capsys):
    out, err = capsys.readouterr()
    return out.splitlines(), err


class TestParseValueFlags:
    def test_pairs_and_help(self):
        vals = parse_value_flags(
            ["--flight", "/d", "-h", "--addr", "h:1"],
            ("--flight", "--addr"))
        assert vals == {"--flight": "/d", "--help": "-h",
                        "--addr": "h:1"}

    def test_unknown_args_tolerated(self):
        # historical manual loops ignored positionals/unknown flags —
        # the shared parser must too (warm_report's positional cache_dir)
        assert parse_value_flags(["pos", "--nope", "x"], ("--addr",)) == {}

    def test_flag_missing_value_is_none(self):
        assert parse_value_flags(["--addr"], ("--addr",)) == \
            {"--addr": None}


class TestRunReportContract:
    def test_help_goes_to_stderr_rc0(self, capsys):
        rc = run_report(["-h"], DOC,
                        offline=lambda v: {"never": True},
                        live=lambda a, v: {"never": True},
                        no_addr_error="no addr")
        out, err = _lines(capsys)
        assert rc == 0
        assert out == []  # stdout stays machine-parseable
        assert DOC in err

    def test_offline_success_one_json_line(self, capsys):
        rc = run_report(["--src", "x"], DOC,
                        offline=lambda v: {"src": v.get("--src")},
                        live=lambda a, v: {"never": True},
                        no_addr_error="no addr",
                        value_flags=("--src",))
        out, _ = _lines(capsys)
        assert rc == 0
        assert len(out) == 1
        assert json.loads(out[0]) == {"src": "x"}

    def test_live_success_uses_addr_flag(self, capsys, monkeypatch):
        monkeypatch.delenv("DWT_MASTER_ADDR", raising=False)
        rc = run_report(["--addr", "h:9"], DOC,
                        offline=lambda v: None,
                        live=lambda addr, v: {"addr": addr},
                        no_addr_error="no addr")
        out, _ = _lines(capsys)
        assert rc == 0
        assert len(out) == 1
        assert json.loads(out[0]) == {"addr": "h:9"}

    def test_live_addr_from_env(self, capsys, monkeypatch):
        monkeypatch.setenv("DWT_MASTER_ADDR", "envhost:7")
        rc = run_report([], DOC,
                        offline=lambda v: None,
                        live=lambda addr, v: {"addr": addr},
                        no_addr_error="no addr")
        out, _ = _lines(capsys)
        assert rc == 0
        assert json.loads(out[0]) == {"addr": "envhost:7"}

    def test_no_addr_rc2_with_error_line(self, capsys, monkeypatch):
        monkeypatch.delenv("DWT_MASTER_ADDR", raising=False)
        rc = run_report([], DOC,
                        offline=lambda v: None,
                        live=lambda a, v: {"never": True},
                        no_addr_error="pass --addr or set env")
        out, _ = _lines(capsys)
        assert rc == 2
        assert len(out) == 1
        assert json.loads(out[0]) == {"error": "pass --addr or set env"}

    @pytest.mark.parametrize("which", ["offline", "live"])
    def test_failure_rc1_error_line_never_traceback(self, which, capsys,
                                                    monkeypatch):
        monkeypatch.setenv("DWT_MASTER_ADDR", "h:1")

        def blow(*a, **k):
            raise FileNotFoundError("/missing/dir")

        rc = run_report([], DOC,
                        offline=blow if which == "offline"
                        else lambda v: None,
                        live=blow if which == "live"
                        else lambda a, v: {},
                        no_addr_error="no addr")
        out, err = _lines(capsys)
        assert rc == 1
        assert len(out) == 1  # ONE parseable line, no traceback on stdout
        line = json.loads(out[0])
        assert "/missing/dir" in line["error"]
        assert "Traceback" not in out[0]

    def test_error_repr_truncated(self, capsys, monkeypatch):
        monkeypatch.setenv("DWT_MASTER_ADDR", "h:1")
        rc = run_report([], DOC,
                        offline=lambda v: (_ for _ in ()).throw(
                            ValueError("x" * 5000)),
                        live=lambda a, v: {},
                        no_addr_error="no addr")
        out, _ = _lines(capsys)
        assert rc == 1
        assert len(json.loads(out[0])["error"]) <= 500

    def test_argv_none_reads_sys_argv(self, capsys, monkeypatch):
        monkeypatch.setattr("sys.argv", ["tool", "-h"])
        rc = run_report(None, DOC,
                        offline=lambda v: {"never": True},
                        live=lambda a, v: {"never": True},
                        no_addr_error="no addr")
        out, err = _lines(capsys)
        assert rc == 0 and out == [] and DOC in err


def _perf_probe_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_probe_tool", os.path.join(REPO, "tools", "perf_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestMigratedProbeTool:
    def test_perf_probe_streams_lines_then_summary(self, capsys,
                                                   monkeypatch):
        """tools/perf_probe.py after the run_report migration: the
        historical per-probe JSON lines still stream, and the FINAL line
        is the contract summary folding every emitted record."""
        mod = _perf_probe_tool()
        monkeypatch.setitem(
            mod.ALL, "fake",
            lambda: mod._emit("fake", 0.001, note="x"))
        rc = mod.main(["fake"])
        out = capsys.readouterr().out.splitlines()
        assert rc == 0
        assert len(out) == 2  # one per-probe line + ONE summary line
        assert json.loads(out[0]) == {"probe": "fake", "ms": 1.0,
                                      "note": "x"}
        summary = json.loads(out[1])
        assert summary["emitted"] == 1
        assert summary["probes"] == [json.loads(out[0])]

    @pytest.mark.parametrize("argv,want", [
        (["attn_direct", "--shape", "1,48,8,16384,128", "--blocks",
          "1024x1024,512x1024", "--heads", "6,3"],
         {"shape": (1, 48, 8, 16384, 128),
          "blocks": ((1024, 1024), (512, 1024)), "heads": (6, 3)}),
        (["attn_direct", "--shape", "2,32,2,8192,128"],
         {"shape": (2, 32, 2, 8192, 128), "blocks": None, "heads": None}),
        (["attn_direct"], {"shape": None, "blocks": None, "heads": None}),
    ])
    def test_attn_direct_takes_a_grouped_shape_and_blocks(self, capsys,
                                                          monkeypatch, argv,
                                                          want):
        """`attn_direct --shape b,heads,kv,T,d --blocks .. --heads ..`:
        the group forward's sweep at one shape (PR 67); the flags' values
        are no probe names, and no other probe is handed them."""
        mod = _perf_probe_tool()
        seen = []
        monkeypatch.setitem(mod.ALL, "attn_direct",
                            lambda **kw: seen.append(kw))
        monkeypatch.setitem(mod.ALL, "fake", lambda: seen.append("fake"))
        rc = mod.main(argv + ["fake"])
        assert rc == 0 and seen == [want, "fake"]
        assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
            "probes": [], "emitted": 0}
