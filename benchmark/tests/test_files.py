"""Every file the harness finds by name loads and names only things
that exist — and BENCHMARK.json keeps inside the contract's limits.
Adding a cell, a configuration or a metric is new files + new entries."""

import glob
import json
import os
import re

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()
PARKED = cells.load_parked()  # entries taken out for their spread, kept
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _files(kind, ext):
    return sorted(os.path.basename(p)[:-len(ext)] for p in glob.glob(
        os.path.join(cells.HERE, kind, "*" + ext)))


def test_contract_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cellsn = [w["name"] for w in BENCH["workloads"]]
    assert 2 <= len(cellsn) <= 24 and len(set(cellsn)) == len(cellsn)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(cellsn) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for w in m.get("workloads", []):
            assert w in cellsn, (m["name"], w)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    for w in BENCH["workloads"] + BENCH["configs"]:
        assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_parked_entries_stay_apart_and_whole():
    """What is parked is in no list the driver reads, names its own
    cells only, and says what each cell reports and how it spread."""
    shipped = {w["name"] for w in BENCH["workloads"]}
    parked = {w["name"] for w in PARKED["workloads"]}
    assert parked and not parked & shipped
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    own = {m["name"] for m in PARKED["end_to_end"] + PARKED["per_layer"]}
    assert not own & listed
    for m in PARKED["end_to_end"] + PARKED["per_layer"]:
        assert set(m["workloads"]) <= parked, m["name"]
    assert set(PARKED["reports"]) == set(PARKED["measured"]) == parked
    for names in PARKED["reports"].values():
        assert set(names) <= own | listed


@pytest.mark.parametrize("name", [w["name"] for w in
                                  BENCH["workloads"] + PARKED["workloads"]])
def test_cell_loads_and_reports(name):
    cell = cells.load_cell(name)
    driver = cells.load_module("drivers", cell["traffic"]["driver"])
    assert callable(driver.run)
    model = cells.load_module("models", cell["config"]["model_class"])
    for fn in ("build", "seeded_state", "reference_loss",
               "train_flops_per_token", "attention_cost_per_step"):
        assert callable(getattr(model, fn))
    assert cell["global_batch"] % cell["chips"] == 0
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"], "every cell reports a per-layer metric"
    # a per-layer metric is reported only where the metric it moves is
    for m in cell["per_layer"]:
        assert m["moves"] in e2e, (name, m["name"])
    from dlrover_wuqiong_tpu.trainer.trainer import TrainingArgs

    fields = set(TrainingArgs.__dataclass_fields__)
    assert set(cell["traffic"]["training_args"]) <= fields
    assert cell["traffic"]["training_args"]["perf_window_every"] == 0


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["file"].startswith("benchmark/configs/")
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert sorted(cfg["changed"]) == sorted(cfg["reduced"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    # no width is ever cut: the published widths
    published = {"gpt2_124m": (768, 12, 12), "gpt2_xl": (1600, 48, 25)}
    if entry["name"] in published:
        assert (cfg["n_embd"], cfg["n_layer"], cfg["n_head"]) == \
            published[entry["name"]]
        assert cfg["n_embd"] // cfg["n_head"] == 64
        assert cfg["n_positions"] == 1024
    band = cfg["correct"]["loss_band"]
    assert band[0] < band[1], "the loss band is fixed from chip runs"
    from benchmark.models import gpt  # noqa: F401 — importable as a module

    model = cells.load_module("models", cfg["model_class"]).build(cfg)
    assert model.config.n_embd == cfg["n_embd"]
    assert model.config.remat == cfg["program"]["remat"]


@pytest.mark.parametrize("kind,listed", [
    ("end_to_end", BENCH["end_to_end"] + PARKED["end_to_end"]),
    ("layer_metrics", BENCH["per_layer"] + PARKED["per_layer"])])
def test_every_metric_has_its_reader_and_no_reader_is_orphaned(kind, listed):
    assert _files(kind, ".py") == sorted(m["name"] for m in listed)
    for m in listed:
        mod = cells.load_module(kind, m["name"])
        assert (mod.NAME, mod.UNIT, mod.SOURCE) == \
            (m["name"], m["unit"], m["source"])
        if kind == "layer_metrics":
            assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
        # a reader that finds nothing to read returns nothing
        cell = cells.load_cell(BENCH["workloads"][0]["name"])
        assert mod.read(None, [], {}, cell) is None


def test_traffic_files_are_data_and_used():
    used = {w["traffic"] for w in BENCH["workloads"] + PARKED["workloads"]}
    assert set(_files("traffic", ".json")) == used
    for name in used:
        with open(os.path.join(cells.HERE, "traffic", name + ".json")) as f:
            t = json.load(f)
        assert t["driver"] in _files("drivers", ".py")
        assert {"training_args", "data", "window", "traced"} <= set(t)
