"""The step's memory from inside the program (PR 64): the compiled
budget of the step that ran (`telemetry.perf.step_memory`, one reckoning:
`telemetry.memory.compiled_memory`), the devices' readings at the loop's
boundaries (one reader: `telemetry.memory.device_memory`) on records
that exist, the flight dump that holds both, the operator's table of
them (`tools/incident_report.py --memory`) and the benchmark's four
readers over a recording of one chip run.

The CPU of these tests reports no device memory, which is one of the
two cases; the other is a stand-in device with the TPU runtime's keys.
"""

import dataclasses
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.telemetry import (
    load_flight_dumps,
    perf,
    reset_recorder,
)
from dlrover_wuqiong_tpu.telemetry import memory as tmemory
from dlrover_wuqiong_tpu.telemetry import spans as tspans
from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET_KEYS = {"argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
               "generated_code_bytes", "live_bytes"}
HBM_ATTRS = ("hbm", "hbm_at_entry", "hbm_before", "hbm_after")
STEPS, EVERY = 12, 4


@pytest.fixture(autouse=True)
def _fresh():
    AsyncCheckpointSaver.reset()
    tspans.clear_spans()
    reset_recorder()
    perf._step_executables.clear()
    yield
    AsyncCheckpointSaver.reset()


def _model():
    return GPT(dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                   use_flash_attention=False, remat=False))


def _data(step, batch=8, seq=32, vocab=512):
    rng = np.random.default_rng(step % 4)
    x = rng.integers(0, vocab, (batch, seq + 1))
    return {"input_ids": x[:, :-1], "labels": x[:, 1:]}


def _args(tmp_path, **over):
    base = dict(output_dir=str(tmp_path), max_steps=STEPS,
                global_batch_size=8, seq_len=32, warmup_steps=1,
                logging_steps=EVERY, save_steps=0, fused_steps=1,
                perf_window_every=0, save_on_exit=False,
                strategy=[("fsdp", {})])
    return TrainingArgs(**{**base, **over})


def _named(name):
    return [s for s in tspans.spans_snapshot() if s["name"] == name]


def _compiles(since=0.0):
    """(lowerings and compiles this process began at or after `since` on
    `time.monotonic`'s clock, its questions to the persistent cache).
    `durations` is a ring that drops its oldest record: a sum over the
    whole of it goes DOWN when a worker's 65,536th record falls out, so
    what a test may compare is the records newer than its own mark."""
    from dlrover_wuqiong_tpu.auto import compile_cache

    return (sum(d["name"] in ("jax:backend_compile", "jax:lower")
                and d["t_mono"] >= since
                for d in list(compile_cache.durations)),
            compile_cache.counters.hits + compile_cache.counters.misses)


# ------------------------------------------------ the compiled budget


@pytest.fixture
def trained(tmp_path):
    tr = Trainer(_model(), _args(tmp_path), _data)
    try:
        out = tr.train()
        tr.ckpt.wait_staging(60)
        yield tr, out
    finally:
        tr.ckpt.close()


def test_step_memory_is_the_budget_of_the_step_that_ran(trained):
    tr, _ = trained
    budgets = perf.step_memory()
    assert list(budgets) == [1]  # the fusion width that ran
    budget = budgets[1]
    assert set(budget) == BUDGET_KEYS
    assert budget["live_bytes"] == budget["argument_bytes"] \
        + budget["temp_bytes"] + budget["output_bytes"] \
        - budget["alias_bytes"]
    # the same step, lowered and compiled by hand
    batch = tr.res.place_batch(_data(0))
    mem = tr.res.fused_train_step(1).lower(tr.state, batch).compile() \
        .memory_analysis()
    assert budget == {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "generated_code_bytes": mem.generated_code_size_in_bytes,
        "live_bytes": budget["live_bytes"]}
    # the state is donated: the outputs alias it, and it is counted once
    assert 0 < budget["alias_bytes"] <= budget["argument_bytes"]
    assert budget["live_bytes"] < budget["argument_bytes"] \
        + budget["temp_bytes"] + budget["output_bytes"]


def test_asking_for_the_budget_compiles_nothing(trained):
    # even with the loop's own find forgotten, the way back is answered
    # by JAX's caches: nothing is traced, lowered or compiled
    for find in perf._step_executables.values():
        find.cache_clear()
    mark, (_, asked) = time.monotonic(), _compiles()
    assert perf.step_memory()[1]["live_bytes"] > 0
    assert _compiles(since=mark) == (0, asked)


def test_first_step_carries_the_budget_once_a_width(tmp_path):
    # five steps at K=2: widths 2, 2, 1
    tr = Trainer(_model(), _args(tmp_path, fused_steps=2, max_steps=5,
                                 logging_steps=0), _data)
    try:
        tr.train()
    finally:
        tr.ckpt.close()
    firsts = {f["attrs"]["k"]: f["attrs"]
              for f in _named("trainer:first_step")}
    budgets = perf.step_memory()
    assert sorted(firsts) == sorted(budgets) and len(firsts) == 2
    for k, attrs in firsts.items():
        assert {key: attrs[key] for key in BUDGET_KEYS} == budgets[k]
    # K steps in one program hold K batches and the scan's carries
    wide = max(firsts)
    assert firsts[wide]["argument_bytes"] > firsts[1]["argument_bytes"]


def test_a_step_the_way_back_cannot_find_has_no_budget(tmp_path,
                                                       monkeypatch):
    """Telemetry never kills the run."""
    def lost():
        raise RuntimeError("no way back")

    monkeypatch.setattr(perf, "step_executables", lost)
    tr = Trainer(_model(), _args(tmp_path), _data)
    try:
        assert tr.train()["stopped_at"] == STEPS
    finally:
        tr.ckpt.close()
    (first,) = _named("trainer:first_step")
    assert not BUDGET_KEYS & set(first["attrs"])


def test_compiled_memory_of_a_backend_without_analysis():
    class _NoAnalysis:
        def memory_analysis(self):
            return None

    assert tmemory.compiled_memory(_NoAnalysis()) == {}


# ------------------------------ a backend that reads no device memory


def test_without_device_stats_no_record_is_written(trained):
    _, out = trained
    assert out["stopped_at"] == STEPS
    assert tmemory.device_memory() == [] and tmemory.reading() == {}
    assert not _named("trainer:memory")
    for name in ("trainer:build", "trainer:train"):
        for rec in _named(name):
            assert not set(HBM_ATTRS) & set(rec["attrs"]), name


def test_no_backend_is_attached_to_read_memory(monkeypatch):
    """Asked without devices where no backend stands (the agent, a
    process before its first touch of the chip), the reader gives
    nothing and touches nothing."""
    monkeypatch.setattr(tspans, "backend_attached", lambda: False)
    monkeypatch.setattr(jax, "local_devices",
                        lambda: pytest.fail("attached a backend"))
    assert tmemory.device_memory() == [] and tmemory.reading() == {}


# ------------------------------------- a stand-in device with stats

GIB = 2 ** 30
N = 8  # tests/conftest.py's virtual CPU devices
LAST = N - 1


class _Device:
    """A local device that reports memory as the TPU runtime does: the
    keys of `memory_stats()` on a v5 lite.  The last device is the
    fullest, device 0 the least full; live bytes grow by 1 MiB a call,
    so readings can be told apart."""

    calls = []  # (device id, thread name) of every `memory_stats()`

    def __init__(self, real, ident):
        self._real, self.id = real, ident

    def __getattr__(self, name):
        return getattr(self._real, name)

    def memory_stats(self):
        _Device.calls.append((self.id, threading.current_thread().name))
        n = sum(1 for d, _ in _Device.calls if d == self.id)
        in_use = 8 * GIB + self.id * GIB // 4 + n * 2 ** 20
        return {"num_allocs": 400 + n, "bytes_in_use": in_use,
                "peak_bytes_in_use": in_use + GIB,
                "largest_alloc_size": GIB // 2, "bytes_limit": 16 * GIB,
                "bytes_reserved": 3 * GIB, "peak_bytes_reserved": 4 * GIB,
                "bytes_reservable_limit": 7 * GIB,
                "largest_free_block_bytes": 2 * GIB - self.id * GIB // 8}


@pytest.fixture
def with_stats(monkeypatch):
    # every local device gets one: whoever counts them counts the same
    devices = [_Device(dev, i) for i, dev in enumerate(jax.local_devices())]
    _Device.calls = []
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devices)
    return devices


def test_a_reading_is_the_fullest_device_and_the_least(with_stats):
    per = tmemory.device_memory()
    assert [m["device"] for m in per] == list(range(N))
    assert set(per[0]) == {"device", *tmemory.MEMORY_KEYS}
    hbm = tmemory.reading()
    assert hbm["device"] == LAST and hbm["devices"] == N
    assert hbm["bytes_in_use"] > 9 * GIB > hbm["least_bytes_in_use"] > 8 * GIB
    assert tmemory.headroom_bytes(hbm) == \
        16 * GIB - hbm["bytes_in_use"] - 3 * GIB
    # given devices, only those are read (`detect_hbm_per_device`)
    (only,) = tmemory.device_memory(with_stats[:1])
    assert only["device"] == 0 and only["bytes_limit"] == 16 * GIB


def test_the_mesh_and_the_monitor_read_through_the_one_reader(with_stats):
    from dlrover_wuqiong_tpu.agent.monitor import get_accelerator_stats
    from dlrover_wuqiong_tpu.parallel.mesh import detect_hbm_per_device

    assert detect_hbm_per_device(with_stats) == 16 * GIB
    assert detect_hbm_per_device(jax.devices()[:1]) == 16 << 30  # no stats
    stats = get_accelerator_stats()  # the fullest device, not devs[:1]
    assert stats["num_devices"] == float(N)
    assert stats["hbm_bytes_in_use"] > 9 * GIB
    assert stats["hbm_bytes_reserved"] == 3.0 * GIB
    assert stats["hbm_bytes_limit"] == 16.0 * GIB


@pytest.fixture
def faulted(tmp_path, with_stats):
    """A run with device stats that dies in its data source after the
    third logging boundary: the `fault` flight dump beside the
    checkpoints."""
    trainers = []

    def data(step):
        if step == STEPS + 2:
            # the fault's dump is written before the pump is joined: let
            # the third boundary's work land first, so the dump is one
            deadline = time.monotonic() + 60
            while trainers[0]._pump.stats()["drained"] < STEPS // EVERY \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            raise RuntimeError("the data source died")
        return _data(step)

    tr = Trainer(_model(), _args(tmp_path, max_steps=100), data)
    trainers.append(tr)
    try:
        with pytest.raises(RuntimeError, match="data source died"):
            tr.train()
    finally:
        tr.ckpt.close()
    return tr.ckpt.checkpoint_dir


def test_one_memory_event_a_logging_boundary_from_the_pump(faulted):
    events = _named("trainer:memory")
    assert [e["attrs"]["step"] for e in events] == [4, 8, 12]
    for e in events:
        assert set(e["attrs"]) == {"step", "device", "devices",
                                   "least_bytes_in_use",
                                   *tmemory.MEMORY_KEYS}
        assert e["attrs"]["device"] == LAST  # the fullest
        assert e["attrs"]["bytes_in_use"] > e["attrs"]["least_bytes_in_use"]
    # under the submitting `trainer:log_submit`, in `trainer:train`'s trace
    (train,) = _named("trainer:train")
    submits = {h["span_id"] for h in tspans.hot_spans_snapshot()
               if h["name"] == "trainer:log_submit"}
    assert len(submits) == 3
    assert {e["parent_span"] for e in events} == submits
    assert {e["trace_id"] for e in events} == {train["trace_id"]}
    # each after its boundary's readback, on the pump's thread: one call
    # a device a boundary there, and none on the main thread but at the
    # boundaries that are no step's (build's end, train's entry)
    readbacks = sorted(h["t_mono"] + h["dur_s"]
                       for h in tspans.hot_spans_snapshot()
                       if h["name"] == "pump:readback")
    assert all(r <= e["t_mono"] for r, e in zip(readbacks, events))
    pump = [d for d, t in _Device.calls if t == "dwt-metrics-pump"]
    main = [d for d, t in _Device.calls if t == "MainThread"]
    assert sorted(pump) == sorted(list(range(N)) * 3)
    assert sorted(main) == sorted(list(range(N)) * 2)
    assert len(pump) + len(main) == len(_Device.calls)


def test_the_spans_that_exist_carry_the_readings(faulted):
    (build,) = _named("trainer:build")
    (train,) = _named("trainer:train")
    for rec, key in ((build, "hbm"), (train, "hbm_at_entry")):
        hbm = rec["attrs"][key]
        assert hbm["device"] == LAST and hbm["peak_bytes_in_use"] > 9 * GIB
    assert build["attrs"]["hbm"]["bytes_in_use"] \
        < train["attrs"]["hbm_at_entry"]["bytes_in_use"] \
        < _named("trainer:memory")[0]["attrs"]["bytes_in_use"]
    assert train["status"] == "error"


@pytest.mark.parametrize("stats", [True, False], ids=["stats", "no_stats"])
def test_a_save_carries_the_readings_before_and_after(
        tmp_path, request, incident_report, stats):
    """`ckpt:snapshot`: a save's device-side copy is where a job that
    trains fits and a job that saves does not."""
    from dlrover_wuqiong_tpu.telemetry import get_recorder

    if stats:
        request.getfixturevalue("with_stats")
    tr = Trainer(_model(), _args(tmp_path, save_steps=8), _data)
    try:
        tr.train()
    finally:
        tr.ckpt.close()  # waits for the drain; the span is the save's own
    (snap,) = _named("ckpt:snapshot")
    if not stats:
        assert not set(HBM_ATTRS) & set(snap["attrs"])
        return
    before, after = snap["attrs"]["hbm_before"], snap["attrs"]["hbm_after"]
    assert before["device"] == after["device"] == LAST
    assert before["bytes_in_use"] < after["bytes_in_use"]
    # an operator's dump of the run shows both beside the boundaries'
    get_recorder().flush(tr.ckpt.checkpoint_dir, "sigterm")
    table = incident_report.memory_table(tr.ckpt.checkpoint_dir)
    assert "(dumps: sigterm)" in table
    for label in ("ckpt:snapshot hbm_before", "ckpt:snapshot hbm_after"):
        assert sum(label in ln for ln in table.splitlines()) == 1


def test_a_fault_dump_holds_the_budget_and_the_last_readings(faulted):
    (dump,) = [d for d in load_flight_dumps(faulted)
               if d["reason"] == "fault"]
    spans = [e["data"] for e in dump["events"] if e["kind"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    (first,) = by_name["trainer:first_step"]
    assert BUDGET_KEYS <= set(first["attrs"])
    assert first["attrs"]["live_bytes"] == perf.step_memory()[1]["live_bytes"]
    assert [e["attrs"]["step"] for e in by_name["trainer:memory"]] \
        == [4, 8, 12]
    assert "hbm" in by_name["trainer:build"][0]["attrs"]
    assert "hbm_at_entry" in by_name["trainer:train"][0]["attrs"]


@pytest.fixture
def incident_report():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import incident_report
    finally:
        sys.path.pop(0)
    return incident_report


def test_incident_report_prints_the_memory_of_a_fault(faulted,
                                                      incident_report,
                                                      capsys):
    assert incident_report.main(["--memory", faulted]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert f"pid {os.getpid()} (dumps: fault)" in lines[0]
    budget = perf.step_memory()[1]
    row = lines[lines.index(next(ln for ln in lines
                                 if ln.split()[:2] == ["K", "argument"])) + 1]
    assert row.split() == ["1"] + [
        f"{budget[c + '_bytes'] / GIB:.3f}"
        for c in ("argument", "output", "alias", "temp", "generated_code",
                  "live")]
    records = [ln.split() for ln in lines if "trainer:" in ln
               or "ckpt:" in ln]
    labels = [" ".join(r[1:3]) if r[2].startswith("hbm") else r[1]
              for r in records]
    assert labels == ["trainer:build hbm", "trainer:train hbm_at_entry"] \
        + ["trainer:memory"] * 3
    # up to the fault: every reading lies before the dump, in order
    at = [float(r[0]) for r in records]
    assert at == sorted(at) and at[-1] <= 0.0
    (last,) = [r for r in records if r[2] == "12"]  # the last boundary
    assert last[1] == "trainer:memory" \
        and last[-3:] == [str(LAST), "of", str(N)]
    in_use, reserved, total, _, _, headroom, free, least = map(float,
                                                               last[3:11])
    assert total == pytest.approx(in_use + reserved, abs=2e-3)
    assert headroom == pytest.approx(16 - total, abs=2e-3)
    assert (reserved, free) == (3.0, 1.125) and 8 < least < 9 < in_use


def test_incident_report_memory_without_a_record(tmp_path, incident_report,
                                                 capsys):
    from dlrover_wuqiong_tpu.telemetry import get_recorder

    tspans.span_event("rdzv:formed")
    get_recorder().flush(str(tmp_path), "fault")
    assert incident_report.main(["--memory", str(tmp_path)]) == 1
    assert "no memory record" in capsys.readouterr().err


# ------------------------------------- the benchmark's four readers

RECORDED = os.path.join(ROOT, "tests", "data", "step_memory",
                        "gpt2_124m_steady.json")
READERS = ("step.hbm_live_gib", "step.hbm_temp_gib", "step.hbm_args_gib",
           "device.hbm_window_gib")


@pytest.fixture
def recorded(monkeypatch):
    """`tests/data/step_memory/gpt2_124m_steady.json`: the memory
    records of one traced run of `gpt2_124m.steady` on one TPU v5 lite
    (PR 64) — the program's `trainer:build`, `trainer:first_step`,
    `trainer:train` and `trainer:memory` spans, `step_memory()`, and
    the run's `open` / `trace_stop` marks — put back where the readers
    look: the span buffer and `telemetry.perf.step_memory`."""
    with open(RECORDED) as f:
        rec = json.load(f)
    for span in rec["spans"]:
        tspans._record({"span_id": tspans._new_id(), **span})
    budgets = {int(k): v for k, v in rec["step_memory"].items()}
    monkeypatch.setattr(perf, "step_memory", lambda: budgets)
    return rec


def _read(name, events):
    from benchmark import cells

    cell = cells.load_cell("gpt2_124m.steady")
    return cells.load_module("layer_metrics", name).read(
        None, events, {}, cell)


@pytest.mark.parametrize("name", READERS)
def test_reader_over_a_run_recorded_on_the_chip(recorded, name):
    value = _read(name, recorded["events"])
    assert value == pytest.approx(recorded["expect"][name], rel=1e-9)
    budget = recorded["step_memory"]["1"]
    if name == "step.hbm_live_gib":
        assert value * GIB == budget["argument_bytes"] \
            + budget["temp_bytes"] + budget["output_bytes"] \
            - budget["alias_bytes"]
    if name == "device.hbm_window_gib":
        # the largest CURRENT sum inside the window, not a peak and not
        # a reading from before the window opened
        inside = [s["attrs"] for s in recorded["spans"]
                  if s["name"] == "trainer:memory"
                  and recorded["events"][0]["t_sync"] <= s["t_mono"]
                  <= recorded["events"][-1]["t_sync"]]
        assert 0 < len(inside) < sum(s["name"] == "trainer:memory"
                                     for s in recorded["spans"])
        assert value * GIB == max(a["bytes_in_use"] + a["bytes_reserved"]
                                  for a in inside)
        assert value * GIB < max(a["peak_bytes_in_use"]
                                 + a["peak_bytes_reserved"] for a in inside)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_program_without_the_record(recorded, monkeypatch,
                                                name):
    """The parent of PR 64: no `step_memory`, no `trainer:memory`."""
    monkeypatch.delattr(perf, "step_memory")
    tspans.clear_spans()
    assert _read(name, recorded["events"]) is None
    assert _read(name, []) is None
