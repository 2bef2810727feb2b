"""`benchmark/setup_chain.py` and the five readers of PR 49: set-up as
boot | build | caller | warm-up, read off the program's spans of its own
start on the stamps of a run RECORDED on the chip
(`data/setup_stamps_gpt2_124m.json`: the `stamps` of one traced run of
gpt2_124m.steady, `info` line, PR 49) and a span buffer made by hand to
lie where that run's spans lay."""

import json
import os

import pytest

from benchmark import cells, program, readers, setup_chain

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = cells.load_benchmark()
NEW = ("setup.boot_s", "setup.caller_s", "setup.warmup_s",
       "setup.other_programs_s", "setup.unnamed_s")
KERNEL_LEAD = 0.05  # the kernel's start before the harness's first stamp


@pytest.fixture
def events():
    with open(os.path.join(DATA, "setup_stamps_gpt2_124m.json")) as f:
        stamps = json.load(f)["stamps"]
    # the `info` line keeps no `t_sync`; `window_open` is `open`'s
    opened = readers.first(stamps, "window_open")["t"]
    return [dict(e, t_sync=opened) if e["ev"] == "open" else dict(e)
            for e in stamps]


def _at(events, ev):
    return readers.first(events, ev)["t"]


def _span(name, t0, t1, **attrs):
    return {"name": name, "t_mono": t0, "dur_s": t1 - t0, "attrs": attrs,
            "span_id": name, "parent_span": "", "status": "ok"}


@pytest.fixture
def chain(events, monkeypatch):
    """The program's buffer as PR 49 leaves it, by hand: boot from the
    kernel's start to the Trainer, build up to `trainer_built`, train
    from `train_enter` to past the window's end."""
    start = _at(events, "proc_start") - KERNEL_LEAD
    built = _at(events, "trainer_built") - 0.002
    build0 = _at(events, "device_ready") + 0.4
    train0 = _at(events, "train_enter") + 0.001
    spans = [
        _span("proc:boot", start, build0, backend_attached_by="caller"),
        _span("accelerate:init_state", build0 + 1.0, built - 0.5),
        _span("trainer:build", build0, built),
        _span("trainer:first_step", train0 + 0.1, train0 + 0.9, k=1),
        # written when train() returned: it ENDS after the window
        _span("trainer:train", train0, _at(events, "open") + 60.0),
        _span("trainer:build", _at(events, "open") + 70.0,
              _at(events, "open") + 71.0),  # a later Trainer: not set-up
    ]
    monkeypatch.setattr(program, "setup_spans", lambda: list(spans))
    return {"start": start, "build0": build0, "built": built,
            "train0": train0, "spans": spans}


def test_new_metrics_are_appended_and_every_cell_reports_them():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert tuple(names[-len(NEW):]) == NEW
    for m in BENCH["per_layer"][-len(NEW):]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("s", "lower", "program_span", "setup_s")
    for w in BENCH["workloads"]:
        cell = cells.load_cell(w["name"])
        assert set(NEW) <= {m["name"] for m in cell["per_layer"]}


def test_the_recorded_run_has_the_stamps_the_readers_use(events):
    order = ["proc_start", "device_ready", "trainer_built", "seeded",
             "system_checked", "init_checked", "train_enter", "open"]
    stamps = [_at(events, ev) for ev in order]
    assert stamps == sorted(stamps)
    assert readers.last(events, "open")["t_sync"] == \
        _at(events, "window_open")


def test_the_four_parts_partition_set_up(events, chain):
    t_open = _at(events, "window_open")
    boot, build, caller, warm = setup_chain.parts_s(events)
    assert boot == pytest.approx(chain["build0"] - chain["start"])
    assert build == pytest.approx(chain["built"] - chain["build0"])
    assert caller == pytest.approx(chain["train0"] - chain["built"])
    assert warm == pytest.approx(t_open - chain["train0"])
    # each link ends on the next one's start: the sum is the whole
    assert boot + build + caller + warm == \
        pytest.approx(t_open - chain["start"])
    setup_s = cells.load_module("end_to_end", "setup_s").read(
        None, events, {}, {})
    assert setup_chain.unnamed_s(events) == \
        pytest.approx(KERNEL_LEAD, abs=1e-6)
    assert boot + build + caller + warm - setup_s == \
        pytest.approx(KERNEL_LEAD, abs=1e-6)
    # what the recorded run's own stamps say of the same stretches
    assert caller == pytest.approx(
        _at(events, "train_enter") - _at(events, "trainer_built"), abs=0.01)
    assert build <= _at(events, "trainer_built") - \
        _at(events, "device_ready")


@pytest.mark.parametrize("name,fn", [
    ("setup.boot_s", setup_chain.boot_s),
    ("setup.caller_s", setup_chain.caller_s),
    ("setup.warmup_s", setup_chain.warmup_s),
    ("setup.unnamed_s", setup_chain.unnamed_s)])
def test_a_reader_reads_its_part(events, chain, name, fn):
    mod = cells.load_module("layer_metrics", name)
    value = mod.read(None, events, {}, {})
    assert value is not None and value == fn(events)
    assert mod.read(None, [], {}, {}) is None  # no `open`: no window


@pytest.mark.parametrize("name", [n for n in NEW
                                  if n != "setup.other_programs_s"])
def test_a_buffer_without_the_new_spans_reads_nothing(
        events, monkeypatch, name):
    """The parent of PR 49: `trainer:build` and its children, no
    `proc:boot`, no `trainer:train`."""
    old = [_span("accelerate:init_state", 1.0, 2.0),
           _span("trainer:build", 0.5, 3.0)]
    monkeypatch.setattr(program, "setup_spans", lambda: list(old))
    mod = cells.load_module("layer_metrics", name)
    assert mod.read(None, events, {}, {}) is None
    assert setup_chain.parts_s(events) is None
    # the accepted reader still reads what it read
    assert program.setup_span_s(
        [{"ev": "open", "t": 9.0, "t_sync": 9.0}], "trainer:build") == 2.5


def test_train_is_found_by_its_start_and_build_by_its_end(events, chain):
    t_open = _at(events, "window_open")
    train = setup_chain._last("trainer:train", t_open, end=False)
    assert train["t_mono"] == chain["train0"]
    assert train["t_mono"] + train["dur_s"] > t_open
    # `program.setup_span_s` asks for spans that ENDED before `open`
    assert program.setup_span_s(events, "trainer:train") is None
    build = setup_chain._last("trainer:build", t_open, end=True)
    assert build["t_mono"] == chain["build0"]


def test_other_programs_leave_the_steps_two_functions_out(
        events, monkeypatch):
    t_open = _at(events, "window_open")

    class Cache:
        durations = [
            {"name": n, "fun_name": f, "t_mono": t_open - back,
             "dur_s": d}
            for n, f, back, d in (
                ("jax:trace", "train_step", 20.0, 2.0),
                ("jax:lower", "jit(train_step)", 18.0, 1.0),
                ("jax:backend_compile", "jit(fused_train_step)", 17.0, 3.0),
                # traced inside the step's trace: the step's
                ("jax:trace", "_where", 19.5, 0.5),
                # the init, an eager helper, the reference
                ("jax:trace", "_create_state", 40.0, 1.5),
                ("jax:backend_compile", "jit(_create_state)", 38.0, 4.0),
                ("jax:lower", "jit(loss)", 10.0, 0.25),
                # inside the init's compile: covered once
                ("jax:lower", "jit(inner)", 37.0, 1.0),
                # the retrieval lies inside the compile it served
                ("jax:cache_load", "jit(_create_state)", 38.0, 3.5),
                # ends after `open`
                ("jax:trace", "helper", 0.5, 1.0))]

    monkeypatch.setattr(program, "_module",
                        lambda name: Cache if name == "auto.compile_cache"
                        else None)
    assert setup_chain.other_programs_s(events) == \
        pytest.approx(1.5 + 4.0 + 0.25)
    mod = cells.load_module("layer_metrics", "setup.other_programs_s")
    assert mod.read(None, events, {}, {}) == \
        setup_chain.other_programs_s(events)
    Cache.durations = [r for r in Cache.durations
                       if "train_step" in r["fun_name"]]
    assert setup_chain.other_programs_s(events) is None
    monkeypatch.setattr(program, "_module", lambda name: None)
    assert setup_chain.other_programs_s(events) is None
