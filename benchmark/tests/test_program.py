"""`benchmark/program.py`: what the PR 24 readers take from inside the
program — spans cut to the window or to set-up, the `jax:*` duration
records of the step's function, and the split of a traced step by scope
on a pair RECORDED on the chip (`data/steady_scoped_2steps.*`: two
optimizer steps of gpt2_124m.steady and the scope table of the step
program that ran them; `record.py` says how)."""

import gzip
import json
import os
import time

import pytest

from benchmark import cells, program, xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = cells.load_benchmark()
NEW = ("step.attn_dense_ms", "step.mlp_ms", "step.head_loss_ms",
       "step.optimizer_ms", "step.unscoped_ms", "trainer.dispatch_ms",
       "trainer.loop_self_ms", "setup.build_s", "setup.state_init_s",
       "setup.trace_lower_s", "setup.compile_load_s")


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    return (_load("steady_scoped_2steps.json.gz"),
            _load("steady_scoped_2steps.scopes.json.gz"))


@pytest.fixture
def spans():
    """The program's own span module, emptied around the test."""
    from dlrover_wuqiong_tpu.auto import compile_cache
    from dlrover_wuqiong_tpu.telemetry import spans as tspans

    tspans.clear_spans()
    compile_cache.durations.clear()
    yield tspans
    tspans.clear_spans()
    compile_cache.durations.clear()


def test_new_metrics_are_appended_and_every_cell_reports_them():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert tuple(names[-len(NEW):]) == NEW
    for m in BENCH["per_layer"][-len(NEW):]:
        assert "workloads" not in m and m["better"] == "lower"
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup.")
                              else "tokens_per_s")
    for w in BENCH["workloads"]:
        cell = cells.load_cell(w["name"])
        assert set(NEW) <= {m["name"] for m in cell["per_layer"]}


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_buffers(
        name, monkeypatch):
    """The parent of PR 24 has no per-step ring, no duration records, no
    kept executable: a traced run there leaves the metric out."""
    monkeypatch.setattr(program, "_module", lambda name: None)
    monkeypatch.setattr(program, "_table", None)
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "step": 10},
              {"ev": "trace_stop", "t": 3.0, "t_sync": 3.0, "step": 30}]
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, 10]],
                               "ops": [["fusion.1", 0, 10]]}}, "host": []}
    cell = cells.load_cell(BENCH["workloads"][0]["name"])
    mod = cells.load_module("layer_metrics", name)
    assert mod.read(trace, events, {}, cell) is None


GPT_RULES = program.part_rules("gpt")


@pytest.mark.parametrize("scope,part", [
    ("fwd/GPT/h/mlp/c_fc", "mlp"), ("bwd/GPT/h/mlp", "mlp"),
    ("recompute/GPT/GPT/h/mlp/c_proj", "mlp"),
    ("bwd/GPT/h/attn/c_attn", "attn_dense"),
    ("fwd/GPT/h/attn/c_proj", "attn_dense"),
    ("fwd/GPT/h/attn", "unscoped"),  # reshapes around the kernel
    ("fwd/GPT/head/bte,ve->btv", "head_loss"), ("bwd/loss", "head_loss"),
    ("optimizer", "optimizer"), ("fwd/GPT/h/ln_1", "unscoped"),
    ("bwd/GPT/wte", "unscoped"), ("", "unscoped"),
])
def test_part_of(scope, part):
    assert program.part_of(scope, GPT_RULES) == part


def test_every_model_class_names_the_parts_the_readers_ask_for():
    """Each `step.<part>_ms` reader asks for one part: a class's rules
    name all four (`unscoped` is the remainder), as lists of path
    components."""
    asked = {n.removeprefix("step.").removesuffix("_ms")
             for n in NEW if n.startswith("step.")} - {program.UNSCOPED}
    models = os.path.join(cells.HERE, "models")
    files = [f for f in os.listdir(models) if f.endswith(".scopes.json")]
    assert "gpt.scopes.json" in files
    for f in files:
        model_class = f.removesuffix(".scopes.json")
        assert os.path.isfile(os.path.join(models, model_class + ".py"))
        rules = program.part_rules(model_class)
        assert set(rules) == asked, f
        for alternatives in rules.values():
            assert alternatives and all(
                alt and all(isinstance(c, str) and c for c in alt)
                for alt in alternatives), f


def test_a_model_class_without_rules_reports_no_split(
        recorded, monkeypatch):
    """Not a silent `unscoped`: every-cell metrics left out of a traced
    run are refused, and that says a file is missing."""
    trace, table = recorded
    monkeypatch.setattr(program, "_table", table)
    cell = {"config": {"model_class": "gpt"}}
    assert program.part_ms(trace, cell, "mlp") > 0
    assert program.part_rules("no_such_class") is None
    cell = {"config": {"model_class": "no_such_class"}}
    for part in (*GPT_RULES, program.UNSCOPED):
        assert program.part_ms(trace, cell, part) is None


def test_split_of_the_recorded_steps_sums_to_their_op_time(recorded):
    trace, table = recorded
    assert "optimizer" in table.values()
    split = program.split_ms(trace, table, GPT_RULES)
    assert set(split) == {*GPT_RULES, program.UNSCOPED}
    attn = xtrace.per_step_ms(trace, ("dwt_fa_",))
    ops, n = xtrace.ops_in_steps(trace, "0")
    assert n == 2
    total = sum(o[2] for o in ops) / n / 1e6
    assert sum(split.values()) + attn == pytest.approx(total, rel=1e-9)
    # one chip: no collectives; ops are sequential, so the op time is
    # the module's time to well under 1%
    assert total == pytest.approx(xtrace.step_device_ms(trace), rel=0.01)
    # the naming is done: what no part claims is a small share
    assert split["unscoped"] < 0.15 * total
    assert min(split.values()) > 0
    assert split["mlp"] > split["attn_dense"] > split["optimizer"]


def test_spans_are_cut_to_the_window(spans):
    def iteration(data_s=0.0):
        with spans.hot_span("trainer:iteration"):
            with spans.hot_span("trainer:data"):
                time.sleep(data_s)
            with spans.hot_span("trainer:dispatch"):
                time.sleep(0.004)
            time.sleep(0.002)  # the loop's own work

    iteration()  # warm-up: before the window
    t_open = time.monotonic()
    for _ in range(4):
        iteration(0.001)
    t_end = time.monotonic()
    iteration()  # after it
    events = [{"ev": "open", "t": t_open, "t_sync": t_open, "step": 1},
              {"ev": "close", "t": t_end, "t_sync": t_end, "step": 5}]
    disp = program.window_ms_per_step(events, "trainer:dispatch")
    assert 4.0 <= disp < 8.0
    self_ms = program.loop_self_ms(events)
    assert 2.0 <= self_ms < 4.0  # neither the data nor the dispatch
    assert program.window_ms_per_step(events, "trainer:eval") is None
    assert program.window_ms_per_step([], "trainer:dispatch") is None
    mod = cells.load_module("layer_metrics", "trainer.dispatch_ms")
    assert mod.read(None, events, {}, {}) == pytest.approx(disp)


def test_set_up_spans_and_step_durations_end_before_open(spans):
    from dlrover_wuqiong_tpu.auto import compile_cache

    with spans.span("trainer:build"):
        with spans.span("accelerate:init_state"):
            time.sleep(0.01)
    now = time.monotonic()
    for name, fun, dur in (("jax:trace", "train_step", 2.0),
                           ("jax:lower", "jit(train_step)", 1.0),
                           ("jax:backend_compile", "jit(train_step)", 5.0),
                           ("jax:cache_load", "jit(train_step)", 4.0),
                           ("jax:trace", "_where", 9.0),
                           ("jax:backend_compile", "jit(_create_state)",
                            7.0)):
        compile_cache.durations.append(
            {"name": name, "fun_name": fun, "t_mono": now - dur - 1,
             "dur_s": dur})
    t_open = time.monotonic()
    with spans.span("trainer:build"):  # a later one: not set-up
        pass
    compile_cache.durations.append(
        {"name": "jax:trace", "fun_name": "train_step", "t_mono": t_open,
         "dur_s": 3.0})
    events = [{"ev": "open", "t": t_open, "t_sync": t_open, "step": 1}]
    build = program.setup_span_s(events, "trainer:build")
    init = program.setup_span_s(events, "accelerate:init_state")
    assert 0.01 <= init <= build < 1.0
    assert program.setup_step_durations_s(
        events, ("jax:trace", "jax:lower")) == pytest.approx(3.0)
    assert program.setup_step_durations_s(
        events, ("jax:backend_compile",)) == pytest.approx(5.0)
    assert program.setup_span_s(events, "ckpt:open") is None
    assert program.setup_span_s([], "trainer:build") is None


def test_an_executable_from_before_the_scopes_gives_no_table(monkeypatch):
    monkeypatch.setattr(program, "_table", {"fusion.1": "fwd/GPT/h/mlp"})
    assert program.scope_table() is None
    cell = {"config": {"model_class": "gpt"}}
    assert program.part_ms({"devices": {}}, cell, "mlp") is None
    monkeypatch.setattr(program, "_table",
                        {"fusion.1": "fwd/GPT/h/mlp", "f.2": "optimizer"})
    assert program.scope_table() is not None
