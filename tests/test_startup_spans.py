"""The start of a process as one chain of spans (telemetry/spans.py,
trainer/trainer.py): `proc:boot` from the kernel's start of the process
to `Trainer.__init__`, `trainer:build`, the caller's gap, `trainer:train`
with every iteration under it, `trainer:first_step` once a fusion width,
`backend:attach` where the program makes the first touch of the devices.

Counts and orderings only: no wall-time limit anywhere.  The chain is
read in CHILD processes (a process writes `proc:boot` once, and the test
worker has long booted); what needs no fresh process runs here.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from dlrover_wuqiong_tpu.telemetry import spans as tspans
from dlrover_wuqiong_tpu.telemetry.recorder import (
    load_flight_dumps,
    reset_recorder,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A nano Trainer at an explicit fusion width of 2 over 5 steps (widths 2
# and 1), then a second Trainer whose data fails at its third step.
# argv[1] = "touch": the caller attaches the backend before the program.
CHAIN_SCRIPT = r"""
import dataclasses, json, sys, tempfile
import numpy as np
if sys.argv[1] == "touch":
    import jax
    jax.devices()
import jax.numpy as jnp
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.telemetry import spans
from dlrover_wuqiong_tpu.telemetry.recorder import load_flight_dumps
from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs


def data(step, fail_at=None):
    if step == fail_at:
        raise RuntimeError("injected data fault")
    x = np.random.default_rng(step % 4).integers(0, 512, (8, 33))
    return {"input_ids": x[:, :-1], "labels": x[:, 1:]}


def trainer(tag, data_fn):
    model = GPT(dataclasses.replace(
        GPTConfig.nano(), dtype=jnp.float32, use_flash_attention=False,
        remat=False))
    args = TrainingArgs(
        output_dir=tempfile.mkdtemp(prefix=tag), max_steps=5,
        global_batch_size=8, seq_len=32, logging_steps=2, fused_steps=2,
        save_steps=0, save_on_exit=False, perf_window_every=0,
        strategy=[("fsdp", {})])
    return Trainer(model, args, data_fn)


first = trainer("a", data)
first.train()
first.ckpt.close()
out = {"first": spans.spans_snapshot(), "hot": spans.hot_spans_snapshot()}
second = trainer("b", lambda step: data(step, fail_at=2))
try:
    second.train()
except RuntimeError as e:
    out["fault"] = str(e)
second.ckpt.close()
out["both"] = spans.spans_snapshot()
out["dumps"] = [
    {"reason": d["reason"],
     "spans": [e["data"] for e in d["events"] if e["kind"] == "span"]}
    for d in load_flight_dumps(second.ckpt.checkpoint_dir)]
print(json.dumps(out))
"""


def _child(code: str, *argv: str, timeout: float = 240) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for name in ("DWT_TRACE_ID", "DWT_TRACE_PARENT"):
        env.pop(name, None)
    proc = subprocess.run([sys.executable, "-c", code, *argv], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def program_first():
    """The program makes the first touch of the devices."""
    return _child(CHAIN_SCRIPT, "program")


@pytest.fixture(scope="module")
def caller_first():
    """The caller has called `jax.devices()` before the Trainer."""
    return _child(CHAIN_SCRIPT, "touch")


@pytest.fixture
def buffers():
    """This process's span buffer and flight recorder, emptied."""
    tspans.clear_spans()
    yield reset_recorder()
    tspans.clear_spans()
    reset_recorder()


def _named(spans: list, name: str) -> list:
    return [s for s in spans if s["name"] == name]


def _end(span: dict) -> float:
    return span["t_mono"] + span["dur_s"]


# ------------------------------------------------- the process's start


def test_process_start_is_stable_and_lies_in_the_past():
    start = tspans.process_start()
    assert tspans.process_start() is start
    assert start[0] <= tspans._IMPORTED[0] <= time.monotonic()
    assert start[1] <= tspans._IMPORTED[1] <= time.time()


def test_a_childs_start_is_not_before_its_parents():
    code = ("import json, time\n"
            "from dlrover_wuqiong_tpu.telemetry import spans\n"
            "import sys\n"
            "print(json.dumps({'start': spans.process_start(), "
            "'now': time.monotonic(), 'jax': 'jax' in sys.modules}))\n")
    before = time.monotonic()
    child = _child(code, timeout=60)
    # one clock for every process of the machine; the kernel's stamp
    # counts in ticks of 10 ms
    assert tspans.process_start()[0] < child["start"][0] <= child["now"]
    assert child["start"][0] >= before - 0.02
    assert not child["jax"]  # reading the start loads no runtime


@pytest.mark.parametrize("fault", [OSError, ValueError])
def test_without_proc_the_start_is_the_modules_import(monkeypatch, fault):
    def refuse(*a, **kw):
        raise fault("no /proc here")

    monkeypatch.setattr(tspans, "_PROCESS_START", None)
    monkeypatch.setattr("builtins.open", refuse)
    assert tspans.process_start() == tspans._IMPORTED


# ------------------------------------------- a span that lies in the past


def test_a_past_span_reaches_buffer_and_recorder_with_its_start(buffers):
    t1 = time.monotonic()
    with tspans.span("outer") as outer:
        rec = tspans.past_span("stretch", t1 - 5.0, t1 - 2.0, {"k": 2})
    assert rec["t_mono"] == t1 - 5.0 and rec["dur_s"] == pytest.approx(3.0)
    assert rec["t_wall"] + 5.0 == pytest.approx(time.time(), abs=1.0)
    assert (rec["parent_span"], rec["trace_id"]) == \
        (outer["span_id"], outer["trace_id"])
    assert set(rec) == set(outer)  # the same record
    assert _named(tspans.spans_snapshot(), "stretch") == [rec]
    flown = [e for e in buffers.snapshot() if e["name"] == "stretch"]
    assert len(flown) == 1 and flown[0]["kind"] == "span"
    assert flown[0]["data"]["t_mono"] == t1 - 5.0
    assert flown[0]["t_mono"] >= t1  # the event itself is stamped now


def test_a_past_span_beside_a_record_is_its_sibling(buffers):
    with tspans.span("root"):
        with tspans.span("second") as second:
            rec = tspans.past_span("first", 1.0, second["t_mono"],
                                   beside=second)
    assert rec["parent_span"] == second["parent_span"] != ""
    assert rec["trace_id"] == second["trace_id"]
    assert _end(rec) == pytest.approx(second["t_mono"])


def test_the_boot_span_is_written_once_a_process(buffers, monkeypatch):
    import jax

    jax.devices()  # the caller's attach
    monkeypatch.setattr(tspans, "_BOOT_WRITTEN", False)
    with tspans.span("trainer:build") as build:
        boot = tspans.boot_span(beside=build)
        assert tspans.boot_span(beside=build) is None
    assert boot["t_mono"] == tspans.process_start()[0]
    assert _end(boot) == pytest.approx(build["t_mono"])
    assert boot["attrs"] == {"backend_attached_by": "caller"}
    assert len(_named(tspans.spans_snapshot(), "proc:boot")) == 1


def test_no_attach_span_where_a_backend_stands(buffers):
    import jax

    jax.devices()
    assert tspans.backend_attached()
    with tspans.backend_attach("devices") as rec:
        pass
    assert rec is None and not tspans.spans_snapshot()


def test_compile_seconds_between_two_instants():
    from dlrover_wuqiong_tpu.auto import compile_cache

    kept = list(compile_cache.durations)
    compile_cache.durations.clear()
    try:
        # the ring's order: a record is appended when its stretch ends
        for name, t0, dur in (("jax:trace", 1.0, 5.0),  # before
                              ("jax:trace", 10.5, 1.0),  # nested
                              ("jax:trace", 10.0, 2.0),
                              ("jax:lower", 12.0, 1.0),
                              ("jax:cache_load", 13.5, 3.0),
                              ("jax:backend_compile", 13.0, 4.0),
                              ("jax:lower", 19.0, 2.0)):  # runs over
            compile_cache.durations.append(
                {"name": name, "fun_name": "f", "t_mono": t0, "dur_s": dur})
        assert compile_cache.seconds_between(10.0, 20.0) == {
            "trace_s": 2.0, "lower_s": 1.0, "backend_compile_s": 4.0,
            "cache_load_s": 3.0}
        assert set(compile_cache.seconds_between(30.0, 31.0).values()) \
            == {0.0}
    finally:
        compile_cache.durations.clear()
        compile_cache.durations.extend(kept)


# -------------------------------------------------- the worker's chain


@pytest.mark.parametrize("name", ["proc:boot", "trainer:build",
                                  "trainer:train", "accelerate:plan",
                                  "accelerate:init_state", "ckpt:open"])
def test_one_of_each_link_a_trainer(program_first, name):
    assert len(_named(program_first["first"], name)) == 1


def test_the_links_chain_to_the_same_stamp(program_first):
    spans = program_first["first"]
    boot, build, train = (_named(spans, n)[0] for n in (
        "proc:boot", "trainer:build", "trainer:train"))
    assert _end(boot) == pytest.approx(build["t_mono"], abs=1e-6)
    assert boot["t_mono"] < build["t_mono"] < _end(build) \
        <= train["t_mono"] < _end(train)
    # boot is build's sibling, not its child
    assert (boot["parent_span"], boot["trace_id"]) == \
        (build["parent_span"], build["trace_id"])
    for child in ("accelerate:plan", "accelerate:init_state", "ckpt:open"):
        assert _named(spans, child)[0]["parent_span"] == build["span_id"]


def test_train_closes_with_where_it_started_and_stopped(program_first):
    train = _named(program_first["first"], "trainer:train")[0]
    assert train["status"] == "ok"
    assert train["attrs"] == {"start_step": 0, "restored_tier": "",
                              "stopped_at": 5}
    restore = _named(program_first["first"], "ckpt:restore")
    assert [r["parent_span"] for r in restore] == [train["span_id"]]


@pytest.mark.parametrize("child,parent", [
    ("trainer:iteration", "trainer:train"),
    ("trainer:dispatch", "trainer:iteration"),
    ("trainer:data", "trainer:iteration")])
def test_the_loops_spans_hang_where_they_did_one_level_down(
        program_first, child, parent):
    train = _named(program_first["first"], "trainer:train")[0]
    hot = program_first["hot"]
    parents = {train["span_id"]} if parent == "trainer:train" \
        else {s["span_id"] for s in _named(hot, parent)}
    kids = _named(hot, child)
    assert len(kids) == 3  # widths 2, 2, 1 over five steps
    assert {k["parent_span"] for k in kids} <= parents
    assert {k["trace_id"] for k in kids} == {train["trace_id"]}


def test_one_first_step_a_fusion_width(program_first):
    firsts = _named(program_first["first"], "trainer:first_step")
    assert sorted(f["attrs"]["k"] for f in firsts) == [1, 2]
    iterations = {s["span_id"] for s in
                  _named(program_first["hot"], "trainer:iteration")}
    dispatches = _named(program_first["hot"], "trainer:dispatch")
    for f in firsts:
        assert f["parent_span"] in iterations
        # and, since PR 64, the compiled budget of the program it
        # dispatched (tests/test_step_memory.py)
        assert set(f["attrs"]) == {"k", "blk_s", "trace_s", "lower_s",
                                   "backend_compile_s", "cache_load_s",
                                   "argument_bytes", "output_bytes",
                                   "alias_bytes", "temp_bytes",
                                   "generated_code_bytes", "live_bytes"}
        assert f["dur_s"] == pytest.approx(f["attrs"]["blk_s"])
        # the stretch is the dispatch call's: it holds that span
        held = [d for d in dispatches if f["t_mono"] <= d["t_mono"]
                and _end(d) <= _end(f) + 1e-6]
        assert len(held) == 1
        # a first dispatch traces and lowers whatever the cache holds
        assert 0 < f["attrs"]["trace_s"] + f["attrs"]["lower_s"] \
            + f["attrs"]["backend_compile_s"] <= f["dur_s"]


def test_a_second_trainer_boots_nothing(program_first):
    both = program_first["both"]
    assert len(_named(both, "proc:boot")) == 1
    assert len(_named(both, "trainer:build")) == 2
    assert len(_named(both, "backend:attach")) == 1


@pytest.mark.parametrize("who", ["program", "caller"])
def test_who_attached_the_backend(program_first, caller_first, who):
    spans = (program_first if who == "program" else caller_first)["first"]
    boot = _named(spans, "proc:boot")[0]
    assert boot["attrs"] == {"backend_attached_by": who}
    attach = _named(spans, "backend:attach")
    if who == "caller":
        assert not attach
        return
    plan = _named(spans, "accelerate:plan")[0]
    assert [a["parent_span"] for a in attach] == [plan["span_id"]]
    assert attach[0]["attrs"] == {"via": "devices"}
    assert plan["t_mono"] <= attach[0]["t_mono"] \
        and _end(attach[0]) <= _end(plan)


def test_a_faults_dump_holds_the_chain_to_its_end(program_first):
    assert program_first["fault"] == "injected data fault"
    dumps = [d for d in program_first["dumps"] if d["reason"] == "fault"]
    assert len(dumps) == 1
    spans = dumps[0]["spans"]
    assert len(_named(spans, "proc:boot")) == 1
    assert len(_named(spans, "trainer:build")) == 2
    ok, failed = _named(spans, "trainer:train")
    assert (ok["status"], failed["status"]) == ("ok", "error")
    assert failed["attrs"]["stopped_at"] == 2
    # the span ended where the fault was caught and nowhere else
    assert len(_named(program_first["both"], "trainer:train")) == 2


def test_the_loops_own_time_is_read_one_level_below_train(buffers):
    """`trainer.loop_self_ms` takes `trainer:iteration` minus its direct
    children by `parent_span`: a new grandparent moves nothing."""
    from benchmark import program

    def iterations():
        for _ in range(3):
            with tspans.hot_span("trainer:iteration"):
                with tspans.hot_span("trainer:dispatch"):
                    pass

    t_open = time.monotonic()
    iterations()
    bare = tspans.hot_spans_snapshot()
    tspans.clear_spans()
    with tspans.span("trainer:train") as train:
        iterations()
    under = tspans.hot_spans_snapshot()
    events = [{"ev": "open", "t_sync": t_open, "step": 0},
              {"ev": "close", "t_sync": time.monotonic(), "step": 3}]
    assert {s["parent_span"] for s in _named(bare, "trainer:iteration")} \
        == {""}
    assert {s["parent_span"] for s in _named(under, "trainer:iteration")} \
        == {train["span_id"]}
    its = _named(under, "trainer:iteration")
    own = sum(s["dur_s"] for s in its) - sum(
        s["dur_s"] for s in _named(under, "trainer:dispatch"))
    assert program.loop_self_ms(events) == pytest.approx(own / 3 * 1e3)


def test_flight_dumps_are_read_back(tmp_path, buffers):
    """What the operator's table is made from: a past span's record
    survives the flush as it was written."""
    rec = tspans.past_span("trainer:first_step", 3.0, 4.5, {"k": 1})
    buffers.flush(str(tmp_path), "drill")
    (dump,) = load_flight_dumps(str(tmp_path))
    (evt,) = [e for e in dump["events"] if e["name"] == rec["name"]]
    assert evt["data"] == json.loads(json.dumps(rec))


# ------------------------------------- one tree across agent and worker

# imports no JAX; fails once, then succeeds
RESTART_WORKER = r"""
import json, os, sys
from dlrover_wuqiong_tpu.common.constants import NodeEnv
from dlrover_wuqiong_tpu.telemetry import spans

gen = int(os.environ[NodeEnv.RESTART_COUNT])
with spans.span("worker:first") as rec:
    pass
with open(os.path.join(sys.argv[1], f"first_r{gen}.json"), "w") as f:
    json.dump({"span": rec, "jax": "jax" in sys.modules}, f)
sys.exit(1 if gen == 0 else 0)
"""


@pytest.fixture(scope="module")
def restart(tmp_path_factory):
    """The CLI over a worker that exits 1 once: the agent's dump written
    at its exit, and each worker generation's first span."""
    import tempfile

    tmp = tmp_path_factory.mktemp("restart")
    script = tmp / "worker.py"
    script.write_text(RESTART_WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               DWT_JOB_NAME="spans-e2e", DWT_CKPT_DIR=str(tmp / "ckpt"),
               DWT_SOCKET_DIR=tempfile.mkdtemp(prefix="dwt-sp-"),
               DWT_WARM_POOL="0")
    for name in ("DWT_TRACE_ID", "DWT_TRACE_PARENT"):
        env.pop(name, None)
    proc = subprocess.run(
        [sys.executable, "-m", "dlrover_wuqiong_tpu.run", "--standalone",
         "--nproc_per_node=1", "--max_restarts=2", str(script), str(tmp)],
        env=env, capture_output=True, text=True, timeout=150, cwd=ROOT)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    dumps = load_flight_dumps(str(tmp / "ckpt"))
    workers = []
    for gen in (0, 1):
        with open(tmp / f"first_r{gen}.json") as f:
            workers.append(json.load(f))
    return {"dumps": dumps, "workers": workers, "ckpt": str(tmp / "ckpt")}


def _agent_spans(restart) -> list:
    (dump,) = [d for d in restart["dumps"] if d["reason"] == "agent-exit"]
    assert dump["role"] == "agent"
    return [e["data"] for e in dump["events"] if e["kind"] == "span"]


def test_a_restart_is_one_tree_across_agent_and_worker(restart):
    spans = _agent_spans(restart)
    gens = _named(spans, "agent:generation")
    assert [g["attrs"] for g in gens] == [
        {"restart_count": 0, "exit_code": 1},
        {"restart_count": 1, "exit_code": 0}]
    assert len({g["trace_id"] for g in gens}) == 2  # a tree a generation
    launches = _named(spans, "agent:launch_worker")
    assert [s["parent_span"] for s in launches] == \
        [g["span_id"] for g in gens]
    for name, count in (("agent:failure_save", 1), ("agent:stop_worker", 1),
                        ("agent:replication_setup", 2),
                        ("rdzv:elastic-training:join", 2)):
        hits = _named(spans, name)
        assert len(hits) == count, name
        assert {s["parent_span"] for s in hits} <= \
            {g["span_id"] for g in gens}
    exits = _named(spans, "agent:worker_exit")
    assert [e["attrs"]["exit_code"] for e in exits] == [1, 0]
    assert exits[0]["parent_span"] == gens[0]["span_id"]
    assert exits[0]["attrs"]["poll_interval_s"] == 1.0
    # the failure's report to the master (verb `report`, a NodeFailure)
    (report,) = [s for s in _named(spans, "rpc:report")
                 if s["attrs"]["msg"] == "NodeFailure"]
    by_id = {s["span_id"]: s for s in spans}
    while report["parent_span"] in by_id and \
            report["name"] != "agent:generation":
        report = by_id[report["parent_span"]]
    assert report is gens[0]
    # the worker's first span hangs under the launch that started it
    for launch, worker in zip(launches, restart["workers"]):
        first = worker["span"]
        assert not worker["jax"]
        assert (first["trace_id"], first["parent_span"]) == \
            (launch["trace_id"], launch["span_id"])
        assert first["pid"] == launch["attrs"]["worker_pid"]
        assert first["role"] == "trainer"
    assert len(_named(spans, "cli:master")) == 1


def test_the_faults_own_dump_is_written_before_it_is_dealt_with(restart):
    (fault,) = [d for d in restart["dumps"]
                if d["reason"] == "worker-fault"]
    names = [e["name"] for e in fault["events"] if e["kind"] == "span"]
    assert "agent:worker_exit" in names
    assert "agent:failure_save" not in names  # what `agent-exit` adds


def test_the_operators_table_of_the_restart(restart):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import incident_report
    finally:
        sys.path.pop(0)
    table = incident_report.restart_table(restart["ckpt"])
    lines = table.splitlines()
    assert lines[0].startswith("restart 1: generation 0 left with exit "
                               "code 1")
    order = [ln.split()[0] for ln in lines[2:]]
    want = ["agent:generation", "agent:worker_exit", "agent:failure_save",
            "rpc:report", "agent:stop_worker", "agent:generation",
            "rdzv:elastic-training:join", "agent:replication_setup",
            "agent:launch_worker"]
    rest = iter(order)
    assert all(name in rest for name in want), order  # in this order
    assert "msg=NodeFailure" in next(ln for ln in lines
                                     if "rpc:report" in ln)
    exit_row = next(ln for ln in lines[2:] if "agent:worker_exit" in ln)
    assert exit_row.split()[1:4] == ["agent", "0.000", "0.000"]
    assert lines[2].startswith("agent:generation")  # depth 0
    assert next(ln for ln in lines if "agent:launch_worker" in ln) \
        .startswith("  agent:launch_worker")
    with pytest.raises(LookupError):
        incident_report.restart_table(os.path.join(restart["ckpt"], "x"))


RECORDED = os.path.join(ROOT, "tests", "data", "restart_flight")


def test_the_table_of_a_restart_recorded_on_the_chip():
    """`tests/data/restart_flight/`: the agent's `agent-exit` dump (the
    killed generation and the one after it; the monitor's polls cut to
    three a generation) and the resumed worker's `resumed` dump of one
    run of the parked `gpt2_124m.kill_resume` cell (PR 49, one TPU v5
    lite).  The worker flushed inside `train()`: `trainer:train` is in no
    dump, and what hung under it goes to the launch that started it."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import incident_report
    finally:
        sys.path.pop(0)
    lines = incident_report.restart_table(RECORDED).splitlines()
    assert lines[0].startswith("restart 1: generation 1 left with exit "
                               "code -9")
    rows = [(len(ln) - len(ln.lstrip())) // 2 for ln in lines[2:]]
    names = [ln.split()[0] for ln in lines[2:]]
    roles = dict(zip(names, (ln.split()[1] for ln in lines[2:])))
    want = [(0, "agent:generation"), (1, "agent:worker_exit"),
            (1, "agent:failure_save"), (1, "rpc:report"),
            (1, "agent:stop_worker"), (0, "agent:generation"),
            (1, "rdzv:elastic-training:join"),
            (1, "agent:replication_setup"), (1, "agent:launch_worker"),
            (2, "proc:boot"), (2, "trainer:build"), (3, "accelerate:plan"),
            (3, "accelerate:init_state"), (3, "ckpt:open"),
            (2, "ckpt:restore"), (3, "ckpt:restore:shm"),
            (2, "trainer:first_step")]
    rest = iter(zip(rows, names))
    assert all(row in rest for row in want), list(zip(rows, names))
    assert roles["proc:boot"] == roles["trainer:first_step"] == "trainer"
    assert roles["agent:launch_worker"] == "agent"
    first = next(ln for ln in lines if "trainer:first_step" in ln)
    for attr in ("k=1", "trace_s=", "lower_s=", "backend_compile_s=",
                 "cache_load_s="):
        assert attr in first
    # starts run from the exit the agent saw, in order down the chain
    starts = {n: float(ln.split()[2]) for n, ln in zip(names, lines[2:])}
    assert starts["agent:worker_exit"] == 0.0
    chain = ["agent:failure_save", "agent:stop_worker",
             "agent:launch_worker", "trainer:build", "ckpt:restore",
             "trainer:first_step"]
    assert [starts[n] for n in chain] == sorted(starts[n] for n in chain)
    polls = next(ln for ln in lines if "WaitingNodeNumRequest" in ln)
    assert polls.rstrip().endswith("(+2 more)")
