"""The arithmetic from the tap's stamps to the end-to-end metrics."""

from benchmark import cells, readers

CELL = cells.load_cell("gpt2_124m.flash_save")


def _ev(ev, step, t, **kw):
    return {"ev": ev, "t": t, "t_sync": t, "gen": 0, "step": step, **kw}


def _events():
    # step = 100 ms, a save adds 30 ms; window [20, 80): three saves
    evs = [{"ev": "proc_start", "t": 0.0, "gen": 0}, _ev("open", 20, 10.0)]
    t = 10.0
    for s in range(20, 80):
        if (s + 1) % 20 == 0:
            evs.append(_ev("pre_save", s, t))
        t += 0.1
        if (s + 1) % 20 == 0:
            t += 0.03
            evs.append(_ev("post_save", s + 1, t))
    evs.append(_ev("close", 80, t))
    evs.append({"ev": "window_open", "t": 10.0, "gen": 0})
    return evs, t


def test_tokens_per_s_counts_steps_between_synced_instants():
    evs, t_close = _events()
    want = 60 * 24 * 1024 / (t_close - 10.0)
    for name in ("tokens_per_s", "saving_tokens_per_s"):
        mod = cells.load_module("end_to_end", name)
        assert abs(mod.read(None, evs, {}, CELL) - want) < 1e-6


def _trace(step_ns):
    return {"devices": {"0": {"modules": [["jit_train_step(1)", 0, step_ns]],
                              "ops": []}}, "host": []}


def test_save_intervals_cycles_and_the_two_save_metrics():
    evs, _ = _events()
    with_save, without = readers.save_intervals(evs, 20)
    assert len(with_save) == 3 and len(without) == 2
    assert all(abs(d - 0.13) < 1e-9 for d in with_save)
    assert all(abs(d - 0.1) < 1e-9 for d in without)
    cycles = readers.save_cycles(evs, 20)
    assert len(cycles) == 2 and all(abs(c - 2.03) < 1e-9 for c in cycles)
    assert readers.window_saves(evs, 20) == 3 and readers.window_steps(evs) == 60
    trace = _trace(100e6)  # step.device_ms = 100
    stall = cells.load_module("layer_metrics", "ckpt.stall_ms")
    cost = cells.load_module("layer_metrics", "ckpt.save_cost_ms")
    assert abs(stall.read(trace, evs, {}, CELL) - 30.0) < 1e-6
    assert abs(cost.read(trace, evs, {}, CELL) - 30.0) < 1e-6


def test_setup_and_resume():
    evs, _ = _events()
    assert cells.load_module("end_to_end", "setup_s").read(
        None, evs, {}, CELL) == 10.0
    fault = [{"ev": "kill", "t": 100.0, "gen": -1, "measured_gen": 1},
             {"ev": "first_step_done", "t": 50.0, "gen": 0, "step": 1},
             {"ev": "first_step_done", "t": 131.5, "gen": 1, "step": 41}]
    assert cells.load_module("end_to_end", "resume_s").read(
        None, fault, {}, CELL) == 31.5
