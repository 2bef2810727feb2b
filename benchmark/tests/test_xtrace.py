"""The reducer on a small RECORDED trace (`data/steady_2steps.json.gz`:
two optimizer steps of gpt2_124m.steady on a TPU v5 lite, cut from the
traced run of chip call 1, PR 23 — see `_recorded` in the file) and on
hand-made events."""

import gzip
import json
import os

import pytest

from benchmark import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "steady_2steps.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_module_durations(recorded):
    steps = xtrace.step_modules(recorded, "0")
    assert [m[0].split("(")[0] for m in steps] == ["jit_train_step"] * 2
    assert [m[2] for m in steps] == [175474711, 175470752]
    assert xtrace.step_device_ms(recorded) == pytest.approx(175.4727315)
    # a renamed step program still stands out as the largest module
    assert xtrace.step_modules(recorded, "0", match="no_such_name") == steps


def test_named_kernels_sum_per_step(recorded):
    ops = recorded["devices"]["0"]["ops"]
    fa = [o for o in ops if o[0].startswith("dwt_fa_")]
    # no recomputation in this configuration: 12 forward + 12 fused
    # backward kernels per step, none of the split dq/dkv pair
    assert sum(o[0].startswith("dwt_fa_fwd") for o in fa) == 24
    assert sum(o[0].startswith("dwt_fa_bwd_fused") for o in fa) == 24
    assert len(fa) == 48
    by_hand = sum(o[2] for o in fa) / 2 / 1e6
    assert xtrace.per_step_ms(recorded, ("dwt_fa_",)) == \
        pytest.approx(by_hand) == pytest.approx(39.3912115)
    assert xtrace.per_step_ms(recorded, ("dwt_fa_fwd",)) == \
        pytest.approx(11.10974)
    assert xtrace.per_step_ms(recorded, ("dwt_fa_bwd_fused",)) == \
        pytest.approx(28.2814715)
    # one chip: no collective by any of the names the reducer knows
    assert xtrace.per_step_ms(recorded, xtrace.COLLECTIVE_PREFIXES) == 0.0


def test_busy_idle_union_on_the_recorded_trace(recorded):
    busy, window = xtrace.busy_window_s(recorded)
    assert busy == pytest.approx(0.35080867)
    assert window == pytest.approx(0.350963705)
    assert 0.0 < 1.0 - busy / window < 0.001  # 0.044% idle
    top = xtrace.top_device_ops(recorded, 3)
    assert [n for n, _ in top] == ["multiply_reduce_fusion",
                                   "multiply_reduce_fusion.62",
                                   "fusion.2183"]
    gaps = xtrace.idle_gaps(recorded, 3)
    assert gaps[0][1] == pytest.approx(5.0511e-05)
    assert all("->" in g[0] for g in gaps)  # no host span explains 50 us


def test_instruction_name():
    assert xtrace.instruction_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == \
        "fusion.12"
    assert xtrace.instruction_name("%all-gather-start.3 = (bf16[4]) "
                                   "all-gather-start(%x)") == \
        "all-gather-start.3"
    assert xtrace.instruction_name("dwt_fa_fwd.7") == "dwt_fa_fwd.7"


def test_union_counts_overlap_once():
    assert xtrace.union_ns([]) == 0.0
    assert xtrace.union_ns([(0, 10), (5, 10), (30, 5), (31, 1)]) == 20.0
    assert xtrace.union_ns([(10, 5), (0, 5)]) == 10.0


def _two_devices():
    def dev(shift):
        return {"modules": [["jit_train_step(1)", 100 + shift, 1000],
                            ["jit_train_step(1)", 1200 + shift, 1000]],
                "ops": [["fusion.1", 100 + shift, 300],
                        ["all-gather-start.2", 150 + shift, 400],  # overlaps
                        ["all-reduce.9", 600 + shift, 100],
                        ["fusion.1", 1200 + shift, 300],
                        ["all-to-all.4", 1600 + shift, 200],
                        ["reduce-scatter.1", 1900 + shift, 50],
                        ["collective-permute-done.5", 2000 + shift, 50],
                        ["copy.3", 5000 + shift, 10]]}  # outside any step
    return {"devices": {"0": dev(0), "1": dev(10)},
            "host": [["bench.data", 700, 450], ["bench.sync", 0, 90000]]}


def test_collective_names_and_totals():
    t = _two_devices()
    # a TOTAL per step on device 0: 400+100 in step one, 200+50+50 in
    # step two; overlap with compute is counted in full
    assert xtrace.per_step_ms(t, xtrace.COLLECTIVE_PREFIXES) == \
        pytest.approx((500 + 300) / 2 / 1e6)
    ops, n = xtrace.ops_in_steps(t, "0")
    assert n == 2 and "copy.3" not in [o[0] for o in ops]
    assert xtrace.step_device_ms(t) == pytest.approx(1e-3)


def test_busy_is_averaged_over_devices_and_gaps_are_named():
    t = _two_devices()
    busy, window = xtrace.busy_window_s(t)
    # per device: [100,550)+[600,700)+[1200,1500)+[1600,1800)+[1900,1950)
    # +[2000,2050)+[5000,5010) = 1160 ns busy; window 100 .. 5020
    assert busy == pytest.approx(1160e-9)
    assert window == pytest.approx(4920e-9)
    clipped, win = xtrace.busy_window_s(t, 0.0, 1000.0)
    assert win == pytest.approx(1e-6)
    # each device: [100,550) + [600,700) (shifted by 10 on device 1)
    assert clipped == pytest.approx(550e-9)
    gaps = dict((g[0], g[1]) for g in xtrace.idle_gaps(t, 10))
    # the 500 ns gap before step two is covered by the 450 ns data span;
    # the long sync span explains no gap
    assert gaps["host:bench.data"] == pytest.approx(500e-9)
    assert not any(k == "host:bench.sync" for k in gaps)
    assert gaps["collective-permute-done.5->copy.3"] == \
        pytest.approx(2950e-9)
