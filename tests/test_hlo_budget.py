"""HLO collective-budget engine tests.

Three layers: pure-text `count_collectives` parsing, pure-dict
`check_budget` gating (fires on over-count / over-bytes / unexpected op
kinds, clean within budget), and the real-lowering regression pins —
the checked-in BUDGETS are exact count pins against the repo's actual
`make_train_step` lowering on the 8-device virtual CPU mesh, so a model
or partitioner-facing change that inserts a collective fails here
before it ships (ROADMAP item 5's gate).
"""

import pytest

from dlrover_wuqiong_tpu.analysis.hlo_budget import (
    BUDGETS,
    budget_audit,
    check_budget,
    count_collectives,
    lower_case_hlo,
)


class TestCountCollectives:
    def test_counts_ops_and_bytes(self):
        hlo = """
        %ar = f32[16,8]{1,0} all-reduce(f32[16,8]{1,0} %p0), replica_groups={}
        %ag = f32[64]{0} all-gather(f32[8]{0} %p1), dimensions={0}
        %ar2 = f32[4]{0} all-reduce(f32[4]{0} %p2), replica_groups={}
        """
        got = count_collectives(hlo)
        assert got["all-reduce"]["count"] == 2
        assert got["all-reduce"]["bytes"] == 16 * 8 * 4 + 4 * 4
        assert got["all-gather"]["count"] == 1
        assert got["all-gather"]["bytes"] == 64 * 4

    def test_tuple_output_and_start_form(self):
        # async `-start` counts once; `-done` is ignored; tuple outputs
        # sum their element payloads
        hlo = """
        %s = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} %a, f32[8]{0} %b)
        %d = f32[8]{0} all-reduce-done((f32[8]{0}, f32[8]{0}) %s)
        %cp = bf16[2,4]{1,0} collective-permute(bf16[2,4]{1,0} %c), source_target_pairs={{0,1}}
        """
        got = count_collectives(hlo)
        assert got["all-reduce"]["count"] == 1
        assert got["all-reduce"]["bytes"] == 2 * 8 * 4
        assert got["collective-permute"]["count"] == 1
        assert got["collective-permute"]["bytes"] == 2 * 4 * 2  # bf16

    def test_non_collectives_ignored(self):
        hlo = """
        %add = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
        %dot = f32[8,8]{1,0} dot(f32[8,4]{1,0} %x, f32[4,8]{1,0} %y)
        """
        assert count_collectives(hlo) == {}

    def test_scalar_shape(self):
        hlo = "%r = f32[] all-reduce(f32[] %x), replica_groups={}\n"
        got = count_collectives(hlo)
        assert got["all-reduce"] == {"count": 1, "bytes": 4}


class TestCheckBudget:
    BUDGET = {"ops": {"all-reduce": {"max_count": 2, "max_bytes": 1000}}}

    def test_within_budget_clean(self):
        counts = {"all-reduce": {"count": 2, "bytes": 900}}
        assert check_budget("t", counts, self.BUDGET) == []

    def test_over_count_fires(self):
        counts = {"all-reduce": {"count": 3, "bytes": 900}}
        found = check_budget("t", counts, self.BUDGET)
        assert len(found) == 1
        assert found[0].checker == "collective-budget"
        assert found[0].severity == "error"
        assert "count 3 exceeds budget 2" in found[0].message

    def test_over_bytes_fires(self):
        counts = {"all-reduce": {"count": 2, "bytes": 2000}}
        found = check_budget("t", counts, self.BUDGET)
        assert len(found) == 1
        assert "2000 B exceeds budget 1000 B" in found[0].message

    def test_unexpected_op_kind_fires(self):
        counts = {"all-reduce": {"count": 1, "bytes": 10},
                  "all-gather": {"count": 1, "bytes": 10}}
        found = check_budget("t", counts, self.BUDGET)
        assert len(found) == 1
        assert "unexpected collective kind all-gather" in found[0].message


class TestBudgetRegression:
    """Exact pins of the real lowering — the actual regression gate."""

    @pytest.fixture(scope="class")
    def measured(self):
        findings, measured = budget_audit(n_devices=8)
        return findings, measured

    def test_repo_within_budget(self, measured):
        findings, _ = measured
        assert findings == [], [f.format() for f in findings]

    def test_all_strategies_lowered(self, measured):
        _, m = measured
        assert sorted(m) == sorted(BUDGETS)

    def test_fsdp_collective_pin(self, measured):
        # fsdp with the residual stream pinned to the batch layout
        # (parallel/sharding.pin_activation): kernels gathered, weight
        # gradients and scalars summed, and the two all-to-alls of the
        # wte lookup — no activation crosses chips inside a block
        _, m = measured
        assert m["fsdp"]["all-reduce"]["count"] == 3
        assert m["fsdp"]["all-gather"]["count"] == 19
        assert m["fsdp"]["all-to-all"]["count"] == 2
        assert set(m["fsdp"]) == {"all-reduce", "all-gather", "all-to-all"}

    def test_dp_tp_collective_pin(self, measured):
        _, m = measured
        assert m["dp-tp"]["all-reduce"]["count"] == 15
        assert m["dp-tp"]["collective-permute"]["count"] == 8
        assert m["dp-tp"]["all-to-all"]["count"] == 8
        assert set(m["dp-tp"]) == {"all-reduce", "collective-permute",
                                   "all-to-all"}

    def test_budget_fires_when_tightened(self, measured):
        # acceptance: a strategy exceeding its budget IS a finding —
        # reuse the real measured lowering against a tightened budget
        # instead of lowering twice
        _, m = measured
        tight = {"ops": {**BUDGETS["fsdp"]["ops"], "all-reduce": {
            "max_count": m["fsdp"]["all-reduce"]["count"] - 1,
            "max_bytes": 1}}}
        found = check_budget("fsdp", m["fsdp"], tight)
        assert len(found) == 2  # over-count AND over-bytes
        assert all(f.checker == "collective-budget" for f in found)

    def test_coverage_warning_on_unbuildable_case(self):
        # an environment that cannot build a case (here: more devices
        # than the harness has) reports a non-gating coverage warning
        # instead of silently skipping the budget
        findings, measured = budget_audit(
            n_devices=4096, budgets={"fsdp": BUDGETS["fsdp"]})
        assert measured == {}
        assert [f.checker for f in findings] == ["budget-coverage"]
        assert findings[0].severity == "warning"
