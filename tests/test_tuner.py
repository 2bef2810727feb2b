"""Online variant autotuner (auto/tuner.py) + fused-window host overlap.

The jax-free pieces deterministically: the interleaved A/B scorer on an
injected clock (drift robustness, hysteresis no-flap), the atomic
corrupt-tolerant winner store, the autotuner state machine, the
sanctioned env writers, and the trainer's metrics pump.  The
zero-cold-compile cutover pin runs a subprocess worker against a real
persistent compile cache (the warm-pool test idiom).  The live trainer
loop is covered by tests/test_trainer.py and `chaos perf-regress`
invariant 4.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dlrover_wuqiong_tpu.auto.tuner import (
    InterleavedScorer,
    TuningStore,
    Variant,
    VariantAutotuner,
    apply_variant,
    default_variants,
    env_signature,
    family_key,
    load_winner,
    make_record,
    order_variants,
    shape_class,
    tuning_path,
    variant_env,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- env


class TestVariantEnv:
    def test_scoped_flip_restores_previous(self):
        os.environ.pop("DWT_FA_STREAMED", None)
        with variant_env({"DWT_FA_STREAMED": "1"}):
            assert os.environ["DWT_FA_STREAMED"] == "1"
        assert "DWT_FA_STREAMED" not in os.environ

    def test_scoped_flip_restores_explicit_value(self):
        os.environ["DWT_FA_PACK"] = "8"
        try:
            with variant_env({"DWT_FA_PACK": "4"}):
                assert os.environ["DWT_FA_PACK"] == "4"
            assert os.environ["DWT_FA_PACK"] == "8"
        finally:
            os.environ.pop("DWT_FA_PACK", None)

    def test_empty_string_genuinely_deletes(self):
        # unset is a distinct value for DWT_FA_STREAMED (the kernel's
        # heuristic path) — "" must delete, not set-to-empty
        os.environ["DWT_FA_STREAMED"] = "1"
        try:
            with variant_env({"DWT_FA_STREAMED": ""}):
                assert "DWT_FA_STREAMED" not in os.environ
            assert os.environ["DWT_FA_STREAMED"] == "1"
        finally:
            os.environ.pop("DWT_FA_STREAMED", None)

    def test_non_trace_var_rejected(self):
        with pytest.raises(ValueError, match="not a trace-time toggle"):
            apply_variant({"DWT_JOB_NAME": "x"})

    def test_signature_tracks_flips(self):
        base = env_signature()
        with variant_env({"DWT_FA_NO_FUSED": "1"}):
            assert env_signature() != base
        assert env_signature() == base

    def test_new_axes_are_sanctioned_toggles(self):
        # the ISSUE-16 names registered in TRACE_ENV_VARS flow through
        # the tuner's writers like the DWT_FA_* originals
        base = env_signature()
        with variant_env({"DWT_FP8_DENSE": "1"}):
            assert os.environ["DWT_FP8_DENSE"] == "1"
            assert env_signature() != base
        assert "DWT_FP8_DENSE" not in os.environ
        with variant_env({"DWT_REMAT_POLICY": "dots"}):
            assert env_signature() != base
        assert env_signature() == base


class TestDefaultVariants:
    def test_cpu_matrix_small(self):
        names = [v.name for v in default_variants("cpu")]
        assert names == ["default", "no-fused", "streamed"]

    def test_tpu_matrix_adds_pack_axes(self):
        names = [v.name for v in default_variants("tpu")]
        assert "pack4" in names and "unstreamed" in names

    def test_fused_k_ladder(self):
        vs = {v.name: v for v in default_variants("cpu", include_k=(4, 8))}
        assert vs["fused-k4"].fused_steps == 4
        assert vs["fused-k8"].fused_steps == 8

    def test_numerics_axis_is_opt_in(self):
        # fp8 changes the loss trajectory: absent unless explicitly
        # opted in, and marked numerics=True when present
        assert "fp8-dense" not in {v.name for v in default_variants("cpu")}
        vs = {v.name: v for v in default_variants("cpu", numerics=True)}
        fp8 = vs["fp8-dense"]
        assert fp8.numerics and fp8.axis == "quant"
        assert fp8.env == {"DWT_FP8_DENSE": "1"}
        # every other default stays numerics-neutral
        assert not any(v.numerics for n, v in vs.items()
                       if n != "fp8-dense")

    def test_remat_ladder(self):
        vs = {v.name: v
              for v in default_variants(
                  "cpu", remat_policies=("dots", "save_names"))}
        assert vs["remat-dots"].env == {"DWT_REMAT_POLICY": "dots"}
        assert vs["remat-dots"].axis == "remat"
        assert not vs["remat-dots"].numerics  # same math, new HLO
        assert vs["remat-save_names"].env == \
            {"DWT_REMAT_POLICY": "save_names"}


class TestShapeClass:
    def test_geometry_key(self):
        assert shape_class(32, 1024) == "b32-s1024"
        assert shape_class(32, 1024, "d768x12") == "b32-s1024-d768x12"

    def test_distinct_geometries_distinct_keys(self):
        assert shape_class(8, 128, "d128x2") != shape_class(8, 4096,
                                                            "d128x2")
        assert shape_class(8, 128, "d128x2") != shape_class(8, 128,
                                                            "d768x12")


class TestOrderVariants:
    def _space(self):
        return default_variants("tpu", numerics=True,
                                remat_policies=("dots",))

    def test_matmul_heavy_tries_quant_first(self):
        ordered = order_variants(
            self._space(), {"matmul": 8.0, "collective": 1.0})
        names = [v.name for v in ordered]
        assert names[0] == "default"  # incumbent anchors the comparison
        assert names[1] == "fp8-dense"  # quant targets matmul
        # collective-targeting axes follow, untagged keep decl order
        assert names.index("fp8-dense") < names.index("streamed")

    def test_collective_heavy_tries_pack_stream_first(self):
        ordered = order_variants(
            self._space(), {"collective": 8.0, "matmul": 1.0})
        names = [v.name for v in ordered]
        assert names[0] == "default"
        # pack/stream (collective-targeted) outrank quant; ties among
        # them keep declaration order (streamed declared before pack4)
        assert set(names[1:4]) == {"streamed", "pack4", "unstreamed"}
        assert names.index("streamed") < names.index("pack4")
        assert names.index("pack4") < names.index("fp8-dense")

    def test_empty_profile_keeps_declaration_order(self):
        space = self._space()
        assert [v.name for v in order_variants(space, {})] == \
            [v.name for v in space]
        assert [v.name for v in order_variants(space, None)] == \
            [v.name for v in space]


# ------------------------------------------------------------- scorer


class TestInterleavedScorer:
    def test_round_robin_interleave(self):
        s = InterleavedScorer(["a", "b", "c"], min_samples=2)
        order = []
        for _ in range(6):
            c = s.next_candidate()
            order.append(c)
            s.note(c, 1.0)
        assert order == ["a", "b", "c", "a", "b", "c"]

    def test_drift_robust_winner(self):
        # chip-load drift: +8%/sample ramp on EVERY sample.  Interleaved
        # medians keep the 15%-faster candidate ahead; a back-to-back
        # schedule (all of "fast" measured last) would have buried it.
        s = InterleavedScorer(["slow", "fast"], min_samples=5,
                              hysteresis=0.05)
        drift = 1.0
        for i in range(10):
            c = s.next_candidate()
            base = 1.0 if c == "slow" else 0.85
            s.note(c, base * drift)
            drift *= 1.08
        name, decided = s.winner(incumbent="slow")
        assert decided and name == "fast"
        # the same samples laid back-to-back: fast's median exceeds
        # slow's — drift would have flipped the verdict
        back_to_back_fast = [0.85 * 1.08 ** i for i in range(5, 10)]
        back_to_back_slow = [1.0 * 1.08 ** i for i in range(0, 5)]
        assert sorted(back_to_back_fast)[2] > sorted(back_to_back_slow)[2]

    def test_hysteresis_keeps_tied_incumbent(self):
        s = InterleavedScorer(["default", "alt"], min_samples=3,
                              hysteresis=0.05)
        for _ in range(3):
            s.note("default", 1.00)
            s.note("alt", 0.97)  # 3% better: inside the 5% margin
        name, decided = s.winner(incumbent="default")
        assert decided and name == "default"

    def test_clear_margin_beats_hysteresis(self):
        s = InterleavedScorer(["default", "alt"], min_samples=3,
                              hysteresis=0.05)
        for _ in range(3):
            s.note("default", 1.00)
            s.note("alt", 0.90)
        name, decided = s.winner(incumbent="default")
        assert decided and name == "alt"

    def test_incomplete_returns_incumbent_undecided(self):
        s = InterleavedScorer(["a", "b"], min_samples=2)
        s.note("a", 1.0)
        name, decided = s.winner(incumbent="b")
        assert not decided and name == "b"

    def test_measure_uses_injected_clock(self):
        clk = FakeClock()

        def work():
            clk.t += 0.25

        s = InterleavedScorer(["a"], min_samples=1, clock=clk)
        dt = s.measure("a", work)
        assert dt == 0.25 and s.samples["a"] == [0.25]

    def test_input_validation(self):
        with pytest.raises(ValueError, match="at least one"):
            InterleavedScorer([])
        with pytest.raises(ValueError, match="duplicate"):
            InterleavedScorer(["a", "a"])
        with pytest.raises(KeyError):
            InterleavedScorer(["a"]).note("b", 1.0)

    def test_remove_discards_samples_and_rotation(self):
        s = InterleavedScorer(["a", "b", "c"], min_samples=1)
        s.note("a", 1.0)
        s.note("b", 0.1)  # would win
        s.remove("b")
        assert "b" not in s.samples and "b" not in s.candidates
        s.note("c", 0.5)
        assert s.complete()
        name, decided = s.winner(incumbent="a")
        assert decided and name == "c"  # b's samples are gone

    def test_remove_guards(self):
        s = InterleavedScorer(["a", "b"], min_samples=1)
        with pytest.raises(KeyError):
            s.remove("zz")
        s.remove("b")
        with pytest.raises(ValueError, match="last candidate"):
            s.remove("a")


# -------------------------------------------------------------- store


class TestTuningStore:
    def test_missing_file_starts_empty(self, tmp_path):
        st = TuningStore(tuning_path(str(tmp_path)))
        assert st.rows() == {} and st.lookup("fam") is None

    def test_corrupt_file_relearned_not_fatal(self, tmp_path):
        p = tuning_path(str(tmp_path))
        os.makedirs(os.path.dirname(p))
        for payload in ("{truncated", '{"families": "not-a-dict"}',
                        '[]', ""):
            with open(p, "w") as f:
                f.write(payload)
            st = TuningStore(p)
            assert st.rows() == {}
        # and a corrupt store still accepts a fresh publish
        st.publish("fam", {"variant": "streamed"})
        assert TuningStore(p).lookup("fam") == {"variant": "streamed"}

    def test_publish_reload_roundtrip(self, tmp_path):
        p = tuning_path(str(tmp_path))
        rec = make_record(
            Variant("streamed", {"DWT_FA_STREAMED": "1"}, fused_steps=4),
            executable_key="exe-1", fused_steps=4,
            medians={"default": 0.012, "streamed": 0.009}, windows=6)
        TuningStore(p).publish("fam", rec)
        got = TuningStore(p).lookup("fam")
        assert got == rec
        raw = json.load(open(p))
        assert raw["schema"] == 2 and "fam" in raw["families"]
        # v2 nested row: the family winner + the per-geometry map
        assert raw["families"]["fam"]["winner"] == rec
        assert raw["families"]["fam"]["shapes"] == {}
        # atomic publish leaves no tmp droppings
        assert [f for f in os.listdir(os.path.dirname(p))
                if f.endswith(".tmp")] == []

    def test_per_shape_publish_and_fallback(self, tmp_path):
        p = tuning_path(str(tmp_path))
        st = TuningStore(p)
        rec_small = make_record(
            Variant("streamed", {"DWT_FA_STREAMED": "1"}),
            executable_key="e1", fused_steps=1,
            medians={"streamed": 0.01}, windows=6,
            shape_class="b8-s128-d128x2")
        rec_big = make_record(
            Variant("no-fused", {"DWT_FA_NO_FUSED": "1"}),
            executable_key="e2", fused_steps=1,
            medians={"no-fused": 0.09}, windows=6,
            shape_class="b32-s4096-d128x2")
        st.publish("fam", rec_small, shape="b8-s128-d128x2")
        st.publish("fam", rec_big, shape="b32-s4096-d128x2")
        re = TuningStore(p)  # fresh reload
        # exact geometries answer their own winners
        assert re.lookup("fam", "b8-s128-d128x2")["variant"] == "streamed"
        assert re.lookup("fam", "b32-s4096-d128x2")["variant"] == \
            "no-fused"
        # an unseen geometry falls back to the family winner
        # (latest-published wins)
        assert re.lookup("fam", "b1-s32-d128x2")["variant"] == "no-fused"
        assert re.lookup("fam")["variant"] == "no-fused"

    def test_v1_shapeless_store_migrates_forward(self, tmp_path):
        """A PR-14-era flat tuning.json loads, serves its rows as the
        family fallback for every shape, and is upgraded in place to the
        nested layout by the next atomic publish — never re-learned."""
        p = tuning_path(str(tmp_path))
        os.makedirs(os.path.dirname(p))
        v1_row = {"variant": "streamed", "env": {"DWT_FA_STREAMED": "1"},
                  "fused_steps": 0, "executable_key": "e-old",
                  "medians": {"streamed": 0.01}, "windows": 6,
                  "exe_env": {"DWT_FA_STREAMED": "1"}}
        with open(p, "w") as f:
            json.dump({"schema": 1, "families": {"fam": v1_row}}, f)
        st = TuningStore(p)
        # served shapeless AND as the fallback for any geometry
        assert st.lookup("fam")["variant"] == "streamed"
        assert st.lookup("fam", "b8-s128")["variant"] == "streamed"
        assert load_winner(str(tmp_path), "fam",
                           shape="b1-s1")["variant"] == "streamed"
        # next publish upgrades the FILE in place (schema 2, nested),
        # keeping the migrated winner visible alongside the new shape row
        rec = make_record(Variant("no-fused", {"DWT_FA_NO_FUSED": "1"}),
                          executable_key="e-new", fused_steps=1,
                          medians={"no-fused": 0.02}, windows=4,
                          shape_class="b8-s128")
        st.publish("fam2", rec, shape="b8-s128")
        raw = json.load(open(p))
        assert raw["schema"] == 2
        assert raw["families"]["fam"]["winner"]["variant"] == "streamed"
        assert raw["families"]["fam2"]["shapes"]["b8-s128"] == rec
        # and the migrated v1 winner still serves after the upgrade
        assert TuningStore(p).lookup(
            "fam", "b9-s9")["variant"] == "streamed"

    def test_load_winner_shortcut(self, tmp_path):
        fam = family_key("fp", "cpu")
        assert load_winner(str(tmp_path), fam) is None
        assert load_winner("", fam) is None
        TuningStore(tuning_path(str(tmp_path))).publish(
            fam, {"variant": "no-fused"})
        assert load_winner(str(tmp_path), fam)["variant"] == "no-fused"

    def test_family_key_excludes_tunables(self):
        # same program, different backend → different family; the key
        # has no fused-K / env ingredient at all
        assert family_key("fp", "cpu") != family_key("fp", "tpu")
        assert family_key("fp", "cpu") == family_key("fp", "cpu")


# ----------------------------------------------------------- autotuner


def _drive(tuner, times):
    """Feed one window per entry; apply any requested cutover like the
    trainer does (pre-warm assumed instant)."""
    for t in times:
        nxt = tuner.note_window(t(tuner.current().name)
                                if callable(t) else t)
        if nxt is not None:
            tuner.cutover(nxt)


class TestVariantAutotuner:
    def _mk(self, tmp_path, **kw):
        store = TuningStore(tuning_path(str(tmp_path)))
        t = VariantAutotuner(
            default_variants("cpu"), store=store, family="fam",
            windows_per_variant=kw.pop("windows_per_variant", 2),
            clock=FakeClock(), **kw)
        t.bind_executable_context(
            strategy_fingerprint="fp", fused_steps=1, backend="cpu")
        return t

    def test_search_converges_and_persists(self, tmp_path):
        t = self._mk(tmp_path)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8}
        _drive(t, [lambda n, per=per: per[n]] * 6)
        assert t.finished
        assert t.result().name == "streamed"
        assert t.current().name == "streamed"  # poll converges on winner
        row = load_winner(str(tmp_path), "fam")
        assert row["variant"] == "streamed"
        assert row["exe_env"]["DWT_FA_STREAMED"] == "1"
        assert row["exe_env"]["DWT_FA_NO_FUSED"] == ""
        assert row["executable_key"]  # joinable against baselines
        assert row["medians"]["streamed"] == pytest.approx(0.8)

    def test_decision_carries_measured_before_after(self, tmp_path):
        t = self._mk(tmp_path)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8}
        _drive(t, [lambda n, per=per: per[n]] * 6)
        (d,) = t.decisions
        assert d["kind"] == "tuner" and d["variant"] == "streamed"
        assert d["before"]["step_time_s"] == pytest.approx(1.0)
        assert d["after"]["step_time_s"] == pytest.approx(0.8)
        assert d["windows"] == 6
        from dlrover_wuqiong_tpu.brain.policy import tuner_decision_effects

        (row,) = tuner_decision_effects(t.decisions)
        assert row["effect"]["before"] == d["before"]
        assert row["effect"]["after"] == d["after"]
        assert row["decision_id"] == d["decision_id"]

    def test_tied_search_keeps_incumbent(self, tmp_path):
        t = self._mk(tmp_path)
        _drive(t, [1.0] * 6)  # everyone identical: hysteresis holds
        assert t.finished and t.result().name == "default"

    def test_settled_tuner_ignores_further_windows(self, tmp_path):
        t = self._mk(tmp_path)
        _drive(t, [1.0] * 6)
        assert t.note_window(99.0) is None
        assert t.result().name == "default"

    def test_executable_key_changes_with_winner_env(self, tmp_path):
        # the persisted key must be the key the WINNER's windows land on
        t = self._mk(tmp_path)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8}
        _drive(t, [lambda n, per=per: per[n]] * 6)
        from dlrover_wuqiong_tpu.telemetry.perf import executable_key

        row = load_winner(str(tmp_path), "fam")
        assert row["executable_key"] != executable_key("fp", 1, "cpu")
        with variant_env({"DWT_FA_STREAMED": "1"}):
            assert row["executable_key"] == executable_key("fp", 1, "cpu")

    def test_thread_safe_interleave(self, tmp_path):
        # pump thread notes windows while the main loop polls current()
        t = self._mk(tmp_path, windows_per_variant=32)
        stop = threading.Event()
        seen = []

        def poll():
            while not stop.is_set():
                seen.append(t.current().name)

        th = threading.Thread(target=poll, daemon=True)
        th.start()
        try:
            _drive(t, [1.0] * (32 * 3))
        finally:
            stop.set()
            th.join(10)
        assert t.finished and set(seen) <= set(t.variants)

    def test_category_hint_orders_search(self, tmp_path):
        """Observatory-driven search (ROADMAP 4d): under a matmul-heavy
        profile the quant variant is measured before pack/stream; under
        a collective-heavy one pack/stream come first."""
        def first_challenger(hint):
            t = VariantAutotuner(
                default_variants("tpu", numerics=True),
                windows_per_variant=1, category_hint=hint,
                loss_bound=1e9,  # guard armed but never trips here
                clock=FakeClock())
            # first window goes to the incumbent; the answer is the
            # first CHALLENGER the ordered interleave schedules
            nxt = t.note_window(1.0, loss=1.0)
            return nxt.name
        assert first_challenger(
            {"matmul": 8.0, "collective": 1.0}) == "fp8-dense"
        assert first_challenger(
            {"collective": 8.0, "matmul": 1.0}) == "streamed"
        assert first_challenger(None) == "no-fused"  # declaration order

    def test_max_candidates_prunes_ordered_tail(self, tmp_path):
        t = VariantAutotuner(
            default_variants("tpu", numerics=True),
            category_hint={"matmul": 8.0, "collective": 1.0},
            max_candidates=3, clock=FakeClock())
        # incumbent + the two most matmul-relevant survive
        assert set(t.variants) == {"default", "fp8-dense", "streamed"}

    def test_per_shape_winners_distinct_geometries(self, tmp_path):
        """Acceptance (a): two geometries learn DIFFERENT winners in one
        family; a third unseen geometry serves the family fallback."""
        store_path = tuning_path(str(tmp_path))
        per_small = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8}
        per_big = {"default": 1.0, "no-fused": 0.7, "streamed": 1.3}

        def learn(shape, per):
            t = VariantAutotuner(
                default_variants("cpu"), store=TuningStore(store_path),
                family="fam", windows_per_variant=2, shape_class=shape,
                clock=FakeClock())
            t.bind_executable_context(strategy_fingerprint="fp",
                                      fused_steps=1, backend="cpu")
            _drive(t, [lambda n, per=per: per[n]] * 6)
            assert t.finished
            return t.result().name

        assert learn("b8-s128-d128x2", per_small) == "streamed"
        assert learn("b32-s4096-d128x2", per_big) == "no-fused"
        # both winners persisted per geometry, third shape falls back
        assert load_winner(str(tmp_path), "fam",
                           shape="b8-s128-d128x2")["variant"] == "streamed"
        assert load_winner(str(tmp_path), "fam",
                           shape="b32-s4096-d128x2")["variant"] == \
            "no-fused"
        fb = load_winner(str(tmp_path), "fam", shape="b1-s32-d128x2")
        assert fb["variant"] == "no-fused"  # latest family-wide winner
        # the decision carries its geometry
        assert load_winner(str(tmp_path), "fam",
                           shape="b8-s128-d128x2")["shape_class"] == \
            "b8-s128-d128x2"


class TestLossDivergenceGuard:
    """Acceptance (c): a numerics variant whose loss diverges is
    auto-reverted — removed from the search, cut back to the incumbent,
    journaled as a PolicyDecision-style revert."""

    def _mk(self, tmp_path, loss_bound=0.05, **kw):
        t = VariantAutotuner(
            default_variants("cpu", numerics=True),
            store=TuningStore(tuning_path(str(tmp_path))), family="fam",
            windows_per_variant=2, loss_bound=loss_bound,
            shape_class="b8-s128", clock=FakeClock(), **kw)
        t.bind_executable_context(strategy_fingerprint="fp",
                                  fused_steps=1, backend="cpu")
        return t

    def _drive_losses(self, t, per, loss_fn, max_windows=64):
        guard = 0
        while not t.finished and guard < max_windows:
            guard += 1
            cur = t.current()
            nxt = t.note_window(per[cur.name], loss=loss_fn(cur))
            if nxt is not None:
                t.cutover(nxt)

    def test_diverged_fp8_reverted_and_journaled(self, tmp_path):
        t = self._mk(tmp_path)
        # fp8 is the FASTEST candidate — without the guard it would win
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8,
               "fp8-dense": 0.4}
        self._drive_losses(
            t, per, lambda v: 9.0 if v.numerics else 2.0)
        assert t.finished and t.result().name == "streamed"
        assert "fp8-dense" not in t.variants
        reverts = [d for d in t.decisions if d["kind"] == "tuner-revert"]
        assert len(reverts) == 1
        r = reverts[0]
        assert r["reverted"] == "fp8-dense"
        assert r["variant"] == "default"  # cut-back target
        assert r["loss"] == pytest.approx(9.0)
        assert r["loss_ref"] == pytest.approx(2.0)
        assert r["loss_bound"] == pytest.approx(0.05)
        # the degraded step time never entered the scorer
        assert "fp8-dense" not in t.snapshot()["medians"]
        # the persisted winner is the guard's survivor
        assert load_winner(str(tmp_path), "fam",
                           shape="b8-s128")["variant"] == "streamed"

    def test_revert_surfaces_through_policy_bridge(self, tmp_path):
        from dlrover_wuqiong_tpu.brain.policy import tuner_decision_effects

        t = self._mk(tmp_path)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8,
               "fp8-dense": 0.4}
        self._drive_losses(
            t, per, lambda v: 9.0 if v.numerics else 2.0)
        rows = tuner_decision_effects(t.decisions)
        kinds = [r["kind"] for r in rows]
        assert "tuner-revert" in kinds and "tuner" in kinds
        rev = rows[kinds.index("tuner-revert")]
        assert rev["reverted"] == "fp8-dense"
        assert rev["loss"] == pytest.approx(9.0)
        assert rev["effect"]["before"] == {"loss": 9.0}
        assert rev["effect"]["after"] == {"loss": 2.0}
        assert rev["shape_class"] == "b8-s128"

    def test_within_bound_fp8_stays_and_can_win(self, tmp_path):
        t = self._mk(tmp_path)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8,
               "fp8-dense": 0.4}
        # fp8 loss within the 5% margin of the 2.0 reference: no revert
        self._drive_losses(
            t, per, lambda v: 2.05 if v.numerics else 2.0)
        assert t.finished and t.result().name == "fp8-dense"
        assert [d["kind"] for d in t.decisions] == ["tuner"]

    def test_loss_decline_never_reverts(self, tmp_path):
        # one-sided guard: training loss naturally FALLS — a numerics
        # variant with lower loss than the reference must never trip
        t = self._mk(tmp_path)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8,
               "fp8-dense": 0.4}
        self._drive_losses(
            t, per, lambda v: 1.0 if v.numerics else 2.0)
        assert t.finished and t.result().name == "fp8-dense"
        assert not [d for d in t.decisions
                    if d["kind"] == "tuner-revert"]

    def test_guard_disarmed_without_bound(self, tmp_path):
        # loss_bound=0 (trainer default when tune_numerics is off):
        # losses ride along but never disqualify
        t = self._mk(tmp_path, loss_bound=0.0)
        per = {"default": 1.0, "no-fused": 1.2, "streamed": 0.8,
               "fp8-dense": 0.4}
        self._drive_losses(
            t, per, lambda v: 9.0 if v.numerics else 2.0)
        assert t.finished and t.result().name == "fp8-dense"
        assert not [d for d in t.decisions
                    if d["kind"] == "tuner-revert"]


# ------------------------------------------------------- metrics pump


class _FakeTrainer:
    """Just enough surface for _MetricsPump: consume returns the loss,
    optionally raising on demand."""

    def __init__(self):
        self.consumed = []
        self.boom = False

    def _consume_boundary(self, job):
        if self.boom:
            raise RuntimeError("boundary boom")
        self.consumed.append(job["step"])
        return float(job["metrics"]["loss"])


def _job(step, loss, pw=None):
    return {"step": step, "metrics": {"loss": loss}, "pw": pw}


class TestMetricsPump:
    def _pump(self, enabled=True):
        from dlrover_wuqiong_tpu.trainer.trainer import _MetricsPump

        tr = _FakeTrainer()
        return tr, _MetricsPump(tr, enabled=enabled)

    def test_async_drains_in_order(self):
        tr, pump = self._pump()
        try:
            for i in range(5):
                pump.submit(_job(i, float(i)))
        finally:
            pump.stop()
        assert tr.consumed == list(range(5))
        assert pump.last_loss() == 4.0
        assert pump.stats() == {"drained": 5, "errors": 0}

    def test_window_inflight_gates_next_open(self):
        tr, pump = self._pump()
        try:
            pump.submit(_job(0, 0.0, pw=object()))
            deadline = time.monotonic() + 10
            while pump.windows_inflight() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert pump.windows_inflight() == 0
        finally:
            pump.stop()

    def test_consume_error_keeps_window_gate_closed(self):
        # a half-closed window may hold the profiler trace: the error
        # path deliberately leaves windows_inflight elevated (stuck gate
        # safe, nested trace not) and counts the error
        tr, pump = self._pump()
        tr.boom = True
        try:
            pump.submit(_job(0, 0.0, pw=object()))
        finally:
            pump.stop()
        assert pump.windows_inflight() == 1
        assert pump.stats() == {"drained": 0, "errors": 1}

    def test_inline_mode_propagates_exceptions(self):
        tr, pump = self._pump(enabled=False)
        tr.boom = True
        with pytest.raises(RuntimeError, match="boundary boom"):
            pump.submit(_job(0, 0.0))
        tr.boom = False
        pump.submit(_job(1, 2.5))
        assert pump.last_loss() == 2.5
        pump.stop()  # no-op without a thread

    def test_no_thread_leak_after_stop(self):
        _, pump = self._pump()
        pump.stop()
        assert not any(th.name == "dwt-metrics-pump" and th.is_alive()
                       for th in threading.enumerate())


# ---------------------------------------- zero-cold-compile cutover pin


_CUTOVER_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import dataclasses
import jax.numpy as jnp
import optax
from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.auto.compile_cache import counters
from dlrover_wuqiong_tpu.auto.tuner import apply_variant, variant_env
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

# flash attention + remat ON: the DWT_FA_*/DWT_REMAT_POLICY toggles
# change the emitted HLO, so the two variants are genuinely distinct
# executables; DWT_FP8_DENSE swaps the dense matmul kernel without
# touching the param tree, so one state serves both
cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                          use_flash_attention=True, remat=True)
res = auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                      strategy=[("fsdp", {})], devices=jax.devices(),
                      materialize=False)
bsh = res.batch_sharding_fn(2, None, 0)
ab = {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=bsh),
      "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=bsh)}

# pre-warm both candidates (the warm pool does this out of process; the
# in-process fused cache re-keys on the env signature either way)
with variant_env({}):
    fn_a = res.fused_train_step(1)
    fn_a.lower(res.state, ab).compile()
winner_env = {"DWT_FA_NO_FUSED": "", "DWT_FA_PACK": "",
              "DWT_FA_STREAMED": "", "DWT_FP8_DENSE": "1",
              "DWT_REMAT_POLICY": "dots"}
with variant_env(winner_env):
    fn_b = res.fused_train_step(1)
    fn_b.lower(res.state, ab).compile()
prewarm_hits, prewarm_misses = counters.snapshot()

# cutover: adopt the winner for the rest of the process
apply_variant(winner_env)
fn_cut = res.fused_train_step(1)
fn_cut.lower(res.state, ab).compile()
h1, m1 = counters.snapshot()
print(json.dumps({
    "prewarm_misses": prewarm_misses,
    "cutover_misses": m1 - prewarm_misses,
    "cutover_hits": h1 - prewarm_hits,
    "fused_cache_hit": fn_cut is fn_b,
}))
"""


def test_winner_cutover_zero_cold_compiles(tmp_path):
    """Cutover to a pre-warmed winner pays NO cold compile: the fused
    cache answers the same jitted callable (env-signature key) and the
    XLA persistent cache serves the executable it compiled during
    pre-warm — miss counters stay flat across the cutover."""
    script = tmp_path / "cutover_worker.py"
    script.write_text(_CUTOVER_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    for var in ("DWT_FA_NO_FUSED", "DWT_FA_PACK", "DWT_FA_STREAMED",
                "DWT_FP8_DENSE", "DWT_REMAT_POLICY"):
        env.pop(var, None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(script)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["prewarm_misses"] >= 1      # the candidates DID compile
    assert out["cutover_misses"] == 0      # ...and the cutover did not
    assert out["fused_cache_hit"] is True  # same jitted callable back
