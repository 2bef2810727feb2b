"""Strategy search engine + Bayesian optimization tests.

Mirrors reference `atorch/tests/common_tests` engine/strategy tests and
`dlrover/python/tests/test_hpsearch_bo.py`.
"""

import dataclasses
import math

import pytest

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_wuqiong_tpu.auto.bo import BayesianOptimizer, Param
from dlrover_wuqiong_tpu.auto.engine import (
    Candidate,
    generate_candidates,
    score_candidate,
    search_strategy,
)
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.parallel.mesh import MeshPlan


class TestCandidateGeneration:
    def test_divisibility_constraints(self):
        cands = generate_candidates(8, n_head=4, n_layer=2,
                                    with_remat=False)
        for c in cands:
            assert 4 % c.plan.tp == 0
            assert 2 % c.plan.pp == 0
            assert c.plan.num_devices == 8
        # tp can't exceed head count divisors
        assert all(c.plan.tp in (1, 2, 4) for c in cands)
        assert any(c.plan.pp == 2 for c in cands)

    def test_remat_triples_space(self):
        # off / full-remat / selective-dots per mesh plan
        a = generate_candidates(4, with_remat=False)
        b = generate_candidates(4, with_remat=True)
        assert len(b) == 3 * len(a)
        assert any(c.remat and c.remat_policy == "dots" for c in b)
        strat = dict(next(c for c in b if c.remat_policy == "dots"
                          and c.remat).strategy())
        assert strat["checkpoint"] == {"enabled": True, "policy": "dots"}

    def test_strategy_roundtrip(self):
        c = Candidate(plan=MeshPlan(tp=2, fsdp=4), remat=True)
        strat = dict(c.strategy())
        assert strat["tensor_parallel"] == {"size": 2}
        assert strat["fsdp"] == {"size": 4}
        assert strat["checkpoint"] == {"enabled": True}


def test_unknown_device_kind_has_no_roofline():
    """A device the table does not know is an error, never a default."""
    import types

    from dlrover_wuqiong_tpu.auto.engine import _device_roofline

    assert _device_roofline(
        types.SimpleNamespace(device_kind="TPU v5 lite")) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no roofline"):
        _device_roofline(types.SimpleNamespace(device_kind="TPU v99"))


class TestScoring:
    def _model_batch(self):
        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  use_flash_attention=False, remat=False)
        data = np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 33))
        batch = {"input_ids": jnp.asarray(data[:, :-1]),
                 "labels": jnp.asarray(data[:, 1:])}
        return GPT(cfg), batch, cfg

    def test_score_feasible_candidate(self):
        model, batch, cfg = self._model_batch()
        c = Candidate(plan=MeshPlan(fsdp=8))
        score_candidate(c, model, optax.adam(1e-2), batch,
                        jax.devices())
        assert c.feasible
        assert 0 < c.score < math.inf

    def test_infeasible_marked_not_raised(self):
        model, batch, cfg = self._model_batch()
        # tp=8 > n_head=2 → ulysses/TP head divisibility fails inside
        c = Candidate(plan=MeshPlan(tp=8, fsdp=1))
        score_candidate(c, model, optax.adam(1e-2), batch, jax.devices())
        # nano has 2 heads; tp=8 model may still build (GSPMD pads) — the
        # point is: no exception escapes, feasibility is recorded
        assert isinstance(c.feasible, bool)

    # tier-2: ~42s multi-candidate compile sweep; scoring/feasibility
    # logic is tier-1 via the two single-candidate tests above
    @pytest.mark.slow
    def test_search_returns_ranked(self):
        model, batch, cfg = self._model_batch()
        top = search_strategy(model, optax.adam(1e-2), batch,
                              jax.devices(), n_head=cfg.n_head,
                              n_layer=cfg.n_layer, top_k=3)
        assert top
        scores = [c.score for c in top]
        assert scores == sorted(scores)
        assert all(c.feasible for c in top)


class TestBayesianOptimizer:
    def test_finds_quadratic_minimum(self):
        bo = BayesianOptimizer([Param("x", -2.0, 2.0)], seed=1, n_init=4)
        for _ in range(25):
            cfg = bo.ask()
            bo.tell(cfg, (cfg["x"] - 0.7) ** 2)
        best_cfg, best_y = bo.best()
        assert abs(best_cfg["x"] - 0.7) < 0.25
        assert best_y < 0.08

    def test_log_scale_param(self):
        p = Param("lr", 1e-5, 1e-1, log_scale=True)
        assert abs(p.from_unit(p.to_unit(1e-3)) - 1e-3) < 1e-9
        bo = BayesianOptimizer([p], seed=0, n_init=3)
        # minimum at lr=1e-3 on a log parabola
        for _ in range(20):
            cfg = bo.ask()
            bo.tell(cfg, (math.log10(cfg["lr"]) + 3.0) ** 2)
        best_cfg, _ = bo.best()
        assert 1e-4 < best_cfg["lr"] < 1e-2

    def test_multidim(self):
        bo = BayesianOptimizer([Param("a", 0, 1), Param("b", 0, 1)],
                               seed=2, n_init=5)
        for _ in range(30):
            cfg = bo.ask()
            bo.tell(cfg, (cfg["a"] - 0.3) ** 2 + (cfg["b"] - 0.6) ** 2)
        best_cfg, best_y = bo.best()
        assert best_y < 0.1


class TestScheduleCandidates:
    def test_interleaved_candidates_emitted(self):
        from dlrover_wuqiong_tpu.auto.engine import generate_candidates

        cands = generate_candidates(8, n_head=4, n_layer=8,
                                    with_remat=False)
        inter = [c for c in cands if c.pp_schedule == "interleaved"]
        assert inter, "expected interleaved pp candidates"
        for c in inter:
            assert c.plan.pp > 1
            assert c.pp_virtual_stages == 2
            # strategy round-trips the schedule config
            pp_cfg = dict(c.strategy())["pipeline_parallel"]
            assert pp_cfg["schedule"] == "interleaved"
            assert pp_cfg["virtual_stages"] == 2

    def test_no_interleaved_when_layers_dont_divide(self):
        from dlrover_wuqiong_tpu.auto.engine import generate_candidates

        cands = generate_candidates(4, n_head=4, n_layer=2,
                                    with_remat=False)
        assert not [c for c in cands if c.pp_schedule == "interleaved"]


class TestHEBO:
    """HEBO-class search (parity atorch auto/engine/sg_algo/hebo): input
    warping + power-transformed observations + MACE Pareto acquisition."""

    def test_finds_quadratic_minimum(self):
        from dlrover_wuqiong_tpu.auto.hebo import HEBO, Param

        hebo = HEBO([Param("x", -2.0, 2.0), Param("y", -2.0, 2.0)],
                    seed=3, n_init=6)
        for _ in range(26):
            cfg = hebo.ask()
            hebo.tell(cfg, (cfg["x"] - 0.7) ** 2 + (cfg["y"] + 0.3) ** 2)
        best_cfg, best_y = hebo.best()
        assert best_y < 0.08, (best_cfg, best_y)

    def test_outlier_robustness_beats_plain_gp(self):
        """A diverged trial (loss 1e6) must not blind the search — the
        power transform compresses it; plain standardization flattens the
        whole surrogate to ~zero contrast."""
        from dlrover_wuqiong_tpu.auto.hebo import HEBO, Param

        def obj(cfg):
            if cfg["x"] < -1.5:  # divergence region
                return 1e6
            return (cfg["x"] - 0.5) ** 2

        hebo = HEBO([Param("x", -2.0, 2.0)], seed=0, n_init=5)
        for _ in range(22):
            cfg = hebo.ask()
            hebo.tell(cfg, obj(cfg))
        _, best_y = hebo.best()
        assert best_y < 0.05, best_y

    def test_batch_ask_returns_distinct_configs(self):
        from dlrover_wuqiong_tpu.auto.hebo import HEBO, Param

        hebo = HEBO([Param("lr", 1e-5, 1e-1, log_scale=True)], seed=1,
                    n_init=4)
        for _ in range(6):
            cfg = hebo.ask()
            hebo.tell(cfg, abs(math.log10(cfg["lr"]) + 3.0))
        batch = hebo.ask(4)
        assert len(batch) == 4
        assert len({round(c["lr"], 10) for c in batch}) >= 3

    def test_warp_and_transform_sanity(self):
        import numpy as np

        from dlrover_wuqiong_tpu.auto.hebo import (
            _kumaraswamy_cdf,
            _power_transform,
        )

        u = np.linspace(0.01, 0.99, 50)
        w = _kumaraswamy_cdf(u, np.array([1.7]), np.array([0.6]))
        assert (np.diff(w) > 0).all()  # monotone
        assert 0.0 <= w.min() and w.max() <= 1.0
        y = np.array([1.0, 1.1, 0.9, 1.05, 1e6])  # one catastrophic trial
        t, lam, _ = _power_transform(y)
        spread = (t[:-1].max() - t[:-1].min())
        assert spread > 0  # healthy trials keep contrast
        # the outlier no longer dominates the scale by 6 orders
        assert (t[-1] - t[:-1].max()) < 50 * spread
