"""Fused multi-step dispatch (ISSUE 3 tentpole) — correctness contract.

The fused K-step driver (trainer/train_step.py) must be a pure dispatch
optimization: same math as K=1 (exact-resume equivalence), same donation
semantics across the scan carry, boundary checkpoints restore
bit-identically, and the auto-tune policy respects the hook cadences.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.data.elastic_dataset import (
    FusedBatchStager,
    stack_batches,
)
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.trainer.train_step import auto_fused_steps

VOCAB = 512
SEQ = 32


def _model():
    return GPT(dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                   use_flash_attention=False, remat=False))


def _res(**kw):
    import optax

    return auto_accelerate(_model(), optimizer=optax.adam(1e-2),
                           strategy=[("fsdp", {})], **kw)


def _host_batch(step, batch=8, accum=0):
    rng = np.random.default_rng(step)
    shape = (accum, batch, SEQ + 1) if accum else (batch, SEQ + 1)
    x = rng.integers(0, VOCAB, shape, dtype=np.int32)
    return {"input_ids": x[..., :-1], "labels": x[..., 1:]}


def _tree_equal(a, b):
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class TestFusedEquivalence:
    def test_k8_matches_k1_exactly(self):
        """8 unfused steps and one K=8 fusion over the SAME batches land
        on the same params AND opt state — the fused driver is a dispatch
        optimization, not a different training algorithm."""
        res = _res()
        hbs = [_host_batch(i) for i in range(8)]

        st1 = jax.tree.map(jnp.copy, res.state)
        for hb in hbs:
            st1, m1 = res.train_step(st1, res.place_batch(dict(hb)))

        fused = res.fused_train_step(8)
        fb = res.place_fused_batch(stack_batches(hbs))
        st8, m8 = fused(jax.tree.map(jnp.copy, res.state), fb)

        assert int(st1.step) == int(st8.step) == 8
        assert _tree_equal(st1.params, st8.params)
        assert _tree_equal(st1.opt_state, st8.opt_state)
        # per-step metrics accumulated on device: one readback, K values
        assert m8["losses"].shape == (8,)
        assert float(m8["losses"][-1]) == float(m8["loss"])
        assert float(m1["loss"]) == float(m8["loss"])

    def test_fused_composes_with_grad_accum(self):
        """K-step fusion over microbatch accumulation: batch leaves carry
        (K, accum, batch, seq) and both scan levels peel correctly."""
        res = _res(accum_steps=2)
        hbs = [_host_batch(i, accum=2) for i in range(4)]

        st1 = jax.tree.map(jnp.copy, res.state)
        for hb in hbs:
            st1, _ = res.train_step(st1, res.place_batch(dict(hb)))

        fused = res.fused_train_step(4)
        fb = res.place_fused_batch(stack_batches(hbs))
        st4, m4 = fused(jax.tree.map(jnp.copy, res.state), fb)
        assert int(st4.step) == 4
        assert m4["losses"].shape == (4,)
        assert _tree_equal(st1.params, st4.params)

    def test_boundary_checkpoint_restores_bit_identically(self, tmp_path):
        """A checkpoint taken at a fusion boundary round-trips exactly:
        restore-then-continue is indistinguishable from never stopping."""
        from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
            FlashCheckpointer,
            StorageType,
        )
        from dlrover_wuqiong_tpu.checkpoint.ckpt_saver import (
            AsyncCheckpointSaver,
        )

        AsyncCheckpointSaver.reset()
        try:
            res = _res()
            fused = res.fused_train_step(4)
            hbs = [_host_batch(i) for i in range(8)]

            st = jax.tree.map(jnp.copy, res.state)
            st, _ = fused(st, res.place_fused_batch(stack_batches(hbs[:4])))
            ck = FlashCheckpointer(str(tmp_path), job_name="fusedt")
            ck.save_checkpoint(4, st, storage_type=StorageType.DISK)
            ck.wait_latest_checkpoint(120)
            restored = ck.load_checkpoint(jax.tree.map(jnp.copy, st))
            assert restored is not None
            assert _tree_equal(st, restored)

            # continue 4 more steps from the restored state vs straight
            # through: identical end states
            st_cont, _ = fused(restored,
                               res.place_fused_batch(
                                   stack_batches(hbs[4:])))
            st_straight, _ = fused(st, res.place_fused_batch(
                stack_batches(hbs[4:])))
            assert _tree_equal(st_cont.params, st_straight.params)
            assert _tree_equal(st_cont.opt_state, st_straight.opt_state)
            ck.close()
        finally:
            AsyncCheckpointSaver.reset()

    def test_scan_carry_donation_regression(self):
        """The fused driver DONATES its input state exactly like K=1:
        reusing the donated tree afterwards reads dead buffers (CLAUDE.md:
        copy first in tests)."""
        res = _res()
        fused = res.fused_train_step(2)
        donated = jax.tree.map(jnp.copy, res.state)
        _ = fused(donated, res.place_fused_batch(
            stack_batches([_host_batch(0), _host_batch(1)])))
        leaf = jax.tree.leaves(donated.params)[0]
        assert leaf.is_deleted()
        with pytest.raises(RuntimeError):
            _ = float(jnp.asarray(leaf).reshape(-1)[0])
        # res.state itself was never donated here (we passed a copy)
        assert not jax.tree.leaves(res.state.params)[0].is_deleted()

    def test_fused_key_differs_and_local_sgd_rejected(self):
        """K is part of the framework cache key (K changes the HLO), and
        the strategy matrix rejects fusion under local_sgd at resolve
        time, before any parameter init."""
        import optax

        res = _res()
        k1 = res._fused_key_fn(1)
        k8 = res._fused_key_fn(8)
        assert k1 == res.cache_key and k1 != k8

        # resolve-time rejection fires BEFORE any param init, so it does
        # not depend on local_sgd actually being buildable on this jax
        with pytest.raises(ValueError, match="local_sgd"):
            auto_accelerate(
                _model(), optimizer=optax.adam(1e-2),
                strategy=[("data_parallel", {"size": 2}),
                          ("local_sgd", {"sync_every": 4}), ("fsdp", {})],
                fused_steps=4)
        # the lazily-built driver refuses too
        res_ls = auto_accelerate(
            _model(), optimizer=optax.adam(1e-2),
            strategy=[("data_parallel", {"size": 2}),
                      ("local_sgd", {"sync_every": 4}), ("fsdp", {})])
        with pytest.raises(ValueError, match="local_sgd"):
            res_ls.fused_train_step(4)


class TestAutoTunePolicy:
    def test_target_overhead_formula(self):
        # 6ms dispatch, 100ms step, 2% target -> ceil(6 / 2) = 3
        assert auto_fused_steps(0.1, overhead_s=0.006) == 3
        # already amortized: big step, tiny overhead -> K=1
        assert auto_fused_steps(1.0, overhead_s=0.0001) == 1
        # dispatch-bound nano regime hits the cap
        assert auto_fused_steps(0.0001, overhead_s=0.006, cap=64) == 64

    def test_cadence_clamp_keeps_ckpt_reachable(self):
        # K must divide the hook cadence so checkpoint steps stay exact
        assert auto_fused_steps(0.0001, overhead_s=0.006, cadence=10) == 10
        assert auto_fused_steps(0.0001, overhead_s=0.006, cap=8,
                                cadence=10) == 5
        assert auto_fused_steps(0.0001, overhead_s=0.006, cadence=7) == 7
        assert auto_fused_steps(0.0001, overhead_s=0.006, cap=6,
                                cadence=7) == 1

    def test_zero_step_time_capped(self):
        assert auto_fused_steps(0.0, overhead_s=0.006, cap=32) == 32


class TestFusedBatchStager:
    def test_alignment_and_tail(self):
        placed = []

        def place(b):
            placed.append(b)
            return b

        # resume at step 3 (mid-cycle, e.g. rollback), K=4, 13 steps total
        blocks = list(FusedBatchStager(
            lambda s: {"x": np.full((2,), s, np.int32)},
            place, fused_steps=4, start_step=3, max_steps=13,
            place_single=place))
        spans = [(s, k) for s, k, _ in blocks]
        # first block truncated to the next K-boundary, then full blocks,
        # then the tail
        assert spans == [(3, 1), (4, 4), (8, 4), (12, 1)]
        # stacked leaves carry the fused axis; k_eff=1 blocks stay flat
        assert blocks[1][2]["x"].shape == (4, 2)
        assert blocks[1][2]["x"][0, 0] == 4
        assert blocks[0][2]["x"].shape == (2,)

    def test_prefetch_thread_overlaps(self):
        import threading

        main = threading.get_ident()
        threads = set()

        def place(b):
            threads.add(threading.get_ident())
            return b

        out = list(FusedBatchStager(
            lambda s: {"x": np.zeros((1,), np.int32)}, place,
            fused_steps=2, start_step=0, max_steps=6))
        assert [(s, k) for s, k, _ in out] == [(0, 2), (2, 2), (4, 2)]
        assert threads and main not in threads  # placed off-thread

    def test_trainer_fused_matches_unfused(self, tmp_path):
        """End to end: the SAME data schedule through Trainer at K=1 and
        K=4 lands on the same final loss (hooks at boundaries only)."""
        from dlrover_wuqiong_tpu.checkpoint.ckpt_saver import (
            AsyncCheckpointSaver,
        )
        from dlrover_wuqiong_tpu.trainer.trainer import (
            Trainer,
            TrainingArgs,
        )

        def data(step):
            return _host_batch(step % 4)

        losses = {}
        for k in (1, 4):
            AsyncCheckpointSaver.reset()
            args = TrainingArgs(
                output_dir=str(tmp_path / f"k{k}"), max_steps=12,
                global_batch_size=8, seq_len=SEQ, learning_rate=1e-2,
                warmup_steps=2, logging_steps=4, save_steps=0,
                save_on_exit=False, strategy=[("fsdp", {})],
                fused_steps=k)
            tr = Trainer(_model(), args, data)
            out = tr.train()
            losses[k] = out["final_loss"]
            tr.ckpt.close()
        AsyncCheckpointSaver.reset()
        assert losses[1] == pytest.approx(losses[4], rel=1e-6)
