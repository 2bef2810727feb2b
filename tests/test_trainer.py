"""High-level Trainer tests (ATorchTrainer parity).

Runs the full stack on the virtual CPU mesh: strategy → sharded step →
flash ckpt save/resume → eval → callbacks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.checkpoint.ckpt_saver import AsyncCheckpointSaver
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs


@pytest.fixture(autouse=True)
def _fresh_saver():
    AsyncCheckpointSaver.reset()
    yield
    AsyncCheckpointSaver.reset()


def _model():
    return GPT(dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                   use_flash_attention=False, remat=False))


def _data(step, batch=8, seq=32, vocab=512):
    rng = np.random.default_rng(step % 4)  # small cycling dataset
    x = rng.integers(0, vocab, (batch, seq + 1))
    return {"input_ids": x[:, :-1], "labels": x[:, 1:]}


class TestTrainer:
    def test_train_loss_decreases_and_saves(self, tmp_path):
        seen = []
        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=24, global_batch_size=8,
            seq_len=32, learning_rate=1e-2, warmup_steps=2,
            logging_steps=4, save_steps=10, strategy=[("fsdp", {})])
        tr = Trainer(_model(), args, _data,
                     callbacks=[lambda s, m: seen.append((s, m["loss"]))])
        out = tr.train()
        assert out["final_step"] == 24
        assert seen and seen[-1][1] < seen[0][1]  # loss decreased
        # checkpoints committed on the save cadence + exit
        tracker = (tmp_path / "checkpoints" /
                   "latest_checkpointed_iteration.txt")
        assert tracker.exists()
        assert int(tracker.read_text()) == 24
        tr.ckpt.close()

    def test_resume_continues_from_checkpoint(self, tmp_path):
        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=6, seq_len=32,
            global_batch_size=8, warmup_steps=1, save_steps=3,
            logging_steps=2, strategy=[("fsdp", {})])
        tr1 = Trainer(_model(), args, _data)
        tr1.train()
        tr1.ckpt.close()
        AsyncCheckpointSaver.reset()

        args2 = dataclasses.replace(args, max_steps=10)
        tr2 = Trainer(_model(), args2, _data)
        out = tr2.train()
        # resumed (step 6) rather than restarting from zero
        assert int(np.asarray(jax.tree.leaves(tr2.state.step)[0])) == 10
        tracker = (tmp_path / "checkpoints" /
                   "latest_checkpointed_iteration.txt")
        assert int(tracker.read_text()) == 10
        tr2.ckpt.close()

    def test_evaluate(self, tmp_path):
        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=4, seq_len=32,
            global_batch_size=8, warmup_steps=1, save_steps=0,
            eval_steps=2, max_eval_batches=2, logging_steps=0,
            strategy=[("fsdp", {})], save_on_exit=False)
        tr = Trainer(_model(), args, _data, eval_data=_data)
        tr.train()
        loss = tr.evaluate()
        assert np.isfinite(loss)
        tr.ckpt.close()

    def test_lr_schedules(self, tmp_path):
        import optax

        for kind in ("cosine", "linear", "constant"):
            args = TrainingArgs(output_dir=str(tmp_path), max_steps=10,
                                lr_schedule=kind, warmup_steps=2)
            tr = Trainer.__new__(Trainer)
            tr.args = args
            sched = tr._make_schedule(optax)
            assert float(sched(0)) <= args.learning_rate
            assert np.isfinite(float(sched(9)))


class TestTunedConfigLoop:
    """The closed auto-tuning loop: master → agent ParalConfigTuner → file
    → trainer ParalConfigListener → ElasticDataLoader/ckpt cadence.

    Parity: reference trainer/torch/elastic/dataloader.py:97-133."""

    def test_master_tunes_loader_mid_epoch(self, tmp_path):
        from dlrover_wuqiong_tpu.agent.config_tuner import ParalConfigTuner
        from dlrover_wuqiong_tpu.agent.master_client import MasterClient
        from dlrover_wuqiong_tpu.common import messages as msg
        from dlrover_wuqiong_tpu.data.elastic_dataset import (
            ElasticDataLoader,
            ElasticDistributedSampler,
        )
        from dlrover_wuqiong_tpu.master.master import JobMaster

        master = JobMaster(min_nodes=1, max_nodes=1)
        master.prepare()
        try:
            mc = MasterClient(master.addr, node_id=0)
            tuner = ParalConfigTuner(
                mc, config_path=str(tmp_path / "paral.json"))

            vocab, seq = 512, 32
            rng = np.random.default_rng(0)
            table = rng.integers(0, vocab, (4096, seq + 1))

            def read_sample(i):
                return {"input_ids": table[i, :-1], "labels": table[i, 1:]}

            batch_sizes = []

            def collate(buf):
                batch_sizes.append(len(buf))
                return jax.tree.map(lambda *xs: np.stack(xs), *buf)

            loader = ElasticDataLoader(
                read_sample, batch_size=8,
                sampler=ElasticDistributedSampler(dataset_size=4096),
                collate=collate)

            def push(step, metrics):
                if step == 2:  # mid-training: the master retunes
                    master.update_paral_config(msg.ParallelConfig(
                        dataloader_batch_size=16, ckpt_interval_steps=50))
                    tuner.poll_once()

            args = TrainingArgs(
                output_dir=str(tmp_path / "out"), max_steps=8,
                global_batch_size=8, seq_len=seq, warmup_steps=1,
                logging_steps=2, save_steps=0, save_on_exit=False,
                tune_config_steps=1, strategy=[("fsdp", {})])
            tr = Trainer(_model(), args, loader, callbacks=[push])
            tr.train()
            # the loader really emitted differently-sized batches mid-epoch
            assert 8 in batch_sizes and 16 in batch_sizes, batch_sizes
            assert batch_sizes[-1] == 16
            # ckpt cadence followed the master's tuning
            assert tr.args.save_steps == 50
            tr.ckpt.close()
        finally:
            import os

            from dlrover_wuqiong_tpu.common.constants import ConfigPath

            os.environ.pop(ConfigPath.ENV_PARAL_CONFIG, None)
            master.stop()
            MasterClient.reset()


class TestTrainerDepth:
    """Weak-spot coverage (VERDICT r2 #8): callbacks, profiler window,
    save-on-exit, eval cadence asserted tightly."""

    def test_callbacks_cadence_and_metrics(self, tmp_path):
        seen = []
        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=12, global_batch_size=8,
            seq_len=32, warmup_steps=1, logging_steps=3, save_steps=0,
            save_on_exit=False, strategy=[("fsdp", {})])
        Trainer(_model(), args, _data,
                callbacks=[lambda s, m: seen.append((s, m))]).train()
        assert [s for s, _ in seen] == [3, 6, 9, 12]
        for _, m in seen:
            assert {"loss", "tokens_per_sec"} <= set(m)
            assert np.isfinite(m["loss"]) and m["tokens_per_sec"] > 0

    def test_profiler_window_produces_op_profile(self, tmp_path):
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=6,
            global_batch_size=8, seq_len=32, warmup_steps=1,
            logging_steps=0, save_steps=0, save_on_exit=False,
            profile_trace_dir=str(tmp_path / "trace"),
            profile_start_step=2, profile_end_step=4,
            # unfused: the auto-tuned K depends on the machine's load,
            # and a K-step scan traces as `while`, not as its matmuls
            fused_steps=1,
            strategy=[("fsdp", {})])
        tr = Trainer(_model(), args, _data)
        tr.train()
        assert tr.profiler.last_profile is not None
        cats = tr.profiler.last_profile.categories
        assert "matmul" in cats and cats["matmul"] > 0
        import glob

        assert glob.glob(str(tmp_path / "trace" / "plugins" / "profile" /
                             "*" / "*.xplane.pb"))

    def test_save_on_exit_persists_after_crash(self, tmp_path):
        """A mid-train exception must still leave a committed checkpoint
        at the crash step (the finally-block save)."""
        class Boom(RuntimeError):
            pass

        def exploding_cb(step, metrics):
            if step >= 4:
                raise Boom()

        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=20, global_batch_size=8,
            seq_len=32, warmup_steps=1, logging_steps=2, save_steps=0,
            save_on_exit=True, strategy=[("fsdp", {})])
        tr = Trainer(_model(), args, _data, callbacks=[exploding_cb])
        with pytest.raises(Boom):
            tr.train()
        tracker = (tmp_path / "checkpoints" /
                   "latest_checkpointed_iteration.txt")
        assert tracker.exists()
        assert int(tracker.read_text()) == 4
        tr.ckpt.close()

    def test_eval_cadence(self, tmp_path):
        eval_calls = []

        def eval_data(step):
            eval_calls.append(step)
            return _data(step)

        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=8, global_batch_size=8,
            seq_len=32, warmup_steps=1, logging_steps=0, save_steps=0,
            eval_steps=4, max_eval_batches=2, save_on_exit=False,
            strategy=[("fsdp", {})])
        Trainer(_model(), args, _data, eval_data=eval_data).train()
        # 8 steps / eval every 4 = 2 eval passes x 2 batches each
        assert len(eval_calls) == 4


class TestWireDtypeTrainer:
    def test_bf16_wire_train_save_resume(self, tmp_path):
        """ckpt_wire_dtype="bf16" plumbs through to the checkpointer:
        half-width shards on disk, resume still lands on the committed
        step (values bf16-quantized — the documented contract)."""
        import json as _json

        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=4, seq_len=32,
            global_batch_size=8, warmup_steps=1, save_steps=2,
            logging_steps=0, strategy=[("fsdp", {})],
            ckpt_wire_dtype="bf16")
        tr1 = Trainer(_model(), args, _data)
        tr1.train()
        tr1.ckpt.close()
        AsyncCheckpointSaver.reset()
        # f32 params were staged as bf16 on disk
        sdir = tmp_path / "checkpoints" / "checkpoint-4"
        metas = [t for mf in sdir.glob("meta_rank*.json")
                 for t in _json.loads(mf.read_text())["tensors"]]
        kinds = {t["dtype"] for t in metas
                 if "wte" in t["name"] or "kernel" in t["name"]}
        assert kinds == {"bfloat16"}, kinds

        args2 = dataclasses.replace(args, max_steps=6)
        tr2 = Trainer(_model(), args2, _data)
        out = tr2.train()
        assert out["final_step"] == 6
        assert int(np.asarray(jax.tree.leaves(tr2.state.step)[0])) == 6
        tr2.ckpt.close()
