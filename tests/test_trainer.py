"""High-level Trainer tests (ATorchTrainer parity).

Runs the full stack on the virtual CPU mesh: strategy → sharded step →
flash ckpt save/resume → eval → callbacks.
"""

import dataclasses
import logging
import threading
import time

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


def _compiles():
    """How often this process has lowered or compiled, and how often
    it has asked the persistent cache."""
    from dlrover_wuqiong_tpu.auto import compile_cache

    return (sum(d["name"] in ("jax:backend_compile", "jax:lower")
                for d in compile_cache.durations),
            compile_cache.counters.hits + compile_cache.counters.misses)


class TestStepTracing:
    """PR 24: a short `Trainer.train()` leaves the set-up spans, one
    `trainer:iteration` per step with its children, the `jax:*` duration
    records of the step's own function, and the step's `Compiled` —
    kept without compiling anything."""

    STEPS = 12

    @pytest.fixture
    def trained(self, tmp_path):
        from dlrover_wuqiong_tpu.auto import compile_cache
        from dlrover_wuqiong_tpu.telemetry import perf
        from dlrover_wuqiong_tpu.telemetry import spans as tspans

        tspans.clear_spans()
        compile_cache.durations.clear()
        perf._step_executables.clear()
        logged = []

        class _Tap(logging.Handler):
            def emit(self, record):
                if isinstance(record.msg, str) and \
                        record.msg.startswith("step %d loss="):
                    logged.append(record.args)

        tap = _Tap()
        logging.getLogger("dwt.trainer").addHandler(tap)
        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=self.STEPS,
            global_batch_size=8, seq_len=32, warmup_steps=1,
            logging_steps=4, save_steps=8, flash_stage_steps=4,
            fused_steps=1, perf_window_every=0, save_on_exit=False,
            strategy=[("fsdp", {})])
        tr = Trainer(_model(), args, _data)
        try:
            out = tr.train()
            tr.ckpt.wait_staging(60)
        finally:
            logging.getLogger("dwt.trainer").removeHandler(tap)
        yield tr, out, logged
        tr.ckpt.close()

    def test_build_spans_and_one_iteration_per_step(self, trained):
        from dlrover_wuqiong_tpu.telemetry import spans as tspans

        full = {}
        for s in tspans.spans_snapshot():
            full.setdefault(s["name"], []).append(s)
        (build,) = full["trainer:build"]
        for child in ("accelerate:plan", "accelerate:init_state",
                      "ckpt:open"):
            (rec,) = full[child]
            assert rec["parent_span"] == build["span_id"], child
            assert build["t_mono"] <= rec["t_mono"] and \
                rec["t_mono"] + rec["dur_s"] <= \
                build["t_mono"] + build["dur_s"]
        hot = tspans.hot_spans_snapshot()
        its = [s for s in hot if s["name"] == "trainer:iteration"]
        assert len(its) == self.STEPS
        ids = {s["span_id"] for s in its}
        for name, n in (("trainer:data", self.STEPS),
                        ("trainer:dispatch", self.STEPS),
                        ("trainer:log_submit", 3), ("trainer:save", 1),
                        ("trainer:stage", 2)):
            hits = [s for s in hot if s["name"] == name]
            assert len(hits) == n, name
            assert all(s["parent_span"] in ids for s in hits), name
        # the pump's spans hang under the boundary that submitted them
        subs = {s["span_id"] for s in hot
                if s["name"] == "trainer:log_submit"}
        for name in ("pump:readback", "pump:report"):
            hits = [s for s in hot if s["name"] == name]
            assert len(hits) == 3 and \
                all(s["parent_span"] in subs for s in hits), name
        # the engine's spans: snapshot under save under the loop's hook,
        # the drain (another thread) under the save that started it
        hooks = {s["span_id"] for s in hot
                 if s["name"] in ("trainer:save", "trainer:stage")}
        saves = {s["span_id"] for s in full["ckpt:save"]}
        assert len(saves) == 3
        assert all(s["parent_span"] in hooks for s in full["ckpt:save"])
        assert all(s["parent_span"] in saves
                   for s in full["ckpt:snapshot"] + full["ckpt:drain"])
        assert len(full["ckpt:drain"]) == 3
        assert len(full["ckpt:persist"]) == 1  # the one DISK save

    def test_jax_duration_records_name_the_step(self, trained):
        from dlrover_wuqiong_tpu.auto import compile_cache

        mine = [d for d in compile_cache.durations
                if d["fun_name"] in ("train_step", "jit(train_step)")]
        names = [d["name"] for d in mine]
        for name in ("jax:trace", "jax:lower", "jax:backend_compile"):
            assert name in names, names
        assert all(d["dur_s"] >= 0 and d["t_mono"] > 0 for d in mine)
        # a retrieval, where the cache served the step, is named after
        # the compile it served
        loads = [d for d in compile_cache.durations
                 if d["name"] == "jax:cache_load"]
        assert all(d["fun_name"] for d in loads)

    def test_duration_listener_names_loads_under_threads(self):
        """Every thread compiles (loop, pump, eval, drain), and the
        listener runs inside JAX's compile path: a retrieval takes the
        name of its own thread's compile, and nothing raises into it."""
        import threading

        from jax._src import monitoring

        from dlrover_wuqiong_tpu.auto import compile_cache

        compile_cache._install_listeners()
        compile_cache.durations.clear()
        failed = []

        def compiler(tag):
            try:
                for _ in range(1500):
                    monitoring.record_event_duration_secs(
                        "/jax/compilation_cache/cache_retrieval_time_sec",
                        1e-3)
                    monitoring.record_event_duration_secs(
                        "/jax/core/compile/backend_compile_duration",
                        2e-3, fun_name=tag)
            except Exception as e:  # noqa: BLE001
                failed.append(e)

        threads = [threading.Thread(target=compiler, args=(f"f{i}",))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not failed, failed
        recs = list(compile_cache.durations)
        assert len(recs) == 4 * 1500 * 2
        named = {}
        for r in recs:
            named.setdefault((r["name"], r["fun_name"]), 0)
            named[r["name"], r["fun_name"]] += 1
        assert named == {(n, f"f{i}"): 1500 for i in range(4)
                         for n in ("jax:cache_load", "jax:backend_compile")}
        compile_cache.durations.clear()

    def test_keeping_the_compiled_step_compiles_nothing(self, trained):
        from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table
        from dlrover_wuqiong_tpu.auto import compile_cache
        from dlrover_wuqiong_tpu.telemetry.perf import step_executables

        from dlrover_wuqiong_tpu.telemetry import perf

        # the loop kept the way back to the executable; the state it was
        # given has been donated eleven times since
        (find,) = perf._step_executables.values()
        assert not hasattr(find, "as_text")
        before = _compiles()
        (mode,) = step_executables()
        compiled = step_executables()[mode]  # found once, then kept
        assert _compiles() == before
        assert mode == 1 and compiled is step_executables()[mode]
        table = scope_table(compiled.as_text())
        scopes = set(table.values())
        assert "optimizer" in scopes and "fwd/loss" in scopes
        assert any(s.startswith("bwd/GPT/head") for s in scopes)

    def test_kept_step_pins_no_array_without_donation(self, tmp_path,
                                                      monkeypatch):
        """With donation off (optimizer_offload resolves to that) no
        later step frees what the first dispatch returned, so the slot
        must hold shapes and shardings only: the first state's buffers
        are collectable once the loop has moved on, and the executable
        is still found without compiling."""
        import gc
        import weakref

        import jax

        from dlrover_wuqiong_tpu.telemetry import perf

        perf._step_executables.clear()
        first = []
        keep = perf.keep_step_executable

        def tap(mode, jitted, state, batch):
            first.extend(weakref.ref(x)
                         for x in jax.tree.leaves((state, batch)))
            keep(mode, jitted, state, batch)

        monkeypatch.setattr(perf, "keep_step_executable", tap)
        args = TrainingArgs(
            output_dir=str(tmp_path), max_steps=3, global_batch_size=8,
            seq_len=32, warmup_steps=1, logging_steps=0, save_steps=0,
            fused_steps=1, perf_window_every=0, save_on_exit=False,
            strategy=[("fsdp", {}), ("optimizer_offload", {})])
        tr = Trainer(_model(), args, _data)
        try:
            tr.train()
            gc.collect()
            assert first and not [r for r in first if r() is not None]
            before = _compiles()
            (compiled,) = perf.step_executables().values()
            assert _compiles() == before
            assert "optimizer" in compiled.as_text()
        finally:
            tr.ckpt.close()

    def test_logged_tokens_per_s_is_readback_to_readback(self, trained):
        _, out, logged = trained
        assert [a[0] for a in logged] == [4, 8, 12]
        assert out["stopped_at"] == self.STEPS
        # 8 x 32 tokens a step on a CPU: thousands a second, not the
        # millions the dispatch clock used to print
        for step, loss, tps in logged:
            assert np.isfinite(loss) and 0 < tps < 1e6, (step, tps)

    def test_tokens_per_s_counts_device_progress(self, trained):
        """10 steps of 256 tokens between two readbacks 2 s apart are
        1280 tokens/s, however fast the loop dispatched them; the same
        figure reaches the callbacks."""
        tr, _, logged = trained
        got = []
        tr.callbacks = [lambda step, m: got.append((step, m))]
        tr._readback_mark = (100, 10.0)
        job = {"tokens_per_step": 256, "ledger": {}, "pw": None}
        tr._report_boundary(job, 110, 1.5, 12.0)
        assert tr._readback_mark == (110, 12.0)
        assert got == [(110, {"loss": 1.5, "tokens_per_sec": 1280.0})]


def test_first_dispatch_at_a_width_is_compile_then_overhead(tmp_path,
                                                            monkeypatch):
    """The ledger's modes are fusion widths: the first dispatch at a K
    is credited `compile`, every later one `dispatch_overhead`."""
    from dlrover_wuqiong_tpu.telemetry import ledger

    led = ledger.reset_ledger()
    credits = []
    account = led.account
    monkeypatch.setattr(led, "account", lambda state, s: (
        credits.append(state), account(state, s))[1])
    tr = Trainer(_model(), TrainingArgs(
        output_dir=str(tmp_path), max_steps=6, global_batch_size=8,
        seq_len=32, warmup_steps=1, logging_steps=0, save_steps=0,
        fused_steps=2, perf_window_every=0, save_on_exit=False,
        strategy=[("fsdp", {})]), _data)
    try:
        tr.train()
    finally:
        tr.ckpt.close()
    assert tr._compiled_modes == {2}
    assert [c for c in credits if c in ("compile", "dispatch_overhead")] \
        == ["compile", "dispatch_overhead", "dispatch_overhead"]


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
