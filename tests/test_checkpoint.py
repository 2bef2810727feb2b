"""Flash-checkpoint tests: shm staging, async persistence, commit, restore.

Mirrors reference `dlrover/python/tests/test_ckpt_saver.py` and
`dlrover/trainer/tests/torch/checkpoint_egine_test.py` — real POSIX shm on a
single host, sharded arrays over the virtual 8-device CPU mesh.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_wuqiong_tpu.checkpoint.ckpt_saver import (
    AsyncCheckpointSaver,
    read_last_step,
)
from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer,
    StorageType,
)
from dlrover_wuqiong_tpu.checkpoint.engine import CheckpointEngine
from dlrover_wuqiong_tpu.checkpoint.shm_handler import (
    SharedMemoryHandler,
    flatten_state_dict,
)


@pytest.fixture(autouse=True)
def _fresh_saver():
    AsyncCheckpointSaver.reset()
    yield
    AsyncCheckpointSaver.reset()


def _mesh():
    return Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))


class TestShmHandler:
    def test_flatten(self):
        state = {"a": {"b": jnp.ones((2,)), "c": [jnp.zeros((3,))]}}
        flat = flatten_state_dict(state)
        assert set(flat) == {"a/b", "a/c/0"}

    def test_roundtrip_numpy(self):
        h = SharedMemoryHandler(0, "t-shm1")
        state = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                 "b": np.array([1, 2], dtype=np.int32)}
        h.save_state_dict(state, step=7)
        step, flat, metas, extra = h.load_state_dict()
        assert step == 7
        np.testing.assert_array_equal(flat["w"], state["w"])
        np.testing.assert_array_equal(flat["b"], state["b"])
        h.unlink()

    def test_bfloat16_roundtrip(self):
        h = SharedMemoryHandler(0, "t-shm2")
        x = jnp.ones((8, 8), dtype=jnp.bfloat16) * 1.5
        h.save_state_dict({"x": x}, step=1)
        _, flat, _, _ = h.load_state_dict()
        assert flat["x"].dtype.name == "bfloat16"
        np.testing.assert_array_equal(np.asarray(flat["x"], np.float32), 1.5)
        h.unlink()

    def test_sharded_array_staging(self):
        mesh = _mesh()
        x = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("data", "model")))
        h = SharedMemoryHandler(0, "t-shm3")
        h.save_state_dict({"x": x}, step=2)
        _, flat, metas, _ = h.load_state_dict()
        # 8 unique shards staged with indices
        shard_names = [m.name for m in metas]
        assert len(shard_names) == 8
        assert all("#shard" in n for n in shard_names)
        # verify one shard content
        m0 = metas[0]
        slices = tuple(slice(s, e) for s, e in m0.index)
        np.testing.assert_array_equal(
            flat[m0.name], np.asarray(x)[slices])
        h.unlink()

    def test_replicated_array_staged_once(self):
        mesh = _mesh()
        x = jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh, P()))
        h = SharedMemoryHandler(0, "t-shm4")
        h.save_state_dict({"x": x}, step=3)
        _, flat, metas, _ = h.load_state_dict()
        assert [m.name for m in metas] == ["x"]
        h.unlink()


class TestEngineEndToEnd:
    def test_save_load_storage(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine(ckpt_dir, job_name="t-eng1",
                                  standalone=True)
        state = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
                 "step": np.int64(5)}
        blocked = engine.save_to_storage(5, state)
        assert blocked < 5.0
        assert engine.wait_saving_latest(timeout=30)
        assert read_last_step(ckpt_dir) == 5
        flat = engine.load_from_storage()
        np.testing.assert_array_equal(flat["w"],
                                      np.arange(16).reshape(4, 4))
        engine.close()

    def test_sharded_save_and_global_assembly(self, tmp_path):
        mesh = _mesh()
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine(ckpt_dir, job_name="t-eng2",
                                  standalone=True)
        x = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("data", None)))
        engine.save_to_storage(1, {"x": x})
        assert engine.wait_saving_latest(timeout=30)
        flat = engine.load_from_storage()
        np.testing.assert_array_equal(
            flat["x"], np.arange(64, dtype=np.float32).reshape(8, 8))
        engine.close()

    def test_memory_only_then_load_from_shm(self, tmp_path):
        engine = CheckpointEngine(str(tmp_path / "c"), job_name="t-eng3",
                                  standalone=True)
        state = {"v": jnp.ones((4,))}
        engine.save_to_memory(9, state)
        flat = engine.load()
        np.testing.assert_array_equal(flat["v"], np.ones(4))
        engine.close()


class TestFlashCheckpointer:
    def test_full_cycle_with_sharding_restore(self, tmp_path):
        mesh = _mesh()
        sharding = NamedSharding(mesh, P("data", "model"))
        ckpt_dir = str(tmp_path / "run")
        ckpt = FlashCheckpointer(ckpt_dir, job_name="t-fc1",
                                 standalone=True)
        params = {
            "dense": {"kernel": jax.device_put(
                jnp.arange(64, dtype=jnp.float32).reshape(8, 8), sharding)},
            "bias": jnp.zeros((8,)),
        }
        blocked = ckpt.save_checkpoint(10, params,
                                       storage_type=StorageType.DISK)
        assert blocked < 5.0
        assert ckpt.wait_latest_checkpoint(30)

        # fresh checkpointer (simulating restart) restores into template
        AsyncCheckpointSaver.reset()
        ckpt2 = FlashCheckpointer(ckpt_dir, job_name="t-fc2",
                                  standalone=True)
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        # attach shardings to template leaves
        template["dense"]["kernel"] = jax.ShapeDtypeStruct(
            (8, 8), jnp.float32, sharding=sharding)
        restored = ckpt2.load_checkpoint(template)
        assert restored is not None
        np.testing.assert_array_equal(
            np.asarray(restored["dense"]["kernel"]),
            np.arange(64, dtype=np.float32).reshape(8, 8))
        assert restored["dense"]["kernel"].sharding == sharding
        ckpt.close()
        ckpt2.close()

    def test_save_speed_vs_direct_write(self, tmp_path):
        """Flash save must block far less than a full serialize+fsync write."""
        ckpt = FlashCheckpointer(str(tmp_path / "speed"), job_name="t-fc3",
                                 standalone=True)
        big = {"w": jnp.ones((512, 512), dtype=jnp.float32)}
        t0 = time.time()
        blocked = ckpt.save_checkpoint(1, big, storage_type=StorageType.MEMORY)
        assert blocked < 1.0
        ckpt.close()


class TestMultiNodeCommit:
    def test_tracker_waits_for_all_world_shards(self, tmp_path):
        """Node-0's agent must not publish the tracker until every rank's
        done-file lands (reference ckpt_saver.py:863) — a premature tracker
        is a torn checkpoint on any multi-node job."""
        import threading

        from dlrover_wuqiong_tpu.common.constants import CheckpointConstant

        path = str(tmp_path / "mn")
        saver0 = AsyncCheckpointSaver(job_name="t-mn0", local_shard_num=1,
                                      node_rank=0, world_shard_num=2)
        saver1 = AsyncCheckpointSaver(job_name="t-mn1", local_shard_num=1,
                                      node_rank=1, world_shard_num=2)
        try:
            h0 = SharedMemoryHandler(0, "t-mn0")
            h0.save_state_dict({"w": np.ones((4,), np.float32)}, step=3)
            h1 = SharedMemoryHandler(0, "t-mn1")
            h1.save_state_dict({"w": np.ones((4,), np.float32) * 2}, step=3)

            done0 = threading.Event()

            def _node0_save():
                saver0.save_step_checkpoint(3, path, commit_timeout=30)
                done0.set()

            t = threading.Thread(target=_node0_save, daemon=True)
            t.start()
            time.sleep(1.5)  # node 0 alone: commit must still be waiting
            tracker = os.path.join(path, CheckpointConstant.TRACKER_FILE)
            assert not done0.is_set()
            assert not os.path.exists(tracker), "premature tracker publish"

            saver1.save_step_checkpoint(3, path)  # rank!=0 never commits
            assert done0.wait(timeout=30)
            assert read_last_step(path) == 3
        finally:
            saver0._shm_handlers[0].unlink()
            saver1._shm_handlers[0].unlink()
            saver0._event_queue.close()
            saver1._event_queue.close()

    def test_node1_global_rank_offset(self, tmp_path):
        path = str(tmp_path / "gr")
        saver = AsyncCheckpointSaver(job_name="t-gr1", local_shard_num=1,
                                     node_rank=1, world_shard_num=2)
        try:
            h = SharedMemoryHandler(0, "t-gr1")
            h.save_state_dict({"w": np.zeros((2,), np.float32)}, step=1)
            saver.save_step_checkpoint(1, path)
            sdir = os.path.join(path, "checkpoint-1")
            assert os.path.exists(os.path.join(sdir, "meta_rank1.json"))
            assert os.path.exists(os.path.join(sdir, ".done", "rank1.done"))
        finally:
            saver._shm_handlers[0].unlink()
            saver._event_queue.close()


class TestTeardownFlush:
    def test_stop_persists_memory_only_checkpoint(self, tmp_path):
        """A MEMORY-only save newer than the last persisted step must be
        flushed to storage on clean teardown, not discarded with the shm
        segment (reference save_shm_to_storage on teardown, :634)."""
        ckpt_dir = str(tmp_path / "flush")
        ckpt = FlashCheckpointer(ckpt_dir, job_name="t-flush1",
                                 standalone=True)
        state = {"w": jnp.arange(8, dtype=jnp.float32)}
        ckpt.save_checkpoint(4, state, storage_type=StorageType.MEMORY)
        ckpt.close()
        AsyncCheckpointSaver.reset()  # triggers saver.stop() → flush
        assert read_last_step(ckpt_dir) == 4
        eng = CheckpointEngine(ckpt_dir, job_name="t-flush2",
                               standalone=True)
        flat = eng.load_from_storage()
        np.testing.assert_array_equal(flat["w"], np.arange(8))
        eng.close()


class TestObjectStoreStorage:
    def test_scheme_resolution(self):
        from dlrover_wuqiong_tpu.common.storage import (
            ObjectStoreStorage,
            PosixDiskStorage,
            get_checkpoint_storage,
        )

        assert isinstance(get_checkpoint_storage(path_hint="/tmp/x"),
                          PosixDiskStorage)
        assert isinstance(get_checkpoint_storage(path_hint="gs://b/x"),
                          ObjectStoreStorage)

    def test_epath_backend_roundtrip(self, tmp_path):
        """ObjectStoreStorage works over posix paths too (epath routing) —
        the full ckpt cycle runs through it end to end."""
        from dlrover_wuqiong_tpu.common.storage import ObjectStoreStorage

        storage = ObjectStoreStorage()
        ckpt_dir = str(tmp_path / "obj")
        engine = CheckpointEngine(ckpt_dir, job_name="t-obj1",
                                  standalone=True, storage=storage)
        state = {"w": jnp.arange(8, dtype=jnp.float32)}
        engine.save_to_storage(3, state)
        assert engine.wait_saving_latest(30)
        assert read_last_step(ckpt_dir, storage) == 3
        flat = engine.load_from_storage()
        np.testing.assert_array_equal(flat["w"], np.arange(8))
        engine.close()


@pytest.mark.slow  # tier-2: ~210s of orbax serialization; interop only —
# the flash engine's own save/restore integrity is tier-1 elsewhere
class TestOrbaxInterop:
    """Flash <-> Orbax layout adapters (SURVEY §7 item 3): checkpoints are
    not framework-locked — a sharded train state round-trips through
    orbax.checkpoint with values and shardings intact."""

    def _sharded_state(self):
        import optax

        from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
        from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

        res = auto_accelerate(GPT(GPTConfig.nano()),
                              optimizer=optax.sgd(1e-2),
                              strategy=[("fsdp", {})])
        return res.state._asdict()

    def test_flash_to_orbax_roundtrip(self, tmp_path):
        from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
            FlashCheckpointer,
            StorageType,
        )
        from dlrover_wuqiong_tpu.checkpoint.orbax_compat import (
            export_orbax,
            load_orbax,
        )

        state = self._sharded_state()
        flash_dir = str(tmp_path / "flash")
        ck = FlashCheckpointer(flash_dir, job_name=f"orbx{os.getpid()}")
        try:
            ck.save_checkpoint(7, state, storage_type=StorageType.DISK)
            assert ck.wait_latest_checkpoint(120)
        finally:
            ck.close()

        orbax_path = str(tmp_path / "orbax" / "step7")
        export_orbax(flash_dir, orbax_path, state)
        loaded = load_orbax(orbax_path, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert b.sharding == a.sharding  # restored onto the mesh

    def test_orbax_to_flash_import(self, tmp_path):
        from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
            FlashCheckpointer,
        )
        from dlrover_wuqiong_tpu.checkpoint.orbax_compat import (
            import_orbax,
            save_orbax,
        )

        state = self._sharded_state()
        orbax_path = str(tmp_path / "orbax" / "pretrained")
        save_orbax(orbax_path, state)

        flash_dir = str(tmp_path / "flash-import")
        import_orbax(orbax_path, flash_dir, state, step=3)
        ck = FlashCheckpointer(flash_dir, job_name=f"orbi{os.getpid()}")
        try:
            assert ck.last_step() == 3
            loaded = ck.load_checkpoint(state)
        finally:
            ck.close()
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestRollbackBeforeStep:
    def test_load_before_step_picks_pre_spike_commit(self, tmp_path):
        """ADVICE r4: rollback must restore the newest committed step that
        PRECEDES the spike, not the tracker's latest (which may postdate
        spike onset)."""
        ckpt_dir = str(tmp_path / "rb")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-rb1", standalone=True)
        for step in (5, 10, 15):
            ck.save_checkpoint(step, {"w": jnp.full((4,), float(step))},
                               storage_type=StorageType.DISK)
            # each staged step must commit before the next save reuses the
            # shm segment (flash ckpt keeps ONE staged step at a time)
            assert ck.wait_latest_checkpoint(30)
        assert ck.engine.committed_steps() == [5, 10, 15]
        template = {"w": jnp.zeros((4,))}
        # spike detected at step 12 -> newest committed step < 12 is 10
        restored = ck.load_checkpoint(template, before_step=12)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4,), 10.0))
        # rollback durability: the post-spike step 15 is a poisoned
        # lineage — demoted so a later naive resume cannot pick it up
        assert ck.engine.committed_steps() == [5, 10]
        assert ck.last_step() == 10
        # no committed step precedes 5 -> falls back to latest (now 10)
        restored = ck.load_checkpoint(template, before_step=5)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4,), 10.0))
        ck.close()

    def test_partial_step_not_committed_and_not_assembled(self, tmp_path):
        """A step dir with done-files but NO commit marker (crash before
        every shard landed) must be invisible to rollback, and a
        shard-incomplete step must refuse to assemble."""
        import os
        import shutil

        ckpt_dir = str(tmp_path / "rbp")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-rb2", standalone=True)
        for step in (5, 10):
            ck.save_checkpoint(step, {"w": jnp.full((4,), float(step))},
                               storage_type=StorageType.DISK)
            assert ck.wait_latest_checkpoint(30)
        # forge a partial step 8: copy step 5's dir, strip the marker
        src, dst = (os.path.join(ckpt_dir, f"checkpoint-{s}")
                    for s in (5, 8))
        shutil.copytree(src, dst)
        os.remove(os.path.join(dst, ".commit"))
        assert ck.engine.committed_steps() == [5, 10]  # 8 invisible
        restored = ck.load_checkpoint({"w": jnp.zeros((4,))},
                                      before_step=9)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4,), 5.0))
        ck.close()


class TestTrustBoundary:
    """Checkpoint trust boundary (checkpoint/integrity.py): digests at
    every tier, atomic manifest commit, quarantine-not-delete, verified
    fallback, self-heal."""

    def _commit(self, ck, step, value, shape=(8, 8)):
        ck.save_checkpoint(step, {"w": jnp.full(shape, value),
                                  "step": np.int64(step)},
                           storage_type=StorageType.DISK)
        assert ck.wait_latest_checkpoint(30)

    def test_manifest_roundtrip_across_dtypes_and_shardings(self, tmp_path):
        """Property test: a committed generation's manifest verifies
        per-leaf for every dtype/sharding combination the stack stages,
        and restore is exact for each."""
        from dlrover_wuqiong_tpu.checkpoint.integrity import (
            read_manifest,
            verify_storage_step,
        )
        from dlrover_wuqiong_tpu.common.storage import PosixDiskStorage

        mesh = _mesh()
        ckpt_dir = str(tmp_path / "prop")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-tb-prop",
                               standalone=True)
        rng = np.random.default_rng(0)
        state = {
            "f32_2d": jax.device_put(
                jnp.asarray(rng.normal(size=(8, 8)), jnp.float32),
                NamedSharding(mesh, P("data", "model"))),
            "f32_rep": jax.device_put(jnp.asarray(
                rng.normal(size=(4, 4)), jnp.float32),
                NamedSharding(mesh, P())),
            "bf16_row": jax.device_put(jnp.asarray(
                rng.normal(size=(8, 2)), jnp.bfloat16),
                NamedSharding(mesh, P("data", None))),
            "i32": jnp.arange(16, dtype=jnp.int32),
            "u8": jnp.asarray(rng.integers(0, 255, (5,)), jnp.uint8),
            "scalar": np.int64(42),
        }
        ck.save_checkpoint(3, state, storage_type=StorageType.DISK)
        assert ck.wait_latest_checkpoint(30)
        storage = PosixDiskStorage()
        # deep (per-leaf) verification passes on healthy bytes
        v = verify_storage_step(storage, ckpt_dir, 3, per_leaf=True)
        assert v["ok"] and not v["bad_leaves"], v
        m = read_manifest(storage, str(tmp_path / "prop" / "checkpoint-3"))
        assert m["step"] == 3 and m["algo"] and m["ranks"], m
        # exact round trip for every leaf
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None))
            if hasattr(x, "sharding") else x, state)
        ck.engine._shm_handler.mark_empty()  # force the storage tier
        restored = ck.load_checkpoint(template)
        assert ck.last_restore_report["tier"] == "storage"
        for name, a in flatten_state_dict(state).items():
            b = flatten_state_dict(restored)[name]
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ck.close()

    def test_torn_manifest_falls_back_and_quarantines(self, tmp_path):
        ckpt_dir = str(tmp_path / "torn")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-tb-torn",
                               standalone=True)
        for step, val in ((5, 5.0), (10, 10.0)):
            self._commit(ck, step, val)
        # tear the newest manifest mid-json (as a crashed rewrite would)
        mpath = os.path.join(ckpt_dir, "checkpoint-10", "manifest.json")
        raw = open(mpath).read()
        open(mpath, "w").write(raw[:len(raw) // 2])  # graftlint: disable=atomic-publish -- the torn manifest IS the fault under test
        ck.engine._shm_handler.mark_empty()
        restored = ck.load_checkpoint({"w": jnp.zeros((8, 8)),
                                       "step": np.int64(0)})
        rep = ck.last_restore_report
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((8, 8), 5.0))
        assert rep["tier"] == "storage" and rep["step"] == 5
        assert any(f["reason"] == "missing-manifest"
                   for f in rep["fallbacks"])  # torn == unreadable
        qdir = tmp_path / "torn" / ".quarantine" / "checkpoint-10"
        assert qdir.is_dir()  # evidence moved aside, not deleted
        assert (qdir / ".reason").exists()
        ck.close()

    def test_shm_flip_detected_heals_and_reverifies(self, tmp_path):
        ckpt_dir = str(tmp_path / "flip")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-tb-flip",
                               standalone=True)
        self._commit(ck, 7, 7.0)
        h = ck.engine._shm_handler
        ok, _ = h.verify()
        assert ok
        buf = h._buf.buf
        buf[1 << 20] = (buf[1 << 20] + 1) % 256  # first payload byte
        ok, why = h.verify()
        assert not ok and "digest-mismatch" in why
        restored = ck.load_checkpoint({"w": jnp.zeros((8, 8)),
                                       "step": np.int64(0)})
        rep = ck.last_restore_report
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((8, 8), 7.0))
        assert rep["tier"] == "storage" and rep["healed"]
        assert any(f["tier"] == "shm" for f in rep["fallbacks"])
        # self-heal re-staged a verified copy: next load is the fast tier
        restored = ck.load_checkpoint({"w": jnp.zeros((8, 8)),
                                       "step": np.int64(0)})
        assert ck.last_restore_report["tier"] == "shm"
        ck.close()

    def test_corrupt_shm_never_persists(self, tmp_path):
        """The saver digest-checks while streaming shm → storage: a
        segment corrupted AFTER staging must abort the persist, never
        become a committed generation."""
        ckpt_dir = str(tmp_path / "nop")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-tb-nop",
                               standalone=True)
        self._commit(ck, 1, 1.0)
        ck.save_checkpoint(2, {"w": jnp.full((8, 8), 2.0),
                               "step": np.int64(2)},
                           storage_type=StorageType.MEMORY)
        ck.wait_staging(30)
        h = ck.engine._shm_handler
        h._buf.buf[1 << 20] ^= 0xFF  # corrupt the staged step-2 payload
        saver = AsyncCheckpointSaver.get_ckpt_saver()
        saver.save_step_checkpoint(2, ckpt_dir, commit_timeout=3)
        assert read_last_step(ckpt_dir) == 1  # step 2 never committed
        marker = os.path.join(ckpt_dir, "checkpoint-2", ".commit")
        assert not os.path.exists(marker)
        ck.close()

    def test_saver_finds_a_recreated_segment_by_name(self, tmp_path):
        """Between two worker generations the staging segment can be
        unlinked and made anew under the same name (a stale-segment sweep
        reaps a dead creator's; a writer that needs more room recreates
        it).  The agent's saver still maps the old one: it must look the
        name up again before it calls the new step missing."""
        ckpt_dir = str(tmp_path / "remap")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-tb-remap",
                               standalone=True)
        self._commit(ck, 1, 1.0)  # the saver has mapped the segment
        saver = AsyncCheckpointSaver.get_ckpt_saver()
        assert saver._shm_handlers[0].load_header()["step"] == 1
        ck.engine._shm_handler.unlink()  # reaped ...
        writer = SharedMemoryHandler(0, "t-tb-remap")  # ... next generation
        try:
            writer.save_state_dict({"w": np.full((8, 8), 2.0, np.float32),
                                    "step": np.int64(2)}, step=2)
            saver.save_step_checkpoint(2, ckpt_dir, commit_timeout=3)
            assert read_last_step(ckpt_dir) == 2
        finally:
            writer.unlink()
            ck.close()

    def test_replica_blob_verification(self):
        from dlrover_wuqiong_tpu.checkpoint.shm_handler import (
            verify_segment_blob,
        )

        h = SharedMemoryHandler(0, "t-tb-blob")
        try:
            h.save_state_dict(
                {"w": np.arange(32, dtype=np.float32)}, step=4)
            end = 1 << 20
            for m in h.load_header()["metas"]:
                end = max(end, m["offset"] + m["nbytes"])
            blob = bytes(h._buf.buf[:end])
            step, why = verify_segment_blob(blob)
            assert step == 4 and why == ""
            bad = bytearray(blob)
            bad[1 << 20] ^= 0x01
            step, why = verify_segment_blob(bytes(bad))
            assert step is None and "digest-mismatch" in why
            # torn header (truncated mid-json) is rejected too
            step, why = verify_segment_blob(blob[:100])
            assert step is None and why == "torn-header"
        finally:
            h.unlink()


_MID_PERSIST_SAVER = r"""
import os, sys
import numpy as np

from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)

ckpt_dir = sys.argv[1]
ck = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"],
                       standalone=True)
ck.save_checkpoint(1, {"w": np.full((8, 8), 1.0, np.float32),
                       "step": np.int64(1)},
                   storage_type=StorageType.DISK)
assert ck.wait_latest_checkpoint(60)
os.environ["DWT_CKPT_CRASH_POINT"] = sys.argv[2]
ck.save_checkpoint(2, {"w": np.full((8, 8), 2.0, np.float32),
                       "step": np.int64(2)},
                   storage_type=StorageType.DISK)
ck.wait_latest_checkpoint(60)
"""


class TestSigkillMidPersist:
    """The saver dies BETWEEN the shard-file write and the manifest
    publish (and, separately, between done-files and manifest): the torn
    generation is invisible-or-quarantined, restore serves N-1, and the
    dead run's shm segment is reaped by the next saver's sweeper."""

    @pytest.mark.parametrize("crash_point", ["after-bin",
                                             "before-manifest"])
    def test_restore_falls_back_to_previous_generation(
            self, tmp_path, crash_point):
        import subprocess
        import sys as _sys
        import tempfile

        ckpt_dir = str(tmp_path / "mp")
        job = f"mp{os.getpid()}{'a' if crash_point == 'after-bin' else 'b'}"
        script = tmp_path / "saver.py"
        script.write_text(_MID_PERSIST_SAVER)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, DWT_JOB_NAME=job,
                   # a short dir: AF_UNIX socket paths cap at ~108 chars
                   # and pytest tmp_path nests deep
                   DWT_SOCKET_DIR=tempfile.mkdtemp(prefix="dwt-mp-"),
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [_sys.executable, str(script), ckpt_dir, crash_point],
            env=env, cwd=str(tmp_path), capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 137, proc.stdout + proc.stderr
        # generation 2 must be torn by construction: no manifest
        assert not os.path.exists(os.path.join(
            ckpt_dir, "checkpoint-2", "manifest.json"))

        AsyncCheckpointSaver.reset()
        ck = FlashCheckpointer(ckpt_dir, job_name=f"{job}-verify",
                               standalone=True)
        try:
            # sweeper reaped the dead saver's segment on startup
            assert not os.path.exists(f"/dev/shm/{job}_ckpt_shm_0")
            restored = ck.load_checkpoint({"w": jnp.zeros((8, 8)),
                                           "step": np.int64(0)})
            rep = ck.last_restore_report
            assert restored is not None and int(restored["step"]) == 1
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.full((8, 8), 1.0))
            assert rep["step"] == 1 and rep["tier"] == "storage"
        finally:
            ck.close()


class TestCkptDoctor:
    def test_doctor_verifies_flags_and_repairs(self, tmp_path):
        import json as _json
        import subprocess
        import sys as _sys

        ckpt_dir = str(tmp_path / "doc")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-doc1",
                               standalone=True)
        for step, val in ((2, 2.0), (4, 4.0)):
            ck.save_checkpoint(step, {"w": jnp.full((8, 8), val),
                                      "step": np.int64(step)},
                               storage_type=StorageType.DISK)
            assert ck.wait_latest_checkpoint(30)
        ck.close()
        AsyncCheckpointSaver.reset()
        doctor = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "ckpt_doctor.py")

        def run(*args):
            p = subprocess.run([_sys.executable, doctor, ckpt_dir, *args],
                               capture_output=True, text=True, timeout=60)
            return p.returncode, _json.loads(
                p.stdout.strip().splitlines()[-1])["ckpt_doctor"]

        rc, v = run("--deep")
        assert rc == 0 and v["ok"] and v["healthy_steps"] == [4, 2]
        # flip one byte in the newest shard file
        import glob

        bin4 = glob.glob(os.path.join(ckpt_dir, "checkpoint-4",
                                      "shards_rank*.bin"))[0]
        raw = bytearray(open(bin4, "rb").read())
        raw[10] ^= 0x02
        open(bin4, "wb").write(raw)
        rc, v = run()
        assert rc == 1 and not v["ok"]
        bad = [g for g in v["generations"] if not g["ok"]]
        assert [g["step"] for g in bad] == [4]
        # repair: quarantine + tracker repointed to the healthy gen
        rc, v = run("--repair")
        assert v["quarantined_now"] == [4]
        assert v["tracker_step"] == 2
        assert read_last_step(ckpt_dir) == 2
        rc, v = run()
        assert rc == 0 and v["ok"] and v["healthy_steps"] == [2]


class TestStaleSegmentSweeper:
    def test_dead_creator_reaped_live_spared(self, tmp_path):
        import subprocess
        import sys as _sys

        from dlrover_wuqiong_tpu.checkpoint.shm_handler import (
            sweep_stale_segments,
        )

        dead_job = f"t-sweep-dead-{os.getpid()}"
        live_job = f"t-sweep-live-{os.getpid()}"
        # a subprocess stages a segment and exits (its pid dies with it)
        code = (
            "import numpy as np, sys;"
            "from dlrover_wuqiong_tpu.checkpoint.shm_handler import "
            "SharedMemoryHandler;"
            f"h = SharedMemoryHandler(0, {dead_job!r});"
            "h.save_state_dict({'w': np.ones(4, np.float32)}, step=1);"
            "h.close()")
        subprocess.run([_sys.executable, "-c", code], check=True,
                       timeout=60, env=dict(os.environ,
                                            JAX_PLATFORMS="cpu"))
        assert os.path.exists(f"/dev/shm/{dead_job}_ckpt_shm_0")
        # this process stages one too (creator alive)
        h = SharedMemoryHandler(0, live_job)
        h.save_state_dict({"w": np.ones(4, np.float32)}, step=1)
        try:
            reaped = sweep_stale_segments("some-other-job")
            assert f"{dead_job}_ckpt_shm_0" in reaped
            assert not os.path.exists(f"/dev/shm/{dead_job}_ckpt_shm_0")
            # live creator: spared
            assert os.path.exists(f"/dev/shm/{live_job}_ckpt_shm_0")
            # segments of the current job are never touched either
            assert f"{live_job}_ckpt_shm_0" not in sweep_stale_segments(
                live_job)
        finally:
            h.unlink()


class TestWireDtype:
    """bf16 wire staging (r4 verdict next #3): halves bytes end to end.
    Exact-resume contract: f32 leaves come back bf16-quantized (documented
    lossy); bf16 and integer leaves round-trip bit-exactly."""

    def test_bf16_wire_contract(self, tmp_path):
        mesh = _mesh()
        sharding = NamedSharding(mesh, P("data", None))
        ckpt_dir = str(tmp_path / "wire")
        ck = FlashCheckpointer(ckpt_dir, job_name="t-wire1",
                               standalone=True, wire_dtype="bf16")
        f32 = jax.device_put(
            jnp.linspace(0.0, 1.0, 64, dtype=jnp.float32).reshape(8, 8),
            sharding)
        bf16 = jax.device_put(
            jnp.linspace(-1.0, 1.0, 64, dtype=jnp.bfloat16).reshape(8, 8),
            sharding)
        ints = jnp.arange(8, dtype=jnp.int32)
        state = {"f32": f32, "bf16": bf16, "ints": ints}
        ck.save_checkpoint(3, state, storage_type=StorageType.DISK)
        assert ck.wait_latest_checkpoint(30)

        # stored shards are bf16 for the f32 leaf: bytes halved on disk
        import json as _json

        meta_files = list((tmp_path / "wire" / "checkpoint-3").glob(
            "meta_rank*.json"))
        tensors = {t["name"].split("#shard")[0]: t["dtype"]
                   for mf in meta_files
                   for t in _json.loads(mf.read_text())["tensors"]}
        assert tensors["f32"] == "bfloat16", tensors
        assert tensors["ints"] == "int32"

        template = {"f32": jax.ShapeDtypeStruct((8, 8), jnp.float32,
                                                sharding=sharding),
                    "bf16": jax.ShapeDtypeStruct((8, 8), jnp.bfloat16,
                                                 sharding=sharding),
                    "ints": jnp.zeros(8, jnp.int32)}
        restored = ck.load_checkpoint(template)
        # template dtype honored; f32 values are bf16-quantized
        assert restored["f32"].dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(restored["f32"]),
            np.asarray(f32.astype(jnp.bfloat16).astype(jnp.float32)))
        # bf16 and int leaves: bit-exact
        np.testing.assert_array_equal(np.asarray(restored["bf16"]),
                                      np.asarray(bf16))
        np.testing.assert_array_equal(np.asarray(restored["ints"]),
                                      np.asarray(ints))
        ck.close()
