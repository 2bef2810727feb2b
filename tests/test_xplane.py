"""XPlane parsing → per-op-category latency tests (xpu_timer parity).

Real traces from jax.profiler on the CPU mesh, parsed by the stdlib wire
reader, cross-validated against the generated protobuf bindings when
available.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.utils.xplane import (
    OpProfile,
    categorize,
    parse_trace_dir,
    parse_xspace,
    summarize_planes,
)


@pytest.fixture(scope="module")
def traced_dir(tmp_path_factory):
    """One real profiler trace of a sharded matmul + collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    d = str(tmp_path_factory.mktemp("trace"))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    x = jax.device_put(jnp.ones((256, 256)),
                       NamedSharding(mesh, P("dp", "tp")))
    w = jax.device_put(jnp.ones((256, 256)),
                       NamedSharding(mesh, P("tp", None)))

    @jax.jit
    def f(x, w):
        return jnp.tanh(x @ w).sum()

    f(x, w).block_until_ready()  # compile outside the window
    jax.profiler.start_trace(d)
    for _ in range(3):
        f(x, w).block_until_ready()
    jax.profiler.stop_trace()
    return d


class TestWireParser:
    def test_parses_real_trace(self, traced_dir):
        prof = parse_trace_dir(traced_dir)
        assert prof is not None
        assert prof.categories, "no op categories found"
        # the traced program has a dot and a cross-device reduction
        assert "matmul" in prof.categories
        assert "collective" in prof.categories
        assert all(s > 0 for s in prof.categories.values())
        names = [o.name for o in prof.ops]
        assert any("dot" in n for n in names)

    def test_matches_generated_protobuf(self, traced_dir):
        """Cross-validate the stdlib wire reader against the generated
        xplane_pb2 bindings (plane/line/event counts and durations)."""
        import importlib.util

        tf_spec = importlib.util.find_spec("tensorflow")
        pb2_path = None
        if tf_spec and tf_spec.submodule_search_locations:
            for base in tf_spec.submodule_search_locations:
                cand = os.path.join(base, "tsl", "profiler", "protobuf",
                                    "xplane_pb2.py")
                if os.path.exists(cand):
                    pb2_path = cand
                    break
        if pb2_path is None:
            pytest.skip("no generated xplane_pb2 available")
        spec = importlib.util.spec_from_file_location("xplane_pb2", pb2_path)
        pb2 = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pb2)

        files = glob.glob(os.path.join(traced_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        assert files
        for path in files:
            ours = parse_xspace(path)
            theirs = pb2.XSpace()
            with open(path, "rb") as f:
                theirs.ParseFromString(f.read())
            assert len(ours) == len(theirs.planes)
            for op, tp in zip(ours, theirs.planes):
                assert op.name == tp.name
                assert len(op.lines) == len(tp.lines)
                assert sum(len(ln.events) for ln in op.lines) == \
                    sum(len(ln.events) for ln in tp.lines)
                our_dur = sum(e.duration_ps for ln in op.lines
                              for e in ln.events)
                their_dur = sum(e.duration_ps for ln in tp.lines
                                for e in ln.events)
                assert our_dur == their_dur


class TestCategorize:
    @pytest.mark.parametrize("name,cat", [
        ("all-reduce.1", "collective"),
        ("collective-permute.3", "collective"),
        ("reduce-scatter", "collective"),
        ("dot.17", "matmul"),
        ("wrapped_convolution", "matmul"),
        ("ragged-dot", "matmul"),
        ("copy-start.2", "transfer"),
        ("fusion.42", "fused"),
        ("Rendezvous", "sync"),
        ("Wait: pending_threads=3/4", None),  # ':' → host artifact
        ("add.3", "other"),
    ])
    def test_name_prefixes(self, name, cat):
        assert categorize(name) == cat

    def test_host_noise_is_dropped(self):
        assert categorize("PjitFunction(f)") is None
        assert categorize("$profiler.py:213 stop_trace") is None
        assert categorize("") is None

    def test_hlo_category_stat_wins(self):
        # TPU planes carry hlo_category stats; they beat name heuristics
        assert categorize("fusion.3", "convolution fusion") == "matmul"
        assert categorize("fusion.9", "all-reduce") == "collective"
        assert categorize("bitcast.1", "data formatting") == "transfer"


class TestStepProfilerIntegration:
    def test_window_publishes_categories_and_evidence(self, tmp_path):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from dlrover_wuqiong_tpu.utils.profiler import StepProfiler

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
        x = jax.device_put(jnp.ones((128, 128)),
                           NamedSharding(mesh, P("dp", "tp")))
        w = jax.device_put(jnp.ones((128, 128)),
                           NamedSharding(mesh, P("tp", None)))

        @jax.jit
        def f(x, w):
            return jnp.tanh(x @ w).sum()

        f(x, w).block_until_ready()
        prof = StepProfiler(trace_dir=str(tmp_path), start_step=1,
                            end_step=2)
        for step in range(4):
            with prof.step(step):
                f(x, w).block_until_ready()
        assert prof.last_profile is not None
        assert prof.last_profile.categories.get("matmul", 0.0) > 0.0
        evidence = prof.last_profile.collective_evidence()
        assert evidence, "expected collective evidence"
        parsed = json.loads(evidence)
        assert parsed and {"op", "seconds", "count"} <= set(parsed[0])

    def test_diagnosis_evidence_includes_collectives(self):
        import time

        from dlrover_wuqiong_tpu.common import messages as msg
        from dlrover_wuqiong_tpu.diagnosis.manager import (
            CheckTrainingHangOperator,
            DiagnosisDataManager,
            InferenceChain,
            ResolveHangCauseOperator,
        )

        data = DiagnosisDataManager()
        old = time.time() - 3600  # graftlint: disable=wall-clock-duration -- forging node-reported wall timestamps (DiagnosisReport)
        data.store_report(msg.DiagnosisReport(
            node_id=0, payload_type="step", content="5", timestamp=old))
        data.store_report(msg.DiagnosisReport(
            node_id=0, payload_type="op_profile",
            content='[{"op": "all-reduce", "seconds": 1.5, "count": 3}]',
            timestamp=time.time() - 100))  # graftlint: disable=wall-clock-duration -- forging node-reported wall timestamps (DiagnosisReport)
        # stale evidence (older than max_age) is withheld
        assert data.node_op_profile(0, max_age=10) == ""
        chain = InferenceChain([CheckTrainingHangOperator(timeout=60),
                                ResolveHangCauseOperator()])
        conclusions = chain.run(data)
        culprits = [c for c in conclusions if c.name == "hang_culprit"]
        assert culprits
        assert "slowest collectives" in culprits[0].detail
        assert "all-reduce" in culprits[0].detail


class TestParserRobustness:
    def test_corrupt_pb_file_is_skipped(self, tmp_path):
        """A torn/foreign .xplane.pb must not kill the profile publish."""
        run = tmp_path / "plugins" / "profile" / "2026_01_01"
        run.mkdir(parents=True)
        (run / "host.xplane.pb").write_bytes(b"\xff\xfe\xfd garbage")
        assert parse_trace_dir(str(tmp_path)) is None

    def test_empty_trace_dir(self, tmp_path):
        assert parse_trace_dir(str(tmp_path)) is None

    def test_truncated_varint_rejected_cleanly(self, tmp_path):
        run = tmp_path / "plugins" / "profile" / "r"
        run.mkdir(parents=True)
        # field 1, wire type 2, length 100 but no payload → the reader's
        # bounds check raises ValueError (a silent short slice would
        # misparse the corrupt file as an empty plane), caught per-file
        # by parse_trace_dir
        (run / "h.xplane.pb").write_bytes(b"\x0a\x64")
        assert parse_trace_dir(str(tmp_path)) is None
