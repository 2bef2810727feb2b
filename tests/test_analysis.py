"""graftlint static-analysis subsystem (dlrover_wuqiong_tpu/analysis/).

Positive + negative fixtures per checker, the resolve-time wiring into
auto_accelerate, the CLI contract (one JSON line on stdout, rc 1 on
findings), and the tier-1 repo self-lint: graftlint run over this tree
must come back clean — the CLAUDE.md hard-won rules are an enforced
contract, not tribal knowledge.  None of the jaxpr fixtures execute any
device computation: everything goes through jax.make_jaxpr / abstract
state (the acceptance bar for the subsystem).
"""

import json
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from dlrover_wuqiong_tpu.analysis.ast_engine import (
    run_paths,
)
from dlrover_wuqiong_tpu.analysis.findings import (
    Finding,
    render_report,
    summarize,
)
from dlrover_wuqiong_tpu.analysis.jaxpr_engine import (
    check_collective_in_cond,
    check_donation_alias,
    check_host_out_shardings,
    check_remat_noop,
    resolve_donation,
    self_audit,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(n=4):
    return Mesh(jax.devices()[:n], ("x",))


# --------------------------------------------------- collective-in-cond


class TestCollectiveInCond:
    def test_varying_pred_psum_flagged(self):
        def bad(x):
            pred = x[0] > 0  # derived from the sharded input → varying
            return jax.lax.cond(pred,
                                lambda v: jax.lax.psum(v, "x"),
                                lambda v: v, x)

        f = shard_map(bad, mesh=_mesh(), in_specs=P("x"),
                      out_specs=P("x"), check_rep=False)
        found = check_collective_in_cond(f, jnp.ones((8,)))
        assert len(found) == 1
        assert found[0].checker == "collective-in-cond"
        assert "psum" in found[0].message and "'x'" in found[0].message

    def test_axis_index_pred_flagged(self):
        def bad(x):
            i = jax.lax.axis_index("x")
            return jax.lax.cond(i == 0,
                                lambda v: jax.lax.psum(v, "x"),
                                lambda v: v, x)

        f = shard_map(bad, mesh=_mesh(), in_specs=P("x"),
                      out_specs=P("x"), check_rep=False)
        assert check_collective_in_cond(f, jnp.ones((8,)))

    def test_where_masking_clean(self):
        # the CLAUDE.md-prescribed fix: compute unconditionally, mask
        def good(x):
            s = jax.lax.psum(x, "x")
            return jnp.where(x[0] > 0, s, x)

        f = shard_map(good, mesh=_mesh(), in_specs=P("x"),
                      out_specs=P("x"), check_rep=False)
        assert check_collective_in_cond(f, jnp.ones((8,))) == []

    def test_replicated_pred_clean(self):
        # every shard sees the same predicate → same branch → no deadlock
        def ok(x, t):
            return jax.lax.cond(t > 0,
                                lambda v: jax.lax.psum(v, "x"),
                                lambda v: v, x)

        f = shard_map(ok, mesh=_mesh(), in_specs=(P("x"), P()),
                      out_specs=P("x"), check_rep=False)
        assert check_collective_in_cond(
            f, jnp.ones((8,)), jnp.float32(1.0)) == []

    def test_psum_cancels_varyingness(self):
        # pred derived from a psum over 'x' is invariant over 'x' → safe
        def ok(x):
            total = jax.lax.psum(x, "x")
            return jax.lax.cond(total[0] > 0,
                                lambda v: jax.lax.psum(v, "x"),
                                lambda v: v, x)

        f = shard_map(ok, mesh=_mesh(), in_specs=P("x"),
                      out_specs=P("x"), check_rep=False)
        assert check_collective_in_cond(f, jnp.ones((8,))) == []

    def test_abstract_args_no_execution(self):
        def bad(x):
            return jax.lax.cond(x[0] > 0,
                                lambda v: jax.lax.psum(v, "x"),
                                lambda v: v, x)

        f = shard_map(bad, mesh=_mesh(), in_specs=P("x"),
                      out_specs=P("x"), check_rep=False)
        # ShapeDtypeStruct in → pure trace, nothing dispatched
        sds = jax.ShapeDtypeStruct((8,), jnp.float32)
        assert check_collective_in_cond(f, sds)


# ----------------------------------------------------------- remat-noop


def _layer(x, w):
    return jnp.tanh(x @ w)


class TestRematNoop:
    def test_python_loop_prevent_cse_false_flagged(self):
        ck = jax.checkpoint(_layer, prevent_cse=False)

        def loop(x, w):
            for _ in range(3):
                x = ck(x, w)
            return x.sum()

        found = check_remat_noop(jax.grad(loop), jnp.ones((4, 4)),
                                 jnp.ones((4, 4)))
        assert len(found) == 1
        assert found[0].checker == "remat-noop"
        assert "3 identical instances" in found[0].message

    def test_scan_body_prevent_cse_false_clean(self):
        # under scan the loop body is a separate computation: the exact
        # situation prevent_cse=False exists for
        ck = jax.checkpoint(_layer, prevent_cse=False)

        def scanned(x, w):
            def body(c, _):
                return ck(c, w), None

            y, _ = jax.lax.scan(body, x, None, length=3)
            return y.sum()

        assert check_remat_noop(jax.grad(scanned), jnp.ones((4, 4)),
                                jnp.ones((4, 4))) == []

    def test_prevent_cse_true_clean(self):
        ck = jax.checkpoint(_layer)  # prevent_cse=True default

        def loop(x, w):
            for _ in range(3):
                x = ck(x, w)
            return x.sum()

        assert check_remat_noop(jax.grad(loop), jnp.ones((4, 4)),
                                jnp.ones((4, 4))) == []


# ---------------------------------------------- donation / host kinds


class _FakeSharding:
    """Sharding stand-in: memory_kind + device_set(platform), no jax.

    Deliberately NOT a real NamedSharding: the checker must never touch
    the memories API (see _is_explicit_host_kind), so all it needs from
    a leaf is these two attributes.
    """

    def __init__(self, kind, platform="tpu"):
        self.memory_kind = kind
        self._platform = platform

    @property
    def device_set(self):
        class _Dev:
            def __init__(self, platform):
                self.platform = platform

        return {_Dev(self._platform)}


class TestDonationAndHostKinds:
    def test_donation_alias_flagged(self):
        assert check_donation_alias({"optimizer_offload": True}, True)
        assert check_donation_alias({"optimizer_offload": True},
                                    None) == []
        assert check_donation_alias({}, True) == []

    def test_resolve_donation(self):
        assert resolve_donation({}, None) is True
        assert resolve_donation({"optimizer_offload": True}, None) is False
        assert resolve_donation({}, False) is False
        with pytest.raises(ValueError, match="donation-alias"):
            resolve_donation({"optimizer_offload": True}, True)

    def test_host_kind_flagged_when_not_default(self):
        tree = {"m": _FakeSharding("pinned_host", platform="tpu"),
                "ok": _FakeSharding("device", platform="tpu")}
        found = check_host_out_shardings(tree)
        assert len(found) == 1
        assert "pinned_host" in found[0].message
        assert "'m'" in found[0].message

    def test_pinned_host_flagged_even_on_cpu(self):
        # explicit host offload is explicit on every platform
        tree = {"m": _FakeSharding("pinned_host", platform="cpu")}
        assert len(check_host_out_shardings(tree)) == 1

    def test_default_host_kind_on_cpu_clean(self):
        # the CPU backend's default memory kind IS unpinned_host: plain
        # CPU shardings must not be flagged (regression: the first
        # wiring of this check broke every CPU-mesh init)
        tree = {"x": _FakeSharding("unpinned_host", platform="cpu")}
        assert check_host_out_shardings(tree) == []

    def test_unpinned_host_on_a_tpu_flagged(self):
        tree = {"x": _FakeSharding("unpinned_host", platform="tpu")}
        assert len(check_host_out_shardings(tree)) == 1

    def test_real_cpu_state_shardings_clean(self):
        from dlrover_wuqiong_tpu.parallel.mesh import MeshPlan, build_mesh
        from dlrover_wuqiong_tpu.parallel.sharding import ShardingPlanner

        planner = ShardingPlanner(build_mesh(MeshPlan(fsdp=8)))
        assert check_host_out_shardings(planner.replicated()) == []

    def test_auto_accelerate_rejects_donate_with_offload(self):
        import optax

        from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
        from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

        with pytest.raises(ValueError, match="donation-alias"):
            auto_accelerate(
                GPT(GPTConfig.nano()), optimizer=optax.adamw(1e-3),
                strategy=[("fsdp", {}), ("optimizer_offload", {})],
                donate=True, materialize=False)

    def test_make_train_step_rejects_donate_with_host_shardings(self):
        import optax

        from dlrover_wuqiong_tpu.trainer.train_step import make_train_step

        with pytest.raises(ValueError, match="donation-alias"):
            make_train_step(lambda p, b: jnp.float32(0), optax.sgd(0.1),
                            _mesh(), donate=True,
                            opt_host_shardings={"m": None},
                            opt_device_shardings={"m": None})


# --------------------------------------------------------- AST fixtures


def _scan_source(tmp_path, relpath, source, **kw):
    """Write one fixture file into a fake package tree and lint it."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    # package markers so the citation checker sees a real package
    d = path.parent
    while d != tmp_path:
        (d / "__init__.py").touch()
        d = d.parent
    path.write_text(textwrap.dedent(source))
    findings, _ = run_paths([str(tmp_path)], **kw)
    return findings


class TestEnvAtTrace:
    @pytest.mark.parametrize("read", [
        'os.getenv("XLA_FAKE_TOGGLE")',
        'os.environ.get("HOME")',
        'os.environ["DWT_FAKE_TOGGLE"]',
        'os.getenv("<computed>" and x)',
    ])
    def test_any_env_read_in_a_compute_function_flagged(self, tmp_path,
                                                        read):
        """No registry of exempt names: whatever the variable is called,
        a compute-path function does not read it."""
        found = _scan_source(
            tmp_path, "pkg/ops/kern.py", f"""\
            '''Parity: ref.py:1'''
            import os

            def build_kernel(x):
                if {read}:
                    return x
                return x + 1
            """)
        assert [f.checker for f in found] == ["env-at-trace"]
        assert read.split('"')[1] in found[0].message
        assert found[0].line == 5

    def test_module_level_and_non_compute_reads_exempt(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/master/sched.py", """\
            '''Parity: ref.py:1'''
            import os

            def pick():
                return os.getenv("DWT_JOB_NAME")
            """)
        assert found == []
        found = _scan_source(
            tmp_path, "pkg/ops/consts.py", """\
            '''Parity: ref.py:1'''
            import os

            CACHE = os.getenv("DWT_FAKE_DIR")
            """)
        assert found == []


class TestWallClockDuration:
    """wall-clock-duration (warning): time.time() in duration math."""

    def test_elapsed_subtraction_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/master/loop.py", """\
            '''Parity: ref.py:1'''
            import time

            def wait(t0):
                return time.time() - t0
            """)
        assert [f.checker for f in found] == ["wall-clock-duration"]
        assert found[0].severity == "warning"
        assert found[0].line == 5
        assert "monotonic" in found[0].message

    def test_deadline_addition_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/master/loop.py", """\
            '''Parity: ref.py:1'''
            import time

            def deadline(timeout):
                return time.time() + timeout
            """)
        assert [f.checker for f in found] == ["wall-clock-duration"]

    def test_file_timestamp_comparison_exempt(self, tmp_path):
        # mtimes ARE wall clock — comparing against one is correct as is
        found = _scan_source(
            tmp_path, "pkg/master/loop.py", """\
            '''Parity: ref.py:1'''
            import os
            import time

            def age(path):
                return time.time() - os.path.getmtime(path)

            def stat_age(st):
                return time.time() - st.st_mtime
            """)
        assert found == []

    def test_suppression_with_reason_honored(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/master/loop.py", """\
            '''Parity: ref.py:1'''
            import time

            def journal_ts(t0):
                return time.time() - t0  # graftlint: disable=wall-clock-duration -- cross-process journal timestamps are wall clock
            """)
        assert found == []

    def test_monotonic_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/master/loop.py", """\
            '''Parity: ref.py:1'''
            import time

            def wait(t0, timeout):
                return (time.monotonic() - t0) < timeout

            def stamp():
                return time.time()  # bare read, no arithmetic: fine
            """)
        assert found == []

    def test_warning_severity_does_not_gate(self, tmp_path):
        # warnings report but keep ok=true / rc 0 (README contract)
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").touch()
        (pkg / "m.py").write_text(
            "'''Parity: ref.py:1'''\n"
            "import time\n\n\n"
            "def wait(t0):\n"
            "    return time.time() - t0\n")
        rc = main(["--engine", "ast", str(tmp_path)])
        assert rc == 0


class TestDonatedReuse:
    def test_reuse_after_donation_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            def test_step(res, batch):
                state = res.state
                new_state, m = res.train_step(state, batch)
                return state.params  # dead buffer
            """)
        assert [f.checker for f in found] == ["donated-reuse"]
        assert "`state`" in found[0].message

    def test_attribute_reuse_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            def test_step(res, batch):
                s, m = res.train_step(res.state, batch)
                return res.state  # dead buffer
            """)
        assert len(found) == 1 and "`res.state`" in found[0].message

    def test_rebind_pattern_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            def test_step(res, batch, n):
                state = res.state
                for _ in range(n):
                    state, m = res.train_step(state, batch)
                return state
            """)
        assert found == []

    def test_loop_without_rebind_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            def test_step(res, state, batch, n):
                for _ in range(n):
                    out, m = res.train_step(state, batch)
                return out
            """)
        assert len(found) == 1
        assert "loop" in found[0].message

    def test_copy_argument_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            import jax.numpy as jnp

            def test_step(res, state, batch):
                s, m = res.train_step(jax.tree.map(jnp.copy, state), batch)
                return state
            """)
        assert found == []

    def test_pragma_suppression(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            def test_step(res, state, batch):
                s, m = res.train_step(state, batch)
                return state  # graftlint: disable=donated-reuse -- fixture: suppression honored
            """)
        assert found == []

    def test_sparse_update_positions(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_x.py", """\
            def test_emb(cfg, table, state, slots, g):
                t2, s2 = apply_sparse_update(cfg, table, state, slots, g)
                assert g.shape  # grads are NOT donated — fine
                return table.sum()  # table IS donated
            """)
        assert len(found) == 1 and "`table`" in found[0].message


class TestBlockingReadback:
    def test_unconditional_float_in_train_loop_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''

            def run(res, state, batch, n):
                for _ in range(n):
                    state, m = res.train_step(state, batch)
                    loss = float(m["loss"])  # per-step host sync
                return state
            """)
        assert [f.checker for f in found] == ["blocking-readback"]
        assert "float" in found[0].message
        assert found[0].line == 6

    def test_np_asarray_on_step_output_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''
            import numpy as np

            def run(res, state, batch, n):
                for _ in range(n):
                    state, m = res.train_step(state, batch)
                    np.asarray(m["grad_norm"])
                return state
            """)
        assert [f.checker for f in found] == ["blocking-readback"]

    def test_fused_factory_call_recognized(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''

            def run(res, state, batch, n, k):
                for _ in range(n):
                    state, m = res.fused_train_step(k)(state, batch)
                    float(m["loss"])
                return state
            """)
        assert [f.checker for f in found] == ["blocking-readback"]
        assert "fused_train_step" in found[0].message

    def test_cadence_gated_readback_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''

            def run(res, state, batch, n, log_every):
                for i in range(n):
                    state, m = res.train_step(state, batch)
                    if (i + 1) % log_every == 0:
                        print(float(m["loss"]))  # throttled: fine
                return state
            """)
        assert found == []

    def test_readback_after_loop_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''

            def run(res, state, batch, n):
                for _ in range(n):
                    state, m = res.train_step(state, batch)
                return float(m["loss"])  # one sync for the whole chain
            """)
        assert found == []

    def test_non_step_value_readback_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''

            def run(res, state, batch, lrs):
                for lr in lrs:
                    state, m = res.train_step(state, batch)
                    rate = float(lr)  # host value, not a step output
                return state
            """)
        assert found == []

    def test_tests_dir_exempt(self, tmp_path):
        found = _scan_source(
            tmp_path, "tests/test_loop.py", """\
            def test_converges(res, state, batch):
                for _ in range(4):
                    state, m = res.train_step(state, batch)
                    assert float(m["loss"]) < 10  # convergence test: fine
            """)
        assert found == []

    def test_pragma_suppression(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/examples/loop.py", """\
            '''Parity: ref.py:1'''

            def run(res, state, batch, n):
                for _ in range(n):
                    state, m = res.train_step(state, batch)
                    float(m["loss"])  # graftlint: disable=blocking-readback -- fixture: suppression honored
                return state
            """)
        assert found == []


class TestRawRpcCall:
    def test_bare_dial_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/agent/probe.py", """\
            '''Parity: ref.py:1'''
            import socket

            def ping(addr):
                host, port = addr.rsplit(":", 1)
                with socket.create_connection((host, int(port))) as s:
                    s.sendall(b"hi")
            """)
        assert [f.checker for f in found] == ["raw-rpc-call"]
        assert found[0].line == 6

    def test_sock_connect_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/agent/probe.py", """\
            '''Parity: ref.py:1'''
            import socket

            def dial(path):
                sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                sock.connect(path)
                return sock
            """)
        assert [f.checker for f in found] == ["raw-rpc-call"]

    def test_frame_io_outside_comm_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/agent/sidechan.py", """\
            '''Parity: ref.py:1'''
            from ..common.comm import _send_frame

            def push(sk, data):
                _send_frame(sk, data)
            """)
        assert [f.checker for f in found] == ["raw-rpc-call"]

    def test_dial_under_retry_call_clean(self, tmp_path):
        """The sanctioned shape: the dial is the retried attempt — any
        enclosing function routing through retry_call blesses it."""
        found = _scan_source(
            tmp_path, "pkg/agent/probe.py", """\
            '''Parity: ref.py:1'''
            import socket
            from ..common.util import retry_call

            def ping(addr):
                host, port = addr.rsplit(":", 1)

                def attempt():
                    with socket.create_connection((host, int(port))) as s:
                        s.sendall(b"hi")

                return retry_call(attempt, attempts=3)
            """)
        assert found == []

    def test_comm_module_and_tests_exempt(self, tmp_path):
        src = """\
            '''Parity: ref.py:1'''
            import socket

            def dial(addr):
                return socket.create_connection(addr)
            """
        assert _scan_source(tmp_path, "pkg/common/comm.py", src) == []
        assert _scan_source(tmp_path, "tests/test_dial.py", src) == []

    def test_non_socket_connect_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/agent/db.py", """\
            '''Parity: ref.py:1'''
            import sqlite3

            def open_db(path):
                return sqlite3.connect(path)
            """)
        assert found == []

    def test_pragma_suppression(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/agent/probe.py", """\
            '''Parity: ref.py:1'''
            import socket

            def ping(addr):
                return socket.create_connection(addr)  # graftlint: disable=raw-rpc-call -- fixture: suppression honored
            """)
        assert found == []


class TestUnverifiedRestore:
    def test_shm_bytes_to_device_put_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/ckpt/restorer.py", """\
            '''Parity: ref.py:1'''
            import jax

            def resume(handler, sharding):
                step, flat, metas, extra = handler.load_state_dict()
                return jax.device_put(flat["w"], sharding)
            """)
        assert [f.checker for f in found] == ["unverified-restore"]
        assert "device_put" in found[0].message
        assert found[0].line == 6

    def test_frombuffer_to_restore_pytree_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/ckpt/loader.py", """\
            '''Parity: ref.py:1'''
            import numpy as np

            def load(storage, template, path):
                raw = storage.read(path)
                flat = {"w": np.frombuffer(raw, dtype=np.float32)}
                return restore_pytree(template, flat)
            """)
        assert [f.checker for f in found] == ["unverified-restore"]
        assert "restore_pytree" in found[0].message

    def test_verified_decode_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/ckpt/loader.py", """\
            '''Parity: ref.py:1'''
            import numpy as np

            def load(storage, template, path, entry):
                raw = storage.read(path)
                verify_rank_bytes(raw, entry, "crc32c", 0)
                flat = {"w": np.frombuffer(raw, dtype=np.float32)}
                return restore_pytree(template, flat)
            """)
        assert found == []

    def test_sink_without_raw_source_clean(self, tmp_path):
        # restore_pytree fed by the verified engine API in ANOTHER
        # function: the sanctioned shape (engine.load verifies inside)
        found = _scan_source(
            tmp_path, "pkg/ckpt/user.py", """\
            '''Parity: ref.py:1'''
            import jax

            def resume(engine, template, sharding):
                flat = engine.load()
                return jax.device_put(flat["w"], sharding)
            """)
        assert found == []

    def test_tests_and_suppression_exempt(self, tmp_path):
        src = """\
            '''Parity: ref.py:1'''
            import jax

            def resume(handler, sharding):
                step, flat, metas, extra = handler.load_state_dict()
                return jax.device_put(flat["w"], sharding)  # graftlint: disable=unverified-restore -- fixture: suppression honored
            """
        assert _scan_source(tmp_path, "pkg/tests/test_x.py", src) == []
        assert _scan_source(tmp_path, "pkg/ckpt/sanctioned.py", src) == []


class TestControlPlaneHygiene:
    def test_pickle_on_frame_path_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/common/comm.py", """\
            '''Parity: ref.py:1'''
            import pickle

            def encode(x):
                return pickle.dumps(x)
            """)
        assert any(f.checker == "control-plane-hygiene" and
                   "pickle" in f.message for f in found)

    def test_fork_context_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/data/loader.py", """\
            '''Parity: ref.py:1'''
            import multiprocessing

            def start():
                return multiprocessing.get_context("fork")
            """)
        assert any("fork" in f.message for f in found)

    def test_spawn_and_json_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/common/comm.py", """\
            '''Parity: ref.py:1'''
            import json
            import multiprocessing

            def start():
                return multiprocessing.get_context("spawn")
            """)
        assert found == []


class TestDocstringCitation:
    def test_uncited_module_flagged(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/core/thing.py", """\
            '''Helpers.'''

            def f():
                pass
            """)
        assert [f.checker for f in found] == ["docstring-citation"]

    def test_cited_module_clean(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/core/thing.py", """\
            '''Does X.  Parity: reference foo/bar.py:42.'''

            def f():
                pass
            """)
        assert found == []

    def test_init_and_defless_modules_exempt(self, tmp_path):
        found = _scan_source(
            tmp_path, "pkg/core/constants.py", """\
            '''Just constants, no citation needed.'''

            X = 1
            """)
        assert found == []


# ------------------------------------------------------------ findings


class TestFindings:
    def test_format_and_summary(self):
        f = Finding("env-at-trace", "boom", "a/b.py", 7)
        # v2: severity (catalog-defaulted) rides between location and rule
        assert f.format() == "a/b.py:7: error: [env-at-trace] boom"
        assert summarize([f, f, Finding("remat-noop", "x")]) == {
            "env-at-trace": 2, "remat-noop": 1}
        assert "and 1 more" in render_report([f, f, f], limit=2)


# ------------------------------------------------------- CLI contract


class TestCli:
    def test_cli_clean_dir_rc0_single_json_line(self, tmp_path, capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--engine", "ast", str(tmp_path)])
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        assert len(out) == 1
        rec = json.loads(out[0])["graftlint"]
        assert rec["ok"] is True and rec["engines"] == ["ast"]

    def test_cli_violations_rc1_with_file_line_report(self, tmp_path,
                                                      capsys):
        from dlrover_wuqiong_tpu.analysis.__main__ import main

        bad = tmp_path / "test_bad.py"
        bad.write_text(textwrap.dedent("""\
            def test_step(res, state, batch):
                s, m = res.train_step(state, batch)
                return state
            """))
        rc = main(["--engine", "ast", str(tmp_path)])
        cap = capsys.readouterr()
        assert rc == 1
        rec = json.loads(cap.out.strip())["graftlint"]
        assert rec["findings"] == 1
        assert rec["by_checker"] == {"donated-reuse": 1}
        # file:line report on stderr
        assert "test_bad.py:3" in cap.err


# -------------------------------------------------- repo self-lint (t1)


class TestSelfLint:
    def test_ast_engine_repo_clean(self):
        paths = [os.path.join(REPO_ROOT, p)
                 for p in ("dlrover_wuqiong_tpu", "tests", "examples",
                           "tools", "__graft_entry__.py")]
        findings, n_files = run_paths([p for p in paths
                                       if os.path.exists(p)])
        assert n_files > 100
        assert findings == [], "\n" + render_report(findings)

    def test_jaxpr_self_audit_clean(self):
        assert self_audit() == []
