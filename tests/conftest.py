"""Test config: run JAX on a virtual 8-device CPU mesh (no TPU needed).

Mirrors the reference's test strategy (SURVEY.md §4): multi-node logic is tested
on a single host — here with XLA's forced host-platform device count.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch an accelerator
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# one job name and one socket dir per xdist worker: the checkpoint shm
# segment (`<job>_ckpt_shm_<rank>`) and the saver's sockets are named by
# job, and `AsyncCheckpointSaver.reset()` unlinks the segment BY NAME —
# under the default name a reset in one worker's test took away what
# another worker's Trainer had just staged
_worker = os.environ.get("PYTEST_XDIST_WORKER", "")
_suffix = f"-{_worker}" if _worker else ""
os.environ.setdefault("DWT_JOB_NAME", "dwt" + _suffix)
os.environ.setdefault("DWT_SOCKET_DIR", "/tmp/dwt-test/sockets" + _suffix)

# env var for subprocesses, config for this process (covers a jax that
# something imported before conftest ran)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import collections  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Under xdist's `--dist load` (the driver's command) a worker's first
# chunk is `tests // workers // 4` CONSECUTIVE tests and later chunks
# shrink with what is pending, so the last files of the alphabet reach
# the workers two tests at a time: a module fixture of theirs is then
# wanted by every worker at once.  The files whose module fixtures
# compile a whole step for a described chip (25 to 120 s each; built
# once a run behind a file lock, tests/test_tpu_compile.py::_once_a_run,
# so the others would WAIT) each open one worker's first chunk, and
# files of many small independent cases close the run, where the chunks
# are smallest.  The files that read a CPU profile go before the first
# of them: a process that has loaded the TPU's compiler traces no CPU op
# (`OpProfile(categories={})`), and every worker loads it in its first
# chunk now.  Every worker collects and reorders alike.
_BEFORE_THE_TPU_COMPILER = ("test_xplane.py", "test_trainer.py",
                            "test_perf.py", "test_metrics.py")
_OPEN_A_CHUNK = ("test_xing4_0_compile.py", "test_tpu_compile.py",
                 "test_kimi_vl_compile.py", "test_smallthinker_compile.py",
                 "test_olmo_hybrid_compile.py", "test_scale_8b.py")
_CLOSE_THE_RUN = ("test_program_from_arguments.py", "test_ssd_kernel.py",
                  "test_moe_rows.py", "test_flash_attention_tiles.py")


def pytest_collection_modifyitems(config, items):
    workers = int(os.environ.get("PYTEST_XDIST_WORKER_COUNT") or 0)
    if workers < 2:
        return
    by_file = collections.defaultdict(list)
    for item in items:
        by_file[os.path.basename(str(item.fspath))].append(item)
    moved = {*_BEFORE_THE_TPU_COMPILER, *_OPEN_A_CHUNK, *_CLOSE_THE_RUN}
    rest = iter([item for item in items
                 if os.path.basename(str(item.fspath)) not in moved])
    chunk = max(len(items) // workers // 4, 2)
    openers = [by_file.get(name, []) for name in _OPEN_A_CHUNK]
    openers[0] = [item for name in _BEFORE_THE_TPU_COMPILER
                  for item in by_file.get(name, [])] + openers[0]
    ordered = []
    for opener in openers:  # each starts on a chunk's boundary
        ordered += opener
        ordered += [item for _, item in zip(range(-len(opener) % chunk),
                                            rest)]
    ordered += list(rest)
    for name in _CLOSE_THE_RUN:
        ordered += by_file.get(name, [])
    assert len(ordered) == len(items)
    items[:] = ordered


@pytest.fixture
def on_tpu(request, monkeypatch):
    """The backend said to be the TPU for the whole test — to EVERY
    caller at once: `ops/mosaic.on_tpu` is the one reading of the backend
    (tests/test_program_from_arguments.py guards that no module keeps a
    copy), so a kernel module added later is covered without being named
    here.  Parametrise it indirectly with False for a route's "off the
    TPU" cases.  What the test then traces is what one TPU device
    traces: run it only behind the kernels' interpret mode
    (`held_rows_interpreted`, a file's own fixture), or compile it for a
    described chip.  A test that wants the patch for one block only
    (`monkeypatch.context()`) sets the same one name."""
    from dlrover_wuqiong_tpu.ops import mosaic

    said = getattr(request, "param", True)
    monkeypatch.setattr(mosaic, "on_tpu", lambda: said)
    return said


@pytest.fixture
def held_rows_interpreted(on_tpu, monkeypatch):
    """What a share of an expert layer takes on one TPU device, here:
    `experts_route`'s own decision with the backend said to be the TPU
    and a row tile (32) that divides the nano buffers, the grouped
    products, the maps between them and the unwritten buffer in
    interpret mode."""
    from dlrover_wuqiong_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_ROW_TILE", 32)
    for name in ("_grouped_kernels", "_rows_map_kernels",
                 "_unwritten_kernel"):
        monkeypatch.setattr(gm, name, functools.partial(
            getattr(gm, name), interpret=True))


@pytest.fixture(scope="session")
def schema_lock():
    """The committed wire-surface lockfile (analysis/schema.lock.json).

    The ADD-ONLY pin tests assert the LIVE registries/messages still
    cover the locked surface, so the lock is the single source of truth
    for what "add-only" means; graftlint's schema engine gates the lock
    itself against the source tree.  Each family keeps ONE hand-pinned
    canary so a bad `--update-lock` regeneration can't silently shrink
    both sides at once."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "dlrover_wuqiong_tpu", "analysis", "schema.lock.json")
    with open(path) as f:
        return json.load(f)

#: thread-name prefixes tests may legitimately leave running: pytest/
#: plugin internals plus library pools that outlive a single test by
#: design (jax/XLA dispatch pools, concurrent.futures executors are
#: daemonic or process-lifetime and excluded by the daemon check anyway).
_THREAD_ALLOWLIST_PREFIXES = (
    "MainThread", "pydevd.", "ThreadPoolExecutor",
)


def _nondaemon_threads():
    return {
        t for t in threading.enumerate()
        if t.is_alive() and not t.daemon
        and not t.name.startswith(_THREAD_ALLOWLIST_PREFIXES)
    }


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    """Fail any test that leaks a non-daemon thread.

    A leaked non-daemon thread hangs interpreter exit — exactly the
    thread-lifecycle wedge graftlint's concurrency engine flags in
    product code; this guard enforces the same discipline on test
    scaffolding.  Pre-existing survivors (leaked by an EARLIER test)
    are baselined out so one leaker doesn't cascade failures; a short
    join grace absorbs threads that are mid-shutdown when the test
    body returns."""
    before = _nondaemon_threads()
    yield
    leaked = _nondaemon_threads() - before
    if not leaked:
        return
    deadline = 1.0 / max(len(leaked), 1)
    for t in leaked:
        t.join(timeout=deadline)
    leaked = {t for t in leaked if t.is_alive()}
    if leaked:
        names = sorted(f"{t.name} (target={getattr(t, '_target', None)})"
                       for t in leaked)
        pytest.fail(
            f"test leaked non-daemon thread(s): {names} — join them or "
            f"mark them daemon (see graftlint thread-lifecycle)",
            pytrace=False)
