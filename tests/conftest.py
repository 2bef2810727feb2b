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

import json  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


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
