"""The multi-chip dry-run gate must be bulletproof against caller state.

Failure mode: a caller runs `dryrun_multichip(8)` from a process whose jax
default backend is a live TPU but which happens to have >= 8 virtual CPU
devices, so the dry run executes eager ops on the TPU backend. These tests
pin the
contract: in-process execution ONLY in a pure-CPU jax world; anything else
re-execs a clean `JAX_PLATFORMS=cpu` subprocess.
"""

import os
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import __graft_entry__ as graft  # noqa: E402


def test_in_process_requires_cpu_default_backend(monkeypatch):
    # even with plenty of cpu devices, a non-cpu default backend must force
    # the subprocess path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not graft._can_run_in_process(2)


def test_in_process_requires_enough_devices():
    assert not graft._can_run_in_process(10_000)


def test_in_process_ok_in_cpu_world():
    # backend must already be initialized for the in-process fast path —
    # the gate never triggers discovery itself
    jax.devices()
    assert graft._can_run_in_process(8)


def test_dryrun_subprocess_path_from_noncpu_backend(monkeypatch):
    """Full dryrun_multichip(8) from a simulated TPU-default caller.

    Must take the subprocess path and succeed — this reproduces the driver's
    round-2 caller state (jax imported, cpu devices present, default backend
    not cpu).
    """
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not graft._can_run_in_process(8)
    graft.dryrun_multichip(8)  # raises on failure


def test_dryrun_impl_pins_ops_to_cpu_devices(monkeypatch):
    """_dryrun_impl must not dispatch on the default backend implicitly.

    In this test env the default backend IS cpu, so a TPU escape is not
    directly observable; instead record the two pinning mechanisms in
    action: device selection must go through jax.devices('cpu') and the
    whole run must execute under jax.default_device(<cpu device>).
    """
    devices_platforms = []
    real_devices = jax.devices

    def recording_devices(platform=None):
        devices_platforms.append(platform)
        return real_devices(platform)

    pinned = []
    real_default_device = jax.default_device

    def recording_default_device(device):
        pinned.append(device)
        return real_default_device(device)

    monkeypatch.setattr(jax, "devices", recording_devices)
    monkeypatch.setattr(jax, "default_device", recording_default_device)
    graft._dryrun_impl(2)
    assert "cpu" in devices_platforms
    assert pinned and all(d.platform == "cpu" for d in pinned)


def test_can_run_in_process_does_not_initialize_backends(monkeypatch):
    """The gate must never trigger backend discovery in the caller: with no
    backend initialized yet it must answer False without calling
    jax.default_backend()/jax.devices()."""
    from jax._src import xla_bridge

    def boom(*a, **k):
        raise AssertionError("backend discovery triggered in caller")

    monkeypatch.setattr(jax, "default_backend", boom)
    monkeypatch.setattr(jax, "devices", boom)
    monkeypatch.setattr(xla_bridge, "_backends", {})
    assert not graft._can_run_in_process(2)


# slow: the dry run's six compiles in a child process are what
# `test_dryrun_subprocess_path_from_noncpu_backend` holds (at 8 devices
# for this one's 4); what this adds is the child's environment
@pytest.mark.slow
def test_dryrun_subprocess_env_is_clean():
    """The re-exec must force JAX_PLATFORMS=cpu and the device-count flag
    even when the caller env carries conflicting values."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import __graft_entry__ as g; g.dryrun_multichip(4)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "OK" in proc.stdout
