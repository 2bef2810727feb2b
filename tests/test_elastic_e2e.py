"""End-to-end elasticity: CLI → master → agent → worker crash → restart →
resume from flash checkpoint.

Mirrors the reference's chaos experiments (docs/tech_report/
fault_tolerance_exps.md) at unit scale: injected worker failure, loss of no
committed state, training completes after automatic restart.
"""

import json
import os
import subprocess
import sys
import tempfile



def _socket_dir():
    """A SHORT control-socket dir: AF_UNIX paths cap at 107 bytes, and
    pytest's tmp_path (deeper still under xdist) plus the longest socket
    name overruns it."""
    return tempfile.mkdtemp(prefix="dwt-e2e-")


WORKER_SCRIPT = r"""
import os, sys, time
import numpy as np

from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)

ckpt_dir = sys.argv[1]
marker_dir = sys.argv[2]

ctx = init_elastic()
restart = ctx.world.restart_count
ckpt = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"])

template = {"w": np.zeros((4, 4), np.float32), "step": np.zeros((), np.int64)}
state = ckpt.load_checkpoint(template)
start_step = int(state["step"]) + 1 if state is not None else 0

with open(os.path.join(marker_dir, f"start_r{restart}.json"), "w") as f:
    f.write(str(start_step))

for step in range(start_step, 21):
    w = np.full((4, 4), float(step), np.float32)
    ckpt.save_checkpoint(step, {"w": w, "step": np.int64(step)},
                         storage_type=StorageType.DISK)
    ctx.report_step(step)
    time.sleep(0.02)
    if step == 12 and restart == 0:
        ckpt.wait_latest_checkpoint(30)
        os._exit(17)  # injected fault

ok = ckpt.wait_latest_checkpoint(60)
with open(os.path.join(marker_dir, "done.txt"), "w") as f:
    f.write(f"{ok} {step}")
"""


def test_crash_restart_resume(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER_SCRIPT)
    ckpt_dir = tmp_path / "ckpt"
    marker_dir = tmp_path / "markers"
    marker_dir.mkdir()

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "DWT_JOB_NAME": "e2e1",
        "DWT_SOCKET_DIR": _socket_dir(),
        "DWT_CTX_NODE_HEARTBEAT_TIMEOUT": "600",
    })
    proc = subprocess.run(
        [sys.executable, "-m", "dlrover_wuqiong_tpu.run", "--standalone",
         "--nproc_per_node=1", "--max_restarts=2",
         str(script), str(ckpt_dir), str(marker_dir)],
        env=env, capture_output=True, text=True, timeout=150,
        cwd="/root/repo")

    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    done = (marker_dir / "done.txt").read_text()
    assert done.startswith("True 20"), done
    # restart happened and resumed from >= the crash checkpoint
    start_r1 = int((marker_dir / "start_r1.txt").read_text()) \
        if (marker_dir / "start_r1.txt").exists() else None
    r1 = (marker_dir / "start_r1.json")
    assert r1.exists(), "worker was not restarted"
    resumed_from = int(r1.read_text())
    assert resumed_from >= 12, f"resumed too early: {resumed_from}"
    # committed tracker shows the final step
    tracker = ckpt_dir / "latest_checkpointed_iteration.txt"
    assert tracker.read_text().strip() == "20"


JAX_WORKER = r"""
import json, os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

ckpt_dir, marker_dir, mode = sys.argv[1], sys.argv[2], sys.argv[3]

from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
ctx = init_elastic()
restart = ctx.world.restart_count
pid = ctx.world.process_id
nprocs = ctx.world.num_processes

import dataclasses
import jax.numpy as jnp
import optax
from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.checkpoint.checkpointer import (
    FlashCheckpointer, StorageType)

cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                          use_flash_attention=False, remat=False)
res = auto_accelerate(GPT(cfg), optimizer=optax.adam(1e-2),
                      strategy=[("fsdp", {})], devices=jax.devices())
ck = FlashCheckpointer(ckpt_dir, job_name=os.environ["DWT_JOB_NAME"])

state = res.state
start = 0
restored = ck.load_checkpoint(res.state)
if restored is not None:
    state = restored
    start = int(np.asarray(state.step))

data = np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 33))
batch = res.place_batch({"input_ids": jnp.asarray(data[:, :-1]),
                         "labels": jnp.asarray(data[:, 1:])})

marker = os.path.join(
    marker_dir,
    f"start_r{restart}_p{pid}_n{os.getenv('DWT_NODE_ID', 'x')}.json")
with open(marker, "w") as f:
    json.dump({"start": start, "nprocs": nprocs,
               "devices": len(jax.devices()),
               "node": int(os.getenv("DWT_NODE_ID", "-1")),
               "restart": restart, "ospid": os.getpid()}, f)

paced = mode in ("slice", "scale")
TOTAL = 30 if paced else 8
loss_log = os.path.join(marker_dir, f"losses_r{restart}_p{pid}.jsonl")
for _ in range(start, TOTAL):
    state, m = res.train_step(state, batch)
    step = int(np.asarray(state.step))
    with open(loss_log, "a") as f:
        f.write(json.dumps([step, float(m["loss"])]) + "\n")
    ck.save_checkpoint(step, state, storage_type=StorageType.DISK)
    ck.wait_latest_checkpoint(60)
    ctx.report_step(step, force=True)
    if paced:
        time.sleep(0.2)  # widen the externally-injected kill / join window
    if mode == "crash" and restart == 0 and pid == 0 and step == 3:
        os._exit(17)  # injected fault AFTER step-3 commit

if pid == 0:
    with open(os.path.join(marker_dir, "done.txt"), "w") as f:
        f.write(str(int(np.asarray(state.step))))
ck.close()
"""


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_master(port, min_nodes, max_nodes, env):
    return subprocess.Popen(
        [sys.executable, "-c",
         "from dlrover_wuqiong_tpu.master.master import run_master_forever;"
         f"run_master_forever({port}, {min_nodes}, {max_nodes})"],
        env=env, cwd="/root/repo",
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _spawn_agent(node_id, script, args, master_port, env, nnodes="2"):
    aenv = dict(env)
    aenv.update({
        "DWT_MASTER_ADDR": f"127.0.0.1:{master_port}",
        "DWT_NODE_ID": str(node_id),
        "DWT_NODE_RANK": str(node_id),
        "DWT_JOB_NAME": f"{env['DWT_JOB_NAME']}-n{node_id}",
    })
    return subprocess.Popen(
        [sys.executable, "-m", "dlrover_wuqiong_tpu.run",
         f"--nnodes={nnodes}", "--nproc_per_node=2", "--max_restarts=3",
         str(script)] + [str(a) for a in args],
        env=aenv, cwd="/root/repo",
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _base_env(tmp_path, job):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "DWT_JOB_NAME": job,
        "DWT_SOCKET_DIR": _socket_dir(),
        "DWT_CTX_NODE_HEARTBEAT_TIMEOUT": "600",
        "DWT_RESTART_DEBOUNCE_SECS": "2",
    })
    return env


def test_jax_world_crash_restart_resume(tmp_path):
    """Real-mesh elasticity: 2 hosts x 2 virtual devices, fsdp=4 sharded
    TrainState; rank-0 worker crashes after the step-3 commit; both agents
    re-rendezvous, jax.distributed re-forms, sharded state restores, loss
    continues to step 8."""
    script = tmp_path / "worker.py"
    script.write_text(JAX_WORKER)
    ckpt_dir = tmp_path / "ckpt"
    markers = tmp_path / "markers"
    markers.mkdir()
    env = _base_env(tmp_path, "jx1")
    port = _free_port()
    master = _spawn_master(port, 2, 2, env)
    agents = []
    try:
        import time as _t
        _t.sleep(2.0)
        agents = [_spawn_agent(i, script, [ckpt_dir, markers, "crash"],
                               port, env) for i in range(2)]
        for a in agents:
            out, _ = a.communicate(timeout=420)
            assert a.returncode == 0, out[-4000:]
        done = (markers / "done.txt").read_text()
        assert done == "8", done
        # the restarted world resumed from the committed step, not zero
        resumes = [json.loads(p.read_text())
                   for p in markers.glob("start_r*_p*.json")
                   if "start_r0" not in p.name]
        assert resumes, "no restarted worker markers"
        assert all(r["start"] >= 3 for r in resumes), resumes
        assert all(r["nprocs"] == 2 and r["devices"] == 4 for r in resumes)
        # loss continuity: post-restart losses carry on below the first loss
        def _read(pattern):
            out = []
            for f in markers.glob(pattern):
                for line in f.read_text().splitlines():
                    out.append(json.loads(line))
            return out

        pre = _read("losses_r0_p*.jsonl")
        post = _read("losses_r1_p*.jsonl")
        assert pre and post
        first = min(v for s_, v in pre if s_ == 1)
        assert max(v for _, v in post) < first
    finally:
        master.kill()
        for a in agents:
            if a.poll() is None:
                a.kill()


def test_jax_world_scale_up(tmp_path):
    """Membership change: a world of 1 node is joined by a second node;
    the running agent restarts its worker into the 2-node world
    (drives ElasticAgent._membership_changed) with state carried over."""
    script = tmp_path / "worker.py"
    script.write_text(JAX_WORKER)
    ckpt_dir = tmp_path / "ckpt"
    markers = tmp_path / "markers"
    markers.mkdir()
    env = _base_env(tmp_path, "jx2")
    port = _free_port()
    master = _spawn_master(port, 1, 2, env)
    agents = []
    try:
        import time as _t
        _t.sleep(2.0)
        # "scale": paced like "slice" — eight unpaced steps can be over
        # before a loaded host has brought node 1's agent up, and then no
        # world ever has two processes
        agents.append(_spawn_agent(0, script, [ckpt_dir, markers, "scale"],
                                   port, env, nnodes="1:2"))
        # wait until node 0 trains alone, then add node 1
        deadline = _t.time() + 180
        while _t.time() < deadline and \
                not list(markers.glob("start_r0_p0_*.json")):
            _t.sleep(0.5)
        assert list(markers.glob("start_r0_p0_*.json"))
        # wait for a COMMITTED checkpoint, not a fixed sleep: under CI
        # load the solo worker can take >4s to commit its first steps,
        # and the scale-up restart would then legitimately start from 0
        commit_marker = ckpt_dir / "latest_checkpointed_iteration.txt"
        deadline = _t.time() + 120
        while _t.time() < deadline and not commit_marker.exists():
            _t.sleep(0.5)
        assert commit_marker.exists(), "solo worker never committed"
        agents.append(_spawn_agent(1, script, [ckpt_dir, markers, "scale"],
                                   port, env, nnodes="1:2"))
        for a in agents:
            out, _ = a.communicate(timeout=420)
            assert a.returncode == 0, out[-4000:]
        # some worker ran in a 2-process world spanning 4 devices
        worlds = [json.loads(p.read_text())
                  for p in markers.glob("start_r*_p*.json")]
        assert any(w["nprocs"] == 2 and w["devices"] == 4 for w in worlds), \
            worlds
        # node 0's restarted worker carried state over (start > 0)
        restarted = [w for w in worlds if w["nprocs"] == 2 and w["start"] > 0]
        assert restarted, worlds
        assert (markers / "done.txt").exists()
    finally:
        master.kill()
        for a in agents:
            if a.poll() is None:
                a.kill()


def test_jax_world_slice_loss(tmp_path):
    """Multi-slice failure domain (SURVEY §2.5 DCN row; reference node
    groups dist_job_manager.py:88): a whole node group — agent AND its
    worker, i.e. "slice 0", which hosts the jax.distributed coordinator —
    is SIGKILLed mid-training.  The survivor's worker dies on the broken
    world, a replacement node joins, the master re-forms the world with
    {survivor, replacement}, and training resumes from the committed step
    through to completion."""
    import signal
    import time as _t

    script = tmp_path / "worker.py"
    script.write_text(JAX_WORKER)
    ckpt_dir = tmp_path / "ckpt"
    markers = tmp_path / "markers"
    markers.mkdir()
    env = _base_env(tmp_path, "jx3")
    port = _free_port()
    master = _spawn_master(port, 2, 3, env)
    agents = []
    try:
        _t.sleep(2.0)
        a0 = _spawn_agent(0, script, [ckpt_dir, markers, "slice"],
                          port, env)
        a1 = _spawn_agent(1, script, [ckpt_dir, markers, "slice"],
                          port, env)
        agents = [a0, a1]
        # wait until both slices train and a step committed
        deadline = _t.time() + 180
        tracker = ckpt_dir / "latest_checkpointed_iteration.txt"
        node0_marker = None
        while _t.time() < deadline:
            r0 = [json.loads(p.read_text())
                  for p in markers.glob("start_r0_p*.json")]
            if len(r0) == 2 and tracker.exists():
                node0_marker = next(m for m in r0 if m["node"] == 0)
                break
            _t.sleep(0.5)
        assert node0_marker is not None, "slices never started training"
        # kill slice 0 whole: the agent's process group AND its worker
        # (the worker runs in its own session — start_new_session=True)
        os.kill(a0.pid, signal.SIGKILL)
        try:
            os.killpg(node0_marker["ospid"], signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            os.kill(node0_marker["ospid"], signal.SIGKILL)
        # replacement slice joins
        a2 = _spawn_agent(2, script, [ckpt_dir, markers, "slice"],
                          port, env)
        agents.append(a2)
        for a in (a1, a2):
            out, _ = a.communicate(timeout=420)
            assert a.returncode == 0, out[-4000:]
        assert (markers / "done.txt").read_text() == "30"
        # the re-formed 2-node world includes the REPLACEMENT node and
        # resumed from committed state, not zero.  Post-kill markers:
        # the survivor's restarts (restart > 0) and the replacement's
        # first run (node 2, restart 0).
        worlds = [json.loads(p.read_text())
                  for p in markers.glob("start_r*_p*_n*.json")]
        post = [w for w in worlds if w["restart"] > 0 or w["node"] == 2]
        assert any(w["node"] == 2 and w["nprocs"] == 2 for w in post), \
            worlds
        assert all(w["start"] > 0 for w in post), post
    finally:
        master.kill()
        for a in agents:
            if a.poll() is None:
                try:
                    a.kill()
                except ProcessLookupError:
                    pass
