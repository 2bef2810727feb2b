"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the training main path once on ONE TPU chip, through the entry
points a user calls, at the full width of GPT-2 124M (12 layers, 768
wide, 12 heads of 64, sequence 1024, full vocabulary; random weights
from a seed, synthetic data from a seed):

    device   jax.devices() is a TPU — nothing else
    kernel   Pallas flash attention forward + both backward paths against
             `_attention_reference` on the chip, and the lowered GPT-2
             train step carries every kernel as a `tpu_custom_call`
    train    `Trainer(GPT(cfg), TrainingArgs(...), data).train()` in
             process: finite falling loss, MEMORY + DISK checkpoints,
             bit-equal restore from both tiers, perf window, host probes,
             and what a second process that wants the chip gets
    elastic  the same model as a worker under `python -m
             dlrover_wuqiong_tpu.run --standalone --network-check`; the
             worker dies once (os._exit(17)) after a committed save;
             generation 2 resumes from it out of the compile cache

    --multichip   (four chips, run by hand) the same model under
             [("fsdp", {})] on all devices against jax.devices()[:1] in
             one process — and no other phase.

A chip belongs to one process at a time, so the parent NEVER imports JAX
(nor the package): it runs each phase as a child of itself
(`--phase NAME`), one after another, sharing one compile cache
(JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache —
auto/compile_cache.py).  There is no CPU mode: without a TPU the
`device` phase fails and the script exits non-zero with no result line.

Every phase prints one JSON object; numbers in it are smoke output
labelled with the device they ran on, not benchmark results.  The LAST
line of stdout is the contract line:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import glob
import json
import os
import re
import secrets
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "smoke")
SEED = 0
BATCH = 24            # fits one 16 GB chip with room (memory_analysis)
MARK = "SMOKE_RESULT "  # prefix of a phase's result line

# seconds; the whole script stays inside the contract's 1200
PHASE_TIMEOUT = {"device": 120, "kernel": 300, "train": 480,
                 "elastic": 600, "multichip": 900}
TOTAL_BUDGET_S = 1150
TIMEOUT_ENV = "CHIP_SMOKE_PHASE_TIMEOUT_S"  # parent → child, seconds


# --------------------------------------------------------------- helpers


def emit(phase: str, **fields) -> None:
    """A phase's ONE result line (the parent re-prints it unprefixed)."""
    print(MARK + json.dumps({"phase": phase, **fields}, sort_keys=True),
          flush=True)


def device_object():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu():
    """Initialise the default backend; anything but a TPU is a failure —
    and a backend that cannot initialise raises right here."""
    dev = device_object()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (default backend is "
            f"{dev['platform']!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}) — this script only "
            f"runs on the chip")
    return dev


def make_data(vocab: int, batch: int, seq: int, seed: int = SEED):
    """`(step) -> batch`, a pure function of (seed, step) so a resumed
    worker sees the batches the dead one would have.  Windows of one
    fixed random text over a 256-token alphabet: learnable, so the loss
    falls within a few steps."""
    import numpy as np

    text = np.random.default_rng(seed).integers(
        0, min(256, vocab), 1 << 16).astype(np.int32)

    def batch_at(step: int):
        ix = np.random.default_rng((seed, step)).integers(
            0, len(text) - seq - 1, batch)
        x = np.stack([text[i:i + seq + 1] for i in ix])
        return {"input_ids": x[:, :-1], "labels": x[:, 1:]}

    return batch_at


def training_args(out_dir: str, cfg, batch: int, steps: int):
    """ONE TrainingArgs for `train` and `elastic`: same model, shapes,
    strategy and (auto) fused-K, so both compile the same program and a
    cold run of the script compiles the step once.  fused_steps and the
    perf observatory stay at their defaults — the path users get."""
    from dlrover_wuqiong_tpu.trainer.trainer import TrainingArgs

    return TrainingArgs(
        output_dir=out_dir, max_steps=steps, global_batch_size=batch,
        seq_len=cfg.block_size, strategy=[("fsdp", {})],
        warmup_steps=5, logging_steps=5, save_steps=10,
        flash_stage_steps=5, seed=SEED)


_KERNELS = ("tpu_custom_call", "dwt_fa_fwd", "dwt_fa_bwd_fused",
            "dwt_fa_bwd_dq", "dwt_fa_bwd_dkv")


def kernel_counts(text: str):
    """How often a step calls each attention kernel, from its lowered (or
    compiled) text.  A `jax.jit` inside the step (the direct route's
    kernel wrappers) is lowered ONCE, as a private function that every
    layer calls: a name found in such a body counts once for each call
    site of the function, through any depth of calls."""
    parts = re.split(r"func\.func (?:\w+ )?@([\w.]+)", text)
    bodies = dict(zip(parts[1::2], parts[2::2]))

    @functools.lru_cache(maxsize=None)
    def runs(fn: str) -> int:  # times a step runs `fn`; main has no caller
        sites = {g: body.count(f"call @{fn}(")
                 for g, body in bodies.items()}
        return sum(n * runs(g) for g, n in sites.items() if n) or 1

    return {n: parts[0].count(n) + sum(
        body.count(n) * runs(fn) for fn, body in bodies.items())
        for n in _KERNELS}


def tree_bit_equal(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):  # one device→host copy per leaf
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.tobytes() != y.tobytes():
            return False
    return True


# ---------------------------------------------------------------- phases


def phase_device():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    emit("device", ok=True, device=device_object(), jax=jax.__version__,
         memory_stats_keys=sorted(stats),
         bytes_limit=stats.get("bytes_limit"))


def phase_kernel(cfg, batch: int, attn_batch: int = 4):
    """Kernel numerics on the device + kernels present in the model step.

    Tolerances (bf16 operands, f32 accumulation, against the f32
    `_attention_reference`): forward max|err| <= 2e-2, gradients
    max|err| <= 5e-2 relative to the reference gradient's max|value|."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT
    from dlrover_wuqiong_tpu.ops.flash_attention import (
        _attention_reference,
        attention_route,
        flash_attention,
        flash_attention_projected,
    )

    t_phase = time.monotonic()
    h, t, d = cfg.n_head, cfg.block_size, cfg.head_dim
    scale = 1.0 / float(np.sqrt(d))
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, g = (jax.random.normal(kx, (attn_batch, h, t, d), jnp.bfloat16)
                  for kx in (kq, kk, kv, kg))

    def ref_loss(q, k, v):
        out = _attention_reference(*(x.astype(jnp.float32)
                                     for x in (q, k, v)), True, scale)
        return (out * g.astype(jnp.float32)).sum(), out

    (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    errs = {}
    ok = True
    # one block each way (the default at T=1024) = the fused backward;
    # blocks of half the sequence, a 2 x 2 grid = the split dq and dk/dv
    # kernels — together every Pallas attention kernel there is
    for name, blk in (("fused", t), ("split", t // 2)):
        def fa_loss(q, k, v):
            out = flash_attention(q, k, v, True, None, blk, blk)
            return (out.astype(jnp.float32)
                    * g.astype(jnp.float32)).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            fa_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        fwd = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref_out)))
        rel = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                     / jnp.max(jnp.abs(b)))
               for a, b in zip(grads, ref_grads)]
        errs[name] = {"fwd_max_abs": fwd, "grad_rel_dq_dk_dv": rel}
        ok = ok and np.isfinite(fwd) and fwd <= 2e-2 and \
            all(np.isfinite(r) and r <= 5e-2 for r in rel)

    # the projections' own (b, t, h*d) layout, which the model takes
    # where its heads fall on lane slabs: c_attn's q, k and v side by
    # side in ONE array at this model's shape (two heads of 64 a slab,
    # the fused backward), and q, k, v apart at d = 128 over twice the
    # block (a head a slab, the dq and dk/dv kernels)
    def heads_first(x, h):          # (b, t, h*d) -> (b, h, t, d)
        return x.reshape(*x.shape[:2], h, -1).transpose(0, 2, 1, 3)

    d128 = (2, 2 * 1024, 4 * 128)
    for name, n_head, proj in (
            ("direct_qkv", h, (jnp.concatenate([
                x.transpose(0, 2, 1, 3).reshape(attn_batch, t, h * d)
                for x in (q, k, v)], axis=-1),)),
            ("direct_d128", 4, tuple(
                jax.random.normal(kx, d128, jnp.bfloat16)
                for kx in jax.random.split(jax.random.PRNGKey(SEED + 1),
                                           3)))):
        width = proj[0].shape[-1] // (3 // len(proj))
        gp = jax.random.normal(jax.random.PRNGKey(SEED + 2),
                               proj[0].shape[:2] + (width,), jnp.bfloat16)
        assert attention_route(n_head, width // n_head)[0] == "direct"

        def direct_loss(proj):
            out = flash_attention_projected(proj, n_head)
            return (out.astype(jnp.float32)
                    * gp.astype(jnp.float32)).sum(), out

        def plain_loss(proj):
            parts = proj if len(proj) == 3 else jnp.split(proj[0], 3, -1)
            out = _attention_reference(
                *(heads_first(x.astype(jnp.float32), n_head)
                  for x in parts), True,
                1.0 / float(np.sqrt(width // n_head)))
            out = out.transpose(0, 2, 1, 3).reshape(gp.shape)
            return (out * gp.astype(jnp.float32)).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            direct_loss, has_aux=True))(proj)
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            plain_loss, has_aux=True))(proj)
        fwd = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
        rel = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                     / jnp.max(jnp.abs(b)))
               for a, b in zip(grads, want_grads)]
        errs[name] = {"fwd_max_abs": fwd, "grad_rel": rel}
        ok = ok and np.isfinite(fwd) and fwd <= 2e-2 and \
            all(np.isfinite(r) and r <= 5e-2 for r in rel)

    # the model's own train step, lowered (no compile): each attention
    # kernel is in it as a tpu_custom_call — `_use_pallas` did not send
    # the model down the jnp reference.  At its own context the grid is
    # one block each way (fused); at twice that context it is 2 x 2
    # (split), the way a long-context model reaches those kernels
    counts = {}
    for name, seq in (("fused", t), ("split", 2 * t)):
        res = auto_accelerate(
            GPT(dataclasses.replace(cfg, block_size=seq)),
            optimizer=optax.adamw(3e-4), strategy=[("fsdp", {})],
            seq_len=seq, materialize=False)
        bsh = res.batch_sharding_fn(2, None, 0)
        ab = {key: jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                        sharding=bsh)
              for key in ("input_ids", "labels")}
        counts[name] = kernel_counts(
            res.train_step.lower(res.state, ab).as_text())
    n = cfg.n_layer
    in_step = (counts["fused"]["dwt_fa_fwd"] >= n
               and counts["fused"]["dwt_fa_bwd_fused"] >= n
               and counts["split"]["dwt_fa_bwd_dq"] >= n
               and counts["split"]["dwt_fa_bwd_dkv"] >= n)
    emit("kernel", ok=bool(ok and in_step), device=device_object(),
         attn_shape=[attn_batch, h, t, d], errors=errs,
         tolerance={"fwd_max_abs": 2e-2, "grad_rel": 5e-2},
         step_kernels=counts, kernels_in_step=in_step,
         wall_s=round(time.monotonic() - t_phase, 2))
    return ok and in_step


def phase_train(cfg, batch: int, steps: int, out_dir: str):
    import jax
    import numpy as np

    from dlrover_wuqiong_tpu.auto.compile_cache import counters
    from dlrover_wuqiong_tpu.auto.warm_pool import (
        WarmPool,
        load_current_spec,
    )
    from dlrover_wuqiong_tpu.checkpoint.checkpointer import FlashCheckpointer
    from dlrover_wuqiong_tpu.common.util import (
        measure_dispatch_overhead_s,
        measure_h2d_gbps,
        sync_tree,
    )
    from dlrover_wuqiong_tpu.models.gpt import GPT
    from dlrover_wuqiong_tpu.telemetry.ledger import get_ledger
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer

    t_phase = time.monotonic()
    os.environ["DWT_JOB_NAME"] = "smk" + secrets.token_hex(3)
    args = training_args(out_dir, cfg, batch, steps)
    data = make_data(cfg.vocab_size, batch, cfg.block_size)

    tr = Trainer(GPT(cfg), args, data)
    placed = tr.res.place_batch(dict(data(0)))
    # the loss at the untouched init, through the Trainer's own loss fn
    first = float(jax.jit(tr.res.loss_fn)(tr.state.params, placed))
    out = tr.train()
    last = float(out["final_loss"])
    led = get_ledger().snapshot()
    snap = tr._perf.snapshot() if tr._perf is not None else None

    # the step the Trainer ran carries the Pallas kernels
    kcounts = kernel_counts(
        tr.res.fused_train_step(1).lower(tr.state, placed).as_text())

    # checkpoints: both cadences committed, both tiers restore bit-equal
    ckpt_dir = os.path.join(out_dir, "checkpoints")
    committed = tr.ckpt.engine.committed_steps()
    restored = {}
    for tier in ("", "storage"):  # "" = the default chain, shm first
        fresh = FlashCheckpointer(ckpt_dir,
                                  job_name=os.environ["DWT_JOB_NAME"])
        fresh.set_preferred_tier(tier)
        got = fresh.load_checkpoint(tr.state)
        rep = fresh.last_restore_report
        restored[rep.get("tier", "?")] = {
            "step": rep.get("step"),
            "bit_equal": got is not None and tree_bit_equal(got, tr.state)}
        fresh.close()

    # host-side probes the auto fused-K and the offload warning feed on
    t0 = time.perf_counter()
    sync_tree(tr.state)
    sync_first_s = time.perf_counter() - t0  # compiles the helper
    t0 = time.perf_counter()
    sync_tree(tr.state)
    probes = {"dispatch_overhead_s": measure_dispatch_overhead_s(force=True),
              "h2d_gbps": measure_h2d_gbps(force=True),
              "sync_tree_s": time.perf_counter() - t0,
              "sync_tree_first_call_s": sync_first_s}

    # one process per chip: no warm child may be started for the devices
    # this process holds, and nothing may wait for one
    tr.ctx.enable_warm_restarts(tr.res, batch, cfg.block_size)
    spec = load_current_spec(tr.res._cache_dir)
    warm_child = WarmPool(tr.res._cache_dir).warm_async(spec)
    prewarm_cuts_over = tr._prewarm_fused_k(2)
    # ...and what a second process that asks for the chip gets while this
    # one holds it (recorded, not asserted: fails fast, hangs, or shares)
    t0 = time.monotonic()
    try:
        second = subprocess.run(
            [sys.executable, "-c",
             "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
            capture_output=True, text=True, timeout=90)
        second_proc = {"rc": second.returncode,
                       "stdout": second.stdout.strip()[-200:],
                       "stderr_tail": second.stderr.strip()[-300:]}
    except subprocess.TimeoutExpired:
        second_proc = {"rc": None, "hung": True}
    second_proc["wall_s"] = round(time.monotonic() - t0, 2)

    # steady step time: a few more steps of the Trainer's own compiled
    # step, each synced by the loss readback (the perf window's figure
    # includes the profiler; the Trainer's log line times the dispatch)
    step_fn, state, times = tr.res.fused_train_step(1), tr.state, []
    for s in range(steps, steps + 6):
        b = tr.res.place_batch(dict(data(s)))
        t0 = time.perf_counter()
        state, metrics = step_fn(state, b)
        float(metrics["loss"])
        times.append(time.perf_counter() - t0)
    tr.state = state  # the old one was donated
    step_s = float(np.median(times[1:]))

    tr.ckpt.close()
    # the payload (3 x 1.5 GB) must not ride back with the logs
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    dev = device_object()
    ok = (np.isfinite(first) and np.isfinite(last) and last < first
          and out["stopped_at"] == steps
          and set(committed) >= {10, steps}
          and len(restored) == 2
          and all(r["bit_equal"] and r["step"] == steps
                  for r in restored.values())
          and kcounts["dwt_fa_fwd"] >= cfg.n_layer
          and kcounts["dwt_fa_bwd_fused"] + kcounts["dwt_fa_bwd_dq"]
          >= cfg.n_layer
          and snap is not None
          and (dev["platform"] == "cpu"
               or (warm_child is None and prewarm_cuts_over)))
    emit("train", ok=bool(ok), device=dev, steps=steps, batch=batch,
         seq=cfg.block_size, loss_first=first, loss_last=last,
         fused_k=int(getattr(tr, "_fused_k_active", 0)),
         compile_s=round(led["states"].get("compile", 0.0), 2),
         cache_hits=counters.hits, cache_misses=counters.misses,
         step_time_s=step_s, tokens_per_s=batch * cfg.block_size / step_s,
         perf_window={k: (snap or {}).get(k) for k in (
             "step_time_s", "categories", "windows", "overhead_s")},
         ledger_s={k: round(v, 3) for k, v in led["states"].items()},
         committed_steps=committed, restored=restored,
         step_kernels=kcounts, probes=probes,
         warm_child_started=warm_child is not None,
         prewarm_cuts_over=bool(prewarm_cuts_over),
         second_process=second_proc,
         peak_bytes_in_use=(jax.devices()[0].memory_stats() or {}).get(
             "peak_bytes_in_use"),
         wall_s=round(time.monotonic() - t_phase, 2))
    return ok


def phase_worker(cfg, batch: int, steps: int, out_dir: str,
                 crash_after: int = 12):
    """The elastic phase's training script (launched by the agent)."""
    from dlrover_wuqiong_tpu.auto.compile_cache import counters
    from dlrover_wuqiong_tpu.models.gpt import GPT
    from dlrover_wuqiong_tpu.telemetry.ledger import get_ledger
    from dlrover_wuqiong_tpu.trainer.elastic import init_elastic
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer

    restart = init_elastic().world.restart_count
    tracker = os.path.join(out_dir, "checkpoints",
                           "latest_checkpointed_iteration.txt")
    inner = make_data(cfg.vocab_size, batch, cfg.block_size)
    first_step = []

    def data(step: int):
        first_step.append(step)
        if restart == 0 and step >= crash_after:
            # injected fault, once: die as soon as a save has COMMITTED
            # (the tracker names it) — a hard exit, no cleanup
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                try:
                    with open(tracker) as f:
                        if int(f.read().strip() or 0) > 0:
                            os._exit(17)
                except (OSError, ValueError):
                    pass
                time.sleep(0.2)
        return inner(step)

    tr = Trainer(GPT(cfg), training_args(out_dir, cfg, batch, steps), data)
    out = tr.train()
    led = get_ledger().snapshot()
    emit("worker", restart=restart, device=device_object(),
         resumed_step=tr.ckpt.last_restore_report.get("step", 0),
         restore_tier=tr.ckpt.last_restore_report.get("tier", ""),
         first_data_step=first_step[0], stopped_at=out["stopped_at"],
         loss_last=float(out["final_loss"]),
         compile_s=round(led["states"].get("compile", 0.0), 2),
         cache_hits=counters.hits, cache_misses=counters.misses,
         fused_k=int(getattr(tr, "_fused_k_active", 0)))


def phase_elastic(out_dir: str, worker_cmd=None):
    """Never touches JAX: runs the elastic CLI, which runs the worker
    (`worker_cmd`: training script + args, this file's `worker` phase)."""
    t_phase = time.monotonic()
    job = "smk" + secrets.token_hex(3)  # fresh per invocation; keys no cache
    sockets = os.path.join(out_dir, "s")
    if len(sockets) > 60:  # AF_UNIX paths cap at 107 bytes
        raise SystemExit(f"socket dir {sockets!r} is too long for AF_UNIX")
    env = dict(os.environ, DWT_JOB_NAME=job, DWT_SOCKET_DIR=sockets)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "dlrover_wuqiong_tpu.run", "--standalone",
           "--nproc_per_node=1", "--max_restarts=2", "--network-check",
           *(worker_cmd or [os.path.abspath(__file__), "--phase", "worker"])]
    proc = subprocess.Popen(cmd, env=env, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    try:
        # give up before the parent's own limit does, so the cleanup
        # below still runs
        log, _ = proc.communicate(timeout=float(os.environ.get(
            TIMEOUT_ENV, PHASE_TIMEOUT["elastic"])) - 30)
    except subprocess.TimeoutExpired:
        kill_group(proc.pid)
        log, _ = proc.communicate()
    finally:
        # workers lead their own sessions: stop any the agent left behind
        for pid in re.findall(r"launched worker pid=(\d+)", log or ""):
            kill_group(int(pid))
    with open(os.path.join(out_dir, "cli.log"), "w") as f:
        f.write(log)
    shutil.rmtree(os.path.join(out_dir, "checkpoints"), ignore_errors=True)
    workers = [json.loads(line[len(MARK):]) for line in log.splitlines()
               if line.startswith(MARK)]
    gens = {w["restart"]: w for w in workers if w.get("phase") == "worker"}
    g2 = gens.get(1, {})
    leftovers = glob.glob(f"/dev/shm/*{job}*") + (
        os.listdir(sockets) if os.path.isdir(sockets) else [])
    launches = len(re.findall(r"launched worker pid=", log))
    ok = (proc.returncode == 0 and launches == 2 and 0 not in gens
          and g2.get("resumed_step", 0) > 0
          and g2.get("first_data_step") == g2.get("resumed_step")
          and g2.get("cache_hits", 0) > 0 and not leftovers)
    emit("elastic", ok=bool(ok), cli_rc=proc.returncode, launches=launches,
         device=g2.get("device"), generation_2=g2, leftovers=leftovers,
         node_check_children=len(re.findall(r"node check child:", log)),
         wall_s=round(time.monotonic() - t_phase, 2))
    if not ok:
        print(log[-6000:], file=sys.stderr, flush=True)
    return ok


def phase_multichip(cfg, batch: int, steps: int):
    """One process, every device: [("fsdp", {})] through auto_accelerate
    against the same seed and batches on jax.devices()[:1].  Per-step
    losses agree within rtol 2e-2 (bf16 compute; sharding changes the
    reduction order)."""
    import jax
    import numpy as np
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT

    t_phase = time.monotonic()
    devices = jax.devices()
    data = make_data(cfg.vocab_size, batch, cfg.block_size)

    def run(devs):
        res = auto_accelerate(GPT(cfg), optimizer=optax.adamw(3e-4),
                              strategy=[("fsdp", {})], devices=devs,
                              seq_len=cfg.block_size,
                              rng=jax.random.PRNGKey(SEED))
        state = res.state
        t0 = time.monotonic()
        compiled = res.train_step.lower(
            state, res.place_batch(dict(data(0)))).compile()
        compile_s = time.monotonic() - t0
        losses, times = [], []
        for s in range(steps):
            b = res.place_batch(dict(data(s)))
            t0 = time.monotonic()
            state, metrics = compiled(state, b)
            losses.append(float(metrics["loss"]))
            times.append(time.monotonic() - t0)
        return res, state, compiled, losses, compile_s, float(
            np.median(times[1:]))

    _, _, _, ref_losses, ref_compile_s, ref_step_s = run(devices[:1])
    res, state, compiled, losses, compile_s, step_s = run(devices)

    big = [x for x in jax.tree.leaves(state) if x.nbytes >= 1 << 20]
    bad_leaves = []
    for x in big:
        shards = x.addressable_shards
        if len({s.device for s in shards}) != len(devices) or any(
                abs(s.data.nbytes * len(devices) - x.nbytes)
                > 0.05 * x.nbytes for s in shards):
            bad_leaves.append([list(x.shape), str(x.sharding.spec)])
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devices]
    text = compiled.as_text()
    coll = {op: len(re.findall(rf" {op}(?:-start)?\(", text))
            for op in ("all-gather", "reduce-scatter", "all-reduce",
                       "all-to-all", "collective-permute")}
    kcounts = kernel_counts(text)
    rel = float(np.max(np.abs(np.array(losses) - np.array(ref_losses))
                       / np.abs(np.array(ref_losses))))
    ok = (len(devices) > 1 and np.all(np.isfinite(losses)) and rel <= 2e-2
          and losses[-1] < losses[0] and not bad_leaves
          and min(in_use) > 0 and max(in_use) <= 3 * min(in_use)
          and coll["all-gather"] > 0
          and coll["reduce-scatter"] + coll["all-reduce"] > 0
          and kcounts["dwt_fa_fwd"] >= cfg.n_layer)
    emit("multichip", ok=bool(ok), device=device_object(),
         mesh=res.strategy.plan.describe(), steps=steps, batch=batch,
         losses=losses, ref_losses=ref_losses, max_rel_diff=rel,
         tolerance_rtol=2e-2, large_leaves=len(big),
         leaves_not_quarter_sharded=bad_leaves, bytes_in_use=in_use,
         collectives=coll, step_kernels=kcounts,
         compile_s=round(compile_s, 2), step_time_s=step_s,
         tokens_per_s=batch * cfg.block_size / step_s,
         ref_one_device={"compile_s": round(ref_compile_s, 2),
                         "step_time_s": ref_step_s,
                         "tokens_per_s": batch * cfg.block_size / ref_step_s},
         wall_s=round(time.monotonic() - t_phase, 2))
    return ok


# ---------------------------------------------------------------- parent


def kill_group(pid: int) -> None:
    try:
        os.killpg(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def run_phase_child(name: str, timeout: float):
    """Run one phase as a child; returns its result dict (or None)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        cwd=ROOT, stdout=subprocess.PIPE, text=True, start_new_session=True,
        env=dict(os.environ, **{TIMEOUT_ENV: str(int(timeout))}))
    timer_fired = []

    def _on_timeout(signum, frame):
        timer_fired.append(True)
        kill_group(proc.pid)

    signal.signal(signal.SIGALRM, _on_timeout)
    signal.alarm(max(1, int(timeout)))
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(MARK):
                try:
                    got = json.loads(line[len(MARK):])
                except ValueError:
                    continue
                if got.get("phase") == name:
                    result = got
                    print(json.dumps(got, sort_keys=True), flush=True)
            else:
                sys.stderr.write(line)
        rc = proc.wait()
    finally:
        signal.alarm(0)
        kill_group(proc.pid)
    if timer_fired:
        print(f"chip_smoke: phase {name} timed out after {int(timeout)}s",
              file=sys.stderr, flush=True)
        return None
    if rc != 0 or result is None or not result.get("ok"):
        print(f"chip_smoke: phase {name} FAILED (exit code {rc})",
              file=sys.stderr, flush=True)
        return None
    return result


def parent(multichip: bool) -> int:
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    deadline = time.monotonic() + TOTAL_BUDGET_S
    device = None
    for name in (["multichip"] if multichip
                 else ["device", "kernel", "train", "elastic"]):
        t0 = time.monotonic()
        result = run_phase_child(
            name, min(PHASE_TIMEOUT[name], deadline - time.monotonic()))
        print(f"chip_smoke: phase {name} took "
              f"{time.monotonic() - t0:.1f}s", file=sys.stderr, flush=True)
        if result is None:
            return 1
        device = device or result["device"]
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def child(name: str) -> int:
    sys.path.insert(0, ROOT)
    if name == "elastic":
        return 0 if phase_elastic(os.path.join(OUT, "elastic")) else 1
    require_tpu()
    from dlrover_wuqiong_tpu.models.gpt import GPTConfig

    cfg = GPTConfig.gpt2()
    if name == "device":
        phase_device()
        return 0
    if name == "kernel":
        return 0 if phase_kernel(cfg, BATCH) else 1
    if name == "train":
        return 0 if phase_train(cfg, BATCH, 30,
                                os.path.join(OUT, "train")) else 1
    if name == "worker":
        phase_worker(cfg, BATCH, 30, os.path.join(OUT, "elastic"))
        return 0
    if name == "multichip":
        return 0 if phase_multichip(cfg, BATCH, 10) else 1
    raise SystemExit(f"unknown phase {name!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", default="",
                    help="run ONE phase in this process (the parent's "
                         "children; also the elastic worker)")
    ap.add_argument("--multichip", action="store_true",
                    help="the four-chip fsdp phase and nothing else")
    a = ap.parse_args()
    if a.phase:
        return child(a.phase)
    return parent(a.multichip)


if __name__ == "__main__":
    sys.exit(main())
