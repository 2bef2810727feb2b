"""8B/70B north-star fit prober: AOT-compile the FULL auto_accelerate train
step for real model configs on a virtual device mesh and report per-device
memory from `compiled.memory_analysis()`.

Nothing is materialized (auto_accelerate(materialize=False) builds the
abstract sharded state; parity: reference meta_model_utils.py:1-759 meta-
device init for 65B-class models).  The proof this provides:

- the SPMD program COMPILES at the 8B/70B scale with the strategy's real
  shardings (no shape/sharding surprises that only appear past toy scale);
- `argument_size_in_bytes` / `output_size_in_bytes` are EXACT per-device
  train-state bytes under the strategy — the dominant term of the 8B fit;
- with optimizer_offload, `host_argument_size_in_bytes` proves the
  moments landed in pinned_host AT COMPILE TIME (not just at runtime).

`temp_size_in_bytes` is reported but is an UPPER BOUND artifact on the CPU
backend: XLA:CPU's buffer assignment reports the SUM of temp allocations
without the liveness-based reuse the TPU assignment performs — measured
here: an 8B config with remat OFF and remat 'dots' report the SAME temp
bytes (18.33 GiB at L4/s1024), so CPU temps cannot distinguish remat
policies, let alone model TPU peak.  Activation peak on TPU is instead
bounded analytically (see tests/test_scale_8b.py docstring) and verified
empirically at bench scale on the real chip.

Usage (subprocess; the virtual device count must be set before jax init):
    python tools/scale_fit.py <n_devices> <config_json>
where config_json = {"model": "8b"|"70b", "seq": 4096,
                     "strategy": [["fsdp", {}], ...], "batch": N}
Prints one JSON line with the measurements.
"""

import json
import os
import sys
import time


def main():
    n_dev = int(sys.argv[1])
    cfg_in = json.loads(sys.argv[2])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
    from dlrover_wuqiong_tpu.telemetry.memory import compiled_memory

    cfg = {"8b": LlamaConfig.llama3_8b,
           "70b": LlamaConfig.llama3_70b}[cfg_in.get("model", "8b")]()
    seq = int(cfg_in.get("seq", 4096))
    batch = int(cfg_in.get("batch", n_dev))
    strategy = [tuple(s) for s in cfg_in["strategy"]]

    t0 = time.monotonic()
    res = auto_accelerate(Llama(cfg), optimizer=optax.adamw(3e-4),
                          strategy=strategy, materialize=False, seq_len=seq)
    bsh = res.batch_sharding_fn(2, None, 0)
    ab = {"input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                            sharding=bsh),
          "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                         sharding=bsh)}
    compiled = res.train_step.lower(res.state, ab).compile()
    ma = compiled.memory_analysis()
    budget = compiled_memory(compiled)
    out = {
        "ok": True,
        "mesh": res.strategy.plan.describe(),
        "params": cfg.num_params(),
        "seq": seq, "batch": batch, "n_devices": n_dev,
        "compile_s": round(time.monotonic() - t0, 1),
        "arg_gib": round(budget["argument_bytes"] / 2**30, 3),
        "out_gib": round(budget["output_bytes"] / 2**30, 3),
        "alias_gib": round(budget["alias_bytes"] / 2**30, 3),
        "temp_gib_cpu_upper_bound": round(
            budget["temp_bytes"] / 2**30, 3),
        "live_gib_cpu_upper_bound": round(
            budget["live_bytes"] / 2**30, 3),
        "host_arg_gib": round(
            ma.host_argument_size_in_bytes / 2**30, 3),
        "host_out_gib": round(ma.host_output_size_in_bytes / 2**30, 3),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
