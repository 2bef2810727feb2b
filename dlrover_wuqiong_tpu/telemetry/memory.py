"""Device memory from inside the program: the runtime's readings of each
local device, and the compiled budget of one executable.

Parity: reference `dlrover/python/elastic_agent/monitor/resource.py`
(pynvml `nvmlDeviceGetMemoryInfo` of every GPU, reported to the master
every 30 s) — the reference has no account of what a step was compiled
to need, and its readings are the agent's, on a timer, not the
training loop's at the points where memory changes.

TPU redesign: two sources, one module.

- `device_memory()` is the package's ONE reader of
  `Device.memory_stats()` (a PJRT call a device, no device sync): the
  allocator's current and peak bytes, the region it reserves for the
  programs' temporaries, its largest free block and its limit.  The
  trainer takes a `reading()` — the fullest device's, the one that dies
  first — at the boundaries where memory changes (`trainer:build`'s
  end, `trainer:train`'s entry, every logging boundary on the pump
  thread, around `ckpt:snapshot`), each on a span that exists, so a
  flight dump holds the timeline.  A backend without such stats (the
  CPU of the tests) gives `[]` / `{}`, and no record is written.
- `compiled_memory()` is the ONE reckoning of what an executable holds
  while it runs, from `Compiled.memory_analysis()` (per device: the SPMD
  program's own): arguments + temporaries + outputs - what the outputs
  alias of the arguments (a donated state is counted once).
  `telemetry.perf.step_memory()` asks it of the step that ran; the
  strategy search (`auto/engine.py`), `tools/scale_fit.py`,
  `tools/perf_probe.py` and the described-chip compile tests ask it of
  what they compiled.

JAX is taken from `sys.modules`, never imported: the agent and the
master stay clear of it (CLAUDE.md), and a reading is never what
attaches a backend.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional, Sequence

#: what a reading keeps of `Device.memory_stats()`, under the runtime's
#: own names
MEMORY_KEYS = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
               "peak_bytes_reserved", "largest_free_block_bytes",
               "bytes_limit")


def device_memory(devices: Optional[Sequence] = None) -> List[Dict[str, int]]:
    """`MEMORY_KEYS` of each of `devices` (default: this process's local
    devices) that reports any, with the device's `id`.  `[]` where the
    backend gives none, where JAX is not loaded, or — asked without
    devices — where no backend stands yet."""
    if devices is None:
        from .spans import backend_attached

        if not backend_attached():
            return []
        devices = sys.modules["jax"].local_devices()
    out = []
    for dev in devices:
        stats = dev.memory_stats()
        if stats:
            out.append({"device": int(dev.id),
                        **{k: int(stats.get(k, 0)) for k in MEMORY_KEYS}})
    return out


def held_bytes(mem: Dict[str, int]) -> int:
    """What a device cannot give to the next allocation: live buffers
    plus the region reserved for the programs' temporaries."""
    return mem["bytes_in_use"] + mem["bytes_reserved"]


def reading(devices: Optional[Sequence] = None) -> Dict[str, int]:
    """One boundary's record: the fullest device's `MEMORY_KEYS` (by
    `bytes_in_use + bytes_reserved`: the device that dies first), its
    `device` id, `least_bytes_in_use`, the least-full device's live
    bytes (the two differ where the state is sharded unevenly), and
    `devices`, how many were read.  `{}` where `device_memory` finds
    nothing."""
    per_device = device_memory(devices)
    if not per_device:
        return {}
    return {**max(per_device, key=held_bytes),
            "least_bytes_in_use": min(m["bytes_in_use"]
                                      for m in per_device),
            "devices": len(per_device)}


def note(span_rec: Dict[str, Any], key: str) -> None:
    """Put a `reading()` on an open span's record as its attr `key`;
    where there is none, no attr."""
    hbm = reading()
    if hbm:
        span_rec["attrs"][key] = hbm


def headroom_bytes(mem: Dict[str, int]) -> int:
    """`bytes_limit` less what the device holds, of one reading."""
    return mem["bytes_limit"] - held_bytes(mem)


def compiled_memory(compiled: Any) -> Dict[str, int]:
    """The budget of one `jax.stages.Compiled`, per device, in bytes:
    `argument_bytes`, `output_bytes`, `alias_bytes`, `temp_bytes`,
    `generated_code_bytes` and `live_bytes` = argument + temp + output -
    alias, what the program holds at once.  `{}` where the backend has
    no analysis of it."""
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    out = {name + "_bytes": int(getattr(mem, name + "_size_in_bytes"))
           for name in ("argument", "output", "alias", "temp",
                        "generated_code")}
    out["live_bytes"] = (out["argument_bytes"] + out["temp_bytes"]
                         + out["output_bytes"] - out["alias_bytes"])
    return out
