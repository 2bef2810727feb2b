"""Agent-side resource monitor: host cpu/mem (+ TPU runtime metrics) → master.

Parity: reference `elastic_agent/monitor/resource.py` (ResourceMonitor :86,
report_resource :157; psutil+pynvml there, psutil+libtpu-metrics here) and
`monitor/training.py` (TrainingProcessReporter).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from ..common.log import get_logger
from .master_client import MasterClient

logger = get_logger("monitor")

_PROC = None
_PROC_LOCK = threading.Lock()


def _psutil_process():
    """Cached, PRIMED psutil.Process.

    `cpu_percent(interval=None)` measures since the previous call on the
    same Process object — the first call has no baseline and always
    returns 0.0.  A fresh Process per report (the old code) therefore
    reported a flat 0% CPU forever.  Prime once at acquisition and reuse;
    re-acquire after fork/spawn (pid check) so a child never reads the
    parent's baseline."""
    global _PROC
    import psutil

    with _PROC_LOCK:
        if _PROC is None or _PROC.pid != os.getpid():
            proc = psutil.Process()
            proc.cpu_percent(interval=None)  # prime the baseline sample
            _PROC = proc
        return _PROC


def get_process_resource() -> Dict[str, float]:
    """Host usage of this process tree (no psutil dependency required)."""
    stats: Dict[str, float] = {"cpu_percent": 0.0, "memory_mb": 0.0}
    try:
        proc = _psutil_process()
        stats["cpu_percent"] = proc.cpu_percent(interval=None)
        stats["memory_mb"] = proc.memory_info().rss / (1 << 20)
    except ImportError:
        try:
            import resource

            usage = resource.getrusage(resource.RUSAGE_SELF)
            stats["memory_mb"] = usage.ru_maxrss / 1024.0
        except Exception:  # noqa: BLE001
            pass
    return stats


def get_accelerator_stats() -> Dict[str, float]:
    """Device memory of the FULLEST local device (the one that dies
    first), where this process holds devices that report any
    (`telemetry/memory.py`: JAX is never imported from here — the agent
    stays clear of it, and then reports none)."""
    from ..telemetry import memory as tmemory

    full = tmemory.reading()
    if not full:
        return {}
    return {"num_devices": float(full["devices"]),
            "hbm_bytes_in_use": float(full["bytes_in_use"]),
            "hbm_bytes_reserved": float(full["bytes_reserved"]),
            "hbm_bytes_limit": float(full["bytes_limit"])}


class ResourceMonitor:
    def __init__(self, master_client: MasterClient,
                 interval: float = 30.0):
        self.mc = master_client
        self.interval = interval
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="dwt-resource-monitor")
        self._thread.start()

    def _loop(self):
        from ..common import messages as msg

        while not self._stopped.wait(self.interval):
            try:
                host = get_process_resource()
                accel = get_accelerator_stats()
                self.mc._client.report(msg.ResourceStats(
                    node_id=self.mc.node_id,
                    cpu_percent=host["cpu_percent"],
                    memory_mb=host["memory_mb"],
                    accelerator_stats=accel))
            except Exception:  # noqa: BLE001
                logger.debug("resource report failed", exc_info=True)

    def stop(self):
        self._stopped.set()
