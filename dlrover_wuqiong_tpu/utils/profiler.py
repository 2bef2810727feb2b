"""Training-side profiling orchestration.

Parity: reference `atorch/atorch/utils/prof.py` (torch.profiler window
orchestration + timeline dump) and the xpu_timer runtime-timing intent
(`atorch/dev/xpu_timer/common/manager.cc` — always-on step timings exported
to Prometheus).

TPU redesign: heavyweight tracing is `jax.profiler` (XPlane/TensorBoard
format) started for a bounded step window, whose op-category split
(`utils/xplane.py`) stays on the profiler as `last_profile` for the perf
observatory's sentinel.  The always-on step timing is the Trainer's own
per-step spans (`telemetry/spans.py`: `trainer:iteration`,
`trainer:dispatch`): a worker's Prometheus series land in a registry only
the master exports, so this module writes none (the device timeline
inside a jit step is XLA's domain — per-op host hooks like LD_PRELOAD
shims don't exist on TPU, the trace viewer covers that instead).
"""

from __future__ import annotations

import contextlib
from typing import Optional

from ..common.log import get_logger

logger = get_logger("profiler")


class StepProfiler:
    """Windowed jax.profiler trace around the steps of a loop.

    Usage:
        prof = StepProfiler(trace_dir="/tmp/trace", start_step=10,
                            end_step=12)
        for step in ...:
            with prof.step(step):
                state, m = train_step(state, batch)
    """

    def __init__(self, trace_dir: Optional[str] = None,
                 start_step: int = -1, end_step: int = -1,
                 device_only: bool = False):
        """`device_only`: leave the Python tracer out of the trace — for
        windows whose only reader is the xplane op split (the perf
        observatory).  Every Python call of every thread otherwise lands
        in the file, which the pure-Python reducer then has to walk."""
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.end_step = end_step
        self._device_only = device_only
        self._tracing = False
        self.last_profile = None  # OpProfile of the latest closed window

    @contextlib.contextmanager
    def step(self, step: int):
        self._maybe_start_trace(step)
        try:
            yield
        finally:
            self._maybe_stop_trace(step)

    def _maybe_start_trace(self, step: int):
        if (self.trace_dir and not self._tracing
                and step == self.start_step):
            import jax

            options = None
            if self._device_only:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self._tracing = True
            logger.info("jax.profiler trace started at step %d → %s",
                        step, self.trace_dir)

    def closes_at(self, step: int) -> bool:
        """True when leaving `step(step)` will stop the trace.  Dispatch
        is asynchronous: the caller must wait for the device work it
        wants in the trace BEFORE leaving, or the file holds the first
        few milliseconds of it."""
        return self._tracing and step >= self.end_step

    def _maybe_stop_trace(self, step: int):
        if self.closes_at(step):
            import jax

            jax.profiler.stop_trace()
            self._tracing = False
            logger.info("jax.profiler trace stopped at step %d", step)
            self._publish_op_profile()

    def _publish_op_profile(self):
        """xpu_timer parity: per-op-category latencies from the XPlane,
        kept as `last_profile` (the sentinel's input, diagnosis
        evidence) and logged."""
        from .xplane import parse_trace_dir

        try:
            prof = parse_trace_dir(self.trace_dir)
        except Exception:  # noqa: BLE001 — observability must not kill train
            logger.exception("xplane parse FAILED for %s — this window "
                             "has no op profile", self.trace_dir)
            return
        if prof is None:
            logger.error("trace window left no parseable xplane file "
                         "under %s — this window has no op profile",
                         self.trace_dir)
            return
        self.last_profile = prof
        logger.info(
            "op profile: %s",
            " ".join(f"{c}={s * 1e3:.2f}ms"
                     for c, s in sorted(prof.categories.items())))

    def close(self):
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False
            self._publish_op_profile()


@contextlib.contextmanager
def annotate(name: str):
    """Named region in the device trace (TraceAnnotation)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
