"""Profiling hooks: XPlane traces + per-stage timer reports.

The reference's three tracing tiers (SURVEY.md §5.1): (a) cheap inline
Timers woven through every stage (platform/timer.h — our utils/timer.py),
(b) per-op profile mode (TrainFilesWithProfiler), (c) the full profiler
emitting chrome-tracing (platform/profiler/). On TPU, (c) maps to
jax.profiler traces viewable in XProf/TensorBoard; (a)/(b) map to the
timer-report helpers here.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Iterator, Optional

from paddlebox_tpu.utils.stats import StatRegistry
from paddlebox_tpu.utils.timer import Timer


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a jax.profiler trace (XPlane; open in XProf/TensorBoard).
    The chrome-tracing-JSON role of platform/profiler/chrometracing_logger.
    While the trace runs, every obs.span() also opens a TraceAnnotation so
    the ring spans land in the XPlane timeline too (the ring export via
    obs.export_chrome_trace works WITHOUT any of this — CPU container).
    One span ``profiler_trace`` covers start_trace to stop_trace on both
    clocks (ring and XPlane host plane): the mark to align them by."""
    import jax

    from paddlebox_tpu.obs import tracer as _obs_tracer
    jax.profiler.start_trace(logdir)
    _obs_tracer.set_jax_annotation(jax.profiler.TraceAnnotation)
    try:
        with _obs_tracer.span("profiler_trace"):
            yield
    finally:
        _obs_tracer.set_jax_annotation(None)
        jax.profiler.stop_trace()


def timer_report(timers: Dict[str, Timer], prefix: str = "") -> str:
    """PrintSyncTimer/PrintDeviceInfo-style one-liner per stage
    (box_wrapper.h:784-801)."""
    lines = []
    for name in sorted(timers):
        t = timers[name]
        if not t.count:
            continue
        lines.append("%s%-12s calls=%-6d total=%8.1fms avg=%8.1fus"
                     % (prefix, name, t.count, t.elapsed_ms(),
                        t.elapsed_us() / max(1, t.count)))
    return "\n".join(lines)


def stats_report() -> str:
    """Named-counter dump (StatRegistry / STAT_INT_ADD, monitor.h:80,137)."""
    snap = StatRegistry.instance().snapshot()
    return "\n".join("%-32s %d" % (k, v) for k, v in sorted(snap.items()))
