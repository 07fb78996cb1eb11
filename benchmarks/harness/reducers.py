"""The closed set of reducer kinds behind benchmarks/layer_metrics/*.json.

A metric file is {layer, unit, moves, better, kind, args}. A reducer gets
the traced slice's context and returns a number, or None when it finds
nothing to read (the harness then leaves the metric out of the line; a
share of a peak is never reported as 0).

ctx keys: spans [(name, t0, t1)], counters {name: delta}, passes, steps,
examples, window_s (host clock), trace (trace_reduce.load() or None),
trace_window (lo, hi) on the trace's clock, chips, peaks, cfg, cfg_mod,
unique_rows_per_example.
"""

from __future__ import annotations

from typing import Optional

from . import trace_reduce as tr


def _per(ctx: dict, per: str) -> float:
    n = {"pass": ctx["passes"], "step": ctx["steps"],
         "example": ctx["examples"]}[per]
    return float(n)


def span_total_per(ctx, spans, per, scale=1000.0) -> Optional[float]:
    """Sum of the named host spans' durations / passes|steps|examples,
    times ``scale`` (1000: seconds -> ms)."""
    wanted = set(spans)
    found = [t1 - t0 for name, t0, t1 in ctx["spans"] if name in wanted]
    if not found or not _per(ctx, per):
        return None
    return scale * sum(found) / _per(ctx, per)


def counter_delta_per(ctx, counters, per) -> Optional[float]:
    if not all(c in ctx["counters"] for c in counters) or not _per(ctx, per):
        return None
    return sum(ctx["counters"][c] for c in counters) / _per(ctx, per)


def device_busy_in_programs_per(ctx, pattern, per, scale=1000.0
                                ) -> Optional[float]:
    if ctx.get("trace") is None or not _per(ctx, per):
        return None
    got = tr.busy_in_programs(ctx["trace"], pattern, ctx["trace_window"])
    if not got["runs"] or got["seconds"] <= 0:
        return None
    return scale * got["seconds"] / _per(ctx, per)


def device_idle_share(ctx) -> Optional[float]:
    if ctx.get("trace") is None:
        return None
    b = tr.busy(ctx["trace"], ctx["trace_window"])
    if b["busy_s"] <= 0 or b["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def work_over_peak(ctx, work, peak, time) -> Optional[float]:
    """100 * (work per example * examples / peak) / seconds, where seconds
    is the traced window (time == "window": the whole step's share of the
    chips' peak) or the device time inside the programs matching
    time["programs"] (a roofline share of the kernels' time)."""
    fn = getattr(ctx["cfg_mod"], work)
    if work == "bytes_per_example":
        per_example = fn(ctx["cfg"], ctx["unique_rows_per_example"])
    else:
        per_example = fn(ctx["cfg"])
    least = per_example * ctx["examples"] / ctx["peaks"][peak]
    if time == "window":
        secs = ctx["window_s"] * ctx["chips"]
    else:
        if ctx.get("trace") is None:
            return None
        got = tr.busy_in_programs(ctx["trace"], time["programs"],
                                  ctx["trace_window"])
        secs = got["seconds"] * ctx["chips"]
    if secs <= 0 or least <= 0:
        return None
    return 100.0 * least / secs


KINDS = {f.__name__: f for f in (
    span_total_per, counter_delta_per, device_busy_in_programs_per,
    device_idle_share, work_over_peak)}


def reduce_metric(spec: dict, ctx: dict) -> Optional[float]:
    if spec["kind"] not in KINDS:
        raise KeyError("unknown reducer kind %r (have %s)"
                       % (spec["kind"], sorted(KINDS)))
    return KINDS[spec["kind"]](ctx, **spec.get("args", {}))
