"""Reduce a profiler trace (.xplane.pb) to numbers: the device's busy
union, device time inside named programs, and the idle gaps attributed to
the host span open at the time.

Reads the file with jax.profiler.ProfileData alone. A device plane is one
whose name starts with ``/device:``; on it the line ``XLA Ops`` carries one
event per executed operation and ``XLA Modules`` one per executed program
(named after its jit). Host spans are the events of the ``/host:CPU``
plane (TraceAnnotation names).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Interval = Tuple[float, float]
MAX_GAPS = 2000


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under " + logdir)
    return paths[-1]


def load(path: str) -> dict:
    """{'devices': {plane: {line: [(name, start_s, end_s)]}},
        'host': [(name, start_s, end_s)]} with times in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:")
        is_host = plane.name.startswith("/host:CPU")
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for ev in line.events]
            if is_dev:
                devices.setdefault(plane.name, {})[line.name] = evs
            else:
                host.extend(evs)
    return {"devices": devices, "host": host}


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines and the commonest event names: for a look by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            names: Dict[str, int] = {}
            n = 0
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
                n += 1
            lines[line.name] = {"events": n, "top": sorted(
                names.items(), key=lambda kv: -kv[1])[:top]}
        out[plane.name] = lines
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Both sorted and disjoint (as union() returns them)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def window_of(trace: dict) -> Interval:
    """The span from the first to the last device event."""
    starts, ends = [], []
    for lines in trace["devices"].values():
        for evs in lines.values():
            if evs:
                starts.append(min(s for _, s, _ in evs))
                ends.append(max(e for _, _, e in evs))
    if not starts:
        raise ValueError("no device event in the trace")
    return min(starts), max(ends)


def _ops(lines: dict) -> list:
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def busy(trace: dict, window: Optional[Interval] = None) -> dict:
    """busy_s averaged over the device planes, and each plane's busy
    union, inside ``window`` (default: first to last device event)."""
    lo, hi = window or window_of(trace)
    per = {}
    for name, lines in trace["devices"].items():
        per[name] = clip(union((s, e) for _, s, e in _ops(lines)), lo, hi)
    if not per:
        raise ValueError("no device plane in the trace")
    busy_s = float(np.mean([total(u) for u in per.values()]))
    return {"busy_s": busy_s, "window_s": hi - lo, "per_device": per}


def busy_in_programs(trace: dict, pattern: str,
                     window: Optional[Interval] = None) -> dict:
    """Seconds in which an operation ran inside a program whose name
    matches ``pattern`` (mean over device planes), the number of such
    program runs, and the names matched."""
    lo, hi = window or window_of(trace)
    rx = re.compile(pattern)
    secs, runs, names = [], 0, set()
    for lines in trace["devices"].values():
        mods = [(n, s, e) for n, s, e in lines.get(MODULES_LINE, [])
                if rx.search(n)]
        names.update(n for n, _, _ in mods)
        runs = max(runs, len(clip([(s, e) for _, s, e in mods], lo, hi)))
        prog = clip(union((s, e) for _, s, e in mods), lo, hi)
        ops = clip(union((s, e) for _, s, e in _ops(lines)), lo, hi)
        secs.append(total(intersect(ops, prog)))
    return {"seconds": float(np.mean(secs)) if secs else 0.0,
            "runs": runs, "names": sorted(names)}


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def short_name(event_name: str) -> Tuple[str, str]:
    """('%fusion.8', 'fusion') from the HLO text the trace names an
    operation by; (name, '') where the text has no ' = '."""
    head, sep, rest = event_name.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    return head.strip(), (m.group(1) if m else "")


def top_ops(trace: dict, k: int = 10,
            window: Optional[Interval] = None) -> List[list]:
    """The operations that took most device time, by short name; the
    operations that only contain others (while, conditional, call) are
    left out, their bodies' operations are listed."""
    lo, hi = window or window_of(trace)
    acc: Dict[str, float] = {}
    n_dev = max(1, len(trace["devices"]))
    for lines in trace["devices"].values():
        for name, s, e in _ops(lines):
            d = min(e, hi) - max(s, lo)
            op, code = short_name(name)
            if d > 0 and code not in CONTAINERS:
                key = (op + " " + code).strip()
                acc[key] = acc.get(key, 0.0) + d / n_dev
    return [[n, v] for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: dict, span_names: Sequence[str], k: int = 10,
              window: Optional[Interval] = None) -> List[list]:
    """Idle seconds of the first device plane by what the host was doing:
    each gap between device operations goes to the listed host span that
    overlaps it longest, or to 'none'."""
    lo, hi = window or window_of(trace)
    plane = sorted(trace["devices"])[0]
    b = clip(union((s, e) for _, s, e in _ops(trace["devices"][plane])),
             lo, hi)
    edges = [lo] + [t for s, e in b for t in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    wanted = set(span_names)
    spans = sorted((s, e, n) for n, s, e in trace["host"] if n in wanted)
    # the longest gaps are attributed one by one; the many gaps of a few
    # microseconds between operations are summed under one name
    gaps.sort(key=lambda g: g[0] - g[1])
    acc: Dict[str, float] = {}
    if len(gaps) > MAX_GAPS:
        acc["short_gaps"] = total(gaps[MAX_GAPS:])
    for gs, ge in gaps[:MAX_GAPS]:
        best, best_ov = "none", 0.0
        for s, e, n in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        acc[best] = acc.get(best, 0.0) + (ge - gs)
    return [[n, v] for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
