"""Span tracer: lock-free per-thread ring buffers → chrome-tracing JSON.

The cheap always-on tier of the reference's tracing ladder (SURVEY.md
§5.1): platform::RecordEvent spans feeding chrometracing_logger. Here a
span is ONE perf_counter pair appended to the calling thread's private
ring (no lock, no allocation beyond a tuple), so instrumenting every hot
path costs ~1us/span and the last `capacity` spans per thread are always
available — to the watchdog's stall dump, and to export_chrome() which
emits valid chrome-tracing JSON loadable in Perfetto WITHOUT jax.profiler
(works on the CPU-fallback container; when a real jax trace is running,
utils/profiler.trace installs TraceAnnotation so the same spans also land
in the XPlane).

Round 14 adds CROSS-PLANE trace ids: a span optionally carries a 64-bit
trace id (thread-local "current trace" context, set per step by the
runners and per request by the serving client), the id travels in mesh
frame headers / serving request dicts, and receiver-side spans record
the SENDER's id — which is what lets tools/trace_stitch.py merge
per-rank chrome traces into one cluster timeline with ph:s/f flow
events across ranks. Exported traces carry a wall-clock origin in their
metadata so the stitcher can place every rank on one absolute axis.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple
from paddlebox_tpu.utils.lockwatch import make_rlock

# process-relative clock origin: chrome ts fields are µs since this epoch.
# _EPOCH_UNIX is the SAME instant on the wall clock (taken back-to-back)
# — the anchor trace_stitch uses to align per-rank traces on one axis.
_EPOCH = time.perf_counter()
_EPOCH_UNIX = time.time()

# jax.profiler.TraceAnnotation factory while a real trace is running
# (installed/removed by utils/profiler.trace) — None = spans are ring-only
_JAX_ANNOTATE = None


def set_jax_annotation(factory) -> None:
    global _JAX_ANNOTATE
    _JAX_ANNOTATE = factory


# ------------------------------------------------------------- trace ids
# Thread-local "current trace": spans recorded while a trace id is set
# carry it into the ring (and from there into the chrome export's args),
# so one request/step can be followed across every span it touches.
_TRACE_CTX = threading.local()
# client-side request ids: salted counter — correlated by equality,
# never decoded. The 15-bit salt mixes the pid with random bytes: a pid
# alone collides under modern pid_max (4M >> 2^15, two processes equal
# mod 32768 would mint identical sequences), the random mix makes a
# cross-process collision 2^-15 per pair instead of systematic.
_NEXT_REQ = itertools.count(1)
_REQ_SALT = ((os.getpid() ^ (os.getpid() >> 15)
              ^ int.from_bytes(os.urandom(2), "little")) & 0x7FFF)


def step_trace_id(rank: int, step: int) -> int:
    """Deterministic 64-bit per-step id: rank in the high 16 bits, step
    counter below — collision-free across ranks because each sender only
    ever mints ids in its own rank-space."""
    return ((int(rank) & 0xFFFF) << 48) | (int(step) & 0xFFFFFFFFFFFF)


def pass_trace_id(rank: int, pass_index: int) -> int:
    """Per-pass id: bit 61 set, rank (13 bits) at 48, pass counter below.
    Every span of one pass carries it (the boundary on the main thread,
    the parse of pass N+1 on the reader threads while pass N trains, the
    chunk stager), so a pass's spans are told apart by id, not by time.
    Bit 61 alone collides with no other mint: step ids leave 61-63 clear
    for ranks below 8192, mesh frames set 62, request ids set 63."""
    return ((1 << 61) | ((int(rank) & 0x1FFF) << 48)
            | (int(pass_index) & 0xFFFFFFFFFFFF))


def next_trace_id() -> int:
    """Per-request id for planes without a step counter (serving client
    pulls): process-salted monotonic counter, high bit set so the id
    space never collides with step_trace_id's rank<<48 layout."""
    return ((1 << 63) | (_REQ_SALT << 48)
            | (next(_NEXT_REQ) & 0xFFFFFFFFFFFF))


def current_trace() -> Optional[int]:
    return getattr(_TRACE_CTX, "id", None)


def set_trace(trace: Optional[int]) -> Optional[int]:
    """Set this thread's current trace id; returns the previous one."""
    prev = getattr(_TRACE_CTX, "id", None)
    _TRACE_CTX.id = trace
    return prev


def with_current_trace(fn):
    """``fn`` as a thread target that runs under THIS thread's current
    trace id: a worker started for a pass (dataset readers, the promote
    prefetcher, the chunk stager) records its spans under the pass it
    serves, not under whatever the main thread has moved on to."""
    trace = current_trace()

    def run(*args, **kwargs):
        set_trace(trace)
        return fn(*args, **kwargs)
    return run


class trace_ctx:
    """``with trace_ctx(tid): ...`` — spans inside carry ``tid``.
    Restores the previous id on exit (nesting-safe)."""

    __slots__ = ("_id", "_prev")

    def __init__(self, trace: Optional[int]) -> None:
        self._id = trace

    def __enter__(self):
        self._prev = set_trace(self._id)
        return self._id

    def __exit__(self, *exc):
        set_trace(self._prev)
        return False


class _ThreadRing:
    """One thread's span ring. Only its owner thread writes; readers
    (export, watchdog dump) take a best-effort snapshot — a torn slot
    under concurrent wrap is an acceptable trade for zero locking on the
    record path."""

    __slots__ = ("buf", "idx", "cap", "tid", "tname", "owner")

    def __init__(self, cap: int, tid: int, tname: str, owner) -> None:
        self.buf: List[Optional[Tuple[str, float, float,
                                      Optional[int]]]] = [None] * cap
        self.idx = 0
        self.cap = cap
        self.tid = tid
        self.tname = tname
        self.owner = owner      # weakref to the owning thread

    def record(self, name: str, t0: float, t1: float,
               trace: Optional[int] = None) -> None:
        i = self.idx
        self.buf[i % self.cap] = (name, t0, t1, trace)
        self.idx = i + 1

    def spans(self) -> List[Tuple[str, float, float, Optional[int]]]:
        """Oldest-first snapshot of the live slots."""
        i, cap = self.idx, self.cap
        if i <= cap:
            out = self.buf[:i]
        else:
            cut = i % cap
            out = self.buf[cut:] + self.buf[:cut]
        return [s for s in out if s is not None]


class _NullSpan:
    """Reusable no-op context manager for the disabled path. Its stamps
    read 0.0: a caller that accounts a span's width (``with span(..) as
    s: ...; s.t1 - s.t0``) accounts nothing while tracing is off."""

    __slots__ = ()
    t0 = t1 = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    """One live span. ``t0`` and ``t1`` are the perf_counter pair the ring
    holds, handed back so that a counter taken from a span and the span
    itself agree (``with span(..) as s`` then ``s.t0``, ``s.t1``)."""

    __slots__ = ("_tr", "name", "t0", "t1", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str) -> None:
        self._tr = tracer
        self.name = name

    def __enter__(self):
        ann = _JAX_ANNOTATE
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = t1 = time.perf_counter()
        self._tr._ring().record(self.name, self.t0, t1,
                                getattr(_TRACE_CTX, "id", None))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class SpanTracer:
    """Registry of per-thread rings + chrome-trace export."""

    # dead threads' rings retained (newest-first) so a trace exported
    # after a pass still carries its finished stager/producer threads'
    # spans; older ones are pruned at the next thread registration —
    # a job running thousands of passes (one short-lived thread each)
    # must not accumulate dead 4096-slot rings forever
    MAX_DEAD_RINGS = 32

    def __init__(self, capacity: int = 4096) -> None:
        self.capacity = int(capacity)
        self.enabled = True
        self._rings: List[_ThreadRing] = []   # guarded-by: _reg_lock
        # RLock, not Lock: the flight recorder's fatal-signal seal path
        # reads last_spans() from the signal handler, which may interrupt
        # this very thread mid-all_spans() — a plain lock would deadlock
        # the dying process instead of sealing and re-delivering
        self._reg_lock = make_rlock("SpanTracer._reg_lock")
        self._local = threading.local()

    def _ring(self) -> _ThreadRing:
        r = getattr(self._local, "ring", None)
        if r is None:
            t = threading.current_thread()
            r = _ThreadRing(self.capacity, t.ident or 0, t.name,
                            weakref.ref(t))
            self._local.ring = r
            with self._reg_lock:
                # registration is rare (once per thread): keep the
                # newest MAX_DEAD_RINGS dead-thread rings, prune older
                dead = [x for x in self._rings
                        if (th := x.owner()) is None or not th.is_alive()]
                if len(dead) > self.MAX_DEAD_RINGS:
                    drop = {id(x) for x in dead[:-self.MAX_DEAD_RINGS]}
                    self._rings = [x for x in self._rings
                                   if id(x) not in drop]
                self._rings.append(r)
        return r

    def span(self, name: str):
        """Context manager timing one named region on this thread. The
        disabled path is one attribute read + one identity return."""
        if not self.enabled:
            return _NULL
        return _Span(self, name)

    def record_span(self, name: str, t0: float, t1: float,
                    trace: Optional[int] = None) -> None:
        """Post-hoc span from perf_counter stamps the caller already
        took (sites that time a region anyway record it span-free).
        An explicit ``trace`` (receiver-side spans tagging the SENDER's
        id) wins over this thread's current trace context."""
        if self.enabled:
            if trace is None:
                trace = getattr(_TRACE_CTX, "id", None)
            self._ring().record(name, t0, t1, trace)

    def clear(self) -> None:
        with self._reg_lock:
            self._rings = []
        # each thread lazily re-registers a fresh ring (its old one is
        # unreachable from the registry, so export never sees it again);
        # this thread's cache is dropped eagerly
        self._local = threading.local()

    # ------------------------------------------------------------- readers
    def all_spans(self) -> List[Tuple[str, int, str, float, float,
                                      Optional[int]]]:
        """(name, tid, thread_name, t0, t1, trace) across every thread,
        t0-sorted; trace is None for spans recorded outside a trace
        context."""
        with self._reg_lock:
            rings = list(self._rings)
        out = []
        for r in rings:
            for name, t0, t1, trace in r.spans():
                out.append((name, r.tid, r.tname, t0, t1, trace))
        out.sort(key=lambda s: s[3])
        return out

    def last_spans(self, k: int = 64) -> List[Tuple[str, int, str, float,
                                                    float, Optional[int]]]:
        return self.all_spans()[-k:]

    def export_chrome(self, path: Optional[str] = None, pid: int = 0,
                      meta: Optional[Dict] = None) -> dict:
        """Chrome-tracing JSON (the chrometracing_logger role): complete
        ("X") events in µs since process epoch plus thread-name metadata,
        loadable in Perfetto / chrome://tracing. Returns the document;
        writes it to `path` when given."""
        events = []
        seen_tids = set()
        for name, tid, tname, t0, t1, trace in self.all_spans():
            if tid not in seen_tids:
                seen_tids.add(tid)
                events.append({"ph": "M", "name": "thread_name", "pid": pid,
                               "tid": tid, "args": {"name": tname}})
            ev = {
                "ph": "X", "cat": "obs", "name": name, "pid": pid,
                "tid": tid,
                "ts": round((t0 - _EPOCH) * 1e6, 3),
                "dur": round((t1 - t0) * 1e6, 3),
            }
            if trace is not None:
                # hex STRING, not int: 64-bit ids exceed the 2^53 range
                # json numbers survive in every consumer
                ev["args"] = {"trace": "0x%016x" % (trace & (2**64 - 1))}
            events.append(ev)
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               # wall-clock instant of ts=0 on THIS process — the anchor
               # tools/trace_stitch.py aligns per-rank traces with
               "metadata": {"rank": pid,
                            "clock_origin_unix_s": _EPOCH_UNIX}}
        if meta:
            doc["metadata"].update(dict(meta))
        if path:
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(doc, fh)
        return doc


# ---------------------------------------------------------------- module API
_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    return _TRACER


def span(name: str):
    """``with obs.span("h2d_stage"): ...`` — the one-liner every hot path
    uses. Near-free when tracing is disabled."""
    if not _TRACER.enabled:
        return _NULL
    return _Span(_TRACER, name)


def record_span(name: str, t0: float, t1: float,
                trace: Optional[int] = None) -> None:
    _TRACER.record_span(name, t0, t1, trace)


def configure_from_flags() -> None:
    """Sync the module tracer with the obs_trace / obs_trace_capacity
    flags (called by the trainers at construction; safe to call often)."""
    from paddlebox_tpu.config import flags
    _TRACER.enabled = bool(flags.get_flag("obs_trace"))
    cap = int(flags.get_flag("obs_trace_capacity"))
    if cap > 0 and cap != _TRACER.capacity:
        _TRACER.capacity = cap
        _TRACER.clear()
