"""Runtime lock-order validator — the dynamic twin of boxlint's BX7xx.

The static pass (tools/boxlint/lockorder.py) proves properties about
``Class._attr`` *identities* with instances conflated and unresolvable
calls invisible; this module watches the orders that actually happen.
Behind flag ``debug_lock_order`` the package's locks are constructed
through :func:`make_lock` / :func:`make_rlock`, which

  * record the per-thread acquisition stack (thread-local, no shared
    state on the acquire hot path beyond one registry lock hold per
    FIRST-seen nesting pair),
  * maintain the global nesting-order graph in the same
    ``Class._attr`` vocabulary the static pass emits into
    ``tools/boxlint/lock_graph.txt`` — so a dynamic edge can be checked
    against the committed static inventory by eye,
  * flag an INVERSION the moment some thread acquires B-then-A after
    any thread ever acquired A-then-B (the AB/BA deadlock precondition —
    caught on the first interleaving that *could* deadlock, not the
    unlucky run that does), logging it loudly once per pair and counting
    ``lockwatch_inversions`` in the StatRegistry,
  * publish hold-time histograms ``lock_hold_us_<name>`` through the
    existing obs StatRegistry fixed-bucket machinery (report windows and
    cluster aggregation ride along for free).

When the flag is off (default) the factories return plain
``threading.Lock``/``RLock`` objects — a construction-time branch, zero
per-acquire cost (tests/test_lockwatch.py asserts the type identity).

The StatRegistry's own ``_lock`` is deliberately NEVER watched: the
release path publishes hold-time samples INTO the registry, so watching
the registry's lock would recurse release→observe→acquire forever.

Tests/suites: ``assert_consistent()`` raises on any recorded inversion;
the hostplane / serving swap-hammer / flight-seal suites run with the
flag on and assert it at teardown (tests/test_lockwatch.py seeds a toy
AB/BA pair and pins detection).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = ["enabled", "make_lock", "make_rlock", "reset", "edges",
           "inversions", "assert_consistent", "order_report",
           "order_cycles", "current_held"]


def enabled() -> bool:
    try:
        from paddlebox_tpu.config import flags
        return bool(flags.get_flag("debug_lock_order"))
    except Exception:  # rationale: flags registry absent during early
        # import / stripped deployments — the watch must fail OPEN to
        # plain locks, never break lock construction
        return False


class _Watch:
    """Process-global order graph + inversion record."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._edges: Dict[Tuple[str, str], int] = {}  # guarded-by: _lock
        self._inversions: List[dict] = []             # guarded-by: _lock
        self._warned: set = set()                     # guarded-by: _lock
        self._tls = threading.local()
        # every thread's stack, so clear() can empty them all — a foreign
        # release (lock handed across threads) otherwise leaves a phantom
        # "held" entry that fabricates edges forever after
        self._stacks: List[List[Tuple[str, float]]] = []  # guarded-by: _lock

    # ------------------------------------------------------------ tls stack
    def _held(self) -> List[Tuple[str, float]]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
            with self._lock:
                self._stacks.append(held)
        return held

    # ------------------------------------------------------------- events
    def on_acquired(self, name: str) -> None:
        held = self._held()
        names = [n for n, _ in held]
        if names and name not in names:   # reentrant re-entry: no edge
            with self._lock:
                for h in names:
                    pair = (h, name)
                    first = pair not in self._edges
                    self._edges[pair] = self._edges.get(pair, 0) + 1
                    if first and (name, h) in self._edges:
                        self._record_inversion_locked(pair)
        held.append((name, time.perf_counter()))

    def on_released(self, name: str) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] == name:
                _, t0 = held.pop(i)
                self._observe_hold(name, time.perf_counter() - t0)
                return
        # release of a lock this thread never acquired through the
        # wrapper (e.g. handed across threads) — count it, don't crash
        from paddlebox_tpu.utils.stats import stat_add
        stat_add("lockwatch_foreign_release")

    def _record_inversion_locked(self, pair: Tuple[str, str]) -> None:  # boxlint: disable=BX401 — caller holds _lock (the *_locked contract)
        key = tuple(sorted(pair))
        self._inversions.append({
            "pair": pair, "thread": threading.current_thread().name,
            "stack_names": [n for n, _ in self._held()]})
        from paddlebox_tpu.utils.stats import stat_add
        stat_add("lockwatch_inversions")
        if key not in self._warned:
            self._warned.add(key)
            try:
                from paddlebox_tpu.obs import log
                log.error(
                    "LOCK-ORDER INVERSION: %s acquired while holding %s, "
                    "but the opposite nesting was also observed — AB/BA "
                    "deadlock precondition" % (pair[1], pair[0]),
                    thread=threading.current_thread().name)
            except Exception:  # rationale: inversion reporting must never
                # take down the locking it observes; the counter + record
                # above already carry the finding
                pass

    def _observe_hold(self, name: str, secs: float) -> None:
        from paddlebox_tpu.utils.stats import hist_observe
        hist_observe("lock_hold_us_%s" % name.replace(".", "_"),
                     secs * 1e6)

    # -------------------------------------------------------------- queries
    def snapshot_edges(self) -> Dict[Tuple[str, str], int]:
        with self._lock:
            return dict(self._edges)

    def snapshot_inversions(self) -> List[dict]:
        with self._lock:
            return list(self._inversions)

    def clear(self) -> None:
        """Test-isolation reset: callers quiesce their threads first —
        emptying a stack out from under a thread mid-critical-section
        would only skew that lock's hold-time sample."""
        with self._lock:
            self._edges.clear()
            self._inversions.clear()
            self._warned.clear()
            for s in self._stacks:
                del s[:]


_WATCH = _Watch()


class _WatchedLock:
    """threading.Lock/RLock wrapper reporting to the watch. Supports the
    full context-manager + acquire/release + ``Condition(lock)`` surface
    for BOTH kinds: the Condition protocol methods (``_is_owned``,
    ``_release_save``, ``_acquire_restore``) are implemented here with
    watch bookkeeping, because hiding the inner RLock's versions would
    make ``Condition(make_rlock(...)).wait`` misbehave exactly and only
    when the debug flag is on — a debug flag must never change
    semantics."""

    __slots__ = ("_name", "_inner")

    def __init__(self, name: str, inner) -> None:
        self._name = name
        self._inner = inner

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            _WATCH.on_acquired(self._name)
        return ok

    def release(self) -> None:
        _WATCH.on_released(self._name)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    # ---- Condition(lock) protocol (threading.Condition duck types) ----
    def _is_owned(self) -> bool:
        inner_io = getattr(self._inner, "_is_owned", None)
        if inner_io is not None:
            return inner_io()
        # plain Lock: Condition's own default probe, mirrored so it
        # rides the INNER lock without fabricating watch events
        if self._inner.acquire(False):
            self._inner.release()
            return False
        return True

    def _release_save(self):
        rs = getattr(self._inner, "_release_save", None)
        if rs is None:        # plain Lock: one level, through release()
            self.release()
            return None
        # RLock: full recursive release — pop every held level
        levels = max(1, sum(1 for n, _ in _WATCH._held()
                            if n == self._name))
        state = rs()
        for _ in range(levels):
            _WATCH.on_released(self._name)
        return (state, levels)

    def _acquire_restore(self, state) -> None:
        if state is None:     # plain Lock
            self.acquire()
            return
        inner_state, levels = state
        self._inner._acquire_restore(inner_state)
        for _ in range(levels):
            _WATCH.on_acquired(self._name)

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return f"<WatchedLock {self._name} {self._inner!r}>"


def make_lock(name: str) -> threading.Lock:
    """A mutex registered under ``name`` (use the static identity
    vocabulary: ``Class._attr``). Plain ``threading.Lock`` when
    ``debug_lock_order`` is off — zero added cost."""
    if not enabled():
        return threading.Lock()
    return _WatchedLock(name, threading.Lock())


def make_rlock(name: str) -> threading.RLock:
    """Reentrant variant of :func:`make_lock`. Reentrant re-acquisition
    records no self-edge (the held-stack dedups by name)."""
    if not enabled():
        return threading.RLock()
    return _WatchedLock(name, threading.RLock())


# ----------------------------------------------------------------- queries

def edges() -> Dict[Tuple[str, str], int]:
    """(outer, inner) -> times observed, across all threads so far."""
    return _WATCH.snapshot_edges()


def inversions() -> List[dict]:
    return _WATCH.snapshot_inversions()


def current_held() -> List[str]:
    """Names this thread currently holds (outermost first)."""
    return [n for n, _ in _WATCH._held()]


def reset() -> None:
    """Drop all recorded edges/inversions (test isolation)."""
    _WATCH.clear()


def order_cycles() -> List[List[str]]:
    """Cycles in the observed nesting graph, each as a node list. AB/BA
    pairs surface eagerly as inversions; cycles of length >= 3 (A->B,
    B->C, C->A — every pair individually consistent) only exist in the
    graph view, so the consistency check must walk it: this is the same
    deadlock precondition the static twin's Tarjan pass (BX701) flags."""
    graph: Dict[str, List[str]] = {}
    for (a, b) in _WATCH.snapshot_edges():
        graph.setdefault(a, []).append(b)
        graph.setdefault(b, [])
    cycles: List[List[str]] = []
    color: Dict[str, int] = {}   # 0/absent=white, 1=on stack, 2=done

    def dfs(v: str, path: List[str]) -> None:
        color[v] = 1
        path.append(v)
        for w in sorted(graph[v]):
            if color.get(w, 0) == 1:
                cycles.append(path[path.index(w):] + [w])
            elif color.get(w, 0) == 0:
                dfs(w, path)
        path.pop()
        color[v] = 2

    for v in sorted(graph):
        if color.get(v, 0) == 0:
            dfs(v, [])
    return cycles


def assert_consistent() -> None:
    """Raise AssertionError when any AB/BA inversion was observed OR the
    nesting graph contains a cycle (length >= 3 cycles never trip the
    eager pairwise check — see order_cycles)."""
    inv = _WATCH.snapshot_inversions()
    if inv:
        lines = ", ".join("%s after %s (thread %s)"
                          % (i["pair"][1], i["pair"][0], i["thread"])
                          for i in inv[:5])
        raise AssertionError(
            f"lock-order inversions observed ({len(inv)}): {lines}")
    cycles = order_cycles()
    if cycles:
        shown = "; ".join(" -> ".join(c) for c in cycles[:3])
        raise AssertionError(
            f"lock-order cycle(s) observed ({len(cycles)}): {shown}")


def order_report() -> str:
    """Human-readable dynamic nesting inventory (the runtime twin of
    tools/boxlint/lock_graph.txt)."""
    es = _WATCH.snapshot_edges()
    lines = [f"{a} -> {b} x{n}" for (a, b), n in sorted(es.items())]
    inv = _WATCH.snapshot_inversions()
    lines.append(f"# {len(es)} edges, {len(inv)} inversions")
    return "\n".join(lines)
