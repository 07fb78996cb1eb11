"""Device plane: the obs tier that watches the XLA/device layer.

Every other obs tier (spans, StepReports, flight, health, /metrics)
watches the HOST. The two open perf mysteries live BELOW it: the
>=4M-row regime step is hypothesized to be a donation-miss slab copy
(tools/regime_step_probe.py measured the 1.36x fresh-vs-donated gap),
and every roofline claim rests on one-shot offline runs of
tools/step_audit.py. This module makes the device layer continuously
observable through the UNCHANGED publication machinery:

  * instrument_jit(fn, name, donate_argnums=...) — the one wrapper every
    jit entry point goes through (boxlint BX901 enforces it). Per
    function it keeps compile count + compile wall time, a one-time
    cost_analysis()/memory_analysis() snapshot (the step_audit math,
    shared — see analyze_compiled), and a RECOMPILE SENTINEL: a
    steady-state recompile (same name, more compiles than the
    device_recompile_warmup allowance — shape/dtype churn from a
    mis-staged batch) bumps the ``device_recompiles`` stat, logs loudly
    once per fn, and turns the rank unhealthy through HealthMonitor.
  * donation audit — for donated entry points the wrapper compares the
    donated buffers' unsafe_buffer_pointer() against the outputs'
    (backend-guarded): a donated buffer that did NOT come back as an
    output was copied, not aliased — the regime-step mechanism — and
    bumps the ``donation_miss`` stat. The count is DEBOUNCED per
    executable: a miss is recorded only when the same executable's
    previous audited call also missed. The pass's first step donates
    the host-STAGED slab — a buffer jax zero-copied from numpy memory,
    which cannot be aliased in place and is copied exactly once
    (measured 100% on the CPU backend; alignment-dependent, hence
    flaky without the debounce) — while the regime the alarm exists
    for is the recurring per-step copy, which is counted from its
    second consecutive call. Buffers below device_donation_min_bytes
    are not audited (tiny buffers are aliasing noise; the alarm exists
    for slab-scale copies).
  * compile spans — every instrumented compile records a ring span
    ``device_compile``; one jax.monitoring listener records EVERY
    backend compile of the process (instrumented or not: an eager
    ``slab[idx]`` at a pass boundary is seven small programs for every
    new length of ``idx``, which no entry would show) as a ring span
    ``backend_compile`` and counts ``device_backend_compiles``. It adds
    nothing to the entries: their ``compiles`` stay the instrumented
    entry points' own.
  * scope map — at an entry's first compile, ``scopes``: {HLO
    instruction name: innermost of SCOPE_NAMES on its op_name path}
    and ``module`` (the XLA module's name) from compiled.as_text(). A
    device trace names a program by its module and an operation by its
    HLO instruction, not by the jax.named_scope it was traced under;
    these join the two (tools/scope_times.py).
  * transfer ledger — account_h2d/account_d2h: the runners' staging and
    write-back paths count ``device_transfer_bytes_{h2d,d2h}`` and feed
    the ``device_{h2d,d2h}_bytes`` fixed-bucket histograms.
  * HBM/live-buffer ledger — sample_ledger() buckets jax.live_arrays()
    by registered logical owner (slab / dense params / opt state /
    other) into gauges at report cadence, with a monotonic-growth leak
    detector across samples (``device_leak_suspect``).

Everything lands in the StatRegistry, so StepReports carry the deltas,
/metrics exports the series, the flight recorder seals a device
snapshot, cluster aggregation min/med/max's them at rank 0, and the
/device endpoint serves snapshot() live.

Mechanism: the wrapper runs jax.jit through the explicit AOT path —
lower().compile() once per (pytree structure, shape, dtype) signature,
cached here — so compile COUNT and WALL TIME are exact (not inferred
from call latency) and the cost/memory analyses come free with the
executable instead of a second compile. Dispatch cost against the C++
jit fast path is not measured on the chip; instrumented-vs-bare
bit-parity on the e2e trainer is pinned by tests/test_device_obs.py. Signature keying is CONSERVATIVE: python
scalar args re-key by value (jax.jit would retrace only on dtype
change) — none of the instrumented entry points take bare scalars, and
a finer key can only add a counted compile, never reuse a wrong
executable.

Import surface is jax-free (the obs contract): jax is imported lazily
at wrapper construction and ledger sampling, both of which only happen
in jax-using processes. Flag ``device_obs`` off returns bare jax.jit —
the zero-risk escape hatch.
"""

from __future__ import annotations

import inspect
import re
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from paddlebox_tpu.obs.tracer import record_span
from paddlebox_tpu.utils.lockwatch import make_lock, make_rlock
from paddlebox_tpu.utils.stats import (gauge_set, hist_observe, stat_add,
                                       stat_peek)

SCHEMA_VERSION = 1

#: compiled-executable signatures retained per instrumented fn (LRU):
#: far above any legitimate signature count; under pathological shape
#: churn the sentinel fires long before the cache evicts.
MAX_SIGNATURES = 32


def _warn(msg: str, **fields) -> None:
    # lazy: obs/__init__ imports this module; importing log at module
    # scope mid-package-init would be order-sensitive
    from paddlebox_tpu.obs import log as obs_log
    obs_log.warning(msg, **fields)


# --------------------------------------------------------- shared analysis

def analyze_compiled(compiled, examples: Optional[int] = None,
                     slab_bytes: Optional[int] = None) -> dict:
    """The ONE copy of the compiled-step cost/memory math (tools/
    step_audit.py refactors onto this; the instrument_jit snapshot uses
    it too). Best-effort per backend: analysis failures land as error
    strings, never raise.

      examples   — examples one call processes; adds *_per_example
                   (cost_analysis counts a scan BODY once = one batch,
                   so scan callers pass the batch size, not chunk*batch)
      slab_bytes — donated slab size; adds temp_includes_slab_copy (the
                   donated slab must never reappear as a temp copy)
    """
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        if ca:
            out["flops"] = float(ca.get("flops", 0.0))
            out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
            if examples:
                out["flops_per_example"] = round(out["flops"] / examples)
                out["bytes_accessed_per_example"] = round(
                    out["bytes_accessed"] / examples)
    except Exception as e:  # noqa: BLE001 — analysis is best-effort per backend
        out["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        out["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", -1))
        out["arg_bytes"] = int(getattr(ma, "argument_size_in_bytes", -1))
        out["output_bytes"] = int(getattr(ma, "output_size_in_bytes", -1))
        out["alias_bytes"] = int(getattr(ma, "alias_size_in_bytes", -1))
        if slab_bytes and out["temp_bytes"] >= 0:
            out["temp_includes_slab_copy"] = bool(
                out["temp_bytes"] >= int(slab_bytes))
    except Exception as e:  # noqa: BLE001
        out["memory_analysis_error"] = repr(e)
    return out


# -------------------------------------------------------------- scope map

#: the jax.named_scope names of the program's device phases: the train
#: step's (train/trainer.py, ops/, embedding/optimizers.py) and
#: delta_promote's and writeback_gather's (embedding/pass_table.py);
#: inside fwd_bwd a sequence tower's kernels (models/afmoe.py,
#: models/granite_hybrid.py, models/nemotron_h.py, ops/attention.py,
#: ops/routed_experts.py, ops/ssd.py): the innermost name on an
#: operation's path wins, and the backward pass's operations carry the
#: forward's
SCOPE_NAMES = frozenset((
    "pull", "pool", "fwd_bwd", "dense_opt", "push_grads", "push_merge",
    "push_opt", "push_write", "promote_scatter", "writeback_gather",
    "attn_window", "attn_full", "moe_route", "moe_experts", "dense_mlp",
    "ssm_proj", "ssm_conv", "ssd_scan", "ssm_gate_norm", "moe_latent",
    "moe_shared"))

_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE_WRAP = re.compile(r"^(?:\w+\()+|\)+$")  # transpose(jvp(pool)) -> pool


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name (no '%'): scope} over every instruction of a
    compiled module's text: the innermost SCOPE_NAMES component of the
    instruction's metadata op_name path (autodiff wraps a scope as
    jvp(pool) / transpose(jvp(pool)): unwrapped), "" where the path has
    none or the instruction carries no metadata. A fusion carries the
    op_name of the instruction that names it; an instruction printed over
    several lines (a Mosaic kernel's custom-call) carries its metadata on
    a later one."""
    out: Dict[str, str] = {}
    open_instr = None   # an instruction whose text runs on, no op_name yet
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        op = _HLO_OP_NAME.search(line)
        if m is None:
            # a Mosaic kernel's custom-call prints its kernel_metadata
            # over several lines, its own metadata on the last
            if open_instr is not None and op is not None:
                out[open_instr] = _innermost_scope(op.group(1))
                open_instr = None
            continue
        out[m.group(1)] = "" if op is None else _innermost_scope(op.group(1))
        open_instr = m.group(1) if op is None else None
    return out


def _innermost_scope(op_name: str) -> str:
    for part in reversed(op_name.split("/")):
        part = _SCOPE_WRAP.sub("", part)
        if part in SCOPE_NAMES:
            return part
    return ""


# ---------------------------------------------------------- compile listener

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listening = False  # jax.monitoring keeps listeners for the process's life


def _on_jax_duration(event: str, secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        t1 = time.perf_counter()
        record_span("backend_compile", t1 - secs, t1)
        stat_add("device_backend_compiles", 1)


def _listen_for_compiles() -> None:
    """Register the one backend-compile listener (idempotent)."""
    global _listening
    if not _listening:
        import jax
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)


# ------------------------------------------------------------ the monitor

class _JitEntry:
    """One instrumented entry point's device-plane record. Mutated only
    under the owning wrapper's lock; snapshot() reads are
    field-at-a-time (ints/floats/bools — torn reads are stale, never
    corrupt)."""

    def __init__(self, name: str, donate_argnums: Tuple[int, ...],
                 audit_argnums: Tuple[int, ...]) -> None:
        self.name = name
        self.donate_argnums = donate_argnums
        self.audit_argnums = audit_argnums
        self.compiles = 0
        self.compile_ms_total = 0.0
        self.last_compile_ms = 0.0
        self.steady_recompiles = 0
        self.recompile_flagged = False
        self.donation_checks = 0
        self.donation_misses = 0
        self.donation_flagged = False
        # True (assumed until a pointer read fails; `checks` says whether
        # any call actually verified) / False (nothing to audit) /
        # "unsupported:<err>" (backend without buffer-pointer introspection
        # — e.g. sharded arrays; the audit disables itself for this fn)
        self.donation_supported: Any = bool(audit_argnums)
        self.analysis: Optional[dict] = None
        self.module: Optional[str] = None
        self.scopes: Optional[Dict[str, str]] = None
        self.donated_bytes = 0
        self.signatures = 0

    def snapshot(self) -> dict:
        d = {"compiles": self.compiles,
             "compile_ms": round(self.compile_ms_total, 3),
             "last_compile_ms": round(self.last_compile_ms, 3),
             "signatures": self.signatures,
             "steady_recompiles": self.steady_recompiles,
             "recompile_flagged": self.recompile_flagged,
             "donate_argnums": list(self.donate_argnums)}
        if self.audit_argnums:
            d["donation"] = {"checks": self.donation_checks,
                             "misses": self.donation_misses,
                             "supported": self.donation_supported,
                             "donated_bytes": self.donated_bytes}
        if self.analysis is not None:
            d["analysis"] = dict(self.analysis)
        if self.scopes is not None:
            d["module"] = self.module
            d["scopes"] = dict(self.scopes)
        return d


class DeviceMonitor:
    """Process-global registry of instrumented entry points + owner
    getters + the live-buffer ledger state."""

    def __init__(self) -> None:
        # REENTRANT: the fatal-signal flight seal calls snapshot() from a
        # handler that may have interrupted this same thread inside
        # register()/sample_ledger() — a plain lock would deadlock the
        # DYING process instead of sealing (the PR-9 tracer._reg_lock
        # class); make_rlock keeps it visible to debug_lock_order
        self._lock = make_rlock("DeviceMonitor._lock")
        self._entries: Dict[str, _JitEntry] = {}  # guarded-by: _lock
        self._owners: Dict[str, Callable[[], Any]] = {}  # guarded-by: _lock
        self._ledger: Optional[dict] = None  # guarded-by: _lock
        self._growth_streak = 0  # guarded-by: _lock
        self._streak_base = 0  # guarded-by: _lock
        self._prev_total: Optional[int] = None  # guarded-by: _lock

    # -------------------------------------------------------------- entries
    def register(self, entry: _JitEntry) -> None:
        """A fresh wrapper REPLACES the entry under its name (a rebuilt
        trainer starts a fresh compile budget; global stats stay
        cumulative)."""
        with self._lock:
            self._entries[entry.name] = entry

    @property
    def active(self) -> bool:
        with self._lock:
            return bool(self._entries or self._owners)

    # --------------------------------------------------------------- owners
    def register_owner(self, name: str, getter: Callable[[], Any]) -> None:
        """Logical buffer owner for the HBM ledger: getter() returns the
        owner's current array/pytree (or None). Getters must hold weak
        references to their runner — registration must not extend its
        lifetime (the ledger would then CAUSE the leak it detects)."""
        with self._lock:
            self._owners[name] = getter

    def clear_owners(self) -> None:
        with self._lock:
            self._owners.clear()

    # --------------------------------------------------------------- ledger
    def sample_ledger(self) -> Optional[dict]:
        """Bucket jax.live_arrays() by registered owner into gauges +
        run the monotonic-growth leak detector. No-op (None) in a
        process that never imported jax."""
        import sys
        if "jax" not in sys.modules:
            return None
        import jax
        with self._lock:
            owners = dict(self._owners)
        owner_of: Dict[int, str] = {}
        for name, getter in owners.items():
            try:
                tree = getter()
            except Exception:  # noqa: BLE001 — a dead runner's getter must not kill reporting
                continue
            if tree is None:
                continue
            for leaf in jax.tree_util.tree_leaves(tree):
                owner_of[id(leaf)] = name
        buckets: Dict[str, int] = {name: 0 for name in owners}
        buckets["other"] = 0
        total = 0
        count = 0
        try:
            live = jax.live_arrays()
        except Exception:  # noqa: BLE001 — backend-guarded (no live-array introspection)
            return None
        for arr in live:
            nb = int(getattr(arr, "nbytes", 0) or 0)
            total += nb
            count += 1
            buckets[owner_of.get(id(arr), "other")] += nb
        sample = {"ts": time.time(), "total_bytes": total, "arrays": count,
                  "owners": buckets}
        gauge_set("device_live_bytes_total", float(total))
        gauge_set("device_live_arrays", float(count))
        for name, nb in buckets.items():
            gauge_set("device_live_bytes_" + name, float(nb))
        self._leak_check(total, sample)
        with self._lock:
            self._ledger = sample
        return sample

    def _leak_check(self, total: int, sample: dict) -> None:
        from paddlebox_tpu.config import flags
        windows = int(flags.get_flag("device_leak_windows"))
        min_bytes = int(flags.get_flag("device_leak_min_bytes"))
        fire = False
        with self._lock:
            prev = self._prev_total
            self._prev_total = total
            if prev is None or total <= prev:
                self._growth_streak = 0
                self._streak_base = total
            else:
                if self._growth_streak == 0:
                    self._streak_base = prev
                self._growth_streak += 1
                if (self._growth_streak >= windows
                        and total - self._streak_base >= min_bytes):
                    fire = True
                    grew = total - self._streak_base
                    streak = self._growth_streak
                    # a fired streak restarts — one alarm per sustained
                    # climb, not one per additional window
                    self._growth_streak = 0
                    self._streak_base = total
        if fire:
            stat_add("device_leak_suspect", 1)
            sample["leak_suspect"] = True
            _warn("device live-buffer ledger: monotonic growth — "
                  "possible leaked device array",
                  windows=streak, grew_bytes=grew,
                  total_bytes=total)

    # ------------------------------------------------------------- snapshot
    def snapshot(self) -> dict:
        with self._lock:
            entries = {n: e.snapshot() for n, e in self._entries.items()}
            ledger = dict(self._ledger) if self._ledger else None
        # stat_peek, not stat_get: this runs inside the fatal-signal
        # flight seal, which may have interrupted stat_add mid-hold on
        # the registry's plain lock — a locked read would self-deadlock
        return {
            "type": "device_plane", "v": SCHEMA_VERSION,
            "active": bool(entries or ledger),
            "entries": entries,
            "transfers": {
                "h2d_bytes": stat_peek("device_transfer_bytes_h2d"),
                "d2h_bytes": stat_peek("device_transfer_bytes_d2h"),
            },
            "recompiles": stat_peek("device_recompiles"),
            "donation_miss": stat_peek("donation_miss"),
            "leak_suspect": stat_peek("device_leak_suspect"),
            "ledger": ledger,
        }

    def reset(self) -> None:
        """Test isolation: forget entries/owners/ledger state (the
        StatRegistry is reset separately by the conftest fixture)."""
        with self._lock:
            self._entries.clear()
            self._owners.clear()
            self._ledger = None
            self._growth_streak = 0
            self._streak_base = 0
            self._prev_total = None


_MONITOR = DeviceMonitor()


def monitor() -> DeviceMonitor:
    return _MONITOR


def snapshot() -> dict:
    return _MONITOR.snapshot()


def register_owner(name: str, getter: Callable[[], Any]) -> None:
    _MONITOR.register_owner(name, getter)


def sample_ledger() -> Optional[dict]:
    return _MONITOR.sample_ledger()


def on_report() -> None:
    """StepReport assembly hook (obs/report.py): sample the live-buffer
    ledger at report cadence. Near-free when the device plane is idle
    (serving replicas, jax-free processes)."""
    if _MONITOR.active:
        _MONITOR.sample_ledger()


# ----------------------------------------------------------- transfer ledger

def account_h2d(nbytes: int) -> None:
    """One host→device staging transfer (bytes). Counter + histogram —
    the StepReport window carries the delta, /metrics the series."""
    n = int(nbytes)
    if n > 0:
        stat_add("device_transfer_bytes_h2d", n)
        hist_observe("device_h2d_bytes", n)


def account_d2h(nbytes: int) -> None:
    """One device→host write-back/extraction transfer (bytes)."""
    n = int(nbytes)
    if n > 0:
        stat_add("device_transfer_bytes_d2h", n)
        hist_observe("device_d2h_bytes", n)


def tree_nbytes(tree) -> int:
    """Total array bytes of a host pytree (dict/tuple of numpy arrays) —
    the staging paths' one-line accounting helper. jax-free: walks
    plain containers, reads .nbytes."""
    total = 0
    stack = [tree]
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
        else:
            total += int(getattr(x, "nbytes", 0) or 0)
    return total


# ------------------------------------------------------------ instrument_jit

def _leaf_sig(leaf):
    dt = getattr(leaf, "dtype", None)
    if dt is not None:
        # sharding is part of the executable's input contract: an AOT
        # Compiled REJECTS a same-shape array with a different sharding
        # (where the C++ jit path would recompile), so it must re-key —
        # the 8-virtual-device test mesh exercises this on every runner
        return (leaf.shape, dt, getattr(leaf, "weak_type", False),
                getattr(leaf, "sharding", None))
    # non-array leaf (python scalar / hashable static object): key by
    # VALUE — conservative vs jax.jit (see module docstring)
    return (type(leaf), leaf)


class InstrumentedJit:
    """jax.jit twin with the device plane attached. Call convention,
    donation semantics and results are identical to jax.jit(fn, ...)
    (bit-parity pinned by tests); .lower() passes through for AOT
    consumers (tools/step_audit.py)."""

    def __init__(self, fn: Callable, name: str,
                 donate_argnums: Tuple[int, ...] = (),
                 static_argnums: Tuple[int, ...] = (),
                 static_argnames: Tuple[str, ...] = (),
                 audit_argnums: Optional[Tuple[int, ...]] = None,
                 example_count: Optional[int] = None,
                 recompile_warmup: Optional[int] = None,
                 **jit_kwargs) -> None:
        import jax
        self._fn = fn
        self.name = str(name)
        self._tree_flatten = jax.tree_util.tree_flatten
        self._tree_leaves = jax.tree_util.tree_leaves
        self._tracer_cls = jax.core.Tracer
        kw = dict(jit_kwargs)
        if donate_argnums:
            kw["donate_argnums"] = donate_argnums
        if static_argnums:
            kw["static_argnums"] = static_argnums
        if static_argnames:
            kw["static_argnames"] = static_argnames
        # boxlint: disable=BX901 — this IS the instrumentation layer
        self._jitted = jax.jit(fn, **kw)
        self._example_count = example_count
        self._recompile_warmup = recompile_warmup
        # AOT Compiled objects are called with the DYNAMIC args only
        # (statics are baked into the executable) — resolve static
        # names to positions once so dispatch can strip them
        self._static_argnames = tuple(static_argnames)
        static_pos = set(static_argnums)
        if static_argnames:
            try:
                names = list(inspect.signature(fn).parameters)
                for nm in static_argnames:
                    if nm in names:
                        static_pos.add(names.index(nm))
            except (TypeError, ValueError):
                pass
        self._static_pos = frozenset(static_pos)
        audit = tuple(donate_argnums) if audit_argnums is None \
            else tuple(audit_argnums)
        self._audit_argnums = audit
        self._entry = _JitEntry(self.name, tuple(donate_argnums), audit)
        self._lock = make_lock("InstrumentedJit._lock")
        self._cache: "OrderedDict[Any, Any]" = OrderedDict()
        # per-executable previous-call-missed flag (the audit debounce);
        # guarded-by: _lock, pruned with the cache
        self._last_missed: Dict[Any, bool] = {}
        _MONITOR.register(self._entry)
        _listen_for_compiles()

    # ---------------------------------------------------------- jit surface
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return self._jitted.eval_shape(*args, **kwargs)

    @property
    def __wrapped__(self):
        return self._fn

    # ------------------------------------------------------------- dispatch
    def _compile(self, key, args, kwargs):
        from paddlebox_tpu.config import flags
        t0 = time.perf_counter()
        compiled = self._jitted.lower(*args, **kwargs).compile()
        t1 = time.perf_counter()
        dt_ms = (t1 - t0) * 1e3
        record_span("device_compile", t0, t1)
        hist_observe("device_compile_ms", dt_ms)
        e = self._entry
        warmup = (self._recompile_warmup
                  if self._recompile_warmup is not None
                  else int(flags.get_flag("device_recompile_warmup")))
        with self._lock:
            self._cache[key] = compiled
            while len(self._cache) > MAX_SIGNATURES:
                old_key, _ = self._cache.popitem(last=False)
                self._last_missed.pop(old_key, None)
            e.compiles += 1
            e.compile_ms_total += dt_ms
            e.last_compile_ms = dt_ms
            e.signatures = len(self._cache)
            first = e.compiles == 1
            steady = e.compiles > max(warmup, 1)
            if steady:
                e.steady_recompiles += 1
            flag_now = steady and not e.recompile_flagged
            if flag_now:
                e.recompile_flagged = True
        if first:
            # one-time analysis snapshot: comes free with the executable
            # (the AOT path's whole point — no second compile)
            donated = 0
            for i in self._audit_argnums:
                if i < len(args):
                    donated += sum(
                        int(getattr(l, "nbytes", 0) or 0)
                        for l in self._tree_leaves(args[i]))
            e.donated_bytes = donated
            e.analysis = analyze_compiled(
                compiled, examples=self._example_count,
                slab_bytes=donated or None)
            try:
                text = compiled.as_text()
                named = _HLO_MODULE.match(text)
                e.module = named.group(1) if named else None
                e.scopes = scope_map(text)
            except Exception as err:  # noqa: BLE001 — best-effort per backend, like the analysis
                e.analysis["scopes_error"] = repr(err)
        if steady:
            # the sentinel: a recompile past warmup is shape/dtype churn
            # in what must be a steady-state loop
            stat_add("device_recompiles", 1)
        if flag_now:
            _warn("device recompile sentinel: steady-state recompile "
                  "(shape/dtype churn past warmup) — every recompile "
                  "stalls the step for a full XLA compile",
                  fn=self.name, compiles=e.compiles, warmup=warmup,
                  compile_ms=round(dt_ms, 1))
        return compiled

    def _donated_ptrs(self, args) -> Optional[set]:
        """Buffer pointers of the audited (to-be-donated) args, read
        BEFORE the call — donation deletes the input buffers, so they
        are unreadable after. None disables the check for this call
        (and, on a backend without pointer introspection, for good)."""
        from paddlebox_tpu.config import flags
        min_bytes = int(flags.get_flag("device_donation_min_bytes"))
        try:
            in_ptrs = set()
            for i in self._audit_argnums:
                if i >= len(args):
                    continue
                for leaf in self._tree_leaves(args[i]):
                    if int(getattr(leaf, "nbytes", 0) or 0) < min_bytes:
                        continue
                    in_ptrs.add(leaf.unsafe_buffer_pointer())
            return in_ptrs or None
        except Exception as e_ptr:  # noqa: BLE001 — backend without buffer pointers
            with self._lock:
                self._entry.donation_supported = (
                    "unsupported:" + repr(e_ptr)[:120])
                self._audit_argnums = ()
            return None

    def _verify_donation(self, key, in_ptrs: set, out) -> None:
        e = self._entry
        try:
            out_ptrs = set()
            for leaf in self._tree_leaves(out):
                p = getattr(leaf, "unsafe_buffer_pointer", None)
                if p is not None:
                    out_ptrs.add(p())
        except Exception as e_ptr:  # noqa: BLE001 — backend without buffer pointers
            with self._lock:
                e.donation_supported = "unsupported:" + repr(e_ptr)[:120]
                self._audit_argnums = ()
            return
        missed = in_ptrs - out_ptrs
        with self._lock:
            e.donation_supported = True
            e.donation_checks += 1
            # debounce (module docstring): an isolated miss is the
            # unavoidable one-time copy of a host-staged (zero-copy-from-
            # numpy) input buffer; only a RECURRING miss on the same
            # executable is the slab-copy regime
            counted = bool(missed) and self._last_missed.get(key, False)
            self._last_missed[key] = bool(missed)
            if counted:
                e.donation_misses += 1
            flag_now = counted and not e.donation_flagged
            if flag_now:
                e.donation_flagged = True
        if counted:
            stat_add("donation_miss", 1)
        if flag_now:
            _warn("device donation audit: donated buffer was COPIED, "
                  "not aliased (its pointer is absent from the outputs)"
                  " — the donation-miss slab-copy regime "
                  "(tools/regime_step_probe.py)",
                  fn=self.name, donated_bytes=e.donated_bytes,
                  missed_buffers=len(missed))

    def __call__(self, *args, **kwargs):
        leaves, treedef = self._tree_flatten((args, kwargs))
        tracer = self._tracer_cls
        if any(isinstance(x, tracer) for x in leaves):
            # called INSIDE another trace (the sharded scan traces its
            # instrumented shard step under lax.scan): an AOT Compiled
            # cannot take tracers — delegate to the inner jax.jit, which
            # inlines into the outer trace exactly like the pre-device-
            # plane jit-of-jit did; the OUTER entry point carries the
            # monitoring
            return self._jitted(*args, **kwargs)
        # the ONE cache-key recipe: treedef + per-leaf _leaf_sig
        key = (treedef, tuple(_leaf_sig(x) for x in leaves))
        with self._lock:
            compiled = self._cache.get(key)
            if compiled is not None:
                self._cache.move_to_end(key)
        if compiled is None:
            compiled = self._compile(key, args, kwargs)
        in_ptrs = (self._donated_ptrs(args)
                   if self._audit_argnums else None)
        if self._static_pos or self._static_argnames:
            call_args = tuple(a for i, a in enumerate(args)
                              if i not in self._static_pos)
            call_kwargs = {k: v for k, v in kwargs.items()
                           if k not in self._static_argnames}
            out = compiled(*call_args, **call_kwargs)
        else:
            out = compiled(*args, **kwargs)
        if in_ptrs is not None:
            self._verify_donation(key, in_ptrs, out)
        return out


def instrument_jit(fn: Callable, name: str,
                   donate_argnums: Tuple[int, ...] = (),
                   static_argnums: Tuple[int, ...] = (),
                   static_argnames: Tuple[str, ...] = (),
                   audit_argnums: Optional[Tuple[int, ...]] = None,
                   example_count: Optional[int] = None,
                   recompile_warmup: Optional[int] = None,
                   **jit_kwargs) -> Callable:
    """The one jit entry point (BX901): jax.jit + the device plane.

      name             — stable entry-point name; stats/logs/the /device
                         endpoint key on it
      audit_argnums    — argnums whose donation the audit verifies;
                         defaults to donate_argnums (pass explicitly to
                         audit an entry point that SHOULD donate but
                         doesn't — the deliberately-non-donated twin in
                         tests, or a path where jax declined donation)
      example_count    — examples one call processes (per-example cost
                         normalization in the analysis snapshot)
      recompile_warmup — per-fn override of device_recompile_warmup for
                         entry points whose legitimate signature space
                         is wider than the default allowance
                         (delta_promote compiles once per power-of-two
                         promote bucket)

    Flag ``device_obs`` off returns bare jax.jit(fn, ...) — identical
    call surface minus the monitoring."""
    from paddlebox_tpu.config import flags
    from paddlebox_tpu.utils.platform import ensure_compile_cache
    # every jit entry comes through here, so this is where the persistent
    # compile cache gets its directory (the AOT lower().compile() below
    # goes through the same cache as jax.jit's own dispatch)
    ensure_compile_cache()
    if not flags.get_flag("device_obs"):
        import jax
        kw = dict(jit_kwargs)
        if donate_argnums:
            kw["donate_argnums"] = donate_argnums
        if static_argnums:
            kw["static_argnums"] = static_argnums
        if static_argnames:
            kw["static_argnames"] = static_argnames
        # boxlint: disable=BX901 — the flag-off bare tier of the wrapper
        return jax.jit(fn, **kw)
    if inspect.isgeneratorfunction(fn):
        raise TypeError("instrument_jit cannot wrap a generator")
    return InstrumentedJit(
        fn, name, donate_argnums=tuple(donate_argnums),
        static_argnums=tuple(static_argnums),
        static_argnames=tuple(static_argnames),
        audit_argnums=audit_argnums, example_count=example_count,
        recompile_warmup=recompile_warmup, **jit_kwargs)
