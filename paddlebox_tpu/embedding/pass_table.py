"""Pass-lifecycle sparse table: the TPU-native BoxPS core.

Re-design of the reconstructed boxps::BoxPSBase contract (SURVEY.md, every
call site in box_wrapper.{h,cc}) around XLA's static-shape model:

  BeginFeedPass/AddKeys/EndFeedPass  → collect the pass's key set and
        assign each key its slab row (embedding/row_map.py: a resident key
        keeps the row it has, an arriving key takes a free one; the first
        pass is dense, rows 0..n-1 by sorted rank), replacing the device
        hash table: the feed pass gives the exact working set
  BeginPass  → promote host rows → device HBM slab  [capacity, width]
  PullSparse → gather rows by id (keys pre-translated to ids at pack time,
        so DedupKeysAndFillIdx becomes a host-side searchsorted)
  PushSparse → per-batch id-dedup (jnp.unique, static size) → segment-sum
        gradient merge → in-table optimizer → scatter rows back
  EndPass    → slab → host write-back (+ optional delta save hook)

The last slab row (capacity-1) is a reserved trash row addressed by padding
ids; its values never reach the host store.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config.configs import TableConfig
from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.accessor import (PushLayout, ValueLayout,
                                              decode_slab_rows_np,
                                              encode_slab_rows_np)
from paddlebox_tpu.embedding.host_store import HostEmbeddingStore
from paddlebox_tpu.embedding.native_store import make_host_store
from paddlebox_tpu.embedding.optimizers import apply_push
from paddlebox_tpu.embedding.row_map import (KeyFold, RowMap,
                                              sorted_member)
from paddlebox_tpu.obs.device import account_d2h, account_h2d, instrument_jit
from paddlebox_tpu.obs.tracer import span as obs_span
from paddlebox_tpu.utils.stats import gauge_set, stat_add
from paddlebox_tpu.utils.lockwatch import make_lock


def _pull_kernel_impl(slab: jnp.ndarray, ids: jnp.ndarray,
                      layout: ValueLayout) -> jnp.ndarray:
    """Gather pull view [show, click, embed_w, embedx...] per key
    (PullCopy semantics, box_wrapper.cu:75-120). Padding ids hit the trash
    row; callers mask by segment validity downstream."""
    from paddlebox_tpu.ops.sparse import pull_sparse  # lazy: avoids cycle
    return pull_sparse(slab, ids, layout)


_pull_kernel = instrument_jit(_pull_kernel_impl, "table_pull",
                              static_argnames=("layout",))


def _push_kernel_impl(slab: jnp.ndarray, ids: jnp.ndarray,
                      grads: jnp.ndarray, prng: jax.Array,
                      layout: ValueLayout, conf) -> jnp.ndarray:
    """jit wrapper over the dedup-merge-optimize-scatter push."""
    from paddlebox_tpu.embedding.optimizers import push_sparse_dedup
    return push_sparse_dedup(slab, ids, grads, prng, layout, conf)


_push_kernel = instrument_jit(_push_kernel_impl, "table_push",
                              donate_argnums=(0,),
                              static_argnames=("layout", "conf"))


def _delta_promote_impl(slab, new_idx, new_rows):
    """The arrived keys' rows scatter into the (donated) resident slab at
    the rows the RowMap gave them; every other row stays where it is.
    new_idx is padded to a power-of-two bucket with `capacity` (out of
    range, mode='drop') so promote counts don't recompile per pass.
    Dtype-agnostic on purpose: under the bf16 slab diet the rows are
    ENCODED uint16 and must move without arithmetic."""
    with jax.named_scope("promote_scatter"):
        return slab.at[new_idx].set(new_rows, mode="drop")


# donated: begin_pass consumes the previous pass's slab in place — one
# live slab at any moment, like the full path (test-mode passes donate
# too; their eval slab can't become resident, so keeping a second copy
# would only double peak HBM)
# recompile_warmup: promote counts pad to power-of-two buckets, so the
# legitimate signature space is ~log2(capacity) shapes, not the default
# steady-state allowance
_delta_promote = instrument_jit(_delta_promote_impl, "delta_promote",
                                donate_argnums=(0,), recompile_warmup=32)


def _writeback_gather_impl(slab, idx):
    """One chunk of end_pass's write-back: the rows at idx, a fixed
    [chunk] index whatever the pass touched (the tail of the last chunk
    repeats a valid row; the host drops it), so every boundary of the
    table's life runs the one program. Dtype-agnostic as _delta_promote
    is: ENCODED uint16 rows move without arithmetic."""
    with jax.named_scope("writeback_gather"):
        return slab[idx]


# not donated: the slab lives on as the next pass's resident slab
_writeback_gather = instrument_jit(_writeback_gather_impl,
                                   "writeback_gather")

# what one chunk of the write-back holds on the device, of the order of:
# large enough that a chunk's dispatch and transfer set-up are noise,
# small enough that two in flight are noise beside the slab
_WRITEBACK_CHUNK_BYTES = 32 << 20


def _writeback_chunk_rows(layout: ValueLayout, capacity: int) -> int:
    """Rows a write-back chunk gathers: the power of two whose device
    bytes fit _WRITEBACK_CHUNK_BYTES, never more than the slab has.
    From the row's bytes alone, so it holds for the table's life: a rule
    on the count of touched rows would compile at the pass that crosses
    its edge."""
    fit = max(_WRITEBACK_CHUNK_BYTES // layout.device_bytes_per_row, 1)
    return min(1 << (fit.bit_length() - 1), _pow2_pad(capacity))


def _slab_embed_dtype() -> str:
    """Resolve the slab_embed_dtype flag at table construction: the
    DEVICE slab's weight-column precision (round-11 dtype diet). Read
    once per table, not per pass — the codec layout is baked into every
    jitted step's static ValueLayout."""
    from paddlebox_tpu.config import flags
    return str(flags.get_flag("slab_embed_dtype"))


def _pow2_pad(m: int) -> int:
    p = 1
    while p < m:
        p <<= 1
    return p


def push_domain(n_u: int, K: int, floor: int = 0) -> int:
    """Static size U of a push's unique-row domain, from the dedup's own
    count: the power-of-two bucket that holds the n_u real uids (the rule
    begin_pass uses for its scatter), capped at K, one slot an occurrence
    (a key vector with no repeats keeps the [K] program), and never under
    ``floor``, the caller's high-water mark for this K, so a stager
    compiles one program a bucket it has ever reached. The push's device
    cost is per index, padding included: the slots past n_u write
    nothing and cost as much as a row that does."""
    return max(min(_pow2_pad(n_u), K), floor)


def dedup_ids(ids: np.ndarray, pad_base: int):
    """Host-side per-batch id dedup for push_sparse_hostdedup: the device
    analog (jnp.unique) is an XLA sort of the whole key vector inside every
    train step; here it rides the already-overlapped host batch stage
    (DedupKeysAndFillIdx host-side, box_wrapper_impl.h:129).

    Returns (uids, perm, inv, n_u): three int32 [K] arrays and a count:
      uids — unique ids (tail padded with pad_base+i: unique and
      out-of-slab → scatter-dropped); perm — occurrence indices grouped by
      unique id; inv — merged-row index per PERMUTED occurrence,
      nondecreasing so the device merge is a sorted segment-sum; n_u — how
      many of uids are real: they are uids[:n_u] and every inv value is
      below n_u, so uids[:U] with any U >= n_u is the same push on fewer
      padded slots (push_domain picks U; perm and inv are per occurrence
      and keep their [K]).

    Fast path: native rt_dedup (hash dedup + counting sort, no comparison
    sort); numpy argsort fallback. The native tier returns uids in
    hash-probe order, the numpy tier ascending: no consumer relies on
    the order (dedup_uids_sorted is the sorted product)."""
    raw = np.asarray(ids)
    ids = np.ascontiguousarray(raw, dtype=np.int32)
    K = ids.shape[0]
    # ids must be nonnegative pass-local ids; a raw uint64 feasign wrapped
    # by the int32 cast would alias rt_dedup's -1 empty sentinel and break
    # the unique-uids scatter contract
    if K and (ids.min() < 0 or (raw.dtype != np.int32
                                and np.uint64(raw.max()) > np.uint64(2**31 - 1))):
        raise ValueError("dedup_ids expects nonnegative int32 pass-local "
                         "ids, got range [%s, %s] dtype %s"
                         % (raw.min(), raw.max(), raw.dtype))
    from paddlebox_tpu.native.build import get_lib
    lib = get_lib()
    if lib is not None and K:
        import ctypes
        uids = np.empty(K, np.int32)
        perm = np.empty(K, np.int32)
        inv = np.empty(K, np.int32)
        scratch = np.empty(2 * K, np.int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        n_u = lib.rt_dedup(
            ids.ctypes.data_as(i32p), K, pad_base,
            uids.ctypes.data_as(i32p), perm.ctypes.data_as(i32p),
            inv.ctypes.data_as(i32p),
            scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if n_u >= 0:
            return uids, perm, inv, int(n_u)
    perm = np.argsort(ids, kind="stable").astype(np.int32)
    sorted_ids = ids[perm]
    newseg = np.empty(K, dtype=bool)
    if K:
        newseg[0] = True
        np.not_equal(sorted_ids[1:], sorted_ids[:-1], out=newseg[1:])
    inv = np.cumsum(newseg, dtype=np.int32) - 1
    uids = np.full(K, 0, dtype=np.int32)
    real = sorted_ids[newseg]
    n_u = real.shape[0]
    uids[:n_u] = real
    uids[n_u:] = pad_base + np.arange(K - n_u, dtype=np.int32)
    return uids, perm, inv, n_u


def dedup_uids_sorted(ids: np.ndarray, pad_base: int) -> np.ndarray:
    """[K] SORTED unique ids, tail padded with pad_base+i — the uid-wire
    host product (round 8): the device derives inv/first/pos by binary
    search against this vector, so unlike dedup_ids (whose native fast
    path returns hash-probe order) sortedness is load-bearing.

    Fast path (round 11): native rt_dedup_sorted — calloc'd presence-mark
    dedup over the K occurrences, then an LSD radix sort of the n_u
    UNIQUES only (byte passes skip when constant), so heavy key
    recurrence pays one byte store per occurrence + O(n_u) sort instead
    of np.unique's comparison sort of the whole occurrence vector.
    The kernel DECLINES low-duplication shapes and any id
    outside [0, pad_base) — both return -1 and this wrapper keeps the
    numpy tier, which also remains the oracle the sortedness contract
    test pins both against (tests/test_wire_modes.py).

    ENGAGEMENT (re-keyed round 13, the PR-6 named follow-up): the
    decline predicate runs on the live id SPAN, not pad_base — wired
    callers pass pad_base = table/shard capacity but their pass-local
    ids cluster in [0, working set) with the trash id (pad_base-1) as
    the one far outlier, which the kernel tracks out-of-band. Engaging
    requires 2*span <= K, which guarantees mean duplication
    K/n_unique >= 2 (n_unique <= span) — production bucket
    concatenations now take the native tier."""
    ids = np.ascontiguousarray(np.asarray(ids), np.int32)
    K = ids.shape[0]
    if K and ids.min() < 0:
        raise ValueError("dedup_uids_sorted expects nonnegative int32 "
                         "pass-local ids")
    from paddlebox_tpu.native.build import get_lib
    lib = get_lib()
    # hoisted engagement screen (ONE vectorized max) so clearly-
    # declining shapes skip the scratch allocs and the FFI call: engage
    # when the span bound already guarantees dup >= 2, and FORWARD the
    # trash-topped shape (m == pad_base-1, the wired bucket padding) to
    # the kernel, whose single top-two prepass decides from the
    # out-of-band span — a numpy twin here would re-pay that pass as a
    # mask + copy + second max on every ENGAGED production call; the
    # declining trash shapes instead pay the kernel one O(K) scan
    # before their numpy fallback, the cheaper side of the tradeoff
    native_ok = lib is not None and K and hasattr(lib, "rt_dedup_sorted")
    if native_ok:
        m = int(ids.max())
        native_ok = m < pad_base and (2 * (m + 1) <= K
                                      or m == pad_base - 1)
    if native_ok:
        import ctypes
        out = np.empty(K, np.int32)
        scratch = np.empty(K, np.int64)
        n_u = lib.rt_dedup_sorted(
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), K, pad_base,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if n_u >= 0:
            return out
    uniq = np.unique(ids)
    out = np.empty(K, np.int32)
    n = uniq.shape[0]
    out[:n] = uniq
    out[n:] = pad_base + np.arange(K - n, dtype=np.int32)
    return out


def occurrence_uid_slots(perm: np.ndarray, inv: np.ndarray) -> np.ndarray:
    """[K] int32 slot in the dedup's uids of each occurrence, in the batch's
    own order: ids[k] == uids[occ_uid[k]], every value below n_u. The
    inverse of (perm, inv), which name the same slots in PERMUTED order;
    no second pass over the keys. The step pulls by it: the slab is
    gathered once a uid and each occurrence takes its view from that block
    (ops/sparse.pull_sparse_unique)."""
    occ_uid = np.empty_like(inv)
    occ_uid[perm] = inv
    return occ_uid


def pos_for_rebuild(uids: np.ndarray, capacity: int) -> np.ndarray:
    """[capacity] int32 inverse of a dedup's uids for the
    push_write='rebuild' slab write: pos[r] = row index into the push's
    new_rows for touched slab rows, -1 elsewhere. One definition shared by
    every trainer's host stage (BoxTrainer per batch, the sharded stager
    per destination shard) so the rebuild contract can't diverge."""
    pos = np.full(capacity, -1, np.int32)
    m = uids < capacity
    pos[uids[m]] = np.arange(uids.shape[0], dtype=np.int32)[m]
    return pos


# the base of a pass that has begun: no resident map is this object
_SPENT = object()


class FeedPlan(NamedTuple):
    """What a feed pass derives, apart from the table that will run it:
    the pass's sorted unique keys and the RowMap (with its index) that
    succeeds ``base``, by rank where ``base`` is None. It holds while
    ``base`` is the map the slab holds (PassTable.install_feed_plan).
    ``stamps`` is the plan's clock: {span name: (t0, t1)} of the live
    spans it was finished under (feed_unique, promote_diff where there is
    a base, feed_route_index; the preloader's feed-ahead thread adds its
    own two), and ``counts`` what deriving it took and found: {counter:
    amount} (the fold's time and keys, the keys that arrived and left,
    the index shared or rebuilt), both for the pass that consumes the
    plan to account (preload.account_feed_plan). The stamps are 0.0
    while tracing is off."""
    keys: np.ndarray
    rows: RowMap
    base: Optional[RowMap]
    stamps: Dict[str, Tuple[float, float]]
    counts: Dict[str, int]


class FeedFold:
    """A feed pass being derived, one registered key chunk at a time
    (PassTable.begin_feed_fold / finish_feed_fold). With a ``base`` each
    chunk is folded against it as it comes (row_map.KeyFold) under a live
    span feed_fold, on the thread that brings it; with none the chunks
    wait for the one sort of the finish. One thread at a time."""

    def __init__(self, base: Optional[RowMap]) -> None:
        self.base = base
        self.on_base = None if base is None else KeyFold(base)
        self.chunks: list = []  # no base: every chunk, for np.unique
        self.fold_us = 0  # the feed_fold spans' widths, whole us

    @property
    def folded(self) -> int:
        """Keys probed against the base so far."""
        return 0 if self.on_base is None else self.on_base.folded

    def _timed(self, fn, *args):
        with obs_span("feed_fold") as s:
            out = fn(*args)
        self.fold_us += int((s.t1 - s.t0) * 1e6)
        return out

    def add(self, chunk: np.ndarray) -> None:
        if self.on_base is None:
            self.chunks.append(np.asarray(chunk, np.uint64))
        else:
            self._timed(self.on_base.add, chunk)

    def arrivals(self) -> np.ndarray:
        """The keys found to arrive since the last call (sorted unique, in
        no earlier return): what a promote prefetcher may read ahead.
        Empty with no base: the whole slab is built then."""
        if self.on_base is None or not self.on_base.unsettled:
            return np.empty(0, np.uint64)
        return self._timed(self.on_base.take_arrivals)


class PassTable:
    """Single-shard (one-device or host-replicated) sparse table with the
    BoxPS pass lifecycle. The pod-sharded variant composes these per shard
    (parallel/sharded table)."""

    def __init__(self, table: TableConfig, seed: int = 0,
                 store: Optional[HostEmbeddingStore] = None) -> None:
        self.config = table
        self.layout = ValueLayout(table.embedx_dim, table.optimizer.optimizer,
                                  expand_dim=table.expand_embed_dim,
                                  embed_dtype=_slab_embed_dtype())
        self.push_layout = PushLayout(table.embedx_dim,
                                      table.expand_embed_dim)
        # store contents move under concurrent access (native arena rows
        # relocate on spill/resize) — every touch while a PromotePrefetcher
        # can be live holds store_lock; lock-free boundary sites carry an
        # explicit boxlint disable with their single-threaded rationale
        # `is None`, not truthiness: an explicitly-passed EMPTY store is
        # falsy through __len__ and used to be silently replaced
        self.store = (store if store is not None
                      else make_host_store(self.layout, table, seed))  # guarded-by: store_lock
        self.capacity = table.pass_capacity
        self._writeback_rows = _writeback_chunk_rows(self.layout,
                                                     self.capacity)
        self._feed_keys: list = []
        self._pass_keys: Optional[np.ndarray] = None  # sorted unique
        # key → slab row of _pass_keys (row_map.py owns the assignment)
        self._rows: Optional[RowMap] = None
        self._slab: Optional[jnp.ndarray] = None
        self._in_feed_pass = False
        self._in_pass = False
        self._test_mode = False
        self._prng = jax.random.PRNGKey(seed)
        # pass-to-pass state (BoxPS keep-rows-resident cadence): after
        # end_pass _resident records which key occupies which row, and the
        # next feed pass assigns its rows as that map's successor. With
        # incremental_pass the slab stays in HBM too and begin_pass
        # promotes only the keys that arrived; without, it rebuilds the
        # slab from the store AT THOSE ROWS, so a key has one row whichever
        # way the flag stands. _rows_base is the map _rows succeeded: the
        # assignment holds while that object is the resident one, and is
        # _SPENT once the pass has begun (and before any is installed).
        # store_lock serializes host-store access between end_pass and the
        # preload promote stager.
        self._resident: Optional[RowMap] = None
        self._rows_base = _SPENT
        self._touched: Optional[np.ndarray] = None  # bool[capacity] mirror
        self._touch_seen = False  # any mark this pass? (else full writeback)
        self._residency_poisoned = False  # mid-pass invalidate: drop at end
        self._staged: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.store_lock = make_lock("PassTable.store_lock")
        # touched-row journal (round 15): when attached, end_pass appends
        # the rows it writes back and the lifecycle mutations append
        # deterministic event records (train/journal.py)
        self._journal = None

    # --------------------------------------------------------------- journal
    # setup-time wiring, called before any worker thread exists
    def attach_journal(self, journal) -> None:  # boxlint: disable=BX401
        """Attach a train.journal.TouchedRowJournal: end_pass write-backs
        append their touched (keys, rows) delta; end_day/shrink append
        event records; spill/fault-in/promote append MOVE records through
        the store's journal sink (installed here) so the epoch stays
        replayable with the SSD tier active. External loads still taint."""
        self._journal = journal
        set_sink = getattr(self.store, "set_journal_sink", None)
        if set_sink is not None:
            set_sink(None if journal is None else journal.append_move)

    def _journal_rows(self, keys: np.ndarray, rows: np.ndarray) -> None:
        if self._journal is not None:
            self._journal.append_rows(keys, rows)

    def _journal_event(self, code: int) -> None:
        if self._journal is not None:
            self._journal.append_event(code)

    # ------------------------------------------------------- pass lifecycle
    def begin_feed_pass(self) -> None:
        """BeginFeedPass (box_wrapper.cc:129): open key registration."""
        if self._in_feed_pass:
            raise RuntimeError("feed pass already open")
        self._feed_keys = []
        self._in_feed_pass = True

    def add_keys(self, keys: np.ndarray) -> None:
        """PSAgentBase::AddKeys (box_wrapper.h:1218): register feasigns seen
        in the incoming pass. Thread-safe append (list.append is atomic)."""
        if not self._in_feed_pass:
            raise RuntimeError("add_keys outside feed pass")
        self._feed_keys.append(np.asarray(keys, dtype=np.uint64))

    def end_feed_pass(self) -> None:
        """EndFeedPass (box_wrapper.cc:153): freeze the pass key set and
        assign each key its slab row: plan on what is resident now, then
        install, back to back."""
        if not self._in_feed_pass:
            raise RuntimeError("end_feed_pass without begin_feed_pass")
        plan = self.plan_feed_pass(self._feed_keys, self._resident)
        self._feed_keys = []
        self._in_feed_pass = False
        self.install_feed_plan(plan)

    def next_base(self) -> Optional[RowMap]:
        """The map the slab will hold at the next pass boundary, which a
        plan made ahead of it succeeds: the rows of the installed pass
        that has not ended (open, or its base not yet spent by a
        begin_pass), else the resident map. A poisoned or test-mode pass
        ends with another, and install_feed_plan sees it."""
        pending = self._in_pass or self._rows_base is not _SPENT
        return self._rows if pending else self._resident

    def plan_feed_pass(self, chunks: Sequence[np.ndarray],
                       base: Optional[RowMap]) -> FeedPlan:
        """Derive a pass from its registered key chunks, all at once:
        fold every chunk, then finish. The preloader makes the same two
        calls a chunk at a time under the load (preload.FeedAhead)."""
        fold = self.begin_feed_fold(base)
        for c in chunks:
            fold.add(c)
        return self.finish_feed_fold(fold)

    def begin_feed_fold(self, base: Optional[RowMap]) -> FeedFold:
        """Open the derivation of the pass that succeeds ``base``. It
        reads ``base`` and writes no field of the table, so the preloader
        runs it on threads of its own while the pass before trains (the
        stager probes ``base``'s index beside it: route.cc's index is
        probe-only once built)."""
        return FeedFold(base)

    def finish_feed_fold(self, fold: FeedFold) -> FeedPlan:
        """The plan of the chunks ``fold`` took: the key set finished
        (with a base, the arrivals settled; with none, np.unique over
        every chunk), the capacity check, which raises and writes
        nothing, then the rows that succeed the base and their index."""
        base = fold.base
        with obs_span("feed_unique") as unique:
            if base is not None:
                fold.on_base.take_arrivals()
                keys, n = None, fold.on_base.size
            else:
                keys = (np.unique(np.concatenate(fold.chunks))
                        if fold.chunks else np.empty(0, dtype=np.uint64))
                n = int(keys.size)
        if n > self.capacity - 1:
            raise RuntimeError(
                f"pass working set {n} exceeds table "
                f"pass_capacity {self.capacity} (raise TableConfig.pass_capacity)")
        plan = self._assign_rows(
            keys, base, {"feed_unique": (unique.t0, unique.t1)},
            fold.on_base)
        plan.counts.update(feed_plan_fold_us=fold.fold_us,
                           feed_keys_folded=fold.folded)
        return plan

    def _assign_rows(self, keys: Optional[np.ndarray],
                     base: Optional[RowMap],
                     stamps: Dict[str, Tuple[float, float]],
                     fold: Optional[KeyFold] = None) -> FeedPlan:
        """Ask the row owner for the map that succeeds ``base``: keys that
        stay keep their rows, rows of keys that left are freed, keys that
        arrive take free rows. From ``fold``, which took the chunks against
        ``base``, as a delta on it; from the sorted unique ``keys`` where
        there is none (a plan redone on the boundary). With no base (first
        pass, after invalidate_residency or a test-mode pass), rows 0..n-1
        by sorted rank. Then its index: the base's, shared, where nothing
        arrived and nothing left. Its two spans' stamps join ``stamps``."""
        if base is None:
            # padding_id is never assigned
            rows = RowMap.by_rank(keys, self.capacity - 1)
        else:
            with obs_span("promote_diff") as diff:
                rows = (base.succeed(keys) if fold is None
                        else fold.successor())
            stamps["promote_diff"] = (diff.t0, diff.t1)
        with obs_span("feed_route_index") as index:
            # native key→row hash index, one a key set and probed per
            # batch (~1 cache miss/key vs searchsorted's ~20): the host-side
            # DedupKeysAndFillIdx tier at line rate
            shared = rows.index_like(base)
        stamps["feed_route_index"] = (index.t0, index.t1)
        return FeedPlan(rows.keys, rows, base, stamps, {
            "feed_plan_arrived_keys": int(np.count_nonzero(rows.arrived)),
            "feed_plan_departed_keys": rows.freed,
            "feed_index_shared": int(shared),
            "feed_index_rebuilt": int(not shared)})

    def install_feed_plan(self, plan: FeedPlan) -> None:
        """Make a plan the active pass: O(1) while its base is the object
        that is resident now. Where it is not (invalidate_residency, a
        test-mode pass or a poisoned one came between), the assignment is
        redone here from the plan's keys on what is resident."""
        if self._in_pass:
            raise RuntimeError("feed plan installed under an open pass")
        if plan.base is self._resident:
            stat_add("feed_plan_installed")
        else:
            plan = self._redo(plan.keys)
        self._pass_keys, self._rows, self._rows_base = plan[:3]

    def _redo(self, keys: np.ndarray) -> FeedPlan:
        stat_add("feed_plan_redone")
        return self._assign_rows(keys, self._resident, {})

    @staticmethod
    def _incremental() -> bool:
        from paddlebox_tpu.config import flags
        return bool(flags.get_flag("incremental_pass"))

    def _promote_missing_rows(self, missing_keys: np.ndarray) -> np.ndarray:
        """Host rows for the keys being promoted this pass. Rows the
        preload promote stager already read (store-present keys) come from
        the staged cache; the remainder goes through ONE sorted store call
        — lookup_or_create draws init rng for genuinely-new keys in the
        same sorted order the full path would."""
        W = self.layout.width
        rows = np.empty((missing_keys.size, W), np.float32)
        need = np.ones(missing_keys.size, bool)
        if self._staged is not None and not self._test_mode:
            skeys, srows = self._staged
            pos, hit = sorted_member(skeys, missing_keys)
            if hit.any():
                rows[hit] = srows[pos[hit]]
                need = ~hit
                stat_add("pass_rows_promote_prefetched", int(hit.sum()))
        if need.any():
            rem = missing_keys[need]
            with self.store_lock:
                got = (self.store.lookup(rem) if self._test_mode
                       else self.store.lookup_or_create(rem))
            rows[need] = got
        return rows

    def begin_pass(self) -> None:
        """BeginPass (box_wrapper.cc:171): promote the working set into the
        device slab.

        The feed pass assigned this key set's rows as the resident map's
        successor, so a resident key's row does not move. Incremental mode
        (incremental_pass flag, default on): the previous pass's slab
        stayed in HBM and only the keys that ARRIVED are promoted:
        host-store read, H2D and one in-place scatter into the donated
        slab, for the delta alone. A pass with 90% key overlap does ~10%
        of the full build's host and wire work; one with 100% moves
        nothing. Rows freed by keys that left keep stale bits until a new
        key overwrites them whole. With no slab (first pass, after
        invalidate_residency or a test-mode pass, flag off) the slab is
        built whole from the store, every key at its assigned row: row for
        row the bits the incremental path holds."""
        if self._in_pass:
            raise RuntimeError("pass already open")
        if self._pass_keys is None:
            raise RuntimeError("begin_pass before feed pass completed")
        with obs_span("pass_begin"):
            self._begin_pass()

    def _begin_pass(self) -> None:
        n = self._pass_keys.size
        gauge_set("pass_rows", n)
        if self._rows_base is not self._resident:
            # residency changed since the install (invalidated, or this
            # is a second pass over one feed): assign against what is there
            self._pass_keys, self._rows, self._rows_base = self._redo(
                self._pass_keys)[:3]
        rows = self._rows
        if self._slab is not None:
            with obs_span("promote_store_read"):
                new_keys = self._pass_keys[rows.arrived]
                new_rows = self._promote_missing_rows(new_keys)
                # journal the promote delta: lookup_or_create CREATES
                # missing features here (init rows the touched write-back
                # may never revisit) — replay must see them; re-recording
                # store-present non-resident rows is an idempotent upsert
                # of equal bits
                if not self._test_mode:
                    self._journal_rows(new_keys, new_rows)
            with obs_span("promote_stage"):
                m = new_keys.size
                pad = _pow2_pad(max(m, 1))
                idx_p = np.full(pad, self.capacity, np.int32)  # drop sentinel
                # promote boundary: freshly-read host f32 rows encode to
                # the device layout here (identity for f32 slabs); resident
                # rows stay where they are
                rows_p = np.zeros((pad, self.layout.device_width),
                                  self.layout.device_dtype)
                idx_p[:m] = rows.rows[rows.arrived]
                rows_p[:m] = encode_slab_rows_np(new_rows, self.layout)
            # test mode CONSUMES the resident slab too (donated — a copy
            # would hold 2× slab HBM for the whole eval, an OOM at the
            # capacity-probe scale the chip is sized to); the eval slab
            # can't become resident (zero rows for store-missing keys),
            # so end_pass drops residency and the next train pass pays
            # one full rebuild — the pre-round-6 eval HBM profile
            with obs_span("promote_dispatch"):
                account_h2d(rows_p.nbytes + idx_p.nbytes)  # promote delta
                self._slab = _delta_promote(self._slab, jnp.asarray(idx_p),
                                            jnp.asarray(rows_p))
            stat_add("pass_rows_promote_hit", n - m)
            stat_add("pass_rows_promote_new", m)
        else:
            with obs_span("build_store_read"):
                with self.store_lock:
                    host_rows = (self.store.lookup(self._pass_keys)
                                 if self._test_mode else
                                 self.store.lookup_or_create(self._pass_keys))
                # full build: every pass key may have been created just now
                if not self._test_mode:
                    self._journal_rows(self._pass_keys, host_rows)
            with obs_span("build_encode"):
                shape = (self.capacity, self.layout.device_width)
                if rows.dense:
                    # zero only the tail beyond n: a full-capacity zeros()
                    # here was pure memcpy waste — every [0, n) row is
                    # overwritten
                    slab = np.empty(shape, dtype=self.layout.device_dtype)
                    slab[n:] = 0
                    where = slice(0, n)
                else:
                    slab = np.zeros(shape, dtype=self.layout.device_dtype)
                    where = rows.rows
                if n:
                    slab[where] = encode_slab_rows_np(host_rows, self.layout)
            with obs_span("build_h2d"):
                account_h2d(slab.nbytes)  # full slab build transfer
                self._slab = jnp.asarray(slab)
        stat_add("pass_rows_freed", rows.freed)
        gauge_set("pass_free_rows", rows.free_rows)
        # the slab now holds _rows' assignment; end_pass makes it resident.
        # The base is spent (and let go: a map owns a native index): a
        # second begin_pass over this feed assigns on what is resident then
        self._resident = None
        self._rows_base = _SPENT
        self._touch_seen = False
        self._residency_poisoned = False
        if not self._test_mode:
            self._staged = None  # consumed (or stale) either way
            if self._incremental():
                self._touched = np.zeros(self.capacity, bool)
        self._in_pass = True

    def note_touched(self, ids: np.ndarray) -> None:
        """Accumulate the per-pass touched-row bitmap (host mirror, OR'd
        per batch): every id that reaches a pull/push marks its row so
        end_pass can write back only rows the pass actually updated.
        Idempotent True stores — safe from concurrent staging threads.
        No-op outside an incremental train pass. end_pass uses the delta
        only when at least one mark arrived — raw-slab callers that
        bypass lookup_ids/push still get the full writeback."""
        t = self._touched
        if t is not None:
            t[ids] = True
            self._touch_seen = True

    def end_pass(self) -> None:
        """EndPass (box_wrapper.cc:188): write the slab back to the host
        store. Incremental mode transfers and writes back only TOUCHED
        rows (untouched rows are bit-identical to the host store by
        construction) and keeps the slab resident in HBM for the next
        pass's delta promote; test-mode passes never establish residency
        (their slab holds zero rows for store-missing keys)."""
        if not self._in_pass:
            raise RuntimeError("end_pass without begin_pass")
        with obs_span("pass_end"):
            self._end_pass()

    def _write_back(self, keys: np.ndarray, idx: np.ndarray) -> None:
        """Write slab rows idx back as keys' rows, a chunk of
        _writeback_rows at a time: a chunk is gathered on the device,
        crosses to the host, decodes to host f32 (identity for f32
        slabs), is journaled and lands in the store, while the next
        chunk's gather and copy are already under way. Two chunks live
        on the device at most; every row is in the store when this
        returns."""
        R = self._writeback_rows
        m = idx.size

        def start(lo: int):
            chunk = np.empty(R, np.int32)
            c = min(R, m - lo)
            chunk[:c] = idx[lo:lo + c]
            chunk[c:] = chunk[0]  # any valid row
            dev = _writeback_gather(self._slab, jnp.asarray(chunk))
            dev.copy_to_host_async()
            return dev

        ahead = None
        for lo in range(0, m, R):
            hi = min(lo + R, m)
            with obs_span("writeback_d2h"):
                dev = start(lo) if ahead is None else ahead
                ahead = start(hi) if hi < m else None
                dev_rows = np.asarray(dev)[:hi - lo]
                account_d2h(dev.nbytes)
                del dev
            stat_add("pass_writeback_chunks")
            with obs_span("writeback_decode"):
                rows = decode_slab_rows_np(dev_rows, self.layout)
                self._journal_rows(keys[lo:hi], rows)
            with obs_span("writeback_store"):
                with self.store_lock:
                    self.store.write_back(keys[lo:hi], rows)

    def _end_pass(self) -> None:
        n = self._pass_keys.size
        if self._test_mode:
            # no write-back, no residency from an eval slab
            self._slab = None
        else:
            if n:
                if self._touched is not None and self._touch_seen:
                    with obs_span("writeback_select"):
                        keys, idx = self._rows.touched(self._touched)
                    stat_add("pass_rows_written_back", int(idx.size))
                    stat_add("pass_rows_writeback_skipped", n - int(idx.size))
                else:  # every assigned row
                    keys, idx = self._pass_keys, self._rows.rows
                self._write_back(keys, idx)
            if not self._residency_poisoned:
                # each key keeps its row (BoxPS cadence): the next feed
                # pass assigns its rows as this map's successor
                self._resident = self._rows
            if self._residency_poisoned or not self._incremental():
                # a mid-pass store mutation poisoned the residency
                # (invalidate_residency during the pass must not be undone
                # here), or flag off: the next begin_pass builds the slab
                # whole. Else it lives on in HBM and the next begin_pass
                # promotes only the keys that arrive
                self._slab = None
        self._touched = None
        self._residency_poisoned = False
        self._in_pass = False
        with obs_span("pass_mem_check"):
            self.check_need_limit_mem()  # spill>0 invalidates internally

    def invalidate_residency(self) -> None:
        """Drop the cross-pass resident state (slab, row assignment,
        staged promote rows). Must be called after ANY host-store mutation
        that bypasses the pass cadence — aging, shrink/decay, spill,
        checkpoint stat rewrites, load — or the next delta promote would
        reuse stale row bits. The next begin_pass falls back to a full
        build, rows by rank: every checkpoint save lands here, so a run
        resumed from one (a fresh table, rows by rank) assigns the rows
        the uninterrupted run does. Called mid-pass, the live slab
        survives (the pass still needs it) but a poison flag stops
        end_pass from re-establishing residency."""
        if self._in_pass:
            self._residency_poisoned = True
        else:
            self._slab = None
        self._resident = None
        self._staged = None

    # ------------------------------------------------- preload promote hooks
    def promote_prefetch_ctx(self):
        """(None, store, lock) for preload.PromotePrefetcher, or None when
        the overlapped promote cannot run (flag off, test mode, store
        without lookup_present, or no active pass to succeed). No
        known_fn: the prefetcher of a table that plans is fed by the feed
        fold (begin_feed_fold) with the keys found to arrive, and probes
        nothing itself."""
        from paddlebox_tpu.config import flags
        if (not flags.get_flag("incremental_pass")
                or not flags.get_flag("preload_promote")
                or self._test_mode
                # capability probe, no store mutation; no prefetcher is
                # live before this ctx is handed out
                or not hasattr(self.store, "lookup_present")  # boxlint: disable=BX401
                or self._pass_keys is None or self._pass_keys.size == 0):
            return None
        # handing the ref out, not touching contents: the prefetcher's
        # own accesses are the locked ones (preload.PromotePrefetcher)
        return None, self.store, self.store_lock  # boxlint: disable=BX401

    def accept_staged_rows(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Install the promote stager's prefetched (key, row) pairs for the
        next train begin_pass. keys must be sorted unique."""
        if keys.size:
            self._staged = (keys, rows)

    def check_need_limit_mem(self) -> int:
        """Pass-cadence memory limiter (CheckNeedLimitMem/ShrinkResource,
        box_wrapper.h:627-629): when the host store exceeds the configured
        SSD budget, spill the coldest rows down to it. No-op without
        ssd_dir + ssd_threshold_mb."""
        max_resident = self.config.ssd_max_resident_rows(self.layout.width)
        if max_resident is None:
            return 0
        # under the lock: a concurrent PromotePrefetcher lookup_present
        # must never observe the spill mid-flight (native store has no
        # internal lock — arena rows move)
        with self.store_lock:
            n = self.store.spill(max_resident)
        if n:
            # rows left the store: the resident slab no longer mirrors it
            # (internal, so DIRECT callers are covered too — matching the
            # sharded table). The spill itself was journaled as an
            # MV_SPILL MOVE record by the store's sink — no taint.
            self.invalidate_residency()
        return n

    def set_test_mode(self, test: bool) -> None:
        """SetTestMode (box_wrapper.cc:183): inference pulls — no feature
        creation, no write-back."""
        self._test_mode = test

    @property
    def test_mode(self) -> bool:
        return self._test_mode

    # ------------------------------------------------------------- id space
    @property
    def pass_size(self) -> int:
        return 0 if self._pass_keys is None else int(self._pass_keys.size)

    @property
    def padding_id(self) -> int:
        return self.capacity - 1

    def lookup_ids(self, keys: np.ndarray,
                   valid: Optional[np.ndarray] = None) -> np.ndarray:
        """Translate feasign keys → pass-local ids, the keys' slab rows
        (host-side analog of DedupKeysAndFillIdx). Positions where ``valid``
        is False (packer padding) map to the trash row. Native hash-index
        fast path (~1 probe per key); numpy searchsorted fallback."""
        keys = np.asarray(keys, dtype=np.uint64)
        if self._rows is None:
            raise RuntimeError("no active pass key set")
        ids = self._rows.lookup(keys, valid, self.padding_id)
        # every staged train batch flows through here, so this is the
        # accumulation point for the touched-row bitmap (uids are a
        # subset of these ids); a chunk looked up in a plan ahead of its
        # pass (lookup_in) is marked when that pass takes it
        self.note_touched(ids)
        return ids

    def lookup_in(self, plan: FeedPlan, keys: np.ndarray,
                  valid: Optional[np.ndarray] = None) -> np.ndarray:
        """lookup_ids against a plan that is not installed yet (the next
        pass's first chunk, staged while this pass trains): the keys' rows
        in ``plan.rows``. It marks nothing, since the touched rows are the
        open pass's; the ids are the next pass's while it runs that map
        (runs)."""
        return plan.rows.lookup(np.asarray(keys, dtype=np.uint64), valid,
                                self.padding_id)

    def runs(self, rows: RowMap) -> bool:
        """Whether the open pass runs ``rows``, a plan's map, as it was
        made: False where the boundary redid the assignment (the plan's
        base was not resident: invalidate_residency, an eval pass, a
        poisoned pass)."""
        return self._in_pass and self._rows is rows

    def dedup_for_push(self, ids: np.ndarray):
        """Host-side per-batch dedup for push_sparse_hostdedup (see
        dedup_ids): padding ids start at this table's capacity."""
        return dedup_ids(ids, self.capacity)

    def pos_for_rebuild(self, uids: np.ndarray) -> np.ndarray:
        """[capacity] int32 inverse of the dedup's uids for the
        push_write='rebuild' slab write (see pos_for_rebuild below). Rides
        the overlapped host batch stage like the dedup itself."""
        return pos_for_rebuild(uids, self.capacity)

    # ------------------------------------------------------------ pull/push
    def pull(self, ids: jnp.ndarray) -> jnp.ndarray:
        """PullSparseGPU analog: per-key pull view [K, 3+D]."""
        if not self._in_pass:
            raise RuntimeError("pull outside pass")
        return _pull_kernel(self._slab, ids, self.layout)

    def push(self, ids: jnp.ndarray, grads: jnp.ndarray) -> None:
        """PushSparseGPU analog: merged grads through the in-table optimizer."""
        if not self._in_pass:
            raise RuntimeError("push outside pass")
        if self._test_mode:
            return
        # direct pushes may carry ids that never went through lookup_ids
        # (raw-op callers); this is the slow per-call path, so the D2H of
        # a [K] id vector is noise next to the dispatch
        self.note_touched(np.asarray(ids))
        self._prng, sub = jax.random.split(self._prng)
        self._slab = _push_kernel(self._slab, ids, grads, sub,
                                  self.layout, self.config.optimizer)

    # raw access for fused train steps that thread the slab functionally
    @property
    def slab(self) -> jnp.ndarray:
        return self._slab

    def set_slab(self, slab: jnp.ndarray) -> None:
        self._slab = slab

    def next_prng(self) -> jax.Array:
        self._prng, sub = jax.random.split(self._prng)
        return sub

    # ------------------------------------------------------------ lifecycle
    def shrink_table(self) -> int:
        """ShrinkTable (box_wrapper.h:627): decay + delete on the host tier.
        Mutates every resident store row (decay) — drops pass residency."""
        self.invalidate_residency()
        with self.store_lock:
            n = self.store.shrink()
        from paddlebox_tpu.train.journal import EV_SHRINK
        self._journal_event(EV_SHRINK)
        return n

    def end_day(self, age: bool = True) -> int:
        """Day boundary (the python-driven day cadence around
        SaveBase(…, day)): age every feature's unseen_days — shrink_table's
        delete_after_unseen_days rule keys off it — then shrink. Returns
        rows deleted.

        age=False when CheckpointManager.save_base already ran this
        boundary: its update_stat_after_save(param=3) ages the table, and
        aging twice per day halves every feature's configured lifetime.
        save_base touches only RESIDENT rows, so the spilled rows' lazy
        day clock still advances here either way."""
        self.invalidate_residency()  # aging rewrites every store row
        from paddlebox_tpu.train.journal import (EV_AGE_DAYS,
                                                 EV_TICK_SPILL_AGE)
        # event appends stay INSIDE the store_lock hold: a concurrent
        # promote prefetcher fault-in journals MV_FAULT_IN under the same
        # lock, and replay must apply it against the same tier epoch the
        # live store saw (record order == mutation order)
        with self.store_lock:
            if age:
                self.store.age_unseen_days()
                self._journal_event(EV_AGE_DAYS)
            else:
                self.store.tick_spill_age()
                self._journal_event(EV_TICK_SPILL_AGE)
        return self.shrink_table()

    # checkpoint boundary: the driver serializes save/load against passes,
    # so no prefetch thread can be live here
    def save(self, path: str) -> None:  # boxlint: disable=BX401
        self.store.save(path)

    def load(self, path: str) -> None:  # boxlint: disable=BX401
        self.invalidate_residency()
        if self._journal is not None:
            self._journal.taint("store loaded outside the checkpoint plane")
        self.store.load(path)

    def load_ssd_to_mem(self) -> int:
        """LoadSSD2Mem (box_wrapper.cc:1319): promote every spilled row
        back to DRAM — the explicit warm-up after a model load, before the
        day's first feed pass. Returns rows promoted."""
        # load boundary, same single-threaded window as load()
        if hasattr(self.store, "load_spilled"):  # boxlint: disable=BX401
            self.invalidate_residency()  # fault-in applies missed days
            return self.store.load_spilled()  # boxlint: disable=BX401
        return 0
