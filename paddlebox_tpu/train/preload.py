"""Pass preload overlap: load pass N+1 while pass N trains.

The BoxHelper cadence (PreLoadIntoMemory / WaitFeedPassDone,
box_wrapper.h:1131-1172): the dataset's read/parse/merge threads for the
NEXT pass run concurrently with the device steps of the CURRENT pass.

Key registration stays OUTSIDE the table's active state, so the active
pass's routing state (_pass_keys / _rows) is untouched while the next pass
streams in. The feed pass itself was the most expensive thing a pass did
with the device idle, and nothing it reads is unknown while the previous
pass trains: its keys are the chunks as they are registered, and the map
the slab will hold at the boundary is the installed pass's own
(PassTable.next_base), which already has nearly every one of those keys,
sorted, with its row, behind a native index. So where the table can derive
a pass apart from making it the active one (PassTable.begin_feed_fold /
finish_feed_fold / install_feed_plan), the plan is a delta on that base,
folded a chunk at a time under the load: a feed-fold thread probes each
chunk against the base as it is registered (the working set at preload(),
then every parsed block; one probe a key in native code, nothing sorted
but the keys the base does not hold), a feed-ahead thread joins the load
and the fold and finishes the plan (the delta's row assignment; the base's
own index, shared, where nothing arrived and nothing left), and the
boundary installs it: O(1) while the plan's base is the object that is
resident then, else the assignment is redone there from the plan's keys
(after a save's invalidate_residency, an eval pass, a poisoned pass). With
no base (the first pass, after any of those) the chunks wait for one
np.unique and rows by rank, as a first pass always did. A table that
offers no plan (ShardedPassTable: its end_feed_pass writes the active
pass's routing state in place and may run a host collective) keeps its
feed pass on the boundary, the part the reference also leaves in
EndFeedPass (box_wrapper.cc:153-168).

Incremental promote overlap (round-6): with the incremental pass
lifecycle, most of begin_pass's remaining host cost is store reads for
keys that are NOT in the currently-resident set but HAVE been seen in
earlier passes. A PromotePrefetcher thread reads those rows from the host
store while the previous pass still trains, the same tail-hiding the
reference gets from PreLoad/WaitFeedPassDone. Which keys those are the
fold already knows (the keys it found missing from the base, unique across
chunks), and feeds it just them; for a table without a fold an
UnknownUnseen screen picks them out of the raw chunks on the prefetcher's
thread. Creation of genuinely-new keys stays at the pass boundary so
init-rng draw order (and therefore every bit) matches the non-overlapped
path.

Stage ahead: the plan holds the exact key -> row map the boundary
installs, and staging a batch reads no slab value, so once the plan is
done the feed-ahead thread goes on to the pass's shuffle, split and first
scan chunk (the trainer's StagedAhead: pack, lookup in the plan, dedup,
stack), all while the pass before still trains. The pass dispatches that
chunk first, while the boundary installed the plan as it was made; where
the assignment was redone there, the chunk is dropped and staged again.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from paddlebox_tpu.obs import log as obs_log
from paddlebox_tpu.obs.tracer import (get_tracer, pass_trace_id,
                                      span as obs_span, trace_ctx,
                                      with_current_trace)
from paddlebox_tpu.utils.stats import gauge_set, stat_add


class UnknownUnseen:
    """Screen for the prefetcher of a table that folds nothing
    (ShardedPassTable): of a batch of raw registered chunks, the keys its
    known_fn(keys)->bool mask does not mark resident and no earlier batch
    brought, sorted unique. The dedup stays in numpy (sorted_member probe
    + union1d merge); a Python set at feed-key line rate would cost
    hundreds of ms/pass on this thread."""

    def __init__(self, known_fn) -> None:
        self._known = known_fn
        self._seen = np.empty(0, np.uint64)

    def __call__(self, chunk: np.ndarray) -> np.ndarray:
        from paddlebox_tpu.embedding.row_map import sorted_member
        cand = np.unique(chunk)
        cand = cand[~self._known(cand)]
        if cand.size:
            cand = cand[~sorted_member(self._seen, cand)[1]]
        if cand.size:
            self._seen = np.union1d(self._seen, cand)
        return cand


class PromotePrefetcher:
    """Background host-store read of the next pass's non-resident keys
    (the overlapped half of the incremental begin_pass).

    It is fed the keys that arrive, each once (the feed fold's
    arrivals), or, with a ``screen``, raw chunks the screen reduces to
    those on this thread; store.lookup_present(keys)->(rows, found) reads
    WITHOUT creating, so rng parity with the boundary path holds; lock
    serializes store access against the current pass's end_pass
    writeback."""

    def __init__(self, store, lock: threading.Lock, screen=None) -> None:
        self._screen = screen
        # the table's store_lock: every store touch from this worker must
        # hold it or race the current pass's end_pass writeback (round-6
        # serialization claim, machine-checked by boxlint BX401)
        self._store = store  # guarded-by: _lock
        self._lock = lock
        self._q: "queue.Queue" = queue.Queue()
        self._keys: List[np.ndarray] = []
        self._rows: List[np.ndarray] = []
        self._err: Optional[BaseException] = None
        # the worker's spans carry the pass it reads for
        self._thread = threading.Thread(
            target=with_current_trace(self._run), daemon=True,
            name="promote-prefetch")
        self._thread.start()

    def feed(self, keys: np.ndarray) -> None:
        self._q.put(np.asarray(keys, np.uint64))

    def _run(self) -> None:
        try:
            done = False
            while not done:
                chunk = self._q.get()
                if chunk is None:
                    return
                # drain everything already queued: readers feed many small
                # chunks, and one store call (and one screen) over the
                # batch beats one a chunk
                parts = [chunk]
                while True:
                    try:
                        nxt = self._q.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None:
                        done = True  # process this batch, then exit
                        break
                    parts.append(nxt)
                cand = np.concatenate(parts)
                if self._screen is not None and cand.size:
                    cand = self._screen(cand)
                if not cand.size:
                    continue
                with self._lock:
                    rows, found = self._store.lookup_present(cand)
                if found.any():
                    self._keys.append(cand[found])
                    self._rows.append(rows[found])
        except BaseException as e:  # surfaced at finish()
            self._err = e

    def finish(self) -> Tuple[np.ndarray, np.ndarray]:
        """Join the worker and return (sorted unique keys, rows)."""
        self._q.put(None)
        self._thread.join()
        if self._err is not None:
            raise self._err
        if not self._keys:
            return np.empty(0, np.uint64), np.empty((0, 0), np.float32)
        keys = np.concatenate(self._keys)
        rows = np.vstack(self._rows)
        order = np.argsort(keys, kind="stable")
        return keys[order], rows[order]

    def stop(self) -> None:
        """Abandon the prefetch (error paths): unblock and join the
        worker, discarding whatever it staged."""
        self._q.put(None)
        self._thread.join(timeout=30.0)


class FeedAhead:
    """The feed pass of the next pass, derived on two threads of its own
    under the current pass's training. The feed-fold thread takes the key
    chunks as they are registered (feed(), from the preloading thread and
    the dataset's readers) and folds each against ``base``
    (PassTable.begin_feed_fold: nothing to fold with no base), handing
    the keys found to arrive to the promote prefetcher. The feed-ahead
    thread, started once the readers are (start()), joins the dataset's
    load (the final concat and the quality pass run on that join), then
    what is left of the fold, then finishes the plan
    (PassTable.finish_feed_fold, which writes no field of the table).
    Their spans carry the pass they plan for, and the plan carries their
    stamps and counts (FeedPlan) to the boundary that consumes it, where
    PassPreloader.wait accounts them. With a ``stage`` (the trainer's
    StagedAhead), the feed-ahead thread goes on, once the plan is handed
    out, to stage the pass's first chunk against it under a span
    stage_ahead of its own; a plan that failed skips it."""

    def __init__(self, table, base, prefetch, stage=None) -> None:
        self._table = table
        self.fold = table.begin_feed_fold(base)
        self._prefetch = prefetch
        self._stage = stage
        self._q: "queue.Queue" = queue.Queue()
        self._plan = None
        self._err: Optional[BaseException] = None
        self._planned = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._folder = threading.Thread(
            target=with_current_trace(self._fold_chunks), daemon=True,
            name="feed-fold")
        self._folder.start()

    def feed(self, keys: np.ndarray) -> None:
        self._q.put(keys)

    def _fold_chunks(self) -> None:
        try:
            while True:
                chunk = self._q.get()
                if chunk is None:
                    break
                self.fold.add(chunk)
                if self._q.empty():
                    self._hand_arrivals()
            self._hand_arrivals()
        except BaseException as e:  # surfaced at finish()
            self._err = e

    def _hand_arrivals(self) -> None:
        if self._prefetch is not None:
            new = self.fold.arrivals()
            if new.size:
                self._prefetch.feed(new)

    def start(self, dataset) -> None:
        """Join the load, and plan, on the feed-ahead thread: called once
        dataset.preload_into_memory has started the readers."""
        self._thread = threading.Thread(
            target=with_current_trace(self._run), args=(dataset,),
            daemon=True, name="feed-ahead")
        self._thread.start()

    def _run(self, dataset) -> None:
        try:
            with obs_span("ingest_feed_ahead") as ahead:
                try:
                    with obs_span("ingest_load_join") as join:
                        dataset.wait_preload_done()
                finally:
                    # what of the fold the load did not hide
                    with obs_span("feed_fold_join"):
                        self.stop()
                if self._err is not None:
                    raise self._err
                plan = self._table.finish_feed_fold(self.fold)
            plan.stamps["ingest_load_join"] = (join.t0, join.t1)
            plan.stamps["ingest_feed_ahead"] = (ahead.t0, ahead.t1)
            self._plan = plan
        except BaseException as e:  # surfaced at finish()
            self._err = e
        finally:
            self._planned.set()
        if self._stage is None:
            return
        if self._plan is None:
            self._stage.skip()
            return
        # outside ingest_feed_ahead: the chain's clock stops at the plan
        with obs_span("stage_ahead"):
            self._stage.run(self._plan)

    def stop(self) -> None:
        """End the feed-fold thread once it has folded what was fed."""
        self._q.put(None)
        self._folder.join()

    def finish(self):
        """Wait for the plan and return it, or raise what the workers
        raised (the load's error, the fold's, the plan's capacity check).
        A stage ahead goes on after it, on the feed-ahead thread, which is
        joined where there is none."""
        self._planned.wait()
        if self._stage is None or self._err is not None:
            self._thread.join()
        if self._err is not None:
            raise self._err
        return self._plan


# counter <- the span of the feed-ahead chain it is taken from
FEED_PLAN_COUNTERS = (("feed_plan_us", "ingest_feed_ahead"),
                      ("feed_plan_load_join_us", "ingest_load_join"),
                      ("feed_plan_unique_us", "feed_unique"),
                      ("feed_plan_diff_us", "promote_diff"),
                      ("feed_plan_index_us", "feed_route_index"))


def account_feed_plan(plan, t_ask: float) -> None:
    """Work done ahead for a pass, accounted to the pass that consumes it
    at the moment it is consumed: the chain's length and its four serial
    stages (whole microseconds of the spans' own perf_counter pairs; a
    plan with no base has no diff: 0), what deriving the plan took and
    found (FeedPlan.counts: the fold's time and keys, the keys that
    arrived and left, the index shared or rebuilt), and the slack, how
    long the finished plan lay waiting before wait() asked for it at
    ``t_ask``. Slack and a non-trivial ingest_wait_preload exclude each
    other; a plan redone on the boundary still adds the chain that made
    it. The two gauges hold the newest pass's values for the pass report,
    /metrics and the flight recorder. All of it is the tracing's: with
    obs_trace off every stamp is 0.0, so is every sum, and the counts
    are not added."""
    def whole_us(t0: float, t1: float) -> int:
        return int((t1 - t0) * 1e6)

    stamps = plan.stamps
    t0, t_done = stamps["ingest_feed_ahead"]
    slack = max(0, whole_us(t_done, t_ask))
    for counter, name in FEED_PLAN_COUNTERS:
        stat_add(counter, whole_us(*stamps.get(name, (0.0, 0.0))))
    if get_tracer().enabled:
        for counter, amount in plan.counts.items():
            stat_add(counter, amount)
    stat_add("feed_plan_slack_us", slack)
    gauge_set("feed_plan_last_ms", whole_us(t0, t_done) / 1000.0)
    gauge_set("feed_plan_slack_last_ms", slack / 1000.0)


class PassPreloader:
    """One in-flight preload at a time, like BoxHelper's single feed agent."""

    # the main thread's spans in wait(): what the overlap did not hide,
    # then the install (the streaming runner names its own)
    WAIT_SPAN = "ingest_wait_preload"
    FEED_SPAN = "ingest_feed_pass"

    def __init__(self, table) -> None:
        self.table = table
        self._buffer: Optional[List[np.ndarray]] = None
        self._dataset = None
        self._prefetch: Optional[PromotePrefetcher] = None
        self._ahead: Optional[FeedAhead] = None

    def preload(self, dataset, stage=None) -> None:
        """Start the next pass's read threads; returns immediately. A
        table that can plan a feed pass apart from installing it
        (PassTable) gets a FeedAhead, which folds the key chunks against
        the map the slab will hold as they are registered, joins the load
        and plans, all under the current pass's training, and then runs
        ``stage`` (the trainer's StagedAhead) against the plan. When the
        incremental lifecycle is active, a PromotePrefetcher also pulls
        the next pass's non-resident rows from the host store under that
        training: fed by the fold, or through a screen of its own for a
        table without one. A ``stage`` that no plan will come for (a table
        without one, a failed launch) is skipped."""
        if self._dataset is not None:
            raise RuntimeError("a preload is already in flight")
        self._buffer = []
        self._dataset = dataset
        plans = hasattr(self.table, "plan_feed_pass")
        if stage is not None and not plans:
            stage.skip()
            stage = None
        # the base is read here, on the thread that installs and ends
        # passes: the pass installed now has not begun
        base = self.table.next_base() if plans else None
        ctx_fn = getattr(self.table, "promote_prefetch_ctx", None)
        ctx = ctx_fn() if ctx_fn is not None else None
        try:
            # with a plan but no base the slab is built whole: no row
            # read ahead would be used
            if ctx is not None and (base is not None or not plans):
                known, store, lock = ctx
                self._prefetch = PromotePrefetcher(
                    store, lock,
                    screen=None if plans else UnknownUnseen(known))
            if plans:
                self._ahead = FeedAhead(self.table, base, self._prefetch,
                                        stage)
                add = self._ahead.feed
            elif self._prefetch is not None:
                buf = self._buffer
                pre = self._prefetch

                def add(keys):
                    buf.append(keys)
                    pre.feed(keys)
            else:
                add = self._buffer.append
            dataset.preload_into_memory(add_keys_fn=add)
            if plans:
                self._ahead.start(dataset)
        except BaseException:
            # a failed launch must not wedge the preloader (or leave a
            # worker parked on its queue forever)
            if stage is not None:
                stage.skip()
            self._reset()
            raise

    def _reset(self) -> None:
        """Drop all in-flight preload state (error paths included) so the
        preloader can accept a fresh preload() instead of reporting 'a
        preload is already in flight' forever."""
        if self._prefetch is not None:
            try:
                self._prefetch.stop()
            finally:
                self._prefetch = None
        if self._ahead is not None:
            try:
                self._ahead.stop()
            finally:
                self._ahead = None
        self._buffer = None
        self._dataset = None

    def wait(self, dataset, allgather=None, admit_fn=None) -> bool:
        """Join the load and make the buffered keys the table's pass
        (WaitFeedPassDone: dataset_->WaitPreLoadDone() + EndFeedPass):
        the feed-ahead thread's plan is installed, or, for a table that
        offers none, its feed pass runs here. admit_fn(dataset), when
        given, is asked after the join: a refusal drops the keys, the
        plan and the prefetcher's staged rows, leaves the table as it
        was and returns False. However it ends, errors included, the
        preloader resets: a retrying driver can preload again."""
        if dataset is not self._dataset:
            raise RuntimeError("wait() for a dataset that was not preloaded")
        try:
            # the WaitFeedPassDone stall: whatever of the load and of the
            # feed-ahead plan the overlap did NOT hide shows up as this
            # span's width in the exported trace
            plan = None
            with obs_span(self.WAIT_SPAN) as asked:
                if self._ahead is None:
                    dataset.wait_preload_done()
                else:
                    plan = self._ahead.finish()
            if admit_fn is not None and not admit_fn(dataset):
                return False
            if plan is not None:
                account_feed_plan(plan, asked.t0)
            pre, self._prefetch = self._prefetch, None
            if pre is not None:
                with obs_span("promote_prefetch_finish"):
                    keys, rows = pre.finish()
                    if keys.size:
                        self.table.accept_staged_rows(keys, rows)
            with obs_span(self.FEED_SPAN):
                if plan is not None:
                    self.table.install_feed_plan(plan)
                else:
                    self._feed_on_the_boundary(allgather)
            return True
        finally:
            # done, refused or failed: nothing of this preload is kept
            self._reset()

    def _feed_on_the_boundary(self, allgather) -> None:
        """A table without a plan (ShardedPassTable: its end_feed_pass
        writes the active pass's routing state in place and, across
        processes, runs a host collective)."""
        self.table.begin_feed_pass()
        for ks in self._buffer or []:
            self.table.add_keys(ks)
        import inspect
        params = inspect.signature(self.table.end_feed_pass).parameters
        if "allgather" in params:
            self.table.end_feed_pass(allgather=allgather)
        else:  # a single-chip table takes no allgather
            self.table.end_feed_pass()


def run_preloaded_passes(trainer, datasets: Iterable,
                         release: bool = True,
                         after_pass=None) -> List[Dict[str, float]]:
    """Drive a sequence of datasets with load(N+1) ∥ train(N) overlap.

    Works with BoxTrainer and ShardedBoxTrainer (both accept
    train_pass(dataset, preloaded=True)). after_pass(pass_index, stats),
    when given, runs after each pass WITH the next pass's readers already
    live — the hook for pass-cadenced work like delta saves
    (end_pass(need_save_delta)). Returns per-pass stats dicts.

    Each dataset gets a pass_trace_id, held around everything done for it:
    its preload (so the reader threads parsing pass N+1 under pass N's
    training, and the feed-ahead thread planning it, carry N+1's id), its
    wait, its train_pass and its release.

    A trainer that stages ahead (BoxTrainer.stage_ahead) gets, with each
    preload over a table that plans, the pass's shuffle, split and first
    scan chunk made on the feed-ahead thread once the plan is done, under
    the pass before; train_pass takes them. Its shuffle seed is drawn at
    the preload, so in pass order.
    """
    allgather = None
    if getattr(trainer, "multiprocess", False):
        allgather = trainer.fleet.all_gather
    pre = PassPreloader(trainer.table)
    stage_fn = getattr(trainer, "stage_ahead", None)
    rank = obs_log.get_rank()
    results: List[Dict[str, float]] = []

    def preload(ds):
        stage = None if stage_fn is None else stage_fn(ds)
        pre.preload(ds, stage=stage)
        return {} if stage is None else {"ahead": stage}

    it = iter(datasets)
    cur = next(it, None)
    if cur is None:
        return results
    with trace_ctx(pass_trace_id(rank, 0)):
        ahead = preload(cur)
    while cur is not None:
        k = len(results)  # cur is pass k of this call
        with trace_ctx(pass_trace_id(rank, k)):
            pre.wait(cur, allgather=allgather)
            nxt, nxt_ahead = next(it, None), {}
            if nxt is not None:
                # start pass N+1's read threads and its feed-ahead
                # plan BEFORE training pass N
                with trace_ctx(pass_trace_id(rank, k + 1)):
                    nxt_ahead = preload(nxt)
            results.append(trainer.train_pass(cur, preloaded=True, **ahead))
            if after_pass is not None:
                after_pass(k, results[-1])
            if release:
                with obs_span("pass_release"):
                    cur.release_memory()
        cur, ahead = nxt, nxt_ahead
    return results
