"""Pass preload overlap: load pass N+1 while pass N trains.

The BoxHelper cadence (PreLoadIntoMemory / WaitFeedPassDone,
box_wrapper.h:1131-1172): the dataset's read/parse/merge threads for the
NEXT pass run concurrently with the device steps of the CURRENT pass.

Key registration buffers OUTSIDE the table (a plain list) so the active
pass's routing state (_pass_keys / _rows) is untouched while the next
pass streams in. The feed pass itself (np.unique over every registered
and parsed key, the resident diff, the native index build) is the most
expensive thing a pass does with the device idle, and nothing it reads is
unknown while the previous pass trains: its keys are the buffer, and the
map the slab will hold at the boundary is the installed pass's own
(PassTable.next_base). So where the table can derive a pass apart from
making it the active one (PassTable.plan_feed_pass / install_feed_plan), a
feed-ahead thread joins the load and plans under pass N's steps, and the
boundary installs the finished plan: O(1) while the plan's base is the
object that is resident then, else the assignment is redone there from
the plan's keys (after a save's invalidate_residency, an eval pass, a
poisoned pass). A table that offers no plan (ShardedPassTable: its
end_feed_pass writes the active pass's routing state in place and may run
a host collective) keeps its feed pass on the boundary, the part the
reference also leaves in EndFeedPass (box_wrapper.cc:153-168).

Incremental promote overlap (round-6): with the incremental pass
lifecycle, most of begin_pass's remaining host cost is store reads for
keys that are NOT in the currently-resident set but HAVE been seen in
earlier passes. A PromotePrefetcher thread diffs each arriving key chunk
against the resident set (hash probe over the live pass index) and reads
those rows from the host store while the previous pass still trains —
the same tail-hiding the reference gets from PreLoad/WaitFeedPassDone.
Creation of genuinely-new keys stays at the pass boundary so init-rng
draw order (and therefore every bit) matches the non-overlapped path.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from paddlebox_tpu.obs import log as obs_log
from paddlebox_tpu.obs.tracer import (pass_trace_id, span as obs_span,
                                      trace_ctx, with_current_trace)
from paddlebox_tpu.utils.stats import gauge_set, stat_add


class PromotePrefetcher:
    """Background diff + host-store read of the next pass's non-resident
    keys (the overlapped half of the incremental begin_pass).

    known_fn(keys)->bool mask marks keys already resident (the current
    pass's set — exactly what the next begin_pass will diff against);
    store.lookup_present(keys)->(rows, found) reads WITHOUT creating, so
    rng parity with the boundary path holds; lock serializes store access
    against the current pass's end_pass writeback."""

    def __init__(self, known_fn, store, lock: threading.Lock) -> None:
        self._known = known_fn
        # the table's store_lock: every store touch from this worker must
        # hold it or race the current pass's end_pass writeback (round-6
        # serialization claim, machine-checked by boxlint BX401)
        self._store = store  # guarded-by: _lock
        self._lock = lock
        self._q: "queue.Queue" = queue.Queue()
        # sorted accumulated candidate set — the dedup stays in numpy
        # (sorted_member probe + union1d merge); a Python set at feed-key
        # line rate would cost hundreds of ms/pass on this thread
        self._seen = np.empty(0, np.uint64)
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
        from paddlebox_tpu.embedding.pass_table import sorted_member
        try:
            done = False
            while not done:
                chunk = self._q.get()
                if chunk is None:
                    return
                # drain everything already queued: readers feed many small
                # chunks, and one union over the batch beats one re-sort
                # of the accumulated set per chunk
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
                chunk = np.concatenate(parts)
                if not chunk.size:
                    continue
                cand = np.unique(chunk)
                cand = cand[~self._known(cand)]
                if cand.size:
                    cand = cand[~sorted_member(self._seen, cand)[1]]
                if not cand.size:
                    continue
                self._seen = np.union1d(self._seen, cand)
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
    """The feed pass of the next pass, planned on a thread of its own
    under the current pass's training: joins the dataset's load (the
    final concat and the quality pass run on that join), then plans over
    the buffered keys on ``base`` (PassTable.plan_feed_pass, which writes
    no field of the table). Its spans carry the pass it plans for, and
    the plan carries their stamps (FeedPlan.stamps) to the boundary that
    consumes it, where PassPreloader.wait accounts them."""

    def __init__(self, table, dataset, buffer: List[np.ndarray],
                 base) -> None:
        self._plan = None
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=with_current_trace(self._run),
            args=(table, dataset, buffer, base), daemon=True,
            name="feed-ahead")
        self._thread.start()

    def _run(self, table, dataset, buffer, base) -> None:
        try:
            with obs_span("ingest_feed_ahead") as ahead:
                with obs_span("ingest_load_join") as join:
                    dataset.wait_preload_done()
                plan = table.plan_feed_pass(buffer, base)
            plan.stamps["ingest_load_join"] = (join.t0, join.t1)
            plan.stamps["ingest_feed_ahead"] = (ahead.t0, ahead.t1)
            self._plan = plan
        except BaseException as e:  # surfaced at finish()
            self._err = e

    def finish(self):
        """Join the worker and return its FeedPlan, or raise what it
        raised (the load's error, the plan's capacity check)."""
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


def account_feed_plan(stamps: Dict[str, Tuple[float, float]],
                      t_ask: float) -> None:
    """Work done ahead for a pass, accounted to the pass that consumes it
    at the moment it is consumed: the chain's length and its four stages
    (whole microseconds of the spans' own perf_counter pairs; a plan with
    no base has no diff: 0), and the slack, how long the finished plan lay
    waiting before wait() asked for it at ``t_ask``. Slack and a
    non-trivial ingest_wait_preload exclude each other; a plan redone on
    the boundary still adds the chain that made it. The two gauges hold
    the newest pass's values for the pass report, /metrics and the flight
    recorder. With tracing off every stamp is 0.0 and so is every sum."""
    def whole_us(t0: float, t1: float) -> int:
        return int((t1 - t0) * 1e6)

    t0, t_done = stamps["ingest_feed_ahead"]
    slack = max(0, whole_us(t_done, t_ask))
    for counter, name in FEED_PLAN_COUNTERS:
        stat_add(counter, whole_us(*stamps.get(name, (0.0, 0.0))))
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

    def preload(self, dataset) -> None:
        """Start the next pass's read threads; returns immediately. When
        the incremental lifecycle is active, a PromotePrefetcher also
        starts pulling the next pass's non-resident rows from the host
        store under the current pass's training. A table that can plan a
        feed pass apart from installing it (PassTable) gets a feed-ahead
        thread, which joins the load and plans under that training too."""
        if self._dataset is not None:
            raise RuntimeError("a preload is already in flight")
        self._buffer = []
        self._dataset = dataset
        ctx_fn = getattr(self.table, "promote_prefetch_ctx", None)
        ctx = ctx_fn() if ctx_fn is not None else None
        try:
            if ctx is not None:
                self._prefetch = PromotePrefetcher(*ctx)
                buf = self._buffer
                pre = self._prefetch

                def add(keys):
                    buf.append(keys)
                    pre.feed(keys)

                dataset.preload_into_memory(add_keys_fn=add)
            else:
                dataset.preload_into_memory(add_keys_fn=self._buffer.append)
            if hasattr(self.table, "plan_feed_pass"):
                # the base is read here, on the thread that installs and
                # ends passes: the pass installed now has not begun
                self._ahead = FeedAhead(self.table, dataset, self._buffer,
                                        self.table.next_base())
        except BaseException:
            # a failed launch must not wedge the preloader (or leave the
            # prefetch worker parked on its queue forever)
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
                account_feed_plan(plan.stamps, asked.t0)
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
    """
    allgather = None
    if getattr(trainer, "multiprocess", False):
        allgather = trainer.fleet.all_gather
    pre = PassPreloader(trainer.table)
    rank = obs_log.get_rank()
    results: List[Dict[str, float]] = []
    it = iter(datasets)
    cur = next(it, None)
    if cur is None:
        return results
    with trace_ctx(pass_trace_id(rank, 0)):
        pre.preload(cur)
    while cur is not None:
        k = len(results)  # cur is pass k of this call
        with trace_ctx(pass_trace_id(rank, k)):
            pre.wait(cur, allgather=allgather)
            nxt = next(it, None)
            if nxt is not None:
                # start pass N+1's read threads and its feed-ahead
                # plan BEFORE training pass N
                with trace_ctx(pass_trace_id(rank, k + 1)):
                    pre.preload(nxt)
            results.append(trainer.train_pass(cur, preloaded=True))
            if after_pass is not None:
                after_pass(k, results[-1])
            if release:
                with obs_span("pass_release"):
                    cur.release_memory()
        cur = nxt
    return results
