"""Streaming continuous training: the day/pass cadence collapsed into a
zero-stall micro-pass pipeline.

``StreamingRunner`` drives a trainer from a ``StreamingDataset``
(data/streaming.py) the way ``run_preloaded_passes`` drives a day's
datasets, generalized to an unbounded cadence:

  * a fetcher thread forms micro-pass windows (watcher poll + line
    count + BoxDataset construction — no jax, no table state) while
    the train thread works, double-buffered through a bounded queue;
  * window N+1's parse→shuffle→pack readers start (preload) BEFORE
    window N trains, so the train thread never stalls on ingest while
    the stream keeps up — the stall it CAN see (a genuinely dry
    source) is measured and reported per pass as ``ingest_wait_secs``;
  * each loaded window passes **drift-gated admission** before it
    trains: a SlotDriftMonitor preview against the rolling reference
    of admitted windows; a poisoned window is refused BEFORE
    begin_pass, so it never mutates the store and never enters the
    reference;
  * every micro-pass boundary publishes the journal (seals the active
    segment — the serving fleet's JournalDeltaSource flips served
    vectors from those bytes without waiting on the SaveDelta
    cadence) and every K admitted passes lands a decimated
    ``save_base(mode='auto')`` micro-checkpoint through the PR-10
    rotation machinery;
  * freshness/lag gauges (``streaming_ingest_lag_secs``,
    ``streaming_publish_lag_secs``) ride the StatRegistry into
    ``/metrics``, and a ``micro_pass`` event goes through the
    trainer's StepReporter at each boundary.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List, Optional

from paddlebox_tpu.config import flags
from paddlebox_tpu.metrics.drift import SlotDriftMonitor
from paddlebox_tpu.obs import log as obs_log
from paddlebox_tpu.obs import watermark as obs_watermark
from paddlebox_tpu.obs.tracer import (current_trace, set_trace,
                                      span as obs_span, step_trace_id)
from paddlebox_tpu.train.preload import PassPreloader
from paddlebox_tpu.utils.stats import gauge_set, stat_add


class _GatedPreloader(PassPreloader):
    """PassPreloader.wait with the runner's admission gate (its admit_fn:
    refusing a window leaves the table and the store exactly as they
    were) under the streaming timeline's span names."""

    WAIT_SPAN = "streaming_wait_ingest"
    FEED_SPAN = "streaming_feed_pass"


class StreamingRunner:
    """Continuous micro-pass training over a StreamingDataset.

    trainer: BoxTrainer/ShardedBoxTrainer (train_pass(ds,
    preloaded=True)); stream: StreamingDataset; cm: optional
    CheckpointManager — when given (with its journal attached), the
    runner publishes journal segments at every boundary and lands
    ``save_base(mode='auto')`` every ``streaming_base_every`` admitted
    passes under day labels ``stream-NNNNNN``.

    Thread contract: run() owns the train thread; one private fetcher
    thread only forms windows (stream.next_window — watcher + file IO,
    no table/trainer state); they meet at a bounded queue.
    """

    def __init__(self, trainer, stream, cm=None,
                 base_every: Optional[int] = None,
                 admission_max_drift: Optional[float] = None,
                 drift_monitor: Optional[SlotDriftMonitor] = None) -> None:
        self.trainer = trainer
        self.stream = stream
        self.cm = cm
        self.base_every = int(
            base_every if base_every is not None
            else flags.get_flag("streaming_base_every"))
        self.admission_max_drift = float(
            admission_max_drift if admission_max_drift is not None
            else flags.get_flag("streaming_admission_max_drift"))
        self.monitor = drift_monitor or SlotDriftMonitor()
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._fetcher: Optional[threading.Thread] = None
        self._fetch_err: Optional[BaseException] = None
        self._eos = False
        self._stop = threading.Event()
        self.admitted = 0
        self.refused = 0
        self.passes: List[Dict] = []

    # ------------------------------------------------------------- fetcher
    def _fetch_loop(self, max_windows: Optional[int],
                    idle_timeout: float) -> None:
        try:
            n = 0
            while not self._stop.is_set():
                if max_windows is not None and n >= max_windows:
                    break
                deadline = (time.time() + idle_timeout
                            if idle_timeout > 0 else None)
                win = self.stream.next_window(deadline=deadline)
                if win is None:
                    break  # idle timeout or stream stopped
                # bounded put: at most 2 formed-but-untrained windows in
                # flight (the double buffer); blocks the FETCHER, never
                # the train thread
                while not self._stop.is_set():
                    try:
                        self._q.put(win, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                n += 1
        except BaseException as e:  # surfaced on the train thread
            self._fetch_err = e
        finally:
            while True:
                try:
                    self._q.put(None, timeout=0.2)
                    break
                except queue.Full:
                    if self._stop.is_set():
                        break

    def _next(self, block: bool) -> Optional[object]:
        """Pop the next formed window. Returns None when nothing is
        ready (non-blocking) or the stream ended — the end sentinel
        latches ``_eos`` so a later blocking pop can't hang on a dead
        fetcher."""
        if self._eos:
            return None
        try:
            win = self._q.get(block=block)
        except queue.Empty:
            return None
        if win is None:
            self._eos = True
            if self._fetch_err is not None:
                raise self._fetch_err
            return None
        return win

    # ------------------------------------------------------------ admission
    def _admit(self, win) -> bool:
        """Score the loaded window before it touches the table."""
        if self.admission_max_drift <= 0:
            win.drift_score = 0.0
            return True
        block = getattr(win.dataset, "block", None)
        if block is None:  # record-path load: nothing to score against
            win.drift_score = 0.0
            return True
        score = self.monitor.preview_block(block)
        win.drift_score = score
        gauge_set("streaming_admission_score", score)
        if score >= self.admission_max_drift:
            stat_add("streaming_windows_refused")
            obs_log.warning(
                "streaming admission refused a micro-pass window",
                window=win.index, score=score,
                threshold=self.admission_max_drift,
                files=str([f.rsplit("/", 1)[-1] for f in win.files][:4]))
            return False
        # only ADMITTED windows advance the rolling reference — a
        # poisoned burst can't normalize itself into "the new normal"
        self.monitor.admit_block(block)
        return True

    # ------------------------------------------------------------- boundary
    def _boundary(self, win, admitted: bool) -> None:
        """Micro-pass boundary: journal publish (the serving-freshness
        edge), decimated micro-checkpoint, ledger commit, gauges."""
        journal = self.cm.journal if self.cm is not None else None
        if journal is not None and admitted:
            with obs_span("streaming_publish"):
                if obs_watermark.enabled():
                    # watermark plane (round 20): the window's born-ts
                    # span + this boundary's trace id ride the segment
                    # into the serving tailer — feed-to-serve freshness
                    # becomes measurable at the pull, and the serving
                    # apply span lands on THIS stitched timeline
                    journal.publish(
                        born_min=getattr(win, "born_min_ts", win.born_ts),
                        born_max=win.born_ts, trace=current_trace())
                else:
                    journal.publish()
            lag = max(0.0, time.time() - win.born_ts)
            gauge_set("streaming_publish_lag_secs", lag)
        if (admitted and self.cm is not None and self.base_every > 0
                and (self.admitted == 1
                     or self.admitted % self.base_every == 0)):
            # the FIRST admitted pass always lands a base: the full-save
            # anchor opens the journal epoch immediately, so every later
            # decimated save is a cheap touched one and segment history
            # never grows unanchored
            with obs_span("streaming_micro_checkpoint"):
                self.cm.save_base(self.trainer.params,
                                  self.trainer.opt_state,
                                  day="stream-%06d" % win.index,
                                  mode="auto")
        self.stream.commit_window(win)
        stat_add("streaming_micro_passes")
        rep = getattr(self.trainer, "reporter", None)
        if rep is not None:
            rep.maybe_report(
                getattr(self.trainer, "_step_count", 0), force=True,
                extra={"event": "micro_pass", "window": win.index,
                       "admitted": admitted,
                       "instances": win.instances,
                       "drift_score": round(
                           getattr(win, "drift_score", 0.0), 4)})

    # ------------------------------------------------------------------ run
    def run(self, max_micro_passes: Optional[int] = None,
            idle_timeout: Optional[float] = None) -> Dict:
        """Drive micro-passes until the stream goes dry (idle_timeout,
        default flag streaming_idle_timeout_secs), max_micro_passes
        windows were processed, or stop(). Returns aggregate stats with
        the per-pass list under "passes"."""
        if idle_timeout is None:
            idle_timeout = float(
                flags.get_flag("streaming_idle_timeout_secs"))
        allgather = None
        if getattr(self.trainer, "multiprocess", False):
            allgather = self.trainer.fleet.all_gather
        self._stop.clear()
        self._eos = False
        self.passes = []
        self.admitted = 0
        self.refused = 0
        resume = getattr(self.stream, "resume", None)
        if resume is not None:  # re-runnable after a prior drain
            resume()
        self._fetcher = threading.Thread(
            target=self._fetch_loop, args=(max_micro_passes, idle_timeout),
            daemon=True, name="stream-fetch")
        self._fetcher.start()
        pre = _GatedPreloader(self.trainer.table)
        t_run = time.perf_counter()
        instances = 0
        try:
            wait0 = time.perf_counter()
            cur = self._next(block=True)
            cur_wait = time.perf_counter() - wait0
            if cur is not None:
                pre.preload(cur.dataset)
            while cur is not None and not self._stop.is_set():
                t0 = time.perf_counter()
                win = cur
                # one stitched timeline per micro-pass: every span this
                # window records on the train thread (ingest wait, feed
                # pass, train, publish, micro-checkpoint) carries the
                # same trace id, and the published watermark forwards
                # it to the serving tailer's apply span
                set_trace(step_trace_id(obs_log.get_rank(), cur.index))
                admitted = pre.wait(
                    cur.dataset, allgather=allgather,
                    admit_fn=lambda _ds: self._admit(win))
                ingest_wait = cur_wait + (time.perf_counter() - t0)
                # overlap: window N+1's readers start BEFORE N trains
                nxt = self._next(block=False)
                if nxt is not None:
                    pre.preload(nxt.dataset)
                stats: Dict = {"window": cur.index, "admitted": admitted,
                               "instances": cur.instances,
                               "drift_score": getattr(cur, "drift_score",
                                                      0.0)}
                if admitted:
                    lag = max(0.0, time.time() - cur.born_ts)
                    gauge_set("streaming_ingest_lag_secs", lag)
                    stats["ingest_lag_secs"] = lag
                    t1 = time.perf_counter()
                    stats.update(self.trainer.train_pass(cur.dataset,
                                                         preloaded=True))
                    stats["train_secs"] = time.perf_counter() - t1
                    self.admitted += 1
                    instances += cur.instances
                else:
                    self.refused += 1
                self._boundary(cur, admitted)
                cur.dataset.release_memory()
                stats["ingest_wait_secs"] = ingest_wait
                self.passes.append(stats)
                if nxt is None and not self._eos:
                    # stream-bound: the only wait the train thread may
                    # see — bounded by the source, measured per pass
                    wait0 = time.perf_counter()
                    nxt = self._next(block=True)
                    cur_wait = time.perf_counter() - wait0
                    if nxt is not None:
                        pre.preload(nxt.dataset)
                else:
                    cur_wait = 0.0
                cur = nxt
        finally:
            set_trace(None)
            self._stop.set()
            self.stream.stop()
            # drain the queue so the fetcher's put can't wedge the join
            while True:
                try:
                    win = self._q.get_nowait()
                except queue.Empty:
                    break
                if win is not None:
                    win.dataset.release_memory()
            if self._fetcher is not None:
                self._fetcher.join(timeout=30.0)
        if self._fetch_err is not None:
            raise self._fetch_err
        wall = max(time.perf_counter() - t_run, 1e-9)
        rate = instances / wall
        gauge_set("streaming_examples_per_sec", rate)
        return {"micro_passes": len(self.passes),
                "admitted": self.admitted, "refused": self.refused,
                "instances": instances, "wall_secs": wall,
                "examples_per_sec": rate,
                "max_ingest_wait_secs": max(
                    (p["ingest_wait_secs"] for p in self.passes),
                    default=0.0),
                "passes": self.passes}

    def stop(self) -> None:
        """Ask the pipeline to wind down after the current micro-pass."""
        self._stop.set()
        self.stream.stop()
