"""Pass-scoped in-memory dataset with threaded load pipeline.

TPU-native PadBoxSlotDataset (paddle/fluid/framework/data_set.h:438-566,
data_set.cc:2217-2817): a pass's files are read by N threads into a channel,
optionally shuffled across hosts (data/shuffle.py transport), merged while
registering every feasign with the table's feed-pass agent (MergeInsKeys →
AddKeys, data_set.cc:2291-2347), then split into equalized per-worker batch
ranges for training (PrepareTrain, data_set.cc:2775-2817).

The preload/wait split mirrors BoxHelper::PreLoadIntoMemory/WaitFeedPassDone
(box_wrapper.h:1131-1172) so pass N+1 loads while pass N trains.
"""

from __future__ import annotations

import threading
from collections.abc import Sequence
from typing import Callable, List, Optional

import numpy as np

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import DataFeedConfig
from paddlebox_tpu.data.columnar import ColumnarBlock
from paddlebox_tpu.data.packer import BatchPacker, PackedBatch
from paddlebox_tpu.data.parser import MultiSlotParser
from paddlebox_tpu.data.slot_record import SlotRecord
from paddlebox_tpu.obs.tracer import span as obs_span
from paddlebox_tpu.obs.tracer import with_current_trace
from paddlebox_tpu.utils.channel import Channel, ChannelClosed
from paddlebox_tpu.utils.stats import stat_add

# add_keys_fn(keys: np.ndarray) registers pass keys (PSAgent AddKeys analog)
AddKeysFn = Callable[[np.ndarray], None]


class BatchPlan(Sequence):
    """One worker's share of BoxDataset.split_batches: which records make
    up each of its batches (a slice of the permutation, or of the record
    list, a batch) and the function that packs one. Read-only. plan[i] and
    iteration pack, each time they are asked and on the asking thread;
    len() and a slice pack nothing. Counter ingest_batches_packed_lazy
    counts the packs."""

    __slots__ = ("_pack", "_chunks")

    def __init__(self, pack: Callable[..., PackedBatch], chunks) -> None:
        self._pack = pack
        self._chunks = tuple(chunks)

    def __len__(self) -> int:
        return len(self._chunks)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return BatchPlan(self._pack, self._chunks[i])
        chunk = self._chunks[i]
        stat_add("ingest_batches_packed_lazy", 1)
        return self._pack(chunk)


# its only Lock guards method-local state (the read-worker file cursor,
# a local in load_into_memory); cross-thread hand-off rides the Channel,
# which carries its own guarded-by contract
class BoxDataset:  # boxlint: disable=BX403
    def __init__(self, feed: DataFeedConfig, read_threads: int = 4,
                 parser: Optional[MultiSlotParser] = None,
                 shuffler=None, columnar: Optional[bool] = None,
                 input_table=None, use_cache_idx: bool = False) -> None:
        """input_table / use_cache_idx: aux-row offset sources wired
        through the packer (the InputTableDataFeed / pull_cache_value
        feed roles — see BatchPacker); they force the record path since
        offsets translate per SlotRecord."""
        self.feed = feed
        self.read_threads = read_threads
        self.parser = parser or MultiSlotParser(feed)
        self.packer = BatchPacker(feed, input_table=input_table,
                                  use_cache_idx=use_cache_idx)
        self.shuffler = shuffler  # cross-host instance shuffle transport
        self._files: List[str] = []
        self._records: List[SlotRecord] = []
        self._preload_threads: List[threading.Thread] = []
        self._merge_thread: Optional[threading.Thread] = None
        self._channel: Optional[Channel] = None
        self._add_keys_fn: Optional[AddKeysFn] = None
        self._load_error: Optional[BaseException] = None
        # columnar fast path: native C++ parser → struct-of-arrays blocks,
        # numpy-only batch packing (no per-record Python objects). Default:
        # on whenever the native lib builds — round 17: a cross-host
        # shuffler no longer forces the record path (blocks ride the
        # shuffle whole via data/block_shuffle.py's codec + vectorized
        # hash routing; flag shuffle_block_codec=False restores the
        # legacy per-record codec, which does need SlotRecords).
        # task-label config errors fail loudly on EVERY host (the native
        # parser would raise only where the lib builds; the record path
        # would silently substitute the click label)
        slot_names = {s.name for s in feed.slots}
        for task, slot_name in getattr(feed, "task_label_slots", ()):
            if slot_name not in slot_names:
                raise ValueError(
                    f"task_label_slots: slot {slot_name!r} (task {task!r}) "
                    f"not in the feed config")
        self._native_parser = None
        if columnar is None:
            # an explicitly-passed custom parser (e.g. a dlopen plugin)
            # translates per record — the built-in native columnar parse
            # would silently ignore it
            columnar = parser is None
        if columnar and feed.rank_offset:
            # pv rank-offset matrices are built from per-record pv fields
            # (search_id/rank/cmatch) which the columnar blocks don't carry
            columnar = False
        if columnar and (input_table is not None or use_cache_idx
                         or getattr(feed, "parse_ins_id", False)):
            # aux offsets and ins_id-prefixed lines translate per
            # SlotRecord; the native columnar parser reads plain lines
            columnar = False
        # per-task label feeds ride the columnar path too: the extended
        # native entry (psr_parse_file2) emits task-label columns; the
        # NativeMultiSlotParser constructor raises if the lib lacks it,
        # which downgrades to the record path below
        if columnar:
            try:
                from paddlebox_tpu.data.native_parser import \
                    NativeMultiSlotParser
                self._native_parser = NativeMultiSlotParser(feed)
            except (RuntimeError, ImportError):
                self._native_parser = None
        self.columnar = self._native_parser is not None
        self._load_columnar = self.columnar  # per-load effective mode
        self._disk_writer = None    # BinaryArchiveWriter when spilling
        self.disk_files: List[str] = []
        self._block = None          # merged ColumnarBlock
        self._perm: Optional[np.ndarray] = None  # shuffle permutation

    # ------------------------------------------------------------ file list
    def set_filelist(self, files: Sequence[str]) -> None:
        self._files = list(files)

    def my_shard_files(self, rank: int, world: int) -> List[str]:
        """Per-rank file split (data_set.cc:1961-1973)."""
        return [f for i, f in enumerate(self._files) if i % world == rank]

    # ----------------------------------------------------------- load paths
    def load_into_memory(self, add_keys_fn: Optional[AddKeysFn] = None) -> None:
        self.preload_into_memory(add_keys_fn)
        self.wait_preload_done()

    def preload_into_memory(self,
                            add_keys_fn: Optional[AddKeysFn] = None) -> None:
        """Spawn read+merge threads; returns immediately
        (PreLoadIntoMemory, data_set.cc:2217-2261)."""
        if self._preload_threads:
            raise RuntimeError("preload already running")
        self._records = []
        self._block = None
        self._perm = None
        self._add_keys_fn = add_keys_fn
        self._load_error = None
        self._channel = Channel(capacity=64, name="dataset_blocks")
        files = list(self._files)
        from paddlebox_tpu.data.archive import is_archive, read_archive
        # per-load state is captured in locals so a failed later call can't
        # flip an in-flight load's mode mid-pass
        disk_writer = self._disk_writer
        # archive inputs and disk spill stream SlotRecords, not columnar
        # blocks — downgrade this load to the record path when either is in
        # play (the archive codec round-trips full records). The eager sniff
        # sweep only runs when columnar is actually a candidate; the record
        # path sniffs lazily per file inside the read workers.
        if self.columnar and disk_writer is None:
            use_columnar = not any(is_archive(f) for f in files)
            if (use_columnar and self.shuffler is not None
                    and not flags.get_flag("shuffle_block_codec")):
                # the legacy per-record shuffle codec (the block codec's
                # bit-parity oracle) moves SlotRecords — this load runs
                # the record path so the oracle stays exercisable
                use_columnar = False
        else:
            use_columnar = False
        self._load_columnar = use_columnar
        lock = threading.Lock()
        cursor = {"i": 0}

        def read_worker():
            try:
                while True:
                    with lock:
                        if cursor["i"] >= len(files):
                            return
                        path = files[cursor["i"]]
                        cursor["i"] += 1
                    if use_columnar:
                        with obs_span("ingest_parse"):
                            block = self._native_parser.parse_file_columnar(
                                path)
                        stat_add("ingest_ins_parsed", block.n_recs)
                        stat_add("ingest_keys_parsed", block.n_keys)
                        self._put_block(block)
                    elif is_archive(path):
                        for recs in read_archive(path):
                            self._put_records(recs)
                    else:
                        batch: List[SlotRecord] = []
                        for rec in self.parser.parse_file(path):
                            batch.append(rec)
                            if len(batch) >= 512:
                                self._put_records(batch)
                                batch = []
                        if batch:
                            self._put_records(batch)
            except BaseException as e:  # surfaced in wait_preload_done
                self._load_error = e

        def merge_worker():
            """MergeInsKeys (data_set.cc:2291-2347): drain channel, register
            keys with the feed-pass agent, append to the pass memory.
            A codec mix — a peer shuffling the OTHER frame kind into this
            pass because a rank-local downgrade diverged the modes (an
            archive file in that rank's shard, a host whose native lib
            didn't build) or the shuffle_block_codec flag was split —
            CONVERTS here with a loud warning instead of failing: one
            stray shard must not kill a cluster pass load (round-17
            review), but the degraded rate must never be silent."""
            blocks = []
            mixed_warned = [False]

            def warn_mix(kind: str) -> None:
                if mixed_warned[0]:
                    return
                mixed_warned[0] = True
                from paddlebox_tpu.obs import log as obs_log
                obs_log.warning(
                    "shuffle codec mix: " + kind + " — a peer runs the "
                    "other ingest mode (archive shard? native lib "
                    "missing? split shuffle_block_codec flag?); "
                    "converting at the merge, throughput degraded")

            try:
                while True:
                    try:
                        items = self._channel.get_many(256)
                    except ChannelClosed:
                        break
                    stray = [it for it in items
                             if isinstance(it, ColumnarBlock)
                             is not use_columnar]
                    if stray:
                        items = [it for it in items
                                 if isinstance(it, ColumnarBlock)
                                 is use_columnar]
                    if use_columnar:
                        if stray:
                            warn_mix("record frames in a columnar pass")
                            from paddlebox_tpu.data.block_shuffle import \
                                records_to_block
                            items = items + [records_to_block(stray,
                                                              self.feed)]
                            stat_add("ingest_codec_mix_converted",
                                     len(stray))
                        with obs_span("ingest_merge"):
                            for block in items:
                                if (self._add_keys_fn is not None
                                        and block.n_keys):
                                    self._add_keys_fn(block.keys)
                                blocks.append(block)
                                stat_add("dataset_ins_merged", block.n_recs)
                        continue
                    recs = items
                    if stray:
                        warn_mix("columnar block frames in a "
                                 "record-path pass")
                        from paddlebox_tpu.data.block_shuffle import \
                            block_to_records
                        for b in stray:
                            recs = recs + block_to_records(b, self.feed)
                            stat_add("ingest_codec_mix_converted",
                                     b.n_recs)
                    if disk_writer is not None:
                        # disk spill: keys are registered when the archives
                        # are loaded back, not at dump time (PreLoadIntoDisk,
                        # data_set.cc:2090-2215)
                        disk_writer.write_records(recs)
                        stat_add("dataset_ins_spilled", len(recs))
                    else:
                        with obs_span("ingest_merge"):
                            if self._add_keys_fn is not None:
                                keys = [r.all_keys() for r in recs]
                                keys = [k for k in keys if k.size]
                                if keys:
                                    self._add_keys_fn(np.concatenate(keys))
                            self._records.extend(recs)
                        stat_add("dataset_ins_merged", len(recs))
                if use_columnar:
                    self._block = ColumnarBlock.concat(blocks)
                    if self._block.n_recs:
                        # slot-level data-quality monitor (round 18,
                        # flag data_quality): one vectorized pass over
                        # the merged block's columns on this merge
                        # thread — the runners roll the window at
                        # pass_end (metrics/drift.py)
                        from paddlebox_tpu.metrics import drift as _drift
                        with obs_span("ingest_quality"):
                            _drift.observe_block(self._block)
                return
            except BaseException as e:
                self._load_error = e
                # keep draining so blocked readers can finish instead of
                # deadlocking on the bounded channel; error surfaces in
                # wait_preload_done
                try:
                    while True:
                        self._channel.get_many(256)
                except ChannelClosed:
                    pass

        # the pass these threads load for (train/preload.py holds its id
        # around this call): their spans carry it, not the training pass's
        read_worker = with_current_trace(read_worker)
        readers = [threading.Thread(target=read_worker, daemon=True)
                   for _ in range(max(1, self.read_threads))]
        for th in readers:
            th.start()
        self._preload_threads = readers
        self._merge_thread = threading.Thread(
            target=with_current_trace(merge_worker), daemon=True)
        self._merge_thread.start()

    def _put_records(self, recs: List[SlotRecord]) -> None:
        """Route through cross-host shuffle when configured
        (ShuffleData, data_set.cc:2438-2545)."""
        stat_add("ingest_ins_parsed", len(recs))
        if self.shuffler is not None and not flags.get_flag(
                "dataset_disable_shuffle"):
            with obs_span("ingest_shuffle"):
                self.shuffler.scatter(recs, self._channel)
        else:
            self._channel.put_many(recs)

    def _put_block(self, block) -> None:
        """Columnar twin of _put_records (round 17): the whole parsed
        block routes through the cross-host shuffle — vectorized hash
        over rec_offsets, fancy-index split, per-destination sub-block
        frames (ShufflerBase.scatter_block) — so shuffled jobs stay
        zero-object end to end."""
        if self.shuffler is not None and not flags.get_flag(
                "dataset_disable_shuffle"):
            with obs_span("ingest_shuffle"):
                self.shuffler.scatter_block(block, self._channel)
        else:
            self._channel.put(block)

    def wait_preload_done(self) -> None:
        """WaitFeedPassDone half: join readers, drain merge
        (data_set.cc:2262)."""
        for th in self._preload_threads:
            th.join()
        flush_error: Optional[BaseException] = None
        try:
            if self.shuffler is not None:
                with obs_span("ingest_shuffle_flush"):
                    self.shuffler.flush(self._channel)
        except BaseException as e:
            # a dead peer must not leave the merge thread blocked on a
            # never-closed channel and the dataset stuck in "preload
            # already running"
            flush_error = e
        finally:
            self._channel.close()
            if self._merge_thread is not None:
                self._merge_thread.join()
            self._preload_threads = []
            self._merge_thread = None
            if self._disk_writer is not None:
                self.disk_files = self._disk_writer.close()
                self._disk_writer = None
        if self._load_error is not None:
            # the load error is the root cause (a dead reader also starves
            # the shuffle); surface it over any secondary flush failure
            raise RuntimeError("dataset load failed") from self._load_error
        if flush_error is not None:
            raise RuntimeError(
                "cross-host shuffle flush failed") from flush_error

    # -------------------------------------------------------------- disk spill
    def preload_into_disk(self, out_prefix: str,
                          max_bytes: int = 0) -> None:
        """Read (+cross-host shuffle) the pass and spill it to rotating
        binary archive shards instead of RAM (PreLoadIntoDisk/DumpIntoDisk,
        data_set.cc:2090-2215). Resulting shard paths land in
        `self.disk_files` after wait_preload_done(); feed them back via
        set_filelist + load_into_memory to train from the spill."""
        from paddlebox_tpu.data.archive import BinaryArchiveWriter
        if self._preload_threads:
            raise RuntimeError("preload already running")
        self._disk_writer = BinaryArchiveWriter(out_prefix, max_bytes)
        self.disk_files = []
        try:
            self.preload_into_memory(None)
        except BaseException:
            self._disk_writer = None
            raise

    def load_into_disk(self, out_prefix: str, max_bytes: int = 0) -> None:
        self.preload_into_disk(out_prefix, max_bytes)
        self.wait_preload_done()

    def slots_shuffle(self, slot_indices: Sequence[int],
                      seed: Optional[int] = None) -> None:
        """Permute the given slots' feasign lists ACROSS records, leaving
        every other slot in place (BoxHelper::SlotsShuffle, box_wrapper.h:
        1174-1198) — the AUC-runner's feature-ablation primitive: retrain/
        re-eval with one slot decorrelated and measure the AUC drop."""
        if self._load_columnar:
            raise RuntimeError("slots_shuffle needs the record path "
                               "(construct the dataset with columnar=False)")
        rng = np.random.RandomState(seed)
        n = len(self._records)
        for si in slot_indices:
            vals = [r.uint64_slots.get(si) for r in self._records]
            perm = rng.permutation(n)
            for r, j in zip(self._records, perm):
                v = vals[j]
                if v is None:
                    r.uint64_slots.pop(si, None)
                else:
                    r.uint64_slots[si] = v

    # -------------------------------------------------------------- train prep
    def local_shuffle(self, seed: Optional[int] = None) -> None:
        if flags.get_flag("dataset_disable_shuffle"):
            # FLAGS_padbox_dataset_disable_shuffle (flags.cc:969): keep load
            # order — deterministic runs / cross-process parity tests
            return
        rng = np.random.RandomState(seed)
        if self._load_columnar:
            if self._block is not None and self._block.n_recs:
                self._perm = rng.permutation(self._block.n_recs)
        else:
            rng.shuffle(self._records)

    @property
    def records(self) -> List[SlotRecord]:
        return self._records

    @property
    def block(self):
        return self._block

    def all_keys(self) -> np.ndarray:
        """Every feasign in the loaded pass (for test-mode feed passes)."""
        if self._load_columnar:
            return (self._block.keys if self._block is not None
                    else np.empty(0, np.uint64))
        if not self._records:
            return np.empty(0, np.uint64)
        return np.concatenate([r.all_keys() for r in self._records])

    def __len__(self) -> int:
        if self._load_columnar:
            return self._block.n_recs if self._block is not None else 0
        return len(self._records)

    def release_memory(self) -> None:
        self._records = []
        self._block = None
        self._perm = None

    def split_batches(self, num_workers: int,
                      equalize: Optional[Callable[[int], int]] = None
                      ) -> List[BatchPlan]:
        """Equalized per-worker batch split (compute_paddlebox_thread_batch,
        data_set.cc:2690-2755): every worker gets the SAME number of batches
        so lockstep collectives never deadlock; short workers wrap around.

        Returns the split, not the batches: per worker a BatchPlan, a
        read-only sequence that knows which records make up each batch. A
        batch is PACKED WHEN IT IS TAKEN (`plan[i]`, iteration) and on the
        thread that takes it: BoxTrainer's chunk-stager, one chunk ahead of
        the device; a caller that walks its batches twice says list(plan).
        `len(plan)` costs nothing and `plan[a:b]` is a plan that has packed
        nothing. A plan holds the block (or the records) and the
        permutation as they were at the split, never the dataset, so
        release_memory(), a reload or another local_shuffle() cannot change
        a batch that is still to be taken.

        equalize: optional allreduce-max over hosts of the local batch count
        (MPI allreduce analog); receives local count, returns global max.
        """
        bs = self.feed.batch_size
        n = len(self)
        per_worker = (n + num_workers - 1) // num_workers
        local_batches = (per_worker + bs - 1) // bs if n else 0
        target = equalize(local_batches) if equalize else local_batches
        if self._load_columnar:
            from paddlebox_tpu.data.columnar import pack_columnar
            block, feed = self._block, self.feed
            sparse_slots = feed.used_sparse_slots()
            max_lens = np.array([s.max_len for s in sparse_slots], np.int64)
            kcap = feed.key_capacity()
            num_slots = len(sparse_slots)
            recs_all = (self._perm if self._perm is not None
                        else np.arange(n, dtype=np.int64))

            def pack(chunk):
                return pack_columnar(block, chunk, feed, kcap, num_slots,
                                     max_lens)
        else:
            # local_shuffle() shuffles the list in place: the slices below
            # are the plan's own copies
            recs_all = self._records
            pack = self.packer.pack
        out: List[BatchPlan] = []
        for w in range(num_workers):
            lo = w * per_worker
            recs = recs_all[lo:min(lo + per_worker, n)]
            chunks = []
            for b in range(target):
                chunk = recs[b * bs:(b + 1) * bs]
                if not len(chunk) and len(recs):
                    # wrap around to equalize step counts
                    chunk = recs[:bs]
                if not len(chunk):
                    chunk = recs_all[:bs]
                chunks.append(chunk)
            out.append(BatchPlan(pack, chunks))
        return out
