"""Process-level flag registry with environment override.

TPU-native analog of the reference's gflags tier (PADDLE_DEFINE_EXPORTED_* in
paddle/fluid/platform/flags.cc; box-cluster flags at flags.cc:946-975). Flags
are declared in code with a typed default and can be overridden by environment
variables named ``PBTPU_<FLAG_NAME>`` (mirroring the ``FLAGS_*`` env convention).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict

_REGISTRY: Dict[str, "_Flag"] = {}
_LOCK = threading.Lock()

_ENV_PREFIX = "PBTPU_"


class _Flag:
    __slots__ = ("name", "default", "value", "help", "parser", "from_env")

    def __init__(self, name: str, default: Any, help: str, parser: Callable[[str], Any]):
        self.name = name
        self.default = default
        self.help = help
        self.parser = parser
        env_name = _ENV_PREFIX + name.upper()
        env = os.environ.get(env_name)
        if env is not None:
            try:
                self.value = parser(env)
            except ValueError as e:
                raise ValueError(
                    f"invalid value {env!r} for flag {name!r} "
                    f"(from env {env_name}): {e}") from e
            self.from_env = True
        else:
            self.value = default
            self.from_env = False


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def _parser_for(default: Any) -> Callable[[str], Any]:
    if isinstance(default, bool):
        return _parse_bool
    if isinstance(default, int):
        return int
    if isinstance(default, float):
        return float
    return str


def define_flag(name: str, default: Any, help: str = "") -> None:
    with _LOCK:
        if name in _REGISTRY:
            raise ValueError(f"flag {name!r} already defined")
        _REGISTRY[name] = _Flag(name, default, help, _parser_for(default))


def get_flag(name: str) -> Any:
    return _REGISTRY[name].value


def set_flag(name: str, value: Any) -> None:
    flag = _REGISTRY[name]
    if not isinstance(value, type(flag.default)) and flag.default is not None:
        value = flag.parser(str(value))
    flag.value = value


def all_flags() -> Dict[str, Any]:
    return {k: f.value for k, f in sorted(_REGISTRY.items())}


# ---------------------------------------------------------------------------
# Core flag set (parity with the box-cluster flag block, flags.cc:946-975, plus
# worker flags boxps_worker.cc:41-54, re-expressed for the TPU runtime).
# ---------------------------------------------------------------------------

# Reference flags that are STRUCTURAL NO-OPS here and therefore do not
# exist (deliberate divergences, see ARCHITECTURE.md):
#   enable_pullpush_dedup_keys — dedup is load-bearing in the fused step's
#       merge-then-optimize contract, never optional
#   padbox_record_pool_max_size / padbox_slotrecord_extend_dim — the
#       zero-object columnar path replaces the SlotObjPool; expand dims
#       live in TableConfig.expand_embed_dim
#   padbox_dataset_disable_polling — readers consume a fixed file list,
#       no polling loop exists
#   enable_sparse_push_barrier — the push is part of the fused step; there
#       is no async push stream to barrier on
#   feed-pass/shuffle/merge thread counts — read parallelism is
#       BoxDataset(read_threads=...); key registration and merge ride the
#       channel consumer; per-chunk staging parallelism is stack_threads

define_flag("shuffle_block_codec", True,
            "cross-host instance shuffle rides whole ColumnarBlocks "
            "(round 17, data/block_shuffle.py): header + raw column "
            "bytes per frame (whole-array tobytes/frombuffer), "
            "destination from ONE vectorized hash over rec_offsets "
            "(bit-parity with SlotRecord.shuffle_hash), fancy-index "
            "split into per-destination sub-blocks — zero per-record "
            "Python end to end. Off = the legacy per-record codec (the "
            "parity oracle; forces the record-path load for shuffled "
            "datasets). Keep it identical on every host for line rate: "
            "mixed frame kinds (also from a RANK-LOCAL downgrade — an "
            "archive file in one rank's shard, a host whose native lib "
            "didn't build) CONVERT at the merge worker with a loud "
            "warning — one stray shard degrades throughput, never "
            "kills the cluster pass")
define_flag("shuffle_connect_secs", 20.0,
            "TcpShuffler peer dial timeout in seconds: a dead peer "
            "raises ShufflePeerUnreachable naming the endpoint instead "
            "of the OS-default ~2-minute connect stall (the utils/"
            "rpc.py round-9 hygiene applied to the shuffle transport). "
            "Established-connection sends stay unbounded — the flush "
            "done-barrier timeout bounds the pass")
define_flag("dataset_disable_shuffle", False,
            "disable BOTH the cross-host instance shuffle stage and local "
            "in-memory shuffling (deterministic load-order passes)")
define_flag("auc_runner_mode", False,
            "AUC-runner replay mode (slots-shuffle evaluation)")
define_flag("check_nan_inf", False,
            "default for TrainerConfig.check_nan_inf: after each batch, "
            "check the loss for NaN/Inf and raise (FLAGS_check_nan_inf)")
define_flag("padbox_max_batch_keys", 0,
            "static per-batch key capacity override; 0 = derive from the "
            "feed config (DataFeedConfig.key_capacity)")
define_flag("sparse_table_load_factor", 0.75,
            "native host hash table resize load factor (hashtable.h:211)")
define_flag("dump_file_max_bytes", 2 << 30,
            "rotation size for debug dump files (2GB like dump writers)")
define_flag("chunk_prefetch_depth", 1,
            "single-host trainer: scan chunks staged AHEAD on a producer "
            "thread while the device trains (the shard_batches stager "
            "role; peak extra memory = this many staged chunks); 0 = "
            "stage inline between dispatches")
define_flag("h2d_uid_wire", True,
            "the sharded runners' push staging (ShardedBoxTrainer, the "
            "pipeline runner; sharded_table.stage_push_dedup): on = stage "
            "ONLY the per-destination SORTED deduped uid vectors and "
            "derive perm/inverse/position maps on device from the a2a'd "
            "bucket ids (push_sparse_uidwire: searchsorted + segment "
            "scatter-add + scatter-min); off = stage the full host "
            "products. Bit-identical either way. The one-chip BoxTrainer "
            "does not read it: its wire is _host_batch's")
define_flag("stack_threads", 4,
            "host batch-staging threads per scan chunk (lookup + dedup; "
            "the feed-thread pool role, box_wrapper.h:862); <=1 = serial")
define_flag("stager_threads", 4,
            "sharded-trainer routing threads: per-worker bucketize and "
            "per-destination push dedup fan out on this pool inside the "
            "stager (reference 20/30 reader/merge threads, "
            "flags.cc:966-968); <=1 = serial")
define_flag("stream_depth", 2,
            "sharded-trainer input stream: staged-ahead step queue depth "
            "(peak live routed steps is this + 2: one in the consumer's "
            "hands, one in flight on the stager thread; boxps "
            "device_reader_->Next double-buffer role)")
define_flag("push_write", "auto",
            "how the push writes updated rows back into the pass slab: "
            "'auto' | 'scatter' | 'rebuild'. 'scatter' = donated row "
            "scatter, cost ~ touched rows; 'rebuild' = pos map + full slab "
            "gather/select, flat cost ~ slab bytes (pos host-staged by "
            "BoxTrainer, device-derived on the sharded uid wire). 'auto' "
            "= rebuild on the TPU where pass_capacity <= 16 x the batch's "
            "key budget, scatter otherwise and on CPU: deepfm-criteo "
            "(67.1M rows against 79,872 keys) scatters, the sequence "
            "towers (16k-32k rows against 8k-16k keys) rebuild. The 16x "
            "crossover was never measured against the other write at "
            "either shape. The 'log' and 'blocked' writes were deleted: "
            "no cell selected them")
define_flag("slab_embed_dtype", "float32",
            "DEVICE slab storage precision for the embedding weight "
            "columns (round-11 dtype diet): 'float32' = the classic "
            "homogeneous f32 [capacity, width] slab; 'bfloat16' = one "
            "uint16 slab where embed_w/embedx/expand weights store bf16 "
            "(half the bytes) and the header + ALL optimizer stats "
            "(g2sum/adam moments) store lossless f32 bit-splits — "
            "~2x pass rows per HBM byte at equal optimizer precision "
            "(accessor.ValueLayout.embed_dtype / encode_slab_rows). "
            "Host stores, checkpoints and the push/pull math stay f32; "
            "rows decode at gather and encode at write. Weight updates "
            "round to bf16 at the slab write (AUC-parity gated, "
            "tests/test_slab_bf16.py), stats round-trip bit-exactly")
define_flag("flatten_dense_opt", True,
            "wrap the dense optimizer in optax.flatten so the whole dense "
            "update runs as one fused vector op instead of per-parameter "
            "op chains (elementwise optimizers only; exact same numbers)")
define_flag("strict_bucket_overflow", False,
            "raise on sharded bucket overflow instead of dropping the "
            "overflowed keys' gradients with a warning (the "
            "PADDLE_ENFORCE discipline, box_wrapper_impl.h:139); the "
            "sharded_bucket_overflow stat counts drops either way")
define_flag("matmul_dtype", "float32",
            "dense matmul operand dtype: bfloat16 (MXU native, f32 "
            "accumulation; wins once the MLP dominates the step) or float32")
define_flag("hostplane", "p2p",
            "multi-process per-step host exchange transport (round 9): "
            "'p2p' = persistent socket mesh (fleet/mesh_comm.py) — "
            "endpoints rendezvous once through the TcpStore, then every "
            "per-step bucket/uid exchange rides direct peer connections "
            "(O(W*P*KB) bytes, true all-to-all; under h2d_uid_wire the "
            "per-destination dedup moves BEFORE the network so only "
            "sorted unique uid vectors travel), with a loud COLLECTIVE "
            "fallback to 'store' when any rank fails to dial its peers; "
            "'store' = the round-5 central TcpStore allgather funnel "
            "(O(W^2*P*KB) through one NIC + 3 counter round-trips per "
            "rank per step). Must be set identically on every rank — a "
            "split setting deadlocks the lockstep exchange")
define_flag("sharding_policy", "key-mod",
            "2-D sparse parallelism policy for the sharded pass table "
            "(round 13, parallel/sharding.py): 'key-mod' = shard by "
            "key % P (the BoxPS split_input_to_shard layout, bit-"
            "identical to the pre-policy path — the parity oracle); "
            "'table-wise' = each table pinned whole to one shard "
            "(table id from the feasign's high bits, see "
            "sharding_table_shift) so a table's sparse traffic flows "
            "only to its owner; '2d-grid' = table-group x row grid "
            "(sharding_grid_rows) with an optional replicated hot-key "
            "tier (sharding_hot_threshold). Must be set identically on "
            "every rank — the p2p rendezvous validates and fails loud "
            "on a split setting")
define_flag("sharding_num_tables", 64,
            "number of logical embedding tables the table-wise/2d-grid "
            "policies route over: table id = "
            "(key >> sharding_table_shift) % this")
define_flag("sharding_table_shift", 48,
            "bit position of the feasign's table/slot field for the "
            "table-wise/2d-grid policies (the reference packs the slot "
            "in the feasign's high bits); 0 = fold the low bits")
define_flag("sharding_grid_rows", 0,
            "row-axis size R of the 2d-grid policy (shard = "
            "table_group * R + key % R); must divide the shard count. "
            "0 = auto (largest divisor of P not above sqrt(P))")
define_flag("sharding_hot_threshold", 0,
            "2d-grid replicated hot tier: keys whose frequency-sketch "
            "estimate reaches this at the pass freeze are REPLICATED "
            "(served from the host mirror, dropped from the p2p uid "
            "wire by senders and re-added by owners) instead of "
            "routed. The sketch must be fed the same frequency "
            "knowledge on every rank (policy.observe is cluster-"
            "deterministic input by contract). 0 = hot tier off")
define_flag("sharding_hot_cap", 1024,
            "max replicated hot keys per shard for the 2d-grid hot "
            "tier — freeze_hot raises beyond it (an unbounded "
            "replicated set defeats the wire saving it exists for)")
define_flag("incremental_pass", True,
            "incremental pass lifecycle (BeginPass/EndPass delta, the "
            "BoxPS keep-rows-resident cadence): the slab stays in HBM "
            "between passes and begin_pass promotes only the keys that "
            "ARRIVED (host-store read, H2D and one in-place scatter of "
            "those rows instead of a full host rebuild + H2D); end_pass "
            "transfers and writes back only the rows the pass actually "
            "touched. WHICH row a key has does not depend on this flag: "
            "the feed pass diffs the new key set against the last pass's "
            "row assignment either way (a key that stays keeps its row, "
            "rows of keys that left are freed, keys that arrive take free "
            "rows; invalidate_residency and a test-mode pass reset it to "
            "rows by rank), so slab rows, store and journal are bit for "
            "bit the same on and off (tests/test_pass_incremental.py), "
            "created embeddings' init draws included (addressed by slab "
            "row). Memory: the single-chip slab stays resident in HBM "
            "between passes (no extra copy); the SHARDED table instead "
            "keeps a host-DRAM mirror of each owned shard's slab between "
            "passes (~slab bytes of host RAM — small next to the host "
            "store itself, but not free). Off = rebuild the whole slab "
            "from the store every pass, each key at its assigned row")
define_flag("obs_trace", True,
            "record named spans into the per-thread ring tracer "
            "(obs/tracer.py — the cheap always-on tier of the reference's "
            "tracing ladder, platform::RecordEvent role). ~1us/span; the "
            "ring is what export_chrome_trace and the stall watchdog "
            "dump read. Off = span() returns a shared no-op")
define_flag("obs_trace_capacity", 4096,
            "spans retained PER THREAD in the tracer ring before "
            "wrap-around (fixed memory: capacity * ~100B per thread)")
define_flag("obs_report_every", 20,
            "StepReport cadence in steps (obs/report.py): every N steps "
            "the trainer assembles one structured record — stage timer "
            "deltas, StatRegistry counter deltas, gauges, histogram "
            "percentiles, examples/sec — and emits it through the "
            "configured sink (obs_report_path); in multi-process runs "
            "non-zero ranks also piggyback it to rank 0 for the merged "
            "cluster view. <=0 = reporting off (zero assembly cost)")
define_flag("obs_report_path", "",
            "StepReport sink: '' = assemble + retain only (the watchdog "
            "and cluster aggregation still see reports), 'stderr' = one "
            "JSON line per report to stderr, any other value = append-"
            "JSONL file path (rank 0's file also carries the merged "
            "cluster_report records in multi-process runs)")
define_flag("obs_watchdog_secs", 0.0,
            "stall watchdog silence threshold in seconds (obs/"
            "watchdog.py): "
            "runners beat at step and exchange boundaries; when no beat "
            "arrives within the threshold the watchdog dumps the last-K "
            "spans, every thread's stack, and the last StepReport to "
            "stderr. <=0 = disabled")
define_flag("obs_flight_dir", "",
            "flight-recorder directory (obs/flight.py, round 14): when "
            "set, every rank keeps an always-on bounded on-disk black "
            "box — segment-rotated JSONL of a flags+env+git-sha header, "
            "StepReports, cluster reports/health, span windows at "
            "report cadence, warning/error log lines and sampled beats, "
            "flushed per record so it survives SIGKILL — plus a SEALED "
            "postmortem manifest (last-K spans, every thread's stack, "
            "last reports) written on excepthook, SIGABRT/SIGTERM, or a "
            "watchdog fire. The failure artifact the elastic fleet "
            "(ROADMAP item 5) consumes. '' = off (zero cost)")
define_flag("obs_flight_segment_bytes", 4 << 20,
            "flight-recorder segment rotation size in bytes; total disk "
            "per rank is bounded by this times obs_flight_segments")
define_flag("obs_flight_segments", 4,
            "flight-recorder segments retained per rank (oldest "
            "deleted at rotation; each segment re-writes the run "
            "header so any surviving segment is self-contained)")
define_flag("obs_watchdog_action", "dump",
            "what the watchdog does after dumping: 'dump' = report only "
            "(fires once per silence window), 'raise' = also interrupt "
            "the main thread (KeyboardInterrupt) so a wedged job dies "
            "loudly instead of burning its reservation")
define_flag("serving_cache_rows", 65536,
            "hot-key embedding cache capacity per serving process in "
            "ROWS (serving/cache.py): the hottest rows live in one "
            "resident [rows, dim] f32 array in front of the mmap'd "
            "view stack, with frequency-gated admission and CLOCK "
            "eviction (HierarchicalKV's cache-semantics model). Memory "
            "= rows * dim * 4 bytes + ~100 B/row bookkeeping. 0 = no "
            "cache (every pull probes the mmap store)")
define_flag("serving_cache_admit", 2,
            "admission threshold for the serving hot-key cache: a "
            "missed key enters the cache only after this many misses "
            "within the admission sketch's aging window (TinyLFU-style "
            "scan resistance — a one-shot sweep over cold keys cannot "
            "flush the hot set). 1 = admit on first miss")
define_flag("serving_refresh_secs", 0.5,
            "delta-refresh poll cadence in seconds (serving/refresh."
            "py): the watcher re-discovers completed xbox views "
            "(SaveDelta/SaveBase DONE markers) on this interval and "
            "atomically swaps a freshly-composed view generation in — "
            "the serving-side bound on model staleness is this poll "
            "plus the new views' compile time. <=0 still polls at the "
            "0.05s floor")
define_flag("serving_pull_threads", 4,
            "bounded lookup pool per serving process (serving/server."
            "py): every pull RPC executes on one of these workers "
            "regardless of how many connections are open, so overload "
            "degrades by queueing (visible in the latency histogram) "
            "instead of by thrashing the box")
define_flag("serving_drain_secs", 10.0,
            "graceful-drain bound in seconds: at shutdown a serving "
            "process refuses new pulls and waits up to this long for "
            "in-flight pulls to finish before the transport stops")
define_flag("serving_report_requests", 200,
            "StepReport cadence for the serving plane, in pull "
            "REQUESTS (the serving step unit): every N pulls the "
            "process emits one obs window record — p50/p99 lookup "
            "latency from the serving_lookup_us histogram, keys/s, "
            "request count, cache hit rate — through the standard "
            "obs_report_path sink. <=0 = reporting off")
define_flag("serving_slo_us", 15000.0,
            "serving lookup latency SLO in microseconds (round 14): "
            "every report window each replica publishes gauge "
            "serving_slo_burn = window p99 of serving_lookup_us divided "
            "by this — burn > 1.0 means the replica is out of SLO and "
            "the cluster health plane (obs/health.py) scores it "
            "degraded. The 15ms default was set from a container CPU "
            "run, not a deployment. <=0 disables the gauge")
define_flag("serving_num_shards", 1,
            "serving fleet width in BOXES (round 21): the sharded tier "
            "partitions the key space across this many boxes; each box "
            "filters its views to its own slice (serving/store.py "
            "ShardSpec) and the fleet client routes every pull by the "
            "same policy. 1 = the single-box plane, no filtering")
define_flag("serving_shard_index", -1,
            "which box of the serving fleet THIS process serves "
            "(0..serving_num_shards-1). -1 = unsharded: serve the full "
            "view (single-box mode, probes, tests). MultiBoxFleet sets "
            "this per child via flag overrides")
define_flag("serving_shard_policy", "",
            "sharding policy name for the serving fleet partition "
            "(parallel/sharding.py resolve_sharding_policy): '' = the "
            "flag-configured trainer policy (sharding_policy), so the "
            "serving partition matches training by default; set "
            "explicitly ('key-mod', '2d-grid') to diverge")
define_flag("serving_hot_keys", "",
            "path to a hot-key set file (serving/store.py "
            "write_hot_keys): every box ADDITIONALLY keeps these rows — "
            "the replicated hot tier — so the client may answer a "
            "head-key pull from ANY box instead of converging on the "
            "owner. '' = no replicated tier")
define_flag("serving_journal_dir", "",
            "comma-separated touched-row journal dirs to tail for "
            "journal-fed freshness (round 21, serving/refresh.py "
            "JournalDeltaSource): touched rows land in the served view "
            "one refresh poll after the trainer flushes them, cutting "
            "staleness from the SaveDelta interval to seconds. '' = "
            "refresh from completed xbox views only")
define_flag("ckpt_format", "columnar",
            "sparse batch-model checkpoint format (round 15): 'columnar' "
            "= sparse.xman manifest + N striped binary part files "
            "written by a parallel writer pool (atomic tmp+fsync+rename "
            "per part; the manifest lands only after every part is "
            "durable) and loaded via mmap + a reader pool "
            "(embedding/ckpt_store.py); 'pickle' = the legacy single "
            "sparse.pkl blob. Loaders sniff the format, so either kind "
            "of checkpoint loads regardless of this flag")
define_flag("ckpt_parts", 8,
            "part files per columnar sparse checkpoint (contiguous row "
            "stripes; trimmed so no part is empty). More parts = more "
            "writer/reader parallelism and smaller atomic units; the "
            "manifest pins the exact part list, so stray parts from an "
            "interrupted larger-parts save are ignored")
define_flag("ckpt_io_threads", 0,
            "checkpoint writer/reader pool threads; 0 = one per part "
            "capped at the box's cores (and at 16). The pool writes/"
            "reads disjoint row stripes — np.tofile/memmap copies "
            "release the GIL, so the threads genuinely overlap")
define_flag("ckpt_journal", True,
            "persistent touched-row journal (train/journal.py): every "
            "end-of-pass write-back appends its touched (keys, rows) "
            "delta and the day-cadence lifecycle mutations append "
            "deterministic event records, into segment-rotated binary "
            "files under <batch_model_dir>/_journal/rank<r>. Enables "
            "save_base(mode='touched'/'auto') — day-boundary snapshot "
            "cost proportional to the delta — and the elastic mid-day "
            "rejoin artifact (replay-over-base, ROADMAP item 5). SSD "
            "tier movement is journaled as MOVE records (spill / "
            "fault-in key sets) so touched saves stay exact with the "
            "tier engaged; only server-side PS spills, rotation loss "
            "and external store loads still taint the epoch")
define_flag("ckpt_journal_segment_bytes", 64 << 20,
            "touched-row journal segment rotation size in bytes; each "
            "segment re-writes a self-describing header (flight-"
            "recorder discipline), records are flushed per append so a "
            "SIGKILL leaves a parseable prefix")
define_flag("ckpt_journal_segments", 32,
            "max live journal segments per rank; exceeding the bound "
            "drops the OLDEST segment and marks the epoch incomplete "
            "(touched saves then fall back to full, which re-anchors "
            "and resets) — bounded disk beats unbounded promises")
define_flag("ckpt_xbox_columnar", True,
            "emit xbox serving views (SaveBase/SaveDelta output) "
            "DIRECTLY as the serving columnar file (view.xcol, sorted "
            "keys) instead of embedding.pkl: serving's compile_view_dir "
            "becomes a detect-and-skip no-op on these dirs and "
            "delta-refresh staleness drops by the pickle->columnar "
            "re-encode. Off = the legacy pkl views (readers handle "
            "both, mixed histories compose)")
define_flag("obs_http_port", 0,
            "per-rank live ops HTTP endpoint (obs/exporter.py, round "
            "18): every rank (and every serving replica, whose replica "
            "index is its rank) binds 127.0.0.1:<port + rank> and "
            "serves /metrics (Prometheus text exposition of the "
            "StatRegistry counters/gauges/histograms + quality-plane "
            "auc/copc/ctr), /report (latest StepReport; rank 0 adds "
            "the merged cluster report), /health (rank 0: per-rank "
            "cluster health scores), /stacks (every thread's stack), "
            "/flight (black-box segment list + tail) and /quality — "
            "all answered from defensive snapshots, never a training "
            "lock. A port already in use warns and disables the "
            "endpoint. 0 = off (zero cost)")
define_flag("quality_metrics", True,
            "tagged quality-metric plane (metrics/quality.py, round "
            "18): the trainers stream per-tag masked AUC (the 'all' "
            "stream, per-cmatch tags, per-task heads), COPC (click "
            "over predicted click — the calibration alarm), actual/"
            "predicted CTR per tag AND per slot into sum-mergeable "
            "bucket tables (MetricMsg parity with the reference's "
            "tagged metric family); pass_end reports carry the "
            "computed bundle, multi-process runs ship the raw state "
            "so rank 0 merges a cluster-wide quality report, and the "
            "quality_auc/quality_copc gauges feed the health plane. "
            "Off = no quality adds (zero cost)")
define_flag("quality_table_size", 65536,
            "bucket count of each tagged quality AUC table (the "
            "BasicAucCalculator table_size role; the reference uses "
            "1<<20 — 65536 keeps per-tag memory at 1 MB and the "
            "pass_end state wire compact while holding AUC resolution "
            "to ~1.5e-5 of pred space). Every rank must use the same "
            "value: cluster merge refuses mismatched table sizes")
define_flag("data_quality", True,
            "slot-level data-quality drift monitor (metrics/drift.py, "
            "round 18): the columnar ingest plane accumulates per-slot "
            "coverage, keys/record and a distinct-key sketch per "
            "report window (one bincount over key_slot per block) "
            "plus label/pred histograms; each pass_end rolls the "
            "window against a rolling reference and publishes the "
            "data_drift_score / data_dropped_slots gauges the cluster "
            "HealthMonitor penalizes — a dropped upstream slot or a "
            "calibration blow-up turns the rank unhealthy through the "
            "same plane the elastic fleet triggers on. Off = no "
            "monitoring (zero cost)")
define_flag("data_quality_warn", 0.5,
            "drift-score warn threshold in [0, 1]: a rolled window "
            "whose worst per-slot departure (coverage drop, keys/"
            "record drift, cardinality collapse) or label/pred "
            "distribution drift reaches this logs a warning on the "
            "victim rank, and rank 0's HealthMonitor scores any rank "
            "whose data_drift_score gauge is past it -0.6 — past the "
            "0.5 healthy bar on its own (flag 'data_drift' in the "
            "cluster_health record)")
define_flag("preload_promote", True,
            "overlap the NEXT pass's host-side promote work (key diff + "
            "host-store reads for non-resident keys) with the current "
            "pass's training on the preload thread (the PreLoad/"
            "WaitFeedPassDone tail-hiding role, box_wrapper.h:1131-1172); "
            "only active with incremental_pass and a store that supports "
            "lookup_present")
define_flag("debug_lock_order", False,
            "construct the package's locks through the lockwatch runtime "
            "validator (utils/lockwatch.py): records per-thread "
            "acquisition order in the static BX7xx Class._attr identity "
            "vocabulary, flags AB/BA inversions loudly the first time "
            "both nestings are observed (lockwatch_inversions stat), and "
            "publishes lock_hold_us_<name> histograms through the obs "
            "StatRegistry. Off (default) = plain threading locks, zero "
            "added cost; the concurrency suites run with it on")
define_flag("device_obs", True,
            "device-plane observability (obs/device.py, round 20): "
            "every jit entry point runs through instrument_jit — exact "
            "per-fn compile counts + compile wall time, a one-time "
            "cost/memory-analysis snapshot (flops & bytes-accessed per "
            "example, temp/alias bytes — the step_audit math, live), a "
            "steady-state RECOMPILE SENTINEL (device_recompiles stat + "
            "HealthMonitor penalty), a donation audit (donation_miss "
            "when a donated buffer was copied instead of aliased — the "
            "regime-step mechanism), and the HBM live-buffer ledger "
            "sampled at report cadence. Off = bare jax.jit everywhere "
            "(zero added cost, zero device signals; the on-cost is not "
            "measured on the chip)")
define_flag("device_recompile_warmup", 3,
            "compiles each instrumented fn may accumulate before the "
            "recompile sentinel treats further compiles as steady-state "
            "shape/dtype churn (counted in device_recompiles, logged "
            "loudly once per fn, scored unhealthy by the cluster "
            "HealthMonitor): legitimate multi-signature entry points "
            "(a tail chunk, an eval twin shape) fit inside the "
            "allowance; a mis-staged batch recompiling every step "
            "does not")
define_flag("device_donation_min_bytes", 65536,
            "donation-audit floor: donated buffers smaller than this "
            "are not pointer-checked (XLA legitimately declines to "
            "alias tiny buffers and the alarm exists for slab-scale "
            "copies — the >=4M-row regime step is a ~272MB one)")
define_flag("device_leak_windows", 3,
            "live-buffer leak detector: consecutive ledger samples "
            "(report cadence) of strictly-growing total device bytes "
            "before device_leak_suspect fires (once per sustained "
            "climb, loud warn with the growth)")
define_flag("device_leak_min_bytes", 1 << 20,
            "live-buffer leak detector: minimum total growth across "
            "the monotonic window before it counts — compile-time "
            "constant buffers and small per-pass arrays must not page "
            "an operator")
define_flag("host_store_stripes", 0,
            "shard the host embedding store's hash index into N "
            "stripes (embedding/striped_store.py): keys route by "
            "splitmix64(key) mod N, each stripe owns an independent "
            "inner store (+ rng seeded seed+stripe) so lookups gather "
            "per-stripe in parallel threads and the single global "
            "index stops being the billion-key bottleneck. 0 (default) "
            "= the flat single-index store — bit-compatible with every "
            "existing checkpoint/journal; striped stores draw a "
            "DIFFERENT init stream (per-stripe rngs), so flip it only "
            "on fresh runs or restored-from-checkpoint runs")
# streaming continuous training (data/streaming.py +
# train/streaming_runner.py): the day/pass cadence collapsed into
# bounded micro-passes tailing a live source
define_flag("streaming_micro_pass_instances", 4096,
            "target instances per streaming micro-pass window: the "
            "directory watcher accumulates ready files until their "
            "line count reaches this bound, then hands the window to "
            "the preloader — the unit of training, admission, "
            "micro-checkpointing and journal publish in the streaming "
            "plane (smaller = fresher served vectors, more per-pass "
            "overhead)")
define_flag("streaming_poll_secs", 0.2,
            "streaming source poll interval: how often the directory "
            "watcher re-lists the watched dir (and the socket spooler "
            "checks its seal cadence) while waiting for new data; also "
            "the granularity of the runner's idle wait")
define_flag("streaming_stable_polls", 2,
            "consecutive size-stable watcher polls before a bare "
            "(non temp-suffixed) file counts as sealed and may enter a "
            "micro-pass window — the torn-write guard for writers that "
            "append in place instead of the write-temp-then-rename "
            "convention (.tmp/.part/._* names are always skipped)")
define_flag("streaming_base_every", 8,
            "micro-checkpoint decimation: save_base(mode='auto') every "
            "K admitted micro-passes (journal segments are published "
            "at EVERY micro-pass boundary regardless — serving "
            "freshness rides the journal, durability rides the base "
            "cadence). 0 = no in-run base saves")
define_flag("streaming_admission_max_drift", 0.8,
            "drift-gated admission threshold: a loaded micro-pass "
            "window whose SlotDriftMonitor preview score against the "
            "rolling reference of ADMITTED windows reaches this is "
            "refused before begin_pass — it never trains, never "
            "mutates the store, and never enters the reference. "
            "0 disables the gate")
define_flag("streaming_idle_timeout_secs", 0.0,
            "streaming runner exit condition: stop after this many "
            "seconds with no new complete window from the source "
            "(0 = run until stop() or max_micro_passes) — the bound "
            "bench/test/demo legs use to drain a finite drop")
# feed-to-serve watermark plane (obs/watermark.py, round 20): born-ts
# lineage through train->journal->serving, tier-hit telemetry, and the
# freshness/tier SLO burn gauges HealthMonitor alarms on
define_flag("obs_watermark", True,
            "feed-to-serve watermark plane master switch: when on, the "
            "streaming boundary stamps every journal publish with the "
            "window's born-ts span, the serving plane stamps pull "
            "responses with its applied watermark, and both ends "
            "observe the end-to-end freshness histogram. Off = no "
            "stamps, no freshness samples (the pairwise overhead "
            "bench's control arm); everything else degrades to "
            "pre-round-20 behavior")
define_flag("freshness_slo_secs", 30.0,
            "feed-to-serve freshness SLO: the serving report window's "
            "p99 of (pull time - applied watermark) is divided by this "
            "to form the serving_freshness_burn gauge — burn > 1 means "
            "served vectors are older than the promise and "
            "HealthMonitor flags the rank (freshness_burn, -0.4). "
            "0 disables the burn computation (freshness is still "
            "measured)")
define_flag("tier_hit_rate_warn", 0.05,
            "tiered-store hit-rate floor: when a warm feed-pass "
            "lookup's resident-hit rate (host-RAM hits / keys looked "
            "up) falls BELOW this, tier_hit_burn (= warn_rate / "
            "observed_rate) exceeds 1 and HealthMonitor flags the rank "
            "(tier_hit_low, -0.3) — the SSD tier is thrashing instead "
            "of absorbing the cold tail. Cold stores (first passes) "
            "never burn. 0 disables")
