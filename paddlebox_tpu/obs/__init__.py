"""obs: the runtime telemetry plane (round 10).

Unifies the reproduction's observability tiers the way the reference's
platform/monitor.h + timer discipline + chrometracing profiler did
(SURVEY.md §5.1), always-on cheap:

  * span tracer  — per-thread ring of named spans; chrome-tracing JSON
                   export loadable in Perfetto without jax.profiler
                   (obs/tracer.py)
  * StepReport   — per-cadence structured record (timer/stat deltas,
                   gauges, histogram percentiles, examples/sec) through a
                   pluggable MetricsSink (obs/report.py)
  * aggregation  — non-zero ranks piggyback their reports to rank 0 over
                   the existing mesh/store plane; rank 0 emits a merged
                   per-rank min/median/max view (obs/aggregate.py)
  * watchdog     — heartbeat thread dumping spans + per-thread stacks +
                   the last StepReport on silence (obs/watchdog.py)
  * log          — rank-prefixed structured lines replacing bare print()
                   in library code (obs/log.py; boxlint BX501 enforces)
  * flight       — always-on bounded on-disk black box per rank with
                   crash SEALING (excepthook / fatal signal / watchdog
                   fire → durable manifest of spans+stacks+reports);
                   the postmortem artifact a SIGKILL'd rank leaves
                   behind (obs/flight.py, round 14)
  * health       — rank 0 folds report freshness, beat age, error-line
                   rate, queue depths and serving SLO burn into a
                   per-rank health score published as cluster_health
                   each aggregation cadence — the elastic-fleet trigger
                   signal (obs/health.py, round 14)
  * trace ids    — 64-bit per-step/per-request ids carried across the
                   p2p mesh and the serving RPC boundary; spans record
                   them, tools/trace_stitch.py merges per-rank chrome
                   traces into one cluster timeline with cross-rank
                   flow events (obs/tracer.py, round 14); per-PASS ids
                   (pass_trace_id) ride every span of a pass across the
                   main, reader, prefetch and stager threads
  * exporter     — per-rank HTTP ops endpoint (flag obs_http_port,
                   port +rank): /metrics Prometheus exposition,
                   /report, /health, /stacks, /flight, /quality,
                   /device — the live READ surface over every tier
                   above, answered from defensive snapshots only
                   (obs/exporter.py, round 18)
  * device       — the XLA/device tier (obs/device.py, round 20):
                   instrument_jit wraps every jit entry point (boxlint
                   BX901 enforces) with exact compile counts/wall time,
                   one-time cost/memory-analysis snapshots, a
                   steady-state recompile sentinel, and a donation
                   audit (donated-buffer pointer reuse); the runners'
                   staging/write-back paths account H2D/D2H transfer
                   bytes and the live-buffer ledger buckets
                   jax.live_arrays() by owner at report cadence with a
                   monotonic-growth leak detector — all through the
                   StatRegistry, so reports/metrics/flight/health carry
                   it unchanged; compiles are ring spans (device_compile,
                   backend_compile) and each entry's snapshot carries
                   `scopes`, the {HLO instruction: jax.named_scope} map
                   that makes a device trace readable by phase

Import surface is deliberately jax-free: every hot-path hook (span,
beat) must stay importable and near-free on any host — the serving
plane (serving/, round 12) runs this whole stack in jax-free replica
processes (per-pull latency histograms, QPS windows, cache-rate extras
ride the same StepReport/sink/aggregation machinery unchanged).
"""

from paddlebox_tpu.obs import device  # noqa: F401
from paddlebox_tpu.obs import exporter  # noqa: F401
from paddlebox_tpu.obs import flight  # noqa: F401
from paddlebox_tpu.obs import log  # noqa: F401
from paddlebox_tpu.obs.device import (account_d2h, account_h2d,  # noqa: F401
                                      instrument_jit)
from paddlebox_tpu.obs.aggregate import (ClusterAggregator,  # noqa: F401
                                         MeshObsTransport, StoreObsTransport,
                                         make_transport,
                                         merge_cluster_reports)
from paddlebox_tpu.obs.flight import FlightRecorder  # noqa: F401
from paddlebox_tpu.obs.health import HealthMonitor  # noqa: F401
from paddlebox_tpu.obs.report import (JsonlSink, ListSink,  # noqa: F401
                                      MetricsSink, NullSink, StderrSink,
                                      StepReporter, make_sink)
from paddlebox_tpu.obs.tracer import (SpanTracer, current_trace,  # noqa: F401
                                      get_tracer, next_trace_id,
                                      pass_trace_id, span, step_trace_id,
                                      trace_ctx)
from paddlebox_tpu.obs.tracer import \
    configure_from_flags as _tracer_configure
from paddlebox_tpu.obs.watchdog import StallWatchdog  # noqa: F401
from paddlebox_tpu.obs.watchdog import beat  # noqa: F401
from paddlebox_tpu.obs.watchdog import ensure_from_flags as _wd_ensure


def make_step_reporter(rank: int = 0, timers=None, aggregator=None,
                       **kwargs) -> StepReporter:
    """Flag-configured reporter + tracer sync + (flag-gated) watchdog +
    (flag-gated) flight recorder — the one call every trainer makes at
    construction."""
    _tracer_configure()
    flight.ensure_from_flags(rank=rank)
    reporter = StepReporter(rank=rank, timers=timers,
                            aggregator=aggregator, **kwargs)
    _wd_ensure(tracer=get_tracer(), report_fn=reporter.peek)
    # live ops endpoint (round 18, flag-gated): /report answers this
    # reporter's peek, /health reaches the health plane through
    # reporter.aggregator — one bind per runner/replica construction
    exp = exporter.ensure_from_flags(rank=rank)
    if exp is not None:
        exp.bind(reporter=reporter)
    return reporter


def obs_rank_world(mesh=None, fleet=None):
    """(rank, world) in the TRANSPORT rank space — mesh rank == fleet
    worker index, the space both piggyback planes address their "rank 0"
    in. Never jax.process_index(): a job is free to map fleet ranks onto
    jax processes differently (MeshComm.positions_of exists for exactly
    that), and a mismatched aggregator would drain nothing while the
    real rank 0 self-publishes into an inbox nobody reads."""
    if mesh is not None:
        return int(mesh.rank), int(mesh.world)
    if fleet is not None and getattr(fleet, "initialized", False):
        return int(fleet.worker_index()), int(fleet.worker_num())
    return 0, 1


def make_cluster_aggregator(mesh=None, fleet=None, rank: int = 0,
                            world: int = 1):
    """The ONE multi-process aggregator wiring both sharded runners use:
    transport from the job's existing plane (p2p mesh, else fleet
    store), rank 0 emitting merged cluster reports — and the derived
    cluster_health records (obs/health.py) — through the flag-configured
    sink. None when no piggyback plane exists."""
    transport = make_transport(mesh=mesh, fleet=fleet)
    if transport is None:
        return None
    from paddlebox_tpu.config import flags
    sink = (make_sink(str(flags.get_flag("obs_report_path")))
            if rank == 0 else None)
    health = (HealthMonitor(
        world, drift_warn=float(flags.get_flag("data_quality_warn")))
        if rank == 0 else None)
    return ClusterAggregator(transport, rank, world, sink=sink,
                             health=health)


def export_chrome_trace(path=None, rank: int = 0) -> dict:
    """Dump the span rings as chrome-tracing JSON (Perfetto-loadable)."""
    return get_tracer().export_chrome(path=path, pid=rank)
