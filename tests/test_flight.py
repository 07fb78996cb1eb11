"""Round 14: flight recorder, crash sealing, trace stitching, health.

Tier-1 covers the postmortem plane in-process: black-box segment
rotation + header self-containment, SEALED manifests (direct, via the
excepthook chain, via a watchdog fire), the flag lifecycle through
make_step_reporter, log-line counting into the health stats, the
aggregator's exponential-backoff re-probe under a flaky transport, the
health monitor's documented scoring, trace ids crossing the REAL p2p
mesh, and trace_stitch producing cross-rank flow events. The
real-2-process chaos leg (SIGABRT/SIGKILL a rank mid-pass) runs the
same assertions out-of-process in the slow tier via
tools/chaos_seal_probe.py.
"""

import concurrent.futures
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddlebox_tpu.obs as obs
from paddlebox_tpu.config import flags
from paddlebox_tpu.obs import flight
from paddlebox_tpu.obs.aggregate import ClusterAggregator
from paddlebox_tpu.obs.health import HealthMonitor
from paddlebox_tpu.obs.flight import FlightRecorder
from paddlebox_tpu.obs.tracer import (SpanTracer, get_tracer,
                                      next_trace_id, step_trace_id,
                                      trace_ctx)
from paddlebox_tpu.obs.watchdog import StallWatchdog
from tools.trace_stitch import stitch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def no_active_flight():
    """Restore the module-active recorder around tests that set it (the
    flag snapshot fixture can't see this module global)."""
    prev = flight.set_active(None)
    yield
    fr = flight.set_active(prev)
    if fr is not None and fr is not prev:
        fr.close()


def _read_jsonl(path):
    with open(path, encoding="utf-8") as fh:
        return [json.loads(ln) for ln in fh]


# ------------------------------------------------------------- black box

def test_flight_header_and_record_types(tmp_path):
    fr = FlightRecorder(str(tmp_path), rank=3)
    fr.record("custom", x=1)
    fr.on_log("WARNING", "w line")
    fr.on_beat("step")
    fr.close()
    recs = _read_jsonl(fr.segments()[0])
    assert recs[0]["type"] == "header"
    hdr = recs[0]
    assert hdr["rank"] == 3 and hdr["pid"] == os.getpid()
    assert "obs_flight_dir" in hdr["flags"]        # full flag snapshot
    assert isinstance(hdr["git_sha"], str)
    types = [r["type"] for r in recs[1:]]
    assert types == ["custom", "log", "beat"]


def test_flight_segment_rotation_bounded(tmp_path):
    fr = FlightRecorder(str(tmp_path), rank=0, segment_bytes=1500,
                        max_segments=3)
    for i in range(200):
        fr.record("noise", i=i, pad="x" * 40)
    fr.close()
    segs = sorted(p for p in os.listdir(tmp_path)
                  if p.startswith("flight_r0_"))
    assert 1 <= len(segs) <= 3                     # bounded on disk
    for s in segs:
        recs = _read_jsonl(os.path.join(str(tmp_path), s))
        # every segment is self-contained: header at its top
        assert recs[0]["type"] == "header"


def test_flight_beats_sampled(tmp_path):
    fr = FlightRecorder(str(tmp_path), rank=0, beat_secs=60.0)
    for _ in range(50):
        fr.on_beat("step")
    fr.close()
    beats = [r for r in _read_jsonl(fr.segments()[0])
             if r["type"] == "beat"]
    assert len(beats) == 1          # 50 beats inside one sample window


def test_seal_manifest_and_numbered_siblings(tmp_path):
    fr = FlightRecorder(str(tmp_path), rank=1)
    tr = get_tracer()
    with trace_ctx(step_trace_id(1, 5)):
        with tr.span("doomed_stage"):
            pass
    fr.on_report({"type": "step_report", "rank": 1, "step": 5})
    fr.on_log("ERROR", "it broke")
    p1 = fr.seal("unit:first")
    p2 = fr.seal("unit:second")
    assert p1.endswith("SEALED_r1.json") and p2.endswith("SEALED_r1.2.json")
    m = json.load(open(p1))
    assert m["reason"] == "unit:first" and m["rank"] == 1
    assert any("doomed_stage" == s[0] for s in m["spans"])
    assert any("0x" in str(s[5]) for s in m["spans"]
               if s[0] == "doomed_stage")          # trace id preserved
    assert m["threads"]                             # every thread's stack
    assert m["last_reports"][-1]["step"] == 5
    assert m["log_tail"][-1]["line"] == "it broke"
    assert m["segments"]
    fr.close()


def test_excepthook_chain_seals(lock_order_watch, tmp_path, no_active_flight):
    fr = FlightRecorder(str(tmp_path), rank=0)
    flight.set_active(fr)
    called = []
    prev = flight._PREV_EXCEPTHOOK
    flight._PREV_EXCEPTHOOK = lambda *a: called.append(a)
    try:
        try:
            raise ValueError("boom")
        except ValueError as e:
            flight._excepthook(ValueError, e, e.__traceback__)
    finally:
        flight._PREV_EXCEPTHOOK = prev
    assert called, "previous excepthook must stay chained"
    m = json.load(open(os.path.join(str(tmp_path), "SEALED_r0.json")))
    assert m["reason"] == "excepthook:ValueError"
    assert "boom" in m["exception"]
    fr.close()


def test_watchdog_fire_seals(lock_order_watch, tmp_path, no_active_flight):
    fr = FlightRecorder(str(tmp_path), rank=0)
    flight.set_active(fr)
    wd = StallWatchdog(threshold_s=0.05, tracer=get_tracer(),
                       stream=open(os.devnull, "w"))
    wd.fire("wedged_stage", 9.9)
    m = json.load(open(os.path.join(str(tmp_path), "SEALED_r0.json")))
    assert m["reason"] == "watchdog_stall:wedged_stage"
    assert "wedged_stage" in m["extra_text"]      # the rendered dump
    fr.close()


def test_flight_flag_lifecycle(tmp_path, no_active_flight):
    flags.set_flag("obs_flight_dir", str(tmp_path))
    rep = obs.make_step_reporter(rank=0, every=1, sink=obs.ListSink())
    assert flight.active() is not None
    with obs.span("lifecycle_stage"):
        pass
    rep.note_examples(10)
    rep.maybe_report(1)
    recs = []
    for s in flight.active().segments():
        recs.extend(_read_jsonl(s))
    types = {r["type"] for r in recs}
    assert {"header", "report"} <= types
    spans_rec = [r for r in recs if r["type"] == "spans"]
    assert spans_rec and any(
        s[0] == "lifecycle_stage" for r in spans_rec for s in r["spans"])
    # empty flag clears the active recorder (test self-healing contract)
    flags.set_flag("obs_flight_dir", "")
    flight.ensure_from_flags()
    assert flight.active() is None
    rep.close()


def test_log_lines_counted_and_recorded(tmp_path, no_active_flight):
    from paddlebox_tpu.obs import log as obs_log
    from paddlebox_tpu.utils.stats import stat_get
    fr = FlightRecorder(str(tmp_path), rank=0)
    flight.set_active(fr)
    w0 = stat_get("log_warning_lines")
    e0 = stat_get("log_error_lines")
    obs_log.warning("w one")
    obs_log.error("e one")
    obs_log.info("info is not counted")
    assert stat_get("log_warning_lines") == w0 + 1
    assert stat_get("log_error_lines") == e0 + 1
    logs = [r for r in _read_jsonl(fr.segments()[0])
            if r["type"] == "log"]
    assert [r["level"] for r in logs] == ["WARNING", "ERROR"]
    fr.close()


# ------------------------------------------------- aggregator backoff

class _FlakyTransport:
    """Fails the first `fail_n` publishes, then heals."""

    def __init__(self, fail_n):
        self.fail_n = fail_n
        self.calls = 0
        self.delivered = []

    def publish(self, payload):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise ConnectionError("NIC blip")
        self.delivered.append(payload)

    def drain(self):
        return []


def test_aggregator_backoff_reprobes_after_transport_heals():
    """The round-14 policy: after 3 consecutive failures skip 1 publish,
    then re-probe; another failure skips 2; a success resets. The
    backoff is denominated in skipped PUBLISHES (= stale windows at
    rank 0), so a transient blip costs a bounded number of windows."""
    tr = _FlakyTransport(fail_n=4)
    agg = ClusterAggregator(tr, rank=1, world=2)
    rep = {"type": "step_report", "rank": 1, "step": 1}
    for _ in range(3):              # failures 1..3 -> backoff starts
        agg.publish(rep)
    assert tr.calls == 3 and agg._skip_remaining == 1
    agg.publish(rep)                # skipped: no transport cost
    assert tr.calls == 3
    agg.publish(rep)                # re-probe: fails -> skips DOUBLE
    assert tr.calls == 4 and agg._skip_remaining == 2
    agg.publish(rep)
    agg.publish(rep)                # two skips burn down
    assert tr.calls == 4
    agg.publish(rep)                # re-probe: transport healed
    assert tr.delivered and agg._failures == 0
    agg.publish(rep)                # straight through, no residue
    assert len(tr.delivered) == 2


def test_aggregator_backoff_skip_cap_and_time_cap():
    tr = _FlakyTransport(fail_n=10**9)
    clock = [0.0]
    agg = ClusterAggregator(tr, rank=1, world=2, clock=lambda: clock[0])
    rep = {"type": "step_report", "rank": 1, "step": 1}
    for _ in range(200):
        agg.publish(rep)
    assert agg._skip_remaining <= ClusterAggregator.BACKOFF_SKIP_CAP
    # slow-cadence jobs: the WALL-CLOCK ceiling re-probes even with
    # skips remaining (a blip must not silence telemetry for minutes)
    calls = tr.calls
    agg._skip_remaining = ClusterAggregator.BACKOFF_SKIP_CAP
    clock[0] = agg._backoff_until + 0.01
    agg.publish(rep)
    assert tr.calls == calls + 1


# ----------------------------------------------------------- health plane

def _merged(stale_ranks=(), metrics=None, step=7):
    return {"type": "cluster_report", "step": step,
            "stale_ranks": list(stale_ranks),
            "metrics": metrics or {}}


def test_health_scoring_contract():
    hm = HealthMonitor(world=3)
    # window 1: rank 2 stale once -> degraded but healthy
    h = hm.update(_merged(stale_ranks=[2]))
    assert h["ranks"]["2"]["score"] == pytest.approx(0.6)
    assert h["ranks"]["2"]["healthy"] and h["unhealthy_ranks"] == []
    # window 2: still stale -> dead (score 0) within 2 windows
    h = hm.update(_merged(stale_ranks=[2]))
    assert h["ranks"]["2"]["score"] == 0.0
    assert h["unhealthy_ranks"] == [2]
    # recovery resets the streak
    h = hm.update(_merged())
    assert h["ranks"]["2"]["healthy"]


def test_health_beat_stall_scores_unhealthy():
    """A rank that still REPORTS but stopped beating (wedged step loop
    behind a live reporting path) must read unhealthy — freshness alone
    cannot see this, which is why beat_age_s is gauged at all."""
    hm = HealthMonitor(world=2, beat_age_warn=30.0)
    h = hm.update(_merged(metrics={
        "gauges.beat_age_s": {"per_rank": {"0": 0.4, "1": 120.0}}}))
    assert h["ranks"]["0"]["healthy"]
    r1 = h["ranks"]["1"]
    assert r1["flags"] == ["beat_stalled"] and not r1["healthy"]
    assert r1["beat_age_s"] == 120.0
    assert h["unhealthy_ranks"] == [1]


def test_flight_rotation_failure_degrades_closed(tmp_path):
    """Mid-run rotation hitting a dead dir must close the recorder, not
    raise into the training step (the record() 'never raises' contract
    covers the rotation path too)."""
    import shutil
    fr = FlightRecorder(str(tmp_path / "d"), rank=0, segment_bytes=400)
    fr.record("ok", pad="x" * 16)
    shutil.rmtree(str(tmp_path / "d"))      # tmpdir-cleanup scenario
    for i in range(50):                     # crosses the rotation bound
        fr.record("noise", i=i, pad="y" * 64)
    assert fr._closed                       # degraded, never raised
    fr.record("after", x=1)                 # still a no-op, still safe
    fr.close()


def test_health_error_rate_depth_and_slo_flags():
    hm = HealthMonitor(world=2)
    h = hm.update(_merged(metrics={
        "stats.log_error_lines": {"per_rank": {"1": 4.0}},
        "gauges.chan_route_depth": {"per_rank": {"1": 999.0}},
        "gauges.serving_slo_burn": {"per_rank": {"1": 1.8}},
    }))
    r1 = h["ranks"]["1"]
    assert set(r1["flags"]) == {"error_lines", "queue_depth", "slo_burn"}
    assert r1["score"] == pytest.approx(0.2) and not r1["healthy"]
    assert h["ranks"]["0"]["score"] == 1.0


def test_cluster_health_published_through_sink():
    class _Quiet:
        def publish(self, payload):
            raise AssertionError("rank 0 never publishes")

        def drain(self):
            return []

    sink = obs.ListSink()
    agg = ClusterAggregator(_Quiet(), rank=0, world=2, sink=sink,
                            health=HealthMonitor(2))
    agg.publish({"type": "step_report", "rank": 0, "step": 3,
                 "examples_per_sec": 1.0})
    types = [r["type"] for r in sink.records]
    assert types == ["cluster_report", "cluster_health"]
    json.loads(json.dumps(sink.records[-1]))       # sink-serializable


def test_in_process_chaos_twin(tmp_path, no_active_flight):
    """The tier-1 twin of the chaos leg: rank 1 publishes once, seals
    (its 'death'), and goes silent; rank 0's health plane flags it
    unhealthy within 2 windows; the SEALED bundle parses."""
    box = []

    class _To0:
        def publish(self, payload):
            box.append(payload)

        def drain(self):
            return []

    class _At0:
        def publish(self, payload):
            raise AssertionError("rank 0 never publishes")

        def drain(self):
            out, box[:] = list(box), []
            return out

    fr1 = FlightRecorder(str(tmp_path), rank=1)
    flight.set_active(fr1)
    sink = obs.ListSink()
    agg1 = ClusterAggregator(_To0(), rank=1, world=2)
    agg0 = ClusterAggregator(_At0(), rank=0, world=2, sink=sink,
                             health=HealthMonitor(2))

    def r(rank, step):
        return {"type": "step_report", "rank": rank, "step": step}

    agg1.publish(r(1, 1))                 # rank 1 alive, window 1
    agg0.publish(r(0, 1))
    assert agg0.last_cluster_health["unhealthy_ranks"] == []
    # rank 1 dies: seals, never publishes again
    sealed = flight.seal_active("signal:SIGABRT")
    windows = 0
    for step in (2, 3):
        agg0.publish(r(0, step))
        windows += 1
        if agg0.last_cluster_health["unhealthy_ranks"]:
            break
    assert windows <= 2
    assert agg0.last_cluster_health["unhealthy_ranks"] == [1]
    assert agg0.last_cluster_health["ranks"]["1"]["stale_windows"] >= 2
    m = json.load(open(sealed))
    assert m["reason"] == "signal:SIGABRT" and m["threads"]
    fr1.close()


# --------------------------------------------------- trace ids + stitch

@pytest.fixture
def mesh_pair():
    from paddlebox_tpu.fleet.mesh_comm import MeshComm
    meshes = [MeshComm(r, 2) for r in range(2)]
    eps = {r: ("127.0.0.1", m.port) for r, m in enumerate(meshes)}
    for m in meshes:
        m.connect(eps)
    yield meshes
    for m in meshes:
        m.close()


def test_mesh_exchange_carries_trace_id(mesh_pair):
    """The wire contract: the receiver-side span records the SENDER's
    step trace id (both virtual ranks share this process's tracer, so
    the pairing is directly observable)."""
    m0, m1 = mesh_pair
    tr = get_tracer()
    tr.clear()
    t0_id = step_trace_id(0, 1)
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        f = pool.submit(lambda: m1.exchange(
            {0: np.arange(4, dtype=np.int32),
             1: np.arange(4, dtype=np.int32)}))
        with trace_ctx(t0_id):
            m0.exchange({0: np.arange(4, dtype=np.int32),
                         1: np.arange(4, dtype=np.int32)})
        f.result()
    spans = tr.all_spans()
    sends = [s for s in spans if s[0] == "mesh_exchange"]
    recvs = [s for s in spans if s[0] == "mesh_recv_part"]
    assert any(s[5] == t0_id for s in sends)       # rank 0 inherited ctx
    assert any(s[5] == t0_id for s in recvs)       # receiver tagged it
    # rank 1 had no ctx: its exchange minted a rank+seq id in the
    # bit-62 namespace — the stager's seq counts ~1:1 with the step
    # counter, so an un-namespaced mint would collide with step ids
    assert any(s[5] == (1 << 62) | step_trace_id(1, 1) for s in sends)


def test_mesh_recv_garbage_trace_never_fails_exchange(mesh_pair):
    """A skewed peer shipping a non-int trace is a telemetry value —
    the lockstep part handler must accept the frame regardless."""
    m0, _ = mesh_pair
    assert m0._on_request({"op": "part", "seq": 999, "from": 1,
                           "data": b"\x00\x00\x00\x00",
                           "dtype": "int32", "shape": (1,),
                           "trace": "0xdeadbeef"}) is True
    with m0._cv:                    # the part parked despite the trace
        assert (999, 1) in m0._inbox


def test_trace_stitch_cross_rank_flow(tmp_path):
    """Acceptance pin: stitched output is loadable chrome JSON with >=1
    flow event whose source and destination spans live on DIFFERENT
    ranks."""
    tr0, tr1 = SpanTracer(32), SpanTracer(32)
    t = step_trace_id(0, 9)
    now = time.perf_counter()
    tr0.record_span("mesh_exchange", now, now + 0.002, trace=t)
    tr1.record_span("mesh_recv_part", now + 0.001, now + 0.0015, trace=t)
    tr1.record_span("untraced", now, now + 0.001)
    docs = [tr0.export_chrome(pid=0), tr1.export_chrome(pid=1)]
    stitched, summary = stitch(docs)
    assert summary["cross_rank_flows"] >= 1
    text = json.dumps(stitched)
    loaded = json.loads(text)
    flows = [e for e in loaded["traceEvents"] if e.get("ph") in "stf"]
    assert {e["ph"] for e in flows} >= {"s", "f"}
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], set()).add(e["pid"])
    assert any(len(pids) > 1 for pids in by_id.values())   # cross-rank
    # X events keep the Perfetto-required fields after stitching
    for e in loaded["traceEvents"]:
        if e.get("ph") == "X":
            for field in ("name", "ts", "dur", "pid", "tid"):
                assert field in e, field


def test_trace_stitch_aligns_clock_origins():
    tr0, tr1 = SpanTracer(8), SpanTracer(8)
    now = time.perf_counter()
    tr0.record_span("a", now, now + 0.001)
    tr1.record_span("b", now, now + 0.001)
    d0, d1 = tr0.export_chrome(pid=0), tr1.export_chrome(pid=1)
    # pretend rank 1 booted 2s later: its self-relative ts would be 2s
    # behind without the anchor shift
    d1["metadata"]["clock_origin_unix_s"] += 2.0
    for ev in d1["traceEvents"]:
        if "ts" in ev:
            ev["ts"] -= 2e6
    stitched, _ = stitch([d0, d1])
    xs = {e["pid"]: e["ts"] for e in stitched["traceEvents"]
          if e.get("ph") == "X"}
    assert abs(xs[0] - xs[1]) < 1e4    # realigned within 10ms


def test_trace_stitch_unanchored_doc_stays_unshifted():
    """A pre-round-14 export without clock_origin_unix_s must not drag
    the merged timeline to unix epoch 0 (a ~54-year shift for every
    anchored rank) — it stays unshifted and is named in the summary."""
    tr0 = SpanTracer(8)
    now = time.perf_counter()
    tr0.record_span("a", now, now + 0.001)
    d0 = tr0.export_chrome(pid=0)
    legacy = {"traceEvents": [{"ph": "X", "name": "old", "pid": 9,
                               "tid": 1, "ts": 5.0, "dur": 1.0}]}
    stitched, summary = stitch([d0, legacy])
    assert summary["unanchored_ranks"] == [1]
    xs = {e["pid"]: e["ts"] for e in stitched["traceEvents"]
          if e.get("ph") == "X"}
    assert xs[1] == 5.0                      # unshifted
    assert xs[0] < 1e13                      # no 54-year offset either


def test_flight_unwritable_dir_degrades_not_raises(tmp_path,
                                                   no_active_flight):
    blocker = tmp_path / "a_file"
    blocker.write_text("not a dir")
    flags.set_flag("obs_flight_dir", str(blocker / "sub"))
    assert flight.ensure_from_flags(rank=0) is None   # warned, not raised
    assert flight.active() is None


def test_next_trace_id_unique_and_disjoint():
    ids = {next_trace_id() for _ in range(100)}
    assert len(ids) == 100
    assert all(i >> 63 for i in ids)               # request-id space
    assert step_trace_id(3, 12) >> 63 == 0         # step-id space


# ------------------------------------------------------------ chaos leg

@pytest.mark.slow
def test_chaos_seal_real_cluster():
    """Kill a rank mid-pass in a REAL 2-process cluster (SIGABRT and
    SIGKILL legs): parseable SEALED bundle / flight segments for the
    dead rank, rank 0 health flags it within 2 cadences, and the
    per-rank traces stitch with cross-rank flows."""
    r = subprocess.run(
        [sys.executable, "-u",
         os.path.join(REPO, "tools", "chaos_seal_probe.py")],
        capture_output=True, text=True, timeout=280,
        cwd=REPO)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["all_ok"] is True
