"""Device-plane observability (obs/device.py, round 20).

Pins the tentpole's four signals end to end through the unchanged
publication machinery:

  * recompile sentinel — forced shape churn is counted, logged loudly
    EXACTLY once per fn, and scores the rank unhealthy through
    HealthMonitor within one window;
  * donation audit — a deliberately non-donated twin trips
    donation_miss (and a properly donated fn never does, pinned on CPU
    where donation IS honored);
  * HBM live-buffer ledger — owner bucketing, and the leak detector
    fires on an intentionally leaked array across passes while staying
    silent across clean passes;
  * surfaces — StepReport stats deltas, the /device + /metrics
    endpoints, and the flight-recorder seal all carry the device
    snapshot (schemas pinned);

plus the safety contract that makes the wrapper deployable at every
jit site: instrumented-vs-bare bit-parity on the e2e trainer.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.obs import device
from paddlebox_tpu.obs.device import InstrumentedJit, instrument_jit
from paddlebox_tpu.obs.exporter import ObsExporter
from paddlebox_tpu.obs.health import HealthMonitor
from paddlebox_tpu.obs.report import ListSink, StepReporter
from paddlebox_tpu.train import BoxTrainer
from paddlebox_tpu.utils.stats import StatRegistry, stat_get

DEVICE_STATS = ("device_recompiles", "donation_miss", "device_leak_suspect",
                "device_transfer_bytes_h2d", "device_transfer_bytes_d2h")

# big enough to clear the device_donation_min_bytes audit floor (64 KB)
BIG = (64, 1024)


def _reset_device_state():
    reg = StatRegistry.instance()
    snap = reg.snapshot_all()
    names = set(DEVICE_STATS)
    for kind in ("counters", "gauges", "hists"):
        names.update(k for k in snap[kind] if k.startswith("device_"))
    for k in names:
        reg.reset(k)
    device.monitor().reset()


@pytest.fixture(autouse=True)
def _device_isolation():
    """Zero the device-plane stats + monitor around every test: the
    stats are process-global counters and every other suite's trainers
    bump them."""
    mon = device.monitor()
    with mon._lock:
        before = list(mon._entries.values())
    # a module-level jit's entry counts its compiles over the process's
    # life: whether the suites this worker ran before left one (say
    # pass_table._writeback_gather, a shape a table) past its warmup is
    # the scheduler's draw, not this test's doing
    history = [(e, e.compiles, e.steady_recompiles, e.recompile_flagged)
               for e in before]
    for e in before:
        e.compiles, e.steady_recompiles, e.recompile_flagged = 0, 0, False
    _reset_device_state()
    yield
    _reset_device_state()
    # a module-level jit (pass_table._delta_promote) registers its entry
    # ONCE, at import: put back what this worker's later suites read
    for e, compiles, steady, flagged in history:
        e.compiles += compiles
        e.steady_recompiles += steady
        e.recompile_flagged = e.recompile_flagged or flagged
        mon.register(e)


def _f(x, y):
    return x * 2 + y, x.sum()


def _big(v=1.0):
    return jnp.full(BIG, v, jnp.float32)


# ----------------------------------------------------------- the wrapper

def test_instrumented_jit_matches_bare_jit():
    j = instrument_jit(_f, "parity")
    b = jax.jit(_f)
    x, y = _big(3.0), _big(5.0)
    out_i = j(x, y)
    out_b = b(x, y)
    for a, c in zip(jax.tree_util.tree_leaves(out_i),
                    jax.tree_util.tree_leaves(out_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_compile_counted_once_per_signature():
    j = instrument_jit(_f, "count")
    j(_big(), _big())
    j(_big(2.0), _big(2.0))     # same signature: cache hit
    e = device.snapshot()["entries"]["count"]
    assert e["compiles"] == 1
    assert e["compile_ms"] > 0
    assert e["signatures"] == 1
    assert e["analysis"]["temp_bytes"] >= 0
    assert e["analysis"]["bytes_accessed"] > 0


def test_lower_passthrough_and_shared_analysis():
    """The AOT surface step_audit consumes, and the ONE copy of the
    per-example math."""
    j = instrument_jit(_f, "aot")
    compiled = j.lower(_big(), _big()).compile()
    out = device.analyze_compiled(compiled, examples=64)
    assert out["bytes_accessed_per_example"] == round(
        out["bytes_accessed"] / 64)
    assert out["flops_per_example"] == round(out["flops"] / 64)


def test_static_argnames_dispatch():
    def g(x, n):
        return x * n
    j = instrument_jit(g, "static", static_argnames=("n",))
    np.testing.assert_array_equal(np.asarray(j(jnp.arange(4.0), 3)),
                                  np.arange(4.0) * 3)
    np.testing.assert_array_equal(np.asarray(j(jnp.arange(4.0), 5)),
                                  np.arange(4.0) * 5)
    assert device.snapshot()["entries"]["static"]["compiles"] == 2


# ---------------------------------------------------- recompile sentinel

def test_recompile_sentinel_counts_and_flags_once(monkeypatch):
    warns = []
    from paddlebox_tpu.obs import log as obs_log
    real = obs_log.warning
    monkeypatch.setattr(
        obs_log, "warning",
        lambda msg, **kw: (warns.append(msg) if "recompile" in msg
                           else real(msg, **kw)))
    flags.set_flag("device_recompile_warmup", 2)
    j = instrument_jit(_f, "churny")
    for n in (8, 16, 32, 64, 128):   # 5 distinct signatures
        a = jnp.ones((n,), jnp.float32)
        j(a, a)
    e = device.snapshot()["entries"]["churny"]
    assert e["compiles"] == 5
    # warmup 2 -> compiles 3, 4, 5 are steady-state churn
    assert e["steady_recompiles"] == 3
    assert stat_get("device_recompiles") == 3
    assert e["recompile_flagged"] is True
    assert len(warns) == 1, warns    # loud ONCE per fn


def test_recompile_warmup_override():
    flags.set_flag("device_recompile_warmup", 1)
    j = instrument_jit(_f, "wide", recompile_warmup=16)
    for n in (8, 16, 32, 64):
        a = jnp.ones((n,), jnp.float32)
        j(a, a)
    assert stat_get("device_recompiles") == 0
    assert not device.snapshot()["entries"]["wide"]["recompile_flagged"]


def test_recompiles_scored_unhealthy_by_health_monitor():
    """Acceptance: the sentinel turns the rank unhealthy within 2 report
    windows — the very FIRST window carrying the stat delta scores it."""
    hm = HealthMonitor(world=2)
    merged = {"step": 10, "stale_ranks": [],
              "metrics": {"stats.device_recompiles":
                          {"per_rank": {"0": 3.0}}}}
    rec = hm.update(merged)
    assert rec["ranks"]["0"]["healthy"] is False
    assert "device_recompiles" in rec["ranks"]["0"]["flags"]
    assert rec["ranks"]["1"]["healthy"] is True
    assert 0 in rec["unhealthy_ranks"]


# -------------------------------------------------------- donation audit

def test_donated_entry_point_reuses_buffer():
    """CPU honors donation (trainer.py's documented contract): the
    donated pointer comes back as an output and the audit stays clean."""
    j = instrument_jit(_f, "donated", donate_argnums=(0,))
    for _ in range(3):
        j(_big(), _big())
    d = device.snapshot()["entries"]["donated"]["donation"]
    assert d["supported"] is True
    assert d["checks"] == 3
    assert d["misses"] == 0
    assert stat_get("donation_miss") == 0


def test_non_donated_twin_trips_donation_miss(monkeypatch):
    warns = []
    from paddlebox_tpu.obs import log as obs_log
    real = obs_log.warning
    monkeypatch.setattr(
        obs_log, "warning",
        lambda msg, **kw: (warns.append(msg) if "donation" in msg
                           else real(msg, **kw)))
    j = instrument_jit(_f, "twin", audit_argnums=(0,))  # audited, NOT donated
    for _ in range(3):
        j(_big(), _big())
    d = device.snapshot()["entries"]["twin"]["donation"]
    # every call misses; the debounce counts from the SECOND consecutive
    # miss of the executable (an isolated miss is the one-time copy of a
    # host-staged buffer, not the regime)
    assert d["checks"] == 3
    assert d["misses"] == 2
    assert stat_get("donation_miss") == 2
    assert len(warns) == 1, warns    # loud once per fn

    hm = HealthMonitor(world=1)
    rec = hm.update({"step": 1, "stale_ranks": [],
                     "metrics": {"stats.donation_miss":
                                 {"per_rank": {"0": 2.0}}}})
    assert rec["ranks"]["0"]["healthy"] is False
    assert "donation_miss" in rec["ranks"]["0"]["flags"]


def test_donation_miss_debounced_per_executable():
    """An ISOLATED miss is never counted: the pass's first step donates
    the host-staged slab — a buffer jax zero-copied from numpy memory
    that cannot be aliased in place and is copied exactly once — while
    the regime-step alarm is for the RECURRING per-step copy."""
    # one audited call that misses, then silence: not counted
    j = instrument_jit(_f, "lone", audit_argnums=(0,))
    j(_big(), _big())
    d = device.snapshot()["entries"]["lone"]["donation"]
    assert d["checks"] == 1 and d["misses"] == 0
    assert stat_get("donation_miss") == 0

    # the e2e shape: host-staged first input misses once, the chained
    # device-produced outputs alias cleanly — audit stays at zero
    k = instrument_jit(_f, "staged", donate_argnums=(0,))
    x = jnp.asarray(np.full(BIG, 1.0, np.float32))  # host-backed
    for _ in range(3):
        x, _ = k(x, _big())
    d = device.snapshot()["entries"]["staged"]["donation"]
    assert d["checks"] == 3 and d["misses"] == 0
    assert stat_get("donation_miss") == 0


def test_donation_audit_skips_small_buffers():
    """Buffers under device_donation_min_bytes are aliasing noise —
    never audited, never counted."""
    j = instrument_jit(_f, "tiny", audit_argnums=(0,))
    a = jnp.ones((8,), jnp.float32)
    j(a, a)
    d = device.snapshot()["entries"]["tiny"]["donation"]
    assert d["checks"] == 0
    assert stat_get("donation_miss") == 0


# -------------------------------------------------------- transfer ledger

def test_transfer_ledger_counters_and_hists():
    device.account_h2d(100_000)
    device.account_h2d(50_000)
    device.account_d2h(7_000)
    snap = device.snapshot()["transfers"]
    assert snap["h2d_bytes"] == 150_000
    assert snap["d2h_bytes"] == 7_000
    hists = StatRegistry.instance().snapshot_all()["hists"]
    assert sum(hists["device_h2d_bytes"]) == 2
    assert sum(hists["device_d2h_bytes"]) == 1


def test_tree_nbytes_walks_containers():
    t = {"a": np.zeros(10, np.float32),
         "b": [np.zeros(3, np.int64), (np.zeros(2, np.uint8), None)]}
    assert device.tree_nbytes(t) == 40 + 24 + 2


# ------------------------------------------------------ HBM ledger + leak

def test_ledger_buckets_by_owner():
    keep = _big()  # 256 KB
    device.register_owner("slab", lambda: keep)
    # an entry so the monitor reads active
    j = instrument_jit(_f, "ledgered")
    j(keep, _big())
    sample = device.sample_ledger()
    assert sample["owners"]["slab"] == keep.nbytes
    assert sample["total_bytes"] >= keep.nbytes
    g = StatRegistry.instance().snapshot_all()["gauges"]
    assert g["device_live_bytes_slab"] == float(keep.nbytes)
    assert g["device_live_bytes_total"] == float(sample["total_bytes"])


def test_leak_detector_fires_on_leak_and_stays_silent_when_clean():
    flags.set_flag("device_leak_windows", 3)
    flags.set_flag("device_leak_min_bytes", 100_000)
    leaked = []

    # three clean passes: stable totals, no alarm
    base = _big()
    for _ in range(3):
        device.sample_ledger()
    assert stat_get("device_leak_suspect") == 0

    # leak one ~256 KB array per "pass": 3 consecutive growth windows
    for _ in range(4):
        leaked.append(_big())
        device.sample_ledger()
    assert stat_get("device_leak_suspect") >= 1
    fired = stat_get("device_leak_suspect")

    # growth stopped: streak resets, no further alarms
    for _ in range(3):
        device.sample_ledger()
    assert stat_get("device_leak_suspect") == fired
    del base, leaked


# ------------------------------------------------------- report plumbing

def test_step_report_carries_device_stats_and_ledger_gauges():
    """Acceptance (i): a forced recompile and a donation miss land in
    the StepReport stats delta; the ledger gauges ride the same record."""
    flags.set_flag("device_recompile_warmup", 1)
    j = instrument_jit(_f, "report_churn")
    for n in (8, 16, 32):
        a = jnp.ones((n,), jnp.float32)
        j(a, a)
    t = instrument_jit(_f, "report_twin", audit_argnums=(0,))
    t(_big(), _big())
    t(_big(), _big())  # second consecutive miss crosses the debounce

    sink = ListSink()
    rep = StepReporter(rank=0, every=1, sink=sink)
    rep.note_examples(10)
    rec = rep.maybe_report(1, force=True)
    assert rec["stats"]["device_recompiles"] == 2
    assert rec["stats"]["donation_miss"] == 1
    # ledger sampled at report cadence (monitor is active)
    assert rec["gauges"]["device_live_bytes_total"] > 0
    rep.close()


# ----------------------------------------------------------- HTTP surface

def _get(exp, path):
    r = urllib.request.urlopen(
        "http://127.0.0.1:%d%s" % (exp.port, path), timeout=5.0)
    return r.read().decode("utf-8")


def test_device_endpoint_schema_pinned():
    """Acceptance (ii): the forced signals are visible on /device and
    /metrics."""
    flags.set_flag("device_recompile_warmup", 1)
    j = instrument_jit(_f, "http_churn", donate_argnums=(0,))
    for n in (256, 512, 1024):
        j(jnp.ones((n, 64), jnp.float32), jnp.ones((n, 64), jnp.float32))
    t = instrument_jit(_f, "http_twin", audit_argnums=(0,))
    t(_big(), _big())
    t(_big(), _big())  # second consecutive miss crosses the debounce
    device.account_h2d(12345)

    exp = ObsExporter(port=0)
    try:
        snap = json.loads(_get(exp, "/device"))
        assert snap["type"] == "device_plane"
        assert snap["v"] == 1
        assert snap["active"] is True
        assert snap["rank"] == 0
        e = snap["entries"]["http_churn"]
        for key in ("compiles", "compile_ms", "last_compile_ms",
                    "signatures", "steady_recompiles", "recompile_flagged",
                    "donate_argnums", "donation", "analysis"):
            assert key in e, key
        assert e["compiles"] == 3
        assert e["recompile_flagged"] is True
        assert snap["entries"]["http_twin"]["donation"]["misses"] == 1
        assert snap["recompiles"] == 2
        assert snap["donation_miss"] == 1
        assert snap["transfers"]["h2d_bytes"] == 12345

        text = _get(exp, "/metrics")
        assert "pbtpu_device_recompiles 2" in text
        assert "pbtpu_donation_miss 1" in text
        assert "pbtpu_device_transfer_bytes_h2d 12345" in text
        assert 'pbtpu_device_compile_ms_bucket{le="+Inf"} 4' in text

        # the index advertises the new endpoint
        assert "/device" in json.loads(_get(exp, "/"))["endpoints"]
    finally:
        exp.close()


# ----------------------------------------------------------- flight seal

def test_flight_seal_includes_device_snapshot(tmp_path):
    """Acceptance (iv): a seal carries the device snapshot — the
    postmortem says whether the dying rank was recompiling or copying
    its slab."""
    from paddlebox_tpu.obs.flight import FlightRecorder
    flags.set_flag("device_recompile_warmup", 1)
    j = instrument_jit(_f, "seal_churn")
    for n in (8, 16, 32):
        a = jnp.ones((n,), jnp.float32)
        j(a, a)
    fr = FlightRecorder(str(tmp_path), rank=0)
    try:
        path = fr.seal("test_seal")
        with open(path, "r", encoding="utf-8") as fh:
            manifest = json.load(fh)
        dev = manifest["device"]
        assert dev["type"] == "device_plane"
        assert dev["entries"]["seal_churn"]["recompile_flagged"] is True
        assert dev["recompiles"] == 2
    finally:
        fr.close()


def test_snapshot_reentrant_from_seal_path():
    """The fatal-signal seal calls snapshot() from a handler that can
    interrupt this same thread inside a monitor mutation or stat_add —
    the monitor RLock + lock-free stat peeks must let the dying process
    seal instead of self-deadlocking (the PR-9 tracer._reg_lock class)."""
    from paddlebox_tpu.utils.stats import StatRegistry
    j = instrument_jit(_f, "sealable")
    j(_big(), _big())
    with device.monitor()._lock:          # handler fired mid-register
        snap = device.snapshot()
    assert snap["entries"]["sealable"]["compiles"] == 1
    with StatRegistry.instance()._lock:   # handler fired mid-stat_add
        snap = device.snapshot()
    assert snap["entries"]["sealable"]["compiles"] == 1


# --------------------------------------------------------- e2e bit parity

NUM_SLOTS = 4
D = 8


def _mini_trainer(feed, seed=0):
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=1 << 12,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)
    model = CtrDnn(spec, hidden=(16,))
    return BoxTrainer(model, table_cfg, feed,
                      TrainerConfig(dense_lr=3e-3), seed=seed)


def test_e2e_instrumented_vs_bare_bit_parity(tmp_path):
    """The wrapper is a pure twin: a training pass under device_obs on
    vs off (bare jax.jit) produces BIT-identical params and slab — and
    the instrumented pass is recompile/donation-miss clean (the
    steady-state gates the regression probe enforces)."""
    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=1, lines_per_file=400,
        num_slots=NUM_SLOTS, vocab_per_slot=50, max_len=3, seed=3)
    feed = type(feed)(slots=feed.slots, batch_size=64)

    results = {}
    for obs_on in (True, False):
        flags.set_flag("device_obs", obs_on)
        trainer = _mini_trainer(feed)
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        trainer.train_pass(ds)
        ds.release_memory()
        results[obs_on] = (
            jax.tree_util.tree_map(np.asarray, trainer.params),
            np.asarray(trainer.table._slab),  # resident post-pass slab
        )
        if obs_on:
            # steady state is clean: no sentinel trips, no misses
            assert stat_get("device_recompiles") == 0
            assert stat_get("donation_miss") == 0
            assert device.snapshot()["entries"]["train_step"] is not None
        trainer.close()

    on_leaves = jax.tree_util.tree_leaves(results[True][0])
    off_leaves = jax.tree_util.tree_leaves(results[False][0])
    assert len(on_leaves) == len(off_leaves)
    for a, b in zip(on_leaves, off_leaves):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(results[True][1], results[False][1])


def test_flag_off_returns_bare_jit():
    flags.set_flag("device_obs", False)
    j = instrument_jit(_f, "bare")
    assert not isinstance(j, InstrumentedJit)
    out = j(_big(), _big())
    assert np.asarray(out[1]) == pytest.approx(64 * 1024.0)
    assert "bare" not in device.snapshot()["entries"]


# ------------------------------------------- the sequence tower's scopes

@pytest.mark.parametrize("name", ["attn_window", "attn_full", "moe_route",
                                  "moe_experts", "dense_mlp", "ssm_proj",
                                  "ssm_conv", "ssd_scan", "ssm_gate_norm",
                                  "moe_latent", "moe_shared"])
def test_tower_scope_resolves_through_autodiff_and_checkpoint(name):
    """A kernel's scope inside fwd_bwd, under jax.checkpoint and
    value_and_grad: the backward pass's operations, the recomputed
    forward's among them, carry the forward's scope (jvp / transpose
    wrappers and the checkpoint's own path components are seen
    through), and the innermost name wins over fwd_bwd."""
    import re

    assert name in device.SCOPE_NAMES

    @jax.checkpoint
    def layer(w, x):
        with jax.named_scope(name):
            return jnp.tanh(x @ w)

    def loss(w, x):
        with jax.named_scope("fwd_bwd"):
            return layer(w, layer(w, x)).sum()

    w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
    text = jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()
    scopes = device.scope_map(text)
    seen = {"backward": 0, "checkpoint": 0, "recomputed": 0}
    for line in text.splitlines():
        instr = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        path = re.search(r'op_name="([^"]*)"', line)
        if instr is None or path is None:
            continue
        parts = path.group(1).split("/")
        if name not in parts:
            continue
        assert scopes[instr.group(1)] == name, line
        seen["backward"] += "transpose(jvp(fwd_bwd))" in parts
        seen["checkpoint"] += "checkpoint" in parts
        seen["recomputed"] += "rematted_computation" in parts
    assert all(seen.values()), seen
    # the scope itself under the wrappers, as autodiff names it when the
    # scope is the outermost of the differentiated function
    wrapped = "\n".join([
        "HloModule jit_f",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        '  %a = f32[4]{0} parameter(0)',
        '  ROOT %k.1 = f32[4]{0} custom-call(%a), metadata={op_name='
        '"jit(f)/while/body/checkpoint/transpose(jvp(fwd_bwd))/'
        'transpose(jvp(' + name + '))/vmap(pallas_call)"}',
        "}"])
    assert device.scope_map(wrapped)["k.1"] == name
    # a Mosaic kernel's custom-call is printed over several lines, its
    # metadata on the last: the instruction named on the first gets it
    spread = "\n".join([
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        '  %splash_fwd.3 = (f32[4]{0}, f32[4]{0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={',
        '}}, metadata={op_name="jit(f)/jvp(fwd_bwd)/checkpoint/'
        + name + '/jit(_splash_attention)/pallas_call"}, backend_config={}',
        '  %gte.4 = f32[4]{0} get-tuple-element(%splash_fwd.3), index=0',
        "}"])
    assert device.scope_map(spread) == {"splash_fwd.3": name, "gte.4": ""}
