"""Test env: CPU backend with 8 virtual devices so multi-chip sharding
paths (mesh/pjit/shard_map/all_to_all) are exercised without TPU hardware —
the multi-host-sim test tier called for by SURVEY.md §4. Both are
environment variables, set here before jax is first imported."""

import json
import os
import time

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _flag_isolation():
    """Snapshot/restore the process-flag registry around EVERY test:
    round-4's full-suite-order flake (test_hierarchical_mesh_matches_flat
    passing alone, failing in suite order) was cross-test contamination of
    exactly this global state — a test that sets a flag and raises (or
    just forgets to reset) silently changes every later test's numerics.
    Restoring unconditionally makes test order irrelevant to flags."""
    from paddlebox_tpu.config import flags as _f

    snapshot = _f.all_flags()
    yield
    for name, value in snapshot.items():
        if _f.get_flag(name) != value:
            _f.set_flag(name, value)
    # round 18: the quality/drift planes keep module-global state (the
    # live-ops exporter reads them without a binding dance); a drift
    # reference window leaking across tests would score phantom drift
    # against the previous test's slot schema
    from paddlebox_tpu.metrics import drift as _drift
    from paddlebox_tpu.metrics import quality as _quality
    _quality.set_active(None)
    _drift.set_active(None)
    # with obs_http_port restored (default 0) this closes any exporter
    # a test left listening, releasing its port for later tests
    from paddlebox_tpu.obs import exporter as _exporter
    _exporter.ensure_from_flags()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the budgeted tier-1 run (-m 'not slow'); "
        "runs in the slow-inclusive suite and on TPU windows")
    config._pbtpu_t0 = time.monotonic()


# ---------------------------------------------------------------------------
# Tier-1 budget visibility (round 14): the suite runs against a hard
# 870s timeout with no per-test attribution — this hook writes one
# durations JSONL per run (who pays), prints the 15 slowest, and WARNS
# (never fails) when the run lands past 90% of the budget.

_DURATIONS = {}


def pytest_runtest_logreport(report):
    if report.when in ("setup", "call", "teardown"):
        _DURATIONS[report.nodeid] = (
            _DURATIONS.get(report.nodeid, 0.0) + report.duration)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _DURATIONS:
        return
    path = os.environ.get("PBTPU_TEST_DURATIONS",
                          "/tmp/pbtpu_test_durations.jsonl")
    wall = time.monotonic() - getattr(config, "_pbtpu_t0",
                                      time.monotonic())
    ranked = sorted(_DURATIONS.items(), key=lambda kv: -kv[1])
    try:
        with open(path, "w", encoding="utf-8") as fh:
            for nodeid, dur in ranked:
                fh.write(json.dumps({"nodeid": nodeid,
                                     "duration_s": round(dur, 3)}) + "\n")
            fh.write(json.dumps({"summary": True, "tests": len(ranked),
                                 "sum_s": round(sum(_DURATIONS.values()),
                                                1),
                                 "wall_s": round(wall, 1)}) + "\n")
    except OSError:
        path = "<unwritable>"
    tw = terminalreporter
    tw.write_line("")
    tw.write_line("slowest 15 tests (durations jsonl: %s)" % path)
    for nodeid, dur in ranked[:15]:
        tw.write_line("  %8.2fs  %s" % (dur, nodeid))
    budget = float(os.environ.get("PBTPU_TIER1_BUDGET_SECS", "870"))
    # wall is the honest projection (it includes collection + import);
    # the per-test sum attributes it
    if budget > 0 and wall > 0.9 * budget:
        tw.write_line(
            "WARNING: suite wall %.0fs exceeds 90%% of the %.0fs tier-1 "
            "budget (sum of test durations %.0fs) — new suites must "
            "earn their seconds or go slow" % (
                wall, budget, sum(_DURATIONS.values())),
            yellow=True)


@pytest.fixture
def lock_order_watch():
    """Run a concurrency test under the lockwatch runtime validator
    (utils/lockwatch.py, flag debug_lock_order): locks constructed while
    this fixture is live record per-thread acquisition order, and the
    teardown ASSERTS no AB/BA inversion was observed — the dynamic twin
    of boxlint's static BX7xx pass. Order matters: list this fixture
    BEFORE any fixture that constructs the objects under test, so their
    locks are built through the watch."""
    from paddlebox_tpu.config import flags
    from paddlebox_tpu.utils import lockwatch

    flags.set_flag("debug_lock_order", True)
    lockwatch.reset()
    yield lockwatch
    try:
        lockwatch.assert_consistent()
    finally:
        lockwatch.reset()
        flags.set_flag("debug_lock_order", False)
