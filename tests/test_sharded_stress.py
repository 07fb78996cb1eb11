"""Seeded stress harness around the sharded staging path (round 12).

Chases the PR-6 flake (test_sharded_blocked_matches_scatter failed once
under native-recompile load: 6/780 show-like elements off by one —
never reproduced). The harness lives in tools/sharded_stress_probe.py
so campaigns can run long outside pytest; this suite keeps it honest:

  * the tier-flip hypothesis check runs for real (native vs numpy
    router must product-match absent bucket overflow)
  * one seeded stress rep under burner load runs the 4-config parity

Both slow-marked: multi-minute sharded e2e compositions (the flaky
composition itself is slow-marked too).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def stress_data(tmp_path_factory):
    from tools.sharded_stress_probe import make_data
    return make_data(13, str(tmp_path_factory.mktemp("stress")))


def test_router_tier_flip_product_match(stress_data):
    """Native router vs numpy fallback train bit-identically at the
    flaky test's shape (no bucket overflow): a mid-run recompile window
    flipping the tier cannot explain the PR-6 flake here. If THIS ever
    fails, the flake mechanism is pinned — record the diff and the
    bucketize-overflow state in PERF.md."""
    from tools.sharded_stress_probe import run_tier_flip
    files, feed = stress_data
    diff = run_tier_flip(files, feed, seed=13)
    assert diff is None, diff


def test_seeded_stress_rep_parity(stress_data):
    """One harness rep under burner load: blocked == scatter bit-exact
    on both wires. A failure here is the PR-6 flake reproducing —
    DON'T retry it away; capture the seed + diff into PERF.md."""
    from tools.sharded_stress_probe import LoadBurners, run_rep
    files, feed = stress_data
    burners = LoadBurners(2)
    try:
        bad = run_rep(files, feed, seed=17)
    finally:
        burners.stop()
    assert not bad, bad


def test_diff_states_detects_planted_mismatch():
    """The harness's comparator itself (fast): a planted off-by-one in
    one element must be reported with count/col diagnostics — guards
    against a silently-vacuous campaign."""
    from tools.sharded_stress_probe import diff_states
    k = np.arange(10, dtype=np.uint64)
    v = np.ones((10, 5), np.float32)
    v2 = v.copy()
    assert diff_states([(k, v)], [(k, v2)]) is None
    v2[3, 2] += 1.0
    d = diff_states([(k, v)], [(k, v2)])
    assert d == {"shard": 0, "kind": "values", "n_bad": 1, "of": 50,
                 "max_abs_diff": 1.0, "cols": [2]}
    # permuted key order is still the same state
    perm = np.random.RandomState(0).permutation(10)
    assert diff_states([(k, v)], [(k[perm], v[perm])]) is None
