"""Seeded stress-rerun harness for the PR-6 sharded parity flake.

The target: tests/test_push_blocked.py::test_sharded_blocked_matches_
scatter flaked EXACTLY ONCE (2026-08-03, round 11) — 6/780 store
elements off by one in a show-like column (≈0.9 vs 1.9: one occurrence
of one key counted in one run and not the other) — in the only run
where the native .so recompile subprocess was executing concurrently.
10 clean reruns followed; root cause not found. This harness makes the
reproduction attempt MECHANICAL instead of anecdotal:

  * ``--reps N`` seeded stress reruns of the 4-config sharded
    blocked-vs-scatter parity (fresh synthetic data per seed), each rep
    under synthetic co-tenant load: GIL-dropping numpy sort burners
    plus an optional looping g++ compile subprocess (``--recompile``,
    the exact co-tenant the flake run had)
  * ``--tier-flip`` runs a HYPOTHESIS test directly: the same config
    trained once with the native router and once with the numpy
    fallback (what a mid-run recompile window can flip between). The
    two tiers only contract to identical products while no bucket
    overflows — WHICH occurrences drop on overflow is explicitly
    unspecified (sharded_table.bucketize docstring), and a dropped
    occurrence is exactly a show-column off-by-one. A mismatch here
    pins that mechanism; a match kills the hypothesis for this shape.

RESOLUTION (round 12): the race was PINNED — not by this e2e harness
(whose shape manifests it only rarely) but by the concurrent-parity
audit it motivated: rt_bucketize kept its generation-tagged dedup
scratch in the SHARED RouteIndex while the stager pool calls it
concurrently on one index with the GIL dropped; two callers drawing the
same generation read each other's seen-marks and silently mis-route an
occurrence (a direct 4-thread repro mismatched 1379/2400 routings).
Fixed by thread-local scratch (native/route.cc round-12 thread
contract); tests/test_native.py::test_concurrent_bucketize_parity is
the regression pin, and this harness remains the e2e-level guard.

Every line is JSON; a parity mismatch prints the differing element
count / max abs diff / affected columns + the rep's seed, and exits 1.

Usage:
  timeout 3600 python -u tools/sharded_stress_probe.py \
      [--reps 5] [--seed 13] [--burners 2] [--recompile] [--tier-flip]
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np  # noqa: E402

D = 4
NUM_SLOTS = 4


def make_data(seed, workdir):
    from paddlebox_tpu.data import write_synthetic_ctr_files
    files, feed = write_synthetic_ctr_files(
        os.path.join(workdir, f"data_{seed}"), num_files=2,
        lines_per_file=480, num_slots=NUM_SLOTS, vocab_per_slot=120,
        max_len=3, seed=seed)
    return files, type(feed)(slots=feed.slots, batch_size=64)


def train_states(files, feed, mode, uid, seed, force_numpy_route=False):
    """One ShardedBoxTrainer pass at (push_write, uid wire); returns the
    per-shard store state — the flaky test's exact workload shape.
    force_numpy_route drops the batch router to the numpy tier (the
    tier a broken/mid-recompile native load falls back to)."""
    from paddlebox_tpu.config import flags
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.parallel import ShardedBoxTrainer
    from paddlebox_tpu.parallel import sharded_table as st

    snapshot = {k: flags.get_flag(k) for k in  # boxlint: disable=BX305
                ("push_write", "push_block_rows", "h2d_uid_wire")}
    real_route = st._route_lib
    flags.set_flag("push_write", mode)
    flags.set_flag("push_block_rows", 128)
    flags.set_flag("h2d_uid_wire", uid)
    if force_numpy_route:
        st._route_lib = lambda: None
    try:
        table_cfg = TableConfig(
            embedx_dim=D, pass_capacity=8 * (1 << 9),
            optimizer=SparseOptimizerConfig(
                mf_create_thresholds=0.0, mf_initial_range=1e-3,
                feature_learning_rate=0.1, mf_learning_rate=0.1))
        model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                       hidden=(16,))
        trainer = ShardedBoxTrainer(model, table_cfg, feed,
                                    TrainerConfig(dense_lr=3e-3),
                                    seed=seed)
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files[:1])
        trainer.train_pass(ds)
        states = [s.state_items() for s in trainer.table.stores]
        trainer.close()
        ds.release_memory()
        return states
    finally:
        st._route_lib = real_route
        for k, v in snapshot.items():
            # restoring the snapshot taken above — registry names
            flags.set_flag(k, v)  # boxlint: disable=BX305


def diff_states(a, b):
    """None when bit-identical, else a diagnostic dict."""
    for shard, ((ka, va), (kb, vb)) in enumerate(zip(a, b)):
        oa, ob = np.argsort(ka), np.argsort(kb)
        if not np.array_equal(ka[oa], kb[ob]):
            return {"shard": shard, "kind": "key_set"}
        va, vb = va[oa], vb[ob]
        if va.shape != vb.shape or not np.array_equal(va, vb):
            bad = np.nonzero(va != vb)
            return {
                "shard": shard, "kind": "values",
                "n_bad": int(bad[0].size), "of": int(va.size),
                "max_abs_diff": float(np.abs(va - vb).max()),
                "cols": sorted(set(bad[1].tolist()))[:8],
            }
    return None


class LoadBurners:
    """GIL-dropping co-tenant load: numpy sorts on daemon threads."""

    def __init__(self, n):
        self._stop = threading.Event()
        self._threads = []
        for i in range(n):
            a = np.random.RandomState(i).randint(0, 1 << 40, 1 << 19)

            def burn(arr=a):
                while not self._stop.is_set():
                    np.sort(arr)

            t = threading.Thread(target=burn, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)


class RecompileLoop:
    """Loops a real g++ -O3 compile of route.cc into a scratch dir —
    the exact co-tenant process mix of the one observed flake (the
    repo's own .so is never touched)."""

    def __init__(self):
        self._stop = threading.Event()
        self._scratch = tempfile.mkdtemp(prefix="pbx_stress_cc_")
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "paddlebox_tpu", "native",
            "route.cc")
        self._src = shutil.copy(src, self._scratch)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        out = os.path.join(self._scratch, "scratch.so")
        while not self._stop.is_set():
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-o", out, self._src],
                capture_output=True)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=60.0)
        shutil.rmtree(self._scratch, ignore_errors=True)


CONFIGS = (("scatter", False), ("blocked", False),
           ("scatter", True), ("blocked", True))


def run_rep(files, feed, seed):
    """One seeded stress rep: all 4 configs, blocked-vs-scatter parity
    per wire. Returns list of mismatch diagnostics (empty = clean)."""
    states = {}
    for mode, uid in CONFIGS:
        states[(mode, uid)] = train_states(files, feed, mode, uid, seed)
    bad = []
    for uid in (False, True):
        d = diff_states(states[("blocked", uid)], states[("scatter", uid)])
        if d is not None:
            d["wire"] = "uid" if uid else "full"
            bad.append(d)
    return bad


def run_tier_flip(files, feed, seed):
    """Native-vs-numpy router tier at a FIXED config (scatter, full
    wire): the products contract to be identical absent bucket
    overflow. A diff here = the recompile-window tier flip can produce
    exactly the observed off-by-one class."""
    a = train_states(files, feed, "scatter", False, seed,
                     force_numpy_route=False)
    b = train_states(files, feed, "scatter", False, seed,
                     force_numpy_route=True)
    return diff_states(a, b)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=13)
    ap.add_argument("--burners", type=int, default=2)
    ap.add_argument("--recompile", action="store_true")
    ap.add_argument("--tier-flip", action="store_true")
    args = ap.parse_args()

    work = tempfile.mkdtemp(prefix="pbx_stress_")
    failures = 0
    burners = LoadBurners(args.burners) if args.burners else None
    recompile = RecompileLoop() if args.recompile else None
    try:
        if args.tier_flip:
            files, feed = make_data(args.seed, work)
            d = run_tier_flip(files, feed, args.seed)
            print(json.dumps({"stage": "tier_flip", "seed": args.seed,
                              "match": d is None, "diff": d}),
                  flush=True)
            failures += d is not None
        for rep in range(args.reps):
            seed = args.seed + rep
            files, feed = make_data(seed, work)
            t0 = time.perf_counter()
            bad = run_rep(files, feed, seed)
            print(json.dumps({
                "stage": "stress_rep", "rep": rep, "seed": seed,
                "clean": not bad, "diffs": bad,
                "burners": args.burners,
                "recompile": bool(recompile),
                "secs": round(time.perf_counter() - t0, 1)}),
                flush=True)
            failures += len(bad)
    finally:
        if burners:
            burners.stop()
        if recompile:
            recompile.stop()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"failures": failures}), flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
