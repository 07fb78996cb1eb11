"""What a pass boundary costs when the working set DRIFTS (ISSUE 26).

The benchmark's cells register the same key set every pass, so they show
the boundary at 100% overlap only. This drives a bare PassTable at a
cell's capacity and row width through a full build and then `passes`
incremental passes that each replace `drift` of the keys, under
profiler.trace, and prints per pass: the lifecycle spans (ms), the device
time inside `delta_promote`, rows kept / promoted / freed and the free
rows left. On the chip only (a CPU time is no device number; exit 3
without one, as benchmarks/run.py):

  chiprun -- python tools/promote_drift_probe.py [rows] [capacity] [embedx_dim] [drift] [passes]
"""
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import numpy as np

SPANS = ("feed_fold", "feed_unique", "promote_diff", "feed_route_index",
         "pass_begin", "promote_store_read", "promote_stage",
         "promote_dispatch", "pass_end", "writeback_select", "writeback_d2h",
         "writeback_store")
COUNTERS = ("pass_rows_promote_hit", "pass_rows_promote_new",
            "pass_rows_freed", "pass_rows_written_back")


def main(rows=12_500_000, capacity=1 << 26, embedx_dim=10, drift=0.1,
         passes=3, touched=127_000, seed=0):
    import jax
    from harness import trace_reduce as tr
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    from paddlebox_tpu.embedding.pass_table import PassTable
    from paddlebox_tpu.obs.tracer import get_tracer
    from paddlebox_tpu.utils import profiler
    from paddlebox_tpu.utils.stats import gauge_get, stat_get

    if jax.default_backend() != "tpu":
        print("promote_drift_probe needs a TPU chip; found backend %r"
              % jax.default_backend(), file=sys.stderr)
        raise SystemExit(3)
    table = PassTable(TableConfig(
        embedx_dim=embedx_dim, pass_capacity=capacity,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=0.0)), seed=seed)
    rng = np.random.default_rng(seed)
    pool = np.unique(rng.integers(1, 1 << 62, int(rows * (1 + drift * passes))
                                  + 1024, dtype=np.uint64))
    rng.shuffle(pool)
    cur, spare = np.sort(pool[:rows]), pool[rows:]
    logdir = tempfile.mkdtemp(prefix="drift_trace_")
    out = []
    with profiler.trace(logdir):
        for p in range(passes + 1):
            get_tracer().clear()
            before = [stat_get(c) for c in COUNTERS]
            table.begin_feed_pass()
            table.add_keys(cur)
            table.end_feed_pass()
            table.begin_pass()
            jax.block_until_ready(table.slab)
            table.lookup_ids(cur[rng.integers(0, cur.size, touched)])
            table.end_pass()
            rec = {"pass": p, "build": "full" if p == 0 else "incremental",
                   "free_rows": int(gauge_get("pass_free_rows"))}
            for c, b in zip(COUNTERS, before):
                rec[c] = int(stat_get(c) - b)
            for s in get_tracer().all_spans():
                if s[0] in SPANS:
                    rec[s[0] + "_ms"] = round(
                        rec.get(s[0] + "_ms", 0.0) + 1e3 * (s[4] - s[3]), 2)
            out.append(rec)
            n_out = int(rows * drift)
            keep = np.ones(cur.size, bool)
            keep[rng.choice(cur.size, n_out, replace=False)] = False
            cur = np.sort(np.concatenate([cur[keep], spare[:n_out]]))
            spare = spare[n_out:]
    trace = tr.load(tr.find_xplane(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    got = tr.busy_in_programs(trace, "delta_promote")
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rows": rows, "capacity": capacity, "embedx_dim": embedx_dim,
        "drift": drift, "delta_promote_runs": got["runs"],
        "delta_promote_device_ms_per_run": (
            1e3 * got["seconds"] / got["runs"] if got["runs"] else None),
        "peak_bytes_in_use": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use"),
        "passes": out}), flush=True)


if __name__ == "__main__":
    a = sys.argv[1:]
    main(*(int(x) for x in a[:3]), *(float(x) for x in a[3:4]),
         *(int(x) for x in a[4:5]))
