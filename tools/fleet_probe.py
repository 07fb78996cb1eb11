"""Multi-box serving fleet ladder: QPS vs box count, coalescing RPC
reduction, journal-fed freshness, and the kill-one-replica error budget.

Round-21 acceptance probe: REAL spawned MultiBoxFleet grids (B boxes x
R replicas, every replica its own process mmapping a shard-filtered
view), driven closed-loop from a threaded FleetClient. Four legs:

  ladder    one rung per box count (default 1,2 at R=1): routing parity
            vs the full-view oracle first (bit-exact, or the rung
            fails), then `secs` of concurrency-`threads` pulls.
            Client-side keys/s + server-side p99 from the merged replica
            histograms. Acceptance: QPS grows with box count while p99
            stays in the same regime — the split views are each smaller
            and the boxes scan in parallel.
  coalesce  one B=2 fleet, two clients: coalesce on vs off, same fixed
            pull count at concurrency 8. Per-box RPC counts from the
            fleet request counters; acceptance: on-arm sends measurably
            fewer RPCs for the same answered pulls (ISSUE bar: visible
            reduction at concurrency >= 4).
  journal   the SAME B=2 fleet tails a real TouchedRowJournal; the
            probe appends touched rows and measures seconds until a
            pull returns them bit-exactly — the staleness a SaveDelta
            interval (minutes) used to impose.
  kill      B=2 x R=2 grid; SIGKILL one replica of box 0 mid-traffic;
            error rate over the following pulls must stay within the
            failover budget (<= 10%).

Usage:  timeout 240 python -u tools/fleet_probe.py [--boxes 1,2]
            [--n 200000] [--batch 4096] [--threads 8] [--secs 1.5]
Prints one JSON line {"probe": "fleet", ...}; exits 1 on failure.
Heavy imports stay inside functions: spawn re-imports this file in
every fleet child, which must come up jax-free in milliseconds.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

EMBEDX = 8
DIM = 1 + EMBEDX          # embed_w + embedx: the served row width
WIDTH = 7 + 1 + EMBEDX    # header + adagrad state + embedx (store row)
HOT_ROWS = 2048


def build_store(root: str, n: int):
    """One xbox day dir + the shared hot-key file; returns the key
    universe, the oracle view path, and the hot-key path."""
    from paddlebox_tpu.serving.store import (write_hot_keys,
                                             write_xbox_columnar)
    rng = np.random.RandomState(99)
    keys = np.unique(rng.randint(1, 1 << 40, n).astype(np.uint64))
    rows = rng.randn(keys.size, DIM).astype(np.float32)
    day = os.path.join(root, "day0")
    os.makedirs(day, exist_ok=True)
    view = os.path.join(day, "view.xcol")
    write_xbox_columnar(view, keys, rows)
    with open(os.path.join(day, "DONE"), "w") as f:
        f.write(str(time.time()))
    hot_path = os.path.join(root, "hot.keys")
    write_hot_keys(hot_path, np.sort(rng.choice(keys, HOT_ROWS,
                                                replace=False)))
    return keys, view, hot_path


def check_parity(fc, oracle, keys, hot) -> None:
    """Bit-exact routing parity on a mixed hit/miss/hot probe — run
    before any timing so a wrong ladder never gets published."""
    rng = np.random.RandomState(7)
    for _ in range(3):
        probe = np.concatenate([
            rng.choice(keys, 300), rng.choice(hot, 40),
            rng.randint(1 << 41, 1 << 42, 20).astype(np.uint64)])
        rng.shuffle(probe)
        a = np.ascontiguousarray(fc.pull(probe)).view(np.uint32)
        b = np.ascontiguousarray(oracle.lookup(probe)).view(np.uint32)
        assert np.array_equal(a, b), "fleet parity vs oracle broke"


def drive(fc, keys, threads: int, secs: float, batch: int):
    """Closed-loop fixed-duration load; (keys_pulled, wall_s, errors)."""
    stop_at = time.perf_counter() + secs
    counts = [0] * threads
    errs = [0] * threads

    def worker(i: int) -> None:
        rng = np.random.RandomState(31 + i)
        while time.perf_counter() < stop_at:
            probe = rng.choice(keys, batch)
            try:
                fc.pull(probe)
                counts[i] += batch
            except (ConnectionError, RuntimeError):
                errs[i] += 1

    t0 = time.perf_counter()
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return sum(counts), time.perf_counter() - t0, sum(errs)


def drive_fixed(fc, keys, threads: int, pulls: int, batch: int) -> int:
    """Fixed-count load (the coalesce A/B arms must answer the SAME
    number of pulls); returns caller errors."""
    errs = [0] * threads

    def worker(i: int) -> None:
        rng = np.random.RandomState(131 + i)
        for _ in range(pulls):
            try:
                fc.pull(rng.choice(keys, batch))
            except (ConnectionError, RuntimeError):
                errs[i] += 1

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return sum(errs)


def ladder_rung(root: str, keys, view: str, hot_path: str, boxes: int,
                threads: int, secs: float, batch: int) -> dict:
    from paddlebox_tpu.serving.fleet import MultiBoxFleet
    from paddlebox_tpu.serving.store import MmapViewStack, read_hot_keys
    oracle = MmapViewStack([], extra_files=(view,))
    hot = read_hot_keys(hot_path)
    with MultiBoxFleet(root, days=["day0"], boxes=boxes, replicas=1,
                       hot_keys_path=hot_path,
                       start_timeout=120.0) as fleet:
        fc = fleet.client(timeout=10.0)
        try:
            check_parity(fc, oracle, keys, hot)
            drive(fc, keys, threads, 0.3, batch)      # warm the pages
            fc.fleet_stats()
            pulled, wall, errors = drive(fc, keys, threads, secs, batch)
            st = fc.fleet_stats()
        finally:
            fc.close()
    return {"boxes": boxes, "replicas": 1,
            "keys_per_sec": int(pulled / wall),
            # answered-pull rate (round 20): drive() counts keys, so
            # pulls = keys / batch
            "qps": round(pulled / batch / wall, 1),
            "p99_us": st["p99_us"], "p50_us": st["p50_us"],
            "errors": errors, "parity": "ok"}


def service_legs(root: str, keys, view: str, hot_path: str,
                 threads: int, batch: int) -> dict:
    """Coalesce A/B + journal freshness + kill-one-replica, all on one
    B=2 x R=2 grid (one spawn, three measurements)."""
    from paddlebox_tpu.serving.fleet import MultiBoxFleet
    from paddlebox_tpu.serving.store import MmapViewStack
    from paddlebox_tpu.train.journal import TouchedRowJournal
    from paddlebox_tpu.utils import journal_format as jf
    import types

    layout = types.SimpleNamespace(width=WIDTH, embedx_dim=EMBEDX,
                                   optimizer="adagrad")
    j = TouchedRowJournal(os.path.join(root, "_journal"), layout, None)
    oracle = MmapViewStack([], extra_files=(view,))
    out = {}
    with MultiBoxFleet(root, days=["day0"], boxes=2, replicas=2,
                       hot_keys_path=hot_path, journal_dirs=[j.dir],
                       flag_overrides={"serving_refresh_secs": 0.2},
                       start_timeout=120.0) as fleet:
        # --- coalesce A/B: same pull count, RPC delta per arm
        rpcs = {}
        for arm, coalesce in (("on", True), ("off", False)):
            fc = fleet.client(timeout=10.0, coalesce=coalesce)
            try:
                before = fc.fleet_stats()["requests"]
                errs = drive_fixed(fc, keys, threads, 25, batch)
                rpcs[arm] = fc.fleet_stats()["requests"] - before
            finally:
                fc.close()
            assert errs == 0, f"coalesce arm {arm}: {errs} pull errors"
        out["coalesce"] = {
            "threads": threads, "pulls_per_arm": threads * 25,
            "rpcs_on": int(rpcs["on"]), "rpcs_off": int(rpcs["off"]),
            "rpc_reduction": round(rpcs["off"] / max(1, rpcs["on"]), 2),
            "ok": rpcs["on"] < 0.8 * rpcs["off"]}

        # --- journal freshness: append -> poll until served bit-exact
        fc = fleet.client(timeout=10.0)
        try:
            tk = np.sort(np.random.RandomState(3).choice(
                keys, 64, replace=False))
            tv = (np.arange(tk.size * WIDTH, dtype=np.float32)
                  .reshape(tk.size, WIDTH) + 0.5)
            cols = jf.xbox_embed_cols(EMBEDX, "adagrad")
            expect = np.ascontiguousarray(tv[:, cols]).view(np.uint32)
            t0 = time.time()
            j.append_rows(tk, tv)
            landed = None
            while time.time() - t0 < 20.0:
                got = np.ascontiguousarray(fc.pull(tk)).view(np.uint32)
                if np.array_equal(got, expect):
                    landed = time.time() - t0
                    break
                time.sleep(0.05)
            assert landed is not None, "journal rows never reached serving"
            out["journal"] = {"staleness_s": round(landed, 2),
                              "ok": landed < 10.0}

            # --- kill one replica of box 0; failover absorbs it. The
            # oracle is the BASE view, so probe only untouched keys —
            # the fleet (correctly) serves the fresher journal values
            # for tk
            fleet.boxes[0]._procs[0].kill()
            pool = np.setdiff1d(keys, tk)
            errors, total = 0, 40
            rng = np.random.RandomState(11)
            for _ in range(total):
                probe = rng.choice(pool, 256)
                try:
                    a = np.ascontiguousarray(fc.pull(probe)).view(np.uint32)
                    b = np.ascontiguousarray(
                        oracle.lookup(probe)).view(np.uint32)
                    assert np.array_equal(a, b), "post-kill parity broke"
                except (ConnectionError, RuntimeError):
                    errors += 1
            out["kill"] = {"errors": errors, "total": total,
                           "error_rate": round(errors / total, 3),
                           "ok": errors <= total * 0.1}
        finally:
            fc.close()
    j.close()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--boxes", default="1,2")
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--secs", type=float, default=1.5)
    args = ap.parse_args()
    ok = True
    result = {"probe": "fleet", "n_keys": args.n, "batch": args.batch,
              "threads": args.threads}
    try:
        with tempfile.TemporaryDirectory(prefix="pbtpu-fleet-probe-") as tmp:
            keys, view, hot_path = build_store(tmp, args.n)
            ladder = []
            for b in [int(x) for x in args.boxes.split(",")]:
                ladder.append(ladder_rung(tmp, keys, view, hot_path, b,
                                          args.threads, args.secs,
                                          args.batch))
            result["ladder"] = ladder
            # acceptance: more boxes must dominate — more keys/s AND
            # p99 no worse (each box scans a smaller view in parallel;
            # in practice p99 roughly halves box-to-box)
            if len(ladder) > 1:
                r = ladder[-1]["keys_per_sec"] / max(
                    1, ladder[0]["keys_per_sec"])
                result["qps_scaling"] = round(r, 2)
                result["qps_scales"] = (
                    r > 1.05
                    and ladder[-1]["p99_us"] <= 1.1 * ladder[0]["p99_us"])
                ok = ok and result["qps_scales"]
            legs = service_legs(tmp, keys, view, hot_path,
                                max(4, args.threads), args.batch)
            result.update(legs)
            ok = ok and legs["coalesce"]["ok"] and legs["journal"]["ok"] \
                and legs["kill"]["ok"]
            ok = ok and all(r["errors"] == 0 for r in ladder)
    except Exception as e:  # noqa: BLE001 — publish the failure, exit 1
        ok = False
        result["error"] = repr(e)[:400]
    result["ok"] = ok
    print(json.dumps(result), flush=True)
    print(json.dumps({"all_ok": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
