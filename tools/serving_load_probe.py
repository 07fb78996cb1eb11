"""Serving-plane load generator (round 12, ROADMAP item 3).

Measures the online tier the way a capacity planner needs it measured:

  * batch-size ladder — closed-loop max throughput (keys/s and
    requests/s) per pull batch size, hot and uniform key mixes, through
    the REAL RPC path (server process-local, socket loopback)
  * open-loop QPS sweep — requests are scheduled at a fixed offered
    rate regardless of completions (the arrival process real traffic
    has); p50/p99 latency per offered-rate step shows where queueing
    starts (the knee), which closed-loop probing structurally hides
  * cache ablation — hot mix with the hot-key cache on vs off

The synthetic base is built directly on disk in chunks (no RAM ingest,
same as tools/xbox_store_probe.py) and served via a pre-built
ViewManager handed to ServingServer — the probe measures the serving
plane, not day-training.

Usage: timeout 1800 python -u tools/serving_load_probe.py \
        [n_keys] [dim] [secs_per_point]
Prints one JSON line per measurement, keyed by "stage".
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from paddlebox_tpu.serving.store import _XBOX_MAGIC  # noqa: E402

N = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
DIM = int(sys.argv[2]) if len(sys.argv) > 2 else 9
SECS = float(sys.argv[3]) if len(sys.argv) > 3 else 3.0
PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "_serving_probe.store")
CHUNK = 2_000_000
HOT_SET = 1 << 16          # distinct hot keys (cacheable working set)
BATCHES = (256, 4096, 32768)


def build_file():
    """Sorted keys 16*i+3 (misses probeable), rows f32 pattern —
    written in chunks, never resident."""
    t0 = time.perf_counter()
    key_off = (8 + 8 + 8 + 63) // 64 * 64
    row_off = (key_off + N * 8 + 63) // 64 * 64
    with open(PATH, "wb") as f:
        f.write(_XBOX_MAGIC)
        f.write(np.int64(N).tobytes())
        f.write(np.int64(DIM).tobytes())
        for lo in range(0, N, CHUNK):
            n = min(CHUNK, N - lo)
            ks = np.arange(lo, lo + n, dtype=np.uint64) * 16 + np.uint64(3)
            f.seek(key_off + lo * 8)
            ks.tofile(f)
        for lo in range(0, N, CHUNK):
            n = min(CHUNK, N - lo)
            rows = np.ones((n, DIM), np.float32)
            rows[:, 0] = ((np.arange(lo, lo + n, dtype=np.int64)
                           & 0xFFFF).astype(np.float32))
            f.seek(row_off + lo * DIM * 4)
            rows.tofile(f)
    print(json.dumps({"stage": "build_file", "n": N, "dim": DIM,
                      "bytes": os.path.getsize(PATH),
                      "secs": round(time.perf_counter() - t0, 1)}),
          flush=True)


def make_server(cache_rows):
    from paddlebox_tpu.config import flags
    from paddlebox_tpu.serving import ServingServer
    from paddlebox_tpu.serving.cache import HotKeyCache
    from paddlebox_tpu.serving.refresh import ViewManager
    from paddlebox_tpu.serving.store import MmapViewStack

    flags.set_flag("serving_report_requests", 0)     # probe does its own
    stack = MmapViewStack.from_files([PATH])
    cache = (HotKeyCache(cache_rows, DIM, admit=2) if cache_rows
             else None)
    return ServingServer(manager=ViewManager(stack, cache), watch=False)


def key_mix(rng, mix, batch, n_batches):
    if mix == "hot":
        ids = rng.randint(0, min(N, HOT_SET), n_batches * batch)
    else:
        ids = rng.randint(0, N, n_batches * batch)
    keys = ids.astype(np.uint64) * np.uint64(16) + np.uint64(3)
    if mix == "uniform":
        keys[::10] += np.uint64(1)          # 10% misses
    return keys.reshape(n_batches, batch)


def closed_loop(client, batches, secs):
    """One pinned client connection pulling as fast as answers return;
    latency per pull recorded locally (the client-side view)."""
    lat = []
    client.pull(batches[0])                  # warm (page-in + admit)
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < secs:
        s = time.perf_counter()
        client.pull(batches[reps % len(batches)])
        lat.append(time.perf_counter() - s)
        reps += 1
    dt = time.perf_counter() - t0
    lat_us = np.sort(np.array(lat) * 1e6)
    return (reps / dt, reps * batches.shape[1] / dt,
            float(lat_us[int(0.50 * (lat_us.size - 1))]),
            float(lat_us[int(0.99 * (lat_us.size - 1))]))


def open_loop(endpoint, batches, qps, secs):
    """Offered-rate arrivals on a scheduler clock; sender threads so a
    slow answer doesn't gate the next arrival (up to a small pool —
    beyond it the probe records the saturation honestly as p99). Each
    sender owns its OWN connection: a shared FramedClient serializes
    every call on its conn mutex, which would measure the client lock
    instead of the server's bounded pull pool."""
    import threading as _th
    from concurrent.futures import ThreadPoolExecutor

    from paddlebox_tpu.serving import ServingClient
    lat = []
    lock = threading.Lock()
    pool = ThreadPoolExecutor(8)
    tls = _th.local()

    def one(i):
        if not hasattr(tls, "client"):
            tls.client = ServingClient([endpoint])
        s = time.perf_counter()
        tls.client.pull(batches[i % len(batches)])
        with lock:
            lat.append(time.perf_counter() - s)

    warm = [pool.submit(one, i) for i in range(8)]  # conns + pool threads
    for f in warm:
        f.result()
    lat.clear()
    n = max(4, int(qps * secs))
    t0 = time.perf_counter()
    futs = []
    for i in range(n):
        target = t0 + i / qps
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        futs.append(pool.submit(one, i))
    for f in futs:
        f.result()
    pool.shutdown(wait=True)
    achieved = n / (time.perf_counter() - t0)
    lat_us = np.sort(np.array(lat) * 1e6)
    return (achieved,
            float(lat_us[int(0.50 * (lat_us.size - 1))]),
            float(lat_us[int(0.99 * (lat_us.size - 1))]))


def store_matches():
    """Reuse the on-disk base only when its HEADER matches this run's
    n/dim — a size-only check would happily serve a stale larger base
    while labeling every line with the new parameters."""
    if not os.path.exists(PATH):
        return False
    with open(PATH, "rb") as f:
        if f.read(8) != _XBOX_MAGIC:
            return False
        n = int(np.frombuffer(f.read(8), np.int64)[0])
        dim = int(np.frombuffer(f.read(8), np.int64)[0])
    return (n, dim) == (N, DIM) and os.path.getsize(PATH) > N * (8 + DIM * 4)


def main():
    if not store_matches():
        build_file()
    from paddlebox_tpu.serving import ServingClient

    rng = np.random.RandomState(0)
    # ---- batch ladder, both mixes, cache on --------------------------
    server = make_server(cache_rows=1 << 17)
    client = ServingClient([("127.0.0.1", server.port)])
    knee_batches = None
    for batch in BATCHES:
        for mix in ("hot", "uniform"):
            batches = key_mix(rng, mix, batch, 8)
            rps, kps, p50, p99 = closed_loop(client, batches, SECS)
            print(json.dumps({
                "stage": f"closed_{mix}_b{batch}",
                "requests_per_sec": round(rps, 1),
                "keys_per_sec": round(kps, 0),
                "p50_us": round(p50, 0), "p99_us": round(p99, 0)}),
                flush=True)
            if mix == "hot" and batch == 4096:
                knee_batches, knee_rps = batches, rps
    # ---- open-loop QPS sweep at the mid batch ------------------------
    for frac in (0.3, 0.6, 0.9):
        qps = max(1.0, knee_rps * frac)
        achieved, p50, p99 = open_loop(("127.0.0.1", server.port),
                                       knee_batches, qps, SECS)
        print(json.dumps({
            "stage": f"open_hot_b4096_load{int(frac * 100)}",
            "offered_qps": round(qps, 1),
            "achieved_qps": round(achieved, 1),
            "p50_us": round(p50, 0), "p99_us": round(p99, 0)}),
            flush=True)
    st = client.stats()
    print(json.dumps({"stage": "cache_counters",
                      "hit": st["cache_hit"], "miss": st["cache_miss"],
                      "evict": st["cache_evict"]}), flush=True)
    client.close()
    server.drain(timeout=5.0)

    # ---- cache ablation: hot mix, cache off --------------------------
    server = make_server(cache_rows=0)
    client = ServingClient([("127.0.0.1", server.port)])
    batches = key_mix(rng, "hot", 4096, 8)
    rps, kps, p50, p99 = closed_loop(client, batches, SECS)
    print(json.dumps({"stage": "closed_hot_b4096_nocache",
                      "requests_per_sec": round(rps, 1),
                      "keys_per_sec": round(kps, 0),
                      "p50_us": round(p50, 0),
                      "p99_us": round(p99, 0)}), flush=True)
    client.close()
    server.drain(timeout=5.0)

    # ---- ops endpoint scrape under pull load (round 18) ---------------
    # a replica with obs_http_port set binds /metrics at construction
    # (make_step_reporter → exporter.ensure_from_flags); the leg runs
    # the closed loop on a side thread while the parent scrapes, so the
    # number recorded is scrape latency WITH the pull plane busy — the
    # operator's actual experience — plus the pull rate while scraped.
    import urllib.request

    from paddlebox_tpu.config import flags as _flags
    from paddlebox_tpu.obs import exporter as _exporter

    _flags.set_flag("obs_http_port", 19790)
    server = make_server(cache_rows=0)
    exp = _exporter.active()
    if exp is None:
        # the exporter's documented degrade (port 19790 taken by a
        # co-tenant/stale probe): skip the leg loudly, don't crash it
        server.drain(timeout=5.0)
        _flags.set_flag("obs_http_port", 0)
        print(json.dumps({"stage": "scrape_under_pull_load",
                          "skipped": "obs http port 19790 unusable — "
                                     "exporter degraded off"}),
              flush=True)
        return
    client = ServingClient([("127.0.0.1", server.port)])
    pulled = {}

    def drive():
        pulled["res"] = closed_loop(client, batches, SECS)

    th = threading.Thread(target=drive)
    th.start()
    lat, errs = [], 0
    url = "http://127.0.0.1:%d/metrics" % exp.port
    while th.is_alive():
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(url, timeout=5) as r:
                ok = (r.status == 200
                      and b"pbtpu_serving_lookup_us_p99" in r.read())
            if not ok:
                errs += 1
        except OSError:
            errs += 1
        lat.append(time.perf_counter() - t0)
        time.sleep(0.02)
    th.join()
    client.close()
    server.drain(timeout=5.0)
    _flags.set_flag("obs_http_port", 0)
    _exporter.ensure_from_flags()       # close + release the port
    slat = np.sort(np.array(lat) * 1e6)
    rps, kps, p50, p99 = pulled["res"]
    print(json.dumps({
        "stage": "scrape_under_pull_load",
        "scrapes": int(slat.size), "scrape_errors": errs,
        "scrape_p50_us": round(float(slat[slat.size // 2]), 0),
        "scrape_p99_us": round(float(slat[int(0.99 * (slat.size - 1))]),
                               0),
        "keys_per_sec_during_scrape": round(kps, 0),
        "pull_p99_us": round(p99, 0)}), flush=True)


if __name__ == "__main__":
    main()
