"""Host-plane exchange ladder: store allgather vs p2p a2a vs p2p+uid,
plus the round-13 sharding-POLICY leg.

Round-9 acceptance probe: REAL multi-process measurement of the per-step
cluster bucket exchange (the staging stage the p2p mesh replaces), at 2-4
processes on one machine. Three tiers, all producing bit-identical
per-destination `push_uids` (asserted on the first step):

  store    exchange_outgoing_buckets through the central TcpStore
           (every rank's FULL [n_local, P, KB] set bounces through one
           server: O(W^2*P*KB) bytes + 3 counter round-trips/rank/step)
  p2p      exchange_incoming_p2p over the persistent socket mesh (each
           rank ships each peer only that peer's destination columns:
           O(W*P*KB) direct bytes), dedup after the wire
  p2p_uid  exchange_push_uids_p2p (dedup BEFORE the wire: only sorted
           unique uid vectors travel)

Per tier: `runs` timed drives of `steps` exchanges each, MEDIAN per-step
staging ms reported (container CPU noise otherwise dominates), plus
exchange bytes/step from the hostplane stat counters.

POLICY leg (--policies, round 13): the full route-and-stage path
(bucketize through the policy's native router + the p2p uid exchange)
on a SKEWED-TABLE workload — zipf-ish table sizes with a hot long-tail
key set carrying half of all occurrences — under key-mod, table-wise,
2d-grid, and 2d-grid with the replicated hot tier active. Per policy:
median staging ms + exchange bytes/step from the hostplane counters
(the PR-5 obs stats are the per-policy measurement), p2p-vs-store
product parity per rank, and per-rank received-byte imbalance. The
acceptance bar: the hot-tier leg must cut per-rank exchange bytes vs
key-mod (routing alone conserves total routed ids — only replication
removes bytes from this host plane).

Usage:  timeout 900 python -u tools/hostplane_probe.py [--worlds 2,4]
            [--kb 32768] [--steps 4] [--runs 3] [--policies]
Prints one JSON line per world plus {"all_ok": ...}; exits 1 on failure.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

NUM_DEVICES = 8


def _owned_positions(rank: int, world: int):
    return [int(p) for p in np.array_split(np.arange(NUM_DEVICES), world)[rank]]


def stage_tier(kind: str, buckets, positions, num_devices: int,
               shard_cap: int, all_gather=None, mesh=None, pool=None):
    """ONE host-plane staging step (exchange + per-destination uid dedup)
    at ladder tier `kind` -> {dest: push_uids}. The single definition the
    probe worker, the dryrun_multichip hostplane leg, and any future
    parity check share — the three tiers must produce bit-identical
    products, so their composition lives in exactly one place."""
    from paddlebox_tpu.embedding.pass_table import dedup_uids_sorted
    from paddlebox_tpu.parallel.sharded_table import (
        exchange_incoming_p2p, exchange_outgoing_buckets,
        exchange_push_uids_p2p)
    if kind == "store":
        gb = exchange_outgoing_buckets(buckets, positions, num_devices,
                                       all_gather)
        return {d: dedup_uids_sorted(
            np.concatenate([gb[s][d] for s in range(num_devices)]),
            shard_cap) for d in positions}
    if kind == "p2p":
        inc = exchange_incoming_p2p(buckets, positions, num_devices, mesh)
        return {d: dedup_uids_sorted(inc[d].reshape(-1), shard_cap)
                for d in positions}
    if kind == "p2p_uid":
        return exchange_push_uids_p2p(buckets, positions, num_devices,
                                      shard_cap, mesh, pool=pool)
    raise ValueError("unknown hostplane tier %r" % kind)


def _policy_legs(num_devices: int, num_tables: int, shift: int):
    """The measured policy ladder (construction shared by worker and any
    parity caller): hot threshold 2 on the last leg; the hot set is
    observed deterministically pre-freeze so every rank agrees."""
    from paddlebox_tpu.parallel.sharding import (KeyModPolicy,
                                                 TableWisePolicy,
                                                 TwoDGridPolicy)
    return [
        ("key-mod", KeyModPolicy(num_devices)),
        ("table-wise", TableWisePolicy(num_devices, num_tables, shift)),
        ("2d-grid", TwoDGridPolicy(num_devices, num_tables,
                                   rows=2, table_shift=shift)),
        ("2d-grid+hot", TwoDGridPolicy(num_devices, num_tables, rows=2,
                                       table_shift=shift,
                                       hot_threshold=2, hot_cap=4096)),
    ]


def _skewed_world(num_tables: int, shift: int, n_keys: int, n_hot: int):
    """Deterministic skewed-table key universe (same on every rank):
    zipf-ish per-table sizes, table id in the high bits, plus a hot
    long-tail set that will carry half of every batch's occurrences."""
    rng = np.random.RandomState(777)
    w = 1.0 / np.arange(1, num_tables + 1)
    sizes = np.maximum(16, (w / w.sum() * n_keys)).astype(np.int64)
    parts = []
    for t, n in enumerate(sizes):
        low = rng.randint(0, 1 << 30, int(n)).astype(np.uint64)
        parts.append((np.uint64(t) << np.uint64(shift)) | low)
    keys = np.unique(np.concatenate(parts))
    hot = np.sort(rng.choice(keys, n_hot, replace=False))
    return keys, hot


def policy_worker() -> None:
    """One rank of the policy-leg ladder: route (bucketize via the
    policy router) + stage (p2p uid exchange under the policy) a skewed
    batch stream per policy; parity vs the store path; measure ms and
    exchange bytes from the hostplane stat counters."""
    from concurrent.futures import ThreadPoolExecutor

    from paddlebox_tpu.config import flags
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    from paddlebox_tpu.fleet.fleet import Fleet
    from paddlebox_tpu.fleet.role_maker import RoleMaker
    from paddlebox_tpu.parallel.sharded_table import (ShardedPassTable,
                                                      stage_push_dedup)
    from paddlebox_tpu.utils.stats import StatRegistry

    kb = int(os.environ["HOSTPLANE_KB"])
    steps = int(os.environ["HOSTPLANE_STEPS"])
    runs = int(os.environ["HOSTPLANE_RUNS"])
    parity_only = bool(os.environ.get("HOSTPLANE_PARITY_ONLY"))
    # any bucket overflow would silently change products per policy —
    # fail loud instead of publishing a corrupt ladder
    flags.set_flag("strict_bucket_overflow", True)
    T, SHIFT = 8, 48
    fl = Fleet().init(RoleMaker())
    rank, world = fl.worker_index(), fl.worker_num()
    positions = _owned_positions(rank, world)
    mesh = fl.make_mesh_comm(positions)
    assert mesh is not None, "p2p mesh bring-up failed in probe worker"
    # the zipf-hot regime the 2-D paper targets: a hot set ~kb wide
    # carries 3/4 of every batch's occurrences, so per-(src,dest)
    # uniques are hot-dominated — the shape where replication pays
    keys, hot = _skewed_world(T, SHIFT, n_keys=6 * kb, n_hot=max(256, kb))
    K = 2 * kb                       # occurrences per source per step
    shard_cap = 1 << max(12, (6 * kb).bit_length())
    cfg = TableConfig(embedx_dim=8,
                      pass_capacity=NUM_DEVICES * shard_cap,
                      optimizer=SparseOptimizerConfig())
    pool = ThreadPoolExecutor(4)
    stats = StatRegistry.instance()

    def batch_for(step_i: int, pos_j: int) -> np.ndarray:
        rng = np.random.RandomState(10_000 + rank * 211 + pos_j * 31
                                    + step_i)
        nh = (3 * K) // 4           # hot tail carries 3/4 of the load
        b = np.concatenate([rng.choice(hot, nh),
                            rng.choice(keys, K - nh)]).astype(np.uint64)
        rng.shuffle(b)
        return b

    out = {}
    for name, pol in _policy_legs(NUM_DEVICES, T, SHIFT):
        table = ShardedPassTable(cfg, NUM_DEVICES, kb, policy=pol)
        if getattr(pol, "hot_threshold", 0) > 0:
            # deterministic global frequency knowledge, identical on
            # every rank — the cluster-agreement contract freeze_hot
            # relies on
            for _ in range(pol.hot_threshold):
                pol.observe(hot)
        table.begin_feed_pass()
        table.add_keys(keys)
        table.end_feed_pass()       # freezes the hot tier

        def stage(step_i: int, use_mesh):
            buckets = []
            for j in range(len(positions)):
                b = batch_for(step_i, j)
                valid = np.ones(b.size, bool)
                buckets.append(table.bucketize(b, valid).buckets)
            return stage_push_dedup(
                buckets, positions, NUM_DEVICES, table.shard_cap,
                multiprocess=True, all_gather=fl.all_gather,
                rebuild=False, pool=pool, uid_only=True,
                mesh=use_mesh, policy=pol)

        # parity leg: p2p product vs store product on step 0. The hot
        # leg's p2p product may exceed the store one by EXACTLY the
        # replicated set (owners re-add whole hot sets; the store path
        # ships everything) — anything else is corruption.
        p2p0 = stage(0, mesh)
        store0 = stage(0, None)
        for i, d in enumerate(positions):
            a = p2p0["push_uids"][i]
            b = store0["push_uids"][i]
            real_a = set(a[a < table.shard_cap].tolist())
            real_b = set(b[b < table.shard_cap].tolist())
            h = pol.hot_local_ids(d)
            extra = real_a - real_b
            assert real_b <= real_a, f"{name} dest {d}: p2p lost ids"
            assert not extra or (h is not None and extra <= set(
                h.tolist())), f"{name} dest {d}: non-hot extras {extra}"
        if parity_only:
            continue
        fl.barrier_worker()
        per_ms, per_bytes = [], []
        for r in range(runs):
            fl.barrier_worker()
            b0 = stats.get("hostplane_exchange_bytes")
            t0 = time.perf_counter()
            for s in range(steps):
                stage(1 + r * steps + s, mesh)
            dt = time.perf_counter() - t0
            per_ms.append(dt * 1e3 / steps)
            per_bytes.append(
                (stats.get("hostplane_exchange_bytes") - b0) // steps)
        out[name] = {
            "exchange_ms": round(float(np.median(per_ms)), 2),
            "runs_ms": [round(x, 2) for x in per_ms],
            "exchange_bytes": int(np.median(per_bytes)),
            "hot_replicated": int(sum(
                h.size for h in (pol.hot_local_ids(d)
                                 for d in range(NUM_DEVICES))
                if h is not None)),
        }
    if parity_only:
        out = {"parity": "ok"}
    print("RESULT " + json.dumps({"rank": rank, "world": world, "kb": kb,
                                  "tiers": out}), flush=True)
    pool.shutdown(wait=False)
    fl.stop()


def worker() -> None:
    from concurrent.futures import ThreadPoolExecutor

    from paddlebox_tpu.fleet.fleet import Fleet
    from paddlebox_tpu.fleet.role_maker import RoleMaker
    from paddlebox_tpu.utils.stats import StatRegistry

    kb = int(os.environ["HOSTPLANE_KB"])
    steps = int(os.environ["HOSTPLANE_STEPS"])
    runs = int(os.environ["HOSTPLANE_RUNS"])
    shard_cap = int(os.environ.get("HOSTPLANE_SHARD_CAP", str(1 << 16)))
    fl = Fleet().init(RoleMaker())
    rank, world = fl.worker_index(), fl.worker_num()
    positions = _owned_positions(rank, world)
    mesh = fl.make_mesh_comm(positions)
    assert mesh is not None, "p2p mesh bring-up failed in probe worker"

    rng = np.random.RandomState(1234 + rank)
    buckets = rng.randint(0, shard_cap - 1,
                          (len(positions), NUM_DEVICES, kb)).astype(np.int32)
    # trash-pad a tail like bucketize does
    buckets[:, :, -kb // 8:] = shard_cap - 1
    # the runners hand their stager pool to the pre-wire dedup — match it
    pool = ThreadPoolExecutor(4)

    def tier_fn(kind):
        return lambda: stage_tier(kind, buckets, positions, NUM_DEVICES,
                                  shard_cap, all_gather=fl.all_gather,
                                  mesh=mesh, pool=pool)

    tiers = [(k, tier_fn(k)) for k in ("store", "p2p", "p2p_uid")]
    # parity across the whole ladder before timing anything
    parity_only = bool(os.environ.get("HOSTPLANE_PARITY_ONLY"))
    ref = tiers[0][1]()
    stats = StatRegistry.instance()
    out = {}
    for name, fn in tiers:
        got = fn()
        for d in positions:
            np.testing.assert_array_equal(
                got[d], ref[d], err_msg=f"tier {name} dest {d}")
        if parity_only:
            continue
        fl.barrier_worker()
        per_step, per_bytes = [], []
        for _ in range(runs):
            fl.barrier_worker()
            b0 = stats.get("hostplane_exchange_bytes")
            t0 = time.perf_counter()
            for _ in range(steps):
                fn()
            dt = time.perf_counter() - t0
            per_step.append(dt * 1e3 / steps)
            per_bytes.append(
                (stats.get("hostplane_exchange_bytes") - b0) // steps)
        out[name] = {"exchange_ms": round(float(np.median(per_step)), 2),
                     "runs_ms": [round(x, 2) for x in per_step],
                     "exchange_bytes": int(np.median(per_bytes))}
    if parity_only:
        out = {"parity": "ok"}
    print("RESULT " + json.dumps({"rank": rank, "world": world, "kb": kb,
                                  "tiers": out}), flush=True)
    fl.stop()


def run_world(world: int, kb: int, steps: int, runs: int,
              parity_only: bool = False, timeout: float = 600.0,
              policies: bool = False) -> dict:
    """Spawn a `world`-process localhost cluster of probe workers (the
    test_multihost subprocess pattern — but pure host-plane: no jax
    collectives, so it runs on this CPU container). policies=True runs
    the round-13 policy ladder instead of the transport ladder."""
    import uuid

    from paddlebox_tpu.fleet.store import KVStoreServer
    server = KVStoreServer(host="127.0.0.1")
    run_id = uuid.uuid4().hex[:8]
    procs = []
    try:
        for rank in range(world):
            env = dict(os.environ)
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
            env.update({
                "PBTPU_TRAINER_ID": str(rank),
                "PBTPU_TRAINERS_NUM": str(world),
                "PBTPU_STORE_ENDPOINT": "127.0.0.1:%d" % server.port,
                "PBTPU_RUN_ID": run_id,
                "HOSTPLANE_WORKER": "1",
                "HOSTPLANE_KB": str(kb),
                "HOSTPLANE_STEPS": str(steps),
                "HOSTPLANE_RUNS": str(runs),
                "JAX_PLATFORMS": "cpu",
            })
            if parity_only:
                env["HOSTPLANE_PARITY_ONLY"] = "1"
            if policies:
                env["HOSTPLANE_POLICIES"] = "1"
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        results = {}
        for p in procs:
            sout, serr = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError("probe worker failed:\n" + serr[-3000:])
            for line in sout.splitlines():
                if line.startswith("RESULT "):
                    r = json.loads(line[len("RESULT "):])
                    results[r["rank"]] = r
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
    if set(results) != set(range(world)):
        raise RuntimeError("missing probe results: got %s" % sorted(results))
    return results[0]


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--worlds", default="2,4")
    ap.add_argument("--kb", type=int, default=32768)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--policies", action="store_true",
                    help="run the round-13 sharding-policy ladder "
                         "(key-mod / table-wise / 2d-grid / +hot) on "
                         "the skewed-table workload")
    args = ap.parse_args()
    ok = True
    for world in [int(w) for w in args.worlds.split(",")]:
        try:
            r = run_world(world, args.kb, args.steps, args.runs,
                          policies=args.policies)
            tiers = r["tiers"]
            if args.policies:
                # acceptance: the replicated hot tier must remove bytes
                # from the wire (pure re-routing conserves them)
                better = (tiers["2d-grid+hot"]["exchange_bytes"]
                          < tiers["key-mod"]["exchange_bytes"])
                ok = ok and better
                print(json.dumps({
                    "probe": "hostplane_policy", "world": world,
                    "kb": r["kb"], "tiers": tiers,
                    "hot_beats_keymod_bytes": better}), flush=True)
                continue
            # the acceptance bar: p2p must beat the store funnel
            faster = (tiers["p2p"]["exchange_ms"] < tiers["store"]["exchange_ms"]
                      or tiers["p2p_uid"]["exchange_ms"]
                      < tiers["store"]["exchange_ms"])
            ok = ok and faster
            print(json.dumps({"probe": "hostplane", "world": world,
                              "kb": r["kb"], "tiers": tiers,
                              "p2p_beats_store": faster}), flush=True)
        except Exception as e:  # noqa: BLE001 — keep the ladder going
            ok = False
            print(json.dumps({"probe": "hostplane", "world": world,
                              "error": repr(e)[:400]}), flush=True)
    print(json.dumps({"all_ok": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    if os.environ.get("HOSTPLANE_WORKER"):
        if os.environ.get("HOSTPLANE_POLICIES"):
            policy_worker()
        else:
            worker()
    else:
        main()
