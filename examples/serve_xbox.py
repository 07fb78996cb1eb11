"""Serving handoff demo on the round-12 serving plane.

Default (demo) role — the dryrun leg, end to end on one box:
train day0 (run_day: cadenced delta saves + base save), bring up a
ServingServer over the day's xbox output (mmap view stack + hot-key
cache + delta-refresh watcher), pull embeddings through the
plain-container RPC client, check bit-parity against the XboxModelReader
oracle, then land a MID-DAY day1 SaveDelta and watch the served vectors
refresh within one poll interval.

    JAX_PLATFORMS=cpu python examples/serve_xbox.py

Deployment roles (the same modules, split across boxes):

    # loader/serving box (N replica processes):
    python examples/serve_xbox.py --role server --root /path/xbox \
        --days day0,day1 --processes 2
    # any client box:
    python examples/serve_xbox.py --role client \
        --endpoints host:port,host:port --keys 123,456
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def role_server(args) -> None:
    """Serving fleet on the store root (jax never imports here)."""
    from paddlebox_tpu.serving import ServingFleet
    days = args.days.split(",") if args.days else None
    with ServingFleet(args.root, days=days,
                      processes=args.processes) as fleet:
        print("serving fleet up:", fleet.endpoints, flush=True)
        try:
            while True:
                time.sleep(60)
        except KeyboardInterrupt:
            print("draining fleet")


def role_client(args) -> None:
    import numpy as np

    from paddlebox_tpu.serving import ServingClient
    eps = [(h, int(p)) for h, p in
           (e.split(":") for e in args.endpoints.split(","))]
    client = ServingClient(eps)
    keys = np.array([int(k) for k in args.keys.split(",")], np.uint64)
    emb = client.pull(keys)
    print(f"serving gen {client.last_gen}")
    for k, row in zip(keys.tolist(), emb):
        print(f"  feasign {k}: embed_w={row[0]:+.4f} "
              f"embedx={np.round(row[1:4], 4)}...")
    client.close()


def role_demo(args) -> None:
    import numpy as np

    from paddlebox_tpu.config import flags
    from paddlebox_tpu.config.configs import (CheckpointConfig,
                                              SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.serving import ServingClient, ServingServer
    from paddlebox_tpu.train import BoxTrainer, CheckpointManager
    from paddlebox_tpu.train.checkpoint import XboxModelReader, run_day

    import tempfile

    work = tempfile.mkdtemp(prefix="pbx_serve_")
    files, feed = write_synthetic_ctr_files(
        os.path.join(work, "data"), num_files=2, lines_per_file=800,
        num_slots=8, vocab_per_slot=400, max_len=4, seed=9)
    feed = type(feed)(slots=feed.slots, batch_size=128)

    D = 8
    table = TableConfig(
        embedx_dim=D, pass_capacity=1 << 15,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    trainer = BoxTrainer(
        CtrDnn(ModelSpec(num_slots=8, slot_dim=3 + D), hidden=(64, 32)),
        table, feed, TrainerConfig(dense_lr=1e-3), seed=0)
    cm = CheckpointManager(
        CheckpointConfig(batch_model_dir=os.path.join(work, "batch"),
                         xbox_model_dir=os.path.join(work, "xbox"),
                         async_save=False, save_delta_every_passes=1),
        trainer.table)
    dss = []
    for _ in range(args.passes):
        ds = BoxDataset(feed, read_threads=2)
        ds.set_filelist(files)
        dss.append(ds)
    stats, (batch_dir, xbox_dir) = run_day(trainer, dss, cm, day="day0")
    print(f"trained day0: {len(stats)} passes, final loss "
          f"{stats[-1]['loss']:.4f}")

    xbox_root = os.path.dirname(xbox_dir)
    reader = XboxModelReader(xbox_root, "day0")
    print(f"serving view: {len(reader)} features x {reader.dim} cols "
          f"({reader.deltas_applied} deltas composed)")

    # ---- serving tier: mmap view stack + cache + RPC behind one server
    flags.set_flag("serving_refresh_secs", 0.2)
    flags.set_flag("serving_report_requests", 2)  # demo-size obs cadence
    # days auto-discover each poll: day1's streaming deltas join the
    # composition the moment their DONE markers land
    server = ServingServer(xbox_root)
    client = ServingClient([("127.0.0.1", server.port)])
    from paddlebox_tpu.serving.store import read_xbox_view
    keys = np.asarray(read_xbox_view(xbox_dir)[0][:64], np.uint64)
    t0 = time.perf_counter()
    emb = client.pull(keys)
    dt = time.perf_counter() - t0
    assert np.array_equal(emb, reader.lookup(keys)), \
        "served vectors must be bit-identical to the XboxModelReader oracle"
    print(f"pull RPC: {keys.size} keys in {dt * 1e3:.2f} ms "
          f"(gen {client.last_gen}), oracle parity OK")
    for k, row in zip(keys[:3].tolist(), emb):
        print(f"  feasign {k}: embed_w={row[0]:+.4f} "
              f"embedx={np.round(row[1:4], 4)}...")

    # ---- mid-day refresh: land a day1 SaveDelta while serving
    ds = BoxDataset(feed, read_threads=2)
    ds.set_filelist(files[:1])
    trainer.train_pass(ds)
    ds.release_memory()
    cm.save_delta("day1", 1)
    cm.wait()
    oracle2 = XboxModelReader(xbox_root, "day0", "day1")
    deadline = time.time() + 10.0
    while time.time() < deadline:
        emb2 = client.pull(keys)
        if np.array_equal(emb2, oracle2.lookup(keys)):
            break
        time.sleep(0.05)
    else:
        raise AssertionError("day1 delta not served within 10s")
    changed = int((emb2 != emb).any(axis=1).sum())
    print(f"delta refresh: day1 SaveDelta visible in served vectors "
          f"(gen {client.last_gen}, {changed}/{keys.size} keys changed), "
          f"oracle parity OK")
    st = client.stats()
    rep = st["last_report"] or {}
    hists = rep.get("hists", {}).get("serving_lookup_us", {})
    print(f"obs: {st['requests']} pulls, cache {st['cache_hit']} hit / "
          f"{st['cache_miss']} miss, lookup p50={hists.get('p50')}us "
          f"p99={hists.get('p99')}us")
    client.close()
    server.drain()
    trainer.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("demo", "server", "client"),
                    default="demo")
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--root", help="xbox model root (server role)")
    ap.add_argument("--days", default="",
                    help="comma-separated day dirs in cadence order "
                         "(default: auto-discover)")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--endpoints", default="",
                    help="host:port,host:port (client role)")
    ap.add_argument("--keys", default="1,2,3")
    args = ap.parse_args()
    if args.role == "server":
        role_server(args)
    elif args.role == "client":
        role_client(args)
    else:
        role_demo(args)


if __name__ == "__main__":
    main()
