"""Ingest shuffle ladder: record-TCP vs block-TCP vs block-mesh.

Round-17 acceptance probe: REAL multi-process measurement of the
cross-host instance shuffle (the pass-load stage the block codec and the
p2p mesh transport replace), at 2-4 processes on one machine. Each rank
parses its own synthetic file shard and the full parse→shuffle→merge
load runs per tier, all three landing IDENTICAL per-rank content
(asserted via a per-rank digest before anything is timed):

  record-tcp  the legacy per-record codec over the ad-hoc TcpShuffler
              sockets (struct-pack loop per instance, both directions)
  block-tcp   the columnar block codec (header + raw column bytes,
              vectorized hash route + fancy-index split) over the SAME
              TcpShuffler transport — isolates the codec win
  block-mesh  the block codec over the PERSISTENT p2p host-plane mesh
              (fleet/mesh_comm.py, MeshShuffler) — the production tier

Per tier: `runs` timed full loads, MEDIAN wall + records/s landed on
this rank, plus shuffle wire bytes from the shuffle stat counters.
NOTE the tiers are END-TO-END loads: record-tcp includes the Python
record parse (the record path's production reality — SlotRecords are
what that codec moves), the block tiers the native columnar parse: this
probe records the pipeline each config actually runs.

Usage:  timeout 900 python -u tools/ingest_probe.py [--worlds 2]
            [--lines 4000] [--files 2] [--runs 3]
Prints one JSON line per world plus {"all_ok": ...}; exits 1 on failure.
"""

import hashlib
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

TIERS = ("record-tcp", "block-tcp", "block-mesh")


def _digest(ds) -> str:
    """Per-rank content digest, codec-independent: sorted key multiset +
    sorted labels + instance count."""
    keys = np.sort(ds.all_keys())
    if ds._load_columnar:
        labels = ds.block.labels if ds.block is not None else \
            np.empty(0, np.int32)
    else:
        labels = np.array([r.label for r in ds.records], np.int32)
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(keys, np.uint64).tobytes())
    h.update(np.sort(labels).astype(np.int32).tobytes())
    h.update(str(len(ds)).encode())
    return h.hexdigest()


def worker() -> None:
    import tempfile
    import threading

    from paddlebox_tpu.config import flags
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.data.shuffle import MeshShuffler
    from paddlebox_tpu.fleet.fleet import Fleet
    from paddlebox_tpu.fleet.role_maker import RoleMaker
    from paddlebox_tpu.utils.stats import stat_get

    lines = int(os.environ["INGEST_LINES"])
    files_per_rank = int(os.environ["INGEST_FILES"])
    runs = int(os.environ["INGEST_RUNS"])
    parity_only = bool(os.environ.get("INGEST_PARITY_ONLY"))
    fl = Fleet().init(RoleMaker())
    rank, world = fl.worker_index(), fl.worker_num()

    out_dir = tempfile.mkdtemp(prefix="pbtpu_ingest_r%d_" % rank)
    files, feed = write_synthetic_ctr_files(
        out_dir, num_files=files_per_rank, lines_per_file=lines,
        num_slots=16, vocab_per_slot=5000, max_len=4, seed=100 + rank)
    feed = type(feed)(slots=feed.slots, batch_size=512)

    # transports: the mesh rendezvouses COLLECTIVELY first, then the
    # TCP endpoints all_gather (same order on every rank). Flags are
    # saved and RESTORED — the probe picks each tier's plane itself and
    # must not leave the process on a plane the operator didn't select
    prev_plane = flags.get_flag("hostplane")
    prev_codec = flags.get_flag("shuffle_block_codec")
    mesh = fl.make_mesh_comm(positions=())
    assert mesh is not None, "p2p mesh bring-up failed in ingest probe"
    mesh_sh = MeshShuffler(mesh)
    flags.set_flag("hostplane", "store")
    try:
        tcp_sh = fl.make_shuffler()
    finally:
        flags.set_flag("hostplane", prev_plane)

    def load(tier: str):
        flags.set_flag("shuffle_block_codec", tier != "record-tcp")
        sh = mesh_sh if tier == "block-mesh" else tcp_sh
        try:
            ds = BoxDataset(feed, read_threads=2, shuffler=sh)
            ds.set_filelist(files)
            ds.load_into_memory()
        finally:
            flags.set_flag("shuffle_block_codec", prev_codec)
        want_columnar = tier != "record-tcp"
        assert ds._load_columnar == want_columnar, tier
        return ds

    out = {}
    digests = {}
    for tier in TIERS:
        ds = load(tier)                      # warm + parity leg
        digests[tier] = _digest(ds)
        if parity_only:
            continue
        walls, rates, wire = [], [], []
        for _ in range(runs):
            fl.barrier_worker()
            b0 = (stat_get("shuffle_bytes_sent")
                  + stat_get("shuffle_bytes_received"))
            t0 = time.perf_counter()
            ds = load(tier)
            dt = time.perf_counter() - t0
            walls.append(dt * 1e3)
            rates.append(len(ds) / dt)
            wire.append(stat_get("shuffle_bytes_sent")
                        + stat_get("shuffle_bytes_received") - b0)
        out[tier] = {
            "load_ms": round(float(np.median(walls)), 1),
            "runs_ms": [round(x, 1) for x in walls],
            "records_per_sec": round(float(np.median(rates)), 0),
            "shuffle_bytes": int(np.median(wire)),
            "instances_landed": len(ds),
        }
    ref = digests[TIERS[0]]
    for tier, dig in digests.items():
        assert dig == ref, ("tier %s content diverged on rank %d"
                            % (tier, rank))
    if parity_only:
        out = {"parity": "ok"}
    print("RESULT " + json.dumps({"rank": rank, "world": world,
                                  "lines": lines, "tiers": out}),
          flush=True)
    mesh_sh.close()
    tcp_sh.close()
    fl.stop()


def run_world(world: int, lines: int, files_per_rank: int, runs: int,
              parity_only: bool = False, timeout: float = 600.0) -> dict:
    """Spawn a `world`-process localhost cluster of probe workers (the
    hostplane_probe subprocess pattern — pure host plane, no jax
    collectives)."""
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
                "INGEST_WORKER": "1",
                "INGEST_LINES": str(lines),
                "INGEST_FILES": str(files_per_rank),
                "INGEST_RUNS": str(runs),
                "JAX_PLATFORMS": "cpu",
            })
            if parity_only:
                env["INGEST_PARITY_ONLY"] = "1"
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        results = {}
        for p in procs:
            sout, serr = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError("ingest probe worker failed:\n"
                                   + serr[-3000:])
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
    ap.add_argument("--worlds", default="2")
    ap.add_argument("--lines", type=int, default=4000)
    ap.add_argument("--files", type=int, default=2)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args()
    ok = True
    for world in [int(w) for w in args.worlds.split(",")]:
        try:
            r = run_world(world, args.lines, args.files, args.runs)
            tiers = r["tiers"]
            # the acceptance bar: the block codec must beat the record
            # codec on the SAME transport (the codec is the claim; the
            # mesh tier is recorded alongside)
            faster = (tiers["block-tcp"]["records_per_sec"]
                      > tiers["record-tcp"]["records_per_sec"])
            ok = ok and faster
            print(json.dumps({"probe": "ingest", "world": world,
                              "lines": r["lines"], "tiers": tiers,
                              "block_beats_record": faster}), flush=True)
        except Exception as e:  # noqa: BLE001 — keep the ladder going
            ok = False
            print(json.dumps({"probe": "ingest", "world": world,
                              "error": repr(e)[:400]}), flush=True)
    print(json.dumps({"all_ok": ok}), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    if os.environ.get("INGEST_WORKER"):
        worker()
    else:
        main()
