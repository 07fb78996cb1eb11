"""Multi-process launcher.

Analog of `python -m paddle.distributed.launch` (python/paddle/distributed/
fleet/launch.py): spawns N worker processes with rank/world/store env vars
set, hosts the rendezvous KV store in the launcher process, forwards the
script's stdout/stderr, and propagates the first non-zero exit code.

    python -m paddlebox_tpu.fleet.launch --nproc 2 train.py --epochs 3

One host = one process = one mesh over its local chips: a chip belongs to
one process at a time, and nothing here divides a host's chips between
children. `--nproc N` with N > 1 on ONE host is therefore the CPU-backend
tier (JAX_PLATFORMS=cpu: the multi-process tests and examples, each child
with its own virtual devices); on a chip host run one process (N = 1 per
host) and let its mesh span the local chips (device_mesh_1d()).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import uuid
from typing import List

from paddlebox_tpu.fleet.store import KVStoreServer


def launch(nproc: int, cmd: List[str], env_extra=None) -> int:
    server = KVStoreServer(host="127.0.0.1")
    run_id = uuid.uuid4().hex[:12]
    procs = []
    try:
        for rank in range(nproc):
            env = dict(os.environ)
            # no PBTPU_COORDINATOR: workers rendezvous the jax.distributed
            # coordinator through the KV store (fleet.init_distributed),
            # avoiding a pick-then-rebind port race in the launcher
            env.update({
                "PBTPU_TRAINER_ID": str(rank),
                "PBTPU_TRAINERS_NUM": str(nproc),
                "PBTPU_STORE_ENDPOINT": "127.0.0.1:%d" % server.port,
                "PBTPU_RUN_ID": run_id,
            })
            if env_extra:
                env.update(env_extra)
            procs.append(subprocess.Popen([sys.executable] + cmd, env=env))
        rc = 0
        for p in procs:
            p.wait()
            if p.returncode and not rc:
                rc = p.returncode
        return rc
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        server.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="paddlebox_tpu.fleet.launch")
    ap.add_argument("--nproc", type=int, default=1,
                    help="worker processes to spawn")
    ap.add_argument("script", help="training script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    return launch(args.nproc, [args.script] + args.script_args)


if __name__ == "__main__":
    sys.exit(main())
