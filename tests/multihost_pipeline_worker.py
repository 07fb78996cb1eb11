"""Worker for the 2-process sharded-pipeline cluster test: each process
owns one dp row of a (dp=world, stage=devs_per_proc) mesh — its pipeline
row's stages live on its own devices (a row never straddles processes) —
while the pass table key-mod-shards over ALL 2×4 devices, so every pull
and push crosses the real process boundary through the a2a.

Run via tests/test_multihost.py run_cluster, never directly by pytest.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    _devs = os.environ.get("PBTPU_DEVS_PER_PROC", "4")
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=" + _devs).strip()
os.environ["PBTPU_DATASET_DISABLE_SHUFFLE"] = "1"  # strict parity

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    from jax.sharding import Mesh
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    from paddlebox_tpu.data import BoxDataset
    from paddlebox_tpu.data.generator import default_feed_config
    from paddlebox_tpu.fleet.fleet import fleet
    from paddlebox_tpu.parallel.pipeline import (STAGE_AXIS,
                                                 ShardedCtrPipelineRunner)

    cfg = json.loads(sys.argv[1])
    fleet.init()
    fleet.init_distributed()
    rank, world = fleet.worker_index(), fleet.worker_num()
    n_devs = len(jax.devices())
    S = n_devs // world

    # GPUPS variant: shard stores front ONE central CPU PS over TCP
    # (sections over the distributed PS at real process boundaries)
    ps_client = None
    store_factory = None
    if cfg.get("ps_endpoint"):
        from paddlebox_tpu.embedding.ps_store import ps_store_factory
        from paddlebox_tpu.ps import TcpPSClient
        host, port = cfg["ps_endpoint"].rsplit(":", 1)
        ps_client = TcpPSClient(host, int(port))
        store_factory = ps_store_factory(ps_client, cfg["ps_table_id"],
                                         process_primary=(rank == 0))

    nf = len(cfg["files"]) // world
    files = cfg["files"][rank * nf:(rank + 1) * nf]
    D = cfg["embedx_dim"]
    feed = default_feed_config(num_slots=cfg["num_slots"],
                               batch_size=cfg["batch_size"],
                               max_len=cfg["max_len"])
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=n_devs * 1024,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))
    # dp axis spans the processes (jax.devices() orders by process), the
    # stage axis stays within each
    mesh = Mesh(np.array(jax.devices()).reshape(world, S),
                ("dp", STAGE_AXIS))
    runner = ShardedCtrPipelineRunner(
        table_cfg, feed, n_stages=S, d_model=24, layers_per_stage=1,
        lr=1e-2, n_micro=cfg["n_micro"], mesh=mesh, seed=0, fleet=fleet,
        store_factory=store_factory)
    assert runner.multiprocess and runner.local_rows == [rank]

    losses, steps = [], 0
    for _ in range(cfg["passes"]):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        stats = runner.train_pass(ds)
        losses.append(stats["loss"])
        steps += stats["steps"]
        ds.release_memory()

    rows = {}
    if ps_client is None:
        for s in runner.local_positions:
            st = runner.table.stores[s]
            keys, vals = st.state_items()
            order = np.argsort(keys)
            for k, v in zip(keys[order[:3]], vals[order[:3]]):
                rows[str(int(k))] = [round(float(x), 6) for x in v]
    ps_rows = (int(ps_client.sparse_size(cfg["ps_table_id"]))
               if ps_client is not None else None)
    # first stage block of this process's dp replica (replicated over dp
    # — every rank must report identical values; the global array is not
    # fully addressable, so read the lowest addressable stage shard)
    def _start(s):
        pos = s.index[0]
        return (pos.start or 0) if isinstance(pos, slice) else int(pos)

    sh0 = min(runner.params["blk_w"].addressable_shards, key=_start)
    blk = np.asarray(sh0.data).reshape(-1)[:8]
    print("RESULT " + json.dumps({
        "rank": rank, "losses": losses, "steps": steps, "rows": rows,
        "blk_head": [round(float(x), 6) for x in blk],
        "ps_rows": ps_rows,
    }), flush=True)
    if ps_client is not None:
        ps_client.close()
    fleet.stop()


if __name__ == "__main__":
    main()
