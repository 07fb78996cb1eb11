"""Worker script for the 2-process localhost cluster test (the subprocess
cluster pattern of the reference's test_dist_base.py:896-1012).

Each process: 4 virtual CPU devices → 8-device global mesh via
jax.distributed; loads its own half of the files; trains the sharded
trainer with cross-process feed-key union, equalized batch counts, and
metric allreduce. Prints ONE json line of results for the parent to check
against the single-process oracle.

Run via tests/test_multihost.py, never directly by pytest.
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
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset
    from paddlebox_tpu.data.generator import default_feed_config
    from paddlebox_tpu.fleet.fleet import fleet
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.parallel.mesh import device_mesh_1d, device_mesh_2d
    from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer

    cfg = json.loads(sys.argv[1])
    fleet.init()
    fleet.init_distributed()   # store-based coordinator rendezvous
    rank, world = fleet.worker_index(), fleet.worker_num()
    assert jax.process_count() == world, (jax.process_count(), world)
    n_devs = len(jax.devices())
    want = world * int(os.environ.get("PBTPU_DEVS_PER_PROC", "4"))
    assert n_devs == want, (n_devs, want)

    # GPUPS variant: every process's shard stores live on ONE central CPU
    # PS over TCP (the distributed-full-store → per-pass-HBM-slab
    # composition, ps_gpu_wrapper.cc:337-760); the parent created the table
    ps_client = None
    store_factory = None
    if cfg.get("ps_endpoint"):
        from paddlebox_tpu.embedding.ps_store import ps_store_factory
        from paddlebox_tpu.ps import TcpPSClient
        host, port = cfg["ps_endpoint"].rsplit(":", 1)
        ps_client = TcpPSClient(host, int(port))
        store_factory = ps_store_factory(ps_client, cfg["ps_table_id"],
                                         process_primary=(rank == 0))

    assert len(cfg["files"]) % world == 0, (len(cfg["files"]), world)
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
                                        mf_learning_rate=0.1),
        **(cfg.get("table_overrides") or {}))
    # mesh_2d: the node axis spans the processes (real DCN boundary)
    # and the chip axis the in-process devices — hierarchical dense sync
    mesh = (device_mesh_2d(world, n_devs // world) if cfg.get("mesh_2d")
            else device_mesh_1d(n_devs))
    trainer = ShardedBoxTrainer(
        CtrDnn(ModelSpec(num_slots=cfg["num_slots"], slot_dim=3 + D),
               hidden=(32, 16)),
        table_cfg, feed,
        TrainerConfig(dense_lr=0.01,
                      sync_mode=cfg.get("sync_mode", "step")),
        mesh=mesh, seed=0, fleet=fleet,
        store_factory=store_factory)
    trainer.metrics.init_metric(
        "auc", "label", "pred", table_size=1 << 14, mask_var="mask",
        mode_collect_in_device=bool(cfg.get("device_auc")))

    losses = []
    for _ in range(cfg["passes"]):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        stats = trainer.train_pass(ds)
        losses.append(stats["loss"])
        ds.release_memory()

    msg = trainer.metrics.get_metric_msg(
        "auc", allreduce=fleet.metric_allreduce())

    # sample rows from OWNED stores for the parity check (PS-backed shards
    # keep their rows server-side; the parent samples via its own client)
    rows = {}
    if ps_client is None:
        for s in trainer.local_positions:
            st = trainer.table.stores[s]
            keys, vals = st.state_items()
            order = np.argsort(keys)
            take = order[:3]
            for k, v in zip(keys[take], vals[take]):
                rows[str(int(k))] = [round(float(x), 6) for x in v]

    # ---- cross-host instance shuffle phase (ShuffleData/PaddleShuffler):
    # re-enable shuffle, route the load through the TcpShuffler, train one
    # more pass; instance totals must be conserved across the cluster
    local_after_shuffle = total_after_shuffle = shuffled_loss = None
    if not cfg.get("skip_shuffle_phase"):
        from paddlebox_tpu.config import flags as pbx_flags
        pbx_flags.set_flag("dataset_disable_shuffle", False)
        shuffler = fleet.make_shuffler(batch_records=64)
        ds = BoxDataset(feed, read_threads=1, shuffler=shuffler)
        ds.set_filelist(files)
        shuffled_stats = trainer.train_pass(ds)
        local_after_shuffle = len(ds)
        total_after_shuffle = int(fleet.all_reduce(
            np.asarray([local_after_shuffle], np.int64), "sum")[0])
        shuffled_loss = shuffled_stats["loss"]
        ds.release_memory()
        if shuffler is not None:
            shuffler.close()
        pbx_flags.set_flag("dataset_disable_shuffle", True)

    # ---- GPUPS spill + day boundary leg (4-proc composition test):
    # apply the table-wide DRAM budget (primary-gated limit_mem), train one
    # more pass so spilled rows fault back through the server pull, then
    # run the day boundary — aging and the shrink decay must hit the
    # server EXACTLY once across the whole cluster (process_primary
    # gating; the Px-decay bug class ps_store.py defends against)
    spilled = post_spill_loss = probe_key = show_before = None
    if cfg.get("spill_and_day") and ps_client is not None:
        # train_pass applies the budget at every pass end already (the
        # CheckNeedLimitMem cadence); one more pass proves spilled rows
        # fault back through the server pull, and the accumulated stat
        # shows the limit ran ONLY through this process's primary
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        post_spill_loss = trainer.train_pass(ds)["loss"]
        ds.release_memory()
        from paddlebox_tpu.utils.stats import stat_get
        spilled = int(stat_get("ps_rows_spilled"))
        if rank == 0:
            # a key this rank owns and trained in the last pass
            probe_key = int(trainer.table._shard_keys[
                trainer.local_positions[0]][0])
            from paddlebox_tpu.embedding import accessor as acc
            show_before = float(ps_client.pull_sparse(
                cfg["ps_table_id"], np.array([probe_key], np.uint64),
                create=False)[0, acc.SHOW])
        fleet.barrier_worker()         # probe read before any decay
        trainer.table.end_day(age=True)
        fleet.barrier_worker()         # boundary done on every rank

    ps_rows = (int(ps_client.sparse_size(cfg["ps_table_id"]))
               if ps_client is not None else None)
    print("RESULT " + json.dumps({
        "rank": rank, "losses": losses, "auc": msg["auc"],
        "size": msg["size"], "rows": rows,
        "collect_T": trainer._collect_T,
        "local_after_shuffle": local_after_shuffle,
        "total_after_shuffle": total_after_shuffle,
        "shuffled_loss": shuffled_loss,
        "ps_rows": ps_rows,
        "spilled": spilled, "post_spill_loss": post_spill_loss,
        "probe_key": probe_key, "show_before": show_before,
    }), flush=True)
    if ps_client is not None:
        ps_client.close()
    fleet.stop()


if __name__ == "__main__":
    main()
