"""Multi-chip CTR training demo: key-sharded table over a device mesh.

Runs the pod-sharded trainer (table sharded key % P, pull/push as
all_to_all on ICI, dense grads psum'd) with load(N+1) ∥ train(N) preload
overlap. Works on real chips or on virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_sharded.py --passes 3 [--sync k_step|sharding]

For the GPUPS variant (pass slabs built from / dumped to a distributed
CPU PS over TCP), pass --gpups. For a real multi-process cluster, see
tests/multihost_worker.py + paddlebox_tpu.fleet.launch.
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--sync", default="step",
                    choices=["step", "k_step", "sharding"])
    ap.add_argument("--mesh-2d", type=int, default=0, metavar="NODES",
                    help="hierarchical (node, chip) mesh with this many "
                         "node rows: dense sync reduce-scatters on ICI "
                         "and psums 1/chips of the bytes over DCN")
    ap.add_argument("--a2a-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="wire format of the pull/push value all_to_alls "
                         "(bfloat16 halves the ICI bytes)")
    ap.add_argument("--device-auc", action="store_true",
                    help="accumulate the AUC bucket table inside the "
                         "jitted step (one D2H per pass, no per-step "
                         "prediction transfer)")
    ap.add_argument("--gpups", action="store_true",
                    help="back the shard stores with a TCP CPU PS")
    ap.add_argument("--ssd-budget-mb", type=float, default=0,
                    help="feed-ranking posture: host-DRAM row budget; rows "
                         "beyond it spill to an SSD tier each end_pass")
    args = ap.parse_args()
    if args.mesh_2d:
        import jax as _jax
        if len(_jax.devices()) % args.mesh_2d:
            ap.error(f"--mesh-2d {args.mesh_2d} does not divide "
                     f"{len(_jax.devices())} devices")
    if args.gpups and args.ssd_budget_mb:
        ap.error("--ssd-budget-mb spills the LOCAL host stores; with "
                 "--gpups the stores live on the CPU PS (its tables manage "
                 "their own tiering) — pick one")

    import jax

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.parallel.mesh import device_mesh_1d, device_mesh_2d
    from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer
    from paddlebox_tpu.train.preload import run_preloaded_passes

    P = len(jax.devices())
    print(f"devices: {P} × {jax.devices()[0].platform}")
    data_dir = tempfile.mkdtemp(prefix="pbx_sharded_")
    files, feed = write_synthetic_ctr_files(
        data_dir, num_files=max(4, P), lines_per_file=1000, num_slots=16,
        vocab_per_slot=800, max_len=4, seed=11)
    feed = type(feed)(slots=feed.slots, batch_size=128)

    D = 8
    table = TableConfig(
        embedx_dim=D, pass_capacity=P * (1 << 15),
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3),
        ssd_dir=(os.path.join(data_dir, "ssd") if args.ssd_budget_mb else None),
        ssd_threshold_mb=args.ssd_budget_mb)
    tcfg = TrainerConfig(dense_lr=1e-3, sync_mode=args.sync,
                         sync_weight_step=4 if args.sync == "k_step" else 1,
                         sharding=args.sync == "sharding",
                         a2a_dtype=args.a2a_dtype)

    store_factory = None
    ps_client = None
    if args.gpups:
        from paddlebox_tpu.embedding.ps_store import ps_store_factory
        from paddlebox_tpu.ps import PSServer, TcpPSClient
        server = PSServer()
        ps_client = TcpPSClient("127.0.0.1", server.port)
        ps_client.create_sparse_table(0, table, shard_num=P, seed=0)
        store_factory = ps_store_factory(ps_client, 0)
        print(f"GPUPS mode: CPU PS on 127.0.0.1:{server.port}")

    trainer = ShardedBoxTrainer(
        DeepFM(ModelSpec(num_slots=16, slot_dim=3 + D), hidden=(256, 128)),
        table, feed, tcfg,
        mesh=(device_mesh_2d(args.mesh_2d, P // args.mesh_2d)
              if args.mesh_2d else device_mesh_1d(P)),
        seed=0, store_factory=store_factory)
    trainer.metrics.init_metric("auc", "label", "pred", mask_var="mask",
                                mode_collect_in_device=args.device_auc)

    dss = []
    for _ in range(args.passes):
        ds = BoxDataset(feed, read_threads=2)
        ds.set_filelist(files)
        dss.append(ds)
    stats = run_preloaded_passes(trainer, dss)  # load N+1 ∥ train N

    for i, s in enumerate(stats):
        print(f"pass {i}: loss={s['loss']:.4f} batches={s['batches']}")
    msg = trainer.metrics.get_metric_msg("auc")
    print("streaming AUC:", round(msg["auc"], 4), "size:", int(msg["size"]))
    if ps_client is not None:
        print("rows on the PS:", ps_client.sparse_size(0))
        ps_client.stop_server()
        ps_client.close()


if __name__ == "__main__":
    main()
