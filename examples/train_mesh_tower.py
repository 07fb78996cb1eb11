"""Model-parallel tower demo: TP wide DeepFM or expert-parallel MMoE.

The towers the reference replicates stay small; when a tower does NOT fit
replicated, its wide layer column/row-splits (Megatron) or its expert
blocks shard over a `mp` mesh axis, and MeshTowerTrainer runs the full
sparse hot loop with the TP autodiff contracts enforced in code
(tp_loss_scale + tp_fix_grads — no partial/P-scaled gradients).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_mesh_tower.py --kind tp [--passes 4]
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", choices=("tp", "ep"), default="tp")
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--wide", type=int, default=1024,
                    help="TP tower hidden width (splits over the mesh)")
    args = ap.parse_args()

    import jax

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.wide_tower import EpMMoE, TpDeepFM
    from paddlebox_tpu.train.factory import create_trainer

    P = len(jax.devices())
    data_dir = tempfile.mkdtemp(prefix="pbx_mt_")
    files, feed = write_synthetic_ctr_files(
        data_dir, num_files=4, lines_per_file=800, num_slots=8,
        vocab_per_slot=500, max_len=4, seed=11)
    feed = type(feed)(slots=feed.slots, batch_size=128)
    D = 8
    table = TableConfig(
        embedx_dim=D, pass_capacity=1 << 15,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    spec = ModelSpec(num_slots=8, slot_dim=3 + D)
    if args.kind == "tp":
        model = TpDeepFM(spec, n_shards=P, d_wide=args.wide, d_mid=64)
        print(f"TP DeepFM: {args.wide}-wide layer split over {P} devices "
              f"({args.wide // P} columns each)")
    else:
        model = EpMMoE(spec, n_shards=P, n_experts=2 * P, d_hidden=64,
                       d_out=32)
        print(f"EP MMoE: {2 * P} experts over {P} devices (2 each)")
    trainer = create_trainer("MeshTowerTrainer", model, table, feed,
                             TrainerConfig(dense_lr=5e-3), seed=0)

    for i in range(args.passes):
        ds = BoxDataset(feed, read_threads=2)
        ds.set_filelist(files)
        stats = trainer.train_pass(ds)
        print(f"pass {i}: loss={stats['loss']:.4f} "
              f"batches={stats['batches']}")
        ds.release_memory()
    keys, _ = trainer.table.store.state_items()
    print("features trained:", keys.size)


if __name__ == "__main__":
    main()
