"""End-to-end CTR training demo: the BoxPS day workflow on one chip.

Generates synthetic MultiSlot data, then runs the full pass cadence a
PaddleBox user knows — preload-overlapped passes, streaming AUC, two-tier
checkpointing (batch model + xbox serving view), pass-boundary recovery —
on the single-chip trainer.

    python examples/train_ctr.py [--passes 4] [--bf16]
"""

import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--bf16", action="store_true",
                    help="bfloat16 dense compute (MXU path)")
    ap.add_argument("--expand-dim", type=int, default=0,
                    help="NN-cross: train a second (expand) embedding "
                         "block per feature through the extended pull "
                         "(pull_box_extended_sparse path)")
    ap.add_argument("--push-write", default="auto",
                    choices=("auto", "scatter", "rebuild"),
                    help="slab write strategy (auto = rebuild below a "
                         "capacity/batch-keys crossover on tpu, scatter "
                         "elsewhere)")
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()

    from paddlebox_tpu.config import flags
    flags.set_flag("push_write", args.push_write)

    from paddlebox_tpu.config.configs import (CheckpointConfig,
                                              SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.models import CtrDnnExpand, DeepFM
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.train.checkpoint import CheckpointManager
    from paddlebox_tpu.train.recovery import RecoverableRunner
    from paddlebox_tpu.train.trainer import BoxTrainer

    work = args.workdir or tempfile.mkdtemp(prefix="pbx_demo_")
    data_dir = os.path.join(work, "data")
    print(f"workdir: {work}")

    # --- data: 4 files of learnable synthetic CTR text (MultiSlot format)
    files, feed = write_synthetic_ctr_files(
        data_dir, num_files=4, lines_per_file=2000, num_slots=16,
        vocab_per_slot=1000, max_len=4, seed=7)
    feed = type(feed)(slots=feed.slots, batch_size=256)

    # --- model + table (DeepFM over a per-pass HBM slab)
    D = 8
    table = TableConfig(
        embedx_dim=D, pass_capacity=1 << 18,
        expand_embed_dim=args.expand_dim,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    spec = ModelSpec(num_slots=16, slot_dim=3 + D)
    model = (CtrDnnExpand(spec, expand_dim=args.expand_dim,
                          hidden=(256, 128)) if args.expand_dim
             else DeepFM(spec, hidden=(256, 128)))
    trainer = BoxTrainer(
        model,
        table, feed,
        TrainerConfig(dense_lr=1e-3,
                      compute_dtype="bfloat16" if args.bf16 else "float32"),
        seed=0)
    trainer.metrics.init_metric("auc", "label", "pred", mask_var="mask")

    # --- pass cadence with per-pass checkpoints (resume-able: rerun this
    #     script with --workdir to continue after a crash); see
    #     examples/train_sharded.py for the preload-overlap + multi-chip
    #     variant
    ckpt = CheckpointManager(CheckpointConfig(
        batch_model_dir=os.path.join(work, "batch_model"),
        xbox_model_dir=os.path.join(work, "xbox_model"),
        async_save=False), trainer.table)
    runner = RecoverableRunner(trainer, ckpt, day="demo")

    def datasets():
        out = []
        for _ in range(args.passes):
            ds = BoxDataset(feed, read_threads=2)
            ds.set_filelist(files)
            out.append(ds)
        return out

    done = runner.completed_passes()
    if done:
        print(f"resuming after {done} completed passes")
    stats = runner.run(datasets())  # skips completed passes itself

    for i, s in enumerate(stats):
        print(f"pass {i}: loss={s['loss']:.4f} instances={s['instances']}")
    msg = trainer.metrics.get_metric_msg("auc")
    print("streaming AUC:", {k: round(v, 4) for k, v in msg.items()
                             if k in ("auc", "size", "actual_ctr")})
    print(f"checkpoints under {work}/batch_model/demo/ "
          f"(xbox serving views under xbox_model/)")


if __name__ == "__main__":
    main()
