"""Pipeline-parallel CTR training demo: the program split across stages.

The reference's HeterPipelineTrainer/SectionWorker capability
(optimizer.py:7496-7575 cut_list → section_worker.cc) as one SPMD
program: stage 0 owns the sparse section (pull → fused seqpool+CVM →
input projection), every stage owns a block of the deep tower, the last
stage owns the head and the loss; micro-batches flow on the ppermute ring
and gradients flow back through the transposed pipeline into the
in-table sparse optimizer.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_pipeline.py --passes 4 [--stages 4]
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--stages", type=int, default=0,
                    help="pipeline stages (default: all devices)")
    ap.add_argument("--micro", type=int, default=0,
                    help="micro-batches per step (default: 2 x stages)")
    ap.add_argument("--sharded-slab", action="store_true",
                    help="key-mod-shard the pass table over the stage "
                         "devices (O(pass/P) table memory per device) "
                         "instead of replicating it")
    args = ap.parse_args()

    import jax

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.train.factory import create_trainer

    S = args.stages or len(jax.devices())
    print(f"pipeline: {S} stages × {jax.devices()[0].platform}")
    data_dir = tempfile.mkdtemp(prefix="pbx_pipe_")
    files, feed = write_synthetic_ctr_files(
        data_dir, num_files=4, lines_per_file=800, num_slots=8,
        vocab_per_slot=500, max_len=4, seed=7)
    feed = type(feed)(slots=feed.slots, batch_size=64)

    D = 8
    table = TableConfig(
        embedx_dim=D, pass_capacity=1 << 15,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    # the factory resolves the reference trainer name to the CTR program
    # split (trainer_factory.cc name surface); --sharded-slab picks the
    # composition over the full key-mod-sharded PS (section_worker.cc
    # sections against the sharded table)
    name = ("ShardedCtrPipelineTrainer" if args.sharded_slab
            else "HeterPipelineTrainer")
    runner = create_trainer(name, table, feed,
                            n_stages=S, d_model=64, layers_per_stage=1,
                            lr=5e-3, n_micro=args.micro or 2 * S, seed=0)

    for i in range(args.passes):
        ds = BoxDataset(feed, read_threads=2)
        ds.set_filelist(files)
        stats = runner.train_pass(ds)
        print(f"pass {i}: loss={stats['loss']:.4f} steps={stats['steps']} "
              f"(dropped {stats['dropped_batches']} tail batches)")
        ds.release_memory()
    if args.sharded_slab:
        keys, _ = runner.table.store_view().state_items()
        print(f"features trained: {keys.size} across "
              f"{runner.table.num_shards} shards "
              f"(shard slab {runner.table.shard_cap} rows)")
    else:
        keys, _ = runner.table.store.state_items()
        print("features trained:", keys.size)


if __name__ == "__main__":
    main()
