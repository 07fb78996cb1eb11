"""Shared helpers for the tools and tests: ONE definition of the
synthetic CTR workload and of the probe-shape trainer."""

from typing import List

import numpy as np


def make_ctr_batches(feed, n_batches: int, num_slots: int, max_len: int,
                     seed: int = 0) -> List:
    """Synthetic CTR batches: ~(max_len+1)/2 keys per slot per
    instance, globally slot-disambiguated uint64 feasigns, 25% positives."""
    from paddlebox_tpu.data.packer import BatchPacker
    from paddlebox_tpu.data.slot_record import SlotRecord

    rng = np.random.RandomState(seed)
    packer = BatchPacker(feed)
    out = []
    for _ in range(n_batches):
        recs = []
        for _ in range(feed.batch_size):
            slots = {}
            for si in range(num_slots):
                n = rng.randint(1, max_len + 1)
                feas = (rng.randint(0, 1 << 22, n).astype(np.uint64)
                        * np.uint64(num_slots) + np.uint64(si))
                slots[si] = feas
            recs.append(SlotRecord(label=int(rng.rand() < 0.25),
                                   uint64_slots=slots))
        out.append(packer.pack(recs))
    return out


def make_bench_trainer(pass_cap: int = 1 << 20, batch: int = 1024,
                       num_slots: int = 32, max_len: int = 4, d: int = 8,
                       trainer_cfg=None):
    """ONE definition of the probe-shape trainer (DeepFM 512/256/128, bf16
    dense, adagrad in-table) for the compiled-step audit
    (tools/step_audit.py) and the step probes. Returns (trainer, feed)."""
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data.generator import default_feed_config
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.deepfm import DeepFM
    from paddlebox_tpu.train.trainer import BoxTrainer

    feed = default_feed_config(num_slots=num_slots, batch_size=batch,
                               max_len=max_len)
    table = TableConfig(embedx_dim=d, pass_capacity=pass_cap,
                        optimizer=SparseOptimizerConfig(
                            mf_create_thresholds=0.0, mf_initial_range=1e-3))
    model = DeepFM(ModelSpec(num_slots=num_slots, slot_dim=3 + d),
                   hidden=(512, 256, 128))
    return BoxTrainer(model, table, feed,
                      trainer_cfg or TrainerConfig(
                          dense_lr=1e-3, compute_dtype="bfloat16"),
                      seed=0), feed
