"""data_norm model integration: the streaming "summary" params
(boxps_worker.cc:89-95) updated by the running-sums rule inside the fused
train step — never by the dense optimizer — in both trainers.

Also pins the ratio-invariance fact the multi-device design relies on:
data_norm output depends only on batch_sum/batch_size and
batch_size/batch_square_sum, so a pmean over workers (instead of the
reference's DenseDataNormal sum) changes nothing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset
from paddlebox_tpu.data.generator import write_synthetic_ctr_files
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.ops.data_norm import DataNormState, data_norm
from paddlebox_tpu.parallel.mesh import device_mesh_1d
from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer
from paddlebox_tpu.train.trainer import BoxTrainer

N_SLOTS = 8
D = 4


def _data(tmp_path, batch_size=32):
    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=2, lines_per_file=256, num_slots=N_SLOTS,
        vocab_per_slot=500, max_len=3, seed=2)
    return files, dataclasses.replace(feed, batch_size=batch_size)


def _table():
    return TableConfig(embedx_dim=D, pass_capacity=1 << 13,
                       optimizer=SparseOptimizerConfig(
                           mf_create_thresholds=0.0, mf_initial_range=1e-3))


def test_ratio_invariance_under_worker_mean():
    """pmean of (batch_size, batch_sum, batch_square_sum) across P workers
    normalizes identically to the reference's P-worker sum."""
    rng = np.random.RandomState(0)
    P = 4
    states = [DataNormState(
        batch_size=jnp.asarray(rng.rand(6).astype(np.float32) + 1.0),
        batch_sum=jnp.asarray(rng.randn(6).astype(np.float32)),
        batch_square_sum=jnp.asarray(rng.rand(6).astype(np.float32) + 1.0))
        for _ in range(P)]
    mean_st = DataNormState(*[sum(getattr(s, f) for s in states) / P
                              for f in states[0]._fields])
    sum_st = DataNormState(*[sum(getattr(s, f) for s in states)
                             for f in states[0]._fields])
    x = jnp.asarray(rng.randn(16, 6).astype(np.float32))
    np.testing.assert_allclose(np.asarray(data_norm(x, mean_st)),
                               np.asarray(data_norm(x, sum_st)),
                               rtol=1e-5)


def test_box_trainer_data_norm_learns_and_accumulates(tmp_path):
    files, feed = _data(tmp_path)
    model = CtrDnn(ModelSpec(num_slots=N_SLOTS, slot_dim=3 + D),
                   hidden=(32, 16), use_data_norm=True)
    tr = BoxTrainer(model, _table(), feed,
                    TrainerConfig(dense_lr=1e-2, scan_chunk=2))
    try:
        ds = BoxDataset(feed)
        ds.set_filelist(files)
        bs0 = float(np.asarray(tr.params["dn_summary"]["batch_size"])[0])
        losses = [tr.train_pass(ds)["loss"] for _ in range(3)]
        bs1 = float(np.asarray(tr.params["dn_summary"]["batch_size"])[0])
        # summary accumulated every step (init 1e4, +batch rows per step)
        assert bs1 > bs0, (bs0, bs1)
        assert losses[-1] < losses[0], losses
        # the state stayed out of the optimizer: batch_sum finite and the
        # normalized model still separates classes in eval
        preds, labels = tr.predict_batches(ds)
        assert np.isfinite(preds).all()
    finally:
        tr.close()


def test_async_dense_data_norm_accumulates(tmp_path):
    """Async-dense mode: summary deltas ride the flat grad vector and the
    host table's summary mask applies them RAW (not through adam)."""
    files, feed = _data(tmp_path)
    model = CtrDnn(ModelSpec(num_slots=N_SLOTS, slot_dim=3 + D),
                   hidden=(32, 16), use_data_norm=True)
    tr = BoxTrainer(model, _table(), feed,
                    TrainerConfig(dense_lr=1e-2, async_mode=True,
                                  dense_optimizer="adam"))
    try:
        ds = BoxDataset(feed)
        ds.set_filelist(files)
        bs0 = float(np.asarray(tr.params["dn_summary"]["batch_size"])[0])
        tr.train_pass(ds)
        tr.train_pass(ds)
        bs1 = float(np.asarray(tr.params["dn_summary"]["batch_size"])[0])
        # init 1e4 decayed + per-step row counts added — strictly grows
        assert bs1 > bs0, (bs0, bs1)
        assert np.isfinite(
            np.asarray(tr.params["dn_summary"]["batch_sum"])).all()
    finally:
        tr.close()


def test_mixed_precision_preserves_summary_f32():
    """cast_for_compute must leave dn_summary in f32 (normalization at
    8-bit mantissa would defeat apply's explicit f32 cast)."""
    from paddlebox_tpu.train.trainer import cast_for_compute
    params = {"w": jnp.ones((4, 4), jnp.float32),
              "dn_summary": {"batch_size": jnp.full((4,), 1e4)}}
    cast = cast_for_compute(params, jnp.bfloat16)
    assert cast["w"].dtype == jnp.bfloat16
    assert cast["dn_summary"]["batch_size"].dtype == jnp.float32


def test_sharded_trainer_data_norm_replicated(tmp_path):
    files, feed = _data(tmp_path)
    P = len(jax.devices())
    model = CtrDnn(ModelSpec(num_slots=N_SLOTS, slot_dim=3 + D),
                   hidden=(32, 16), use_data_norm=True)
    tr = ShardedBoxTrainer(model, _table(), feed,
                           TrainerConfig(dense_lr=1e-2),
                           mesh=device_mesh_1d(P), seed=0)
    ds = BoxDataset(feed)
    ds.set_filelist(files)
    losses = [tr.train_pass(ds)["loss"] for _ in range(5)]
    assert losses[-1] < losses[0], losses
    # replicated params: every device holds the SAME pmean'd summary
    dn = tr.params["dn_summary"]["batch_size"]
    per_dev = [np.asarray(s.data) for s in dn.addressable_shards]
    for v in per_dev[1:]:
        np.testing.assert_allclose(v, per_dev[0], rtol=1e-6)
    assert float(per_dev[0][0]) > 1e4
