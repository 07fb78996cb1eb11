"""The in-table update is DESCENT: build_push_grads hands the optimizer the
embedding cotangent negated (PushCopy's -1), and the in-table rules add
what they are pushed. PR 24-33 pushed it as it came, so the rows climbed
the loss while every parity test, whose oracle shared the push, agreed."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.ops.sparse import (build_push_grads,
                                      build_push_grads_extended)
from paddlebox_tpu.train import BoxTrainer


def _cotangent(K, D):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.normal(size=(K, 3 + D)), jnp.float32),
            jnp.arange(K) % 3, jnp.asarray(rng.integers(0, 2, K)),
            jnp.asarray([True] * (K - 2) + [False] * 2))


def test_build_push_grads_negates_the_cotangent():
    d_emb, slots, clicks, valid = _cotangent(8, 4)
    pg = np.asarray(build_push_grads(d_emb, slots, clicks, valid))
    v = np.asarray(valid, np.float32)[:, None]
    np.testing.assert_array_equal(pg[:, 0], np.asarray(slots, np.float32))
    np.testing.assert_array_equal(pg[:, 1:2], v)        # show: not negated
    np.testing.assert_array_equal(
        pg[:, 2], np.asarray(clicks, np.float32) * v[:, 0])
    np.testing.assert_array_equal(pg[:, 3:], -np.asarray(d_emb)[:, 2:] * v)


def test_build_push_grads_extended_negates_both_blocks():
    d_emb, slots, clicks, valid = _cotangent(8, 4)
    d_exp = d_emb[:, :3] * 2.0
    pg = np.asarray(build_push_grads_extended(d_emb, d_exp, slots, clicks,
                                              valid))
    v = np.asarray(valid, np.float32)[:, None]
    np.testing.assert_array_equal(pg[:, 3:8], -np.asarray(d_emb)[:, 2:] * v)
    np.testing.assert_array_equal(pg[:, 8:], -np.asarray(d_exp) * v)


@pytest.mark.parametrize("optimizer,rate", [("adagrad", 3.0),
                                            ("adam", 0.05)])
def test_rows_alone_take_the_loss_down(tmp_path, optimizer, rate):
    """The dense tower frozen (sgd at rate 0), no show/click columns in
    its input (they grow with every pass), the same file pass after pass:
    only the pushed rows move, and the loss has to fall. Up the gradient
    it rose (adam: 0.70, 0.81, 1.03, 1.32, 1.67, 2.10)."""
    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=1, lines_per_file=256, num_slots=4,
        vocab_per_slot=20, max_len=1, seed=11)
    feed = type(feed)(slots=feed.slots, batch_size=64)
    table = TableConfig(
        embedx_dim=8, pass_capacity=512,
        optimizer=SparseOptimizerConfig(
            optimizer=optimizer, mf_create_thresholds=0.0,
            mf_initial_range=0.05,
            # the pushed gradient is the MEAN loss's: adagrad needs a rate
            # of the batch's order to move a row visibly in five passes
            mf_learning_rate=rate, feature_learning_rate=rate))
    model = CtrDnn(ModelSpec(num_slots=4, slot_dim=1 + 8), hidden=(16,))
    tr = BoxTrainer(model, table, feed,
                    TrainerConfig(dense_optimizer="sgd", dense_lr=0.0,
                                  scan_chunk=2), seed=3, use_cvm=False)
    try:
        losses = []
        for _ in range(6):
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files)
            losses.append(float(tr.train_pass(ds)["loss"]))
            ds.release_memory()
    finally:
        tr.close()
    # the first pass creates the embeddings; from the second on they train
    assert all(b < a for a, b in zip(losses[1:], losses[2:])), losses
    assert losses[-1] < losses[1] - 1e-3, losses
