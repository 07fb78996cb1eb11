"""Model zoo: shapes, grad flow, and multi-task output contracts."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.models import MODEL_ZOO, CtrDnn, DeepFM, WideDeep, DLRM, MMoE, ESMM
from paddlebox_tpu.models.base import ModelSpec

B, S, D = 4, 6, 8
SPEC = ModelSpec(num_slots=S, slot_dim=3 + D, dense_dim=5)
SPEC_NODENSE = ModelSpec(num_slots=S, slot_dim=3 + D, dense_dim=0)


@pytest.fixture
def inputs():
    rng = np.random.RandomState(0)
    pooled = jnp.asarray(rng.rand(B, S, 3 + D).astype(np.float32))
    dense = jnp.asarray(rng.rand(B, 5).astype(np.float32))
    return pooled, dense


@pytest.mark.parametrize("cls", [CtrDnn, DeepFM, WideDeep, DLRM])
def test_single_task_models(cls, inputs):
    pooled, dense = inputs
    model = cls(SPEC)
    params = model.init(jax.random.PRNGKey(0))
    logits = model.apply(params, pooled, dense)
    assert logits.shape == (B,)
    # grads flow to every param leaf
    g = jax.grad(lambda p: model.apply(p, pooled, dense).sum())(params)
    for name, leaf in g.items():
        assert np.isfinite(np.asarray(leaf)).all(), name
        assert np.abs(np.asarray(leaf)).sum() > 0, f"dead param {name}"


@pytest.mark.parametrize("cls", [CtrDnn, DeepFM, WideDeep, DLRM])
def test_models_without_dense(cls, inputs):
    pooled, _ = inputs
    model = cls(SPEC_NODENSE)
    params = model.init(jax.random.PRNGKey(0))
    assert model.apply(params, pooled, None).shape == (B,)


@pytest.mark.parametrize("cls", [MMoE, ESMM])
def test_multi_task_models(cls, inputs):
    pooled, dense = inputs
    model = cls(SPEC)
    params = model.init(jax.random.PRNGKey(0))
    out = model.apply(params, pooled, dense)
    assert set(out) == set(model.task_names)
    for t, lg in out.items():
        assert lg.shape == (B,)
    g = jax.grad(lambda p: sum(v.sum() for v in
                               model.apply(p, pooled, dense).values()))(params)
    for name, leaf in g.items():
        assert np.abs(np.asarray(leaf)).sum() > 0, f"dead param {name}"


def test_zoo_registry():
    assert set(MODEL_ZOO) == {"ctr_dnn", "deepfm", "wide_deep", "dlrm",
                              "mmoe", "esmm", "join_pv_dnn",
                              "ctr_dnn_expand", "ctr_dnn_aux",
                              "bst_seq_ctr", "tp_deepfm", "ep_mmoe", "afmoe",
                              "granite_hybrid", "nemotron_h"}


def test_esmm_entire_space_loss():
    """loss_mode='esmm' composes pCTCVR = pCTR*pCVR (entire-space loss)."""
    import jax.numpy as jnp
    from paddlebox_tpu.train.trainer import _multi_task_loss

    logits = {"ctr": jnp.array([0.5, -1.0]), "cvr": jnp.array([0.2, 0.3])}
    labels = {"ctr": jnp.array([1, 0]), "cvr": jnp.array([1, 0])}
    ins_valid = jnp.array([True, True])
    loss, preds = _multi_task_loss(logits, labels, ins_valid, "esmm")
    assert set(preds) == {"ctr", "cvr", "ctcvr"}
    np.testing.assert_allclose(
        np.asarray(preds["ctcvr"]),
        np.asarray(preds["ctr"]) * np.asarray(preds["cvr"]), rtol=1e-6)
    assert np.isfinite(float(loss))
    # independent-sum mode differs from entire-space mode
    loss_sum, _ = _multi_task_loss(logits, labels, ins_valid, "sum")
    assert abs(float(loss) - float(loss_sum)) > 1e-6


@pytest.mark.parametrize("cls", [WideDeep, DLRM])
def test_zoo_models_learn_e2e(cls, tmp_path):
    """Every single-task zoo model must LEARN through the full fused-step
    pipeline, not just produce shapes (ctr_dnn/deepfm have their own e2e
    suites; this covers the rest of the zoo)."""
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.train import BoxTrainer
    import dataclasses

    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=2, lines_per_file=400, num_slots=6,
        vocab_per_slot=300, max_len=3, seed=5)
    feed = dataclasses.replace(feed, batch_size=64)
    table = TableConfig(embedx_dim=D, pass_capacity=1 << 13,
                        optimizer=SparseOptimizerConfig(
                            mf_create_thresholds=0.0, mf_initial_range=1e-3,
                            feature_learning_rate=0.1, mf_learning_rate=0.1))
    model = cls(ModelSpec(num_slots=6, slot_dim=3 + D))
    tr = BoxTrainer(model, table, feed, TrainerConfig(dense_lr=3e-3,
                                                      scan_chunk=2))
    try:
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        losses = [tr.train_pass(ds)["loss"] for _ in range(4)]
        # architectures converge at different rates (DLRM's dot-interaction
        # warms slower than the MLP towers): require a clear decrease
        assert losses[-1] < losses[0] - 0.005, (cls.__name__, losses)
    finally:
        tr.close()
