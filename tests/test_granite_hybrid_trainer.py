"""GraniteHybrid through BoxTrainer (the same scan_steps as DeepFM and
AfMoE): passes at small sizes against the plain reference's steps through
the same trainer, the loss falling, the step counter ssd_chunks_scanned,
and one compile of scan_steps for every pass (ISSUE 37)."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import granite_hybrid_reference as ref  # noqa: E402
from test_granite_hybrid import build  # noqa: E402

from paddlebox_tpu.config import flags  # noqa: E402
from paddlebox_tpu.config.configs import (SparseOptimizerConfig,  # noqa: E402
                                          TableConfig, TrainerConfig)
from paddlebox_tpu.data import (BoxDataset,  # noqa: E402
                                write_synthetic_ctr_files)
from paddlebox_tpu.obs import device  # noqa: E402
from paddlebox_tpu.train import BoxTrainer  # noqa: E402
from paddlebox_tpu.utils.stats import stat_get  # noqa: E402

# 24 positions (one-valued slots) in chunks of 8: a state-space layer, the
# attention layer, a state-space layer
CFG = dict(hidden_size=32, intermediate_size=48,
           layer_types=["mamba", "attention", "mamba"],
           num_attention_heads=2, num_key_value_heads=1, head_dim=16,
           attention_multiplier=0.0625, mamba_n_heads=4, mamba_d_head=16,
           mamba_d_state=8, mamba_n_groups=1, mamba_d_conv=4,
           mamba_chunk_size=8, mamba_expand=2, embedding_multiplier=12.0,
           residual_multiplier=0.22, rms_norm_eps=1e-5, head_scale=2.0,
           num_sparse_slots=24, embedx_dim=32, dense_dim=0)
PASSES = 3


class PlainTower:
    """The plain reference on the models' protocol: the same trainer then
    takes the reference's steps (same pull, pool, dense optimizer, push)."""

    name = "granite_hybrid_plain"
    task_names = ("ctr",)

    def __init__(self, model):
        self.init = model.init

    def apply(self, params, pooled, dense=None):
        return ref.forward(CFG, params, pooled)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("granite_trainer_data")
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=1, lines_per_file=16,
        num_slots=CFG["num_sparse_slots"], vocab_per_slot=12, max_len=1,
        seed=5)
    return files, type(feed)(slots=feed.slots, batch_size=4)


def passes(model, data, lr=1e-3):
    files, feed = data
    table = TableConfig(
        embedx_dim=CFG["embedx_dim"], pass_capacity=1024,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=0.05))
    tr = BoxTrainer(model, table, feed,
                    TrainerConfig(scan_chunk=2, dense_lr=lr), seed=3)
    try:
        # the first pass creates the embeddings (a row starts at nought),
        # the later ones train through them
        losses = []
        for _ in range(PASSES):
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files)
            losses.append(tr.train_pass(ds)["loss"])
            ds.release_memory()
        keys, vals = tr.table.store.state_items()
        return (losses, jax.tree.map(np.asarray, tr.params),
                vals[np.argsort(keys)])
    finally:
        tr.close()


# both slab writes: 'rebuild' is the one 'auto' picks on the chip at the
# towers' shapes, 'scatter' the one it picks on a CPU
@pytest.mark.parametrize("write", ["scatter", "rebuild"])
def test_passes_match_the_references_steps_and_the_loss_falls(data, write):
    flags.set_flag("push_write", write)
    model = build(CFG)
    device.monitor().reset()
    chunks0 = stat_get("ssd_chunks_scanned")
    losses, params, rows = passes(model, data)
    chunks = stat_get("ssd_chunks_scanned") - chunks0
    compiles = device.snapshot()["entries"]["scan_steps"]["compiles"]
    want_losses, want_params, want_rows = passes(PlainTower(model), data)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    for name in params:
        np.testing.assert_allclose(params[name], want_params[name],
                                   rtol=1e-4, atol=1e-6, err_msg=name)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-6)
    assert losses[-1] < losses[0]
    # 3 passes x 4 steps x (4 sequences x 3 chunks x 2 state-space
    # layers), handed back by the step and added at each chunk's drain;
    # the plain tower's passes add nothing
    assert chunks == PASSES * 4 * (4 * 3 * 2)
    assert stat_get("ssd_chunks_scanned") - chunks0 == chunks
    # no compile after the first pass
    assert compiles == 1


# ------------------------------------- what a 746M tower forced in train/

def test_a_large_tower_keeps_the_dense_update_per_tensor(monkeypatch):
    """optax.flatten concatenates every gradient and splits every update:
    kept for a small dense side, left out past FLATTEN_DENSE_MAX values
    (at 746M parameters it was 6 GB of a step); the numbers are the same
    either way."""
    import jax.numpy as jnp
    from paddlebox_tpu.train import trainer
    params = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(4)}
    grads = {"a": jnp.full((2, 3), 0.5), "b": jnp.arange(4.0)}
    small = trainer.make_dense_optimizer(TrainerConfig(dense_lr=1e-2))
    flat_state = small.init(params)
    assert not any(isinstance(s.mu, dict) for s in jax.tree.leaves(
        flat_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu"))
    want, _ = small.update(grads, flat_state, params)
    monkeypatch.setattr(trainer, "FLATTEN_DENSE_MAX", 9)    # 10 values here
    large = trainer.make_dense_optimizer(TrainerConfig(dense_lr=1e-2))
    tree_state = large.init(params)
    mus = [s.mu for s in jax.tree.leaves(
        tree_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    assert mus and all(set(m) == {"a", "b"} for m in mus)
    got, _ = large.update(grads, tree_state, params)
    for k in params:
        np.testing.assert_array_equal(got[k], want[k])


def test_the_optimizer_state_is_made_at_its_first_read(data):
    """A caller that installs its own weights and a fresh state for them
    (benchmarks/run.py build_trainer) never holds the trainer's first
    state beside the new one: at 746M parameters the two were 12 GB."""
    _files, feed = data
    table = TableConfig(embedx_dim=CFG["embedx_dim"], pass_capacity=1024,
                        optimizer=SparseOptimizerConfig())
    tr = BoxTrainer(build(CFG), table, feed, TrainerConfig(scan_chunk=2),
                    seed=3)
    try:
        assert tr._opt_state is None
        tr.params = jax.tree.map(lambda a: a + 0, tr.params)
        fresh = tr.dense_opt.init(tr.params)
        assert tr._opt_state is None            # init reads no old state
        tr.opt_state = fresh
        assert tr.opt_state is fresh
    finally:
        tr.close()
    tr = BoxTrainer(build(CFG), table, feed, TrainerConfig(scan_chunk=2),
                    seed=3)
    try:
        state = tr.opt_state                    # read first: made from params
        assert state is tr.opt_state and tr._opt_state is not None
    finally:
        tr.close()
