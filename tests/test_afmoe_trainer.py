"""AfMoE through BoxTrainer (the same scan_steps as DeepFM): one pass at
small sizes against the plain reference's steps, the step counter
moe_pairs_held, and the donation of the dense state that a 600M-parameter
tower needs (ISSUE 34)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import afmoe_reference as ref  # noqa: E402
from test_afmoe import build  # noqa: E402

from paddlebox_tpu.config import flags  # noqa: E402
from paddlebox_tpu.config.configs import (SparseOptimizerConfig,  # noqa: E402
                                          TableConfig, TrainerConfig)
from paddlebox_tpu.data import (BoxDataset,  # noqa: E402
                                write_synthetic_ctr_files)
from paddlebox_tpu.models import CtrDnn  # noqa: E402
from paddlebox_tpu.models.base import ModelSpec  # noqa: E402
from paddlebox_tpu.obs import device  # noqa: E402
from paddlebox_tpu.train import BoxTrainer  # noqa: E402
from paddlebox_tpu.utils.stats import stat_get  # noqa: E402

# 16 positions (one-valued slots), window 8, 32 wide, 8 router outputs of
# which 2 held, top 2: a dense layer (sliding) and a routed one (full)
CFG = dict(hidden_size=32, num_attention_heads=2, num_key_value_heads=1,
           head_dim=16, sliding_window=8,
           layer_types=["sliding_attention", "full_attention"],
           num_dense_layers=1, intermediate_size=48,
           moe_intermediate_size=16, num_experts_published=8, num_experts=2,
           expert_offset=2, num_experts_per_tok=2, route_scale=2.826,
           rope_theta=10000.0, rms_norm_eps=1e-5, head_scale=2.0,
           num_sparse_slots=16, embedx_dim=32, dense_dim=0)


class PlainTower:
    """The plain reference on the models' protocol: the same trainer then
    takes the reference's steps (same pull, pool, dense optimizer, push)."""

    name = "afmoe_plain"
    task_names = ("ctr",)

    def __init__(self, model):
        self.init = model.init

    def apply(self, params, pooled, dense=None):
        return ref.forward(CFG, params, pooled)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("afmoe_trainer_data")
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=1, lines_per_file=16,
        num_slots=CFG["num_sparse_slots"], vocab_per_slot=12, max_len=1,
        seed=5)
    return files, type(feed)(slots=feed.slots, batch_size=4)


def one_pass(model, data):
    files, feed = data
    table = TableConfig(
        embedx_dim=CFG["embedx_dim"], pass_capacity=512,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=0.05))
    tr = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2), seed=3)
    try:
        # two passes: the first creates the embeddings (a row starts at
        # nought), the second trains through them
        losses = []
        for _ in range(2):
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
def test_one_pass_matches_the_references_steps(data, write):
    flags.set_flag("push_write", write)
    model = build(CFG)
    pairs0 = stat_get("moe_pairs_held")
    losses, params, rows = one_pass(model, data)
    pairs = stat_get("moe_pairs_held") - pairs0
    want_losses, want_params, want_rows = one_pass(PlainTower(model), data)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    for name in params:
        np.testing.assert_allclose(params[name], want_params[name],
                                   rtol=1e-4, atol=1e-6, err_msg=name)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-6)
    # 8 steps x 64 tokens x top 2 in the one routed layer, a quarter of it
    # at an even routing; the plain tower's passes add nothing
    assert 0 < pairs <= 8 * 64 * 2
    assert stat_get("moe_pairs_held") - pairs0 == pairs


# -------------------------------------------------------------- donation

def _staged_chunk(feed, files):
    model = CtrDnn(ModelSpec(num_slots=CFG["num_sparse_slots"],
                             slot_dim=3 + 4), hidden=(256, 256))
    table = TableConfig(embedx_dim=4, pass_capacity=4096,
                        optimizer=SparseOptimizerConfig())
    tr = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2), seed=1)
    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files)
    tr.table.begin_feed_pass()
    ds.load_into_memory(add_keys_fn=tr.table.add_keys)
    tr.table.end_feed_pass()
    tr.table.begin_pass()
    group = ds.split_batches(num_workers=1)[0][:2]
    return tr, tr._stack_batches(group)


def test_scan_steps_donates_the_dense_state_and_the_audit_sees_it(data):
    """params and opt_state handed to scan_steps are dead after the call
    (their buffers went into the outputs), the audit counts their bytes
    and reports no miss, and the donated program computes the bits of an
    undonated twin."""
    files, feed = data
    device.monitor().reset()
    tr, stacked = _staged_chunk(feed, files)
    try:
        prng = tr.table.next_prng()
        copies = jax.tree.map(jnp.copy, (tr.table.slab, tr.params,
                                         tr.opt_state))
        twin = jax.jit(tr.fns.scan_steps.__wrapped__)
        want = twin(*copies, stacked, prng)
        slab, params, opt_state = tr.table.slab, tr.params, tr.opt_state
        dense_bytes = sum(int(l.nbytes) for l in jax.tree.leaves(
            (params, opt_state)) if hasattr(l, "nbytes"))
        out = tr.fns.scan_steps(slab, params, opt_state, stacked, prng)
        for leaf in jax.tree.leaves((slab, params, opt_state)):
            if hasattr(leaf, "is_deleted") and leaf.size:
                assert leaf.is_deleted()
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # a second chunk on the outputs: the state is carried, not copied
        out = tr.fns.scan_steps(out[0], out[1], out[2], stacked, out[5])
        entry = device.snapshot()["entries"]["scan_steps"]
        assert entry["donate_argnums"] == [0, 1, 2]
        assert entry["donation"]["donated_bytes"] >= (
            dense_bytes + int(np.prod(slab.shape)) * 4)
        assert entry["donation"]["checks"] == 2
        assert entry["donation"]["misses"] == 0
        tr.table.set_slab(out[0])
        tr.params, tr.opt_state = out[1], out[2]
        tr.table.end_pass()
    finally:
        tr.close()


def test_train_step_donates_the_dense_state(data):
    files, feed = data
    tr, stacked = _staged_chunk(feed, files)
    try:
        batch = {k: v[0] for k, v in stacked.items()}
        slab, params, opt_state = tr.table.slab, tr.params, tr.opt_state
        out = tr.fns.step(slab, params, opt_state, batch,
                          tr.table.next_prng())
        assert all(l.is_deleted() for l in jax.tree.leaves(params))
        assert slab.is_deleted()
        assert device.snapshot()["entries"]["train_step"][
            "donate_argnums"] == [0, 1, 2]
        tr.table.set_slab(out[0])
        tr.params, tr.opt_state = out[1], out[2]
        tr.table.end_pass()
    finally:
        tr.close()


def test_weakly_typed_weights_compile_scan_steps_once(data):
    """A caller's own weights may come weakly typed (the benchmark draws a
    norm's weight with jnp.where(c, -1.0, 1.0)); the step's outputs never
    are. train_pass strips the weak type before the first dispatch, so
    the second chunk runs the program the first one compiled: at 602.9M
    parameters the second compile was 100 s of a run's set-up."""
    files, feed = data
    model = build(CFG)
    table = TableConfig(embedx_dim=CFG["embedx_dim"], pass_capacity=512,
                        optimizer=SparseOptimizerConfig())
    device.monitor().reset()
    tr = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2), seed=3)
    try:
        tr.params = {k: (jnp.where(v < 0, -1.0, 1.0) if "norm" in k else v)
                     for k, v in tr.params.items()}
        tr.opt_state = tr.dense_opt.init(tr.params)
        assert any(l.weak_type for l in jax.tree.leaves(tr.params))
        for _ in range(2):
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files)
            tr.train_pass(ds)
            ds.release_memory()
        assert not any(getattr(l, "weak_type", False)
                       for l in jax.tree.leaves((tr.params, tr.opt_state)))
        assert device.snapshot()["entries"]["scan_steps"]["compiles"] == 1
    finally:
        tr.close()
