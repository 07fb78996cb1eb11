"""Xing4 through BoxTrainer (the same scan_steps as DeepFM and the other
towers): two passes at small sizes against the plain reference's steps
through the same trainer, under both slab writes; the loss falling, the
step counter in utils/stats, and one compile of scan_steps for every
pass."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import xing4_reference as ref  # noqa: E402
from test_xing4 import WHOLE, build  # noqa: E402

from paddlebox_tpu.config import flags  # noqa: E402
from paddlebox_tpu.config.configs import (SparseOptimizerConfig,  # noqa: E402
                                          TableConfig, TrainerConfig)
from paddlebox_tpu.data import (BoxDataset,  # noqa: E402
                                write_synthetic_ctr_files)
from paddlebox_tpu.models import MODEL_ZOO  # noqa: E402
from paddlebox_tpu.obs import device  # noqa: E402
from paddlebox_tpu.train import BoxTrainer  # noqa: E402
from paddlebox_tpu.utils.stats import stat_get  # noqa: E402

# 24 positions (one-valued slots); one chip's share: experts 2-5 of 8; a
# dense layer, then a routed layer
CFG = dict(WHOLE, n_routed_experts=4, expert_offset=2, head_scale=2.0,
           num_sparse_slots=24)
PASSES = 2
STEPS, BATCH = 4, 4


class PlainTower:
    """The plain reference on the models' protocol: the same trainer then
    takes the reference's steps (same pull, pool, dense optimizer, push)."""

    name = "xing4_plain"
    task_names = ("ctr",)

    def __init__(self, model):
        self.init = model.init

    def apply(self, params, pooled, dense=None):
        return ref.forward(CFG, params, pooled)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("xing4_trainer_data")
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=1, lines_per_file=STEPS * BATCH,
        num_slots=CFG["num_sparse_slots"], vocab_per_slot=12, max_len=1,
        seed=5)
    return files, type(feed)(slots=feed.slots, batch_size=BATCH)


def passes(model, data, lr=1e-3):
    files, feed = data
    table = TableConfig(
        embedx_dim=CFG["embedx_dim"], pass_capacity=1024,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=0.05))
    tr = BoxTrainer(model, table, feed,
                    TrainerConfig(scan_chunk=2, dense_lr=lr), seed=3)
    try:
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


@pytest.fixture(scope="module")
def plain(data):
    """The reference's passes: the same whichever write the program's
    trainer takes."""
    with pytest.MonkeyPatch.context() as mp:
        from paddlebox_tpu.ops import routed_experts as module
        mp.setattr(module, "TILING", (32, 32, 32))
        return passes(PlainTower(build(CFG)), data)


# both slab writes: 'rebuild' is the one 'auto' picks on the chip at the
# towers' shapes, 'scatter' the one it picks on a CPU
@pytest.mark.parametrize("write", ["scatter", "rebuild"])
def test_passes_match_the_references_steps_and_the_loss_falls(
        data, plain, monkeypatch, write):
    flags.set_flag("push_write", write)
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 32, 32))
    assert MODEL_ZOO["xing4_0"] is type(build(CFG))
    model = build(CFG)
    assert model.step_counters == ("moe_pairs_held", "mhc_fused_tokens")
    device.monitor().reset()
    before = stat_get("moe_pairs_held")
    fused = stat_get("mhc_fused_tokens")
    losses, params, rows = passes(model, data)
    pairs = stat_get("moe_pairs_held") - before
    # hidden 32 is no lane-aligned stream: the XLA form, nothing counted
    assert stat_get("mhc_fused_tokens") == fused
    compiles = device.snapshot()["entries"]["scan_steps"]["compiles"]
    want_losses, want_params, want_rows = plain
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    # adam moves a weight by lr x m / sqrt(v): where an element saw next
    # to no gradient the ratio is rounding's, so a weight may differ by a
    # small part of the 8 steps' 8e-3
    for name in params:
        np.testing.assert_allclose(params[name], want_params[name],
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-5)
    assert losses[-1] < losses[0]
    # 1 routed layer x 96 tokens x 3 choices a step, of which the 4 of 8
    # held experts get their share, handed back by the step and added at
    # each chunk's drain
    assert 0 < pairs < PASSES * STEPS * BATCH * 24 * 3
    # no compile after the first pass
    assert compiles == 1
