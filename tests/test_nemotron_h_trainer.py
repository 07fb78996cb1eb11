"""NemotronH through BoxTrainer (the same scan_steps as DeepFM, AfMoE and
GraniteHybrid): passes at small sizes against the plain reference's steps
through the same trainer, the loss falling, the three step counters in
utils/stats, and one compile of scan_steps for every pass (ISSUE 41)."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import nemotron_h_reference as ref  # noqa: E402
from test_nemotron_h import WHOLE, build  # noqa: E402

from paddlebox_tpu.config import flags  # noqa: E402
from paddlebox_tpu.config.configs import (SparseOptimizerConfig,  # noqa: E402
                                          TableConfig, TrainerConfig)
from paddlebox_tpu.data import (BoxDataset,  # noqa: E402
                                write_synthetic_ctr_files)
from paddlebox_tpu.models import MODEL_ZOO  # noqa: E402
from paddlebox_tpu.obs import device  # noqa: E402
from paddlebox_tpu.train import BoxTrainer  # noqa: E402
from paddlebox_tpu.utils.stats import stat_get  # noqa: E402

# 24 positions (one-valued slots) in chunks of 8; one chip's share: 2 of
# the 4 state-space groups, 2 of the 4 query heads with the key-value
# head they read, half of the shared expert's columns, experts 2-5 of 8;
# a state-space layer, a LatentMoE layer, the attention layer, a LatentMoE
# layer
CFG = dict(WHOLE, hybrid_override_pattern="ME*E", num_hidden_layers=4,
           mamba_num_heads=4, n_groups=2, num_attention_heads=2,
           num_key_value_heads=1, attention_head_offset=2,
           moe_shared_expert_columns_held=48, n_routed_experts=4,
           expert_offset=2, chunk_size=8, head_scale=2.0,
           num_sparse_slots=24)
PASSES = 3
STEPS, BATCH = 4, 4


class PlainTower:
    """The plain reference on the models' protocol: the same trainer then
    takes the reference's steps (same pull, pool, dense optimizer, push)."""

    name = "nemotron_h_plain"
    task_names = ("ctr",)

    def __init__(self, model):
        self.init = model.init

    def apply(self, params, pooled, dense=None):
        return ref.forward(CFG, params, pooled)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("nemotron_trainer_data")
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


# both slab writes: 'rebuild' is the one 'auto' picks on the chip at the
# towers' shapes, 'scatter' the one it picks on a CPU
@pytest.mark.parametrize("write", ["scatter", "rebuild"])
def test_passes_match_the_references_steps_and_the_loss_falls(
        data, monkeypatch, write):
    flags.set_flag("push_write", write)
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
    assert MODEL_ZOO["nemotron_h"] is type(build(CFG))
    model = build(CFG)
    names = ("ssd_chunks_scanned", "moe_pairs_held", "moe_pairs_max_expert")
    assert model.step_counters == names
    device.monitor().reset()
    before = {n: stat_get(n) for n in names}
    losses, params, rows = passes(model, data)
    got = {n: stat_get(n) - before[n] for n in names}
    compiles = device.snapshot()["entries"]["scan_steps"]["compiles"]
    want_losses, want_params, want_rows = passes(PlainTower(model), data)
    # the plain tower's passes add nothing
    assert got == {n: stat_get(n) - before[n] for n in names}
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    # adam moves a weight by lr x m / sqrt(v): where an expert's element
    # saw next to no gradient, the ratio is rounding's, so a weight may
    # differ by a small part of the 12 steps' 1.2e-2: 1e-4 at most
    for name in params:
        np.testing.assert_allclose(params[name], want_params[name],
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-5)
    assert losses[-1] < losses[0]
    # 3 passes x 4 steps x (4 sequences x 3 chunks x 1 state-space layer),
    # handed back by the step and added at each chunk's drain
    steps = PASSES * STEPS
    assert got["ssd_chunks_scanned"] == steps * (BATCH * 3 * 1)
    # 2 LatentMoE layers x 96 tokens x 3 choices a step, of which the 4 of
    # 8 held experts get their share; the fullest expert at least a
    # quarter of it and never all of it
    pairs, fullest = got["moe_pairs_held"], got["moe_pairs_max_expert"]
    assert 0 < pairs < steps * 2 * BATCH * 24 * 3
    assert pairs / 4 <= fullest < pairs
    # no compile after the first pass
    assert compiles == 1
