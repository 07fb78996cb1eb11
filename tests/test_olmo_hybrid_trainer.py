"""OlmoHybrid through BoxTrainer (the same scan_steps as DeepFM, AfMoE,
GraniteHybrid and NemotronH): passes at small sizes against the plain
reference's steps through the same trainer, the loss falling, the step
counter in utils/stats, and one compile of scan_steps for every pass
(ISSUE 43)."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import olmo_hybrid_reference as ref  # noqa: E402
from test_olmo_hybrid import CFG as SHARE, build  # noqa: E402

from paddlebox_tpu.config import flags  # noqa: E402
from paddlebox_tpu.config.configs import (SparseOptimizerConfig,  # noqa: E402
                                          TableConfig, TrainerConfig)
from paddlebox_tpu.data import (BoxDataset,  # noqa: E402
                                write_synthetic_ctr_files)
from paddlebox_tpu.models import MODEL_ZOO  # noqa: E402
from paddlebox_tpu.obs import device  # noqa: E402
from paddlebox_tpu.train import BoxTrainer  # noqa: E402
from paddlebox_tpu.utils.stats import stat_get  # noqa: E402

# 24 positions (one-valued slots) in chunks of 8; one chip's half of the
# heads and columns; a linear-attention layer, then a full-attention one
CFG = dict(SHARE, layer_types=["linear_attention", "full_attention"],
           num_hidden_layers=2, chunk_size=8, num_sparse_slots=24)
PASSES = 3
STEPS, BATCH = 4, 4


class PlainTower:
    """The plain reference on the models' protocol: the same trainer then
    takes the reference's steps (same pull, pool, dense optimizer, push)."""

    name = "olmo_hybrid_plain"
    task_names = ("ctr",)

    def __init__(self, model):
        self.init = model.init

    def apply(self, params, pooled, dense=None):
        return ref.forward(CFG, params, pooled)


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
def test_passes_match_the_references_steps_and_the_loss_falls(tmp_path,
                                                             write):
    flags.set_flag("push_write", write)
    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=1, lines_per_file=STEPS * BATCH,
        num_slots=CFG["num_sparse_slots"], vocab_per_slot=12, max_len=1,
        seed=5)
    data = files, type(feed)(slots=feed.slots, batch_size=BATCH)
    model = build(CFG)
    assert MODEL_ZOO["olmo_hybrid"] is type(model)
    assert model.step_counters == ("delta_chunks_scanned",)
    device.monitor().reset()
    before = stat_get("delta_chunks_scanned")
    losses, params, rows = passes(model, data)
    got = stat_get("delta_chunks_scanned") - before
    compiles = device.snapshot()["entries"]["scan_steps"]["compiles"]
    want_losses, want_params, want_rows = passes(PlainTower(model), data)
    # the plain tower's passes add nothing
    assert stat_get("delta_chunks_scanned") - before == got
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    # adam moves a weight by lr x m / sqrt(v): where an element saw next
    # to no gradient, the ratio is rounding's, so a weight may differ by a
    # small part of the 12 steps' 1.2e-2: 1e-4 at most
    for name in params:
        np.testing.assert_allclose(params[name], want_params[name],
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    # the rows' embeddings after 12 pushes: the readout's norm (N_V) gives
    # a gradient of q and k that carries float32's order of sums as 1 / |o|
    # (tests/test_olmo_hybrid.py), so one value in ~15,000 reads 1.1e-5
    # apart where afmoe's and nemotron_h's stay inside 1e-5
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=3e-5)
    assert losses[-1] < losses[0]
    # 3 passes x 4 steps x (4 sequences x 3 chunks x 1 linear layer),
    # handed back by the step and added at each chunk's drain
    assert got == PASSES * STEPS * (BATCH * 3 * 1)
    # no compile after the first pass
    assert compiles == 1
