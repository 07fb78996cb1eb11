"""Preload overlap (BoxHelper PreLoadIntoMemory/WaitFeedPassDone cadence):
pipelined passes must train identically to sequential passes."""

import numpy as np
import pytest

from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.parallel.mesh import device_mesh_1d
from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer
from paddlebox_tpu.train.preload import PassPreloader, run_preloaded_passes
from paddlebox_tpu.train.trainer import BoxTrainer
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.utils.stats import stat_get

D = 4
NUM_SLOTS = 4


def table_cfg():
    return TableConfig(
        embedx_dim=D, pass_capacity=1 << 13,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("preload")
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=2, lines_per_file=200, num_slots=NUM_SLOTS,
        vocab_per_slot=80, max_len=3, seed=13)
    feed = type(feed)(slots=feed.slots, batch_size=32)
    return files, feed


@pytest.fixture(autouse=True)
def no_shuffle():
    from paddlebox_tpu.config import flags
    flags.set_flag("dataset_disable_shuffle", True)
    yield
    flags.set_flag("dataset_disable_shuffle", False)


def datasets(files, feed, n):
    out = []
    for _ in range(n):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        out.append(ds)
    return out


def test_box_trainer_preload_parity(data):
    files, feed = data
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)

    seq = BoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                     TrainerConfig(dense_lr=0.01), seed=0)
    seq_losses = []
    for ds in datasets(files, feed, 3):
        seq_losses.append(seq.train_pass(ds)["loss"])

    pipe = BoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                      TrainerConfig(dense_lr=0.01), seed=0)
    stats = run_preloaded_passes(pipe, datasets(files, feed, 3))
    np.testing.assert_allclose([s["loss"] for s in stats], seq_losses,
                               rtol=1e-6)
    assert all(s["instances"] == 400 for s in stats)


def test_sharded_trainer_preload_parity(data):
    files, feed = data
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)

    seq = ShardedBoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                            TrainerConfig(dense_lr=0.01, scan_chunk=1),
                            mesh=device_mesh_1d(8), seed=0)
    seq_losses = []
    for ds in datasets(files, feed, 3):
        seq_losses.append(seq.train_pass(ds)["loss"])

    pipe = ShardedBoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                             TrainerConfig(dense_lr=0.01, scan_chunk=1),
                             mesh=device_mesh_1d(8), seed=0)
    stats = run_preloaded_passes(pipe, datasets(files, feed, 3))
    np.testing.assert_allclose([s["loss"] for s in stats], seq_losses,
                               rtol=1e-6)


def test_preloader_guards(data):
    files, feed = data
    tr = BoxTrainer(CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                           hidden=(16,)),
                    table_cfg(), feed, TrainerConfig(), seed=0)
    pre = PassPreloader(tr.table)
    ds1, ds2 = datasets(files, feed, 2)
    pre.preload(ds1)
    with pytest.raises(RuntimeError):
        pre.preload(ds2)          # one in-flight preload at a time
    with pytest.raises(RuntimeError):
        pre.wait(ds2)             # wait() must match the preloaded dataset
    pre.wait(ds1)
    tr.table.begin_pass()
    tr.table.end_pass()


# ---- ISSUE 33: the feed pass of pass N+1 is planned under pass N
def store_items(table):
    keys, vals = table.store.state_items()
    order = np.argsort(keys)
    return keys[order], vals[order]


def save_like(trainer):
    """What a checkpoint save does to the table between two passes."""
    return lambda _k, _stats: trainer.table.invalidate_residency()


@pytest.mark.parametrize("between", ["nothing", "save"])
def test_three_preloaded_passes_leave_the_store_of_three_plain_ones(
        data, between):
    """run_preloaded_passes plans pass N+1 on the feed-ahead thread and
    installs it at the boundary; train_pass(preloaded=False) plans and
    installs back to back. Same rows, so the same store to the last bit,
    also when a save between the passes makes every install redo."""
    files, feed = data
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)

    def trainer():
        return BoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                          TrainerConfig(dense_lr=0.01), seed=0)

    seq = trainer()
    seq_losses = []
    for k, ds in enumerate(datasets(files, feed, 3)):
        seq_losses.append(seq.train_pass(ds)["loss"])
        if between == "save":
            save_like(seq)(k, None)
    pipe = trainer()
    installed = stat_get("feed_plan_installed")
    redone = stat_get("feed_plan_redone")
    stats = run_preloaded_passes(
        pipe, datasets(files, feed, 3),
        after_pass=save_like(pipe) if between == "save" else None)
    assert [s["loss"] for s in stats] == seq_losses
    for got, want in zip(store_items(pipe.table), store_items(seq.table)):
        np.testing.assert_array_equal(got, want)
    # a save lands while the next plan is in flight: its base is gone
    want = (1, 2) if between == "save" else (3, 0)
    assert (stat_get("feed_plan_installed") - installed,
            stat_get("feed_plan_redone") - redone) == want


class KeysDataset:
    """The two calls a PassPreloader makes of a dataset."""

    def __init__(self, keys, error=None):
        self.keys, self.error = np.asarray(keys, np.uint64), error

    def preload_into_memory(self, add_keys_fn=None):
        add_keys_fn(self.keys)

    def wait_preload_done(self):
        if self.error is not None:
            raise self.error


@pytest.mark.parametrize("fault", ["load_error", "capacity_overflow"])
def test_a_fault_on_the_feed_ahead_thread_surfaces_from_wait(fault):
    """What the feed-ahead thread raises (the load's error, the plan's
    capacity check) comes out of wait(); the table is as it was and the
    preloader takes a fresh preload."""
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=64), seed=0)
    pre = PassPreloader(table)
    first = KeysDataset(np.arange(1, 30))
    pre.preload(first)
    assert pre.wait(first) is True
    held = (table._pass_keys, table._rows)
    bad = (KeysDataset(np.arange(1, 30), error=OSError("disk gone"))
           if fault == "load_error" else KeysDataset(np.arange(1, 200)))
    pre.preload(bad)
    with pytest.raises(OSError if fault == "load_error" else RuntimeError,
                       match="disk gone" if fault == "load_error"
                       else "pass_capacity"):
        pre.wait(bad)
    assert (table._pass_keys, table._rows) == held
    again = KeysDataset(np.arange(10, 50))
    pre.preload(again)                      # not "already in flight"
    assert pre.wait(again) is True
    np.testing.assert_array_equal(table._pass_keys,
                                  np.arange(10, 50, dtype=np.uint64))
    table.begin_pass()
    table.end_pass()


def test_a_refused_window_leaves_the_table_as_it_was():
    """wait(admit_fn=) is the streaming runner's gate: a refusal drops
    the plan uninstalled."""
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=64), seed=0)
    pre = PassPreloader(table)
    first, second, third = (KeysDataset(np.arange(lo, lo + 20))
                            for lo in (1, 15, 30))
    pre.preload(first)
    assert pre.wait(first, admit_fn=lambda ds: ds is first) is True
    held = (table._pass_keys, table._rows, table._resident)
    pre.preload(second)
    asked = []
    assert pre.wait(second, admit_fn=lambda ds: asked.append(ds)) is False
    assert asked == [second]
    assert (table._pass_keys, table._rows, table._resident) == held
    table.begin_pass()
    np.testing.assert_array_equal(
        table.lookup_ids(np.arange(1, 21, dtype=np.uint64)), np.arange(20))
    pre.preload(third)                      # planned on the open pass
    table.end_pass()
    assert pre.wait(third) is True
    assert table._rows_base is held[1]
