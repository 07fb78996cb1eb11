"""ISSUE 35: BoxDataset.split_batches hands out the split (a BatchPlan a
worker), and a batch is packed when it is taken. The batches are the eager
split's, bit for bit; the plan is a read-only sequence that owns what it
reads; BoxTrainer trains the same pass from it at any prefetch depth."""

import dataclasses

import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.data.columnar import pack_columnar
from paddlebox_tpu.data.dataset import BatchPlan
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.train.trainer import BoxTrainer
from paddlebox_tpu.utils.stats import stat_get

D, NUM_SLOTS, BS = 4, 4, 16
FIELDS = ("keys", "slots", "segments", "valid", "labels", "ins_valid",
          "dense", "task_labels", "n_ins")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    # 100 records of 16 a batch: three workers get 34, 34 and 32, so the
    # last one is a batch short and wraps around
    files, feed = write_synthetic_ctr_files(
        str(tmp_path_factory.mktemp("batch_plan")), num_files=2,
        lines_per_file=50, num_slots=NUM_SLOTS, vocab_per_slot=60,
        max_len=3, dense_dim=2, seed=5, conversion=True)
    return files, dataclasses.replace(feed, batch_size=BS)


def loaded(data, columnar, shuffle_seed=None):
    files, feed = data
    ds = BoxDataset(feed, read_threads=1, columnar=columnar)
    ds.set_filelist(files)
    ds.load_into_memory()
    assert ds._load_columnar == columnar
    ds.local_shuffle(shuffle_seed)
    return ds


def eager_split(ds, num_workers, equalize=None):
    """The split as split_batches made it before ISSUE 35: every batch
    packed at the split, into lists."""
    bs, n = ds.feed.batch_size, len(ds)
    per_worker = (n + num_workers - 1) // num_workers
    local = (per_worker + bs - 1) // bs if n else 0
    target = equalize(local) if equalize else local
    if ds._load_columnar:
        slots = ds.feed.used_sparse_slots()
        max_lens = np.array([s.max_len for s in slots], np.int64)
        everything = (ds._perm if ds._perm is not None
                      else np.arange(n, dtype=np.int64))

        def pack(chunk):
            return pack_columnar(ds.block, chunk, ds.feed,
                                 ds.feed.key_capacity(), len(slots), max_lens)
    else:
        everything, pack = ds.records, ds.packer.pack
    out = []
    for w in range(num_workers):
        recs = everything[w * per_worker:min((w + 1) * per_worker, n)]
        batches = []
        for b in range(target):
            chunk = recs[b * bs:(b + 1) * bs]
            if len(chunk) == 0 and len(recs):
                chunk = recs[:bs]
            if len(chunk) == 0:
                chunk = everything[:bs]
            batches.append(pack(chunk))
        out.append(batches)
    return out


def assert_same_batch(a, b):
    for f in FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        if isinstance(x, dict):
            assert x.keys() == y.keys(), f
            for t in x:
                assert x[t].dtype == y[t].dtype, (f, t)
                np.testing.assert_array_equal(x[t], y[t], err_msg=f)
        elif isinstance(x, np.ndarray):
            assert x.dtype == y.dtype and x.shape == y.shape, f
            np.testing.assert_array_equal(x, y, err_msg=f)
        else:
            assert x is not None and x == y, f


@pytest.mark.parametrize("equalize", [None, lambda n: n + 2],
                         ids=["local-count", "equalize-raises-target"])
@pytest.mark.parametrize("num_workers", [1, 3])
@pytest.mark.parametrize("shuffled", [True, False],
                         ids=["shuffled", "disable-shuffle"])
@pytest.mark.parametrize("columnar", [True, False],
                         ids=["columnar", "records"])
def test_the_plan_packs_the_eager_splits_batches(data, columnar, shuffled,
                                                 num_workers, equalize):
    flags.set_flag("dataset_disable_shuffle", not shuffled)
    try:
        ds = loaded(data, columnar, shuffle_seed=11)
    finally:
        flags.set_flag("dataset_disable_shuffle", False)
    if columnar:
        assert (ds._perm is not None) == shuffled
    plans = ds.split_batches(num_workers, equalize=equalize)
    want = eager_split(ds, num_workers, equalize=equalize)
    assert len(plans) == num_workers == len(want)
    local = -(-(-(-100 // num_workers)) // BS)     # ceil of a ceil
    for plan, batches in zip(plans, want):
        assert isinstance(plan, BatchPlan)
        assert len(plan) == len(batches) == (local + 2 if equalize
                                             else local)
        for got, b in zip(plan, batches):
            assert_same_batch(got, b)
    if num_workers == 3:
        # the short worker's third batch is its first again
        assert_same_batch(plans[2][2], plans[2][0])
        assert plans[0][2].n_ins == 2 and plans[2][2].n_ins == BS


@pytest.mark.parametrize("columnar", [True, False],
                         ids=["columnar", "records"])
def test_a_worker_without_records_takes_the_datasets_first_batch(data,
                                                                 columnar):
    files, feed = data
    ds = BoxDataset(dataclasses.replace(feed, batch_size=64), read_threads=1,
                    columnar=columnar)
    ds.set_filelist(files)
    ds.load_into_memory()
    # 100 records over 51 workers of 2: the last worker's range is empty
    plans = ds.split_batches(num_workers=51)
    want = eager_split(ds, 51)
    assert len(plans[50]) == 1 and plans[50][0].n_ins == 64
    assert_same_batch(plans[50][0], want[50][0])
    assert_same_batch(plans[49][0], want[49][0])


@pytest.mark.parametrize("columnar", [True, False],
                         ids=["columnar", "records"])
def test_a_plan_is_a_read_only_sequence_that_owns_what_it_reads(data,
                                                                columnar):
    ds = loaded(data, columnar, shuffle_seed=3)
    plan = ds.split_batches(num_workers=1)[0]
    want = eager_split(ds, 1)[0]
    assert len(plan) == 7
    packed0 = stat_get("ingest_batches_packed_lazy")
    # len and slices pack nothing; a slice of a slice is a plan
    tail = plan[2:]
    inner = tail[1:3]
    assert isinstance(inner, BatchPlan) and (len(tail), len(inner)) == (5, 2)
    assert len(plan[7:]) == 0 and list(plan[7:]) == []
    assert stat_get("ingest_batches_packed_lazy") == packed0
    assert_same_batch(inner[0], want[3])
    assert_same_batch(inner[-1], want[4])
    assert_same_batch(plan[-1], want[6])
    assert stat_get("ingest_batches_packed_lazy") == packed0 + 3
    with pytest.raises(IndexError):
        plan[7]
    with pytest.raises(TypeError):
        plan[0] = want[0]
    with pytest.raises(AttributeError):
        plan.append(want[0])
    # iteration twice gives equal batches, each time packed anew
    first, second = list(plan), list(plan)
    assert stat_get("ingest_batches_packed_lazy") == packed0 + 3 + 14
    for a, b, w in zip(first, second, want):
        assert a is not b
        assert_same_batch(a, w)
        assert_same_batch(b, w)
    # neither a release, nor another shuffle, nor a reload of the dataset
    # changes a batch that is still to be taken
    ds.local_shuffle(99)
    assert_same_batch(plan[1], want[1])
    ds.release_memory()
    assert len(ds) == 0
    assert_same_batch(plan[5], want[5])
    ds.load_into_memory()
    ds.local_shuffle(7)
    for got, w in zip(tail, want[2:]):
        assert_same_batch(got, w)


class ListedSplit(BoxDataset):
    """The dataset as it split before ISSUE 35: every batch packed at the
    split, on the caller's thread, and handed out in lists."""

    def split_batches(self, num_workers, equalize=None):
        return [list(plan)
                for plan in super().split_batches(num_workers, equalize)]


def train_two_passes(data, dataset_cls, depth):
    """Two passes of 100 examples, 16 a batch, scan chunks of 4: one full
    chunk, then a remainder of three batches for the per-step loop."""
    files, feed = data
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=1 << 12,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))
    flags.set_flag("chunk_prefetch_depth", depth)
    trainer = BoxTrainer(
        CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D, dense_dim=2),
               hidden=(16,)),
        table_cfg, feed, TrainerConfig(dense_lr=0.01, scan_chunk=4), seed=0)
    try:
        losses = []
        for _ in range(2):
            ds = dataset_cls(feed, read_threads=1)
            ds.set_filelist(files)
            packed0 = stat_get("ingest_batches_packed_lazy")
            stats = trainer.train_pass(ds)
            assert stats["batches"] == 7
            assert stat_get("ingest_batches_packed_lazy") == packed0 + 7
            losses.append(stats["loss"])
        keys, rows = trainer.table.store.state_items()
        order = np.argsort(keys)
        return losses, keys[order], rows[order]
    finally:
        trainer.close()
        flags.set_flag("chunk_prefetch_depth", 1)


@pytest.fixture(scope="module")
def listed_run(data):
    return train_two_passes(data, ListedSplit, 1)


@pytest.mark.parametrize("depth", [0, 1])
def test_a_pass_trained_from_the_plan_is_the_pass_trained_from_lists(
        data, listed_run, depth):
    losses, keys, rows = train_two_passes(data, BoxDataset, depth)
    want_losses, want_keys, want_rows = listed_run
    assert losses == want_losses
    np.testing.assert_array_equal(keys, want_keys)
    np.testing.assert_array_equal(rows, want_rows)
