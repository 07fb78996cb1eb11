"""The native columnar pack (psr_pack_batch behind pack_columnar) against
the numpy pack it replaces: every leaf of the batch bit for bit, the same
drop counts, and the counter ingest_batches_packed_native moving only
where the kernel packed. The numpy pack is what pack_columnar runs where
the native library is missing, so the oracle is pack_columnar with
get_lib() answering None."""

import dataclasses
import types
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.data import columnar
from paddlebox_tpu.data.columnar import ColumnarBlock, pack_columnar
from paddlebox_tpu.native import available
from paddlebox_tpu.utils.stats import stat_get

pytestmark = pytest.mark.skipif(not available(),
                                reason="native library did not build")

COUNTERS = ("ingest_batches_packed_native", "packer_keys_dropped",
            "ingest_ins_packed")


def block_of(counts, rng, dense_dim=0, tasks=()):
    """A block whose record r holds counts[r, s] keys of slot s, slots in
    order (the built-in parser's layout)."""
    n_recs, num_slots = counts.shape
    key_slot = np.repeat(np.tile(np.arange(num_slots, dtype=np.int32),
                                 n_recs), counts.ravel())
    return block_from(key_slot, counts.sum(1), rng, dense_dim, tasks)


def block_from(key_slot, per_rec, rng, dense_dim=0, tasks=()):
    n_recs = len(per_rec)
    offsets = np.zeros(n_recs + 1, np.int64)
    np.cumsum(per_rec, out=offsets[1:])
    dense = (rng.random((n_recs, dense_dim), dtype=np.float32)
             if dense_dim else None)
    return ColumnarBlock(
        keys=rng.integers(0, np.iinfo(np.uint64).max, key_slot.size,
                          dtype=np.uint64, endpoint=True),
        key_slot=np.asarray(key_slot, np.int32),
        labels=rng.integers(0, 2, n_recs, dtype=np.int32),
        rec_offsets=offsets, dense=dense,
        task_labels={t: rng.integers(0, 2, n_recs, dtype=np.int32)
                     for t in tasks} or None)


def feed_of(B, tasks=()):
    return types.SimpleNamespace(batch_size=B, task_label_slots=tuple(
        (t, "slot_" + t) for t in tasks))


def case_deepfm(rng):
    # deepfm-criteo: 39 one-key slots, 2,048 a batch, a shuffled block
    block = block_of(np.ones((3000, 39), np.int64), rng, dense_dim=13,
                     tasks=("ctr", "cvr"))
    return (block, rng.permutation(3000)[:2048], feed_of(2048, ("ctr",
                                                                "cvr")),
            39, np.ones(39, np.int64), 2048 * 39, True)


def case_towers(B):
    def make(rng):
        # a tower: one key in each of 4,096 one-key slots an example
        block = block_of(np.ones((6, 4096), np.int64), rng)
        return (block, rng.permutation(6)[:B], feed_of(B), 4096,
                np.ones(4096, np.int64), B * 4096, True)
    return make


def case_one_slot_of_4096(B):
    def make(rng):
        block = block_of(np.full((6, 1), 4096, np.int64), rng)
        return (block, rng.permutation(6)[:B], feed_of(B), 1,
                np.array([4096]), B * 4096, True)
    return make


def case_max_len_cut(rng):
    counts = rng.integers(0, 6, (300, 7))
    max_lens = np.array([1, 2, 3, 0, 1, 5, 2], np.int64)
    return (block_of(counts, rng), rng.permutation(300)[:128], feed_of(128),
            7, max_lens, 128 * int(np.minimum(max_lens, 16).sum()), True)


def case_kcap_cut(rng):
    counts = rng.integers(0, 4, (100, 5))
    return (block_of(counts, rng), np.arange(64), feed_of(64), 5,
            np.full(5, 2), 100, True)


def case_empty_records_short_batch(rng):
    counts = rng.integers(0, 3, (50, 4))
    counts[::3] = 0                        # every third record holds no key
    return (block_of(counts, rng, dense_dim=2), np.arange(5, 45)[::-1],
            feed_of(64), 4, np.full(4, 2), 64 * 8, True)


def case_no_records(rng):
    return (block_of(rng.integers(0, 3, (10, 4)), rng),
            np.empty(0, np.int64), feed_of(16), 4, np.full(4, 2), 128, True)


def case_more_records_than_batch(rng):
    return (block_of(rng.integers(1, 3, (40, 3)), rng),
            rng.permutation(40).astype(np.int32), feed_of(16), 3,
            np.full(3, 2), 96, True)


def case_slot_runs_apart_dropped(rng):
    # slot 0 in two runs with slot 1 between, slot 1 dropped whole by its
    # max_len: the kept segments stay sorted, and each run of slot 0
    # counts its own ordinal (max_len 1 keeps one key of EACH run)
    key_slot = np.tile(np.array([0, 0, 1, 1, 0, 0, 2], np.int32), 20)
    return (block_from(key_slot, np.full(20, 7), rng), np.arange(20),
            feed_of(32), 3, np.array([1, 0, 2]), 32 * 3, True)


def case_slot_runs_apart_kept(rng):
    # the same runs with slot 1 kept: a kept segment falls below the one
    # before it, the kernel declines and the numpy pack sorts
    key_slot = np.tile(np.array([0, 0, 1, 1, 0, 0, 2], np.int32), 20)
    return (block_from(key_slot, np.full(20, 7), rng), np.arange(20),
            feed_of(32), 3, np.array([1, 2, 2]), 32 * 3, False)


def case_descending_slots(rng):
    # a plugin parser that emits each record's slots in descending order
    counts = rng.integers(1, 3, (30, 4))
    per_rec = counts.sum(1)
    key_slot = np.concatenate([np.repeat(np.arange(3, -1, -1, dtype=np.int32),
                                         c[::-1]) for c in counts])
    return (block_from(key_slot, per_rec, rng), np.arange(30), feed_of(32),
            4, np.full(4, 2), 32 * 8, False)


def case_descent_past_kcap(rng):
    # the only descent lies among keys cut by kcap: nothing to repair
    key_slot = np.array([0, 1, 1, 0], np.int32)
    return (block_from(key_slot, np.array([2, 2]), rng), np.arange(2),
            feed_of(2), 2, np.ones(2, np.int64), 3, True)


CASES = {
    "deepfm-criteo-39x1-B2048": case_deepfm,
    "tower-4096x1-B2": case_towers(2),
    "tower-4096x1-B4": case_towers(4),
    "one-slot-of-4096-B2": case_one_slot_of_4096(2),
    "one-slot-of-4096-B4": case_one_slot_of_4096(4),
    "max-len-cut": case_max_len_cut,
    "kcap-cut": case_kcap_cut,
    "empty-records-short-batch": case_empty_records_short_batch,
    "no-records": case_no_records,
    "more-records-than-batch": case_more_records_than_batch,
    "slot-runs-apart-dropped": case_slot_runs_apart_dropped,
    "slot-runs-apart-kept-declines": case_slot_runs_apart_kept,
    "descending-slots-declines": case_descending_slots,
    "descent-past-kcap": case_descent_past_kcap,
}


def counted(fn):
    before = [stat_get(c) for c in COUNTERS]
    out = fn()
    return out, {c: stat_get(c) - b for c, b in zip(COUNTERS, before)}


def assert_same_batch(got, want):
    for f in dataclasses.fields(want):
        x, y = getattr(got, f.name), getattr(want, f.name)
        if isinstance(y, dict):
            assert x.keys() == y.keys(), f.name
            for t in y:
                assert x[t].dtype == y[t].dtype, (f.name, t)
                np.testing.assert_array_equal(x[t], y[t], err_msg=f.name)
        elif isinstance(y, np.ndarray):
            assert x.dtype == y.dtype and x.shape == y.shape, f.name
            np.testing.assert_array_equal(x, y, err_msg=f.name)
        else:
            assert x == y, f.name


def numpy_pack(monkeypatch, *args):
    with monkeypatch.context() as m:
        m.setattr(columnar, "get_lib", lambda: None)
        return pack_columnar(*args)


@pytest.mark.parametrize("name", list(CASES))
def test_native_pack_is_the_numpy_pack_bit_for_bit(name, monkeypatch):
    block, rec_idx, feed, num_slots, max_lens, kcap, native = CASES[name](
        np.random.default_rng(sum(map(ord, name))))
    args = (block, rec_idx, feed, kcap, num_slots, max_lens)
    want, want_counts = counted(lambda: numpy_pack(monkeypatch, *args))
    got, got_counts = counted(lambda: pack_columnar(*args))
    assert_same_batch(got, want)
    assert want_counts["ingest_batches_packed_native"] == 0
    assert got_counts["ingest_batches_packed_native"] == int(native)
    for c in ("packer_keys_dropped", "ingest_ins_packed"):
        assert got_counts[c] == want_counts[c], c
    # the batch the device sees: sorted segments, padding on the last one
    seg = got.segments
    assert (np.diff(seg) >= 0).all()
    assert (seg[~got.valid] == feed.batch_size * num_slots - 1).all()


def test_threads_pack_side_by_side_as_one_thread_does():
    block, _, feed, num_slots, max_lens, kcap, _ = case_deepfm(
        np.random.default_rng(7))
    # eight batches of a micro pass, records drawn with repeats
    chunks = np.array_split(
        np.random.default_rng(8).integers(0, 3000, 2048 * 8), 8)

    def pack(chunk):
        return pack_columnar(block, chunk, feed, kcap, num_slots, max_lens)

    serial = [pack(c) for c in chunks]
    with ThreadPoolExecutor(4) as pool:
        for _ in range(3):
            together, counts = counted(lambda: list(pool.map(pack, chunks)))
            assert counts["ingest_batches_packed_native"] == 8
            for got, want in zip(together, serial):
                assert_same_batch(got, want)


def test_the_counter_reads_the_batch_count_through_the_plan(tmp_path):
    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=2, lines_per_file=50, num_slots=4,
        vocab_per_slot=60, max_len=3, dense_dim=2, seed=5, conversion=True)
    feed = dataclasses.replace(feed, batch_size=16)
    ds = BoxDataset(feed, read_threads=1, columnar=True)
    ds.set_filelist(files)
    ds.load_into_memory()
    assert ds._load_columnar
    ds.local_shuffle(3)
    plan = ds.split_batches(num_workers=1)[0]
    batches, counts = counted(lambda: list(plan))
    assert len(batches) == len(plan) == 7
    assert counts["ingest_batches_packed_native"] == len(plan)
