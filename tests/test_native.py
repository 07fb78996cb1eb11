"""Native C++ components: parser parity vs the Python reference parser,
columnar packer parity vs the object packer, host store behavior."""

import numpy as np
import pytest

from paddlebox_tpu.config.configs import DataFeedConfig, SlotConfig
from paddlebox_tpu.data import (BatchPacker, BoxDataset, MultiSlotParser,
                                write_synthetic_ctr_files)
from paddlebox_tpu.data.columnar import (ColumnarBlock, pack_columnar,
                                         _group_cumcount, _run_aranges)
from paddlebox_tpu.native import available

pytestmark = pytest.mark.skipif(not available(),
                                reason="native lib unavailable")


@pytest.fixture
def feed():
    return DataFeedConfig(slots=(
        SlotConfig("click", type="float", dim=1, is_used=False),
        SlotConfig("s0", type="uint64", max_len=3),
        SlotConfig("s1", type="uint64", max_len=2),
        SlotConfig("dense", type="float", dim=2),
    ), batch_size=4)


@pytest.fixture
def data_files(tmp_path):
    files, gen_feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=2, lines_per_file=200, num_slots=3,
        vocab_per_slot=50, dense_dim=2, seed=3)
    return files, type(gen_feed)(slots=gen_feed.slots, batch_size=32)


def test_native_parser_matches_python(data_files):
    from paddlebox_tpu.data.native_parser import NativeMultiSlotParser
    files, feed = data_files
    py = MultiSlotParser(feed)
    nat = NativeMultiSlotParser(feed)
    for path in files:
        recs = list(py.parse_file(path))
        block = nat.parse_file_columnar(path)
        assert block.n_recs == len(recs)
        np.testing.assert_array_equal(block.labels,
                                      [r.label for r in recs])
        for i, rec in enumerate(recs):
            lo, hi = block.rec_offsets[i], block.rec_offsets[i + 1]
            np.testing.assert_array_equal(block.keys[lo:hi], rec.all_keys())
            np.testing.assert_allclose(block.dense[i], rec.float_slots[0],
                                       rtol=1e-5)


def test_native_parser_drops_malformed(feed, tmp_path):
    from paddlebox_tpu.data.native_parser import NativeMultiSlotParser
    p = tmp_path / "bad.txt"
    p.write_text("1 1 2 11 22 1 33 2 0.5 -1.5\n"   # good
                 "1 1 5 11\n"                        # truncated slot
                 "1 1 2 11 xx 1 3 2 0 0\n"          # non-numeric
                 "\n"                                # empty (skipped)
                 "1 0 1 7 1 8 2 1.0 2.0\n")          # good
    block = NativeMultiSlotParser(feed).parse_file_columnar(str(p))
    assert block.n_recs == 2
    np.testing.assert_array_equal(block.labels, [1, 0])
    np.testing.assert_array_equal(block.keys[:2], [11, 22])


def test_columnar_pack_matches_object_packer(data_files):
    files, feed = data_files
    # object path
    ds_obj = BoxDataset(feed, read_threads=1, columnar=False)
    ds_obj.set_filelist(files)
    ds_obj.load_into_memory()
    # columnar path
    ds_col = BoxDataset(feed, read_threads=1, columnar=True)
    ds_col.set_filelist(files)
    ds_col.load_into_memory()
    assert ds_col.columnar and len(ds_col) == len(ds_obj)

    obj_batches = ds_obj.split_batches(num_workers=2)
    col_batches = ds_col.split_batches(num_workers=2)
    assert len(obj_batches[0]) == len(col_batches[0])
    for w in range(2):
        for bo, bc in zip(obj_batches[w], col_batches[w]):
            np.testing.assert_array_equal(bo.keys, bc.keys)
            np.testing.assert_array_equal(bo.slots, bc.slots)
            np.testing.assert_array_equal(bo.segments, bc.segments)
            np.testing.assert_array_equal(bo.valid, bc.valid)
            np.testing.assert_array_equal(bo.labels, bc.labels)
            np.testing.assert_allclose(bo.dense, bc.dense, rtol=1e-6)


def test_columnar_max_len_truncation(feed):
    block = ColumnarBlock.from_key_rec(
        keys=np.arange(1, 11, dtype=np.uint64),
        key_slot=np.zeros(10, np.int32),  # all slot 0, max_len 3
        key_rec=np.zeros(10, np.int64),
        labels=np.array([1], np.int32))
    b = pack_columnar(block, np.array([0]), feed, kcap=64, num_slots=2,
                      max_lens=np.array([3, 2]))
    assert b.valid.sum() == 3
    np.testing.assert_array_equal(b.keys[:3], [1, 2, 3])


def test_vector_helpers():
    np.testing.assert_array_equal(_run_aranges(np.array([3, 1, 2])),
                                  [0, 1, 2, 0, 0, 1])
    np.testing.assert_array_equal(
        _group_cumcount(np.array([5, 5, 5, 7, 9, 9])),
        [0, 1, 2, 0, 0, 1])


def test_native_host_store_roundtrip():
    import ctypes
    from paddlebox_tpu.native import get_lib
    lib = get_lib()
    W = 8
    s = lib.hs_create(W, 0.75)
    try:
        keys = np.array([5, 1 << 60, 7, 5], dtype=np.uint64)
        rows = np.empty(4, np.int64)
        created = np.empty(4, np.uint8)
        lib.hs_lookup_or_create(
            s, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), 4,
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            created.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        assert lib.hs_size(s) == 3
        np.testing.assert_array_equal(created, [1, 1, 1, 0])
        assert rows[0] == rows[3]  # dup key → same row

        vals = np.arange(4 * W, dtype=np.float32).reshape(4, W)
        lib.hs_scatter(s, rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                       4, vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        out = np.zeros((4, W), np.float32)
        lib.hs_gather(s, rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                      4, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        np.testing.assert_array_equal(out[1], vals[1])
        np.testing.assert_array_equal(out[0], vals[3])  # dup overwrote

        # erase middle key, probe chain must stay intact
        gone = np.array([1 << 60], dtype=np.uint64)
        n = lib.hs_erase(
            s, gone.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), 1)
        assert n == 1 and lib.hs_size(s) == 2
        r2 = np.empty(4, np.int64)
        lib.hs_lookup(s, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                      4, r2.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        assert r2[1] == -1 and r2[0] >= 0 and r2[2] >= 0
    finally:
        lib.hs_destroy(s)


def test_native_host_store_grows():
    import ctypes
    from paddlebox_tpu.native import get_lib
    lib = get_lib()
    s = lib.hs_create(4, 0.75)
    try:
        n = 200_000
        keys = np.arange(1, n + 1, dtype=np.uint64)
        rows = np.empty(n, np.int64)
        lib.hs_lookup_or_create(
            s, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), None)
        assert lib.hs_size(s) == n
        # re-lookup hits the same rows
        r2 = np.empty(n, np.int64)
        lib.hs_lookup(s, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                      n, r2.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        np.testing.assert_array_equal(rows, r2)
    finally:
        lib.hs_destroy(s)


def test_concurrent_bucketize_parity():
    """Round-12 thread contract: the stager pool calls rt_bucketize on
    ONE route index from several threads concurrently (ctypes drops the
    GIL), so concurrent routings must be bit-identical to serial ones.
    The pre-fix per-INDEX dedup scratch let concurrent callers draw the
    same generation and read each other's seen-marks — a silently
    mis-routed occurrence (the PR-6 show-off-by-one flake class);
    this reproduced it in the first few trials.
    Scratch is per-thread now."""
    from concurrent.futures import ThreadPoolExecutor

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    from paddlebox_tpu.parallel.sharded_table import ShardedPassTable

    P, KB, K = 8, 2048, 8192
    rng = np.random.RandomState(0)
    pass_keys = np.unique(
        rng.randint(0, 1 << 30, 1 << 15).astype(np.uint64))
    t = ShardedPassTable(
        TableConfig(embedx_dim=8, pass_capacity=1 << 18,
                    optimizer=SparseOptimizerConfig()),
        num_shards=P, bucket_cap=KB)
    t.begin_feed_pass()
    t.add_keys(pass_keys)
    t.end_feed_pass()
    # distinct batches sharing many keys: the cross-batch scratch
    # collision food the race needed
    batches = [rng.choice(pass_keys, K).astype(np.uint64)
               for _ in range(6)]
    valid = np.ones(K, bool)
    oracle = [t.bucketize(b, valid.copy()) for b in batches]
    pool = ThreadPoolExecutor(4)
    try:
        for _trial in range(30):
            futs = [pool.submit(
                lambda b=b: t.bucketize(b, valid.copy()))
                for b in batches]
            for got, want in zip([f.result() for f in futs], oracle):
                np.testing.assert_array_equal(got.buckets, want.buckets)
                np.testing.assert_array_equal(got.restore, want.restore)
    finally:
        pool.shutdown(wait=False)


def test_library_name_carries_source_hash(tmp_path, monkeypatch):
    """The .so's file name is a content hash of the three sources and the
    compile command: a library left in _build/ by OTHER sources (the dir
    is git-ignored and travels with copied trees, where mtimes say
    nothing) has another name and is never loaded — the build compiles
    the sources that are actually there."""
    import os
    import shutil

    from paddlebox_tpu.native import build

    loaded = build._lib_path()
    assert os.path.exists(loaded)           # what this process runs on
    src_dir = tmp_path / "native"
    src_dir.mkdir()
    for src in build._SOURCES:
        shutil.copy(os.path.join(build._DIR, src), src_dir / src)
    with open(src_dir / build._SOURCES[-1], "a") as fh:
        fh.write("\n// edited\n")
    stale = src_dir / "_build" / os.path.basename(loaded)
    stale.parent.mkdir()
    stale.write_bytes(b"not a shared object")   # the would-be stale .so
    monkeypatch.setattr(build, "_DIR", str(src_dir))
    monkeypatch.setattr(build, "_BUILD", str(stale.parent))
    fresh = build._lib_path()
    assert os.path.basename(fresh) != os.path.basename(loaded)
    assert not os.path.exists(fresh)    # so _build() compiles, not loads
    # the compile command is in the hash too
    monkeypatch.setattr(build, "_CXX", build._CXX + ["-DX"])
    assert build._lib_path() != fresh
    monkeypatch.undo()
    assert build._lib_path() == loaded
