"""end_pass writes back through ONE compiled gather of a fixed chunk
(ISSUE 38): the touched rows cross R at a time, whatever their count, so
no pass boundary compiles. Held here: the store and the journal get, for
every key written back, the bits a plain ``np.asarray(slab)[rows]`` holds,
at every count around a chunk's edge, on both slab dtypes and all three
ways end_pass picks its rows; and the program count of three passes that
touch three different numbers of rows."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import SparseOptimizerConfig, TableConfig
from paddlebox_tpu.embedding import pass_table
from paddlebox_tpu.embedding.accessor import (ValueLayout,
                                              decode_slab_rows_np,
                                              encode_slab_rows_np)
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.utils.stats import stat_get

D = 4
CAP = 1 << 8
R = 8
COUNTS = [0, 1, R - 1, R, R + 1, 3 * R + 3]
UNTOUCHED = 13  # keys of the pass the touched path leaves alone


def table_cfg(capacity=CAP):
    return TableConfig(
        embedx_dim=D, pass_capacity=capacity,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))


class RecordingJournal:
    """Keeps every append_rows call as it came."""

    def __init__(self):
        self.calls = []

    def append_rows(self, keys, rows):
        self.calls.append((np.array(keys), np.array(rows)))

    def append_event(self, code):
        pass

    def append_move(self, op, keys):
        pass


def feed(t, keys):
    t.begin_feed_pass()
    t.add_keys(keys)
    t.end_feed_pass()


def bits(rows):
    return np.ascontiguousarray(rows, np.float32).view(np.uint32)


def scramble_slab(t, rng):
    """Give every slab row bits the store does not hold, without a mark
    on the touched mirror: a row written back by mistake shows."""
    shape = (t.capacity, t.layout.width)
    host = rng.uniform(-4.0, 4.0, shape).astype(np.float32)
    t.set_slab(jnp.asarray(encode_slab_rows_np(host, t.layout)))


def open_pass(t, rng, n, dense):
    """A pass of n keys, begun; its map dense (a first pass, rows by
    rank) or not (a pass before it left rows free and keys stayed)."""
    pool = np.unique(rng.randint(1, 1 << 40, 4 * (n + 8)).astype(np.uint64))
    rng.shuffle(pool)
    if not dense:
        before = np.sort(pool[:n + 3])
        feed(t, before)
        t.begin_pass()
        t.end_pass()
        arrive = min(n, 2)
        keys = np.sort(np.concatenate([before[3:3 + n - arrive],
                                       pool[n + 3:n + 3 + arrive]]))
    else:
        keys = np.sort(pool[:n])
    feed(t, keys)
    t.begin_pass()
    assert t._rows.dense == dense
    return keys


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("embed_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["touched", "every_row_dense",
                                  "every_row_not_dense"])
def test_the_store_holds_the_slab_s_bits_for_every_row_written_back(
        path, embed_dtype, count):
    flags.set_flag("slab_embed_dtype", embed_dtype)
    flags.set_flag("incremental_pass", True)
    rng = np.random.RandomState(1000 + 7 * count)
    t = PassTable(table_cfg(), seed=3)
    t._writeback_rows = R
    journal = RecordingJournal()
    t.attach_journal(journal)
    if path == "touched":
        keys = open_pass(t, rng, count + UNTOUCHED, dense=False)
        sel = np.sort(rng.choice(keys.size, count, replace=False))
    else:
        keys = open_pass(t, rng, count, dense=path == "every_row_dense")
        sel = np.arange(keys.size)
    scramble_slab(t, rng)
    rows = t._rows.probe(keys)
    if path == "touched":
        # a mark on the padding row selects nothing: count 0 still takes
        # the touched path, and writes nothing
        t.note_touched(np.append(rows[sel], t.padding_id).astype(np.int32))
    want = decode_slab_rows_np(np.asarray(t.slab)[rows[sel]], t.layout)
    held = t.store.lookup(keys).copy()
    journal.calls.clear()
    chunks0 = stat_get("pass_writeback_chunks")

    t.end_pass()

    got = t.store.lookup(keys)
    np.testing.assert_array_equal(bits(got[sel]), bits(want))
    rest = np.setdiff1d(np.arange(keys.size), sel)
    np.testing.assert_array_equal(bits(got[rest]), bits(held[rest]))
    n_chunks = -(-count // R)
    assert stat_get("pass_writeback_chunks") - chunks0 == n_chunks
    # the journal got each key written back once, in key order, a chunk a
    # call, with the rows the store got
    assert len(journal.calls) == n_chunks
    assert all(k.size <= R for k, _ in journal.calls)
    if count:
        np.testing.assert_array_equal(
            np.concatenate([k for k, _ in journal.calls]), keys[sel])
        np.testing.assert_array_equal(
            bits(np.concatenate([r for _, r in journal.calls])), bits(want))


def test_the_chunk_is_sized_from_the_row_s_bytes_alone():
    """~32 MiB a chunk, a power of two, never past the slab: 262,144 rows
    of deepfm-criteo's 76 B, 2,048 of a tower's 8,228 B."""
    rows = pass_table._writeback_chunk_rows
    assert rows(ValueLayout(10, "adagrad"), 1 << 26) == 262144
    assert rows(ValueLayout(2048, "adagrad"), 1 << 15) == 2048
    assert rows(ValueLayout(10, "adagrad", embed_dtype="bfloat16"),
                1 << 26) == 524288
    assert rows(ValueLayout(10, "adagrad"), 1000) == 1024
    t = PassTable(table_cfg(), seed=0)
    assert t._writeback_rows == CAP


def test_three_passes_of_three_sizes_compile_the_gather_once():
    """The test that fails if someone indexes the slab eagerly at a
    boundary again: eager ``slab[idx]`` is seven backend compiles for
    every new length of idx."""
    flags.set_flag("incremental_pass", True)
    rng = np.random.RandomState(5)
    # a capacity and a chunk no other test of this process compiles
    t = PassTable(table_cfg(capacity=(1 << 9) + 64), seed=1)
    t._writeback_rows = 32
    entry = pass_table._writeback_gather._entry
    pool = np.unique(rng.randint(1, 1 << 40, 600).astype(np.uint64))[:400]
    compiles, backend = [entry.compiles], []
    for touched in (37, 150, 91):
        feed(t, pool)
        t.begin_pass()
        t.lookup_ids(pool[rng.choice(pool.size, touched, replace=False)])
        before = stat_get("device_backend_compiles")
        written0 = stat_get("pass_rows_written_back")
        t.end_pass()
        assert stat_get("pass_rows_written_back") - written0 == touched
        backend.append(stat_get("device_backend_compiles") - before)
        compiles.append(entry.compiles)
    assert compiles[1] == compiles[0] + 1, compiles
    assert compiles[2:] == [compiles[1]] * 2, compiles
    assert backend[1:] == [0, 0], backend
