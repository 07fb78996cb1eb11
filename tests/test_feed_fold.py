"""ISSUE 40: the next pass's plan as a delta on its base, folded a chunk at
a time (row_map.KeyFold, PassTable.begin_feed_fold / finish_feed_fold). The
oracle is the sorted derivation that stays in the tree for the plan redone
on a boundary: np.unique over every chunk, then RowMap.succeed."""

import contextlib
import gc
import unittest.mock as mock
import zlib

import numpy as np
import pytest

from paddlebox_tpu.config.configs import TableConfig
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.embedding.row_map import KeyFold, RowMap, merge_sorted
from paddlebox_tpu.native import build as native_build

ALL_ONES = np.uint64(2**64 - 1)
CAPACITY = 512
FIELDS = ("keys", "rows", "holes", "top", "limit", "arrived", "freed",
          "dense")
DRIFTS = (0.0, 0.1, 1.0)
CHUNKINGS = ("one_chunk", "many_with_repeats", "empty_chunks", "unsorted")
BASES = ("dense_by_rank", "holes_after_churn", "one_under_capacity",
         "holds_all_ones")


def seeded(*case):
    """An rng of the case's own, the same in every process."""
    return np.random.RandomState(zlib.crc32(repr(case).encode()))


def fresh(rng, n, taken=()):
    """n distinct keys in no `taken` array, unsorted."""
    pool = np.setdiff1d(
        rng.randint(1, 1 << 40, size=4 * n + 64).astype(np.uint64),
        np.concatenate([np.asarray(t, np.uint64) for t in taken])
        if taken else np.empty(0, np.uint64))
    return rng.permutation(pool)[:n]


def indexed(m):
    m.build_index()
    return m


def make_base(kind, rng, limit=CAPACITY - 1):
    """A base map with its index, and every key it or its forebears held."""
    if kind == "one_under_capacity":
        keys = np.sort(fresh(rng, limit))
        return indexed(RowMap.by_rank(keys, limit)), keys
    keys = np.sort(fresh(rng, 200))
    if kind == "holds_all_ones":
        keys[-1] = ALL_ONES
    base = indexed(RowMap.by_rank(keys, limit))
    if kind != "holes_after_churn":
        return base, keys
    # 60 leave and 25 arrive, twice: rows no longer follow rank, holes stay
    seen = keys
    for _ in range(2):
        stay = np.sort(rng.permutation(base.keys)[:base.keys.size - 60])
        new = fresh(rng, 25, [seen])
        seen = np.concatenate([seen, new])
        base = indexed(base.succeed(np.sort(np.concatenate([stay, new]))))
    assert base.holes.size and not base.dense
    return base, seen


def next_set(base, ever, drift, rng):
    """The next pass's keys: `drift` of the base's replaced by keys it
    never held, the all-ones key among them where it was never held."""
    n = base.keys.size
    n_new = int(round(n * drift))
    stay = rng.permutation(base.keys)[:n - n_new]
    new = fresh(rng, n_new, [ever])
    if n_new and ALL_ONES not in ever:
        new[0] = ALL_ONES
    return np.concatenate([stay, new])


def chunked(keys, how, rng):
    keys = rng.permutation(keys)
    if how == "one_chunk":
        return [np.sort(keys)]
    if how == "unsorted":
        return [keys]
    if how == "empty_chunks":
        half = keys.size // 2
        none = np.empty(0, np.uint64)
        return [none, keys[:half], none, keys[half:], none]
    parts = np.array_split(keys, 5)
    # repeats inside a chunk, and a chunk of keys other chunks brought
    return ([np.concatenate([p, p[:3]]) for p in parts]
            + [keys[::7], parts[0]])


def oracle(table, chunks, base):
    return table._assign_rows(np.unique(np.concatenate(chunks)), base, {})


def assert_same_map(got, want):
    for f in FIELDS:
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f), f)
        assert np.asarray(getattr(got, f)).dtype == np.asarray(
            getattr(want, f)).dtype, f


@pytest.mark.parametrize("base_kind", BASES)
@pytest.mark.parametrize("chunking", CHUNKINGS)
@pytest.mark.parametrize("drift", DRIFTS)
def test_the_folded_plan_is_the_sorted_plan_field_for_field(
        drift, chunking, base_kind):
    rng = seeded(drift, chunking, base_kind)
    table = PassTable(TableConfig(embedx_dim=4, pass_capacity=CAPACITY))
    base, ever = make_base(base_kind, rng)
    chunks = chunked(next_set(base, ever, drift, rng), chunking, rng)
    plan = table.plan_feed_pass(chunks, base)
    want = oracle(table, chunks, base)
    assert plan.base is base
    np.testing.assert_array_equal(plan.keys, want.keys)
    assert_same_map(plan.rows, want.rows)
    # the index answers for the rows the map holds, and for nothing else
    np.testing.assert_array_equal(
        plan.rows.lookup(plan.keys[::-1], None, CAPACITY - 1),
        plan.rows.rows[::-1])
    with pytest.raises(KeyError):
        plan.rows.lookup(np.array([12345 + (1 << 50)], np.uint64), None,
                         CAPACITY - 1)
    n_new = int(round(base.keys.size * drift))
    assert plan.counts == {
        "feed_plan_arrived_keys": n_new, "feed_plan_departed_keys": n_new,
        "feed_index_shared": int(not n_new),
        "feed_index_rebuilt": int(bool(n_new)),
        "feed_plan_fold_us": plan.counts["feed_plan_fold_us"],
        "feed_keys_folded": sum(c.size for c in chunks)}
    assert (plan.rows._index is base._index) == (not n_new)


@pytest.mark.parametrize("base_kind", BASES)
@pytest.mark.parametrize("drift", DRIFTS)
def test_the_searchsorted_tier_folds_to_the_same_plan(drift, base_kind):
    """Without the native library the fold probes by searchsorted and the
    maps have no index: the same plan."""
    plans = []
    for native in (True, False):
        rng = seeded(drift, base_kind)
        with (contextlib.nullcontext() if native else mock.patch.object(
                native_build, "get_lib", return_value=None)):
            table = PassTable(TableConfig(embedx_dim=4,
                                          pass_capacity=CAPACITY))
            base, ever = make_base(base_kind, rng)
            assert (base._index is not None) == native
            chunks = chunked(next_set(base, ever, drift, rng),
                             "many_with_repeats", rng)
            plans.append(table.plan_feed_pass(chunks, base))
            assert (plans[-1].rows._index is not None) == native
    assert_same_map(plans[1].rows, plans[0].rows)
    assert plans[1].counts["feed_keys_folded"] == plans[0].counts[
        "feed_keys_folded"]


@pytest.mark.parametrize("drift", [0.0, 0.1])
def test_a_shared_index_outlives_its_base_and_is_destroyed_once(drift):
    """Zero drift shares the base's native index: one owner, which
    answers after the base is deleted and collected, and is destroyed
    once when the last map lets go. A drift builds the successor's own."""
    rng = np.random.RandomState(7)
    table = PassTable(TableConfig(embedx_dim=4, pass_capacity=CAPACITY))
    base, ever = make_base("holes_after_churn", rng)
    keys = next_set(base, ever, drift, rng)
    plan = table.plan_feed_pass([keys], base)
    handle = base._index.handle
    assert (plan.rows._index.handle == handle) == (drift == 0.0)
    assert plan.counts["feed_index_shared"] == int(drift == 0.0)
    assert plan.counts["feed_index_rebuilt"] == int(drift != 0.0)
    destroyed = []
    real = native_build.destroy_route_index
    with mock.patch.object(native_build, "destroy_route_index",
                           side_effect=lambda h: (destroyed.append(h),
                                                  real(h))):
        rows = plan.rows
        del base, plan
        gc.collect()
        # the base's index went with the base, unless the successor has it
        assert destroyed == ([] if drift == 0.0 else [handle])
        np.testing.assert_array_equal(
            rows.lookup(rows.keys, None, CAPACITY - 1), rows.rows)
        own = rows._index.handle
        del rows
        gc.collect()
        assert destroyed == ([handle] if drift == 0.0 else [handle, own])


def test_the_fold_hands_out_each_arrival_once_across_chunks():
    """take_arrivals returns, sorted unique, the misses no earlier call
    returned, whatever repeats the chunks bring; stayed counts a base row
    once; the side set is their union."""
    rng = np.random.RandomState(11)
    base, ever = make_base("dense_by_rank", rng)
    new = np.sort(fresh(rng, 30, [ever]))
    fold = KeyFold(base)
    assert fold.take_arrivals().size == 0
    fold.add(np.concatenate([base.keys[:50], new[:10], new[:10]]))
    fold.add(np.concatenate([new[5:20], base.keys[40:60]]))
    assert fold.unsettled
    np.testing.assert_array_equal(fold.take_arrivals(), new[:20])
    assert not fold.unsettled and fold.take_arrivals().size == 0
    fold.add(np.concatenate([new[15:], base.keys[:5]]))
    np.testing.assert_array_equal(fold.take_arrivals(), new[20:])
    np.testing.assert_array_equal(fold.arrived, new)
    assert (fold.stayed, fold.size) == (60, 90)
    assert fold.folded == 70 + 35 + 20
    assert_same_map(fold.successor(), base.succeed(
        np.sort(np.concatenate([base.keys[:60], new]))))


@pytest.mark.parametrize("over_by", ["arrivals", "one_key"])
def test_a_folded_plan_over_capacity_raises_and_writes_nothing(over_by):
    rng = np.random.RandomState(13)
    table = PassTable(TableConfig(embedx_dim=4, pass_capacity=CAPACITY))
    base, ever = make_base("one_under_capacity", rng)
    held = (table._pass_keys, table._rows, table._resident)
    extra = fresh(rng, 40 if over_by == "arrivals" else 1, [ever])
    with pytest.raises(RuntimeError, match="pass_capacity"):
        table.plan_feed_pass([base.keys[:300], extra, base.keys[300:]],
                             base)
    assert (table._pass_keys, table._rows, table._resident) == held
    # as many leave as arrive: it fits again
    plan = table.plan_feed_pass([base.keys[extra.size:], extra], base)
    assert plan.keys.size == CAPACITY - 1 and plan.rows.free_rows == 0


def test_a_first_pass_has_no_base_and_is_sorted_by_rank():
    """No base: the one np.unique and rows by rank, as before the fold;
    nothing is folded and every key arrives."""
    table = PassTable(TableConfig(embedx_dim=4, pass_capacity=CAPACITY))
    keys = np.array([9, 3, 3, ALL_ONES, 7, 9], np.uint64)
    fold = table.begin_feed_fold(None)
    fold.add(keys[:3])
    assert fold.arrivals().size == 0 and fold.folded == 0
    fold.add(keys[3:])
    plan = table.finish_feed_fold(fold)
    np.testing.assert_array_equal(plan.keys, np.unique(keys))
    assert_same_map(plan.rows, RowMap.by_rank(np.unique(keys), CAPACITY - 1))
    assert "promote_diff" not in plan.stamps
    assert plan.counts == {
        "feed_plan_arrived_keys": 4, "feed_plan_departed_keys": 0,
        "feed_index_shared": 0, "feed_index_rebuilt": 1,
        "feed_plan_fold_us": 0, "feed_keys_folded": 0}


@pytest.mark.parametrize("sizes", [(0, 0), (0, 5), (5, 0), (40, 7),
                                   (7, 40)])
def test_merge_sorted_is_the_sorted_union(sizes):
    rng = np.random.RandomState(sum(sizes))
    both = np.sort(fresh(rng, sum(sizes)))
    pick = np.zeros(both.size, bool)
    pick[rng.permutation(both.size)[:sizes[1]]] = True
    merged, from_b = merge_sorted(both[~pick], both[pick])
    np.testing.assert_array_equal(merged, both)
    np.testing.assert_array_equal(from_b, pick)


def test_a_large_chunk_is_folded_a_slice_a_thread_to_the_same_marks(
        monkeypatch):
    """A chunk of several _FOLD_SLICE is probed on several threads into
    one set of marks: the misses come back in the chunk's order and a row
    whose key two slices hold is counted once, as on one thread."""
    rng = np.random.RandomState(17)
    keys = np.sort(fresh(rng, 5000))
    base = indexed(RowMap.by_rank(keys[:4000], 1 << 13))
    chunk = np.concatenate([keys, keys[::-1], keys[:4500]])  # 14,500 keys
    one = KeyFold(base)
    one.add(chunk)
    monkeypatch.setattr(native_build, "_FOLD_SLICE", 1000)
    monkeypatch.setattr(native_build.os, "cpu_count", lambda: 8)
    lib, slices = native_build.get_lib(), []
    real = lib.rt_fold
    monkeypatch.setattr(lib, "rt_fold", lambda *a: (slices.append(a[2]),
                                                    real(*a))[1])
    many = KeyFold(base)
    many.add(chunk)
    # 14 slices' worth, half of 8 cores
    assert sorted(slices) == [3625, 3625, 3625, 3625]
    assert (many.stayed, many.folded) == (one.stayed, one.folded) == (
        4000, 14500)
    np.testing.assert_array_equal(many._seen, one._seen)
    np.testing.assert_array_equal(many._loose[0], one._loose[0])
    np.testing.assert_array_equal(many.take_arrivals(), keys[4000:])
    assert_same_map(many.successor(), one.successor())
