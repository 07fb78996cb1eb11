"""Parity per key: incremental pass lifecycle vs the full rebuild path.

The incremental lifecycle (a resident key keeps its slab row, only the
keys that arrive are promoted, touched-row writeback, cross-pass HBM
residency, flags.incremental_pass) must hold, for every key, the bits the
full begin_pass/end_pass round trip holds: the same slab row contents
under ``lookup_ids`` after every begin_pass and push, the same host-store
contents (values INCLUDING optimizer state columns) after every end_pass,
and a journal that replays to the same rows — across consecutive
overlapping passes, at 0% overlap, and through a test_mode (no-create,
no-writeback) eval pass in the middle. WHICH row a key occupies is the
same in both (the assignment is the table's and outlives the flag: an
embedding a push creates draws its init from its slab row) and is held by
the row-assignment tests."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import SparseOptimizerConfig, TableConfig
from paddlebox_tpu.embedding.pass_table import PassTable, _delta_promote
from paddlebox_tpu.obs import device as obs_device
from paddlebox_tpu.parallel.sharded_table import ShardedPassTable
from paddlebox_tpu.utils.stats import gauge_get, stat_get

D = 4
CAP = 1 << 10


def table_cfg(capacity=CAP):
    return TableConfig(
        embedx_dim=D, pass_capacity=capacity,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))


@pytest.fixture
def incremental_flag():
    """Restore the flag whatever a test sets it to."""
    saved = flags.get_flag("incremental_pass")
    yield
    flags.set_flag("incremental_pass", saved)


def make_passes(rng, n_passes=3, n_keys=500, overlap=0.9):
    """Consecutive sorted-unique key sets with ~`overlap` retention."""
    cur = np.unique(rng.randint(0, 1 << 30, n_keys).astype(np.uint64))
    out = [cur]
    for _ in range(n_passes - 1):
        keep = rng.rand(cur.size) < overlap
        fresh = np.unique(
            rng.randint(0, 1 << 30, max(8, int(n_keys * (1 - overlap))))
            .astype(np.uint64))
        cur = np.unique(np.concatenate([cur[keep], fresh]))
        out.append(cur)
    return out


def sorted_store_items(store):
    keys, vals = store.state_items()
    order = np.argsort(keys)
    return keys[order], vals[order]


class ReplayedJournal:
    """Stands in for train.journal.TouchedRowJournal: folds the row
    records into the state a replay would reach (key -> last row)."""

    def __init__(self):
        self.state = {}
        self.events = []

    def append_rows(self, keys, rows):
        for k, r in zip(keys.tolist(), np.array(rows)):
            self.state[k] = r

    def append_event(self, code):
        self.events.append(code)

    def append_move(self, op, keys):
        self.events.append(("move", op, tuple(keys.tolist())))

    def items(self):
        keys = np.array(sorted(self.state), np.uint64)
        return keys, np.stack([self.state[k] for k in keys.tolist()])


def feed(t, keys):
    t.begin_feed_pass()
    t.add_keys(keys)
    t.end_feed_pass()


def push_some(t, ids):
    """Real device pushes on `ids` (with a deterministic gradient)."""
    pl = t.push_layout
    g = np.zeros((ids.size, pl.width), np.float32)
    g[:, pl.SHOW] = 1.0
    g[:, pl.CLICK] = (np.arange(ids.size) % 2).astype(np.float32)
    g[:, pl.EMBED_G] = 0.05
    g[:, pl.embedx_g:] = 0.01
    t.push(jnp.asarray(ids), jnp.asarray(g))


def rows_of(t, keys):
    """The slab's bits per key: what a pull of `keys` would be made of."""
    return np.asarray(t.slab)[t._rows.probe(keys)]


def run_single(passes, incremental, test_pass=None, seed=11):
    """Drive a PassTable through the passes with real device pushes;
    returns per-pass (rows per key after the pushes, store_keys,
    store_vals, replayed journal, each key's slab row). test_pass, when
    given, is a key set run in test_mode between the train passes (after
    the first one)."""
    flags.set_flag("incremental_pass", incremental)
    t = PassTable(table_cfg(), seed=seed)
    journal = ReplayedJournal()
    t.attach_journal(journal)
    out = []
    for pi, ks in enumerate(passes):
        if test_pass is not None and pi == 1:
            # eval pass in the middle: no create, no writeback
            t.set_test_mode(True)
            feed(t, test_pass)
            t.begin_pass()
            eval_ids = t.lookup_ids(test_pass)
            eval_rows = np.asarray(t.pull(jnp.asarray(eval_ids)))
            t.end_pass()
            t.set_test_mode(False)
            ek, ev = sorted_store_items(t.store)
            out.append(("eval", eval_rows, ek, ev, journal.items(),
                        eval_ids))
        feed(t, ks)
        t.begin_pass()
        # push gradients on a deterministic subset (with repeats, so the
        # dedup + merge path runs), leave the rest untouched
        sub = np.concatenate([ks[: max(1, ks.size // 2)], ks[:7]])
        push_some(t, t.lookup_ids(sub))
        by_key = rows_of(t, ks)
        t.end_pass()
        k, v = sorted_store_items(t.store)
        out.append(("train", by_key, k, v, journal.items(),
                    t._rows.rows.copy()))
    return out


def assert_runs_equal(full, inc):
    assert len(full) == len(inc)
    for (tag_f, rows_f, k_f, v_f, j_f, at_f), (tag_i, rows_i, k_i, v_i, j_i,
                                               at_i) in zip(full, inc):
        assert tag_f == tag_i
        # a key has one slab row whichever way the flag stands: an
        # embedding a push creates draws its init from (prng, slab row)
        # (optimizers._fresh_uniform), so the bits below depend on it
        np.testing.assert_array_equal(at_f, at_i)
        np.testing.assert_array_equal(rows_f, rows_i)
        np.testing.assert_array_equal(k_f, k_i)
        np.testing.assert_array_equal(v_f, v_i)
        np.testing.assert_array_equal(j_f[0], j_i[0])
        np.testing.assert_array_equal(j_f[1], j_i[1])


def test_pass_table_parity_overlapping(incremental_flag):
    passes = make_passes(np.random.RandomState(0), n_passes=4, overlap=0.9)
    full = run_single(passes, incremental=False)
    inc = run_single(passes, incremental=True)
    assert_runs_equal(full, inc)


def test_pass_table_parity_zero_overlap(incremental_flag):
    rng = np.random.RandomState(1)
    # disjoint ranges: 0% overlap — the incremental worst case must still
    # be exact per key (every row freed + promoted each pass)
    passes = [np.unique((rng.randint(0, 1 << 20, 300)
                         + (p << 32)).astype(np.uint64))
              for p in range(3)]
    full = run_single(passes, incremental=False)
    inc = run_single(passes, incremental=True)
    assert_runs_equal(full, inc)


def test_pass_table_parity_through_test_mode(incremental_flag):
    rng = np.random.RandomState(2)
    passes = make_passes(rng, n_passes=3, overlap=0.85)
    # the eval set mixes resident keys with NEVER-SEEN keys: test mode
    # must not create them, and the incremental path must not leak the
    # eval slab (zero rows for unseen keys) into the next train promote
    unseen = np.unique((rng.randint(0, 1 << 20, 64)
                        + (7 << 40)).astype(np.uint64))
    test_keys = np.unique(np.concatenate([passes[0][:100], unseen]))
    full = run_single(passes, incremental=False, test_pass=test_keys)
    inc = run_single(passes, incremental=True, test_pass=test_keys)
    assert_runs_equal(full, inc)
    # the eval pass must not have created the unseen keys in either run
    for run in (full, inc):
        tag, _, keys = run[1][:3]
        assert tag == "eval"
        assert not np.isin(unseen, keys).any()


def test_rows_outlive_a_flag_flip(incremental_flag):
    """The assignment is the table's, not the resident slab's: flag off
    builds the slab whole with every key at the row it had, and a flip
    back to on finds the rows where the off passes left them."""
    passes = make_passes(np.random.RandomState(12), n_passes=5, overlap=0.8)
    want = run_single(passes, incremental=True)
    flags.set_flag("incremental_pass", False)
    t = PassTable(table_cfg(), seed=11)
    hit = stat_get("pass_rows_promote_hit")
    for pi, ks in enumerate(passes):
        flags.set_flag("incremental_pass", pi in (0, 3, 4))
        feed(t, ks)
        t.begin_pass()
        sub = np.concatenate([ks[: max(1, ks.size // 2)], ks[:7]])
        push_some(t, t.lookup_ids(sub))
        np.testing.assert_array_equal(t._rows.rows, want[pi][5])
        np.testing.assert_array_equal(rows_of(t, ks), want[pi][1])
        t.end_pass()
        assert (t.slab is None) == (pi in (1, 2))
    assert not t._rows.dense                # the set drifted: rows != rank
    # passes 1 and 4 found the slab the on-pass before them left; 0, 2
    # and 3 built theirs whole
    stayed = sum(np.isin(passes[p], passes[p - 1]).sum() for p in (1, 4))
    assert stat_get("pass_rows_promote_hit") - hit == stayed
    k, v = sorted_store_items(t.store)
    np.testing.assert_array_equal(k, want[-1][2])
    np.testing.assert_array_equal(v, want[-1][3])


def test_pass_table_delta_path_actually_ran(incremental_flag):
    """Guard against the delta promote silently falling back to full
    builds: at high overlap the resident-hit stat must move."""
    passes = make_passes(np.random.RandomState(3), n_passes=3, overlap=0.9)
    before = stat_get("pass_rows_promote_hit")
    run_single(passes, incremental=True)
    assert stat_get("pass_rows_promote_hit") > before


def test_pass_table_invalidation_forces_full_build(incremental_flag):
    """A store mutation outside the pass cadence (end_day aging) must
    drop residency — and the next pass must still hold, per key, the bits
    of a full-path table subjected to the same cadence."""
    passes = make_passes(np.random.RandomState(4), n_passes=2, overlap=0.9)

    def run(incremental):
        flags.set_flag("incremental_pass", incremental)
        t = PassTable(table_cfg(), seed=5)
        outs = []
        for ks in passes:
            feed(t, ks)
            t.begin_pass()
            ids = t.lookup_ids(ks[: ks.size // 2])
            pl = t.push_layout
            g = np.zeros((ids.size, pl.width), np.float32)
            g[:, pl.SHOW] = 1.0
            g[:, pl.EMBED_G] = 0.1
            t.push(jnp.asarray(ids), jnp.asarray(g))
            outs.append(rows_of(t, ks))
            # every pass after an end_day is a full build: rows by rank
            np.testing.assert_array_equal(t._rows.rows, np.arange(ks.size))
            t.end_pass()
            t.end_day()  # ages + shrinks between every pass
        return outs, sorted_store_items(t.store)

    slabs_f, store_f = run(False)
    slabs_i, store_i = run(True)
    for a, b in zip(slabs_f, slabs_i):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(store_f[0], store_i[0])
    np.testing.assert_array_equal(store_f[1], store_i[1])


# -------------------------------------------------------- row assignment
def begin_counts(t):
    """One begin_pass; the counters' and the gauge's reading of it."""
    names = ("pass_rows_promote_hit", "pass_rows_promote_new",
             "pass_rows_freed")
    before = [stat_get(c) for c in names]
    t.begin_pass()
    got = dict(zip(("hit", "new", "freed"),
                   (stat_get(c) - b for c, b in zip(names, before))))
    got["free"] = gauge_get("pass_free_rows")
    return got


def assert_assignment_sound(t, keys):
    """Distinct rows below the padding row, and no row lost or made."""
    rows = t._rows.probe(keys)
    assert rows.min() >= 0 and rows.max() < t.padding_id
    assert np.unique(rows).size == keys.size
    assert gauge_get("pass_free_rows") + keys.size == t.capacity - 1
    free = np.concatenate([t._rows.holes,
                           np.arange(t._rows.top, t.padding_id)])
    assert free.size == t._rows.free_rows == gauge_get("pass_free_rows")
    assert not np.isin(free, rows).any()


def test_same_key_set_twice_moves_nothing(incremental_flag):
    flags.set_flag("incremental_pass", True)
    keys = make_passes(np.random.RandomState(20), n_passes=1)[0]
    t = PassTable(table_cfg(), seed=3)
    feed(t, keys)
    t.begin_pass()
    first = t.lookup_ids(keys)
    push_some(t, first[:100])
    t.end_pass()
    feed(t, keys)
    got = begin_counts(t)
    assert got == {"hit": keys.size, "new": 0, "freed": 0,
                   "free": CAP - 1 - keys.size}
    np.testing.assert_array_equal(t.lookup_ids(keys), first)
    t.end_pass()
    # a second pass over ONE feed finds its own rows resident, too
    got = begin_counts(t)
    assert (got["hit"], got["new"], got["freed"]) == (keys.size, 0, 0)
    np.testing.assert_array_equal(t.lookup_ids(keys), first)
    t.end_pass()


def test_overlap_keeps_rows_and_fills_free_rows(incremental_flag):
    flags.set_flag("incremental_pass", True)
    a, b = make_passes(np.random.RandomState(21), n_passes=2, overlap=0.9)
    t = PassTable(table_cfg(), seed=3)
    feed(t, a)
    t.begin_pass()
    rows_a = t.lookup_ids(a)
    t.end_pass()
    feed(t, b)
    got = begin_counts(t)
    stayed = np.isin(b, a)
    left = ~np.isin(a, b)
    assert got["hit"] == stayed.sum() and got["new"] == (~stayed).sum()
    assert got["freed"] == left.sum() > 0
    rows_b = t.lookup_ids(b)
    # every surviving key keeps its row
    np.testing.assert_array_equal(rows_b[stayed], rows_a[np.isin(a, b)])
    # every new key has a row of its own: freed ones first, lowest first,
    # then never-used ones; none is the padding row
    want = np.sort(rows_a[left])[: got["new"]]
    want = np.concatenate([want, np.arange(
        a.size, a.size + got["new"] - want.size)])
    np.testing.assert_array_equal(rows_b[~stayed], want)
    assert_assignment_sound(t, b)
    t.end_pass()


def test_churn_at_full_capacity_leaks_no_row(incremental_flag):
    """20 passes at a working set of capacity - 1, 0-100% overlap drawn
    per pass: rows always distinct, free + assigned = capacity - 1, and
    every key reads its own bits."""
    flags.set_flag("incremental_pass", True)
    cap = 1 << 8
    rng = np.random.RandomState(22)
    t = PassTable(table_cfg(cap), seed=3)
    pool = np.unique(rng.randint(1, 1 << 40, 4 * cap).astype(np.uint64))
    cur = np.sort(rng.choice(pool, cap - 1, replace=False))
    overlaps = [1.0, 0.0] + list(rng.rand(18))
    for i, overlap in enumerate(overlaps):
        keep = cur[rng.rand(cur.size) < overlap]
        fresh = np.setdiff1d(pool, cur)
        cur = np.sort(np.concatenate(
            [keep, rng.choice(fresh, cap - 1 - keep.size, replace=False)]))
        feed(t, cur)
        got = begin_counts(t)
        assert got["free"] == 0
        # the first pass builds the slab whole; every later one promotes
        assert got["hit"] + got["new"] == (cap - 1 if i else 0)
        assert got["new"] == got["freed"] == cap - 1 - (keep.size if i else
                                                        cap - 1)
        assert_assignment_sound(t, cur)
        ids = t.lookup_ids(cur)
        push_some(t, ids[::3])
        slab = np.asarray(t.slab)
        t.end_pass()
        with t.store_lock:
            np.testing.assert_array_equal(slab[ids], t.store.lookup(cur))


def test_a_returning_key_reads_its_own_bits(incremental_flag):
    """A key leaves, a new key takes its row, the old key returns later:
    each reads its own bits from the store, not the other's."""
    flags.set_flag("incremental_pass", True)
    base = np.arange(10, 60, dtype=np.uint64)
    old, new = np.uint64(5), np.uint64(7)      # both sort first
    t = PassTable(table_cfg(), seed=3)

    def one_pass(keys, pushed):
        feed(t, np.sort(keys))
        t.begin_pass()
        row = {int(k): int(r) for k, r in zip(keys, t.lookup_ids(keys))}
        push_some(t, t.lookup_ids(np.array([pushed] * 3, np.uint64)))
        bits = {int(k): np.asarray(t.slab)[row[int(k)]].copy()
                for k in keys}
        t.end_pass()
        return row, bits

    row1, bits1 = one_pass(np.append(base, old), old)
    row2, bits2 = one_pass(np.append(base, new), new)
    assert row2[int(new)] == row1[int(old)]     # the freed row, reused
    assert not np.array_equal(bits2[int(new)], bits1[int(old)])
    row3, bits3 = one_pass(np.concatenate([base, [old, new]]), base[0])
    assert row3[int(new)] == row2[int(new)] != row3[int(old)]
    np.testing.assert_array_equal(bits3[int(old)], bits1[int(old)])
    np.testing.assert_array_equal(bits3[int(new)], bits2[int(new)])
    with t.store_lock:
        got = t.store.lookup(np.array([old, new], np.uint64))
    np.testing.assert_array_equal(got[0], bits1[int(old)])
    np.testing.assert_array_equal(got[1], bits2[int(new)])


@pytest.mark.parametrize("how", ["test_mode", "invalidate",
                                 "invalidate_after_feed"])
def test_fallback_to_the_full_build_resets_the_assignment(incremental_flag,
                                                          how):
    flags.set_flag("incremental_pass", True)
    a, b, c = make_passes(np.random.RandomState(23), n_passes=3, overlap=0.7)
    t = PassTable(table_cfg(), seed=3)
    for ks in (a, b):
        feed(t, ks)
        t.begin_pass()
        t.lookup_ids(ks)
        t.end_pass()
    assert not np.array_equal(t._rows.rows, np.arange(b.size))  # churned
    if how == "test_mode":
        t.set_test_mode(True)
        feed(t, a[:50])
        t.begin_pass()       # consumes the resident slab
        t.end_pass()
        t.set_test_mode(False)
    elif how == "invalidate":
        t.invalidate_residency()
    feed(t, c)
    if how == "invalidate_after_feed":
        # the feed pass planned c's rows on the resident map; begin_pass
        # must not keep that plan for a slab it builds whole
        assert not np.array_equal(t._rows.rows, np.arange(c.size))
        t.invalidate_residency()
    got = begin_counts(t)
    assert (got["hit"], got["new"], got["freed"]) == (0, 0, 0)  # full build
    np.testing.assert_array_equal(t.lookup_ids(c), np.arange(c.size))
    assert t._rows.holes.size == 0 and t._rows.top == c.size
    assert_assignment_sound(t, c)
    with t.store_lock:
        np.testing.assert_array_equal(rows_of(t, c), t.store.lookup(c))
    t.end_pass()


# ---- ISSUE 33: a feed pass is planned ahead of its boundary, then installed
OVERLAPS = [1.0, 0.9, 0.0]


def three_passes(seed, overlap):
    if overlap == 1.0:
        return make_passes(np.random.RandomState(seed), n_passes=1) * 3
    return make_passes(np.random.RandomState(seed), n_passes=3,
                       overlap=overlap)


def map_fields(rows):
    return (rows.keys, rows.rows, rows.arrived, rows.holes, rows.top,
            rows.freed, rows.dense, rows.limit)


def run_open_pass(t, ks, plan_during=None):
    """begin_pass, look up and push, end_pass; plan_during() is called
    while the pass is open. Returns what the open pass read and wrote."""
    t.begin_pass()
    sub = np.concatenate([ks[: max(1, ks.size // 2)], ks[:7]])
    ids = t.lookup_ids(sub)
    planned = plan_during() if plan_during is not None else None
    t.note_touched(ids[:3])
    push_some(t, ids)
    touched = t._touched.copy()
    by_key = rows_of(t, ks)
    t.end_pass()
    return planned, (ids, touched, by_key) + sorted_store_items(t.store)


@pytest.mark.parametrize("when", ["before_begin", "while_open"])
@pytest.mark.parametrize("overlap", OVERLAPS)
def test_a_plan_made_ahead_installs_what_the_boundary_derives(
        incremental_flag, overlap, when):
    """The plan of pass N+1, made once pass N is installed (before its
    begin_pass, or while it is open), writes nothing the open pass reads,
    and installs the keys, rows, arrived mask and free rows that
    end_feed_pass derives on the boundary."""
    flags.set_flag("incremental_pass", True)
    passes = three_passes(31, overlap)
    ref, t = PassTable(table_cfg(), seed=3), PassTable(table_cfg(), seed=3)
    counts = [stat_get("feed_plan_installed"), stat_get("feed_plan_redone")]
    plan = t.plan_feed_pass([passes[0]], t.next_base())
    for i, ks in enumerate(passes):
        feed(ref, ks)
        t.install_feed_plan(plan)
        for got, want in zip(map_fields(t._rows), map_fields(ref._rows)):
            np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(t._pass_keys, ref._pass_keys)
        assert t._rows._index is not None
        nxt = passes[i + 1] if i + 1 < len(passes) else None

        def make_plan():
            held = (t._pass_keys, t._rows, t._resident, t._slab,
                    None if t._touched is None else t._touched.copy())
            # chunks as a preload buffers them: unsorted, with repeats
            made = t.plan_feed_pass([nxt[::-1], nxt[:9]], t.next_base())
            assert made.base is t._rows
            now = (t._pass_keys, t._rows, t._resident, t._slab, t._touched)
            assert all(a is b for a, b in zip(held[:4], now[:4]))
            if held[4] is not None:
                np.testing.assert_array_equal(held[4], now[4])
            return made

        if nxt is not None and when == "before_begin":
            plan = make_plan()
        made, got = run_open_pass(
            t, ks, make_plan if nxt is not None and when == "while_open"
            else None)
        plan = made if made is not None else plan
        _, want = run_open_pass(ref, ks)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    # ref's three feeds and t's three installs each held; none was redone
    assert stat_get("feed_plan_installed") - counts[0] == 6
    assert stat_get("feed_plan_redone") == counts[1]


@pytest.mark.parametrize("how", ["invalidate", "test_mode", "poison"])
@pytest.mark.parametrize("overlap", OVERLAPS)
def test_a_plan_whose_base_is_gone_is_redone_at_the_install(
        incremental_flag, overlap, how):
    """A save's invalidate_residency, an eval pass or a poisoned pass
    between the plan and its boundary: the base is not resident at the
    install, the assignment is redone there (rows by rank, as the full
    build that follows needs them), and no row is stale."""
    flags.set_flag("incremental_pass", True)
    a, b, c = three_passes(32, overlap)
    t = PassTable(table_cfg(), seed=3)
    feed(t, a)
    run_open_pass(t, a)
    feed(t, b)
    plan = t.plan_feed_pass([c], t.next_base())
    assert plan.base is t._rows
    if overlap not in (0.0, 1.0):
        assert not plan.rows.dense              # churned: rows != rank
    if how == "poison":
        run_open_pass(t, b, t.invalidate_residency)
    else:
        run_open_pass(t, b)
    if how == "invalidate":
        t.invalidate_residency()
    elif how == "test_mode":
        t.set_test_mode(True)
        feed(t, a[:50])
        t.begin_pass()       # consumes the resident slab
        t.end_pass()
        t.set_test_mode(False)
    assert t._resident is None
    redone = stat_get("feed_plan_redone")
    installed = stat_get("feed_plan_installed")
    t.install_feed_plan(plan)
    assert stat_get("feed_plan_redone") == redone + 1
    assert stat_get("feed_plan_installed") == installed
    assert t._rows is not plan.rows and t._rows.dense
    got = begin_counts(t)
    assert (got["hit"], got["new"], got["freed"]) == (0, 0, 0)  # full build
    np.testing.assert_array_equal(t.lookup_ids(c), np.arange(c.size))
    assert_assignment_sound(t, c)
    with t.store_lock:
        np.testing.assert_array_equal(rows_of(t, c), t.store.lookup(c))
    t.end_pass()


def test_a_plan_is_not_installed_under_an_open_pass(incremental_flag):
    flags.set_flag("incremental_pass", True)
    a, b = make_passes(np.random.RandomState(33), n_passes=2)
    t = PassTable(table_cfg(), seed=3)
    feed(t, a)
    t.begin_pass()
    plan = t.plan_feed_pass([b], t.next_base())
    with pytest.raises(RuntimeError, match="open pass"):
        t.install_feed_plan(plan)
    np.testing.assert_array_equal(t.lookup_ids(a), np.arange(a.size))
    t.end_pass()
    t.install_feed_plan(plan)
    assert t._rows is plan.rows


def test_a_plan_over_capacity_is_refused_and_writes_nothing():
    t = PassTable(table_cfg(capacity=64), seed=3)
    keys = np.arange(1, 40, dtype=np.uint64)
    feed(t, keys)
    held = (t._pass_keys, t._rows)
    with pytest.raises(RuntimeError, match="pass_capacity"):
        t.plan_feed_pass([np.arange(1, 100, dtype=np.uint64)], t.next_base())
    assert (t._pass_keys, t._rows) == held
    t.begin_feed_pass()
    t.add_keys(np.arange(1, 100, dtype=np.uint64))
    with pytest.raises(RuntimeError, match="pass_capacity"):
        t.end_feed_pass()
    assert (t._pass_keys, t._rows) == held


def test_searchsorted_fallback_returns_the_native_rows(incremental_flag):
    """Without the native library the owner's searchsorted tier must
    assign, probe and look up the same rows as the hash index."""
    import unittest.mock as mock
    flags.set_flag("incremental_pass", True)
    passes = make_passes(np.random.RandomState(24), n_passes=3, overlap=0.8)

    def run():
        t = PassTable(table_cfg(), seed=3)
        out = []
        for ks in passes:
            feed(t, ks)
            t.begin_pass()
            valid = np.arange(ks.size) % 5 != 0
            out.append((t.lookup_ids(ks), t.lookup_ids(ks, valid),
                        t._rows._index is not None))
            with pytest.raises(KeyError):
                t.lookup_ids(np.array([ks.max() + 1], np.uint64))
            t.end_pass()
        return out

    native = run()
    with mock.patch("paddlebox_tpu.native.build.get_lib", return_value=None):
        plain = run()
    assert not any(p[2] for p in plain)
    for (ids_n, masked_n, _), (ids_p, masked_p, _) in zip(native, plain):
        np.testing.assert_array_equal(ids_n, ids_p)
        np.testing.assert_array_equal(masked_n, masked_p)
        assert ids_p.dtype == np.int32


def test_delta_promote_scatters_in_place_on_the_donated_slab():
    """The program of begin_pass: its output aliases the donated slab and
    no slab-sized temporary (the old whole-slab gather) survives; it is
    still the entry `delta_promote`, scope `promote_scatter`."""
    cap, width, bucket = 1 << 14, 24, 64
    slab_bytes = cap * width * 4
    compiled = _delta_promote.lower(
        jax.ShapeDtypeStruct((cap, width), jnp.float32),
        jax.ShapeDtypeStruct((bucket,), jnp.int32),
        jax.ShapeDtypeStruct((bucket, width), jnp.float32)).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == ma.output_size_in_bytes == slab_bytes
    assert ma.temp_size_in_bytes < slab_bytes // 100
    text = compiled.as_text()
    assert "gather(" not in text
    slab = jnp.arange(cap * width, dtype=jnp.float32).reshape(cap, width)
    want = np.asarray(slab).copy()
    want[[3, 9]] = 1.0
    idx = np.full(bucket, cap, np.int32)        # the drop sentinel
    idx[:2] = [3, 9]
    out = _delta_promote(slab, jnp.asarray(idx),
                         jnp.ones((bucket, width), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), want)
    entry = obs_device.snapshot()["entries"]["delta_promote"]
    assert entry["module"] == "jit__delta_promote_impl"
    assert entry["donate_argnums"] == [0]
    assert entry["analysis"]["temp_includes_slab_copy"] is False


# --------------------------------------------------------------- sharded
def run_sharded(passes, incremental, seed=9, num_shards=4):
    """Drive a ShardedPassTable through build → simulated push →
    write_back; the 'push' mutates a deterministic subset of each shard's
    rows on the host copy (the device step is exercised by the trainer
    tests; here the contract under test is the table's promote/writeback
    bookkeeping). Returns per-pass (built_slabs, store items per shard)."""
    flags.set_flag("incremental_pass", incremental)
    t = ShardedPassTable(table_cfg(), num_shards=num_shards,
                         bucket_cap=256, seed=seed)
    out = []
    for ks in passes:
        t.begin_feed_pass()
        t.add_keys(ks)
        t.end_feed_pass()
        slabs = t.build_slabs()
        built = slabs.copy()
        # simulate training: bump half of each shard's working set and
        # report those rows touched (the stage_push_dedup callback role)
        for s in range(num_shards):
            n = t._shard_keys[s].size
            if not n:
                continue
            rows = np.arange(0, n, 2, dtype=np.int32)
            slabs[s, rows] += 0.125
            t.note_touched(s, rows)
        t.write_back(slabs)
        items = [sorted_store_items(st) for st in t.stores]
        out.append((built, slabs.copy(), items))
    return out


def test_sharded_parity_overlapping(incremental_flag):
    passes = make_passes(np.random.RandomState(6), n_passes=4, overlap=0.9)
    full = run_sharded(passes, incremental=False)
    inc = run_sharded(passes, incremental=True)
    for (b_f, s_f, it_f), (b_i, s_i, it_i) in zip(full, inc):
        np.testing.assert_array_equal(b_f, b_i)
        np.testing.assert_array_equal(s_f, s_i)
        for (k_f, v_f), (k_i, v_i) in zip(it_f, it_i):
            np.testing.assert_array_equal(k_f, k_i)
            np.testing.assert_array_equal(v_f, v_i)


def test_sharded_parity_zero_overlap(incremental_flag):
    rng = np.random.RandomState(7)
    passes = [np.unique((rng.randint(0, 1 << 20, 300)
                         + (p << 32)).astype(np.uint64))
              for p in range(3)]
    full = run_sharded(passes, incremental=False)
    inc = run_sharded(passes, incremental=True)
    for (b_f, s_f, it_f), (b_i, s_i, it_i) in zip(full, inc):
        np.testing.assert_array_equal(b_f, b_i)
        for (k_f, v_f), (k_i, v_i) in zip(it_f, it_i):
            np.testing.assert_array_equal(k_f, k_i)
            np.testing.assert_array_equal(v_f, v_i)


def test_sharded_test_mode_no_create_no_writeback(incremental_flag):
    flags.set_flag("incremental_pass", True)
    rng = np.random.RandomState(8)
    passes = make_passes(rng, n_passes=2, overlap=0.9)
    t = ShardedPassTable(table_cfg(), num_shards=4, bucket_cap=256, seed=1)
    # train pass 0
    t.begin_feed_pass()
    t.add_keys(passes[0])
    t.end_feed_pass()
    slabs = t.build_slabs()
    t.write_back(slabs)
    sizes = [len(st) for st in t.stores]
    items = [sorted_store_items(st) for st in t.stores]
    # eval pass with unseen keys: stores must not change at all
    unseen = np.unique((rng.randint(0, 1 << 20, 50)
                        + (9 << 40)).astype(np.uint64))
    t.set_test_mode(True)
    t.begin_feed_pass()
    t.add_keys(np.concatenate([passes[0][:50], unseen]))
    t.end_feed_pass()
    eval_slabs = t.build_slabs()
    t.write_back(eval_slabs + 1.0)  # must be ignored in test mode
    t.set_test_mode(False)
    assert [len(st) for st in t.stores] == sizes
    for (k0, v0), st in zip(items, t.stores):
        k1, v1 = sorted_store_items(st)
        np.testing.assert_array_equal(k0, k1)
        np.testing.assert_array_equal(v0, v1)


def test_preloaded_incremental_matches_sequential_full(incremental_flag,
                                                       tmp_path):
    """End-to-end: run_preloaded_passes with the incremental lifecycle
    (+ promote prefetch thread) must produce the same losses as plain
    sequential passes with the lifecycle OFF — the whole stack (trainer
    staging, scan path, preloader, writeback) rides the same bits."""
    from paddlebox_tpu.config.configs import TrainerConfig
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.train.preload import run_preloaded_passes
    from paddlebox_tpu.train.trainer import BoxTrainer

    num_slots = 4
    files, feed = write_synthetic_ctr_files(
        str(tmp_path), num_files=2, lines_per_file=160, num_slots=num_slots,
        vocab_per_slot=60, max_len=3, seed=21)
    feed = type(feed)(slots=feed.slots, batch_size=32)
    spec = ModelSpec(num_slots=num_slots, slot_dim=3 + D)
    flags.set_flag("dataset_disable_shuffle", True)
    try:
        def datasets(n):
            out = []
            for _ in range(n):
                ds = BoxDataset(feed, read_threads=1)
                ds.set_filelist(files)
                out.append(ds)
            return out

        flags.set_flag("incremental_pass", False)
        seq = BoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                         TrainerConfig(dense_lr=0.01), seed=0)
        seq_losses = [seq.train_pass(ds)["loss"] for ds in datasets(3)]
        sk, sv = sorted_store_items(seq.table.store)

        flags.set_flag("incremental_pass", True)
        pipe = BoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(), feed,
                          TrainerConfig(dense_lr=0.01), seed=0)
        stats = run_preloaded_passes(pipe, datasets(3))
        np.testing.assert_allclose([s["loss"] for s in stats], seq_losses,
                                   rtol=1e-6)
        pk, pv = sorted_store_items(pipe.table.store)
        np.testing.assert_array_equal(sk, pk)
        np.testing.assert_array_equal(sv, pv)
    finally:
        flags.set_flag("dataset_disable_shuffle", False)
