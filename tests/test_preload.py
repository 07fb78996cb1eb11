"""Preload overlap (BoxHelper PreLoadIntoMemory/WaitFeedPassDone cadence):
pipelined passes must train identically to sequential passes."""

import threading
import time

import numpy as np
import pytest

from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.parallel.mesh import device_mesh_1d
from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer
from paddlebox_tpu.obs.tracer import get_tracer, pass_trace_id
from paddlebox_tpu.train.preload import (FEED_PLAN_COUNTERS, PassPreloader,
                                         run_preloaded_passes)
from paddlebox_tpu.train.trainer import BoxTrainer
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.utils.stats import gauge_get, stat_get

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


def plan_threads():
    return [t.name for t in threading.enumerate()
            if t.name in ("feed-fold", "feed-ahead", "promote-prefetch")]


@pytest.mark.parametrize("fault", ["load_error", "capacity_overflow",
                                   "fold_error"])
def test_a_fault_on_the_feed_ahead_thread_surfaces_from_wait(fault):
    """What the planning threads raise (the load's error, the plan's
    capacity check, the fold's own) comes out of wait(); the table is as
    it was, no thread of the preload is left, and the preloader takes a
    fresh preload."""
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=64), seed=0)
    pre = PassPreloader(table)
    first = KeysDataset(np.arange(1, 30))
    pre.preload(first)
    assert pre.wait(first) is True
    held = (table._pass_keys, table._rows)
    bad, raised, match = {
        "load_error": (KeysDataset(np.arange(1, 30),
                                   error=OSError("disk gone")),
                       OSError, "disk gone"),
        "capacity_overflow": (KeysDataset(np.arange(1, 200)),
                              RuntimeError, "pass_capacity"),
        # ISSUE 40: a chunk the fold cannot take, on the feed-fold thread
        "fold_error": (KeysDataset(np.arange(1, 30)), ValueError, "literal"),
    }[fault]
    if fault == "fold_error":
        bad.keys = np.array(["not", "a key"])
    pre.preload(bad)
    with pytest.raises(raised, match=match):
        pre.wait(bad)
    assert (table._pass_keys, table._rows) == held
    assert plan_threads() == []
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
    assert plan_threads() == []             # the fold went with the plan
    pre.preload(third)                      # planned on the open pass
    table.end_pass()
    assert pre.wait(third) is True
    assert table._rows_base is held[1]


# ---- ISSUE 39: the plan carries its clock to the boundary that consumes it
PLAN_COUNTERS = [c for c, _span in FEED_PLAN_COUNTERS] + ["feed_plan_slack_us"]


def plan_counters():
    return {c: stat_get(c) for c in PLAN_COUNTERS}


def delta(after, before):
    return {c: after[c] - before[c] for c in after}


def whole_us(span):
    return int((span[4] - span[3]) * 1e6)


@pytest.fixture(scope="module")
def three_passes(data):
    """Three preloaded passes; the six counters as each pass ended, the
    two gauges as the last one did, and the ring."""
    from paddlebox_tpu.config import flags
    files, feed = data
    flags.set_flag("dataset_disable_shuffle", True)
    get_tracer().clear()
    trainer = BoxTrainer(
        CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D), hidden=(16,)),
        table_cfg(), feed, TrainerConfig(dense_lr=0.01), seed=0)
    seen = [plan_counters()]
    try:
        run_preloaded_passes(trainer, datasets(files, feed, 3),
                             after_pass=lambda _k, _s: seen.append(
                                 plan_counters()))
    finally:
        trainer.close()
        flags.set_flag("dataset_disable_shuffle", False)
    return {"per_pass": [delta(b, a) for a, b in zip(seen, seen[1:])],
            "gauges": (gauge_get("feed_plan_last_ms"),
                       gauge_get("feed_plan_slack_last_ms")),
            "spans": get_tracer().all_spans()}


def span_of(spans, name, k):
    """Pass k's one span of that name off the main thread's boundary: the
    chain's stages lie on the feed-ahead thread."""
    found = [s for s in spans
             if s[0] == name and s[5] == pass_trace_id(0, k)]
    assert len(found) == 1, (name, k, found)
    return found[0]


@pytest.mark.parametrize("counter,span", FEED_PLAN_COUNTERS)
def test_every_installed_plan_adds_its_span_s_width_once(three_passes,
                                                         counter, span):
    """wait() adds, for the plan it installs, the whole microseconds of
    the span the feed-ahead thread recorded under that pass's id: the same
    perf_counter pair, so counter and span agree to the microsecond."""
    for k, added in enumerate(three_passes["per_pass"]):
        if span == "promote_diff" and k == 0:
            # the first plan has no base and so no diff
            assert added[counter] == 0
            assert not [s for s in three_passes["spans"] if s[0] == span
                        and s[5] == pass_trace_id(0, 0)]
            continue
        s = span_of(three_passes["spans"], span, k)
        assert s[2] == "feed-ahead"
        assert added[counter] == whole_us(s), (k, counter)
    assert all(added["feed_plan_us"] > 0
               for added in three_passes["per_pass"])


def test_the_stages_sum_to_at_most_the_chain(three_passes):
    stages = [c for c, _s in FEED_PLAN_COUNTERS if c != "feed_plan_us"]
    for added in three_passes["per_pass"]:
        assert 0 < sum(added[c] for c in stages) <= added["feed_plan_us"]


def test_slack_is_the_ask_less_the_chain_s_end_and_excludes_a_wait(
        three_passes):
    """feed_plan_slack_us = max(0, open of ingest_wait_preload - close of
    ingest_feed_ahead); where it is positive the wait joined a thread that
    had finished."""
    spans = three_passes["spans"]
    for k, added in enumerate(three_passes["per_pass"]):
        ask = span_of(spans, "ingest_wait_preload", k)
        done = span_of(spans, "ingest_feed_ahead", k)
        assert added["feed_plan_slack_us"] == max(
            0, int((ask[3] - done[4]) * 1e6))
        if added["feed_plan_slack_us"] > 0:
            assert ask[4] - ask[3] < 1e-3
        else:
            assert ask[4] >= done[4]


def test_the_gauges_hold_the_newest_pass_s_plan(three_passes):
    last = three_passes["per_pass"][-1]
    assert three_passes["gauges"] == (
        last["feed_plan_us"] / 1000.0, last["feed_plan_slack_us"] / 1000.0)


class HeldDataset(KeysDataset):
    """A load that is done when the test says so."""

    def __init__(self, keys):
        super().__init__(keys)
        self.loaded = threading.Event()

    def wait_preload_done(self):
        assert self.loaded.wait(30.0)


@pytest.mark.parametrize("held", ["the_load", "the_train_pass"])
def test_who_was_held_back_reads_as_wait_or_as_slack(held):
    """A load that ends 50 ms after wait() asked: a wait, and no slack. A
    main thread that asks 50 ms after the plan was done (a train_pass that
    ran on): slack, and a wait of a finished thread's join."""
    get_tracer().clear()
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=64), seed=0)
    pre = PassPreloader(table)
    ds = HeldDataset(np.arange(1, 30))
    before = plan_counters()
    pre.preload(ds)
    if held == "the_load":
        threading.Timer(0.05, ds.loaded.set).start()
    else:
        ds.loaded.set()
        pre._ahead._thread.join()
        time.sleep(0.05)
    assert pre.wait(ds) is True
    added = delta(plan_counters(), before)
    (wait,) = [s for s in get_tracer().all_spans()
               if s[0] == "ingest_wait_preload"]
    if held == "the_load":
        assert wait[4] - wait[3] >= 0.04
        assert added["feed_plan_slack_us"] == 0
        assert added["feed_plan_load_join_us"] >= 40_000
    else:
        assert wait[4] - wait[3] < 1e-3
        assert added["feed_plan_slack_us"] >= 50_000
    assert added["feed_plan_us"] >= added["feed_plan_load_join_us"]


class PlanlessTable:
    """What PassPreloader asks of a table that offers no plan."""

    def __init__(self):
        self.keys, self.fed = [], None

    def begin_feed_pass(self):
        self.keys = []

    def add_keys(self, keys):
        self.keys.append(keys)

    def end_feed_pass(self):
        self.fed = np.unique(np.concatenate(self.keys))


@pytest.mark.parametrize("case", ["refused", "load_error", "no_plan",
                                  "redone"])
def test_only_an_accepted_plan_is_accounted_and_a_redone_one_once(case):
    """A refused admit_fn, a failed load and a table without a plan add
    nothing. A plan whose base is gone at the boundary adds the chain that
    made it, once: the redo's own spans, on the main thread under
    ingest_feed_pass, are the boundary's."""
    get_tracer().clear()
    table = (PlanlessTable() if case == "no_plan" else
             PassTable(TableConfig(embedx_dim=D, pass_capacity=64), seed=0))
    pre = PassPreloader(table)
    if case in ("refused", "load_error", "redone"):
        first = KeysDataset(np.arange(1, 30))
        pre.preload(first)
        assert pre.wait(first) is True
    before, redone = plan_counters(), stat_get("feed_plan_redone")
    gauges = (gauge_get("feed_plan_last_ms"),
              gauge_get("feed_plan_slack_last_ms"))
    if case == "refused":
        ds = KeysDataset(np.arange(15, 40))
        pre.preload(ds)
        assert pre.wait(ds, admit_fn=lambda _ds: False) is False
    elif case == "load_error":
        ds = KeysDataset(np.arange(15, 40), error=OSError("disk gone"))
        pre.preload(ds)
        with pytest.raises(OSError):
            pre.wait(ds)
    elif case == "no_plan":
        ds = KeysDataset(np.arange(15, 40))
        pre.preload(ds)
        assert pre.wait(ds) is True
        np.testing.assert_array_equal(table.fed, np.arange(15, 40))
    else:
        table.begin_pass()
        ds = KeysDataset(np.arange(15, 40))
        pre.preload(ds)                     # planned on the open pass
        table.end_pass()
        table.invalidate_residency()        # a save: the plan's base is gone
        assert pre.wait(ds) is True
    added = delta(plan_counters(), before)
    if case != "redone":
        assert not any(added.values()), added
        assert gauges == (gauge_get("feed_plan_last_ms"),
                          gauge_get("feed_plan_slack_last_ms"))
        return
    assert stat_get("feed_plan_redone") == redone + 1
    spans = get_tracer().all_spans()
    main = threading.get_ident()
    for counter, name in FEED_PLAN_COUNTERS:
        ahead = [s for s in spans[::-1] if s[0] == name and s[1] != main]
        assert added[counter] == whole_us(ahead[0]), counter
    # the boundary redid the index on the main thread, and no counter has it
    assert [s for s in spans if s[0] == "feed_route_index" and s[1] == main]


# ---- ISSUE 40: the plan is a delta on its base, folded under the load
FOLD_COUNTERS = ["feed_plan_fold_us", "feed_keys_folded",
                 "feed_plan_arrived_keys", "feed_plan_departed_keys",
                 "feed_index_shared", "feed_index_rebuilt"]


def fold_counters():
    return {c: stat_get(c) for c in FOLD_COUNTERS}


def wait_until(cond, seconds=30.0):
    end = time.monotonic() + seconds
    while not cond() and time.monotonic() < end:
        time.sleep(0.002)
    assert cond()


def test_chunks_are_folded_while_the_load_is_still_held():
    """A chunk registered before the load's join is folded before it: the
    fold's count of probed keys advances while wait_preload_done blocks,
    nothing is accounted until the install, and the install adds the
    plan's counts once."""
    get_tracer().clear()
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=64), seed=0)
    pre = PassPreloader(table)
    first = KeysDataset(np.arange(1, 30))
    pre.preload(first)
    assert pre.wait(first) is True          # no base: nothing was folded
    table.begin_pass()
    before = fold_counters()
    ds = HeldDataset(np.arange(1, 30))      # the same set: zero drift
    pre.preload(ds)                         # planned on the open pass
    fold = pre._ahead.fold
    wait_until(lambda: fold.folded == 29)
    pre._ahead.feed(np.arange(20, 29, dtype=np.uint64))   # a parsed block
    wait_until(lambda: fold.folded == 38)
    assert pre._ahead._thread.is_alive() and not ds.loaded.is_set()
    assert fold_counters() == before
    ds.loaded.set()
    table.end_pass()
    assert pre.wait(ds) is True
    added = delta(fold_counters(), before)
    folds = [s for s in get_tracer().all_spans() if s[0] == "feed_fold"]
    assert [s[2] for s in folds] == ["feed-fold"] * 2
    load_join, = [s for s in get_tracer().all_spans()[::-1]
                  if s[0] == "ingest_load_join"][:1]
    assert all(s[4] <= load_join[4] for s in folds)
    assert added == {"feed_plan_fold_us": sum(whole_us(s) for s in folds),
                     "feed_keys_folded": 38, "feed_plan_arrived_keys": 0,
                     "feed_plan_departed_keys": 0, "feed_index_shared": 1,
                     "feed_index_rebuilt": 0}
    assert table._rows._index is table._rows_base._index
    table.begin_pass()
    np.testing.assert_array_equal(
        table.lookup_ids(np.arange(1, 30, dtype=np.uint64)), np.arange(29))
    table.end_pass()


def test_the_prefetcher_is_fed_the_arrivals_alone_each_once(monkeypatch):
    """The fold hands the promote prefetcher the keys it found missing
    from the base, sorted unique and never twice, whatever repeats the
    chunks bring; the rows it stages are the store's own for those of
    them the store holds: what a diff of the whole key set would read."""
    from paddlebox_tpu.train import preload as preload_mod
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=256), seed=0)
    pre = PassPreloader(table)
    old, resident = np.arange(100, 140), np.arange(1, 60)
    for keys in (old, resident):            # `old` ends up in the store only
        ds = KeysDataset(keys)
        pre.preload(ds)
        assert pre.wait(ds) is True
        table.begin_pass()
        table.end_pass()
    fed = []
    real_feed = preload_mod.PromotePrefetcher.feed
    monkeypatch.setattr(preload_mod.PromotePrefetcher, "feed",
                        lambda self, keys: (fed.append(np.array(keys)),
                                            real_feed(self, keys))[1])

    class Blocks(KeysDataset):
        def preload_into_memory(self, add_keys_fn=None):
            for block in self.keys:
                add_keys_fn(block)

    back, new = np.arange(110, 130), np.arange(300, 320)
    ds = Blocks(np.empty(0))
    ds.keys = [np.concatenate([resident[:40], back[:12]]).astype(np.uint64),
               np.concatenate([back[5:], new, back[:3]]).astype(np.uint64),
               np.concatenate([new[::-1], resident[30:50]]).astype(np.uint64)]
    pre.preload(ds)
    assert pre.wait(ds) is True
    arrivals = np.concatenate([back, new]).astype(np.uint64)
    assert all((f[1:] > f[:-1]).all() for f in fed)
    got = np.concatenate(fed)
    assert got.size == np.unique(got).size
    np.testing.assert_array_equal(np.sort(got), arrivals)
    staged_keys, staged_rows = table._staged
    np.testing.assert_array_equal(staged_keys, back.astype(np.uint64))
    with table.store_lock:
        np.testing.assert_array_equal(staged_rows,
                                      table.store.lookup(staged_keys))
    before = stat_get("pass_rows_promote_prefetched")
    table.begin_pass()
    assert stat_get("pass_rows_promote_prefetched") - before == back.size
    table.end_pass()


def test_readers_feed_the_fold_while_the_stager_probes_the_base():
    """Eight threads register chunks at once while this one looks keys up
    through the base's index (probe-only, shared with the fold): every
    lookup answers and the plan is the one the sorted derivation gives."""
    import sys
    table = PassTable(TableConfig(embedx_dim=D, pass_capacity=1 << 15),
                      seed=0)
    pre = PassPreloader(table)
    rng = np.random.RandomState(40)
    resident = np.unique(rng.randint(1, 1 << 40, 20000).astype(np.uint64))
    first = KeysDataset(resident)
    pre.preload(first)
    assert pre.wait(first) is True
    table.begin_pass()
    base = table._rows
    nxt = np.concatenate([resident[::2], np.unique(
        rng.randint(1 << 41, 1 << 42, 6000).astype(np.uint64))])
    blocks = [rng.choice(nxt, 5000) for _ in range(63)] + [nxt]

    class Readers(HeldDataset):
        def preload_into_memory(self, add_keys_fn=None):
            self.threads = [threading.Thread(
                target=lambda part: [add_keys_fn(b) for b in part],
                args=(blocks[i::8],)) for i in range(8)]
            for t in self.threads:
                t.start()

    ds = Readers(np.empty(0))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pre.preload(ds)
        while any(t.is_alive() for t in ds.threads):
            np.testing.assert_array_equal(
                table.lookup_ids(resident[::3]), base.rows[::3])
        for t in ds.threads:
            t.join(30.0)
            assert not t.is_alive()
        ds.loaded.set()
        table.end_pass()
        assert pre.wait(ds) is True
    finally:
        sys.setswitchinterval(interval)
    want = base.succeed(np.unique(nxt))
    for f in ("keys", "rows", "holes", "top", "arrived", "freed", "dense"):
        np.testing.assert_array_equal(getattr(table._rows, f),
                                      getattr(want, f), f)
    assert table._rows_base is base and plan_threads() == []
