"""A pass's first scan chunk staged under the pass before it: once pass
N+1's feed plan is done, the feed-ahead thread shuffles and splits pass
N+1's examples and stages its first chunk against the plan's map
(BoxTrainer.stage_ahead, PassTable.lookup_in); pass N+1 dispatches it
first. It must train every bit as a run that stages nothing ahead."""

import threading

import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.obs.tracer import get_tracer
from paddlebox_tpu.parallel.mesh import device_mesh_1d
from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer
from paddlebox_tpu.train.preload import PassPreloader, run_preloaded_passes
from paddlebox_tpu.train.trainer import BoxTrainer
from paddlebox_tpu.utils.stats import stat_get

D, NUM_SLOTS = 4, 4
PASSES = 4
SECONDS = 120.0     # each test's own limit
# the two slab writes: a staged chunk carries push_pos under 'rebuild',
# the write 'auto' picks on the chip at the towers' shapes
WRITES = pytest.mark.parametrize("write", ["scatter", "rebuild"])


def within(fn, seconds=SECONDS):
    """fn() on a thread of its own, failed where it outlasts ``seconds``;
    what it returns or raises comes back here."""
    out = {}

    def body():
        try:
            out["value"] = fn()
        except BaseException as e:
            out["error"] = e

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), "did not finish within %.0f s" % seconds
    if "error" in out:
        raise out["error"]
    return out["value"]


def table_cfg():
    return TableConfig(
        embedx_dim=D, pass_capacity=1 << 13,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """400 examples of 32 a batch: three scan chunks of four, then one
    single step. A wide vocabulary, so that many rows are touched by one
    batch alone."""
    files, feed = write_synthetic_ctr_files(
        str(tmp_path_factory.mktemp("stage_ahead")), num_files=2,
        lines_per_file=200, num_slots=NUM_SLOTS, vocab_per_slot=1000,
        max_len=3, seed=45)
    return files, type(feed)(slots=feed.slots, batch_size=32)


def trainer(feed):
    return BoxTrainer(
        CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D), hidden=(16,)),
        table_cfg(), feed, TrainerConfig(dense_lr=0.01, scan_chunk=4),
        seed=0)


def datasets(files, feed, n=PASSES):
    out = []
    for _ in range(n):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        out.append(ds)
    return out


class WrittenBack:
    """The (keys, slab rows) each end_pass writes back, a list a pass;
    take() hands them over and starts afresh."""

    def __init__(self, monkeypatch):
        self.passes = []
        write_back = PassTable._write_back
        end_pass = PassTable._end_pass

        def spy_end(table):
            self.passes.append(([], []))
            end_pass(table)

        def spy_write(table, keys, idx):
            self.passes[-1][0].append(np.array(keys))
            self.passes[-1][1].append(np.array(idx))
            write_back(table, keys, idx)

        monkeypatch.setattr(PassTable, "_end_pass", spy_end)
        monkeypatch.setattr(PassTable, "_write_back", spy_write)

    def take(self):
        got, self.passes = self.passes, []
        return [(np.sort(np.concatenate(keys)),
                 set(np.concatenate(idx).tolist())) for keys, idx in got]


def state(tr):
    keys, vals = tr.table.store.state_items()
    order = np.argsort(keys)
    return keys[order], vals[order], np.asarray(tr.table.slab)


def one_by_one(files, feed, after=None):
    tr = trainer(feed)
    try:
        losses = []
        for k, ds in enumerate(datasets(files, feed)):
            losses.append(tr.train_pass(ds)["loss"])
            if after is not None:
                after(tr, k)
        return losses, state(tr)
    finally:
        tr.close()


def preloaded(files, feed, after=None):
    """(losses, state, counters' deltas, scan_steps compiles) of the
    passes through run_preloaded_passes."""
    tr = trainer(feed)
    names = ("stage_ahead_chunks", "stage_ahead_dropped")
    before = {n: stat_get(n) for n in names}
    try:
        stats = run_preloaded_passes(
            tr, datasets(files, feed),
            after_pass=None if after is None else (
                lambda k, _s: after(tr, k)))
        counts = {n: stat_get(n) - before[n] for n in names}
        return ([s["loss"] for s in stats], state(tr), counts,
                tr.fns.scan_steps._entry.compiles)
    finally:
        tr.close()


def assert_same(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@WRITES
def test_preloaded_passes_train_as_the_same_passes_one_by_one(data, write):
    """Four passes, shuffled, through run_preloaded_passes (every first
    chunk staged ahead, against the plan, on the feed-ahead thread) leave
    the losses, the slab and the host store of four train_pass calls to
    the last bit: the seeds are drawn in pass order."""
    flags.set_flag("push_write", write)
    files, feed = data
    want_losses, want = within(lambda: one_by_one(files, feed))
    losses, got, counts, _ = within(lambda: preloaded(files, feed))
    assert losses == want_losses
    assert_same(got, want)
    assert counts == {"stage_ahead_chunks": PASSES, "stage_ahead_dropped": 0}


def invalidate_after_pass_1(tr, k):
    if k == 1:
        tr.table.invalidate_residency()


@WRITES
def test_a_save_between_passes_drops_the_staged_chunk_and_stages_it_again(
        data, write):
    """invalidate_residency (what a save does) after pass 1 takes away the
    base pass 2's plan was made on: the boundary redoes the assignment, the
    chunk staged against the plan is dropped and staged again as any
    chunk, and the passes still train as one by one."""
    flags.set_flag("push_write", write)
    files, feed = data
    want_losses, want = within(
        lambda: one_by_one(files, feed, after=invalidate_after_pass_1))
    losses, got, counts, _ = within(
        lambda: preloaded(files, feed, after=invalidate_after_pass_1))
    assert losses == want_losses
    assert_same(got, want)
    assert counts == {"stage_ahead_chunks": PASSES - 1,
                      "stage_ahead_dropped": 1}


@WRITES
def test_the_rows_written_back_include_the_first_chunk_s(data, monkeypatch,
                                                          write):
    """The staged chunk was looked up before its pass began, marking
    nothing; the pass marks its rows when it takes it, so end_pass writes
    back the rows it wrote back one by one, the first chunk's among
    them."""
    flags.set_flag("push_write", write)
    files, feed = data
    looked_up = []
    lookup_in = PassTable.lookup_in

    def spy(table, plan, keys, valid=None):
        ids = lookup_in(table, plan, keys, valid)
        looked_up.append((plan.rows, ids))
        return ids

    monkeypatch.setattr(PassTable, "lookup_in", spy)
    written = WrittenBack(monkeypatch)
    within(lambda: one_by_one(files, feed))
    seq = written.take()
    within(lambda: preloaded(files, feed))
    pipe = written.take()
    assert len(seq) == len(pipe) == PASSES
    for (got, _), (want, _) in zip(pipe, seq):
        np.testing.assert_array_equal(got, want)
    # four batches a pass were looked up ahead, in that pass's plan
    plans = list(dict.fromkeys(rows for rows, _ in looked_up))
    assert len(plans) == PASSES and len(looked_up) == 4 * PASSES
    padding = table_cfg().pass_capacity - 1
    for (_, rows_written), rows in zip(pipe, plans):
        first = set(np.concatenate(
            [ids.ravel() for r, ids in looked_up if r is rows]).tolist())
        first.discard(padding)
        assert first and first <= rows_written


def test_an_error_staging_ahead_surfaces_at_the_consuming_pass(data,
                                                                monkeypatch):
    """What the stage ahead raises is raised by the train_pass that
    takes the chunk, not by the pass it ran under; the preloader then
    takes a fresh preload, and the next pass trains."""
    files, feed = data
    monkeypatch.setattr(PassTable, "lookup_in", lambda *a, **k: (
        _ for _ in ()).throw(KeyError("a key the plan lacks")))
    tr = trainer(feed)
    pre = PassPreloader(tr.table)
    ds1, ds2 = datasets(files, feed, 2)

    def first_pass():
        ahead = tr.stage_ahead(ds1)
        pre.preload(ds1, stage=ahead)
        assert pre.wait(ds1) is True
        with pytest.raises(KeyError, match="a key the plan lacks"):
            tr.train_pass(ds1, preloaded=True, ahead=ahead)
        assert ahead.err is not None

    try:
        within(first_pass)
        tr.table.end_pass()             # the failed pass, closed
        monkeypatch.undo()
        taken = stat_get("stage_ahead_chunks")

        def second_pass():
            ahead = tr.stage_ahead(ds2)
            pre.preload(ds2, stage=ahead)   # not "already in flight"
            assert pre.wait(ds2) is True
            return tr.train_pass(ds2, preloaded=True, ahead=ahead)

        assert np.isfinite(within(second_pass)["loss"])
        assert stat_get("stage_ahead_chunks") - taken == 1
    finally:
        tr.close()


@WRITES
def test_no_second_scan_steps_compile_across_the_passes(data, write):
    """The chunk staged ahead has the wire, the shapes and the push
    domain of any chunk: scan_steps compiles in the first pass alone (once
    a push-domain bucket the pass's chunks reach), as one by one, and
    never after it, though the next pass's first chunk is staged (and its
    domain marked) while the chunks of the pass before still are."""
    flags.set_flag("push_write", write)
    files, feed = data

    def counted(into):
        return lambda tr, _k: into.append(tr.fns.scan_steps._entry.compiles)

    seen, want = [], []
    *_, compiles = within(lambda: preloaded(files, feed, after=counted(seen)))
    within(lambda: one_by_one(files, feed, after=counted(want)))
    assert seen == [compiles] * PASSES == want


def test_a_push_write_change_between_passes_drops_the_staged_chunk(data):
    """A chunk staged under a scatter pass carries no push_pos: where the
    push_write flag turns to 'rebuild' before the pass that takes it, that
    pass drops it and stages its first chunk as any other, and trains as
    the same pass with nothing staged ahead."""
    files, feed = data
    flags.set_flag("push_write", "scatter")
    tr, plain = trainer(feed), trainer(feed)
    ds1, ds2 = datasets(files, feed, 2)
    fresh1, fresh2 = datasets(files, feed, 2)
    pre = PassPreloader(tr.table)
    names = ("stage_ahead_chunks", "stage_ahead_dropped")

    def staged_then_flipped():
        tr.train_pass(ds1)
        ahead = tr.stage_ahead(ds2)
        pre.preload(ds2, stage=ahead)
        assert pre.wait(ds2) is True
        ahead.done.wait()
        assert ahead.push_write == "scatter" and ahead.host is not None
        assert "push_pos" not in ahead.host
        flags.set_flag("push_write", "rebuild")
        before = {n: stat_get(n) for n in names}
        loss = tr.train_pass(ds2, preloaded=True, ahead=ahead)["loss"]
        return loss, {n: stat_get(n) - before[n] for n in names}

    def unstaged():
        flags.set_flag("push_write", "scatter")
        plain.train_pass(fresh1)
        flags.set_flag("push_write", "rebuild")
        return plain.train_pass(fresh2)["loss"]

    try:
        loss, counts = within(staged_then_flipped)
        assert tr._push_write == "rebuild"
        assert counts == {"stage_ahead_chunks": 0, "stage_ahead_dropped": 1}
        assert loss == within(unstaged)
        assert_same(state(tr), state(plain))
    finally:
        tr.close()
        plain.close()


def test_a_table_without_a_plan_stages_nothing_ahead(data):
    """ShardedPassTable offers no plan: run_preloaded_passes makes no
    stage ahead, and a stage handed to a preloader over such a table is
    skipped, so the pass shuffles with its seed and stages every chunk
    itself."""
    files, feed = data
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)
    sharded = ShardedBoxTrainer(CtrDnn(spec, hidden=(16,)), table_cfg(),
                                feed, TrainerConfig(dense_lr=0.01),
                                mesh=device_mesh_1d(8), seed=0)
    taken = stat_get("stage_ahead_chunks")
    get_tracer().clear()
    try:
        stats = within(lambda: run_preloaded_passes(
            sharded, datasets(files, feed, 2)))
    finally:
        sharded.close()
    assert all(np.isfinite(s["loss"]) for s in stats)
    assert stat_get("stage_ahead_chunks") == taken
    assert not [s for s in get_tracer().all_spans() if s[0] == "stage_ahead"]

    tr, plain = trainer(feed), trainer(feed)
    ds, fresh = datasets(files, feed, 2)
    ahead = tr.stage_ahead(ds)
    pre = PassPreloader(sharded.table)

    def skipped_pass():
        pre.preload(ds, stage=ahead)
        assert ahead.split.is_set() and ahead.done.is_set()
        assert ahead.rows is None and ahead.batches is None
        pre.wait(ds)                    # loads ds, on the sharded table
        tr.table.begin_feed_pass()
        tr.table.add_keys(ds.all_keys())
        tr.table.end_feed_pass()
        return tr.train_pass(ds, preloaded=True, ahead=ahead)["loss"]

    try:
        taken = stat_get("stage_ahead_chunks")
        loss = within(skipped_pass)
        # the first seed of a fresh trainer, drawn by stage_ahead there
        assert loss == within(lambda: plain.train_pass(fresh)["loss"])
        assert stat_get("stage_ahead_chunks") == taken
    finally:
        tr.close()
        plain.close()
