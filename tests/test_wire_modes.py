"""The train batch's wire, and the sharded runners' uid wire.

A BoxTrainer train batch has ONE wire. BoxTrainer._host_batch makes it on
the stager thread: ids, segments, ins_valid, labels [, dense, rank_offset,
aux_offset, labels_<task>] plus the host dedup of the batch's ids, uids[U]
over the push's unique-row domain (pass_table.push_domain), perm[K], inv[K]
and occ_uid[K] (the occurrence's slot in uids) per occurrence,
push_pos[capacity] under push_write=rebuild; an eval batch carries no push
leaf. Inside make_train_step _pull gathers the slab once a uid where the
wire has occ_uid (pull_sparse_unique) and by occurrence where it has not;
_sparse_push is the one consumer of the rest: push_sparse_rebuild where
push_pos is there, push_sparse_hostdedup (the row scatter) otherwise.

Contracts under test:

  * whatever the write and the chunking, the wire trains BIT-IDENTICALLY to
    the plain reference, push_sparse_dedup (device jnp.unique over the
    batch's ids, no host product): the content-addressed lazy-init randoms
    and the ascending-occurrence merge order make the push a pure function
    of (slab, ids, grads, prng) regardless of WHERE the dedup ran;
  * the exact leaf set, shapes and dtypes of host_batch's and
    _stack_batches_host's dicts;
  * predict_batches after a trained pass returns the same bits whichever
    chunking trained it;
  * the switches that selected the deleted wires fail loud;
  * sharded: only per-destination sorted uids stage (h2d_uid_wire,
    stage_push_dedup uid_only); the step derives the maps from the a2a'd
    bucket ids (push_sparse_uidwire) and composes with the 2-process
    host-plane bucket exchange; the sorted-uid contract holds on every
    sharded staging path; the full-product staging's rebuild pos maps
    survive the 2-process exchange."""

import contextlib
import unittest.mock as mock

import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.embedding.pass_table import push_domain
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.train import BoxTrainer

D = 4
NUM_SLOTS = 4
CAPACITY = 2048
WRITES = ("scatter", "rebuild")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("wire_modes_data")
    # small vocab → heavy key recurrence across batches: merge order,
    # first-occurrence reuse and the touched-row delta are exercised hard
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=2, lines_per_file=480, num_slots=NUM_SLOTS,
        vocab_per_slot=120, max_len=3, seed=11)
    feed = type(feed)(slots=feed.slots, batch_size=64)
    return files, feed


@contextlib.contextmanager
def push_write(mode):
    flags.set_flag("push_write", mode)
    try:
        yield
    finally:
        flags.set_flag("push_write", "auto")


@contextlib.contextmanager
def reference_push(on=True):
    """Test-only: while a step is staged and traced under this, the wire
    loses occ_uid, so _pull gathers by occurrence (pull_sparse over the
    batch's ids), and the two push calls of _sparse_push hand those ids
    (as the pull saw them) and the same grads to push_sparse_dedup: device
    jnp.unique, none of the host's dedup products read. Yields a dict
    whose "pushes" counts the substituted calls, so that a run can show it
    took this road; with on=False nothing is substituted and None is
    yielded."""
    if not on:
        yield None
        return
    import paddlebox_tpu.train.trainer as trainer_mod
    from paddlebox_tpu.embedding.optimizers import push_sparse_dedup
    seen = {"pushes": 0}
    pull = trainer_mod.pull_sparse
    host_batch = trainer_mod.BoxTrainer._host_batch

    def no_occ_uid(self, b, ids):
        out, n_u = host_batch(self, b, ids)
        out.pop("occ_uid", None)
        return out, n_u

    def spy_pull(slab, ids, layout):
        seen["ids"] = ids
        return pull(slab, ids, layout)

    def ref_hostdedup(slab, uids, perm, inv, grads, prng, layout, conf,
                      pulled_rows=None, **_kw):
        assert pulled_rows is None
        seen["pushes"] += 1
        return push_sparse_dedup(slab, seen["ids"], grads, prng, layout,
                                 conf)

    def ref_rebuild(slab, uids, pos, *rest, **kw):
        return ref_hostdedup(slab, uids, *rest, **kw)

    with mock.patch.object(trainer_mod.BoxTrainer, "_host_batch",
                           no_occ_uid), \
            mock.patch.object(trainer_mod, "pull_sparse", spy_pull), \
            mock.patch.object(trainer_mod, "push_sparse_hostdedup",
                              ref_hostdedup), \
            mock.patch.object(trainer_mod, "push_sparse_rebuild",
                              ref_rebuild):
        yield seen


def make_trainer(feed, scan_chunk=2):
    table = TableConfig(
        embedx_dim=D, pass_capacity=CAPACITY,
        optimizer=SparseOptimizerConfig(
            mf_create_thresholds=0.0, mf_initial_range=1e-3))
    model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                   hidden=(16,))
    return BoxTrainer(model, table, feed,
                      TrainerConfig(scan_chunk=scan_chunk), seed=0)


def train(tr, files, feed, passes):
    losses = []
    for _ in range(passes):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        losses.append(tr.train_pass(ds)["loss"])
        ds.release_memory()
    return losses


def run_mode(files, feed, mode, scan_chunk=2, passes=2, reference=False):
    """(losses, store keys, store values, dense params) of a trainer built
    and trained under push_write=mode; reference=True trains it through
    reference_push instead of the wire's own push."""
    with push_write(mode), reference_push(reference) as ref:
        tr = make_trainer(feed, scan_chunk)
        try:
            losses = train(tr, files, feed, passes)
            assert ref is None or ref["pushes"] > 0
            keys, vals = tr.table.store.state_items()
            order = np.argsort(keys)
            return losses, keys[order], vals[order], tr.params
        finally:
            tr.close()


def assert_identical(a, b):
    la, ka, va, pa = a
    lb, kb, vb, pb = b
    assert la == lb
    assert np.array_equal(ka, kb)
    assert np.array_equal(va, vb)
    import jax
    for xa, xb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


# ------------------------------------------- the wire against the reference
@pytest.fixture(scope="module")
def reference_runs(data):
    """One reference run a chunking, shared by the two writes."""
    files, feed = data
    runs = {}

    def get(scan_chunk):
        if scan_chunk not in runs:
            runs[scan_chunk] = run_mode(files, feed, "scatter", scan_chunk,
                                        reference=True)
        return runs[scan_chunk]
    return get


@pytest.mark.parametrize("scan_chunk", [1, 2])
@pytest.mark.parametrize("write", WRITES)
def test_full_wire_matches_reference_push(data, reference_runs, write,
                                          scan_chunk):
    """Two passes over recurring keys, embeddings created on the way
    (mf_initial_range > 0): losses, store rows and dense params of the
    host-dedup wire equal, bit for bit, those of a run whose push is
    push_sparse_dedup over the same ids and grads — per step (chunk 1)
    and through scan_steps (chunk 2)."""
    files, feed = data
    ref = reference_runs(scan_chunk)
    assert len(ref[1]) and np.isfinite(ref[0]).all()
    assert_identical(ref, run_mode(files, feed, write, scan_chunk))


def _leaf_specs(tree):
    return {k: (v.shape, v.dtype) for k, v in tree.items()}


@pytest.mark.parametrize("mode", ["train", "test_mode"])
@pytest.mark.parametrize("write", WRITES)
def test_wire_contract(data, write, mode):
    """The exact leaves of host_batch's dict and of _stack_batches_host's
    (a dict with a leading chunk axis, never a tuple): uids of length
    U = push_domain(...), perm/inv/occ_uid of K with ids == uids[occ_uid],
    push_pos only under rebuild, NO push leaf in test mode."""
    files, feed = data
    K, B = feed.key_capacity(), feed.batch_size
    i32 = np.dtype(np.int32)
    with push_write(write):
        tr = make_trainer(feed)
        try:
            tr.table.set_test_mode(mode == "test_mode")
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            tr.table.begin_feed_pass()
            ds.load_into_memory(add_keys_fn=tr.table.add_keys)
            tr.table.end_feed_pass()
            tr.table.begin_pass()
            batches = ds.split_batches(num_workers=1)[0][:3]
            ids = [tr.table.lookup_ids(b.keys, b.valid) for b in batches]
            want = {"ids": ((K,), i32),
                    "segments": ((K,), batches[0].segments.dtype),
                    "ins_valid": ((B,), np.dtype(bool)),
                    "labels": ((B,), batches[0].labels.dtype)}
            n_us = [np.unique(x).size for x in ids]
            if mode == "train":
                U1 = push_domain(n_us[0], K)
                U2 = push_domain(max(n_us[1:]), K, U1)
                want.update(uids=((U1,), i32), occ_uid=((K,), i32),
                            perm=((K,), i32), inv=((K,), i32))
                if write == "rebuild":
                    want["push_pos"] = ((CAPACITY,), i32)
            one = tr.host_batch(batches[0], ids[0])
            assert _leaf_specs(one) == want
            np.testing.assert_array_equal(one["ids"], ids[0])
            staged = tr._stack_batches_host(batches[1:])
            assert isinstance(staged, dict)
            if "uids" in want:
                want["uids"] = ((U2,), i32)
            assert _leaf_specs(staged) == {
                k: ((2,) + shp, dt) for k, (shp, dt) in want.items()}
            if mode == "train":
                assert U1 < K
                real = np.sort(one["uids"][:n_us[0]])
                np.testing.assert_array_equal(real, np.unique(ids[0]))
                assert (one["uids"][n_us[0]:] >= CAPACITY).all()
                np.testing.assert_array_equal(
                    one["uids"][one["occ_uid"]], ids[0])
                np.testing.assert_array_equal(
                    np.take_along_axis(staged["uids"], staged["occ_uid"],
                                       axis=1), np.stack(ids[1:]))
            tr.table.end_pass()
            tr.table.set_test_mode(False)
            ds.release_memory()
        finally:
            tr.close()


# --------------------------------------------------------- eval after train
def _train_and_predict(files, feed, scan_chunk, reference=False):
    with reference_push(reference) as ref:
        tr = make_trainer(feed, scan_chunk)
        try:
            train(tr, files, feed, 1)
            assert ref is None or ref["pushes"] > 0
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            ds.load_into_memory()
            return tr.predict_batches(ds)
        finally:
            tr.close()


@pytest.fixture(scope="module")
def reference_preds(data):
    files, feed = data
    return _train_and_predict(files, feed, 1, reference=True)


@pytest.mark.parametrize("scan_chunk", [1, 2, 4])
def test_eval_after_train_bit_equal(data, reference_preds, scan_chunk):
    """SetTestMode after one trained pass: eval batches stage no push
    product, create nothing, and predict_batches returns the bits that a
    per-step run on the reference push returns, whichever chunking
    trained the table."""
    files, feed = data
    preds, labels = _train_and_predict(files, feed, scan_chunk)
    assert preds.size and np.array_equal(labels, reference_preds[1])
    assert np.array_equal(preds, reference_preds[0])


# ------------------------------------------------------- what went, loudly
REMOVED_SWITCHES = [      # (name, is a flag; else a TrainerConfig field)
    ("h2d_lean", True), ("wire_delta_ids", True), ("h2d_stack_chunks", True),
    ("push_onehot_rows", True), ("sparse_chunk_sync", False),
    ("push_block_rows", True), ("push_blocked_pallas", True),
    ("use_pallas_push", True)]


@pytest.mark.parametrize("name,is_flag", REMOVED_SWITCHES,
                         ids=[n for n, _ in REMOVED_SWITCHES])
def test_removed_switches_fail_loud(name, is_flag):
    """The flags and the TrainerConfig field that selected the lean
    wires, the grouped transfer, the one-hot merge, the chunk-synchronous
    step, the blocked slab write and the Pallas push kernels are gone:
    setting one is an error, not a silent no-op."""
    if is_flag:
        with pytest.raises(KeyError, match=name):
            flags.set_flag(name, 1)
    else:
        with pytest.raises(TypeError, match=name):
            TrainerConfig(**{name: True})


def test_train_batch_without_perm_raises(data):
    """A train batch that reaches the step without the host dedup raises
    at trace time; the step never falls back to a device sort."""
    files, feed = data
    tr = make_trainer(feed)
    try:
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files[:1])
        tr.table.begin_feed_pass()
        ds.load_into_memory(add_keys_fn=tr.table.add_keys)
        tr.table.end_feed_pass()
        tr.table.begin_pass()
        b = ds.split_batches(num_workers=1)[0][0]
        batch = tr.device_batch(b, tr.table.lookup_ids(b.keys, b.valid))
        for k in ("perm", "inv", "uids", "occ_uid"):
            del batch[k]
        with pytest.raises(KeyError, match="host dedup"):
            tr.fns.step(tr.table.slab, tr.params, tr.opt_state, batch,
                        tr.table.next_prng())
        tr.table.end_pass()
        ds.release_memory()
    finally:
        tr.close()


@pytest.mark.parametrize("mode", ["log", "blocked"])
def test_push_write_log_deleted(data, mode):
    """The 'log' and 'blocked' writes are gone (no cell selected either):
    the flag value fails loud, naming the deleted write."""
    files, feed = data
    with pytest.raises(ValueError, match=f"'{mode}' was deleted"):
        run_mode(files, feed, mode, passes=1)


# ------------------------------------------------------------ unit tier
def test_push_sparse_uidwire_unit():
    """Direct kernel parity: device-derived maps (searchsorted inv,
    scatter-min first, scattered pos) against push_sparse_hostdedup /
    push_sparse_rebuild with host dedup products, scatter and rebuild
    writes, with and without pull-row reuse."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout
    from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                    push_sparse_rebuild,
                                                    push_sparse_uidwire)
    from paddlebox_tpu.embedding.pass_table import (dedup_ids,
                                                    dedup_uids_sorted,
                                                    pos_for_rebuild)

    rng = np.random.RandomState(3)
    cap, K = 256, 64
    layout = ValueLayout(D, "adagrad")
    conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                 mf_initial_range=1e-3)
    push = PushLayout(D)
    slab = rng.rand(cap, layout.width).astype(np.float32)
    ids = rng.randint(0, 40, K).astype(np.int32)
    ids[rng.rand(K) < 0.2] = cap - 1          # padding occurrences
    grads = rng.randn(K, push.width).astype(np.float32)
    grads[:, push.SHOW] = 1.0
    grads[ids == cap - 1] = 0.0               # padding rows all-zero
    prng = jax.random.PRNGKey(7)

    uids, perm, inv, _ = dedup_ids(ids, cap)
    pulled = jnp.asarray(slab[ids])
    host = push_sparse_hostdedup(jnp.asarray(slab), jnp.asarray(uids),
                                 jnp.asarray(perm), jnp.asarray(inv),
                                 jnp.asarray(grads), prng, layout, conf,
                                 pulled_rows=jnp.asarray(
                                     slab[np.minimum(uids, cap - 1)]))
    suids = dedup_uids_sorted(ids, cap)
    for pr in (pulled, None):
        wire = push_sparse_uidwire(jnp.asarray(slab), jnp.asarray(suids),
                                   jnp.asarray(ids), jnp.asarray(grads),
                                   prng, layout, conf, pulled_rows=pr)
        np.testing.assert_array_equal(np.asarray(host), np.asarray(wire))

    pos = pos_for_rebuild(uids, cap)
    host_rb = push_sparse_rebuild(jnp.asarray(slab), jnp.asarray(uids),
                                  jnp.asarray(pos), jnp.asarray(perm),
                                  jnp.asarray(inv), jnp.asarray(grads),
                                  prng, layout, conf)
    wire_rb = push_sparse_uidwire(jnp.asarray(slab), jnp.asarray(suids),
                                  jnp.asarray(ids), jnp.asarray(grads),
                                  prng, layout, conf, write="rebuild")
    np.testing.assert_array_equal(np.asarray(host_rb), np.asarray(wire_rb))


# -------------------------------------------------------------- sharded
def make_sharded_trainer(feed, seed=0):
    from paddlebox_tpu.parallel import ShardedBoxTrainer
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=8 * (1 << 9),
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))
    model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                   hidden=(16,))
    return ShardedBoxTrainer(model, table_cfg, feed,
                             TrainerConfig(dense_lr=3e-3), seed=seed)


def test_sharded_uid_wire_matches_full_staging(data):
    """The 8-shard trainer on the uid wire (per-destination sorted uids
    only; maps derived in the shard_map step from the a2a'd bucket ids)
    must train bit-identically to the full push_perm/inv staging."""
    files, feed = data
    states = {}
    for uid_only in (True, False):
        flags.set_flag("h2d_uid_wire", uid_only)
        try:
            trainer = make_sharded_trainer(feed, seed=4)
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            trainer.train_pass(ds)
            states[uid_only] = [st.state_items()
                                for st in trainer.table.stores]
            trainer.close()
        finally:
            flags.set_flag("h2d_uid_wire", True)
    for (k_u, v_u), (k_f, v_f) in zip(states[True], states[False]):
        np.testing.assert_array_equal(k_u, k_f)
        np.testing.assert_array_equal(v_u, v_f)


def test_two_virtual_process_uid_staging():
    """The uid wire composed with the host-plane bucket exchange: two
    VIRTUAL processes (mesh positions 0-3 / 4-7) each stage their owned
    destinations' uids through exchange_outgoing_buckets and must
    reproduce the single-process staging exactly — and the staged uids
    must drive push_sparse_uidwire to the same rows as the full host
    dedup products over the same incoming ids."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout
    from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                    push_sparse_uidwire)
    from paddlebox_tpu.embedding.pass_table import dedup_ids
    from paddlebox_tpu.parallel.sharded_table import stage_push_dedup

    P, KB, shard_cap = 8, 16, 128
    rng = np.random.RandomState(5)
    # [P(src), P(dest), KB] local-id buckets, trash-padded like bucketize
    buckets = np.full((P, P, KB), shard_cap - 1, np.int32)
    for s in range(P):
        for d in range(P):
            n = rng.randint(2, KB)
            buckets[s, d, :n] = rng.randint(0, shard_cap - 1, n)
    pool = concurrent.futures.ThreadPoolExecutor(2)

    single = stage_push_dedup(list(buckets), list(range(P)), P, shard_cap,
                              multiprocess=False, all_gather=None,
                              rebuild=False, pool=pool, uid_only=True)
    assert set(single) == {"push_uids"}

    # two virtual processes: precompute both payloads, fake the gather
    def payload_of(bl, positions):
        bl = np.ascontiguousarray(bl, np.int32)
        header = np.array([len(positions), P, KB] + list(positions),
                          np.int32)
        return np.concatenate([header, bl.ravel()])

    parts = [payload_of(buckets[0:4], [0, 1, 2, 3]),
             payload_of(buckets[4:8], [4, 5, 6, 7])]
    fake_gather = lambda payload: parts  # noqa: E731
    touched = {}

    def note(d, uids):
        touched.setdefault(d, []).append(uids)

    out = {}
    for lo, positions in ((0, [0, 1, 2, 3]), (4, [4, 5, 6, 7])):
        staged = stage_push_dedup(
            list(buckets[lo:lo + 4]), positions, P, shard_cap,
            multiprocess=True, all_gather=fake_gather, rebuild=False,
            pool=pool, note_touched=note, uid_only=True)
        for i, d in enumerate(positions):
            out[d] = staged["push_uids"][i]
    for d in range(P):
        np.testing.assert_array_equal(out[d], single["push_uids"][d],
                                      err_msg=f"dest {d}")
        assert d in touched  # uids host-known -> touched-row accounting

    # numeric tier: staged uids == full host products, row for row
    layout = ValueLayout(D, "adagrad")
    conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                 mf_initial_range=1e-3)
    push = PushLayout(D)
    d = 3
    incoming = np.concatenate([buckets[s][d] for s in range(P)])
    grads = rng.randn(incoming.size, push.width).astype(np.float32)
    grads[:, push.SHOW] = 1.0
    grads[incoming == shard_cap - 1] = 0.0
    slab = rng.rand(shard_cap, layout.width).astype(np.float32)
    prng = jax.random.PRNGKey(1)
    uids, perm, inv, _ = dedup_ids(incoming, shard_cap)
    host = push_sparse_hostdedup(
        jnp.asarray(slab), jnp.asarray(uids), jnp.asarray(perm),
        jnp.asarray(inv), jnp.asarray(grads), prng, layout, conf)
    wire = push_sparse_uidwire(
        jnp.asarray(slab), jnp.asarray(out[d]), jnp.asarray(incoming),
        jnp.asarray(grads), prng, layout, conf)
    np.testing.assert_array_equal(np.asarray(host), np.asarray(wire))
    pool.shutdown(wait=False)


def test_two_virtual_process_rebuild_staging():
    """The full-product staging under push_write=rebuild composed with the
    host-plane bucket exchange: two VIRTUAL processes (mesh positions 0-3
    / 4-7) stage their owned destinations' uids, perm, inv and pos maps
    and must reproduce the single-process staging exactly; push_sparse_
    rebuild over them writes the scatter oracle's rows bit for bit."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout
    from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                    push_sparse_rebuild)
    from paddlebox_tpu.parallel.sharded_table import stage_push_dedup

    P, KB, shard_cap = 8, 16, 128
    rng = np.random.RandomState(8)
    buckets = np.full((P, P, KB), shard_cap - 1, np.int32)
    for s in range(P):
        for d in range(P):
            n = rng.randint(2, KB)
            buckets[s, d, :n] = rng.randint(0, shard_cap - 1, n)
    leaves = ("push_uids", "push_perm", "push_inv", "push_pos")

    def payload_of(bl, positions):
        bl = np.ascontiguousarray(bl, np.int32)
        header = np.array([len(positions), P, KB] + list(positions),
                          np.int32)
        return np.concatenate([header, bl.ravel()])

    parts = [payload_of(buckets[0:4], [0, 1, 2, 3]),
             payload_of(buckets[4:8], [4, 5, 6, 7])]
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        single = stage_push_dedup(list(buckets), list(range(P)), P,
                                  shard_cap, multiprocess=False,
                                  all_gather=None, rebuild=True, pool=pool)
        assert set(single) == set(leaves)
        out = {}
        for lo, positions in ((0, [0, 1, 2, 3]), (4, [4, 5, 6, 7])):
            staged = stage_push_dedup(
                list(buckets[lo:lo + 4]), positions, P, shard_cap,
                multiprocess=True, all_gather=lambda payload: parts,
                rebuild=True, pool=pool)
            for i, d in enumerate(positions):
                out[d] = tuple(staged[k][i] for k in leaves)
    layout = ValueLayout(D, "adagrad")
    conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                 mf_initial_range=1e-3)
    push = PushLayout(D)
    for d in range(P):
        for k, got in zip(leaves, out[d]):
            np.testing.assert_array_equal(got, single[k][d],
                                          err_msg=f"{k} dest {d}")
        uids, perm, inv, pos = (jnp.asarray(x) for x in out[d])
        incoming = np.concatenate([buckets[s][d] for s in range(P)])
        grads = rng.randn(incoming.size, push.width).astype(np.float32)
        grads[:, push.SHOW] = 1.0
        grads[incoming == shard_cap - 1] = 0.0
        slab = jnp.asarray(rng.rand(shard_cap, layout.width)
                           .astype(np.float32))
        grads, prng = jnp.asarray(grads), jax.random.PRNGKey(d)
        oracle = push_sparse_hostdedup(slab, uids, perm, inv, grads, prng,
                                       layout, conf)
        got = push_sparse_rebuild(slab, uids, pos, perm, inv, grads, prng,
                                  layout, conf)
        np.testing.assert_array_equal(np.asarray(oracle), np.asarray(got),
                                      err_msg=f"dest {d}")


# ---------------------------------------------- uid sortedness contract

def _assert_strictly_ascending(uids, where):
    """The uid-wire contract: the host-staged vector is STRICTLY
    ascending over its full length — data ids sorted unique, the padding
    tail (pad_base+i) continuing past them. The device searchsorted
    silently mis-maps every occurrence on unsorted input (no error, just
    corrupt rows), so sortedness must hold on every staging path."""
    uids = np.asarray(uids)
    assert uids.ndim == 1 and uids.size, where
    d = np.diff(uids.astype(np.int64))
    assert (d > 0).all(), "%s: uid vector not strictly ascending " \
        "(first break at %d)" % (where, int(np.argmin(d > 0)))


def test_dedup_uids_sorted_contract_all_paths():
    """Round-10 satellite: assert the sorted-uid contract on every host
    staging path of the uid wire — the raw helper (whose native rt_dedup
    sibling returns hash-probe ORDER, so a refactor absorbing one into
    the other would corrupt silently) and the per-destination sharded
    staging."""
    from paddlebox_tpu.embedding.pass_table import (dedup_ids,
                                                    dedup_uids_sorted)

    rng = np.random.RandomState(7)
    # adversarial shapes: duplicates, full-range, single value, all-pad
    for ids in (rng.randint(0, 50, 256).astype(np.int32),
                np.arange(199, dtype=np.int32)[::-1].copy(),
                np.full(64, 3, np.int32),
                rng.randint(0, 2047, 512).astype(np.int32)):
        _assert_strictly_ascending(dedup_uids_sorted(ids, 2048),
                                   "dedup_uids_sorted")
    # the native rt_dedup fast path really is probe-ordered (the hazard
    # this contract guards): when its uids happen to differ from sorted
    # order, dedup_uids_sorted must still be sorted
    ids = rng.randint(0, 2000, 1024).astype(np.int32)
    _assert_strictly_ascending(dedup_uids_sorted(ids, 2048), "vs rt_dedup")
    uids_raw, _, _, _ = dedup_ids(ids, 2048)
    assert set(uids_raw.tolist()) == set(
        dedup_uids_sorted(ids, 2048).tolist())

    # per-destination sharded staging (single-process + 2-virtual-rank
    # p2p pre-wire dedup): every destination's staged vector is sorted
    import concurrent.futures

    from paddlebox_tpu.fleet.mesh_comm import MeshComm
    from paddlebox_tpu.parallel.sharded_table import (
        exchange_push_uids_p2p, stage_push_dedup)
    P, KB, shard_cap = 4, 32, 256
    buckets = np.full((P, P, KB), shard_cap - 1, np.int32)
    for s in range(P):
        for dd in range(P):
            n = rng.randint(2, KB)
            buckets[s, dd, :n] = rng.randint(0, shard_cap - 1, n)
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        single = stage_push_dedup(list(buckets), list(range(P)), P,
                                  shard_cap, multiprocess=False,
                                  all_gather=None, rebuild=False,
                                  pool=pool, uid_only=True)
        for dd, uids in enumerate(single["push_uids"]):
            _assert_strictly_ascending(uids, "sharded dest %d" % dd)

        meshes = [MeshComm(r, 2) for r in range(2)]
        eps = {r: ("127.0.0.1", m.port) for r, m in enumerate(meshes)}
        pos = {0: [0, 1], 1: [2, 3]}
        try:
            for m in meshes:
                m.connect(eps)
                m.positions_of = dict(pos)
            f = pool.submit(exchange_push_uids_p2p, buckets[2:4], [2, 3],
                            P, shard_cap, meshes[1])
            out0 = exchange_push_uids_p2p(buckets[0:2], [0, 1], P,
                                          shard_cap, meshes[0])
            out1 = f.result()
            for dd, uids in {**out0, **out1}.items():
                _assert_strictly_ascending(uids, "p2p uid dest %d" % dd)
                # p2p pre-wire dedup == single-process product
                np.testing.assert_array_equal(uids, single["push_uids"][dd])
        finally:
            for m in meshes:
                m.close()

def test_rt_dedup_sorted_native_matches_numpy_oracle():
    """Round-11 satellite: the native rt_dedup_sorted fast path (presence
    mark + radix sort over uniques) must return EXACTLY the numpy tier's
    product — sorted uniques + pad_base+i tail — on every accepted shape,
    and must DECLINE (numpy fallback, still correct) low-duplication
    shapes where it measured slower. Skips when the native lib is absent
    (the wrapper is then the numpy tier by construction)."""
    import unittest.mock as mock

    from paddlebox_tpu.embedding.pass_table import dedup_uids_sorted
    from paddlebox_tpu.native.build import get_lib

    lib = get_lib()
    if lib is None or not hasattr(lib, "rt_dedup_sorted"):
        pytest.skip("native lib with rt_dedup_sorted not available")

    def numpy_tier(ids, pad_base):
        with mock.patch("paddlebox_tpu.native.build.get_lib",
                        return_value=None):
            return dedup_uids_sorted(ids, pad_base)

    rng = np.random.RandomState(17)
    shapes = [
        (1024, 64),     # heavy duplication — the accepted regime
        (1024, 512),    # boundary: span ~ K/2, still accepted
        (1024, 600),    # declined (live span > K/2) — numpy fallback
        (64, 1),        # single unique value
        (256, 8),
    ]
    for K, space in shapes:
        ids = rng.randint(0, space, K).astype(np.int32)
        got = dedup_uids_sorted(ids, space)
        ref = numpy_tier(ids, space)
        np.testing.assert_array_equal(got, ref, err_msg=f"K={K} {space}")
        _assert_strictly_ascending(got, f"rt_dedup_sorted K={K} {space}")
    # round-13 engagement re-key (the PR-6 named follow-up): the WIRED
    # shape — pad_base = capacity >> K, ids clustered in a small working
    # set PLUS the trash id (capacity-1) from bucket padding. The old
    # 2*pad_base<=K predicate always declined here; the span predicate
    # engages (the trash id rides out-of-band) and the product must
    # still be the numpy oracle's, bit for bit.
    for K, ws, cap in [(2048, 400, 1 << 16), (1024, 64, 1 << 20),
                       (4096, 2000, 1 << 13), (256, 255, 1 << 8)]:
        ids = rng.randint(0, ws, K).astype(np.int32)
        ids[::7] = cap - 1          # the bucket-padding trash id
        got = dedup_uids_sorted(ids, cap)
        np.testing.assert_array_equal(got, numpy_tier(ids, cap),
                                      err_msg=f"wired K={K} ws={ws}")
        _assert_strictly_ascending(got, f"wired K={K} ws={ws}")
    # all-trash batch (a fully-padded bucket column)
    ids = np.full(128, (1 << 12) - 1, np.int32)
    np.testing.assert_array_equal(dedup_uids_sorted(ids, 1 << 12),
                                  numpy_tier(ids, 1 << 12))
    # clustered low WITHOUT trash (single-host uid-wire shape)
    ids = rng.randint(0, 100, 1024).astype(np.int32)
    np.testing.assert_array_equal(dedup_uids_sorted(ids, 1 << 16),
                                  numpy_tier(ids, 1 << 16))
    # out-of-contract ids (>= pad_base) on an otherwise-accepted shape:
    # the native tier must DECLINE (its presence table is exactly
    # pad_base bytes — marking past it is a heap overwrite) and the
    # wrapper degrade to the numpy tier's well-defined product
    ids = rng.randint(0, 64, 1024).astype(np.int32)
    ids[7] = 100  # would index 36 bytes past the presence table
    np.testing.assert_array_equal(dedup_uids_sorted(ids, 64),
                                  numpy_tier(ids, 64))
    # empty batch: no native call, trivially sorted-empty
    assert dedup_uids_sorted(np.empty(0, np.int32), 16).size == 0
