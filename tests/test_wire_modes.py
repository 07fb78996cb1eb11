"""H2D wire modes — the round-8 lean-wire push reunification.

Contract under test: every wire the trainer can stage a train batch on
must train BIT-IDENTICALLY to the full host-staged oracle (the
perm/inv/uids/first_idx wire), because the content-addressed lazy-init
randoms and the ascending-occurrence merge order make the push a pure
function of (slab, batch, prng) regardless of WHERE the dedup ran:

  * uid wire (h2d_lean + h2d_uid_wire, the default lean config): the
    sorted [K] uid vector ships; inv/first (and the rebuild pos) derive
    on device by searchsorted — push_sparse_uidwire
  * ids-only wire (h2d_uid_wire off): the round-5 tier — nothing ships,
    jnp.unique dedups in the step
  * delta wire (wire_delta_ids): uids ship as (int32 base, int16 deltas)
  * chunk-amortized: sparse_chunk_sync stages ONE uid vector per scan
    chunk ([C*K]) that serves every batch of the chunk
  * sharded: only per-destination uids stage (stage_push_dedup
    uid_only); the step derives the maps from the a2a'd bucket ids —
    composes with the 2-process host-plane bucket exchange

The motivation (wire bytes vs device-sort trade) is what bench.py's e2e
ladder measures."""

import dataclasses

import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.train import BoxTrainer

D = 4
NUM_SLOTS = 4


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


def run_mode(files, feed, mode, wire=None, scan_chunk=2, passes=2,
             chunk_sync=False):
    """wire: None = full host products | 'uid' | 'ids_only' | 'delta'."""
    flags.set_flag("push_write", mode)
    if wire is not None:
        flags.set_flag("h2d_lean", True)
        flags.set_flag("h2d_uid_wire", wire != "ids_only")
        flags.set_flag("wire_delta_ids", wire == "delta")
    try:
        table = TableConfig(
            embedx_dim=D, pass_capacity=2048,
            optimizer=SparseOptimizerConfig(
                mf_create_thresholds=0.0, mf_initial_range=1e-3))
        model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                       hidden=(16,))
        tr = BoxTrainer(model, table, feed, TrainerConfig(
            scan_chunk=scan_chunk, sparse_chunk_sync=chunk_sync), seed=0)
        losses = []
        for p in range(passes):
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files)
            losses.append(tr.train_pass(ds)["loss"])
            ds.release_memory()
        keys, vals = tr.table.store.state_items()
        order = np.argsort(keys)
        params = tr.params
        tr.close()
        return losses, keys[order], vals[order], params
    finally:
        flags.set_flag("push_write", "auto")
        flags.set_flag("h2d_lean", False)
        flags.set_flag("h2d_uid_wire", True)
        flags.set_flag("wire_delta_ids", False)


def assert_identical(a, b):
    la, ka, va, pa = a
    lb, kb, vb, pb = b
    assert la == lb
    assert np.array_equal(ka, kb)
    assert np.array_equal(va, vb)
    import jax
    for xa, xb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


# ------------------------------------------------------ single-host wires
def test_uid_wire_matches_host_dedup_chunked(data):
    """The reunified lean wire at scan_chunk>1 and multiple passes must be
    bit-identical to the full host-staged scatter oracle."""
    files, feed = data
    base = run_mode(files, feed, "scatter")
    uid = run_mode(files, feed, "scatter", wire="uid")
    assert_identical(base, uid)


def test_uid_wire_rebuild_matches_host_rebuild(data):
    """push_write=rebuild under the uid wire (pos derived ON DEVICE by an
    int32 scatter) vs the host-staged [capacity] pos map."""
    files, feed = data
    base = run_mode(files, feed, "rebuild", passes=1)
    uid = run_mode(files, feed, "rebuild", wire="uid", passes=1)
    assert_identical(base, uid)


def test_delta_wire_matches(data):
    """wire_delta_ids: (base, int16 delta)-coded uids decode on device to
    the same sorted vector — identical training, 2 bytes/key less wire."""
    files, feed = data
    base = run_mode(files, feed, "scatter", passes=1)
    delta = run_mode(files, feed, "scatter", wire="delta", passes=1)
    assert_identical(base, delta)


def test_ids_only_lean_matches_host_dedup(data):
    """The round-5 ids-only wire (h2d_uid_wire off): device-side
    jnp.unique dedup with the minimal wire — the content-addressed
    lazy-init randoms make created rows independent of WHERE the dedup
    ran."""
    files, feed = data
    base = run_mode(files, feed, "scatter", passes=1)
    lean = run_mode(files, feed, "auto", wire="ids_only", passes=1)
    assert_identical(base, lean)


def test_ids_only_lean_rejects_host_map_modes(data):
    files, feed = data
    with pytest.raises(ValueError, match="h2d_lean"):
        run_mode(files, feed, "rebuild", wire="ids_only", passes=1)


def test_push_write_log_deleted(data):
    """The round-5 'log' mode is gone (verdict item 8): the flag value
    fails loud with a pointer to the retained findings."""
    files, feed = data
    with pytest.raises(ValueError, match="round 8"):
        run_mode(files, feed, "log", passes=1)


def test_grouped_h2d_matches_per_chunk(data):
    """h2d_stack_chunks>1 (round-5 verdict item 4): G chunks sharing one
    transfer per leaf — with device-side slicing back to per-chunk views
    — must be bit-identical to per-chunk transfers, on the full AND the
    uid wire."""
    files, feed = data
    for wire in (None, "uid"):
        base = run_mode(files, feed, "scatter", wire=wire)
        flags.set_flag("h2d_stack_chunks", 4)
        try:
            grouped = run_mode(files, feed, "scatter", wire=wire)
        finally:
            flags.set_flag("h2d_stack_chunks", 1)
        assert_identical(base, grouped)


# ------------------------------------------------- chunk-amortized dedup
def test_chunk_sync_uid_wire_matches(data):
    """sparse_chunk_sync + uid wire: ONE sorted [C*K] uid vector per scan
    chunk serves every batch (the chunk-amortized dedup) — bit-identical
    to the chunk-sync path with full host-staged cpush products."""
    files, feed = data
    base = run_mode(files, feed, "scatter", chunk_sync=True)
    uid = run_mode(files, feed, "scatter", wire="uid", chunk_sync=True)
    assert_identical(base, uid)


def test_chunk_sync_delta_wire_matches(data):
    files, feed = data
    base = run_mode(files, feed, "scatter", chunk_sync=True, passes=1)
    delta = run_mode(files, feed, "scatter", wire="delta", chunk_sync=True,
                     passes=1)
    assert_identical(base, delta)


# ------------------------------------------------------------- test_mode
def test_uid_wire_test_mode(data):
    """SetTestMode under the uid wire: eval batches stage no push
    products on ANY wire (no creation, no write-back), and a uid-wire-
    trained table serves bit-identical predictions to the host-wire
    oracle."""
    files, feed = data

    def train_and_predict(wire):
        if wire is not None:
            flags.set_flag("h2d_lean", True)
        try:
            table = TableConfig(
                embedx_dim=D, pass_capacity=2048,
                optimizer=SparseOptimizerConfig(
                    mf_create_thresholds=0.0, mf_initial_range=1e-3))
            model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                           hidden=(16,))
            tr = BoxTrainer(model, table, feed,
                            TrainerConfig(scan_chunk=2), seed=0)
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files)
            tr.train_pass(ds)
            ds.release_memory()
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            tr.table.begin_feed_pass()
            ds.load_into_memory(add_keys_fn=tr.table.add_keys)
            tr.table.end_feed_pass()
            preds, labels = tr.predict_batches(ds)
            tr.close()
            return preds, labels
        finally:
            flags.set_flag("h2d_lean", False)

    p_base, l_base = train_and_predict(None)
    p_uid, l_uid = train_and_predict("uid")
    assert np.array_equal(l_base, l_uid)
    assert np.array_equal(p_base, p_uid)


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
                                                    first_occurrence_idx,
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
    first = first_occurrence_idx(perm, inv)
    pulled = jnp.asarray(slab[ids])
    host = push_sparse_hostdedup(jnp.asarray(slab), jnp.asarray(uids),
                                 jnp.asarray(perm), jnp.asarray(inv),
                                 jnp.asarray(grads), prng, layout, conf,
                                 pulled_rows=pulled,
                                 first_idx=jnp.asarray(first))
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


def test_delta_encode_decode_unit():
    """Host coding invariants: exact round trip, padding recode to
    in-range ids stays unique/nondecreasing, oversize gaps fail loud."""
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.optimizers import decode_delta_uids
    from paddlebox_tpu.embedding.pass_table import (dedup_uids_sorted,
                                                    delta_encode_uids)

    cap = 1 << 14
    ids = np.array([5, 9, 5, 100, 2, cap - 1, cap - 1, 9], np.int32)
    uids = dedup_uids_sorted(ids, cap)
    assert np.all(np.diff(uids.astype(np.int64)) > 0)
    base, d16, cut = delta_encode_uids(uids, cap)
    assert d16.dtype == np.int16 and d16[0] == 0
    dec = np.asarray(decode_delta_uids(jnp.asarray(base),
                                       jnp.asarray(d16),
                                       jnp.asarray(cut), cap))
    # trash id (cap-1) present -> exact round trip incl. padding tail
    np.testing.assert_array_equal(dec, uids)
    # the data region is exempt from the trash jump: gaps beyond int16
    # only count BELOW the trash id, so this shape still encodes
    assert cut == 4

    # no trash id in the batch -> the tail decodes to [trash, padding...]
    # (trash maps no occurrence; only its own bits can be written back)
    ids2 = np.array([5, 9, 5, 2], np.int32)
    uids2 = dedup_uids_sorted(ids2, cap)
    base2, d2, cut2 = delta_encode_uids(uids2, cap)
    dec2 = np.asarray(decode_delta_uids(jnp.asarray(base2),
                                        jnp.asarray(d2),
                                        jnp.asarray(cut2), cap))
    np.testing.assert_array_equal(dec2[:3], [2, 5, 9])
    assert dec2[3] == cap - 1 and np.all(np.diff(dec2) > 0)

    with pytest.raises(ValueError, match="int16"):
        delta_encode_uids(np.array([0, 1 << 20], np.int32), 1 << 21)


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
    from paddlebox_tpu.embedding.pass_table import (dedup_ids,
                                                    first_occurrence_idx)
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


def test_dedup_uids_sorted_contract_all_paths(data):
    """Round-10 satellite: assert the sorted-uid contract on EVERY host
    staging path — the raw helper (whose native rt_dedup sibling returns
    hash-probe ORDER, so a refactor absorbing one into the other would
    corrupt silently), the single-host batch wire, the chunk-amortized
    chunk-sync wire, and the per-destination sharded staging."""
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

    # single-host batch wire: host_batch stages out["uids"] under h2d_lean
    files, feed = data
    flags.set_flag("h2d_lean", True)
    try:
        table = TableConfig(
            embedx_dim=D, pass_capacity=2048,
            optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                            mf_initial_range=1e-3))
        model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                       hidden=(16,))
        tr = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2),
                        seed=0)
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files[:1])
        tr.table.begin_feed_pass()
        ds.load_into_memory(add_keys_fn=tr.table.add_keys)
        tr.table.end_feed_pass()
        tr.table.begin_pass()
        batches = ds.split_batches(num_workers=1)[0]
        for b in batches[:3]:
            staged = tr.host_batch(b, tr.table.lookup_ids(b.keys, b.valid))
            _assert_strictly_ascending(staged["uids"], "host_batch uid wire")
        # chunk-amortized wire: ONE [C*K] vector per scan chunk
        tr.sparse_chunk_sync = True
        _, cpush = tr._stack_batches_host(batches[:2])
        _assert_strictly_ascending(cpush["uids"], "chunk-sync cpush")
        tr.sparse_chunk_sync = False
        tr.table.end_pass()
        tr.close()
    finally:
        flags.set_flag("h2d_lean", False)

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
