"""Sparse table numeric parity vs a literal NumPy oracle of the reference
optimizer semantics (optimizer.cuh.h:31-145 adagrad, :148-330 adam), plus
pass-lifecycle and host-store behavior (mirrors ctr_accessor_test.cc /
sparse_sgd_rule_test.cc roles)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config.configs import SparseOptimizerConfig, TableConfig
from paddlebox_tpu.embedding import PassTable, HostEmbeddingStore
from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout
from paddlebox_tpu.embedding.optimizers import apply_push

D = 4


def oracle_adagrad_row(row, grad, conf, layout):
    """Literal dy_mf_update_value for SparseAdagradOptimizer."""
    row = row.astype(np.float64).copy()
    push = PushLayout(layout.embedx_dim)
    g_show = grad[push.SHOW]
    g_click = grad[push.CLICK]
    if g_show <= 0:
        return row.astype(np.float32)
    row[acc.SLOT] = grad[push.SLOT]
    row[acc.SHOW] += g_show
    row[acc.CLICK] += g_click
    row[acc.DELTA_SCORE] += (conf.nonclk_coeff * (g_show - g_click)
                             + conf.clk_coeff * g_click)
    row[acc.UNSEEN_DAYS] = 0.0

    def update_value_work(w, g2sum, g, scale, lr):
        add_g2sum = 0.0
        ratio = lr * np.sqrt(conf.mf_initial_g2sum /
                             (conf.mf_initial_g2sum + g2sum))
        for i in range(len(w)):
            scaled = g[i] / scale
            w[i] += scaled * ratio
            w[i] = np.clip(w[i], conf.mf_min_bound, conf.mf_max_bound)
            add_g2sum += scaled * scaled
        return g2sum + add_g2sum / len(w)

    slot = row[acc.SLOT]
    lr = (conf.mf_learning_rate if slot == conf.nodeid_slot
          else conf.feature_learning_rate)
    w = [row[acc.EMBED_W]]
    row[layout.embed_state] = update_value_work(
        w, row[layout.embed_state], [grad[push.EMBED_G]], g_show, lr)
    row[acc.EMBED_W] = w[0]

    score = (conf.nonclk_coeff * (row[acc.SHOW] - row[acc.CLICK])
             + conf.clk_coeff * row[acc.CLICK])
    if row[acc.MF_SIZE] == 0:
        if conf.mf_create_thresholds <= score:
            row[acc.MF_SIZE] = layout.embedx_dim
            # rng: with mf_initial_range=0 creation is deterministically zero
            row[layout.embedx_w:layout.embedx_w + layout.embedx_dim] = 0.0
    else:
        xw = list(row[layout.embedx_w:layout.embedx_w + layout.embedx_dim])
        row[layout.embedx_state] = update_value_work(
            xw, row[layout.embedx_state],
            grad[push.embedx_g:push.embedx_g + layout.embedx_dim],
            g_show, conf.mf_learning_rate)
        row[layout.embedx_w:layout.embedx_w + layout.embedx_dim] = xw
    return row.astype(np.float32)


@pytest.fixture
def conf():
    return SparseOptimizerConfig(mf_initial_range=0.0)


@pytest.fixture
def layout():
    return ValueLayout(D, "adagrad")


def test_adagrad_parity_vs_oracle(conf, layout):
    rng = np.random.RandomState(1)
    n = 64
    push = PushLayout(D)
    values = np.zeros((n, layout.width), dtype=np.float32)
    values[:, acc.EMBED_W] = rng.randn(n) * 0.1
    values[:, layout.embed_state] = rng.rand(n)
    values[:, acc.SHOW] = rng.randint(0, 30, n)
    values[:, acc.CLICK] = rng.randint(0, 3, n)
    # half the rows already have mf created
    values[:n // 2, acc.MF_SIZE] = D
    values[:n // 2, layout.embedx_w:layout.embedx_w + D] = rng.randn(n // 2, D) * 0.1
    values[:n // 2, layout.embedx_state] = rng.rand(n // 2)

    grads = np.zeros((n, push.width), dtype=np.float32)
    grads[:, push.SLOT] = rng.randint(1, 10, n)
    grads[:, push.SHOW] = rng.randint(0, 4, n)  # some zero-show (padding) rows
    grads[:, push.CLICK] = np.minimum(grads[:, push.SHOW],
                                      rng.randint(0, 2, n))
    grads[:, push.EMBED_G] = rng.randn(n).astype(np.float32)
    grads[:, push.embedx_g:] = rng.randn(n, D).astype(np.float32)

    got = np.asarray(apply_push(jnp.asarray(values), jnp.asarray(grads),
                                jax.random.PRNGKey(0), layout, conf))
    want = np.stack([oracle_adagrad_row(values[i], grads[i], conf, layout)
                     for i in range(n)])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_zero_show_rows_untouched(conf, layout):
    values = np.random.RandomState(0).randn(8, layout.width).astype(np.float32)
    grads = np.zeros((8, PushLayout(D).width), dtype=np.float32)
    got = np.asarray(apply_push(jnp.asarray(values), jnp.asarray(grads),
                                jax.random.PRNGKey(0), layout, conf))
    np.testing.assert_array_equal(got, values)


def test_lazy_mf_creation_range():
    conf = SparseOptimizerConfig(mf_initial_range=0.01, mf_create_thresholds=1.0)
    layout = ValueLayout(D, "adagrad")
    push = PushLayout(D)
    values = np.zeros((4, layout.width), dtype=np.float32)
    grads = np.zeros((4, push.width), dtype=np.float32)
    grads[:, push.SHOW] = 5.0
    grads[:, push.CLICK] = 2.0  # score = 0.1*3 + 2 = 2.3 >= 1.0 → create
    got = np.asarray(apply_push(jnp.asarray(values), jnp.asarray(grads),
                                jax.random.PRNGKey(3), layout, conf))
    assert (got[:, acc.MF_SIZE] == D).all()
    xw = got[:, layout.embedx_w:layout.embedx_w + D]
    assert (xw >= 0).all() and (xw < 0.01).all()
    assert np.abs(xw).sum() > 0  # actually randomized


def test_adam_step_moves_and_bounds():
    conf = SparseOptimizerConfig(optimizer="adam", mf_initial_range=0.0)
    layout = ValueLayout(D, "adam")
    push = PushLayout(D)
    values = layout.new_rows(2, np.random.RandomState(0), conf)
    values[:, acc.MF_SIZE] = D
    grads = np.zeros((2, push.width), dtype=np.float32)
    grads[:, push.SHOW] = 1.0
    grads[:, push.EMBED_G] = np.array([1.0, -1.0])
    grads[:, push.embedx_g:] = 0.5
    got = np.asarray(apply_push(jnp.asarray(values), jnp.asarray(grads),
                                jax.random.PRNGKey(0), layout, conf))
    # first adam step: m=(1-b1)g, v=(1-b2)g^2, ratio=lr*sqrt(1-b2p)/(1-b1p)
    # with b1p=b1, b2p=b2 → step ≈ lr * g/|g| ≈ ±lr
    assert got[0, acc.EMBED_W] > 0.04
    assert got[1, acc.EMBED_W] < -0.04
    es = layout.embed_state
    np.testing.assert_allclose(got[:, es + 2], 0.9 ** 2, rtol=1e-5)  # b1p *= b1
    xw = got[:, layout.embedx_w:layout.embedx_w + D]
    assert (xw > 0).all()


def test_pass_lifecycle_and_dedup():
    table = TableConfig(embedx_dim=D, pass_capacity=1 << 10,
                        optimizer=SparseOptimizerConfig(mf_initial_range=0.0,
                                                        mf_create_thresholds=1.0))
    pt = PassTable(table, seed=0)
    keys = np.array([10**12 + 7, 42, 99, 10**15], dtype=np.uint64)

    pt.begin_feed_pass()
    pt.add_keys(keys[:2])
    pt.add_keys(keys[2:])
    pt.add_keys(keys[:1])  # duplicate registration is fine
    pt.end_feed_pass()
    assert pt.pass_size == 4

    pt.begin_pass()
    # batch references key 42 twice (dedup must merge grads)
    batch_keys = np.array([42, 42, 99, 10**12 + 7], dtype=np.uint64)
    ids = pt.lookup_ids(batch_keys)
    pulled = np.asarray(pt.pull(jnp.asarray(ids)))
    assert pulled.shape == (4, 3 + D)
    np.testing.assert_array_equal(pulled[0], pulled[1])  # same key

    push = PushLayout(D)
    grads = np.zeros((4, push.width), dtype=np.float32)
    grads[:, push.SHOW] = 1.0
    grads[:, push.CLICK] = np.array([1, 0, 1, 0])
    grads[:, push.EMBED_G] = np.array([0.5, 0.5, 1.0, -1.0])
    pt.push(jnp.asarray(ids), jnp.asarray(grads))
    pt.end_pass()

    # duplicate key 42: merged g_show=2, show should be 2 after pass
    row42 = pt.store.lookup(np.array([42], dtype=np.uint64))[0]
    assert row42[acc.SHOW] == 2.0
    assert row42[acc.CLICK] == 1.0
    # unseen key never pushed keeps show 0
    row_unpushed = pt.store.lookup(np.array([10**15], dtype=np.uint64))[0]
    assert row_unpushed[acc.SHOW] == 0.0


@pytest.mark.parametrize("init_range", [0.0, 1e-3])
def test_hostdedup_push_matches_device_dedup(init_range):
    """push_sparse_hostdedup (host dedup + sorted segment-sum, no device
    sort) must produce bit-identical slabs to the jnp.unique path — incl.
    lazily CREATED embedx rows, whose randoms are content-addressed by slab
    id so the two paths' different row orders draw the same values."""
    from paddlebox_tpu.embedding.optimizers import (push_sparse_dedup,
                                                    push_sparse_hostdedup)
    table = TableConfig(embedx_dim=D, pass_capacity=1 << 8,
                        optimizer=SparseOptimizerConfig(
                            mf_initial_range=init_range,
                            mf_create_thresholds=0.0))
    pt = PassTable(table, seed=3)
    rng = np.random.RandomState(5)
    keys = np.unique(rng.randint(1, 10**9, 40).astype(np.uint64))
    pt.begin_feed_pass()
    pt.add_keys(keys)
    pt.end_feed_pass()
    pt.begin_pass()

    K = 64
    occ = rng.choice(keys, K).astype(np.uint64)
    valid = rng.rand(K) > 0.2
    ids = pt.lookup_ids(occ, valid)
    push = PushLayout(D)
    grads = rng.randn(K, push.width).astype(np.float32)
    grads[:, push.SHOW] = 1.0
    grads[:, push.CLICK] = (rng.rand(K) < 0.3)
    grads[~valid] = 0.0

    prng = jax.random.PRNGKey(11)
    slab0 = pt.slab
    ref = push_sparse_dedup(slab0, jnp.asarray(ids), jnp.asarray(grads),
                            prng, pt.layout, table.optimizer)
    uids, perm, inv, _ = pt.dedup_for_push(ids)
    got = push_sparse_hostdedup(slab0, jnp.asarray(uids), jnp.asarray(perm),
                                jnp.asarray(inv), jnp.asarray(grads), prng,
                                pt.layout, table.optimizer)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # the train step re-derives uids ON DEVICE from (ids, perm, inv)
    # (rebuild_uids) instead of transferring them — the rebuild must hit
    # the same slab rows bit-identically
    from paddlebox_tpu.embedding.optimizers import rebuild_uids
    rebuilt = rebuild_uids(jnp.asarray(ids), jnp.asarray(perm),
                           jnp.asarray(inv), table.pass_capacity)
    got2 = push_sparse_hostdedup(slab0, rebuilt, jnp.asarray(perm),
                                 jnp.asarray(inv), jnp.asarray(grads), prng,
                                 pt.layout, table.optimizer)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got2))
    # push_write='rebuild' (gather-rebuild slab write, no scatter) must be
    # bit-identical too — pos comes from the host next to the dedup
    from paddlebox_tpu.embedding.optimizers import push_sparse_rebuild
    pos = pt.pos_for_rebuild(uids)
    assert (pos >= 0).sum() == np.unique(ids).shape[0]
    got3 = push_sparse_rebuild(slab0, jnp.asarray(uids), jnp.asarray(pos),
                               jnp.asarray(perm), jnp.asarray(inv),
                               jnp.asarray(grads), prng,
                               pt.layout, table.optimizer)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got3))
    pt.end_pass()


def test_dedup_for_push_invariants():
    table = TableConfig(embedx_dim=D, pass_capacity=128)
    pt = PassTable(table)
    pt.begin_feed_pass()
    pt.add_keys(np.arange(1, 50, dtype=np.uint64))
    pt.end_feed_pass()
    pt.begin_pass()
    rng = np.random.RandomState(0)
    occ = rng.randint(1, 50, 32).astype(np.uint64)
    valid = rng.rand(32) > 0.3
    ids = pt.lookup_ids(occ, valid)
    for native in (True, False):
        if native and not _native_available():
            continue
        uids, perm, inv, got_n_u = (pt.dedup_for_push(ids) if native
                                    else _numpy_dedup(pt, ids))
        # all uids distinct (unique scatter contract)
        assert np.unique(uids).size == uids.size
        # inv nondecreasing over the permuted occurrence order (sorted
        # segment-sum contract)
        assert (np.diff(inv) >= 0).all()
        # perm is a permutation
        assert np.array_equal(np.sort(perm), np.arange(ids.size))
        # reconstruction: uids[inv] == ids[perm] for every occurrence
        np.testing.assert_array_equal(uids[inv], ids[perm])
        # padding ids out of range exactly beyond the unique count
        n_u = np.unique(ids).size
        assert got_n_u == n_u and inv.max() < n_u
        assert (uids[:n_u] < table.pass_capacity).all()
        assert (uids[n_u:] >= table.pass_capacity).all()
    pt.end_pass()


def _native_available():
    from paddlebox_tpu.native.build import available
    return available()


def _numpy_dedup(pt, ids):
    """Force the numpy fallback branch of dedup_for_push."""
    import unittest.mock as mock
    with mock.patch("paddlebox_tpu.native.build.get_lib", return_value=None):
        return pt.dedup_for_push(ids)


def test_native_lookup_matches_searchsorted():
    """rt_lookup (hash probe) must agree with the numpy fallback, honor
    valid masking, and reject unregistered keys."""
    table = TableConfig(embedx_dim=D, pass_capacity=1 << 10)
    pt = PassTable(table)
    rng = np.random.RandomState(7)
    keys = np.unique(rng.randint(1, 1 << 60, 300).astype(np.uint64))
    pt.begin_feed_pass()
    pt.add_keys(keys)
    pt.end_feed_pass()
    pt.begin_pass()
    batch = rng.choice(keys, 128).astype(np.uint64)
    valid = rng.rand(128) > 0.25
    got = pt.lookup_ids(batch, valid)
    ri, pt._rows._index = pt._rows._index, None
    want = pt.lookup_ids(batch, valid)
    pt._rows._index = ri
    np.testing.assert_array_equal(got, want)
    assert (got[~valid] == pt.padding_id).all()
    if ri is not None:
        with pytest.raises(KeyError):
            pt.lookup_ids(np.array([keys.max() + 1], dtype=np.uint64))
    pt.end_pass()


def test_unregistered_key_raises():
    table = TableConfig(embedx_dim=D, pass_capacity=64)
    pt = PassTable(table)
    pt.begin_feed_pass()
    pt.add_keys(np.array([1, 2, 3], dtype=np.uint64))
    pt.end_feed_pass()
    pt.begin_pass()
    with pytest.raises(KeyError):
        pt.lookup_ids(np.array([4], dtype=np.uint64))
    pt.end_pass()


def test_state_persists_across_passes():
    table = TableConfig(embedx_dim=D, pass_capacity=256)
    pt = PassTable(table, seed=0)
    push = PushLayout(D)
    for i in range(3):
        pt.begin_feed_pass()
        pt.add_keys(np.array([7, 8], dtype=np.uint64))
        pt.end_feed_pass()
        pt.begin_pass()
        ids = pt.lookup_ids(np.array([7, 8], dtype=np.uint64))
        grads = np.zeros((2, push.width), dtype=np.float32)
        grads[:, push.SHOW] = 1.0
        grads[:, push.EMBED_G] = 0.1
        pt.push(jnp.asarray(ids), jnp.asarray(grads))
        pt.end_pass()
    row = pt.store.lookup(np.array([7], dtype=np.uint64))[0]
    assert row[acc.SHOW] == 3.0  # accumulated across passes


def test_shrink_decay_and_delete():
    table = TableConfig(embedx_dim=D, pass_capacity=256,
                        show_click_decay_rate=0.5, delete_threshold=0.8)
    layout = ValueLayout(D, "adagrad")
    store = HostEmbeddingStore(layout, table)
    keys = np.array([1, 2], dtype=np.uint64)
    rows = store.lookup_or_create(keys)
    rows[0, acc.SHOW] = 100.0  # survives: 0.1*50 = 5 >= 0.8
    rows[1, acc.SHOW] = 1.0    # dies: 0.1*0.5 < 0.8
    store.write_back(keys, rows)
    deleted = store.shrink()
    assert deleted == 1
    assert len(store) == 1
    survivor = store.lookup(np.array([1], dtype=np.uint64))[0]
    np.testing.assert_allclose(survivor[acc.SHOW], 50.0)  # decayed


def test_spill_and_fault_in(tmp_path):
    table = TableConfig(embedx_dim=D, pass_capacity=256,
                        ssd_dir=str(tmp_path / "ssd"))
    layout = ValueLayout(D, "adagrad")
    store = HostEmbeddingStore(layout, table)
    keys = np.arange(1, 101, dtype=np.uint64)
    rows = store.lookup_or_create(keys)
    rows[:, acc.EMBED_W] = keys.astype(np.float32)
    rows[:, acc.UNSEEN_DAYS] = np.arange(100)[::-1]  # key 1 = oldest
    store.write_back(keys, rows)

    spilled = store.spill(max_resident=60)
    assert spilled == 40
    assert len(store) == 60
    # the lookup path PEEKs a spilled key: value served off the block,
    # row stays spilled (round 16 — a peek needs no journal MOVE)
    row = store.lookup(np.array([1], dtype=np.uint64))[0]
    assert row[acc.EMBED_W] == 1.0
    assert len(store) == 60
    # promotion is explicit: the BeginFeedPass/LoadSSD2Mem fault-in leg
    store.fault_in_keys(np.array([1], dtype=np.uint64))
    assert len(store) == 61
    # load everything back (LoadSSD2Mem)
    store.load_spilled()
    assert len(store) == 100


def test_save_load_roundtrip(tmp_path):
    table = TableConfig(embedx_dim=D, pass_capacity=256)
    layout = ValueLayout(D, "adagrad")
    store = HostEmbeddingStore(layout, table)
    keys = np.array([5, 6, 7], dtype=np.uint64)
    rows = store.lookup_or_create(keys)
    rows[:, acc.EMBED_W] = [1, 2, 3]
    store.write_back(keys, rows)
    p = str(tmp_path / "table.pkl")
    store.save(p)

    store2 = HostEmbeddingStore(layout, table)
    store2.load(p)
    np.testing.assert_array_equal(
        store2.lookup(keys)[:, acc.EMBED_W], [1, 2, 3])
