"""The push's unique-row domain U (ISSUE 31): the stager cuts uids from
one slot an occurrence (K) to the power-of-two bucket of the dedup's own
count, a high-water mark on the trainer; occ_uid, the pull's per-occurrence
slot in uids (ISSUE 42), keeps its [K].

Contracts under test:

  * the trimmed-domain push writes the SAME BITS as the K-padded push,
    for every full-wire write (scatter, rebuild), with and
    without the pull's rows, on the f32 and the bf16 slab, created
    embeddings included (mf_initial_range > 0);
  * dedup_ids returns n_u from both tiers (and from the numpy tier where
    the native one declines), the real ids in uids[:n_u], every inv below
    n_u;
  * push_domain: pow2, capped at K, never under the mark; U = K when
    nothing repeats;
  * chunks of different n_u inside one bucket share ONE compiled
    scan_steps, and a pass on the trimmed domain leaves the store the
    K-padded staging leaves;
  * push_index_slots / push_unique_rows add U / n_u a staged step.
"""

import types
import unittest.mock as mock

import numpy as np
import pytest

from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.pass_table import (dedup_ids, pos_for_rebuild,
                                                push_domain)
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.utils.stats import stat_get

D = 4
NUM_SLOTS = 4


# ------------------------------------------------------------- unit tier

@pytest.mark.parametrize("embed_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reuse", [False, True],
                         ids=["slab_gather", "pulled_rows"])
@pytest.mark.parametrize("write", ["scatter", "rebuild"])
def test_trimmed_domain_push_writes_the_padded_pushs_bits(write, reuse,
                                                          embed_dtype):
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                    push_sparse_rebuild)

    cap, K = 512, 96
    rng = np.random.RandomState(5)
    layout = acc.ValueLayout(D, "adagrad", embed_dtype=embed_dtype)
    conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                 mf_initial_range=1e-3)
    push = acc.PushLayout(D)
    rows = rng.rand(cap, layout.width).astype(np.float32)
    # half the rows have no embedding yet: their push CREATES one, drawn
    # by slab row id (the bits the domain must not move)
    rows[::2, acc.MF_SIZE] = 0.0
    rows[:, acc.CLICK] = 0.0
    slab = jnp.asarray(acc.encode_slab_rows_np(rows, layout))
    ids = rng.randint(0, 40, K).astype(np.int32)
    ids[rng.rand(K) < 0.2] = cap - 1              # padding occurrences
    grads = rng.randn(K, push.width).astype(np.float32)
    grads[:, push.SHOW] = 1.0
    grads[:, push.CLICK] = rng.randint(0, 2, K)
    grads[ids == cap - 1] = 0.0
    prng = jax.random.PRNGKey(11)

    uids, perm, inv, n_u = dedup_ids(ids, cap)
    U = push_domain(n_u, K)
    assert n_u <= U < K

    def run(u):
        # the pull's block: the rows of uids[:u], padding clipped onto
        # the trash row (ops/sparse.pull_sparse_unique)
        pulled = acc.decode_slab_rows(
            jnp.take(slab, jnp.asarray(uids[:u]), axis=0, mode="clip"),
            layout) if reuse else None
        common = (jnp.asarray(perm), jnp.asarray(inv), jnp.asarray(grads),
                  prng, layout, conf)
        if write == "rebuild":
            # the map is built from the whole dedup: it names real uids
            # only, so it is the same under either domain
            return push_sparse_rebuild(
                slab, jnp.asarray(uids[:u]),
                jnp.asarray(pos_for_rebuild(uids, cap)), *common,
                pulled_rows=pulled)
        return push_sparse_hostdedup(slab, jnp.asarray(uids[:u]), *common,
                                     pulled_rows=pulled)

    padded, trimmed = np.asarray(run(K)), np.asarray(run(U))
    np.testing.assert_array_equal(padded, trimmed)
    touched = np.unique(ids[ids != cap - 1])
    assert (padded[touched] != np.asarray(slab)[touched]).any(axis=1).all()
    created = touched[touched % 2 == 0]
    dec = acc.decode_slab_rows_np(trimmed, layout)
    assert created.size and (dec[created, acc.MF_SIZE] == D).all()


def _ids_case(case: str, K: int = 64, trash: int = 999) -> np.ndarray:
    if case == "none":
        return np.zeros(0, np.int32)
    if case == "one":
        return np.full(K, 7, np.int32)
    if case == "all_distinct":
        return np.random.RandomState(2).permutation(K).astype(np.int32)
    rng = np.random.RandomState(3)
    ids = rng.randint(0, K // 4, K).astype(np.int32)
    if case == "trash":
        ids[rng.rand(K) < 0.3] = trash        # padding occurrences
    return ids


DEDUP_TIERS = ["native", "numpy", "native_declined"]


def dedup_in_tier(ids: np.ndarray, pad_base: int, tier: str):
    """dedup_ids through one tier: the native rt_dedup, the numpy argsort
    with no native library, or the numpy argsort after rt_dedup declines
    (returns -1)."""
    from paddlebox_tpu.native.build import available
    if tier == "native":
        if not available():
            pytest.skip("native library unavailable")
        return dedup_ids(ids, pad_base)
    lib = (None if tier == "numpy"
           else types.SimpleNamespace(rt_dedup=lambda *_a: -1))
    with mock.patch("paddlebox_tpu.native.build.get_lib", return_value=lib):
        return dedup_ids(ids, pad_base)


@pytest.mark.parametrize("tier", DEDUP_TIERS)
@pytest.mark.parametrize("case", ["none", "one", "all_distinct", "repeats",
                                  "trash"])
def test_dedup_ids_counts_its_real_uids(case, tier):
    pad_base = 1000
    ids = _ids_case(case, trash=pad_base - 1)
    uids, perm, inv, n_u = dedup_in_tier(ids, pad_base, tier)
    if tier != "native":
        # the numpy tier's uids come out ascending
        assert (np.diff(uids.astype(np.int64)) > 0).all()
    real = np.unique(ids)
    assert isinstance(n_u, int) and n_u == real.size
    np.testing.assert_array_equal(np.sort(uids[:n_u]), real)
    assert (uids[n_u:] >= pad_base).all()
    assert uids.shape == perm.shape == inv.shape == ids.shape
    if ids.size:
        assert 0 <= inv.min() and inv.max() < n_u
        np.testing.assert_array_equal(uids[inv], ids[perm])


@pytest.mark.parametrize("n_u,K,mark,want", [
    (26_465, 79_872, 0, 32_768),      # the benchmark's step
    (4_800, 16_384, 0, 8_192),
    (0, 96, 0, 1), (1, 96, 0, 1), (33, 96, 0, 64), (64, 96, 0, 64),
    (65, 96, 0, 96),                  # the bucket is capped at K
    (96, 96, 0, 96),                  # nothing repeats: today's program
    (10, 96, 64, 64),                 # never under the mark
    (70, 96, 64, 96),
    (0, 0, 0, 0),
])
def test_push_domain_bucket_rule(n_u, K, mark, want):
    assert push_domain(n_u, K, mark) == want


# ---------------------------------------------------------- trainer tier

@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("push_domain_data")
    # small vocab -> heavy key recurrence: n_u well under K
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=2, lines_per_file=256, num_slots=NUM_SLOTS,
        vocab_per_slot=60, max_len=3, seed=17)
    return files, type(feed)(slots=feed.slots, batch_size=64)


def _trainer(feed, scan_chunk=2, seed=0):
    from paddlebox_tpu.train import BoxTrainer
    table = TableConfig(
        embedx_dim=D, pass_capacity=2048,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                   hidden=(16,))
    return BoxTrainer(model, table, feed,
                      TrainerConfig(scan_chunk=scan_chunk), seed=seed)


def _fake_hosts(K, n_us):
    return [{"uids": np.arange(K, dtype=np.int32),
             "occ_uid": np.zeros(K, dtype=np.int32),
             "perm": np.arange(K, dtype=np.int32)} for _ in n_us]


def test_domain_is_a_high_water_mark_on_the_trainer(data):
    """U over a trainer's life: the bucket of the largest count seen for
    this K, never smaller again; perm and occ_uid stay [K]; another K
    keeps a mark of its own."""
    _files, feed = data
    tr = _trainer(feed)
    try:
        K, seen = 96, []
        for n_us in ([20, 30], [5], [33, 12], [40], [96], [3]):
            hosts = _fake_hosts(K, n_us)
            tr._trim_push_domain(hosts, n_us)
            assert len({h["uids"].shape for h in hosts}) == 1
            assert all(h["occ_uid"].shape == h["perm"].shape == (K,)
                       for h in hosts)
            seen.append(hosts[0]["uids"].shape[0])
        assert seen == [32, 32, 64, 64, 96, 96]
        other = _fake_hosts(4 * K, [50])
        tr._trim_push_domain(other, [50])
        assert other[0]["uids"].shape == (64,)
        # a stage with no host dedup (eval) is left alone
        plain = [{"ids": np.arange(K, dtype=np.int32)}]
        tr._trim_push_domain(plain, [None])
        assert plain[0]["ids"].shape == (K,)
    finally:
        tr.close()


def _run_pass(files, feed, padded: bool):
    """(store keys, store values, losses, scan_steps compiles, staged
    uid shapes) of one pass; padded=True stages one slot an occurrence,
    the parent's staging."""
    import paddlebox_tpu.train.trainer as trainer_mod
    from paddlebox_tpu.obs import device
    tr = _trainer(feed)
    staged_shapes = []
    stack = tr._stack_batches_host

    def spy(group):
        out = stack(group)
        staged_shapes.append((out["uids"].shape, out["occ_uid"].shape,
                              out["perm"].shape))
        return out

    tr._stack_batches_host = spy
    rule = (lambda n_u, K, floor=0: K) if padded else push_domain
    try:
        with mock.patch.object(trainer_mod, "push_domain", rule):
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files)
            loss = tr.train_pass(ds)["loss"]
            ds.release_memory()
        keys, vals = tr.table.store.state_items()
        order = np.argsort(keys)
        compiles = device.snapshot()["entries"]["scan_steps"]["compiles"]
        return keys[order], vals[order], loss, compiles, staged_shapes
    finally:
        tr.close()


def test_one_bucket_one_program_and_the_padded_stagings_store(data):
    files, feed = data
    K = feed.key_capacity()
    keys_t, vals_t, loss_t, compiles_t, shapes_t = _run_pass(
        files, feed, padded=False)
    keys_p, vals_p, loss_p, compiles_p, shapes_p = _run_pass(
        files, feed, padded=True)
    assert len(shapes_t) == 4 and shapes_p == [((2, K),) * 3] * 4
    U = shapes_t[0][0][1]
    assert U < K and U == push_domain(U, K)
    assert shapes_t == [((2, U), (2, K), (2, K))] * 4
    assert compiles_t == 1 and compiles_p == 1
    assert loss_t == loss_p
    np.testing.assert_array_equal(keys_t, keys_p)
    np.testing.assert_array_equal(vals_t, vals_p)


def test_counters_add_slots_and_rows_a_staged_step(data):
    files, feed = data
    tr = _trainer(feed)
    try:
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files[:1])
        tr.table.begin_feed_pass()
        ds.load_into_memory(add_keys_fn=tr.table.add_keys)
        tr.table.end_feed_pass()
        tr.table.begin_pass()
        batches = ds.split_batches(num_workers=1)[0][:2]
        per_batch = [np.unique(tr.table.lookup_ids(b.keys, b.valid))
                     for b in batches]
        slots0 = stat_get("push_index_slots")
        rows0 = stat_get("push_unique_rows")
        staged = tr._stack_batches_host(batches)
        U = staged["uids"].shape[1]
        assert stat_get("push_index_slots") - slots0 == U * 2
        assert stat_get("push_unique_rows") - rows0 == sum(
            u.size for u in per_batch)
        # the one-step program's batch follows the same mark
        one = tr.host_batch(batches[0],
                            tr.table.lookup_ids(batches[0].keys,
                                                batches[0].valid))
        assert one["uids"].shape == (U,)
        assert stat_get("push_index_slots") - slots0 == U * 3
        tr.table.end_pass()
        ds.release_memory()
    finally:
        tr.close()
