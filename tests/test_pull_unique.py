"""The pull over the push's unique-row domain (ISSUE 42): a train step
gathers the slab once a distinct row of its batch (uids[U]), makes the pull
view from those U rows and expands it to the K occurrences from that block
by occ_uid[K], the occurrence's slot in uids; the same U rows are the push's.

Contracts under test:

  * occurrence_uid_slots inverts the dedup of BOTH tiers (native rt_dedup,
    numpy argsort, also where the native one declines): ids ==
    uids[occ_uid], every value below
    n_u, whatever repeats, the trash row among the ids;
  * pull_sparse_unique returns pull_sparse's bits and slab[uids]'s rows,
    out-of-slab padding uids clipped onto the trash row, f32 and bf16 slab;
  * the push fed the pulled block writes the bits of the push that gathers
    its own rows (scatter, rebuild);
  * a step through the unique pull equals, bit for bit (slab, params, loss,
    predictions), the step whose wire lacks occ_uid and so pulls by
    occurrence: the choice is read from the wire's leaves;
  * an eval batch carries no push leaf and still pulls;
  * chunks of different n_u inside one bucket share ONE compiled
    scan_steps;
  * pull_index_slots adds U a staged train step, K an eval step.
"""

import contextlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_push_domain import (DEDUP_TIERS, D, _ids_case,  # noqa: E402
                              _trainer, data,  # noqa: F401  (the fixture)
                              dedup_in_tier)

from paddlebox_tpu.config import flags  # noqa: E402
from paddlebox_tpu.config.configs import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.data import BoxDataset  # noqa: E402
from paddlebox_tpu.embedding import accessor as acc  # noqa: E402
from paddlebox_tpu.embedding.pass_table import (dedup_ids,  # noqa: E402
                                                occurrence_uid_slots,
                                                pos_for_rebuild, push_domain)
from paddlebox_tpu.utils.stats import stat_get  # noqa: E402

CAPACITY = 2048             # test_push_domain._trainer's pass_capacity
WRITES = ("scatter", "rebuild")
DTYPES = ("float32", "bfloat16")


# ------------------------------------------------------------- unit tier

@pytest.mark.parametrize("tier", DEDUP_TIERS)
@pytest.mark.parametrize("case", ["none", "one", "all_distinct", "repeats",
                                  "trash"])
def test_occ_uid_inverts_the_dedup_of_both_tiers(case, tier):
    pad_base = 1000
    ids = _ids_case(case, trash=pad_base - 1)
    uids, perm, inv, n_u = dedup_in_tier(ids, pad_base, tier)
    occ_uid = occurrence_uid_slots(perm, inv)
    assert occ_uid.shape == ids.shape and occ_uid.dtype == np.int32
    np.testing.assert_array_equal(uids[occ_uid], ids)
    if ids.size:
        assert 0 <= occ_uid.min() and occ_uid.max() < n_u
        # every real slot is some occurrence's: the block has no row the
        # view never reads but padding
        np.testing.assert_array_equal(np.unique(occ_uid), np.arange(n_u))


def _slab_and_dedup(embed_dtype, cap=512, K=96):
    import jax.numpy as jnp
    rng = np.random.RandomState(5)
    layout = acc.ValueLayout(D, "adagrad", embed_dtype=embed_dtype)
    rows = rng.rand(cap, layout.width).astype(np.float32)
    # half the rows have no embedding yet: their push CREATES one
    rows[::2, acc.MF_SIZE] = 0.0
    rows[:, acc.CLICK] = 0.0
    slab = jnp.asarray(acc.encode_slab_rows_np(rows, layout))
    ids = rng.randint(0, 40, K).astype(np.int32)
    ids[rng.rand(K) < 0.2] = cap - 1              # padding occurrences
    uids, perm, inv, n_u = dedup_ids(ids, cap)
    return layout, slab, ids, uids, perm, inv, n_u, rng


@pytest.mark.parametrize("embed_dtype", DTYPES)
def test_pull_sparse_unique_returns_the_occurrence_pulls_bits(embed_dtype):
    import jax.numpy as jnp

    from paddlebox_tpu.ops.sparse import pull_sparse, pull_sparse_unique
    layout, slab, ids, uids, perm, inv, n_u, _ = _slab_and_dedup(embed_dtype)
    cap, K = slab.shape[0], ids.shape[0]
    U = push_domain(n_u, K)
    assert n_u <= U < K and (uids[n_u:U] >= cap).any()
    emb, rows_u = pull_sparse_unique(
        slab, jnp.asarray(uids[:U]),
        jnp.asarray(occurrence_uid_slots(perm, inv)), layout)
    want = pull_sparse(slab, jnp.asarray(ids), layout)
    assert emb.shape == (K, 3 + D) and rows_u.shape == (U, layout.width)
    np.testing.assert_array_equal(np.asarray(emb), np.asarray(want))
    # the block is slab[uids], a padding uid clipped onto the trash row
    np.testing.assert_array_equal(
        np.asarray(rows_u),
        np.asarray(acc.decode_slab_rows(
            slab[jnp.asarray(np.minimum(uids[:U], cap - 1))], layout)))


@pytest.mark.parametrize("write", WRITES)
def test_push_fed_the_pulled_block_matches_its_own_gather(write):
    """The rewritten pulled-row reuse contract: the rows the pull gathered
    for uids ARE the push's rows, created embeddings included."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                    push_sparse_rebuild)
    from paddlebox_tpu.ops.sparse import pull_sparse_unique
    layout, slab, ids, uids, perm, inv, n_u, rng = _slab_and_dedup(
        "float32")
    cap, K = slab.shape[0], ids.shape[0]
    conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                 mf_initial_range=1e-3)
    push = acc.PushLayout(D)
    grads = rng.randn(K, push.width).astype(np.float32)
    grads[:, push.SHOW] = 1.0
    grads[:, push.CLICK] = rng.randint(0, 2, K)
    grads[ids == cap - 1] = 0.0
    U = push_domain(n_u, K)
    u = jnp.asarray(uids[:U])
    _, rows_u = pull_sparse_unique(
        slab, u, jnp.asarray(occurrence_uid_slots(perm, inv)), layout)
    common = (jnp.asarray(perm), jnp.asarray(inv), jnp.asarray(grads),
              jax.random.PRNGKey(3), layout, conf)

    def run(pulled):
        if write == "rebuild":
            return push_sparse_rebuild(
                slab, u, jnp.asarray(pos_for_rebuild(uids, cap)), *common,
                pulled_rows=pulled)
        return push_sparse_hostdedup(slab, u, *common, pulled_rows=pulled)

    own, fed = np.asarray(run(None)), np.asarray(run(rows_u))
    np.testing.assert_array_equal(own, fed)
    touched = np.unique(ids[ids != cap - 1])
    assert (fed[touched] != np.asarray(slab)[touched]).any(axis=1).all()
    created = touched[touched % 2 == 0]
    assert created.size and (fed[created, acc.MF_SIZE] == D).all()


@pytest.mark.parametrize("width", [D, 130], ids=["narrow", "wide"])
def test_push_rows_assembled_k_minor_hold_the_columns_values(width):
    """build_push_grads assembles its rows as one flat K-minor vector
    (PERF.md section 6, PR 42): column for column the plain form's bits,
    [slot, valid, click * valid, -d_emb[:, 2:] * valid]."""
    import jax.numpy as jnp

    from paddlebox_tpu.ops.sparse import build_push_grads
    rng = np.random.RandomState(9)
    K = 96
    d_emb = rng.randn(K, 3 + width).astype(np.float32)
    slots = rng.randint(0, 7, K).astype(np.int32)
    clicks = rng.randint(0, 2, K).astype(np.int32)
    valid = rng.rand(K) > 0.25
    got = np.asarray(build_push_grads(jnp.asarray(d_emb), jnp.asarray(slots),
                                      jnp.asarray(clicks),
                                      jnp.asarray(valid)))
    v = valid.astype(np.float32)[:, None]
    want = np.concatenate([slots.astype(np.float32)[:, None], v,
                           clicks.astype(np.float32)[:, None] * v,
                           -d_emb[:, 2:] * v], axis=1)
    assert got.shape == (K, 4 + width) and got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------- trainer tier

@contextlib.contextmanager
def _modes(write="auto", embed_dtype="float32"):
    flags.set_flag("push_write", write)
    flags.set_flag("slab_embed_dtype", embed_dtype)
    try:
        yield
    finally:
        flags.set_flag("push_write", "auto")
        flags.set_flag("slab_embed_dtype", "float32")


@contextlib.contextmanager
def _open_pass(tr, feed, files, test_mode=False):
    """A begun pass over the first file; yields its packed batches."""
    tr.table.set_test_mode(test_mode)
    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files[:1])
    tr.table.begin_feed_pass()
    ds.load_into_memory(add_keys_fn=tr.table.add_keys)
    tr.table.end_feed_pass()
    tr.table.begin_pass()
    try:
        yield ds.split_batches(num_workers=1)[0]
    finally:
        tr.table.end_pass()
        tr.table.set_test_mode(False)
        ds.release_memory()


@pytest.mark.parametrize("embed_dtype", DTYPES)
@pytest.mark.parametrize("write", WRITES)
def test_step_through_unique_pull_equals_occurrence_pull(data, write,
                                                         embed_dtype):
    """Two steps over recurring keys, embeddings created on the way
    (mf_initial_range > 0): the wire with occ_uid and the same wire
    without it (the occurrence gather, the push gathering its own rows)
    leave the same slab, params, loss and predictions, bit for bit."""
    import jax
    import jax.numpy as jnp
    files, feed = data

    def run(unique: bool):
        with _modes(write, embed_dtype):
            tr = _trainer(feed)
            try:
                assert tr.table.layout.embed_dtype == embed_dtype
                with _open_pass(tr, feed, files) as batches:
                    slab, params = tr.table.slab, tr.params
                    opt_state, prng = tr.opt_state, tr.table.next_prng()
                    out = []
                    for b in batches[:2]:
                        host = tr.host_batch(
                            b, tr.table.lookup_ids(b.keys, b.valid))
                        assert ("push_pos" in host) == (write == "rebuild")
                        assert host["uids"].shape[0] < host["ids"].shape[0]
                        if not unique:
                            del host["occ_uid"]
                        batch = {k: jnp.asarray(v) for k, v in host.items()}
                        slab, params, opt_state, loss, preds, prng = \
                            tr.fns.step(slab, params, opt_state, batch, prng)
                        out.append((np.asarray(loss),
                                    np.asarray(preds["ctr"])))
                    res = (np.asarray(slab),
                           [np.asarray(x) for x in jax.tree.leaves(params)],
                           out)
                    tr.table.set_slab(slab)
                    return res
            finally:
                tr.close()

    slab_u, params_u, out_u = run(True)
    slab_o, params_o, out_o = run(False)
    np.testing.assert_array_equal(slab_u, slab_o)
    for a, b in zip(params_u, params_o):
        np.testing.assert_array_equal(a, b)
    for (la, pa), (lb, pb) in zip(out_u, out_o):
        assert np.isfinite(la) and la == lb
        np.testing.assert_array_equal(pa, pb)


def test_eval_batch_carries_no_push_leaf_and_still_pulls(data):
    import jax.numpy as jnp

    from paddlebox_tpu.ops.sparse import pull_sparse
    files, feed = data
    tr = _trainer(feed)
    try:
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files[:1])
        tr.train_pass(ds)
        ds.release_memory()
        with _open_pass(tr, feed, files, test_mode=True) as batches:
            b = batches[0]
            ids = tr.table.lookup_ids(b.keys, b.valid)
            slots0 = stat_get("pull_index_slots")
            host = tr.host_batch(b, ids)
            assert not {"uids", "occ_uid", "perm", "inv",
                        "push_pos"} & set(host)
            assert stat_get("pull_index_slots") - slots0 == ids.shape[0]
            emb = pull_sparse(tr.table.slab, jnp.asarray(ids),
                              tr.table.layout)
            assert np.asarray(emb)[ids != CAPACITY - 1].any()
            preds = tr.fns.eval_step(
                tr.table.slab, tr.params,
                {k: jnp.asarray(v) for k, v in host.items()})
            assert np.isfinite(np.asarray(preds["ctr"])).all()
    finally:
        tr.close()


def test_one_compile_of_scan_steps_over_chunks_of_different_n_u(data):
    from paddlebox_tpu.obs import device
    files, feed = data
    K = feed.key_capacity()
    tr = _trainer(feed)
    staged = []
    stack = tr._stack_batches_host

    def spy(group):
        out = stack(group)
        staged.append(out)
        return out

    tr._stack_batches_host = spy
    try:
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        assert np.isfinite(tr.train_pass(ds)["loss"])
        ds.release_memory()
        assert len(staged) == 4
        U = staged[0]["uids"].shape[1]
        assert U < K
        n_us = set()
        for out in staged:
            assert out["uids"].shape == (2, U)
            assert out["occ_uid"].shape == out["ids"].shape == (2, K)
            np.testing.assert_array_equal(
                np.take_along_axis(out["uids"], out["occ_uid"], axis=1),
                out["ids"])
            n_us.update(int(o.max()) + 1 for o in out["occ_uid"])
        assert len(n_us) > 1 and max(n_us) <= U
        assert device.snapshot()["entries"]["scan_steps"]["compiles"] == 1
    finally:
        tr.close()


def test_counter_adds_the_slab_gathers_of_a_staged_step(data):
    files, feed = data
    K = feed.key_capacity()
    tr = _trainer(feed)
    try:
        with _open_pass(tr, feed, files) as batches:
            pull0 = stat_get("pull_index_slots")
            push0 = stat_get("push_index_slots")
            staged = tr._stack_batches_host(batches[:2])
            U = staged["uids"].shape[1]
            assert U < K
            assert stat_get("pull_index_slots") - pull0 == U * 2
            assert stat_get("push_index_slots") - push0 == U * 2
            b = batches[2]
            one = tr.host_batch(b, tr.table.lookup_ids(b.keys, b.valid))
            assert one["uids"].shape == (U,)
            assert stat_get("pull_index_slots") - pull0 == U * 3
    finally:
        tr.close()
