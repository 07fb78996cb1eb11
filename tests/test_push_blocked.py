"""Round-11 line-rate push: blocked scatter + bf16 slab dtype diet.

Contracts under test:

  * push_write='blocked' (push_blocked_write): bucketize the SORTED uid
    vector into contiguous row blocks, place each touched block with one
    dynamic_update_slice — must be BIT-IDENTICAL to the scatter oracle
    on BoxTrainer's wire at chunk>1, multi-pass, and through the sharded
    runners' stagings (full products and uid wire, 2 virtual processes). The
    staging side must pin the sorted dedup tier (dedup_ids sort=True):
    the native rt_dedup hash order would silently drop rows.
  * push_blocked_pallas: the Mosaic placement kernel (interpreted on
    the CPU; its TPU lowering is checked by cross-lowering) is a
    drop-in for the fori_loop of dynamic_update_slices.
  * slab_embed_dtype='bfloat16' (accessor slab codec): weight columns
    round to bf16 at the slab write; the header and ALL optimizer stats
    round-trip BIT-EXACTLY through encode/decode, the store/checkpoint
    round trip, and a full pass. Training quality is AUC-parity gated
    (no bit oracle).
"""

import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec

D = 4
NUM_SLOTS = 4


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("push_blocked_data")
    # small vocab → heavy key recurrence: many touched rows per block,
    # revisited across batches — the blocked write's hard case
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=2, lines_per_file=480, num_slots=NUM_SLOTS,
        vocab_per_slot=120, max_len=3, seed=13)
    feed = type(feed)(slots=feed.slots, batch_size=64)
    return files, feed


# ------------------------------------------------------------- unit tier

def _unit_setup(seed=3, cap=512, K=96):
    import jax

    from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout

    rng = np.random.RandomState(seed)
    layout = ValueLayout(D, "adagrad")
    conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                 mf_initial_range=1e-3)
    push = PushLayout(D)
    slab = rng.rand(cap, layout.width).astype(np.float32)
    ids = rng.randint(0, cap // 2, K).astype(np.int32)
    ids[rng.rand(K) < 0.2] = cap - 1              # padding occurrences
    grads = rng.randn(K, push.width).astype(np.float32)
    grads[:, push.SHOW] = 1.0
    grads[ids == cap - 1] = 0.0
    prng = jax.random.PRNGKey(11)
    return layout, conf, push, slab, ids, grads, prng


def test_push_blocked_write_unit_parity():
    """push_sparse_hostdedup/uidwire write='blocked' vs the scatter
    oracle, across block sizes spanning touched<blocks and
    touched==blocks regimes — bit-identical placement."""
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                    push_sparse_uidwire)
    from paddlebox_tpu.embedding.pass_table import (dedup_ids,
                                                    dedup_uids_sorted)

    layout, conf, push, slab, ids, grads, prng = _unit_setup()
    cap = slab.shape[0]
    uids, perm, inv, _ = dedup_ids(ids, cap, sort=True)
    assert np.all(np.diff(uids.astype(np.int64)) > 0)
    oracle = push_sparse_hostdedup(
        jnp.asarray(slab), jnp.asarray(uids), jnp.asarray(perm),
        jnp.asarray(inv), jnp.asarray(grads), prng, layout, conf)
    suids = dedup_uids_sorted(ids, cap)
    for block in (8, 64, 256, 512):
        flags.set_flag("push_block_rows", block)
        try:
            got = push_sparse_hostdedup(
                jnp.asarray(slab), jnp.asarray(uids), jnp.asarray(perm),
                jnp.asarray(inv), jnp.asarray(grads), prng, layout, conf,
                write="blocked")
            np.testing.assert_array_equal(np.asarray(oracle),
                                          np.asarray(got),
                                          err_msg=f"hostdedup B={block}")
            got_w = push_sparse_uidwire(
                jnp.asarray(slab), jnp.asarray(suids), jnp.asarray(ids),
                jnp.asarray(grads), prng, layout, conf, write="blocked")
            np.testing.assert_array_equal(np.asarray(oracle),
                                          np.asarray(got_w),
                                          err_msg=f"uidwire B={block}")
        finally:
            flags.set_flag("push_block_rows", 1024)


def test_push_blocked_write_all_pad_and_dense():
    """Degenerate shapes: an all-padding batch writes nothing; a batch
    touching EVERY block still places correctly."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.optimizers import push_blocked_write

    cap, W = 64, 5
    rng = np.random.RandomState(0)
    slab = rng.rand(cap, W).astype(np.float32)
    # all-padding: uids all out of range
    uids = (cap + np.arange(16)).astype(np.int32)
    rows = rng.rand(16, W).astype(np.float32)
    out = push_blocked_write(jnp.asarray(slab), jnp.asarray(uids),
                             jnp.asarray(rows), 16)
    np.testing.assert_array_equal(np.asarray(out), slab)
    # every row touched (uids == arange): blocked == full overwrite
    uids = np.arange(cap, dtype=np.int32)
    rows = rng.rand(cap, W).astype(np.float32)
    out = push_blocked_write(jnp.asarray(slab), jnp.asarray(uids),
                             jnp.asarray(rows), 8)
    np.testing.assert_array_equal(np.asarray(out), rows)
    # non-divisor block fails loud
    with pytest.raises(ValueError, match="divide"):
        jax.jit(lambda s: push_blocked_write(
            s, jnp.asarray(uids), jnp.asarray(rows), 7))(jnp.asarray(slab))


def test_pallas_blocked_write_matches_fori():
    """push_blocked_pallas (interpreted on the CPU): the Mosaic grid
    placement is bit-identical to the XLA fori_loop tier."""
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.optimizers import push_blocked_write

    rng = np.random.RandomState(4)
    cap, W, U = 128, 6, 40
    slab = rng.rand(cap, W).astype(np.float32)
    data = np.sort(rng.choice(cap, U - 8, replace=False)).astype(np.int32)
    uids = np.concatenate([data, cap + np.arange(8, dtype=np.int32)])
    rows = rng.rand(U, W).astype(np.float32)
    base = push_blocked_write(jnp.asarray(slab), jnp.asarray(uids),
                              jnp.asarray(rows), 16)
    flags.set_flag("push_blocked_pallas", True)
    try:
        got = push_blocked_write(jnp.asarray(slab), jnp.asarray(uids),
                                 jnp.asarray(rows), 16)
    finally:
        flags.set_flag("push_blocked_pallas", False)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_pallas_blocked_write_lowers_for_tpu():
    """Cross-lower the COMPILED placement kernel for the TPU at production
    shapes (1M x 17 slab, 512-row blocks, a 4,096-row update): a BlockSpec
    the TPU lowering refuses (the pre-PR-21 (1, B) row_map block) fails
    here, on the CPU, instead of on the chip."""
    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.pallas_push import pallas_blocked_write

    C, W, B, NB = 1 << 20, 17, 512, 2048

    def place(slab, tiles, row_map, blk_idx):
        return pallas_blocked_write(slab, tiles, row_map, blk_idx,
                                    interpret=False)

    exp = jax.export.export(jax.jit(place), platforms=["tpu"])(
        jax.ShapeDtypeStruct((C, W), jnp.float32),
        jax.ShapeDtypeStruct((NB, B, W), jnp.float32),
        jax.ShapeDtypeStruct((NB, B), jnp.int32),
        jax.ShapeDtypeStruct((NB,), jnp.int32))
    assert "tpu_custom_call" in exp.mlir_module()


def test_resolve_blocked_validation():
    """resolve_push_write: blocked demands a positive divisor block —
    refused at resolve time, not deep in the jit."""
    from paddlebox_tpu.train.trainer import resolve_push_write

    flags.set_flag("push_write", "blocked")
    try:
        flags.set_flag("push_block_rows", 1024)
        assert resolve_push_write(capacity=4096, batch_keys=512) == "blocked"
        with pytest.raises(ValueError, match="divide"):
            resolve_push_write(capacity=1000, batch_keys=512)
        flags.set_flag("push_block_rows", 0)
        with pytest.raises(ValueError, match="push_block_rows"):
            resolve_push_write(capacity=4096, batch_keys=512)
    finally:
        flags.set_flag("push_block_rows", 1024)
        flags.set_flag("push_write", "auto")


def test_dedup_ids_sort_option():
    """dedup_ids(sort=True): strictly ascending uids with consistent
    perm/inv (the blocked-write staging contract), even when the native
    hash-order tier is available and would win the default call."""
    from paddlebox_tpu.embedding.pass_table import dedup_ids

    rng = np.random.RandomState(21)
    for K, space in ((256, 50), (512, 500), (64, 8)):
        ids = rng.randint(0, space, K).astype(np.int32)
        uids, perm, inv, _ = dedup_ids(ids, space, sort=True)
        assert np.all(np.diff(uids.astype(np.int64)) > 0)
        assert np.array_equal(np.sort(perm), np.arange(K))
        assert (np.diff(inv) >= 0).all()
        np.testing.assert_array_equal(uids[inv], ids[perm])
        n_u = np.unique(ids).size
        assert (uids[:n_u] < space).all() and (uids[n_u:] >= space).all()


# ------------------------------------------------------------ codec tier

def _stat_cols(layout):
    """Boolean mask of the NON-weight columns (header + optimizer stats)
    — everything the bf16 diet must preserve bit-exactly."""
    from paddlebox_tpu.embedding.accessor import slab_codec_plan
    return ~slab_codec_plan(layout).bf16_cols


def test_slab_codec_roundtrip_bits():
    """encode→decode: stats/header columns recover their EXACT f32 bits
    (incl. negative zero and denormals); weight columns equal the bf16
    round-trip; numpy and jnp codec twins agree bit for bit."""
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.accessor import (ValueLayout,
                                                  decode_slab_rows,
                                                  decode_slab_rows_np,
                                                  encode_slab_rows,
                                                  encode_slab_rows_np)

    rng = np.random.RandomState(6)
    for opt in ("adagrad", "adam"):
        layout = ValueLayout(D, opt, embed_dtype="bfloat16")
        f32 = ValueLayout(D, opt)
        assert layout.device_dtype == np.uint16
        assert f32.device_width == f32.width
        rows = (rng.randn(32, layout.width) * 10).astype(np.float32)
        rows[0, 1] = -0.0
        rows[1, 2] = 1e-42                     # denormal survives the split
        rows[2, 3] = np.float32(np.pi)
        enc_np = encode_slab_rows_np(rows, layout)
        assert enc_np.shape == (32, layout.device_width)
        enc_j = np.asarray(encode_slab_rows(jnp.asarray(rows), layout))
        np.testing.assert_array_equal(enc_np, enc_j)
        dec_np = decode_slab_rows_np(enc_np, layout)
        dec_j = np.asarray(decode_slab_rows(jnp.asarray(enc_j), layout))
        np.testing.assert_array_equal(dec_np, dec_j)
        stats = _stat_cols(layout)
        # stats: exact bit round trip
        np.testing.assert_array_equal(dec_np[:, stats].view(np.uint32),
                                      rows[:, stats].view(np.uint32))
        # weights: exactly the bf16 value (one rounding, no double round)
        w = ~stats
        expect = np.asarray(jnp.asarray(rows[:, w]).astype(
            jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_array_equal(dec_np[:, w], expect)
        # f32 layout: both directions are identity
        np.testing.assert_array_equal(encode_slab_rows_np(rows, f32), rows)
        np.testing.assert_array_equal(decode_slab_rows_np(rows, f32), rows)


def test_bf16_pass_table_store_roundtrip():
    """A full begin_pass/end_pass cycle under the bf16 slab with NO
    training: stats/header columns come back to the store bit-exact;
    weight columns come back as their bf16 rounding, once (idempotent on
    a second cycle — no double rounding drift)."""
    from paddlebox_tpu.embedding.pass_table import PassTable

    keys = np.arange(1, 120, dtype=np.uint64)

    def cycle(table, n=2):
        for _ in range(n):
            table.begin_feed_pass()
            table.add_keys(keys)
            table.end_feed_pass()
            table.begin_pass()
            table.end_pass()
        k, v = table.store.state_items()
        order = np.argsort(k)
        return k[order], v[order]

    cfg = TableConfig(embedx_dim=D, pass_capacity=256)
    base = PassTable(cfg, seed=1)
    k_f32, v_f32 = cycle(base, n=1)
    flags.set_flag("slab_embed_dtype", "bfloat16")
    try:
        diet = PassTable(cfg, seed=1)
        assert diet.layout.embed_dtype == "bfloat16"
        k_b, v_b = cycle(diet, n=1)
        np.testing.assert_array_equal(k_f32, k_b)
        stats = _stat_cols(base.layout)
        np.testing.assert_array_equal(v_f32[:, stats].view(np.uint32),
                                      v_b[:, stats].view(np.uint32))
        import jax.numpy as jnp
        expect = np.asarray(jnp.asarray(v_f32[:, ~stats]).astype(
            jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_array_equal(v_b[:, ~stats], expect)
        # second cycle: already-bf16 weights are fixed points — no drift
        k_b2, v_b2 = cycle(diet, n=1)
        np.testing.assert_array_equal(v_b, v_b2)
    finally:
        flags.set_flag("slab_embed_dtype", "float32")


def test_bf16_differentiable_pull_fails_loud():
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.accessor import ValueLayout
    from paddlebox_tpu.ops.sparse import pull_sparse_differentiable

    layout = ValueLayout(D, "adagrad", embed_dtype="bfloat16")
    with pytest.raises(ValueError, match="float32 slab"):
        pull_sparse_differentiable(jnp.zeros((8, layout.device_width),
                                             jnp.uint16),
                                   jnp.zeros((4,), jnp.int32), layout)


# -------------------------------------------------------------- e2e tier

def run_mode(files, feed, mode, block=256, passes=2,
             embed_dtype="float32", seed=0):
    """Train the single-host trainer; returns (losses, store keys/values,
    dense params)."""
    flags.set_flag("push_write", mode)
    flags.set_flag("push_block_rows", block)
    flags.set_flag("slab_embed_dtype", embed_dtype)
    try:
        table = TableConfig(
            embedx_dim=D, pass_capacity=2048,
            optimizer=SparseOptimizerConfig(
                mf_create_thresholds=0.0, mf_initial_range=1e-3))
        from paddlebox_tpu.train import BoxTrainer
        model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                       hidden=(16,))
        tr = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2),
                        seed=seed)
        losses = []
        for _ in range(passes):
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
        flags.set_flag("push_block_rows", 1024)
        flags.set_flag("slab_embed_dtype", "float32")


def assert_identical(a, b):
    la, ka, va, pa = a
    lb, kb, vb, pb = b
    assert la == lb
    assert np.array_equal(ka, kb)
    assert np.array_equal(va, vb)
    import jax
    for xa, xb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


def test_blocked_e2e_matches_scatter_full_wire(data):
    """push_write=blocked on BoxTrainer's wire (sorted dedup staging) at
    chunk>1 over 2 passes: bit-identical training to scatter."""
    files, feed = data
    base = run_mode(files, feed, "scatter")
    blocked = run_mode(files, feed, "blocked")
    assert_identical(base, blocked)


@pytest.mark.slow
def test_blocked_bf16_matches_scatter_bf16(data):
    """The two tentpole layers compose: under the bf16 slab diet the
    write placement is still bit-identical between scatter and blocked
    (same encoded rows, different placement) — so the diet's AUC gate
    transfers to the blocked path for free.

    Slow tier (round 14, budget): pure composition — the codec's bit
    round-trip, bf16 AUC parity, and blocked-vs-scatter parity each
    stay pinned in tier-1 on their own."""
    files, feed = data
    base = run_mode(files, feed, "scatter", embed_dtype="bfloat16",
                    passes=1)
    blocked = run_mode(files, feed, "blocked", embed_dtype="bfloat16",
                       passes=1)
    assert_identical(base, blocked)


def test_bf16_slab_trains_with_auc_parity(data):
    """The bf16 AUC-parity gate (no bit oracle: weights round at every
    slab write): same data, same seeds, slab f32 vs bf16 — streaming AUC
    must stay within the tolerance (gated at 0.01) and both clearly
    above chance."""
    from paddlebox_tpu.train import BoxTrainer

    files, feed = data

    def train_auc(embed_dtype):
        flags.set_flag("slab_embed_dtype", embed_dtype)
        try:
            table = TableConfig(
                embedx_dim=D, pass_capacity=2048,
                optimizer=SparseOptimizerConfig(
                    mf_create_thresholds=0.0, mf_initial_range=1e-3,
                    feature_learning_rate=0.1, mf_learning_rate=0.1))
            model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                           hidden=(32, 16))
            tr = BoxTrainer(model, table, feed,
                            TrainerConfig(dense_lr=3e-3, scan_chunk=2),
                            seed=0)
            assert tr.table.layout.embed_dtype == embed_dtype
            tr.metrics.init_metric("auc", "label", "pred",
                                   table_size=1 << 14, mask_var="mask")
            for _ in range(4):
                ds = BoxDataset(feed, read_threads=1)
                ds.set_filelist(files)
                tr.train_pass(ds)
                ds.release_memory()
            auc = tr.metrics.get_metric_msg("auc")["auc"]
            tr.close()
            return auc
        finally:
            flags.set_flag("slab_embed_dtype", "float32")

    auc_f32 = train_auc("float32")
    auc_b16 = train_auc("bfloat16")
    # streaming AUC mixes the untrained first pass; the gate is signal
    # clearly above chance, not the fully-trained test_e2e bar
    assert auc_f32 > 0.55 and auc_b16 > 0.55, (auc_f32, auc_b16)
    assert abs(auc_f32 - auc_b16) < 0.01, (auc_f32, auc_b16)


def test_bf16_checkpoint_roundtrip(data, tmp_path):
    """Checkpoint save/load under the bf16 slab: the store (host f32)
    round-trips bit-exactly — optimizer stats included — and the
    restored trainer keeps training on the dieted slab."""
    from paddlebox_tpu.config.configs import CheckpointConfig
    from paddlebox_tpu.train import BoxTrainer, CheckpointManager

    files, feed = data
    flags.set_flag("slab_embed_dtype", "bfloat16")
    try:
        table = TableConfig(
            embedx_dim=D, pass_capacity=2048,
            optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                            mf_initial_range=1e-3))
        model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                       hidden=(16,))
        tr = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2),
                        seed=2)
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        tr.train_pass(ds)
        ds.release_memory()
        cfg = CheckpointConfig(batch_model_dir=str(tmp_path / "batch"),
                               xbox_model_dir=str(tmp_path / "xbox"))
        cm = CheckpointManager(cfg, tr.table)
        # snapshot BEFORE save: save_base's synchronous post-save stat
        # mutation (clear delta score, age unseen days) changes the live
        # store right after the file snapshot is taken
        k0, v0 = tr.table.store.state_items()
        k0, v0 = k0.copy(), v0.copy()
        order0 = np.argsort(k0)
        cm.save_base(tr.params, tr.opt_state, "d0")
        cm.wait()
        tr.close()

        tr2 = BoxTrainer(model, table, feed, TrainerConfig(scan_chunk=2),
                         seed=2)
        cm2 = CheckpointManager(cfg, tr2.table)
        tr2.params, tr2.opt_state, _ = cm2.load_base("d0")
        k1, v1 = tr2.table.store.state_items()
        order1 = np.argsort(k1)
        np.testing.assert_array_equal(k0[order0], k1[order1])
        np.testing.assert_array_equal(v0[order0].view(np.uint32),
                                      v1[order1].view(np.uint32))
        # and the restored table still trains on the dieted slab
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files[:1])
        loss = tr2.train_pass(ds)["loss"]
        assert np.isfinite(loss)
        ds.release_memory()
        tr2.close()
    finally:
        flags.set_flag("slab_embed_dtype", "float32")


# -------------------------------------------------------------- sharded

@pytest.mark.slow
def test_sharded_blocked_matches_scatter(data):
    """The 8-shard trainer with push_write=blocked (per-shard sorted
    staging via stage_push_dedup sort_uids; block must divide SHARD
    capacity) trains bit-identically to scatter — full wire AND uid
    wire."""
    from paddlebox_tpu.parallel import ShardedBoxTrainer

    files, feed = data
    states = {}
    for mode, uid in (("scatter", False), ("blocked", False),
                      ("scatter", True), ("blocked", True)):
        flags.set_flag("push_write", mode)
        flags.set_flag("push_block_rows", 128)   # shard_cap = 512
        flags.set_flag("h2d_uid_wire", uid)
        try:
            table_cfg = TableConfig(
                embedx_dim=D, pass_capacity=8 * (1 << 9),
                optimizer=SparseOptimizerConfig(
                    mf_create_thresholds=0.0, mf_initial_range=1e-3,
                    feature_learning_rate=0.1, mf_learning_rate=0.1))
            model = CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                           hidden=(16,))
            trainer = ShardedBoxTrainer(model, table_cfg, feed,
                                        TrainerConfig(dense_lr=3e-3),
                                        seed=4)
            assert trainer._push_write == mode
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            trainer.train_pass(ds)
            states[(mode, uid)] = [st.state_items()
                                   for st in trainer.table.stores]
            trainer.close()
        finally:
            flags.set_flag("push_write", "auto")
            flags.set_flag("push_block_rows", 1024)
            flags.set_flag("h2d_uid_wire", True)
    for uid in (False, True):
        for (k_b, v_b), (k_s, v_s) in zip(states[("blocked", uid)],
                                          states[("scatter", uid)]):
            np.testing.assert_array_equal(k_b, k_s)
            np.testing.assert_array_equal(v_b, v_s)


def test_two_virtual_process_blocked_staging():
    """2-virtual-process staging for the blocked write: sort_uids=True
    through the multiprocess bucket exchange delivers per-destination
    SORTED full products identical to single-process, and the blocked
    write over them matches the scatter oracle bit for bit."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp

    from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout
    from paddlebox_tpu.embedding.optimizers import push_sparse_hostdedup
    from paddlebox_tpu.parallel.sharded_table import stage_push_dedup

    P, KB, shard_cap = 8, 16, 128
    rng = np.random.RandomState(8)
    buckets = np.full((P, P, KB), shard_cap - 1, np.int32)
    for s in range(P):
        for d in range(P):
            n = rng.randint(2, KB)
            buckets[s, d, :n] = rng.randint(0, shard_cap - 1, n)
    pool = concurrent.futures.ThreadPoolExecutor(2)
    try:
        single = stage_push_dedup(list(buckets), list(range(P)), P,
                                  shard_cap, multiprocess=False,
                                  all_gather=None, rebuild=False, pool=pool,
                                  sort_uids=True)
        for d in range(P):
            assert np.all(np.diff(
                single["push_uids"][d].astype(np.int64)) > 0), d

        def payload_of(bl, positions):
            bl = np.ascontiguousarray(bl, np.int32)
            header = np.array([len(positions), P, KB] + list(positions),
                              np.int32)
            return np.concatenate([header, bl.ravel()])

        parts = [payload_of(buckets[0:4], [0, 1, 2, 3]),
                 payload_of(buckets[4:8], [4, 5, 6, 7])]
        out = {}
        for lo, positions in ((0, [0, 1, 2, 3]), (4, [4, 5, 6, 7])):
            staged = stage_push_dedup(
                list(buckets[lo:lo + 4]), positions, P, shard_cap,
                multiprocess=True, all_gather=lambda payload: parts,
                rebuild=False, pool=pool, sort_uids=True)
            for i, d in enumerate(positions):
                out[d] = tuple(staged[k][i] for k in
                               ("push_uids", "push_perm", "push_inv"))
        layout = ValueLayout(D, "adagrad")
        conf = SparseOptimizerConfig(mf_create_thresholds=0.0,
                                     mf_initial_range=1e-3)
        push = PushLayout(D)
        flags.set_flag("push_block_rows", 32)
        try:
            for d in range(P):
                uids, perm, inv = out[d]
                np.testing.assert_array_equal(uids, single["push_uids"][d])
                incoming = np.concatenate([buckets[s][d] for s in range(P)])
                grads = rng.randn(incoming.size,
                                  push.width).astype(np.float32)
                grads[:, push.SHOW] = 1.0
                grads[incoming == shard_cap - 1] = 0.0
                slab = rng.rand(shard_cap, layout.width).astype(np.float32)
                prng = jax.random.PRNGKey(d)
                oracle = push_sparse_hostdedup(
                    jnp.asarray(slab), jnp.asarray(uids), jnp.asarray(perm),
                    jnp.asarray(inv), jnp.asarray(grads), prng, layout,
                    conf)
                got = push_sparse_hostdedup(
                    jnp.asarray(slab), jnp.asarray(uids), jnp.asarray(perm),
                    jnp.asarray(inv), jnp.asarray(grads), prng, layout,
                    conf, write="blocked")
                np.testing.assert_array_equal(np.asarray(oracle),
                                              np.asarray(got),
                                              err_msg=f"dest {d}")
        finally:
            flags.set_flag("push_block_rows", 1024)
    finally:
        pool.shutdown(wait=False)
