"""The bf16 slab dtype diet (slab_embed_dtype='bfloat16', accessor slab
codec): weight columns round to bf16 at the slab write; the header and ALL
optimizer stats round-trip BIT-EXACTLY through encode/decode, the
store/checkpoint round trip, and a full pass. Training quality is
AUC-parity gated (no bit oracle).
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
    out = tmp_path_factory.mktemp("slab_bf16_data")
    # small vocab → heavy key recurrence: many rows rewritten per pass
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=2, lines_per_file=480, num_slots=NUM_SLOTS,
        vocab_per_slot=120, max_len=3, seed=13)
    feed = type(feed)(slots=feed.slots, batch_size=64)
    return files, feed


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
