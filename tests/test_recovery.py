"""Elastic recovery wiring (VERDICT r1 weak #8): heartbeat death detection
→ pass-boundary stop → restart resumes from the last completed pass with
bit-exact state."""

import numpy as np
import pytest

from paddlebox_tpu.config.configs import (CheckpointConfig,
                                          SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.fleet.elastic import DeadRankError, ElasticManager
from paddlebox_tpu.fleet.store import KVStoreServer, TcpStoreClient
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.train.checkpoint import CheckpointManager
from paddlebox_tpu.train.recovery import RecoverableRunner
from paddlebox_tpu.train.trainer import BoxTrainer

D = 4
NUM_SLOTS = 4


@pytest.fixture(autouse=True)
def no_shuffle():
    from paddlebox_tpu.config import flags
    flags.set_flag("dataset_disable_shuffle", True)
    yield
    flags.set_flag("dataset_disable_shuffle", False)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("recov")
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=2, lines_per_file=200, num_slots=NUM_SLOTS,
        vocab_per_slot=80, max_len=3, seed=17)
    feed = type(feed)(slots=feed.slots, batch_size=32)
    return files, feed


def make_trainer(feed, seed=0):
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=1 << 13,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))
    return BoxTrainer(CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                             hidden=(16,)),
                      table_cfg, feed, TrainerConfig(dense_lr=0.01),
                      seed=seed)


def datasets(files, feed, n):
    out = []
    for _ in range(n):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        out.append(ds)
    return out


def ckpt_cfg(tmp_path, name):
    return CheckpointConfig(batch_model_dir=str(tmp_path / name / "batch"),
                            xbox_model_dir=str(tmp_path / name / "xbox"),
                            async_save=False)


def _store_state(trainer):
    keys, vals = trainer.table.store.state_items()
    order = np.argsort(keys)
    return keys[order], vals[order]


def test_crash_resume_matches_uninterrupted(data, tmp_path):
    files, feed = data

    # oracle: 4 uninterrupted passes under the same runner
    oracle = make_trainer(feed)
    r0 = RecoverableRunner(oracle, CheckpointManager(
        ckpt_cfg(tmp_path, "oracle"), oracle.table), day="d1")
    r0.run(datasets(files, feed, 4))

    # crashing job: dies after pass 2 (mid-sequence), restarts, resumes
    cfg = ckpt_cfg(tmp_path, "crash")
    t1 = make_trainer(feed)
    r1 = RecoverableRunner(t1, CheckpointManager(cfg, t1.table), day="d1")

    class Boom(RuntimeError):
        pass

    dss = datasets(files, feed, 4)
    orig = t1.train_pass
    calls = {"n": 0}

    def crashing_train_pass(ds, **kw):
        if calls["n"] == 2:
            raise Boom()
        calls["n"] += 1
        return orig(ds, **kw)

    t1.train_pass = crashing_train_pass
    with pytest.raises(Boom):
        r1.run(dss)

    # "restart": a FRESH process = fresh trainer + runner over the same dir
    t2 = make_trainer(feed, seed=0)
    r2 = RecoverableRunner(t2, CheckpointManager(cfg, t2.table), day="d1")
    assert r2.completed_passes() == 2
    r2.run(datasets(files, feed, 4))

    # bit-exact parity with the uninterrupted run
    k_ref, v_ref = _store_state(oracle)
    k_got, v_got = _store_state(t2)
    np.testing.assert_array_equal(k_got, k_ref)
    np.testing.assert_allclose(v_got, v_ref, rtol=1e-6, atol=1e-7)
    import jax
    for a, b in zip(jax.tree.leaves(oracle.params),
                    jax.tree.leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6)


def test_dead_rank_stops_at_pass_boundary(data, tmp_path):
    """A peer death flips the elastic watcher; the runner raises at the
    next pass boundary; the checkpoint marker survives for resume."""
    files, feed = data
    server = KVStoreServer(host="127.0.0.1")
    cl0 = TcpStoreClient("127.0.0.1", server.port)
    cl1 = TcpStoreClient("127.0.0.1", server.port)
    # generous staleness margin: under full-suite load a LIVE peer's
    # heartbeat thread can starve past a tight window and get flagged
    # before the scripted death (flaked at 0.3s)
    e0 = ElasticManager(cl0, rank=0, world=2, heartbeat_interval=0.05,
                        stale_after=2.0)
    e1 = ElasticManager(cl1, rank=1, world=2, heartbeat_interval=0.05,
                        stale_after=2.0)
    e0.start()
    e1.start()

    trainer = make_trainer(feed)
    cfg = ckpt_cfg(tmp_path, "elastic")
    runner = RecoverableRunner(trainer, CheckpointManager(cfg, trainer.table),
                               day="d1", elastic=e0)

    dss = datasets(files, feed, 6)
    orig = trainer.train_pass
    calls = {"n": 0}

    import time

    def pass_and_kill_peer(ds, **kw):
        out = orig(ds, **kw)
        calls["n"] += 1
        if calls["n"] == 2:
            e1.stop()  # rank 1 "dies" after the 2nd pass
            deadline = time.time() + 10
            while not e0.dead_ranks and time.time() < deadline:
                time.sleep(0.05)  # let the watcher flag it
        return out

    trainer.train_pass = pass_and_kill_peer
    with pytest.raises(DeadRankError):
        runner.run(dss)
    # at least the first two passes completed and are resumable
    assert runner.completed_passes() >= 2
    assert e0.dead_ranks == [1]
    e0.stop()
    cl0.close()
    cl1.close()
    server.stop()


# tier-1 budget (round-10 headroom audit, 6.8s): crash-resume parity
# is guarded by test_crash_resume_matches_uninterrupted; this variant
# re-runs it with the shuffle stage whose determinism test_shuffle
# covers. Runs in the slow-inclusive suite and on TPU windows
@pytest.mark.slow
def test_crash_resume_parity_with_shuffle_enabled(data, tmp_path):
    """The checkpoint carries the shuffle RNG state, so resume is
    bit-identical even with per-pass local shuffle ON."""
    from paddlebox_tpu.config import flags
    flags.set_flag("dataset_disable_shuffle", False)  # override fixture
    files, feed = data

    oracle = make_trainer(feed)
    r0 = RecoverableRunner(oracle, CheckpointManager(
        ckpt_cfg(tmp_path, "sh_oracle"), oracle.table), day="d1")
    r0.run(datasets(files, feed, 4))

    cfg = ckpt_cfg(tmp_path, "sh_crash")
    t1 = make_trainer(feed)
    r1 = RecoverableRunner(t1, CheckpointManager(cfg, t1.table), day="d1")
    r1.run(datasets(files, feed, 2))  # "crash" after 2 completed passes

    t2 = make_trainer(feed, seed=0)
    r2 = RecoverableRunner(t2, CheckpointManager(cfg, t2.table), day="d1")
    r2.run(datasets(files, feed, 4))

    k_ref, v_ref = _store_state(oracle)
    k_got, v_got = _store_state(t2)
    np.testing.assert_array_equal(k_got, k_ref)
    np.testing.assert_allclose(v_got, v_ref, rtol=1e-6, atol=1e-7)


def test_crash_resume_matches_uninterrupted_on_a_drifting_key_set(tmp_path):
    """Each pass reads another file, so the working set drifts: a key
    that stays keeps its slab row, and a row is then the key's history,
    not its rank. A push creates embeddings at random (mf_initial_range
    1e-3), drawn from (prng, slab row). Every checkpoint save resets the
    assignment (invalidate_residency), so the run resumed from one, whose
    fresh table assigns rows by rank, draws what the uninterrupted run
    draws: bit for bit the same weights, embeddings and optimizer state.
    (delta_score and unseen_days are left out: a key absent from the
    resumed passes reads one save's stat rewrite more or less after a
    resume, before this assignment existed too.)"""
    from paddlebox_tpu.embedding import accessor as acc
    files, feed = write_synthetic_ctr_files(
        str(tmp_path / "drift"), num_files=4, lines_per_file=200,
        num_slots=NUM_SLOTS, vocab_per_slot=400, max_len=3, seed=23)
    feed = type(feed)(slots=feed.slots, batch_size=32)

    def one_file_a_pass(n=4):
        out = []
        for f in files[:n]:
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist([f])
            out.append(ds)
        return out

    # the drift is real: with no save between the passes rows leave rank
    plain = make_trainer(feed)
    sets = []
    for ds in one_file_a_pass(2):
        plain.train_pass(ds)
        sets.append(plain.table._pass_keys)
    assert 0 < np.isin(sets[1], sets[0]).sum() < sets[1].size
    assert not plain.table._rows.dense

    oracle = make_trainer(feed)
    r0 = RecoverableRunner(oracle, CheckpointManager(
        ckpt_cfg(tmp_path, "dr_oracle"), oracle.table), day="d1")
    r0.run(one_file_a_pass())
    assert oracle.table._rows.dense      # every save reset the assignment

    cfg = ckpt_cfg(tmp_path, "dr_crash")
    t1 = make_trainer(feed)
    r1 = RecoverableRunner(t1, CheckpointManager(cfg, t1.table), day="d1")
    r1.run(one_file_a_pass(2))  # "crash" after 2 completed passes

    t2 = make_trainer(feed, seed=0)
    r2 = RecoverableRunner(t2, CheckpointManager(cfg, t2.table), day="d1")
    assert r2.completed_passes() == 2
    r2.run(one_file_a_pass())

    k_ref, v_ref = _store_state(oracle)
    k_got, v_got = _store_state(t2)
    np.testing.assert_array_equal(k_got, k_ref)
    cols = np.setdiff1d(np.arange(v_ref.shape[1]),
                        [acc.DELTA_SCORE, acc.UNSEEN_DAYS])
    assert (v_ref[:, acc.MF_SIZE] > 0).any()     # embeddings were created
    np.testing.assert_array_equal(v_got[:, cols], v_ref[:, cols])


def test_sharded_crash_resume_matches_uninterrupted(data, tmp_path):
    """The same pass-boundary recovery loop over the SHARDED trainer:
    per-pass base checkpoints ride the store_view facade, a restarted
    fresh trainer resumes from the last DONE pass and converges to the
    uninterrupted run (store rows + dense params)."""
    from paddlebox_tpu.parallel import ShardedBoxTrainer
    from paddlebox_tpu.parallel.mesh import device_mesh_1d

    files, feed = data

    def make_sharded(seed=0):
        table_cfg = TableConfig(
            embedx_dim=D, pass_capacity=1 << 13,
            optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                            mf_initial_range=1e-3,
                                            feature_learning_rate=0.1,
                                            mf_learning_rate=0.1))
        return ShardedBoxTrainer(
            CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                   hidden=(16,)),
            table_cfg, feed, TrainerConfig(dense_lr=0.01, scan_chunk=1),
            mesh=device_mesh_1d(8), seed=seed)

    def sharded_state(trainer):
        keys, vals = trainer.table.store_view().state_items()
        order = np.argsort(keys)
        return keys[order], vals[order]

    oracle = make_sharded()
    r0 = RecoverableRunner(oracle, CheckpointManager(
        ckpt_cfg(tmp_path, "sh_oracle"), oracle.table), day="d1")
    r0.run(datasets(files, feed, 4))

    cfg = ckpt_cfg(tmp_path, "sh_crash")
    t1 = make_sharded()
    r1 = RecoverableRunner(t1, CheckpointManager(cfg, t1.table), day="d1")

    class Boom(RuntimeError):
        pass

    orig = t1.train_pass
    calls = {"n": 0}

    def crashing_train_pass(ds, **kw):
        if calls["n"] == 2:
            raise Boom()
        calls["n"] += 1
        return orig(ds, **kw)

    t1.train_pass = crashing_train_pass
    with pytest.raises(Boom):
        r1.run(datasets(files, feed, 4))

    t2 = make_sharded(seed=0)
    r2 = RecoverableRunner(t2, CheckpointManager(cfg, t2.table), day="d1")
    assert r2.completed_passes() == 2
    r2.run(datasets(files, feed, 4))

    k_ref, v_ref = sharded_state(oracle)
    k_got, v_got = sharded_state(t2)
    np.testing.assert_array_equal(k_got, k_ref)
    np.testing.assert_allclose(v_got, v_ref, rtol=1e-5, atol=1e-7)
    import jax
    for a, b in zip(jax.tree.leaves(oracle.params),
                    jax.tree.leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-7)


def test_crash_resume_under_rebuild_push(data, tmp_path):
    """Crash-resume parity must hold with push_write='rebuild' (the
    tpu-side default via 'auto'): the recovered run's state matches the
    uninterrupted one exactly, as in the scatter-mode test above."""
    from paddlebox_tpu.config import flags
    flags.set_flag("push_write", "rebuild")
    try:
        test_crash_resume_matches_uninterrupted(data, tmp_path)
    finally:
        flags.set_flag("push_write", "auto")
