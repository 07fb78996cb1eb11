"""End-to-end spine test: synthetic CTR data → dataset load/feed-pass →
pass-table → fused train step → streaming AUC lift → checkpoint/resume.
The Python analog of running the reference's full BoxPS cadence without the
closed binary (SURVEY.md §4's missing tier)."""

import glob
import json
import os

import numpy as np
import pytest

from paddlebox_tpu.config.configs import (CheckpointConfig,
                                          SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.metrics import BasicAucCalculator
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.train import BoxTrainer, CheckpointManager

D = 8
NUM_SLOTS = 4
BENCH_CONFIGS = sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "*.json")))


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = tmp_path_factory.mktemp("ctr_data")
    files, feed = write_synthetic_ctr_files(
        str(out), num_files=3, lines_per_file=800, num_slots=NUM_SLOTS,
        vocab_per_slot=200, max_len=3, seed=7)
    feed = type(feed)(slots=feed.slots, batch_size=128)
    return files, feed


def make_trainer(feed, seed=0):
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=1 << 13,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)
    model = CtrDnn(spec, hidden=(64, 32))
    return BoxTrainer(model, table_cfg, feed,
                      TrainerConfig(dense_lr=3e-3), seed=seed)


def test_e2e_auc_lift(data):
    files, feed = data
    trainer = make_trainer(feed)
    trainer.metrics.init_metric("auc", "label", "pred", table_size=1 << 14,
                                mask_var="mask")

    for epoch in range(6):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        stats = trainer.train_pass(ds)
        assert stats["instances"] == 2400
        ds.release_memory()

    msg = trainer.metrics.get_metric_msg("auc")
    # streaming AUC mixes all passes (incl. the untrained first one); the
    # learnable signal must still pull it clearly above chance
    assert msg["auc"] > 0.6, msg
    assert msg["size"] == 6 * 2400

    # fresh-eval AUC must beat 0.65 after training
    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files)
    trainer.table.begin_feed_pass()
    ds.load_into_memory(add_keys_fn=trainer.table.add_keys)
    trainer.table.end_feed_pass()
    preds, labels = trainer.predict_batches(ds)
    calc = BasicAucCalculator(1 << 14)
    calc.add_data(preds, labels)
    calc.compute()
    assert calc.auc() > 0.7, calc.auc()


def test_checkpoint_resume(data, tmp_path):
    files, feed = data
    trainer = make_trainer(feed)
    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files[:1])
    trainer.train_pass(ds)

    ckpt_cfg = CheckpointConfig(
        batch_model_dir=str(tmp_path / "batch"),
        xbox_model_dir=str(tmp_path / "xbox"),
        async_save=False)
    cm = CheckpointManager(ckpt_cfg, trainer.table)
    batch_dir, xbox_dir = cm.save_base(trainer.params, trainer.opt_state,
                                       day="20260729")

    # resume into a fresh trainer and verify predictions match
    trainer2 = make_trainer(feed, seed=123)
    cm2 = CheckpointManager(ckpt_cfg, trainer2.table)
    params, opt_state, _ = cm2.load_base("20260729")
    trainer2.params = params
    trainer2.opt_state = opt_state

    ds_eval = BoxDataset(feed, read_threads=1)
    ds_eval.set_filelist(files[:1])
    t1 = trainer
    t1.table.begin_feed_pass()
    ds_eval.load_into_memory(add_keys_fn=t1.table.add_keys)
    t1.table.end_feed_pass()
    p1, _ = t1.predict_batches(ds_eval)

    ds_eval2 = BoxDataset(feed, read_threads=1)
    ds_eval2.set_filelist(files[:1])
    trainer2.table.begin_feed_pass()
    ds_eval2.load_into_memory(add_keys_fn=trainer2.table.add_keys)
    trainer2.table.end_feed_pass()
    p2, _ = trainer2.predict_batches(ds_eval2)

    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_delta_save_covers_touched_keys(data, tmp_path):
    files, feed = data
    trainer = make_trainer(feed)
    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files[:1])
    trainer.train_pass(ds)

    from paddlebox_tpu.serving.store import read_xbox_view
    ckpt_cfg = CheckpointConfig(
        batch_model_dir=str(tmp_path / "batch"),
        xbox_model_dir=str(tmp_path / "xbox"),
        async_save=False)
    cm = CheckpointManager(ckpt_cfg, trainer.table)
    xbox_dir = cm.save_delta("20260729", delta_id=1)
    keys1, emb1 = read_xbox_view(xbox_dir)
    # every trained feature crossed delta_threshold=0.25 (each occurrence
    # adds >= nonclk_coeff*1=0.1... clicks add 1.0), so delta covers most
    assert keys1.size > 0
    assert emb1.shape[1] == 1 + D
    # second delta immediately after: nothing new crossed the threshold
    xbox_dir2 = cm.save_delta("20260729", delta_id=2)
    keys2, _emb2 = read_xbox_view(xbox_dir2)
    assert keys2.size < keys1.size


def test_push_write_rebuild_matches_scatter(data):
    """push_write='rebuild' (gather-rebuild slab write; the TPU-side
    default via 'auto') must train bit-identically to the scatter path —
    whole pass, real feed, host dedup + pos staged per batch."""
    from paddlebox_tpu.config import flags
    files, feed = data
    slabs = {}
    for mode in ("scatter", "rebuild"):
        flags.set_flag("push_write", mode)
        try:
            trainer = make_trainer(feed, seed=9)
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            trainer.train_pass(ds)
            keys = np.sort(trainer.table._pass_keys)
            slabs[mode] = (keys, trainer.table.store.lookup(keys).copy())
        finally:
            flags.set_flag("push_write", "auto")
    np.testing.assert_array_equal(slabs["scatter"][0], slabs["rebuild"][0])
    np.testing.assert_array_equal(slabs["scatter"][1], slabs["rebuild"][1])


def test_push_write_auto_heuristic(monkeypatch):
    """'auto' picks by the measured crossover on tpu backends (rebuild's
    full-slab rewrite loses once the slab dwarfs the per-batch key
    budget) and always scatters on CPU."""
    import jax as _jax
    from paddlebox_tpu.train.trainer import resolve_push_write
    assert resolve_push_write(1 << 20, 131072) == "scatter"  # cpu backend
    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    assert resolve_push_write(1 << 20, 131072) == "rebuild"
    assert resolve_push_write(1 << 22, 131072) == "scatter"  # 32x keys
    assert resolve_push_write(None, None) == "rebuild"       # no hints


@pytest.mark.parametrize("path", BENCH_CONFIGS, ids=lambda p: os.path.basename(
    p)[:-len(".json")])
def test_push_write_auto_picks_each_benchmark_configs_write(path,
                                                            monkeypatch):
    """The write 'auto' picks on the chip for each benchmark configuration,
    from the feed the harness builds (one key a slot) and the
    configuration's pass_capacity: deepfm-criteo's 67.1M rows against
    79,872 keys a batch and dlrm-mlperf's 6.25M against 53,248 scatter;
    the sequence towers' 16k-32k rows against 8k-16k keys rebuild."""
    import jax as _jax

    from benchmarks.harness.traffic import feed_config
    from paddlebox_tpu.train.trainer import resolve_push_write
    with open(path) as f:
        cfg = json.load(f)
    name = os.path.basename(path)[:-len(".json")]
    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    got = resolve_push_write(capacity=int(cfg["pass_capacity"]),
                             batch_keys=feed_config(cfg).key_capacity())
    want = ("scatter" if name in ("deepfm-criteo", "dlrm-mlperf")
            else "rebuild")
    assert got == want


def test_chunk_prefetch_matches_inline(data):
    """The chunk-staging prefetch thread (chunk_prefetch_depth) must be
    invisible to results: bit-identical trained state vs inline staging,
    and a staging error must surface at the caller, not die on the
    producer thread."""
    from paddlebox_tpu.config import flags
    states = {}
    for depth in (0, 2):
        flags.set_flag("chunk_prefetch_depth", depth)
        try:
            files, feed = data
            trainer = make_trainer(feed, seed=21)
            ds = BoxDataset(feed, read_threads=1)
            ds.set_filelist(files[:1])
            trainer.train_pass(ds)
            keys = np.sort(trainer.table._pass_keys)
            states[depth] = (keys, trainer.table.store.lookup(keys).copy())
        finally:
            flags.set_flag("chunk_prefetch_depth", 1)
    np.testing.assert_array_equal(states[0][0], states[2][0])
    np.testing.assert_array_equal(states[0][1], states[2][1])

    # producer-thread staging errors surface at the consumer
    from paddlebox_tpu.train.trainer import run_scan_chunks

    def bad_stack(group):
        raise RuntimeError("staging boom")

    with pytest.raises(RuntimeError, match="staging boom"):
        run_scan_chunks(lambda c, s: (c, None, None), list(range(8)), 4,
                        bad_stack, (), lambda *a: None, prefetch_depth=1)


def test_chunk_prefetch_stager_stops_on_consumer_error():
    """A consumer-side error (e.g. the nan guard) must STOP the producer
    thread — a zombie stager would keep reading the table into the
    caller's next pass (the shard_batches race)."""
    import threading
    import time as _time
    from paddlebox_tpu.train.trainer import run_scan_chunks

    staged = []

    def slow_stack(group):
        staged.append(group)
        _time.sleep(0.05)
        return group

    calls = []

    def scan_call(carry, stacked):
        calls.append(stacked)
        if len(calls) == 2:
            raise FloatingPointError("nan guard")
        return carry, np.zeros(4), None

    before = threading.active_count()
    with pytest.raises(FloatingPointError):
        run_scan_chunks(scan_call, list(range(64)), 4, slow_stack, (),
                        lambda *a: None, prefetch_depth=2)
    # the producer must wind down promptly, not stage all 16 chunks
    _time.sleep(0.5)
    assert threading.active_count() <= before
    assert len(staged) < 16
