"""Shared helpers for bench.py and the tools — ONE definition of
the synthetic workload and of the D2H-synced timing loop, so the probe
decomposes exactly the number the bench reports."""

import time
from typing import List

import numpy as np


def make_ctr_batches(feed, n_batches: int, num_slots: int, max_len: int,
                     seed: int = 0) -> List:
    """The bench's synthetic CTR batches: ~(max_len+1)/2 keys per slot per
    instance, globally slot-disambiguated uint64 feasigns, 25% positives."""
    from paddlebox_tpu.data.packer import BatchPacker
    from paddlebox_tpu.data.slot_record import SlotRecord

    rng = np.random.RandomState(seed)
    packer = BatchPacker(feed)
    out = []
    for _ in range(n_batches):
        recs = []
        for _ in range(feed.batch_size):
            slots = {}
            for si in range(num_slots):
                n = rng.randint(1, max_len + 1)
                feas = (rng.randint(0, 1 << 22, n).astype(np.uint64)
                        * np.uint64(num_slots) + np.uint64(si))
                slots[si] = feas
            recs.append(SlotRecord(label=int(rng.rand() < 0.25),
                                   uint64_slots=slots))
        out.append(packer.pack(recs))
    return out


def timed_scan_chain(scan, state, stacked, reps: int, warmup: int = 2):
    """Run `scan(slab, params, opt_state, stacked, prng)` reps times with the
    state threaded through (each call consumes the previous call's outputs)
    and return seconds per call. The sync point is np.asarray of the LAST
    call's losses — data that depends on the whole chain, a sync that does
    not rest on block_until_ready (which does wait on the attached v5e —
    CHANGES.md PR 21; folding the two mechanisms into one is ROADMAP
    S0)."""
    if warmup < 1:
        raise ValueError("warmup must be >= 1 (the first call compiles)")
    for _ in range(warmup):
        slab, params, opt, losses, _p, key = scan(
            state[0], state[1], state[2], stacked, state[3])
        state = (slab, params, opt, key)
    warm = np.asarray(losses)
    if not np.isfinite(warm).all():
        raise FloatingPointError(f"non-finite warmup losses {warm}")
    t0 = time.perf_counter()
    for _ in range(reps):
        slab, params, opt, losses, _p, key = scan(
            state[0], state[1], state[2], stacked, state[3])
        state = (slab, params, opt, key)
    final = np.asarray(losses)
    dt = (time.perf_counter() - t0) / reps
    if not np.isfinite(final).all():
        raise FloatingPointError(f"non-finite losses {final}")
    return dt


def measure_pass_amortized(trainer, batches, batch_size: int,
                           overlaps=(0.0, 0.9), n_passes: int = 3,
                           workset_rows: int = 1 << 18, seed: int = 123):
    """Honest pass-amortized throughput (round-6 verdict item 2): wall
    clock of the FULL pass lifecycle — begin_feed → build → train →
    end_pass — not just the resident jitted step, for both the full and
    the incremental lifecycle at each working-set overlap ratio. The
    working set is the synthetic batch keys plus `workset_rows` filler
    keys that evolve with ~overlap retention between passes (the filler
    plays the day's long-tail: promoted every pass, never touched by a
    push, exactly the rows the delta lifecycle refuses to move twice).

    Pass 1 of each config is the cold build and is excluded from the
    reported means. Every timed segment ends in a real D2H (np.asarray of
    chain-dependent data).

    Returns the nested dict bench.py emits under "pass_amortized"."""
    from paddlebox_tpu.config import flags as _flags

    tab = trainer.table
    scan = trainer.fns.scan_steps
    # earlier measurement phases leave the table mid-pass with a hacked
    # slab; reset to a clean between-passes state
    tab._in_pass = False
    tab._slab = None
    tab._touched = None
    tab.invalidate_residency()

    batch_keys = np.unique(np.concatenate(
        [np.asarray(b.keys[b.valid], np.uint64) for b in batches]))
    ws = min(workset_rows, max(0, tab.capacity - 1 - int(batch_keys.size)
                               - workset_rows // 8))
    examples = len(batches) * batch_size
    saved_flag = _flags.get_flag("incremental_pass")

    def filler_seq(overlap, rng, n):
        cur = np.unique(rng.randint(0, 1 << 40, ws).astype(np.uint64))
        out = [cur]
        for _ in range(n - 1):
            keep = rng.rand(cur.size) < overlap
            fresh = np.unique(rng.randint(
                0, 1 << 40, max(1, int(ws * (1.0 - overlap))))
                .astype(np.uint64))
            cur = np.unique(np.concatenate([cur[keep], fresh]))
            out.append(cur)
        return out

    def one_pass(filler):
        t0 = time.perf_counter()
        tab.begin_feed_pass()
        tab.add_keys(filler)
        for b in batches:
            tab.add_keys(b.keys[b.valid])
        tab.end_feed_pass()
        tab.begin_pass()
        np.asarray(tab.slab[0, 0:1])  # D2H sync: promote really done
        t1 = time.perf_counter()
        stacked = trainer._stack_batches(batches)
        slab, params, opt, losses, _preds, key = scan(
            tab.slab, trainer.params, trainer.opt_state, stacked,
            tab.next_prng())
        np.asarray(losses)  # D2H sync for the whole chunk
        tab.set_slab(slab)
        trainer.params, trainer.opt_state = params, opt
        t2 = time.perf_counter()
        tab.end_pass()
        t3 = time.perf_counter()
        return t1 - t0, t2 - t1, t3 - t2

    out = {"workset_rows": int(ws), "batches_per_pass": len(batches),
           "examples_per_pass": examples}
    try:
        for overlap in overlaps:
            cellpair = {}
            for mode, incremental in (("full", False), ("incremental", True)):
                _flags.set_flag("incremental_pass", incremental)
                tab.invalidate_residency()
                fillers = filler_seq(overlap, np.random.RandomState(seed),
                                     n_passes)
                segs = [one_pass(f) for f in fillers]
                warm = segs[1:] or segs
                build = float(np.mean([s[0] for s in warm]))
                train = float(np.mean([s[1] for s in warm]))
                end = float(np.mean([s[2] for s in warm]))
                cellpair[mode] = {
                    "examples_per_sec": round(
                        examples / (build + train + end), 1),
                    "build_ms": round(build * 1e3, 2),
                    "train_ms": round(train * 1e3, 2),
                    "end_ms": round(end * 1e3, 2),
                }
                # leave no residency behind for the next config
                _flags.set_flag("incremental_pass", False)
                tab.invalidate_residency()
            # true overlap of the FULL registered sets (batch keys repeat
            # every pass, so the \"0%\" config still carries their share)
            a = np.union1d(fillers[-2], batch_keys)
            b = np.union1d(fillers[-1], batch_keys)
            inter = np.intersect1d(a, b, assume_unique=True).size
            cellpair["measured_overlap"] = round(inter / max(1, b.size), 3)
            out["overlap_%d" % round(overlap * 100)] = cellpair
    finally:
        _flags.set_flag("incremental_pass", saved_flag)
    return out


def make_bench_trainer(pass_cap: int = 1 << 20, batch: int = 1024,
                       num_slots: int = 32, max_len: int = 4, d: int = 8,
                       trainer_cfg=None):
    """ONE definition of the bench-shape trainer (DeepFM 512/256/128, bf16
    dense, adagrad in-table) for the compiled-step audit
    (tools/step_audit.py) — the audit's flops/bytes describe the benched
    program only while the shapes stay identical. Returns (trainer,
    feed)."""
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data.generator import default_feed_config
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.deepfm import DeepFM
    from paddlebox_tpu.train.trainer import BoxTrainer

    feed = default_feed_config(num_slots=num_slots, batch_size=batch,
                               max_len=max_len)
    table = TableConfig(embedx_dim=d, pass_capacity=pass_cap,
                        optimizer=SparseOptimizerConfig(
                            mf_create_thresholds=0.0, mf_initial_range=1e-3))
    model = DeepFM(ModelSpec(num_slots=num_slots, slot_dim=3 + d),
                   hidden=(512, 256, 128))
    return BoxTrainer(model, table, feed,
                      trainer_cfg or TrainerConfig(
                          dense_lr=1e-3, compute_dtype="bfloat16"),
                      seed=0), feed
