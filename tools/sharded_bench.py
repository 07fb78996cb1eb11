"""Multi-device benchmark of ShardedBoxTrainer + the stager.

The software overhead of sharding (host routing, push dedup, device_put,
a2a), on whatever devices JAX exposes (JAX_PLATFORMS=cpu: eight virtual
CPU devices; a chip host: its local chips, one process):

  1. stager routing throughput (keys/s) at 1 vs N threads — the
     _step_host_arrays bucketize + push-dedup stage (flag stager_threads);
  2. end-to-end sharded step throughput (ex/s) with the streamed input,
     vs the single-device BoxTrainer on the same process/platform;
  3. per-step cost attribution: host routing, device_put, step dispatch.

Shapes match bench.py (DeepFM 512/256/128, batch 1024/worker, 32 slots,
1M-row pass slab per device). Emits one JSON dict on stdout, which names
the platform it ran on; a CPU run's times say nothing about the chip.

Run: python tools/sharded_bench.py
"""

import json
import os
import sys
import time

if __name__ == "__main__":
    # only the CPU backend reads this: eight virtual devices there
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    # run as a script: sys.path[0] is tools/, the repo root isn't there
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import numpy as np

D = 8
NUM_SLOTS = 32
BATCH = 1024
MAX_LEN = 4
PASS_CAP = 1 << 20
STEPS = 8          # timed steps per segment
WARMUP = 2


def build_sharded():
    import jax
    from tools.bench_util import make_ctr_batches

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data.generator import default_feed_config
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.deepfm import DeepFM
    from paddlebox_tpu.parallel.mesh import device_mesh_1d
    from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer

    P = len(jax.devices())
    feed = default_feed_config(num_slots=NUM_SLOTS, batch_size=BATCH,
                               max_len=MAX_LEN)
    # weak scaling: each shard gets the single-device bench's 1M-row slab
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=P * PASS_CAP,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    model = DeepFM(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                   hidden=(512, 256, 128))
    trainer = ShardedBoxTrainer(model, table_cfg, feed,
                                TrainerConfig(dense_lr=1e-3),
                                mesh=device_mesh_1d(P), seed=0)
    # one pass worth of per-worker batches (recycled per timed step)
    n_batches = STEPS + WARMUP
    per_worker = [make_ctr_batches(feed, n_batches, NUM_SLOTS, MAX_LEN,
                                   seed=1000 + w) for w in range(P)]
    trainer.table.begin_feed_pass()
    for batches in per_worker:
        for b in batches:
            trainer.table.add_keys(b.keys[b.valid])
    trainer.table.end_feed_pass()
    return trainer, per_worker, P


def time_stager(trainer, per_worker, threads: int) -> dict:
    """Route STEPS steps with the given pool size; keys/s of the host
    routing + push-dedup stage alone (no device_put)."""
    from paddlebox_tpu.config import flags
    flags.set_flag("stager_threads", threads)
    if trainer._pool is not None:
        trainer._pool.shutdown(wait=True)
        trainer._pool = None
    n_steps = len(per_worker[0])
    keys_per_step = sum(b.keys.size for pw in per_worker for b in (pw[0],))
    for i in range(WARMUP):
        trainer._step_host_arrays(per_worker, i % n_steps)
    t0 = time.perf_counter()
    for i in range(STEPS):
        trainer._step_host_arrays(per_worker, i % n_steps)
    dt = (time.perf_counter() - t0) / STEPS
    return {"threads": threads, "ms_per_step": round(dt * 1e3, 2),
            "keys_per_sec": round(keys_per_step / dt, 0)}


def time_sharded_steps(trainer, per_worker) -> dict:
    """End-to-end streamed step throughput + attribution. D2H-synced: the
    final losses depend on every step's full compute chain."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P_

    sharding = NamedSharding(trainer.mesh, P_(trainer.axis))
    slabs = jax.device_put(trainer.table.build_slabs(), sharding)
    mtab, mstats = trainer.make_metric_state()
    prng = jax.random.PRNGKey(0)
    params, opt_state = trainer.params, trainer.opt_state

    # --- attribution: host routing / device_put / dispatch (serial timing
    # of each stage, no overlap — the stream overlaps them in production)
    t0 = time.perf_counter()
    arrs = trainer._step_host_arrays(per_worker, 0)
    t_route = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev = {k: jax.device_put(v, sharding) for k, v in arrs.items()}
    jax.block_until_ready(dev)
    t_put = time.perf_counter() - t0

    # warmup/compile
    for i in range(WARMUP):
        (slabs, params, opt_state, loss, preds, prng, mtab,
         mstats) = trainer._step(slabs, params, opt_state, dev, prng,
                                 mtab, mstats)
    np.asarray(loss)

    # steady state: the bounded stream overlaps routing with device steps
    losses = []
    t0 = time.perf_counter()
    stream = trainer.shard_batches(
        [pw[:STEPS] for pw in per_worker])
    try:
        for batch in stream:
            (slabs, params, opt_state, loss, preds, prng, mtab,
             mstats) = trainer._step(slabs, params, opt_state, batch,
                                     prng, mtab, mstats)
            losses.append(loss)
    finally:
        stream.close()
    final = np.asarray(jax.numpy.stack(losses))   # real D2H sync
    dt = (time.perf_counter() - t0) / STEPS
    assert np.isfinite(final).all()
    P = trainer.P
    return {"ms_per_step": round(dt * 1e3, 2),
            "examples_per_sec": round(P * BATCH / dt, 0),
            "examples_per_sec_per_device": round(BATCH / dt, 0),
            "route_ms": round(t_route * 1e3, 2),
            "device_put_ms": round(t_put * 1e3, 2),
            "stream_high_water": trainer.stream_high_water}


def time_single_device() -> dict:
    """BoxTrainer on ONE device, same shapes — the scaling denominator.
    CPU keeps f32 compute (bf16 is emulated there), matching bench.py."""
    from tools.bench_util import make_ctr_batches

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data.generator import default_feed_config
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.deepfm import DeepFM
    from paddlebox_tpu.train.trainer import BoxTrainer

    feed = default_feed_config(num_slots=NUM_SLOTS, batch_size=BATCH,
                               max_len=MAX_LEN)
    table_cfg = TableConfig(
        embedx_dim=D, pass_capacity=PASS_CAP,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3))
    model = DeepFM(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D),
                   hidden=(512, 256, 128))
    trainer = BoxTrainer(model, table_cfg, feed,
                         TrainerConfig(dense_lr=1e-3), seed=0)
    batches = make_ctr_batches(feed, STEPS, NUM_SLOTS, MAX_LEN, seed=0)
    trainer.table.begin_feed_pass()
    for b in batches:
        trainer.table.add_keys(b.keys[b.valid])
    trainer.table.end_feed_pass()
    trainer.table.begin_pass()
    stacked = trainer._stack_batches(batches)
    scan = trainer.fns.scan_steps
    state = (trainer.table.slab, trainer.params, trainer.opt_state,
             trainer.table.next_prng())
    from tools.bench_util import timed_scan_chain
    dt = timed_scan_chain(scan, state, stacked, 4, warmup=WARMUP)
    return {"ms_per_step": round(dt * 1e3 / STEPS, 2),
            "examples_per_sec": round(STEPS * BATCH / dt, 0)}


def main():
    trainer, per_worker, P = build_sharded()
    import jax
    dev = jax.devices()[0]
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "devices": P, "batch_per_device": BATCH,
           "keys_per_step": sum(b.keys.size for pw in per_worker
                                for b in (pw[0],))}
    out["stager"] = [time_stager(trainer, per_worker, t)
                     for t in (1, 2, 4, 8)]
    out["sharded"] = time_sharded_steps(trainer, per_worker)
    out["single_device"] = time_single_device()
    spd = (out["sharded"]["examples_per_sec"]
           / out["single_device"]["examples_per_sec"])
    out["scaling_vs_1dev"] = round(spd, 3)
    out["scaling_efficiency"] = round(spd / P, 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
