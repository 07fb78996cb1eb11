"""Regime-step probe: step time vs slab size at CONSTANT batch work.

Hypothesis on file (ROADMAP S3): table size leaks into step time above
~4M rows — something the reference's `heter_ps/hashtable.h` design keeps
flat. Unmeasured on the chip. This probe bisects it with the telemetry
plane:

  1. row-count ladder — fine sweep across the 1M->4M threshold, same
     batch/key work at every size; per-step spans feed a StepReport-
     style histogram (utils/stats HIST_BOUNDS) so p50/p90/p99 survive,
     and every timed step is a span in a Perfetto-loadable chrome trace
     (--trace PATH).
  2. constant-bytes — row-width vs row-count at equal slab bytes
     (embedx 8 vs 40): a threshold that tracks BYTES indicts
     allocator/pagewalk mechanics; one that tracks ROWS indicts the
     scatter/gather index path.
  3. donated vs fresh — the production step donates the slab
     (buffer reuse in place); the fresh tier deep-copies the slab
     on device every step so the update can never reuse the pages.
     A regime step that vanishes with donation indicts allocation;
     one that survives it indicts access mechanics.

Runs on whatever backend JAX_PLATFORMS selects; a CPU run measures the
CPU-regime analog only, never the chip.

Usage:
  timeout 3000 python -u tools/regime_step_probe.py \
      [--trace /tmp/regime_trace.json] [--caps 1048576,2097152,...]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.obs.tracer import get_tracer
from paddlebox_tpu.utils.stats import StatRegistry, hist_percentile
from tools.bench_util import make_bench_trainer, make_ctr_batches

D, NUM_SLOTS, BATCH, MAX_LEN = 8, 32, 512, 4
CHUNK, REPS = 4, 6


def _opt(name, default=None):
    for a in sys.argv[1:]:
        if a.startswith("--%s=" % name):
            return a.split("=", 1)[1]
        if a == "--%s" % name:
            i = sys.argv.index(a)
            if i + 1 < len(sys.argv):
                return sys.argv[i + 1]
    return default


def build(cap, d=D):
    """Bench trainer at `cap` rows with a device-resident slab (no
    multi-GB promote H2D)."""
    tr, feed = make_bench_trainer(cap, batch=BATCH, num_slots=NUM_SLOTS,
                                  max_len=MAX_LEN, d=d)
    batches = make_ctr_batches(feed, CHUNK, NUM_SLOTS, MAX_LEN, seed=0)
    tr.table.begin_feed_pass()
    for b in batches:
        tr.table.add_keys(b.keys[b.valid])
    tr.table.end_feed_pass()
    W = tr.table.layout.width
    tr.table._slab = jnp.zeros((cap, W), jnp.float32)
    tr.table._in_pass = True
    stacked = tr._stack_batches(batches)
    return tr, stacked, W


def timed_steps(tr, stacked, label, fresh=False, reps=REPS):
    """Per-rep spans + histogram samples; returns dict of ms stats.
    fresh=True deep-copies the slab on device before every rep so the
    donated-in buffer is a new allocation each call (donation still
    happens — the COPY is what defeats in-place reuse)."""
    tracer = get_tracer()
    reg = StatRegistry.instance()
    hist = "regime_%s_ms" % label
    state = (tr.table.slab, tr.params, tr.opt_state, tr.table.next_prng())
    for _ in range(2):  # compile + warm
        slab, params, opt, losses, _p, key = tr.fns.scan_steps(
            state[0], state[1], state[2], stacked, state[3])
        state = (slab, params, opt, key)
    np.asarray(losses)
    samples = []
    for _ in range(reps):
        slab_in = state[0]
        if fresh:
            slab_in = jax.block_until_ready(
                jax.jit(lambda x: x + 0.0)(slab_in))
        t0 = time.perf_counter()
        slab, params, opt, losses, _p, key = tr.fns.scan_steps(
            slab_in, state[1], state[2], stacked, state[3])
        np.asarray(losses)          # chain-dependent sync point
        t1 = time.perf_counter()
        tracer.record_span("regime_step:%s" % label, t0, t1)
        step_ms = (t1 - t0) / CHUNK * 1e3
        reg.observe(hist, step_ms)
        samples.append(step_ms)
        state = (slab, params, opt, key)
    counts = reg.hist_counts(hist) or []
    return {
        "ms_per_step_min": round(min(samples), 3),
        "ms_per_step_med": round(float(np.median(samples)), 3),
        "hist_p50": round(hist_percentile(counts, 0.50), 3),
        "hist_p90": round(hist_percentile(counts, 0.90), 3),
        "hist_p99": round(hist_percentile(counts, 0.99), 3),
    }


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "platform": dev.platform,
                      "batch": BATCH, "chunk": CHUNK, "reps": REPS}),
          flush=True)
    caps_arg = _opt("caps")
    caps = ([int(c) for c in caps_arg.split(",")] if caps_arg else
            [1 << 20, 3 << 19, 1 << 21, 3 << 20, 1 << 22, 3 << 21])

    # ---- tier 1: row-count ladder (constant work, growing slab) ----
    base_ms = None
    for cap in caps:
        try:
            tr, stacked, W = build(cap)
            rec = {"tier": "row_ladder", "cap_rows": cap,
                   "slab_mb": round(cap * W * 4 / 2**20, 1),
                   "push_write": tr._push_write}
            rec.update(timed_steps(tr, stacked, "rows_%d" % cap))
            if base_ms is None:
                base_ms = rec["ms_per_step_min"]
            rec["vs_first"] = round(rec["ms_per_step_min"] / base_ms, 3)
            tr.close()
        except Exception as e:  # OOM/compile fail is a finding, not a crash
            rec = {"tier": "row_ladder", "cap_rows": cap,
                   "error": repr(e)[:300]}
        print(json.dumps(rec), flush=True)

    # ---- tier 2: constant bytes, rows vs width ----
    # same slab BYTES by trading embedx width against row count: a
    # threshold that follows bytes (both shapes step alike) indicts
    # memory mechanics; one that follows rows indicts the index path
    bytes_target = caps[-1] * 17 * 4          # widest ladder slab, d=8
    for d in (8, 40):
        tmp, feed = make_bench_trainer(1024, batch=8, num_slots=NUM_SLOTS,
                                       max_len=MAX_LEN, d=d)
        W = tmp.table.layout.width
        tmp.close()
        cap = max(1 << 16, int(bytes_target // (4 * W)))
        try:
            tr, stacked, W = build(cap, d=d)
            rec = {"tier": "const_bytes", "embedx": d, "cap_rows": cap,
                   "width": W,
                   "slab_mb": round(cap * W * 4 / 2**20, 1)}
            rec.update(timed_steps(tr, stacked, "w%d_r%d" % (W, cap)))
            tr.close()
        except Exception as e:
            rec = {"tier": "const_bytes", "embedx": d, "cap_rows": cap,
                   "error": repr(e)[:300]}
        print(json.dumps(rec), flush=True)

    # ---- tier 3: donated vs fresh buffers at the threshold ----
    for cap in (caps[0], caps[-1]):
        try:
            rec = {"tier": "donated_vs_fresh", "cap_rows": cap}
            # fresh trainer per tier: the warmup of a timed run DONATES
            # the table's slab buffer — a second run on the same trainer
            # would start from a deleted buffer
            tr, stacked, W = build(cap)
            don = timed_steps(tr, stacked, "don_%d" % cap, fresh=False)
            tr.close()
            tr, stacked, W = build(cap)
            fre = timed_steps(tr, stacked, "fresh_%d" % cap, fresh=True)
            tr.close()
            rec["donated_ms"] = don["ms_per_step_min"]
            rec["fresh_ms"] = fre["ms_per_step_min"]
            rec["fresh_over_donated"] = round(
                fre["ms_per_step_min"] / max(don["ms_per_step_min"], 1e-9),
                3)
        except Exception as e:
            rec = {"tier": "donated_vs_fresh", "cap_rows": cap,
                   "error": repr(e)[:300]}
        print(json.dumps(rec), flush=True)

    trace_path = _opt("trace")
    if trace_path:
        get_tracer().export_chrome(trace_path,
                                   meta={"probe": "regime_step"})
        print(json.dumps({"trace": trace_path}), flush=True)


if __name__ == "__main__":
    main()
