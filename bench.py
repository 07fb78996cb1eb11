"""Benchmark: fused sparse train-step throughput (examples/sec) on one chip.

Prints ONE JSON line {"metric", "value", "unit", "platform", "device_kind",
...}. The number is a chip number or it does not exist: with no TPU the
probe fails, nothing is measured, no rate is printed and the exit code is
non-zero. `JAX_PLATFORMS` alone selects the backend; nothing here overrides
it and there is no CPU tier. (`python chip_smoke.py --dry-run-cpu` is the
command that checks the main path end to end on a CPU — it prints no rate.)

Process layout (one process per chip): the parent never imports JAX. It
probes the backend in a SUBPROCESS with a hard timeout, then runs the
measurement in a second subprocess with its own timeout, so a wedged
runtime cannot hang the driver and the parent never holds the chip its
children need. measure() itself starts threads only (the streaming ladder's
in-process server and clients) — no child of a process that holds the chip
imports JAX. The two multi-process host ladders (hostplane, fleet) run
after the measuring child has exited and are pinned to the CPU backend:
they time host code.

Every timed segment ends in np.asarray() of data that depends on the full
compute chain (tools/bench_util.timed_scan_chain). Whether
jax.block_until_ready alone is enough on the attached chip is recorded in
CHANGES.md (PR 21); folding the two sync mechanisms into one is ROADMAP S0.

Workload: DeepFM over 32 sparse slots, batch 1024, ~12 keys/instance,
1M-row pass slab — the single-chip analog of the BoxPS hot loop
(pull -> seqpool+CVM -> fwd/bwd -> dense adam -> dedup push with in-table
adagrad; boxps_worker.cc:1256-1335). Steady-state chunks after
compile+warmup; each chunk is a lax.scan megastep of CHUNK batches.

Blocks riding in the record (turning them into benchmark cells is ROADMAP
S0/D1): the e2e staging ladder (grouped / ungrouped / ids-only lean /
uid-lean / uid-delta wires, median of 3 each, with wire_bytes_per_step and
host_stage_keys_per_sec), `pass_amortized` (begin_feed -> train -> end_pass
at 0% and ~90% key overlap, full vs incremental), `push_ladder` (the slab
write kernels alone), and host-code ladders: telemetry / flight / quality
/ lockwatch / device-plane overheads, checkpoint, ssd_tier, ingest,
streaming, hostplane, fleet. A ladder that raises is recorded as
{"error": ...} AND makes the run exit non-zero after the record is
printed: a broken block is a failed run, not a quieter record.
"""

import json
import os
import subprocess
import sys
import time

D = 8
NUM_SLOTS = 32
BATCH = 1024
MAX_LEN = 4
PASS_CAP = int(os.environ.get("PBTPU_BENCH_PASSCAP", str(1 << 20)))
# batches per scan megastep dispatch; override for dispatch-amortization
# experiments
CHUNK = int(os.environ.get("PBTPU_BENCH_CHUNK", "8"))
STEPS = 12         # timed chunks
WARMUP = 2

PROBE_TIMEOUT = int(os.environ.get("PBTPU_BENCH_PROBE_TIMEOUT", "120"))
RUN_TIMEOUT = int(os.environ.get("PBTPU_BENCH_RUN_TIMEOUT", "1100"))

# Bump SCHEMA_VERSION when the record's field meanings change, never for
# additive fields.
SCHEMA_VERSION = 3


def _write_record(record: dict) -> str:
    """Write the final record where PBTPU_BENCH_OUT says (the {"n",
    "parsed"} envelope tools/bench_trend.py reads), and nowhere when it
    is unset: a run leaves nothing in the checkout. Returns the path
    written ('' for none)."""
    out = os.environ.get("PBTPU_BENCH_OUT", "")
    if not out:
        return ""
    with open(out, "w", encoding="utf-8") as fh:
        json.dump({"n": 0, "schema_version": SCHEMA_VERSION,
                   "ts": time.time(), "parsed": record}, fh)
    return out


def _require_tpu():
    """The one gate both children pass: the default backend is the chip.
    Returns jax.devices()."""
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(
            "bench.py measures the chip: default backend is %r, not 'tpu' "
            "(no CPU tier; see chip_smoke.py --dry-run-cpu)"
            % jax.default_backend())
    return jax.devices()


def probe() -> None:
    """Tiny end-to-end reality check: the backend is the chip, a matmul
    compiles, and the RESULT comes back to the host. Exits nonzero on any
    failure."""
    dev = _require_tpu()[0]
    import jax.numpy as jnp
    import numpy as np

    y = jnp.ones((128, 128), jnp.float32) @ jnp.ones((128, 128), jnp.float32)
    host = np.asarray(y)
    if host[0, 0] != 128.0:
        raise SystemExit("probe matmul returned %r" % host[0, 0])
    print(json.dumps({"ok": True, "platform": dev.platform,
                      "device_kind": dev.device_kind}))


def measure() -> None:
    """The actual benchmark; prints one JSON line with the raw result.
    Exits nonzero (after printing it) when any guarded ladder raised."""
    devices = _require_tpu()
    import jax
    import numpy as np

    from tools.bench_util import make_ctr_batches, timed_scan_chain

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
    spec = ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D)
    model = DeepFM(spec, hidden=(512, 256, 128))
    # bf16 dense compute (the MXU-native dtype; halves activation traffic)
    dtype = "bfloat16"

    ladder_errors = []

    def guarded(name, fn, *args):
        """Run one ladder that is not the headline metric. A raise must not
        discard what was already measured, so it lands in the record as
        {"error": ...} — and the run exits non-zero once the record is
        printed (ladder_errors)."""
        try:
            return fn(*args)
        except Exception as e:  # noqa: BLE001 — reported, then fails the run
            ladder_errors.append(name)
            return {"error": repr(e)[:300]}
    trainer = BoxTrainer(model, table_cfg, feed,
                         TrainerConfig(dense_lr=1e-3, compute_dtype=dtype),
                         seed=0)

    batches = make_ctr_batches(feed, CHUNK, NUM_SLOTS, MAX_LEN, seed=0)
    trainer.table.begin_feed_pass()
    for b in batches:
        trainer.table.add_keys(b.keys[b.valid])
    trainer.table.end_feed_pass()
    trainer.table.begin_pass()

    scan = trainer.fns.scan_steps
    t_compile = time.perf_counter()
    stacked = trainer._stack_batches(batches)
    state = (trainer.table.slab, trainer.params, trainer.opt_state,
             trainer.table.next_prng())
    dt = timed_scan_chain(scan, state, stacked, STEPS, warmup=WARMUP)
    t_compile = time.perf_counter() - t_compile - dt * STEPS

    from paddlebox_tpu.config import flags as _flags

    def stage_stats() -> dict:
        """Wire accounting for the CURRENT flag config: bytes the staged
        batch leaves put on the H2D wire per step, and the host staging
        rate in keys/s (lookup + dedup + stack — the stager-thread
        budget)."""
        staged = trainer._stack_batches_host(batches)  # warm
        reps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 1.0:
            staged = trainer._stack_batches_host(batches)
            reps += 1
        dt_s = time.perf_counter() - t0
        wire = sum(int(np.asarray(v).nbytes) for v in staged.values())
        keys = CHUNK * feed.key_capacity()
        return {"wire_bytes_per_step": wire // CHUNK,
                "host_stage_keys_per_sec": round(reps * keys / dt_s, 0)}

    def run_e2e(tg: int, n_chunks: int = 4, runs: int = 3,
                on_chunk=None) -> dict:
        """REAL staged-path throughput: host staging + H2D + dispatch +
        per-chunk D2H over fresh chunk items (the train_pass shape), with
        tg chunks sharing one transfer per leaf (h2d_stack_chunks). The
        resident chain above deliberately excludes all of this; BENCH
        reports both (round-5 verdict item 4). MEDIAN of `runs` timed
        drives — the recorded ±30% container-CPU noise otherwise
        dominates tier deltas (round-8 satellite)."""
        import jax.numpy as jnp

        from paddlebox_tpu.train.trainer import run_scan_chunks
        cap, W = trainer.table.capacity, trainer.table.layout.width
        state = jnp.zeros((cap, W), jnp.float32)

        def scan_call(carry, stacked):
            slab, params, opt, losses, preds, key = \
                trainer.fns.scan_steps(carry[0], carry[1], carry[2],
                                       stacked, carry[3])
            return (slab, params, opt, key), losses, preds

        def drive(carry, n):
            return run_scan_chunks(
                scan_call, batches * n, CHUNK,
                trainer._stack_batches_host if tg > 1
                else trainer._stack_batches,
                carry, on_chunk or (lambda *a: None), prefetch_depth=1,
                transfer_group=tg,
                group_fn=trainer._group_to_device if tg > 1 else None)

        carry = (state, trainer.params, trainer.opt_state,
                 trainer.table.next_prng())
        carry, _, _ = drive(carry, 1)      # compile + warm this structure
        rates = []
        for _ in range(runs):
            t0 = time.perf_counter()
            carry, losses, n_done = drive(carry, n_chunks)
            dt_e2e = time.perf_counter() - t0
            assert n_done == n_chunks * CHUNK and np.isfinite(losses).all()
            rates.append(n_done * BATCH / dt_e2e)
        out = {"examples_per_sec": round(float(np.median(rates)), 1),
               "runs": [round(r, 1) for r in rates]}
        out.update(stage_stats())
        return out

    def lean_tier(uid: bool, delta: bool = False) -> dict:
        _flags.set_flag("h2d_lean", True)
        _flags.set_flag("h2d_uid_wire", uid)
        _flags.set_flag("wire_delta_ids", delta)
        try:
            return run_e2e(tg=1)
        finally:
            _flags.set_flag("h2d_lean", False)
            _flags.set_flag("h2d_uid_wire", True)
            _flags.set_flag("wire_delta_ids", False)

    def telemetry_overhead() -> dict:
        """Round-10 acceptance block: the SAME e2e drive with the
        telemetry plane at its default cadence (span tracer on, a
        StepReporter at obs_report_every=20 feeding a JSONL sink, beats)
        vs everything off — median paired on/off ratio over alternating
        back-to-back pairs, plus an in-run validity
        check that the exported chrome trace round-trips json.loads
        with the Perfetto-required event fields."""
        import tempfile

        import paddlebox_tpu.obs as _obs
        from paddlebox_tpu.obs.tracer import get_tracer

        # ONE monotonically increasing step counter across every "on"
        # drive: the reporter's cadence state (_last_step) persists, so a
        # per-drive counter restarting at 0 would fire exactly once ever
        # and under-measure the report-assembly cost
        steps = [0]

        def run_with(trace_on: bool, reporter=None) -> float:
            get_tracer().enabled = trace_on

            def on_chunk(lo, group, losses_np, preds):
                if reporter is None:
                    return
                steps[0] += len(group)
                reporter.note_examples(len(group) * BATCH)
                reporter.maybe_report(steps[0])

            return run_e2e(tg=1, runs=1,
                           on_chunk=on_chunk if reporter else None
                           )["examples_per_sec"]

        fd, tmp = tempfile.mkstemp(suffix="_obs.jsonl")
        os.close(fd)
        reporter = _obs.StepReporter(every=20, sink=_obs.JsonlSink(tmp))
        # PAIRED on/off ratios, order alternating within pairs: container
        # load drifts ±20-30% across minutes, so independent medians (or
        # sequential blocks — the first cut of this block measured "on"
        # 42% FASTER than "off" that way) measure the load phase, not the
        # telemetry. Back-to-back pair members share a load environment;
        # the MEDIAN PAIR RATIO is the drift-robust overhead estimate.
        # 9 pairs: this container's bursts poison whole pairs (a recorded
        # run saw one member at 1557 ex/s against 8400 in the same
        # block), so the median must survive up to 4 bad pairs.
        rates_on, rates_off, ratios = [], [], []
        for i in range(9):
            if i % 2:
                off = run_with(False, None)
                on = run_with(True, reporter)
            else:
                on = run_with(True, reporter)
                off = run_with(False, None)
            rates_on.append(on)
            rates_off.append(off)
            ratios.append(on / max(off, 1e-9))
        reporter.close()
        eps_on = float(np.median(rates_on))
        eps_off = float(np.median(rates_off))
        ratio = float(np.median(ratios))
        # best-rate ratio: co-tenant noise can only LOWER a run's rate
        # (it never makes one faster), so each arm's best run over 9
        # samples is its noise-free ceiling and their ratio is the
        # load-robust overhead estimate — the rate-domain analog of the
        # standard min-time-of-k microbenchmark discipline. The median
        # pair ratio stays recorded as the conservative bound; under
        # heavy load its own noise floor is several percent (recorded
        # pair ratios have spanned 0.74-1.50 on this container).
        ratio_best = float(max(rates_on) / max(max(rates_off), 1e-9))
        get_tracer().enabled = True
        fd, trace_path = tempfile.mkstemp(suffix="_trace.json")
        os.close(fd)
        doc = _obs.export_chrome_trace(path=trace_path)
        trace_ok = False
        try:
            with open(trace_path) as fh:
                loaded = json.loads(fh.read())
            evs = [e for e in loaded["traceEvents"] if e.get("ph") == "X"]
            trace_ok = bool(evs) and all(
                k in e for e in evs[:64]
                for k in ("name", "ts", "dur", "pid", "tid"))
        except (ValueError, OSError, KeyError):
            trace_ok = False
        n_reports = 0
        if os.path.exists(tmp):
            with open(tmp) as fh:
                n_reports = sum(1 for _ in fh)
        for p in (tmp, trace_path):
            try:
                os.unlink(p)
            except OSError:
                pass
        return {"examples_per_sec_on": round(eps_on, 1),
                "examples_per_sec_off": round(eps_off, 1),
                "runs_on": [round(r, 1) for r in rates_on],
                "runs_off": [round(r, 1) for r in rates_off],
                "pair_ratios": [round(r, 4) for r in ratios],
                # best-rate on/off ratio (see above); positive =
                # telemetry costs throughput
                "overhead_pct": round(100.0 * (1.0 - ratio_best), 2),
                # conservative bound: median paired on/off ratio (its
                # noise floor under container load is several percent)
                "overhead_pct_median_pair": round(100.0 * (1.0 - ratio),
                                                  2),
                "reports_emitted": n_reports,
                # ph:"X" spans only — traceEvents also carries one
                # thread_name metadata event per thread
                "span_events": sum(1 for e in doc["traceEvents"]
                                   if e.get("ph") == "X"),
                "chrome_trace_valid": trace_ok}

    def flight_overhead(pairs: int = 7) -> dict:
        """Round-14 acceptance block: the SAME paired-alternating
        protocol as telemetry_overhead, but the "on" arm runs the FULL
        durable tier — span tracer + StepReporter at default cadence +
        an ACTIVE flight recorder (reports, span windows and beats
        landing flushed on disk) — against everything-off. Shorter
        drives (2 chunks) and 7 pairs keep the block inside the bench
        budget; estimators are identical (best-rate ratio headline,
        median pair ratio as the conservative bound)."""
        import shutil
        import tempfile

        import paddlebox_tpu.obs as _obs
        from paddlebox_tpu.obs import flight as _flight
        from paddlebox_tpu.obs import watchdog as _watchdog
        from paddlebox_tpu.obs.tracer import get_tracer

        d = tempfile.mkdtemp(suffix="_flight")
        # direct-constructed recorder, NO crash hooks: the bench process
        # must exit exactly as before, and the recorder swap below must
        # not leak into the other blocks
        fr = _flight.FlightRecorder(d, rank=0)
        reporter = _obs.StepReporter(every=20, sink=_obs.NullSink())
        steps = [0]

        def run_arm(on: bool) -> float:
            get_tracer().enabled = on
            _flight.set_active(fr if on else None)

            def on_chunk(lo, group, losses_np, preds):
                steps[0] += len(group)
                _watchdog.beat("bench_step")   # feeds the flight sampler
                reporter.note_examples(len(group) * BATCH)
                reporter.maybe_report(steps[0])

            try:
                return run_e2e(tg=1, runs=1, n_chunks=2,
                               on_chunk=on_chunk if on else None
                               )["examples_per_sec"]
            finally:
                _flight.set_active(None)

        rates_on, rates_off, ratios = [], [], []
        for i in range(pairs):
            if i % 2:
                off = run_arm(False)
                on = run_arm(True)
            else:
                on = run_arm(True)
                off = run_arm(False)
            rates_on.append(on)
            rates_off.append(off)
            ratios.append(on / max(off, 1e-9))
        get_tracer().enabled = True
        records = 0
        for p in fr.segments():
            with open(p) as fh:
                records += sum(1 for _ in fh)
        fr.close()
        shutil.rmtree(d, ignore_errors=True)
        ratio_best = float(max(rates_on) / max(max(rates_off), 1e-9))
        ratio_med = float(np.median(ratios))
        return {"examples_per_sec_on": round(float(np.median(rates_on)), 1),
                "examples_per_sec_off": round(float(np.median(rates_off)), 1),
                "runs_on": [round(r, 1) for r in rates_on],
                "runs_off": [round(r, 1) for r in rates_off],
                "pair_ratios": [round(r, 4) for r in ratios],
                "overhead_pct": round(100.0 * (1.0 - ratio_best), 2),
                "overhead_pct_median_pair": round(
                    100.0 * (1.0 - ratio_med), 2),
                "flight_records": records}

    def quality_overhead(pairs: int = 7) -> dict:
        """Round-18 acceptance block: the SAME paired-alternating
        protocol as telemetry/flight_overhead, but the "on" arm runs
        the QUALITY + OPS-ENDPOINT planes at their deployed shape — a
        TaggedQuality fed every chunk from the real preds (the 'all'
        stream + a 4-way tag split, per the trainers' feed), the slot
        drift monitor observing a representative block per drive and
        rolling at drive end, and a LIVE ObsExporter being scraped
        every 0.5 s from a side thread — against everything-off.
        Estimators identical (best-rate ratio headline, median pair
        ratio conservative bound); the ≤2% bar is the acceptance
        criterion."""
        import threading
        import urllib.request

        from paddlebox_tpu.metrics import drift as _drift
        from paddlebox_tpu.metrics import quality as _qmod
        from paddlebox_tpu.metrics.quality import TaggedQuality
        from paddlebox_tpu.obs.exporter import ObsExporter

        rng = np.random.RandomState(11)
        fake_tags = rng.randint(0, 4, CHUNK * BATCH)
        fake_labels = (rng.rand(CHUNK * BATCH) < 0.2).astype(np.int64)
        qual = TaggedQuality(table_size=65536)
        _qmod.set_active(qual)
        monitor = _drift.set_active_new()
        # a representative 4-slot ingest block (the per-pass observe)
        from paddlebox_tpu.data.columnar import ColumnarBlock
        n_obs = BATCH
        obs_block = ColumnarBlock.from_key_rec(
            rng.randint(1, 1 << 20, n_obs * 8).astype(np.uint64),
            np.tile(np.arange(4, dtype=np.int32), n_obs * 2),
            np.repeat(np.arange(n_obs, dtype=np.int64), 8),
            fake_labels[:n_obs].astype(np.int32))
        exp = ObsExporter(port=0)
        scrape_n = [0]

        def scraper(stop: threading.Event):
            # 0.5s cadence: ~30x denser than a production Prometheus
            # scrape (10-15s) but not so dense that the scraper thread's
            # GIL share dominates the measurement on a 1-core container
            # (a 0.1s first cut measured the scraper, not the planes)
            url = "http://127.0.0.1:%d/metrics" % exp.port
            while not stop.wait(0.5):
                try:
                    with urllib.request.urlopen(url, timeout=5) as r:
                        r.read()
                    scrape_n[0] += 1
                except OSError:
                    pass

        def on_chunk(lo, group, losses_np, preds):
            pred = np.clip(np.asarray(
                next(iter(preds.values()))).reshape(-1), 0.0, 1.0)
            n = pred.size
            tensors = {"pred": pred, "label": fake_labels[:n]}
            qual.add_batch(tensors)
            qual.add_tagged(pred, fake_labels[:n], fake_tags[:n],
                            prefix="tag:")
            _drift.observe_preds(pred)

        def run_arm(on: bool) -> float:
            # the scraper runs ONLY during the "on" arm: scraping both
            # arms would cancel the scrape cost out of the on/off ratio
            # and the block would no longer bound what it claims to
            stop = threading.Event()
            th = None
            if on:
                monitor.observe_block(obs_block)
                th = threading.Thread(target=scraper, args=(stop,),
                                      daemon=True)
                th.start()
            try:
                return run_e2e(tg=1, runs=1, n_chunks=2,
                               on_chunk=on_chunk if on else None
                               )["examples_per_sec"]
            finally:
                if on:
                    stop.set()
                    th.join(timeout=2.0)
                    monitor.roll()

        rates_on, rates_off, ratios = [], [], []
        try:
            for i in range(pairs):
                if i % 2:
                    off = run_arm(False)
                    on = run_arm(True)
                else:
                    on = run_arm(True)
                    off = run_arm(False)
                rates_on.append(on)
                rates_off.append(off)
                ratios.append(on / max(off, 1e-9))
        finally:
            exp.close()
            _qmod.set_active(None)
            _drift.set_active(None)
        ratio_best = float(max(rates_on) / max(max(rates_off), 1e-9))
        ratio_med = float(np.median(ratios))
        return {"examples_per_sec_on": round(float(np.median(rates_on)), 1),
                "examples_per_sec_off": round(float(np.median(rates_off)),
                                              1),
                "runs_on": [round(r, 1) for r in rates_on],
                "runs_off": [round(r, 1) for r in rates_off],
                "pair_ratios": [round(r, 4) for r in ratios],
                "overhead_pct": round(100.0 * (1.0 - ratio_best), 2),
                "overhead_pct_median_pair": round(
                    100.0 * (1.0 - ratio_med), 2),
                "scrapes_during_block": scrape_n[0],
                "quality_tags": len(qual.report()["tags"])}

    tiers = {
        "grouped": run_e2e(tg=4),
        "ungrouped": run_e2e(tg=1),
        # the round-5 ids-only wire: minimal bytes, jnp.unique in-step
        "lean_ids_only": lean_tier(uid=False),
        # the round-8 reunified lean wire: sorted uids ship, maps derive
        # on device, fast push — the e2e headline tier
        "uid_lean": lean_tier(uid=True),
        # measured wire experiment: int16-delta-coded uid vector
        "uid_delta": lean_tier(uid=True, delta=True),
    }
    e2e_grouped = tiers["grouped"]["examples_per_sec"]
    e2e_per_chunk = tiers["ungrouped"]["examples_per_sec"]
    e2e_lean = tiers["uid_lean"]["examples_per_sec"]

    # telemetry-plane overhead at default cadence (≤2% target)
    telemetry = guarded("telemetry_overhead", telemetry_overhead)

    # flight-recorder overhead at default cadence (≤2% target)
    flight = guarded("flight_overhead", flight_overhead)

    # quality-metric + ops-endpoint overhead under live scrapes (≤2%
    # target)
    quality = guarded("quality_overhead", quality_overhead)

    def lockwatch_overhead() -> dict:
        """Round-19 acceptance block: the runtime lock-order validator
        (flag debug_lock_order, utils/lockwatch.py). OFF is the
        production default and constructs PLAIN threading locks — parity
        with unwired code is by construction (type identity asserted
        here) and the cross-round e2e trend (bench_trend over the
        headline rates) is the step-block regression guard. What needs
        measuring is the ON cost: per-acquire wrapper overhead and the
        hot Channel's put/get rate — each arm constructs its OWN objects
        (locks wire at construction), paired alternating per the
        container-drift discipline of the other overhead blocks."""
        import threading as _th

        from paddlebox_tpu.config import flags as _flags
        from paddlebox_tpu.utils import lockwatch as _lw
        from paddlebox_tpu.utils.channel import Channel as _Chan

        _flags.set_flag("debug_lock_order", False)
        off_is_plain = type(_lw.make_lock("bench._plain")) is type(
            _th.Lock())

        def acquire_rate(lock, n=200_000):
            t0 = time.perf_counter()
            for _ in range(n):
                with lock:
                    pass
            return n / (time.perf_counter() - t0)

        def chan_rate(n=50_000):
            c = _Chan(capacity=1024)
            t0 = time.perf_counter()
            done = 0
            while done < n:
                burst = min(1024, n - done)
                for i in range(burst):
                    c.put(i)
                for _ in range(burst):
                    c.get()
                done += burst
            return n / (time.perf_counter() - t0)

        acq_ratios, chan_ratios = [], []
        try:
            for i in range(5):
                order = (False, True) if i % 2 else (True, False)
                acq, ch = {}, {}
                for on in order:
                    _flags.set_flag("debug_lock_order", on)
                    _lw.reset()
                    acq[on] = acquire_rate(_lw.make_lock(f"bench._l{i}"))
                    ch[on] = chan_rate()
                acq_ratios.append(acq[True] / max(acq[False], 1e-9))
                chan_ratios.append(ch[True] / max(ch[False], 1e-9))
        finally:
            # a raise mid-loop must not leave the watch ON for the later
            # headline blocks (watched Channels are ~9x slower — a leak
            # here would record a phantom cross-round regression)
            _flags.set_flag("debug_lock_order", False)
            _lw.reset()
        acq_med = float(np.median(acq_ratios))
        chan_med = float(np.median(chan_ratios))
        return {"off_constructs_plain_lock": off_is_plain,
                "acquire_on_off_ratios": [round(r, 4) for r in acq_ratios],
                "channel_on_off_ratios": [round(r, 4)
                                          for r in chan_ratios],
                # positive = the WATCHED (debug) mode costs throughput;
                # the off arm is the production path
                "on_acquire_overhead_pct": round(100.0 * (1.0 - acq_med),
                                                 2),
                "on_channel_overhead_pct": round(100.0 * (1.0 - chan_med),
                                                 2)}

    # lockwatch runtime-twin cost record (off = parity by construction +
    # trend guard; on = the debug-mode price)
    lockwatch_cost = guarded("lockwatch_overhead", lockwatch_overhead)

    def device_overhead(pairs: int = 5, reps: int = 4) -> dict:
        """Round-20 acceptance block: instrument_jit's dispatch cost on
        the resident scan chain — the INSTRUMENTED entry point the
        trainer built (AOT cache + signature keying + donation pointer
        audit) against a bare jax.jit twin of the SAME scan fn
        (the wrapper exposes it as __wrapped__), paired alternating per
        the container-drift discipline of the other overhead blocks
        (<=2% bar)."""
        import jax.numpy as jnp

        from paddlebox_tpu.obs.device import InstrumentedJit
        scan_on = trainer.fns.scan_steps
        if not isinstance(scan_on, InstrumentedJit):
            return {"error": "device_obs off at trainer construction"}
        scan_off = jax.jit(scan_on.__wrapped__, donate_argnums=(0,))
        cap, W = trainer.table.capacity, trainer.table.layout.width
        stacked_d = trainer._stack_batches(batches)

        def drive(scan) -> float:
            state = (jnp.zeros((cap, W), jnp.float32), trainer.params,
                     trainer.opt_state, trainer.table.next_prng())
            dt = timed_scan_chain(scan, state, stacked_d, reps, warmup=1)
            return CHUNK * BATCH / dt

        drive(scan_on)          # compile/warm both arms outside timing
        drive(scan_off)
        rates_on, rates_off, ratios = [], [], []
        for i in range(pairs):
            if i % 2:
                off = drive(scan_off)
                on = drive(scan_on)
            else:
                on = drive(scan_on)
                off = drive(scan_off)
            rates_on.append(on)
            rates_off.append(off)
            ratios.append(on / max(off, 1e-9))
        ratio_best = float(max(rates_on) / max(max(rates_off), 1e-9))
        ratio_med = float(np.median(ratios))
        return {"examples_per_sec_on": round(float(np.median(rates_on)),
                                             1),
                "examples_per_sec_off": round(float(np.median(rates_off)),
                                              1),
                "runs_on": [round(r, 1) for r in rates_on],
                "runs_off": [round(r, 1) for r in rates_off],
                "pair_ratios": [round(r, 4) for r in ratios],
                # positive = instrumentation costs throughput; best-rate
                # ratio is the load-robust headline, median pair the
                # conservative bound (same estimators as telemetry)
                "overhead_pct": round(100.0 * (1.0 - ratio_best), 2),
                "overhead_pct_median_pair": round(
                    100.0 * (1.0 - ratio_med), 2)}

    # device-plane dispatch cost (<=2% bar)
    device_cost = guarded("device_overhead", device_overhead)

    def device_block() -> dict:
        """Round-20 record: the device plane's view of this bench run —
        per-entry-point compile counts, one-time cost/memory analyses
        (per-example flops/bytes for the trend), donation status, and
        the transfer/recompile/donation-miss counters. The
        bytes-accessed-per-example headline rides bench_trend like a
        rate, so a byte-budget regression flags across rounds."""
        from paddlebox_tpu.obs import device as _device
        snap = _device.snapshot()
        entries = {}
        for name, e in snap["entries"].items():
            d = {"compiles": e["compiles"],
                 "compile_ms": e["compile_ms"],
                 "donated": bool(e["donate_argnums"])}
            don = e.get("donation")
            if don:
                d["donation"] = don
                d["donation_ok"] = (don["supported"] is True
                                    and don["misses"] == 0)
            ana = e.get("analysis") or {}
            for k in ("flops", "bytes_accessed", "flops_per_example",
                      "bytes_accessed_per_example", "temp_bytes",
                      "alias_bytes", "temp_includes_slab_copy"):
                if k in ana:
                    d[k] = ana[k]
            entries[name] = d
        scan_ana = (snap["entries"].get("scan_steps", {})
                    .get("analysis") or {})
        # the scan's cost analysis counts the body once = ONE batch
        per_ex = (round(scan_ana["bytes_accessed"] / BATCH)
                  if "bytes_accessed" in scan_ana else 0)
        return {"entries": entries,
                "transfers": snap["transfers"],
                "recompiles": snap["recompiles"],
                "donation_miss": snap["donation_miss"],
                "bytes_accessed_per_example": per_ex,
                "overhead": device_cost}

    device_rec = guarded("device_block", device_block)
    device_rec.setdefault("overhead", device_cost)

    # pass-amortized tier (round-6): the full begin_feed → train →
    # end_pass lifecycle at 0% and ~90% working-set overlap, full vs
    # incremental lifecycle — the honest cadence number the resident
    # chain above deliberately excludes. Runs after the headline: a
    # failure here (fresh jit buckets, 12 extra lifecycle passes) must not
    # discard it.
    push_write_mode = trainer._push_write
    from tools.bench_util import measure_pass_amortized
    pass_amortized = guarded("pass_amortized", measure_pass_amortized,
                             trainer, batches, BATCH)
    pa_eps = (pass_amortized.get("overlap_90", {}).get("incremental", {})
              .get("examples_per_sec", 0.0))

    def push_ladder() -> dict:
        """Write-kernel ladder: the uid-wire push (merge + in-table
        optimize + slab write) alone, donated slab threaded through, at
        scatter / rebuild / blocked / blocked+pallas (the Mosaic placement
        kernel, compiled) / blocked+bf16 — median-of-3 keys/s per tier at
        one shape."""
        import functools

        import jax.numpy as jnp

        from paddlebox_tpu.embedding.accessor import (PushLayout,
                                                      ValueLayout)
        from paddlebox_tpu.embedding.optimizers import push_sparse_uidwire
        from paddlebox_tpu.embedding.pass_table import dedup_uids_sorted

        conf = table_cfg.optimizer
        push_l = PushLayout(D)
        rng = np.random.RandomState(7)
        prng = jax.random.PRNGKey(0)

        def tier(write, cap, K, embed_dtype="float32", pallas=False,
                 runs=3):
            layout = ValueLayout(D, "adagrad", embed_dtype=embed_dtype)
            ids = rng.randint(0, cap // 8, K).astype(np.int32)  # dup ~8
            uids = jnp.asarray(dedup_uids_sorted(ids, cap))
            ids_j = jnp.asarray(ids)
            grads = rng.rand(K, push_l.width).astype(np.float32)
            grads[:, push_l.SHOW] = 1.0
            grads_j = jnp.asarray(grads)
            _flags.set_flag("push_blocked_pallas", pallas)
            try:
                step = jax.jit(functools.partial(
                    push_sparse_uidwire, layout=layout, conf=conf,
                    write=write), donate_argnums=(0,))
                state = [jnp.zeros(
                    (cap, layout.device_width), layout.device_dtype)]
                state[0] = jax.block_until_ready(     # compile + warm
                    step(state[0], uids, ids_j, grads_j, prng))
                rates = []
                for _ in range(runs):
                    reps, t0 = 0, time.perf_counter()
                    while time.perf_counter() - t0 < 1.0 and reps < 64:
                        state[0] = jax.block_until_ready(
                            step(state[0], uids, ids_j, grads_j, prng))
                        reps += 1
                    rates.append(reps * K / (time.perf_counter() - t0))
                return {"keys_per_sec": round(float(np.median(rates)), 0),
                        "cap_rows": cap, "batch_keys": K,
                        "bytes_per_row": layout.device_bytes_per_row}
            finally:
                _flags.set_flag("push_blocked_pallas", False)

        cap, K = 1 << 21, 1 << 18
        out = {
            "scatter": tier("scatter", cap, K),
            "rebuild": tier("rebuild", cap, K),
            "blocked": tier("blocked", cap, K),
            "blocked_pallas": tier("blocked", cap, K, pallas=True),
            "blocked_bf16": tier("blocked", cap, K,
                                 embed_dtype="bfloat16"),
        }
        f32_b = out["blocked"]["bytes_per_row"]
        b16_b = out["blocked_bf16"]["bytes_per_row"]
        out["bf16_capacity_gain"] = round(f32_b / b16_b, 3)
        return out

    ladder = guarded("push_ladder", push_ladder)

    def checkpoint_ladder(R: int = 1 << 20) -> dict:
        """Round-15 checkpoint-plane ladder at R rows (adagrad embedx=8,
        width 17 → ~68 MB of row bytes), three layers so each claim is
        attributable (median-of-3 wall each, keys/s):

          * blob tier — the format alone: pickle.dump/load (as shipped:
            NO fsync — DONE could land with the blob still in page
            cache) vs a durability-fair fsync'd pickle vs the columnar
            writer pool at 1 and ckpt_parts stripes, and both loads.
          * store tier — the end-to-end resume path (read + store
            install) per format via PassTable.save/load.
          * snapshot stall — full save_base vs a touched-mode save at a
            ~10%-dirty journal epoch (the day-boundary acceptance bar).

        Pure host tier — no jax arrays, identical on every platform;
        ckpt_io_parallelism records cpu_count (a 1-core container can
        only overlap I/O WAITS, not memcpys — read BASELINE round 15
        before comparing boxes)."""
        import pickle as _pickle
        import shutil
        import tempfile

        from paddlebox_tpu.config.configs import (CheckpointConfig,
                                                  SparseOptimizerConfig,
                                                  TableConfig)
        from paddlebox_tpu.embedding import ckpt_store as cks
        from paddlebox_tpu.embedding.pass_table import PassTable
        from paddlebox_tpu.train.checkpoint import CheckpointManager

        tcfg = TableConfig(embedx_dim=8, pass_capacity=1 << 10,
                           optimizer=SparseOptimizerConfig())
        t = PassTable(tcfg, seed=1)
        rng = np.random.RandomState(5)
        keys = rng.permutation(np.arange(1, R + 1, dtype=np.uint64))
        vals = rng.rand(R, t.layout.width).astype(np.float32)
        vals[:, 1] = rng.randint(1, 40, R)  # SHOW
        t.store.assign(keys, vals)
        meta = {"embedx_dim": tcfg.embedx_dim,
                "optimizer": t.layout.optimizer}
        root = tempfile.mkdtemp(prefix="pbtpu_ckpt_bench_")

        def timed(fn, runs=3):
            walls = []
            for _ in range(runs):
                t0 = time.perf_counter()
                fn()
                walls.append(time.perf_counter() - t0)
            return float(np.median(walls))

        def rate(w):
            return round(R / w, 0)

        try:
            out = {"rows": R, "width": t.layout.width,
                   "ckpt_io_parallelism": os.cpu_count() or 1,
                   "ckpt_parts": int(_flags.get_flag("ckpt_parts"))}
            pkl = os.path.join(root, "blob.pkl")
            xman = os.path.join(root, "blob.xman")

            def pkl_dump(fsync):
                with open(pkl, "wb") as f:
                    _pickle.dump({"keys": keys, "values": vals, **meta},
                                 f, protocol=_pickle.HIGHEST_PROTOCOL)
                    if fsync:
                        f.flush()
                        os.fsync(f.fileno())

            blob = {}
            blob["pickle_dump"] = rate(timed(lambda: pkl_dump(False)))
            blob["pickle_dump_fsync"] = rate(timed(lambda: pkl_dump(True)))
            blob["columnar_write_1part"] = rate(timed(
                lambda: cks.write_sparse_columnar(xman, keys, vals, meta,
                                                  parts=1)))
            blob["columnar_write_pool"] = rate(timed(
                lambda: cks.write_sparse_columnar(xman, keys, vals, meta)))
            blob["pickle_load"] = rate(timed(
                lambda: _pickle.load(open(pkl, "rb"))))
            blob["columnar_load_pool"] = rate(timed(
                lambda: cks.load_sparse_columnar(xman)))
            out["blob_keys_per_sec"] = blob

            store = {}
            for fmt, name in (("pickle", "st.pkl"), ("columnar",
                                                     "st.xman")):
                _flags.set_flag("ckpt_format", fmt)
                p = os.path.join(root, name)
                store[fmt] = {
                    "save_keys_per_sec": rate(timed(lambda: t.save(p))),
                    "load_keys_per_sec": rate(timed(lambda: t.load(p)))}
            _flags.set_flag("ckpt_format", "columnar")
            out["store"] = store
            out["speedup_save_durable"] = round(
                blob["columnar_write_pool"] / blob["pickle_dump_fsync"], 2)
            out["speedup_write_pool_vs_1part"] = round(
                blob["columnar_write_pool"]
                / blob["columnar_write_1part"], 2)

            # day-boundary stall: full snapshot (sparse + xbox + stat)
            # vs touched-only at ~10% of rows dirty in the journal epoch
            cm = CheckpointManager(CheckpointConfig(
                batch_model_dir=os.path.join(root, "batch"),
                xbox_model_dir=os.path.join(root, "xbox"),
                async_save=False), t)
            cm.save_base({}, {}, day="anchor")  # full anchor for touched
            frac = max(1, R // 10)
            stalls_t, stalls_f = [], []
            for i in range(3):
                cm.journal.append_rows(keys[:frac], vals[:frac])
                t0 = time.perf_counter()
                cm.save_base({}, {}, day=f"t{i}", mode="touched")
                stalls_t.append(time.perf_counter() - t0)
            for i in range(3):
                t0 = time.perf_counter()
                cm.save_base({}, {}, day=f"f{i}", mode="full")
                stalls_f.append(time.perf_counter() - t0)
            st, sf = float(np.median(stalls_t)), float(np.median(stalls_f))
            out["touched_save"] = {
                "dirty_rows": frac, "stall_s": round(st, 4),
                "full_stall_s": round(sf, 4),
                "stall_ratio_full_over_touched": round(sf / st, 1)}
            return out
        finally:
            shutil.rmtree(root, ignore_errors=True)

    ckpt = guarded("checkpoint_ladder", checkpoint_ladder)

    def ssd_tier_ladder(R: int = 1 << 18) -> dict:
        """Round-16 SSD-tier ladder at R rows (adagrad embedx=8, width
        17): the three read tiers of the host store, each attributable
        (keys/s), plus the feed-pass prefetch overlap claim:

          * ram_hit — lookup over a fully-resident set (the native
            fused probe+gather when the lib is present): the ceiling.
          * ssd_promote — fault_in_keys of a fully-spilled set, the
            batched by-file BeginFeedPass/LoadSSD2Mem leg (re-spill
            runs off the clock each cycle).
          * cold_fault — the lookup-path PEEK over sleeping rows (mmap
            block read, no residency change): what touching a tier row
            without promoting it costs.
          * prefetch overlap — serial (training tail, THEN boundary
            promote) vs overlapped (PromotePrefetcher pulls the same
            sleeping set under the tail). On a 1-core container only
            I/O waits can hide, so read hidden_frac as a floor."""
        import shutil
        import tempfile
        import threading

        from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                                  TableConfig)
        from paddlebox_tpu.embedding.pass_table import PassTable
        from paddlebox_tpu.train.preload import PromotePrefetcher

        root = tempfile.mkdtemp(prefix="pbtpu_ssd_bench_")
        try:
            tcfg = TableConfig(embedx_dim=8, pass_capacity=1 << 10,
                               ssd_dir=root,
                               optimizer=SparseOptimizerConfig())
            t = PassTable(tcfg, seed=1)
            st = t.store
            rng = np.random.RandomState(7)
            keys = rng.permutation(np.arange(1, R + 1, dtype=np.uint64))
            vals = rng.rand(R, t.layout.width).astype(np.float32)
            st.assign(keys, vals)

            def timed(fn, runs=3):
                walls = []
                for _ in range(runs):
                    t0 = time.perf_counter()
                    fn()
                    walls.append(time.perf_counter() - t0)
                return float(np.median(walls))

            out = {"rows": R, "width": t.layout.width}
            out["ram_hit_keys_per_sec"] = round(
                R / timed(lambda: st.lookup(keys)), 0)

            st.spill_exact(keys)
            out["cold_fault_keys_per_sec"] = round(
                R / timed(lambda: st.lookup(keys)), 0)

            def promote_cycle():
                walls = []
                for _ in range(3):
                    st.spill_exact(keys)
                    t0 = time.perf_counter()
                    st.fault_in_keys(keys)
                    walls.append(time.perf_counter() - t0)
                return float(np.median(walls))

            w_promote = promote_cycle()
            out["ssd_promote_keys_per_sec"] = round(R / w_promote, 0)

            # prefetch overlap: a synthetic training tail sized to the
            # serial promote wall, then the boundary promote — serial
            # pays tail + promote; overlapped runs the real
            # PromotePrefetcher (lookup_present under store_lock) while
            # the tail spins, and the boundary pays only the residual
            tail_s = w_promote
            burn = rng.rand(256, 256).astype(np.float32)

            def tail():
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < tail_s:
                    np.dot(burn, burn)

            st.spill_exact(keys)
            t0 = time.perf_counter()
            tail()
            st.fault_in_keys(keys)
            serial_wall = time.perf_counter() - t0

            st.spill_exact(keys)
            known = lambda k: np.zeros(k.size, bool)  # noqa: E731
            t0 = time.perf_counter()
            pf = PromotePrefetcher(known, st,
                                   getattr(t, "store_lock",
                                           threading.RLock()))
            pf.feed(keys)
            tail()
            pf.finish()
            st.fault_in_keys(keys)        # residual (≈0 when hidden)
            overlapped_wall = time.perf_counter() - t0
            out["prefetch_overlap"] = {
                "tail_s": round(tail_s, 4),
                "serial_wall_s": round(serial_wall, 4),
                "overlapped_wall_s": round(overlapped_wall, 4),
                "hidden_frac": round(
                    max(0.0, 1.0 - (overlapped_wall - tail_s)
                        / max(serial_wall - tail_s, 1e-9)), 3)}
            out["ram_vs_promote"] = round(
                out["ram_hit_keys_per_sec"]
                / max(out["ssd_promote_keys_per_sec"], 1e-9), 1)
            return out
        finally:
            shutil.rmtree(root, ignore_errors=True)

    ssd = guarded("ssd_tier_ladder", ssd_tier_ladder)

    def ingest_ladder() -> dict:
        """Round-17 ingest block — the first measured number on the one
        plane bench.py always skipped (it trains on pre-made synthetic
        batches): per-stage rates for the parse→shuffle→pack ladder plus
        the COLD-PASS end-to-end examples/s (a full train_pass from text
        files through the columnar shuffle to the trained slab) against
        the SAME model's resident scan rate, and the preload-overlapped
        cadence (pass N+1 parse+shuffle under pass N training —
        run_preloaded_passes). Shuffle codec tiers run the codec+routing
        ALONE on identical pre-parsed content (world 2, in-process), so
        block-vs-record is the codec claim, not a parse comparison."""
        import shutil
        import tempfile

        from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
        from paddlebox_tpu.data.block_shuffle import (block_shuffle_dests,
                                                      deserialize_block,
                                                      serialize_block,
                                                      split_block)
        from paddlebox_tpu.data.shuffle import (LocalShuffleGroup,
                                                deserialize_records,
                                                serialize_records)
        from paddlebox_tpu.train.preload import run_preloaded_passes

        I_SLOTS, I_BATCH, I_FILES, I_LINES, IC = 16, 512, 4, 3000, 8
        out_dir = tempfile.mkdtemp(prefix="pbtpu_ingest_bench_")
        itrainer = None
        try:
            files, ifeed = write_synthetic_ctr_files(
                out_dir, num_files=I_FILES, lines_per_file=I_LINES,
                num_slots=I_SLOTS, vocab_per_slot=20000, max_len=MAX_LEN,
                seed=5)
            ifeed = type(ifeed)(slots=ifeed.slots, batch_size=I_BATCH)
            n_total = I_FILES * I_LINES

            def timed_reps(fn, secs):
                fn()                              # warm
                reps, t0 = 0, time.perf_counter()
                while time.perf_counter() - t0 < secs:
                    fn()
                    reps += 1
                return reps, time.perf_counter() - t0

            # parse tier: native columnar read+merge of the whole pass
            ds = BoxDataset(ifeed, read_threads=2)
            ds.set_filelist(files)
            ds.load_into_memory()
            columnar = ds._load_columnar
            n_keys = ds.block.n_keys if columnar else ds.all_keys().size

            def parse_once():
                d2 = BoxDataset(ifeed, read_threads=2)
                d2.set_filelist(files)
                d2.load_into_memory()

            reps, dtp = timed_reps(parse_once, 2.0)
            out = {"instances_per_pass": n_total,
                   "keys_per_instance": round(n_keys / n_total, 1),
                   "columnar": columnar,
                   "parse_keys_per_sec": round(reps * n_keys / dtp, 0),
                   "parse_lines_per_sec": round(reps * n_total / dtp, 0)}

            # shuffle codec ladder: identical pre-parsed content, both
            # codecs, world 2 — serialize + hash-route + deserialize
            block = ds.block
            rec_ds = BoxDataset(ifeed, read_threads=2, columnar=False)
            rec_ds.set_filelist(files)
            rec_ds.load_into_memory()
            recs = rec_ds.records
            sizes = {}

            def block_codec():
                subs = split_block(block, block_shuffle_dests(block, 2), 2)
                payloads = [serialize_block(s) for s in subs
                            if s is not None]
                sizes["block"] = sum(len(p) for p in payloads)
                assert sum(deserialize_block(p).n_recs
                           for p in payloads) == n_total

            def record_codec():
                groups = [[], []]
                for r in recs:
                    groups[r.shuffle_hash() % 2].append(r)
                payloads = [serialize_records(g) for g in groups if g]
                sizes["record"] = sum(len(p) for p in payloads)
                assert sum(len(deserialize_records(p))
                           for p in payloads) == n_total

            b_reps, b_dt = timed_reps(block_codec, 1.5)
            r_reps, r_dt = timed_reps(record_codec, 1.5)
            blk = b_reps * n_total / b_dt
            rec = r_reps * n_total / r_dt
            out["shuffle"] = {
                "block_records_per_sec": round(blk, 0),
                "record_records_per_sec": round(rec, 0),
                "codec_speedup": round(blk / rec, 1),
                "block_bytes_per_pass": sizes["block"],
                "record_bytes_per_pass": sizes["record"]}

            # pack tier: split_batches over the merged block
            per_pass = [None]

            def pack_once():
                per_pass[0] = ds.split_batches(num_workers=1)

            p_reps, p_dt = timed_reps(pack_once, 1.5)
            packed = sum(b.n_ins for b in per_pass[0][0])
            out["pack_examples_per_sec"] = round(p_reps * packed / p_dt, 0)

            # cold pass: parse -> shuffle -> pack -> train, one call
            itrainer = BoxTrainer(
                DeepFM(ModelSpec(num_slots=I_SLOTS, slot_dim=3 + D),
                       hidden=(256, 128)),
                TableConfig(embedx_dim=D, pass_capacity=1 << 19,
                            optimizer=SparseOptimizerConfig(
                                mf_create_thresholds=0.0,
                                mf_initial_range=1e-3)),
                ifeed, TrainerConfig(dense_lr=1e-3, compute_dtype=dtype),
                seed=0)
            group = LocalShuffleGroup(1)   # the routed path, all-local

            def fresh_ds():
                d2 = BoxDataset(ifeed, read_threads=4, shuffler=group[0])
                d2.set_filelist(files)
                return d2

            itrainer.train_pass(fresh_ds())      # compile + warm
            colds = []
            for _ in range(3):
                d2 = fresh_ds()
                t0 = time.perf_counter()
                itrainer.train_pass(d2)
                colds.append(len(d2) / (time.perf_counter() - t0))
            out["cold_pass_examples_per_sec"] = round(
                float(np.median(colds)), 1)
            out["cold_runs"] = [round(r, 1) for r in colds]

            # overlapped cadence: pass N+1 parse+shuffle under pass N
            t0 = time.perf_counter()
            run_preloaded_passes(itrainer, [fresh_ds() for _ in range(3)])
            out["overlapped_examples_per_sec"] = round(
                3 * n_total / (time.perf_counter() - t0), 1)

            # resident tier at the SAME shape/model: scan on pre-staged
            # batches — what the cold number is honestly compared against
            batches_i = per_pass[0][0][:IC]
            itrainer.table.begin_feed_pass()
            for b in batches_i:
                itrainer.table.add_keys(b.keys[b.valid])
            itrainer.table.end_feed_pass()
            itrainer.table.begin_pass()
            stacked_i = itrainer._stack_batches(batches_i)
            st = (itrainer.table.slab, itrainer.params,
                  itrainer.opt_state, itrainer.table.next_prng())
            dti = timed_scan_chain(itrainer.fns.scan_steps, st, stacked_i,
                                   6, warmup=1)
            out["resident_examples_per_sec"] = round(IC * I_BATCH / dti, 1)
            out["cold_vs_resident"] = round(
                out["cold_pass_examples_per_sec"]
                / max(out["resident_examples_per_sec"], 1e-9), 3)
            return out
        finally:
            if itrainer is not None:
                itrainer.close()
            shutil.rmtree(out_dir, ignore_errors=True)

    ingest = guarded("ingest_ladder", ingest_ladder)

    def streaming_ladder() -> dict:
        """Round-19 streaming block: the micro-pass pipeline's sustained
        examples/s against the SAME windows driven as plain preloaded
        batch passes (run_preloaded_passes — the batch-resident cadence
        at the same shape), plus the in-process ingest-to-serve
        freshness: seconds from an atomic file drop to a
        JournalDeltaSource poll returning the trained rows, no SaveDelta
        in between (the multi-process freshness number is the slow leg
        of tests/test_streaming.py).
        Median-of-3 on every tier. The admission gate runs (its preview
        cost belongs in the cadence) with the refusal threshold parked
        high so a borderline drift score can't silently skip a window's
        instances and corrupt the rate."""
        import shutil
        import tempfile
        import threading as _threading

        from paddlebox_tpu.config import flags as _fl
        from paddlebox_tpu.config.configs import CheckpointConfig
        from paddlebox_tpu.data import (BoxDataset, StreamingDataset,
                                        write_synthetic_ctr_files)
        from paddlebox_tpu.serving.refresh import JournalDeltaSource
        from paddlebox_tpu.train import CheckpointManager, StreamingRunner
        from paddlebox_tpu.train.preload import run_preloaded_passes

        S_SLOTS, S_BATCH, S_FILES, S_LINES = 16, 512, 6, 2000
        WIN_FILES = 2                      # files per micro-pass window
        root = tempfile.mkdtemp(prefix="pbtpu_stream_bench_")
        strainer = None
        old_poll = _fl.get_flag("streaming_poll_secs")
        try:
            files, sfeed = write_synthetic_ctr_files(
                os.path.join(root, "staging"), num_files=S_FILES,
                lines_per_file=S_LINES, num_slots=S_SLOTS,
                vocab_per_slot=20000, max_len=MAX_LEN, seed=11)
            sfeed = type(sfeed)(slots=sfeed.slots, batch_size=S_BATCH)
            n_total = S_FILES * S_LINES
            win_instances = WIN_FILES * S_LINES
            n_windows = S_FILES // WIN_FILES
            _fl.set_flag("streaming_poll_secs", 0.02)

            strainer = BoxTrainer(
                DeepFM(ModelSpec(num_slots=S_SLOTS, slot_dim=3 + D),
                       hidden=(256, 128)),
                TableConfig(embedx_dim=D, pass_capacity=1 << 19,
                            optimizer=SparseOptimizerConfig(
                                mf_create_thresholds=0.0,
                                mf_initial_range=1e-3)),
                sfeed, TrainerConfig(dense_lr=1e-3, compute_dtype=dtype),
                seed=0)
            cm = CheckpointManager(
                CheckpointConfig(
                    batch_model_dir=os.path.join(root, "batch"),
                    xbox_model_dir=os.path.join(root, "xbox"),
                    async_save=False),
                strainer.table)

            def win_datasets():
                out = []
                for i in range(0, S_FILES, WIN_FILES):
                    d = BoxDataset(sfeed, read_threads=2)
                    d.set_filelist(files[i:i + WIN_FILES])
                    out.append(d)
                return out

            # batch leg: the SAME windows as plain preloaded passes
            run_preloaded_passes(strainer, win_datasets())  # compile+warm
            batch_rates = []
            for _ in range(3):
                t0 = time.perf_counter()
                run_preloaded_passes(strainer, win_datasets())
                batch_rates.append(n_total / (time.perf_counter() - t0))
            batch_eps = float(np.median(batch_rates))

            def drop_all(source, names):
                for i, f in enumerate(names):
                    dst = os.path.join(source, "drop-%04d.txt" % i)
                    shutil.copyfile(f, dst + ".tmp")
                    os.replace(dst + ".tmp", dst)

            # streaming leg: the same files through watcher discovery,
            # admission preview and per-boundary journal publish
            # (micro-checkpoints off: the checkpoint ladder prices those
            # separately)
            stream_rates, stalls = [], []
            for rep in range(3):
                source = os.path.join(root, "src-%d" % rep)
                os.makedirs(source)
                drop_all(source, files)
                stream = StreamingDataset(
                    sfeed, source, micro_pass_instances=win_instances)
                runner = StreamingRunner(strainer, stream, cm=cm,
                                         base_every=0,
                                         admission_max_drift=10.0)
                res = runner.run(max_micro_passes=n_windows,
                                 idle_timeout=10.0)
                stream_rates.append(res["examples_per_sec"])
                stalls.append(res["max_ingest_wait_secs"])
            stream_eps = float(np.median(stream_rates))

            # freshness leg: atomic drop -> trained rows visible to a
            # serving-side journal poll
            fresh = []
            for rep in range(3):
                source = os.path.join(root, "fsrc-%d" % rep)
                os.makedirs(source)
                stream = StreamingDataset(
                    sfeed, source, micro_pass_instances=win_instances)
                runner = StreamingRunner(strainer, stream, cm=cm,
                                         base_every=0,
                                         admission_max_drift=10.0)
                jsrc = JournalDeltaSource([cm.journal.dir])
                jsrc.poll()                 # drain the pre-drop backlog
                hit = {}

                def tail(js=jsrc, out=hit):
                    while "ts" not in out:
                        if js.poll():
                            out["ts"] = time.time()
                            return
                        time.sleep(0.02)

                t = _threading.Thread(target=tail, daemon=True)
                t.start()
                t0 = time.time()
                drop_all(source, files[:WIN_FILES])
                runner.run(max_micro_passes=1, idle_timeout=5.0)
                t.join(timeout=5.0)
                jsrc.close()
                if "ts" in hit:
                    fresh.append(hit["ts"] - t0)

            # e2e watermark leg (round 20): born -> trained -> journal
            # tailed -> view swapped -> PULLED, sampled per pull against
            # the response's watermark stamp through a live
            # ServingServer — the continuously-sampled feed-to-serve
            # freshness the watermark plane publishes, not a poll probe.
            # Guarded separately: a serving-side failure must not void
            # the streaming rates above.
            e2e_samples: list = []
            e2e_error = None
            try:
                from paddlebox_tpu.serving.client import ServingClient
                from paddlebox_tpu.serving.server import ServingServer
                source = os.path.join(root, "e2e-src")
                os.makedirs(source)
                # one window with base_every=1 lands a base day so the
                # serving root has a composed view to stack on
                stream = StreamingDataset(
                    sfeed, source, micro_pass_instances=win_instances)
                runner = StreamingRunner(strainer, stream, cm=cm,
                                         base_every=1,
                                         admission_max_drift=10.0)
                drop_all(source, files[:WIN_FILES])
                runner.run(max_micro_passes=1, idle_timeout=5.0)
                old_jdir = _fl.get_flag("serving_journal_dir")
                old_ref = _fl.get_flag("serving_refresh_secs")
                _fl.set_flag("serving_journal_dir", cm.journal.dir)
                _fl.set_flag("serving_refresh_secs", 0.05)
                server = cli = None
                try:
                    server = ServingServer(os.path.join(root, "xbox"))
                    cli = ServingClient([("127.0.0.1", server.port)])
                    probe_keys = np.arange(1, 65, dtype=np.uint64)
                    stop_ev = _threading.Event()

                    def puller():
                        while not stop_ev.is_set():
                            try:
                                cli.pull(probe_keys)
                            except Exception:
                                pass
                            if cli.last_watermark > 0:
                                e2e_samples.append(
                                    time.time() - cli.last_watermark)
                            stop_ev.wait(0.02)

                    pt = _threading.Thread(target=puller, daemon=True)
                    pt.start()
                    # continuous feed: the remaining windows drain
                    # through train->journal while pulls sample
                    stream2 = StreamingDataset(
                        sfeed, source,
                        micro_pass_instances=win_instances)
                    runner2 = StreamingRunner(strainer, stream2, cm=cm,
                                              base_every=0,
                                              admission_max_drift=10.0)
                    drop_all(source, files[WIN_FILES:])
                    runner2.run(max_micro_passes=n_windows - 1,
                                idle_timeout=5.0)
                    time.sleep(0.3)  # final swap + a last stamped pull
                    stop_ev.set()
                    pt.join(timeout=5.0)
                finally:
                    if cli is not None:
                        cli.close()
                    if server is not None:
                        server.drain()
                    _fl.set_flag("serving_journal_dir", old_jdir)
                    _fl.set_flag("serving_refresh_secs", old_ref)
            except Exception as e:  # noqa: BLE001 — must not void the
                # rates above; reported, and fails the run like any ladder
                ladder_errors.append("streaming.e2e_watermark")
                e2e_error = repr(e)[:300]
                e2e_samples = []
            return {
                "batch_resident_examples_per_sec": round(batch_eps, 1),
                "streaming_examples_per_sec": round(stream_eps, 1),
                "streaming_vs_batch": round(stream_eps / batch_eps, 3),
                "streaming_runs": [round(r, 1) for r in stream_rates],
                "max_ingest_wait_secs": round(max(stalls), 3),
                "freshness_secs": (round(float(np.median(fresh)), 3)
                                   if fresh else None),
                "freshness_runs": [round(f, 3) for f in fresh],
                "freshness_e2e_p50_secs": (
                    round(float(np.percentile(e2e_samples, 50)), 3)
                    if e2e_samples else None),
                "freshness_e2e_p99_secs": (
                    round(float(np.percentile(e2e_samples, 99)), 3)
                    if e2e_samples else None),
                "freshness_e2e_samples": len(e2e_samples),
                "freshness_e2e_error": e2e_error,
                "window_instances": win_instances}
        finally:
            _fl.set_flag("streaming_poll_secs", old_poll)
            if strainer is not None:
                strainer.close()
            shutil.rmtree(root, ignore_errors=True)

    streaming = guarded("streaming_ladder", streaming_ladder)

    eps = CHUNK * BATCH / dt
    print(json.dumps({
        "schema_version": SCHEMA_VERSION,
        "examples_per_sec": eps,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "compute_dtype": dtype,
        "push_write": push_write_mode,
        "steady_ms_per_step": round(dt * 1e3 / CHUNK, 4),
        "e2e_examples_per_sec": round(
            max(e2e_grouped, e2e_per_chunk, e2e_lean), 1),
        "e2e_grouped": e2e_grouped,
        "e2e_ungrouped": e2e_per_chunk,
        "e2e_lean": e2e_lean,
        "e2e_lean_ids_only": tiers["lean_ids_only"]["examples_per_sec"],
        "e2e_uid_lean": e2e_lean,
        "e2e_uid_delta": tiers["uid_delta"]["examples_per_sec"],
        "e2e_lean_vs_resident": round(e2e_lean / eps, 3),
        "wire_bytes_per_step": {t: v["wire_bytes_per_step"]
                                for t, v in tiers.items()},
        "e2e_tiers": tiers,
        "pass_amortized": pass_amortized,
        "pass_amortized_examples_per_sec": pa_eps,
        "push_ladder": ladder,
        "checkpoint": ckpt,
        "ckpt_save_keys_per_sec": (ckpt.get("store", {})
                                   .get("columnar", {})
                                   .get("save_keys_per_sec", 0)),
        "ckpt_load_keys_per_sec": (ckpt.get("store", {})
                                   .get("columnar", {})
                                   .get("load_keys_per_sec", 0)),
        "ingest": ingest,
        "ingest_cold_pass_examples_per_sec": ingest.get(
            "cold_pass_examples_per_sec", 0),
        "streaming": streaming,
        "streaming_examples_per_sec": streaming.get(
            "streaming_examples_per_sec", 0),
        "streaming_freshness_secs": streaming.get("freshness_secs", 0),
        "freshness_e2e_p99_secs": streaming.get(
            "freshness_e2e_p99_secs", 0),
        "ssd_tier": ssd,
        "ssd_promote_keys_per_sec": ssd.get(
            "ssd_promote_keys_per_sec", 0),
        "ssd_fault_keys_per_sec": ssd.get(
            "cold_fault_keys_per_sec", 0),
        "telemetry_overhead": telemetry,
        "flight_overhead": flight,
        "quality_overhead": quality,
        "lockwatch_overhead": lockwatch_cost,
        "device": device_rec,
        "device_bytes_accessed_per_example": device_rec.get(
            "bytes_accessed_per_example", 0),
        "compile_warmup_s": round(t_compile, 1),
        "ladder_errors": ladder_errors,
    }), flush=True)
    if ladder_errors:
        raise SystemExit("bench.py: ladder(s) raised: %s"
                         % ", ".join(ladder_errors))


def _last_json(stdout: str, probe_name=None):
    """The LAST JSON line of a child's stdout (None when there is none);
    with probe_name, the last one whose "probe" field says so."""
    for line in reversed(stdout.strip().splitlines()):
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if probe_name is None or (isinstance(d, dict)
                                  and d.get("probe") == probe_name):
            return d
    return None


def _sub(args, timeout):
    """Run a bench subcommand in a subprocess. Returns (rc, payload, why):
    payload is the last JSON line of its stdout (None when there is none
    — also after a timeout, where rc is None), why the end of its stderr
    for a failed run."""
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__)] + args,
                           capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, None, f"timeout after {timeout}s"
    payload = _last_json(r.stdout)
    why = ""
    if r.returncode != 0 or payload is None:
        tail = (r.stderr or r.stdout or "").strip().splitlines()[-6:]
        why = f"rc={r.returncode}: " + " | ".join(tail)
    return r.returncode, payload, why


def _host_ladder(script: str, args, probe_name: str) -> dict:
    """One multi-process host-plane ladder (tools/<script>), run after the
    measuring child has released the chip and pinned to the CPU backend —
    it times host code, and its N processes must not fight over the chip.
    A failure is recorded as {"error": ...} and fails the run."""
    try:
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", script), *args],
            capture_output=True, text=True, timeout=240,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    except (OSError, subprocess.TimeoutExpired) as e:
        return {"error": repr(e)[:200]}
    return _last_json(r.stdout, probe_name) or {
        "error": "no %s line; rc=%d" % (probe_name, r.returncode)}


def main() -> int:
    rc, probe_out, why = _sub(["--probe"], PROBE_TIMEOUT)
    if rc != 0 or probe_out is None:
        print("bench.py: no chip, no number — probe failed: " + why,
              file=sys.stderr)
        return 1
    rc, result, why = _sub(["--measure"], RUN_TIMEOUT)
    if result is None or "examples_per_sec" not in result:
        print("bench.py: measurement failed: " + why, file=sys.stderr)
        return 1

    # 2-process host-plane exchange ladder (store allgather vs p2p socket
    # mesh vs p2p+pre-wire-uid-dedup; the 2-and-4-process ladder is
    # tools/hostplane_probe.py) and the multi-box serving fleet ladder
    # (QPS vs box count over real spawned grids, coalescing RPC reduction,
    # journal staleness, kill-one-replica failover — tools/fleet_probe.py)
    hostplane = _host_ladder("hostplane_probe.py",
                             ["--worlds", "2", "--kb", "8192"], "hostplane")
    fleet = _host_ladder("fleet_probe.py", [], "fleet")
    ladder_errors = list(result.get("ladder_errors", []))
    ladder_errors += [n for n, d in (("hostplane", hostplane),
                                     ("fleet", fleet)) if "error" in d]

    rest = dict(result)
    eps = rest.pop("examples_per_sec")
    final = {
        "metric": "deepfm_sparse_train_examples_per_sec_per_chip",
        "value": round(eps, 1),
        "unit": "examples/sec/chip",
        **rest,
        "hostplane": hostplane,
        "fleet": fleet,
        "fleet_pull_keys_per_sec": (fleet.get("ladder") or [{}])[-1].get(
            "keys_per_sec", 0),
        "fleet_qps": (fleet.get("ladder") or [{}])[-1].get("qps", 0),
        "ladder_errors": ladder_errors,
        "probe": probe_out,
    }
    final["bench_json"] = _write_record(final)
    print(json.dumps(final))
    if rc != 0 or ladder_errors:
        print("bench.py: run FAILED after the record — %s"
              % (", ".join(ladder_errors) or why), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if sys.argv[1:] == ["--probe"]:
        probe()
    elif sys.argv[1:] == ["--measure"]:
        measure()
    else:
        sys.exit(main())
