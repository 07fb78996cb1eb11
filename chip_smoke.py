"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of the one model that ever reached a chip (DeepFM, 32 slots,
embedx 8, hidden 512/256/128, batch 1024, 1M-row pass slab, bf16 dense
tower, every flag at its default), and checks what comes out by the repo's
own means. ONE process; nothing it starts touches JAX.

    python chip_smoke.py                # leg A, one chip
    python chip_smoke.py --chips 4      # ... plus leg B on a 4-chip mesh
    python chip_smoke.py --dry-run-cpu  # tiny shapes on the CPU, to debug
                                        # the command before chip time

  leg A    text files -> BoxDataset -> run_preloaded_passes (two
           BoxTrainer.train_pass calls: incremental begin_pass,
           PromotePrefetcher, touched-row end_pass write-back) with a
           streaming AUC registered -> predict_batches on one file.
  leg B    ShardedBoxTrainer on device_mesh_1d(N), N x 1M-row slab, two
           train_passes, twice: once as leg A is configured (bf16, flags
           at their defaults), once in f32 against a one-chip BoxTrainer
           fed the same global batches, whose loss it must match. Every
           device must hold its slab shard in both.

Without --dry-run-cpu a missing chip is a failure: the script exits
non-zero and prints no result. One JSON line per leg, then as the LAST
line of stdout {"ok": true, "device": {...}} with the device as JAX
reports it. Seconds in the leg lines are information labelled with the
device they were taken on, never metrics; a dry run prints none.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Tuple

D = 8
MAX_LEN = 4
FALLBACK_STATS = ("native_lib_unavailable", "host_store_python_fallback",
                  "route_numpy_fallback")
# tests/test_sharded.py's loss tolerance between two reduction orders
SHARDED_LOSS_RTOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    slots: int
    hidden: Tuple[int, ...]
    batch: int              # per worker
    capacity: int           # pass slab rows per chip
    vocab: int              # feasigns per slot
    lines: int              # lines per data file (= one batch)
    a_batches: int          # leg A batches per pass (> scan_chunk, so the
                            # scan megastep AND the per-step tail compile)
    b_steps: int            # leg B global steps per pass
    dense_lr: float


FULL = Sizes(slots=32, hidden=(512, 256, 128), batch=1024, capacity=1 << 20,
             vocab=4000, lines=1024, a_batches=12, b_steps=9, dense_lr=1e-3)
TINY = Sizes(slots=4, hidden=(16, 8), batch=32, capacity=1 << 11, vocab=60,
             lines=32, a_batches=10, b_steps=9, dense_lr=1e-2)


class Failed(Exception):
    """A leg's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


# ------------------------------------------------------------ observation

class CacheWatch:
    """Persistent-compile-cache accounting: where it lives, how many
    entries it holds, and how many compiles it served this process."""

    def __init__(self) -> None:
        import jax
        from paddlebox_tpu.utils.platform import ensure_compile_cache
        self.path = ensure_compile_cache()
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def entries(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.path)
                       if not n.endswith("-atime"))
        except OSError:
            return 0


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def versions() -> dict:
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def device_bytes(devices, stat: str = "peak_bytes_in_use") -> list:
    """One memory_stats() figure per device (None where the backend has
    none, i.e. the CPU dry run)."""
    out = []
    for d in devices:
        ms = d.memory_stats()
        out.append(int(ms[stat]) if ms else None)
    return out


def jit_report() -> dict:
    """Compile counts/seconds and the donation audit's verdict for every
    instrumented entry point that has compiled so far. The registry is by
    name: a later trainer's entry replaces an earlier one's, and names only
    an earlier leg used stay listed."""
    from paddlebox_tpu.obs import device as obs_device
    snap = obs_device.snapshot()
    fns = {}
    for name, e in snap["entries"].items():
        if not e["compiles"]:
            continue
        fns[name] = {"compiles": e["compiles"],
                     "compile_ms": e["compile_ms"]}
        if "donation" in e:
            fns[name]["donation_supported"] = e["donation"]["supported"]
            fns[name]["donation_checks"] = e["donation"]["checks"]
    return {"fns": fns, "donation_miss": snap["donation_miss"],
            "device_recompiles": snap["recompiles"]}


def compile_counts() -> Dict[str, int]:
    from paddlebox_tpu.obs import device as obs_device
    return {n: e["compiles"]
            for n, e in obs_device.snapshot()["entries"].items()}


def new_compiles(before: Dict[str, int], after: Dict[str, int]) -> dict:
    """fn -> compiles added between two compile_counts() (information: the
    device_recompiles stat, which is what gates, allows each fn a warm-up
    of flag device_recompile_warmup signatures before it counts one)."""
    return {n: c - before.get(n, 0) for n, c in after.items()
            if c > before.get(n, 0)}


def fallback_stats() -> Dict[str, int]:
    from paddlebox_tpu.utils.stats import stat_get
    return {name: int(stat_get(name)) for name in FALLBACK_STATS}


def store_show_sum(stores) -> float:
    from paddlebox_tpu.embedding import accessor as acc
    total = 0.0
    for st in stores:
        _keys, vals = st.state_items()
        total += float(vals[:, acc.SHOW].astype("float64").sum())
    return total


# ------------------------------------------------------------------ set-up

def write_files(sz: Sizes, out_dir: str, n_files: int):
    """n_files one-batch files from the repo's synthetic CTR generator
    (fixed seed) + the matching feed config at the leg's batch size."""
    from paddlebox_tpu.data import write_synthetic_ctr_files
    files, feed = write_synthetic_ctr_files(
        out_dir, num_files=n_files, lines_per_file=sz.lines,
        num_slots=sz.slots, vocab_per_slot=sz.vocab, max_len=MAX_LEN, seed=7)
    return files, feed


def table_config(capacity: int, lr: float, mf_range: float):
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    return TableConfig(
        embedx_dim=D, pass_capacity=capacity,
        optimizer=SparseOptimizerConfig(
            mf_create_thresholds=0.0, mf_initial_range=mf_range,
            feature_learning_rate=lr, mf_learning_rate=lr))


def deepfm(sz: Sizes):
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.deepfm import DeepFM
    return DeepFM(ModelSpec(num_slots=sz.slots, slot_dim=3 + D),
                  hidden=sz.hidden)


def dataset(feed, files):
    from paddlebox_tpu.data import BoxDataset
    # one reader: record order = file order, so a run is reproducible
    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files)
    return ds


# ------------------------------------------------------------------- leg A

def leg_a(sz: Sizes, tmp: str, dry: bool) -> dict:
    import jax
    import numpy as np

    from paddlebox_tpu.config.configs import TrainerConfig
    from paddlebox_tpu.metrics import BasicAucCalculator
    from paddlebox_tpu.train.preload import run_preloaded_passes
    from paddlebox_tpu.train.trainer import BoxTrainer
    from paddlebox_tpu.utils.stats import stat_get

    t0 = time.perf_counter()
    half = sz.a_batches // 2
    files, feed = write_files(sz, os.path.join(tmp, "a"),
                              sz.a_batches + half + 1)
    feed = type(feed)(slots=feed.slots, batch_size=sz.batch)
    t_data = time.perf_counter() - t0

    trainer = BoxTrainer(
        deepfm(sz), table_config(sz.capacity, 0.1, 1e-3), feed,
        TrainerConfig(dense_lr=sz.dense_lr, compute_dtype="bfloat16"),
        seed=0)
    trainer.metrics.init_metric("auc", "label", "pred", table_size=1 << 16,
                                mask_var="mask")
    # pass 2 keeps half of pass 1's files: its begin_pass promotes a delta
    # into the resident slab instead of rebuilding it
    passes = [dataset(feed, files[:sz.a_batches]),
              dataset(feed, files[half:half + sz.a_batches])]
    per_pass: List[dict] = []
    marks = [time.perf_counter()]

    def after_pass(_i, stats):
        msg = trainer.metrics.get_metric_msg("auc")
        trainer.metrics.get("auc").calculator.reset()   # AUC per pass
        per_pass.append({
            "loss": stats["loss"], "auc": msg["auc"],
            "batches": stats["batches"], "instances": stats["instances"],
            "show_sum": store_show_sum([trainer.table.store]),
            "recompiles": int(stat_get("device_recompiles")),
            "compiles": compile_counts(),
            "promote_hit": int(stat_get("pass_rows_promote_hit")),
            "written_back": int(stat_get("pass_rows_written_back"))})
        marks.append(time.perf_counter())

    try:
        run_preloaded_passes(trainer, passes, after_pass=after_pass)
        p1, p2 = per_pass

        eval_ds = dataset(feed, files[-1:])
        eval_ds.load_into_memory()
        preds, labels = trainer.predict_batches(eval_ds)
        marks.append(time.perf_counter())
    finally:
        trainer.close()

    calc = BasicAucCalculator(1 << 16)
    calc.add_data(preds, labels)
    calc.compute()
    rep = jit_report()
    rec = {
        "leg": "A", "push_write": trainer._push_write,
        "compute_dtype": trainer.cfg.compute_dtype,
        "steps": p1["batches"] + p2["batches"],
        "first_loss": p1["loss"], "last_loss": p2["loss"],
        "auc_pass1": p1["auc"], "auc_pass2": p2["auc"],
        "auc_predict": calc.auc(), "predict_instances": int(preds.size),
        "store_show_sum": [p1["show_sum"], p2["show_sum"]],
        "promote_hit_rows": p2["promote_hit"],
        "written_back_rows": p2["written_back"],
        "recompiles_in_pass2": p2["recompiles"] - p1["recompiles"],
        "compiles_in_pass2": new_compiles(p1["compiles"], p2["compiles"]),
        "fallback_stats": fallback_stats(), **rep,
        "peak_bytes_in_use": device_bytes(jax.devices()[:1])}
    if not dry:
        rec["seconds_info"] = {
            "device_kind": jax.devices()[0].device_kind,
            "data_gen": round(t_data, 2),
            "pass1_incl_compile": round(marks[1] - marks[0], 2),
            "pass2": round(marks[2] - marks[1], 2),
            "predict_incl_compile": round(marks[3] - marks[2], 2),
            "compile": round(sum(f["compile_ms"] for f in
                                 rep["fns"].values()) / 1e3, 2)}
    print(json.dumps(rec), flush=True)

    check(np.isfinite([p1["loss"], p2["loss"]]).all(), "non-finite loss")
    check(p2["loss"] < p1["loss"], "loss did not fall: %r" % rec)
    if not dry:
        # the AUC gates need the full pass's 12k instances to clear sampling
        # noise; the dry run's few hundred print theirs ungated
        check(p2["auc"] > p1["auc"], "AUC after pass 2 not above pass 1's")
        check(calc.auc() > 0.5, "predict AUC not above chance")
    check(p1["instances"] == p2["instances"] == sz.a_batches * sz.lines,
          "instance count")
    # every valid key occurrence adds one show: the store's counters must
    # have accumulated across BOTH end_pass write-backs
    check(0 < p1["show_sum"] < p2["show_sum"],
          "host-store show counters did not accumulate across end_pass")
    check(p2["promote_hit"] > 0, "pass 2 was not an incremental begin_pass")
    check(p2["written_back"] > 0, "no touched-row write-back")
    check(rec["recompiles_in_pass2"] == 0, "recompile in pass 2")
    check(not any(rec["fallback_stats"].values()),
          "fallback tier active: %r" % rec["fallback_stats"])
    check(preds.shape == labels.shape == (sz.lines,), "predict shape")
    check(np.isfinite(preds).all() and (preds > 0).all()
          and (preds < 1).all(), "predict values")
    return rec


# ------------------------------------------------------------------- leg B

def sharded_two_passes(sz: Sizes, P: int, tcfg, table_cfg, feed_w,
                       pass_files, base: Dict[int, int]) -> dict:
    """Two ShardedBoxTrainer.train_pass calls on device_mesh_1d(P); what
    each pass left behind, the slab placement the trainer reports (bytes
    in use against `base`, device id -> bytes at the leg's start), and the
    jit accounting, taken before any other trainer reuses the entry names."""
    from paddlebox_tpu.parallel.mesh import device_mesh_1d
    from paddlebox_tpu.parallel.sharded_trainer import ShardedBoxTrainer
    from paddlebox_tpu.utils.stats import stat_get

    t0 = time.perf_counter()
    sharded = ShardedBoxTrainer(deepfm(sz), table_cfg, feed_w, tcfg,
                                mesh=device_mesh_1d(P), seed=0)
    sharded.metrics.init_metric("auc", "label", "pred", table_size=1 << 16,
                                mask_var="mask")
    passes = []
    try:
        for fl in pass_files:
            stats = sharded.train_pass(dataset(feed_w, fl))
            auc = sharded.metrics.get_metric_msg("auc")["auc"]
            sharded.metrics.get("auc").calculator.reset()   # AUC per pass
            pl = sharded.last_slab_placement
            pl = {"shape": pl["shape"], "shards": [
                # held: what the device holds beyond the leg's start
                {"device": sh["device"], "shape": sh["shape"],
                 "held": None if sh["bytes_in_use"] is None
                 else sh["bytes_in_use"] - base[sh["device"]]}
                for sh in pl["shards"]]}
            passes.append({
                "loss": stats["loss"], "auc": auc,
                "show_sum": store_show_sum(sharded.table.stores),
                "recompiles": int(stat_get("device_recompiles")),
                "compiles": compile_counts(), "slab": pl})
        p1, p2 = passes
        return {"push_write": sharded._push_write,
                "compute_dtype": sharded.cfg.compute_dtype,
                "recompiles_in_pass2": p2["recompiles"] - p1["recompiles"],
                "compiles_in_pass2": new_compiles(p1.pop("compiles"),
                                                  p2.pop("compiles")),
                "passes": passes, **jit_report(),
                "shard_slab_bytes": sz.capacity
                * sharded.table.layout.device_bytes_per_row,
                "_seconds": time.perf_counter() - t0}
    finally:
        sharded.close()


def check_sharded(run: dict, devs, P: int, what: str) -> None:
    """Finite falling loss, accumulating show counters, and every pass's
    slab stack one [1, cap, W] shard per device with no device holding a
    slab-sized surplus."""
    import numpy as np
    p1, p2 = run["passes"]
    check(np.isfinite([p1["loss"], p2["loss"]]).all(),
          "%s: non-finite loss" % what)
    check(p2["loss"] < p1["loss"], "%s: loss did not fall" % what)
    check(0 < p1["show_sum"] < p2["show_sum"],
          "%s: show counters did not accumulate across end_pass" % what)
    for p in run["passes"]:
        pl = p["slab"]
        check([s["device"] for s in pl["shards"]]
              == sorted(d.id for d in devs[:P])
              and all(s["shape"] == [1] + pl["shape"][1:]
                      for s in pl["shards"]),
              "%s: slab not one shard per device: %r" % (what, pl))
        held = [s["held"] for s in pl["shards"]]
        if held[0] is not None:
            check(min(held) >= run["shard_slab_bytes"],
                  "%s: a device does not hold its slab shard: %r"
                  % (what, held))
            check(max(held) - min(held) < run["shard_slab_bytes"],
                  "%s: a device holds a slab-sized surplus: %r"
                  % (what, held))


def leg_b(sz: Sizes, tmp: str, chips: int, dry: bool) -> dict:
    """ShardedBoxTrainer on `chips` devices, twice.

    default: the production step — leg A's configuration (bf16 tower, mf
    creation randoms, every flag at its default, so push_write=auto and the
    default matmul precision), two train_passes, checked like leg A: finite
    falling loss, show counters, slab placement, no recompile in pass 2.

    parity: against a one-chip BoxTrainer. The oracle is leg A's trainer
    class fed the SAME global batches: global step i is the union of the P
    workers' i-th batches, so the oracle runs batch P*B over files listed
    step-major where the sharded run lists them worker-major (one file =
    one worker batch; shuffle off on both sides). A worker's loss is a mean
    over ITS batch, so its embedding gradients are P times the oracle's
    global-mean ones; the in-table adagrad step is linear in the gradient
    (g2sum stays ~1e-7 against initial_g2sum=3), so the oracle's sparse
    learning rates are scaled by P. Creation randoms are keyed by slab row
    id, which differs between layouts: mf_initial_range=0 takes them out.
    f32 compute at the highest matmul precision on both sides — what this
    run compares is the sharding, not bf16 rounding."""
    import gc

    import jax

    from paddlebox_tpu.config import flags
    from paddlebox_tpu.config.configs import TrainerConfig
    from paddlebox_tpu.train.trainer import BoxTrainer

    devs = jax.devices()
    check(len(devs) >= chips, "--chips %d but JAX sees %d device(s)"
          % (chips, len(devs)))
    P, S = chips, sz.b_steps
    gc.collect()                  # earlier legs' arrays off device 0
    base = dict(zip((d.id for d in devs[:P]),
                    device_bytes(devs[:P], "bytes_in_use")))
    lr = 0.05
    t0 = time.perf_counter()
    per_pass = P * S                     # files (= worker batches) a pass
    files, feed1 = write_files(sz, os.path.join(tmp, "b"),
                               per_pass + per_pass // 2)
    t_data = time.perf_counter() - t0
    feed_w = type(feed1)(slots=feed1.slots, batch_size=sz.batch)
    feed_g = type(feed1)(slots=feed1.slots, batch_size=P * sz.batch)
    pass_files = [files[:per_pass],
                  files[per_pass // 2:per_pass // 2 + per_pass]]

    def step_major(fl):
        # worker w trains fl[w*S + i] at step i; the oracle's i-th batch
        # is those P files back to back
        return [fl[w * S + i] for i in range(S) for w in range(P)]

    default = sharded_two_passes(
        sz, P, TrainerConfig(dense_lr=sz.dense_lr, compute_dtype="bfloat16"),
        table_config(P * sz.capacity, 0.1, 1e-3), feed_w, pass_files, base)

    tcfg = TrainerConfig(dense_lr=sz.dense_lr, compute_dtype="float32")
    old_shuffle = flags.get_flag("dataset_disable_shuffle")
    flags.set_flag("dataset_disable_shuffle", True)
    try:
        with jax.default_matmul_precision("highest"):
            parity = sharded_two_passes(
                sz, P, tcfg, table_config(P * sz.capacity, lr, 0.0), feed_w,
                pass_files, base)
            # the oracle lands on device 0 alone: per-device peaks first
            peaks = device_bytes(devs[:P])
            oracle = BoxTrainer(
                deepfm(sz), table_config(sz.capacity, P * lr, 0.0), feed_g,
                tcfg, seed=0)
            try:
                or_losses = [
                    oracle.train_pass(dataset(feed_g, step_major(fl)))["loss"]
                    for fl in pass_files]
                or_show = store_show_sum([oracle.table.store])
            finally:
                oracle.close()
    finally:
        flags.set_flag("dataset_disable_shuffle", old_shuffle)

    sh_losses = [p["loss"] for p in parity["passes"]]
    rel = [abs(a - b) / abs(b) for a, b in zip(sh_losses, or_losses)]
    secs = {"default": default.pop("_seconds"),
            "parity": parity.pop("_seconds")}
    parity.update(matmul_precision="highest", losses_one_chip=or_losses,
                  loss_rel_diff=rel, loss_rtol=SHARDED_LOSS_RTOL,
                  store_show_sum_one_chip=or_show,
                  one_chip_fns=jit_report()["fns"])
    rec = {"leg": "B", "chips": P, "worker_batch": sz.batch,
           "steps_per_run": 2 * S,
           "mesh_devices": [[d.id, list(getattr(d, "coords", ()))]
                            for d in devs[:P]],
           "default": default, "parity": parity,
           "fallback_stats": fallback_stats(),
           "peak_bytes_in_use": peaks}
    if not dry:
        rec["seconds_info"] = {
            "device_kind": devs[0].device_kind,
            "data_gen": round(t_data, 2),
            "default_two_passes_incl_compile": round(secs["default"], 2),
            "parity_two_passes_incl_compile": round(secs["parity"], 2),
            "total": round(time.perf_counter() - t0, 2)}
    print(json.dumps(rec), flush=True)

    check_sharded(default, devs, P, "default step")
    check(default["recompiles_in_pass2"] == 0,
          "default step: recompile in pass 2")
    check_sharded(parity, devs, P, "parity run")
    check(parity["passes"][1]["show_sum"] == or_show,
          "show counters: sharded %r vs one chip %r"
          % (parity["passes"][1]["show_sum"], or_show))
    check(max(rel) <= SHARDED_LOSS_RTOL,
          "sharded loss %r != one-chip loss %r" % (sh_losses, or_losses))
    check(not any(rec["fallback_stats"].values()), "fallback tier active")
    return rec


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="4 = also run leg B on a 4-chip mesh")
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny shapes on the CPU backend; prints no "
                         "seconds")
    args = ap.parse_args(argv)
    dry = args.dry_run_cpu
    if dry and args.chips > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # the dry run's stand-in for a multi-chip host
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % args.chips)

    import jax
    backend = jax.default_backend()
    if backend != ("cpu" if dry else "tpu"):
        print("chip_smoke: default backend is %r, need %r%s"
              % (backend, "cpu" if dry else "tpu",
                 "" if dry else " (--dry-run-cpu debugs the command on a "
                 "CPU; it is not a result)"), file=sys.stderr)
        return 1
    cache = CacheWatch()            # also imports the package: a directory
    entries_before = cache.entries()  # holding only this file fails here
    sz = TINY if dry else FULL
    head = {"device": device_info(), "versions": versions(),
            "compile_cache": cache.path, "dry_run": dry}
    tmp = tempfile.mkdtemp(prefix="pbtpu_chip_smoke_")
    # every leg of the plan runs: A always, B on a mesh
    plan = [("A", lambda: leg_a(sz, tmp, dry))]
    if args.chips > 1:
        plan.append(("B", lambda: leg_b(sz, tmp, args.chips, dry)))
    failures = []
    t0 = time.perf_counter()
    try:
        for name, run in plan:
            try:
                run()
            except Failed as e:
                failures.append("leg %s: %s" % (name, e))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    summary = dict(head, legs=[name for name, _ in plan],
                   failures=failures,
                   compile_cache_entries=[entries_before, cache.entries()],
                   compile_cache_hits=cache.hits,
                   compile_cache_misses=cache.misses)
    if not dry:
        summary["seconds_info"] = {
            "device_kind": head["device"]["kind"],
            "total": round(time.perf_counter() - t0, 2)}
    print(json.dumps(summary), flush=True)
    if failures:
        for f in failures:
            print("chip_smoke FAILED — " + f, file=sys.stderr)
        return 1
    final = {"ok": True, "device": head["device"]}
    if dry:
        final["dry_run"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
