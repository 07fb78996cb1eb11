"""python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of BENCHMARK.json, one JSON object as the last line
of standard output. The cell's configuration, traffic mix and per-layer
metrics are files found by the names BENCHMARK.json gives them; there is
no branch on a cell's or a configuration's name (benchmarks/README.md).

Without a TPU (or with fewer chips than the cell asks for) it exits 3 and
prints no result. ``--rehearse`` walks set-up -> window -> last line at
tiny shapes (REHEARSE, then the configuration's own `rehearse` keys) on
whatever backend is there and prints no device metric and no rate.
``--control 1`` also puts the lower-precision control and each planted
fault in the program's place and passes it through the same
comparison (its verdict goes under `controls`); ``--control 2 --seeds
a,b,c`` does only that, with no program and no window, one line a seed
(for setting limits; the driver's runs never ask for either).
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

# exact comparisons, held in every cell; the limits of the gaps against
# the reference are the configuration's own (its file's `limits`)
EXACT = {"count_mismatch": 0, "compiles_in_window": 0}
REHEARSE = {"batch_size": 64, "occupied_rows": 20_000,
            "pass_capacity": 1 << 15}
# the host spans an idle gap of the device may go to: siblings, so that
# the longest-overlap rule of trace_reduce.idle_gaps picks one (a parent
# always overlaps a gap at least as long as its child)
HOST_SPANS = ("pass_begin", "pass_end", "ingest_feed_pass", "host_stage",
              "ingest_wait_preload", "chunk_drain", "scan_dispatch",
              "pass_split_batches")
# the counters the exact counts need; the metric files name the others
EXACT_COUNTERS = ("ingest_keys_parsed",)
SAMPLE_ROWS = 200_000


def rehearsal(cfg: dict) -> dict:
    """The configuration at the sizes --rehearse walks it at: REHEARSE,
    then the configuration's own `rehearse` object, any keys (a tower
    shrinks its slots, widths and layers there)."""
    return {**cfg, **REHEARSE, **cfg.get("rehearse", {})}


def log(*a) -> None:
    print("[bench %7.1fs]" % (time.perf_counter() - _T_PROCESS), *a,
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell, its configuration (json + module beside it), its mix and
    the per-layer metrics it reports, all found by name."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name in cells:
        cell = cells[name]
        conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
        cfg_path = os.path.join(ROOT, conf["file"])
    else:
        # a cell that is queued: <config>.<mix> whose files are here but
        # which BENCHMARK.json does not list yet (one chip)
        config, _, mix_name = name.partition(".")
        cfg_path = os.path.join(HERE, "configs", config + ".json")
        if not (os.path.exists(cfg_path) and os.path.exists(os.path.join(
                HERE, "traffic", mix_name + ".json"))):
            raise SystemExit("unknown workload %r (have %s)"
                             % (name, sorted(cells)))
        cell = {"name": name, "config": config, "traffic": mix_name,
                "chips": 1}
    cfg = load_json(cfg_path)
    py = os.path.splitext(cfg_path)[0] + ".py"
    spec = importlib.util.spec_from_file_location("bench_config", py)
    cfg_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cfg_mod)
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]
    layer = {}
    for m in manifest["per_layer"]:
        if reports(m):
            layer[m["name"]] = load_json(os.path.join(
                HERE, "layer_metrics", m["name"] + ".json"))
    end = [m for m in manifest["end_to_end"] if reports(m)]
    counters = set(EXACT_COUNTERS)
    for m in layer.values():
        if m["kind"] == "counter_delta_per":
            counters.update(m["args"]["counters"])
    return {"cell": cell, "cfg": cfg, "cfg_mod": cfg_mod, "mix": mix,
            "layer": layer, "end_to_end": end, "counters": sorted(counters)}


def require_chips(chips: int) -> None:
    import jax
    if jax.default_backend() != "tpu" or len(jax.devices()) < chips:
        print("benchmark needs %d TPU chip(s); found backend %r with %d "
              "device(s)" % (chips, jax.default_backend(),
                             len(jax.devices())), file=sys.stderr)
        raise SystemExit(3)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


class CompileWatch:
    """Backend compiles seen by jax.monitoring (every jit, instrumented
    or not)."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs


def instrumented_compiles() -> dict:
    from paddlebox_tpu.obs import device as obs_device
    return {n: e["compiles"]
            for n, e in obs_device.snapshot()["entries"].items()}


def counters_now(names) -> dict:
    from paddlebox_tpu.utils.stats import stat_get
    return {c: int(stat_get(c)) for c in names}


def scope_maps() -> dict:
    """{XLA module: {HLO instruction: scope}} of the instrumented jit
    entries that compiled in this process (obs/device.py)."""
    from paddlebox_tpu.obs import device as obs_device
    return {e["module"]: e["scopes"]
            for e in obs_device.snapshot()["entries"].values()
            if e.get("scopes") is not None}


def adam_mu(opt_state, params) -> dict:
    """adam's first moment per leaf, as float64 numpy."""
    import jax
    import jax.flatten_util
    import numpy as np
    states = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu"))
    mu = next(s.mu for s in states if hasattr(s, "mu"))
    if not isinstance(mu, dict):     # optax.flatten: one flat vector
        mu = jax.flatten_util.ravel_pytree(params)[1](mu)
    return {k: np.asarray(v, np.float64) for k, v in mu.items()}


def store_writer(table, embedx_dim: int):
    """each(lo, hi, columns) for traffic.Traffic.table: writes a chunk of
    the starting table into the program's host store, in the program's
    row layout: what loading yesterday's model does."""
    import numpy as np
    from harness import traffic
    from paddlebox_tpu.embedding import accessor as acc
    layout, D = table.layout, embedx_dim

    def each(lo: int, hi: int, cols: dict) -> None:
        rows = np.zeros((hi - lo, layout.width), np.float32)
        rows[:, acc.SLOT] = cols["slot"]
        rows[:, acc.SHOW] = cols["show"]
        rows[:, acc.CLICK] = cols["click"]
        rows[:, acc.MF_SIZE] = cols["mf"] * float(D)
        rows[:, acc.EMBED_W] = cols["w"]
        rows[:, layout.embedx_w:layout.embedx_w + D] = cols["x"]
        keys = np.arange(traffic.KEY_BASE + lo, traffic.KEY_BASE + hi,
                         dtype=np.uint64)
        with table.store_lock:
            table.store.assign(keys, rows)
    return each


def table_config(cfg: dict):
    """The program's TableConfig for a configuration file."""
    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig)
    lr = float(cfg["sparse_learning_rate"])
    return TableConfig(
        embedx_dim=int(cfg["embedx_dim"]),
        pass_capacity=int(cfg["pass_capacity"]),
        optimizer=SparseOptimizerConfig(
            optimizer=cfg["sparse_optimizer"],
            mf_create_thresholds=float(cfg["mf_create_thresholds"]),
            mf_initial_range=float(cfg["mf_initial_range"]),
            feature_learning_rate=lr, mf_learning_rate=lr))


def trainer_config(cfg: dict):
    from paddlebox_tpu.config.configs import TrainerConfig
    return TrainerConfig(dense_lr=float(cfg["dense_lr"]),
                         dense_optimizer=cfg["dense_optimizer"],
                         compute_dtype=cfg["compute_dtype"])


def build_trainer(cfg: dict, cfg_mod, feed, seed: int, params0):
    import jax
    from paddlebox_tpu.train.trainer import BoxTrainer
    trainer = BoxTrainer(
        cfg_mod.build_model(cfg), table_config(cfg), feed,
        trainer_config(cfg), seed=seed % (2 ** 31 - 1))
    made = jax.tree.map(lambda a: a.shape, trainer.params)
    given = jax.tree.map(lambda a: a.shape, params0)
    if made != given:
        raise SystemExit("the configuration's param_init does not match "
                         "the model: %r vs %r" % (given, made))
    # the benchmark's weights, from the seed, in the program's place
    trainer.params = jax.tree.map(lambda a: a + 0, params0)
    trainer.opt_state = trainer.dense_opt.init(trainer.params)
    return trainer


class Run:
    """One run of one cell: set-up and window (drive), what the passes
    left behind against the reference (compare), the metrics (measure)
    and the last line (result)."""

    def __init__(self, args, seed=None) -> None:
        self.args = args
        self.spec = load_cell(args.workload)
        self.cell, self.mix = self.spec["cell"], self.spec["mix"]
        self.cfg, self.cfg_mod = dict(self.spec["cfg"]), self.spec["cfg_mod"]
        if args.rehearse:
            self.cfg = rehearsal(self.cfg)
        else:
            require_chips(int(self.cell["chips"]))
        self.seed = int(args.seed if seed is None else seed)
        self.cache_root = os.path.join(HERE, ".cache")
        self.tag = "%s-%s-%s" % (self.cell["config"], self.cell["traffic"],
                                 "rehearse-" if args.rehearse else "")
        self.prog = {}               # what the check pass produced
        self.chunk_losses = []       # scan_steps' losses while recorded
        self.pass_ends = []          # host clock at each window pass's end
        self.k = 0                   # window passes begun
        self.t0 = self.warm_s = self.annot = self.trace_cm = None

    # ---------------------------------------------------- set-up + window
    def make_traffic(self):
        """Traffic and the dense weights from the seed; returns the
        weights as made on the device (the host's copy is the
        reference's). The check pass is one scan chunk of the program's
        own length, so that it runs the window's compiled scan_steps and
        no other."""
        import jax
        from harness import reference, traffic
        self.check_steps = max(1, int(trainer_config(self.cfg).scan_chunk))
        self.tf = traffic.Traffic(self.cfg, self.mix, self.seed,
                                  self.check_steps)
        params0_dev = reference.init_params(
            self.cfg_mod.param_init(self.cfg), self.seed)
        self.params0 = jax.device_get(params0_dev)
        self.check_rows = reference.check_rows(self.tf.check.rows)
        return params0_dev

    def record_scan_losses(self, on: bool) -> None:
        """While on, the losses that the trainer's compiled scan_steps
        returns are kept (device arrays; nothing waits). The jitted
        program underneath is the one the window drives."""
        fns = self.trainer.fns
        if on:
            inner = self._scan_steps = fns.scan_steps

            def recorded(*a, **kw):
                out = inner(*a, **kw)
                self.chunk_losses.append(out[3])
                return out
            fns.scan_steps = recorded
        else:
            fns.scan_steps = self._scan_steps

    def drive(self) -> None:
        import jax
        from harness import traffic
        from paddlebox_tpu.train.preload import run_preloaded_passes
        from paddlebox_tpu.utils.platform import ensure_compile_cache
        ensure_compile_cache()
        # jax keys a cached program without its metadata unless told to,
        # and the scope maps are metadata: with it in the key a program
        # from the cache carries the scopes of the code that asks for it
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        self.watch = CompileWatch()
        params0_dev = self.make_traffic()
        tf = self.tf
        pool_files, check_file = tf.write_files(
            os.path.join(self.cache_root, self.tag + str(self.seed)))
        working_set = tf.working_set()
        log("traffic ready: %d pool files, working set %d keys"
            % (len(pool_files), working_set.size))
        feed = traffic.feed_config(self.cfg)
        self.trainer = build_trainer(self.cfg, self.cfg_mod, feed,
                                     self.seed, params0_dev)
        del params0_dev         # copied; the reference gets the host's
        self.start = tf.table(store_writer(self.trainer.table,
                                           tf.embedx_dim))
        log("store seeded: %d rows" % len(self.trainer.table.store))
        self.record_scan_losses(True)

        def pass_dataset(i: int):
            return traffic.make_dataset(
                feed, [pool_files[j] for j in tf.pass_file_ids(i)],
                working_set)

        def datasets():
            yield traffic.make_dataset(feed, [check_file], working_set,
                                       keep_order=True)
            yield pass_dataset(0)                  # the warm pass
            self.k = 1
            yield pass_dataset(1)                  # the window's first
            while self.another_pass():
                self.k += 1
                yield pass_dataset(self.k)

        try:
            results = run_preloaded_passes(self.trainer, datasets(),
                                           after_pass=self.after_pass)
            jax.block_until_ready(self.trainer.table.slab)
            self.t1 = time.perf_counter()
        finally:
            if self.annot is not None:
                self.annot.__exit__(None, None, None)
                self.trace_cm.__exit__(None, None, None)
        win = results[2:]
        self.passes = len(win)
        self.examples = int(sum(r["instances"] for r in win))
        self.steps = int(sum(r["batches"] for r in win))
        self.window_s = self.t1 - self.t0
        self.setup_s = self.t0 - _T_PROCESS
        log("window closed: %d passes, %d examples, %.2f s"
            % (self.passes, self.examples, self.window_s))

    def another_pass(self) -> bool:
        """Asked just before window pass k trains: pass k+1 runs if it
        would end inside --seconds, by the mean of the passes done (the
        warm pass's time before any is). A traced slice: trace_passes."""
        if self.args.trace:
            return self.k < int(self.mix["trace_passes"])
        done = self.pass_ends
        per_pass = ((done[-1] - self.t0) / len(done) if done
                    else self.warm_s)
        return (self.k + 1) * per_pass <= self.args.seconds

    def store_rows(self, keys):
        import numpy as np
        table = self.trainer.table
        with table.store_lock:
            return np.array(table.store.lookup(keys))

    def read_check_pass(self) -> None:
        """What the check pass left: its steps' losses, adam's first
        moment and the weights' change per leaf, and the check rows as
        end_pass wrote them back."""
        import jax
        import numpy as np
        from harness import traffic
        from paddlebox_tpu.embedding import accessor as acc
        self.record_scan_losses(False)
        prog, layout = self.prog, self.trainer.table.layout
        prog["loss"] = [float(v) for c in self.chunk_losses
                        for v in np.asarray(c).ravel()]
        if len(prog["loss"]) != self.check_steps:
            raise SystemExit(
                "the check pass ran %d steps through scan_steps, not the %d "
                "of one scan chunk" % (len(prog["loss"]), self.check_steps))
        mu = adam_mu(self.trainer.opt_state, self.trainer.params)
        prog["mu"] = {k: float(np.linalg.norm(v)) for k, v in mu.items()}
        p = jax.device_get(self.trainer.params)
        prog["change"] = {k: float(np.linalg.norm(
            (p[k] - self.params0[k]).astype(np.float64))) for k in p}
        at = self.check_rows
        rows = self.store_rows((at + traffic.KEY_BASE).astype(np.uint64))
        xw = layout.embedx_w
        prog["w_delta"] = rows[:, acc.EMBED_W] - self.start["w"][at]
        prog["x_delta"] = (rows[:, xw:xw + layout.embedx_dim]
                           - self.start["x"][at])
        prog["w_g2"] = rows[:, layout.embed_state].copy()
        prog["x_g2"] = rows[:, layout.embedx_state].copy()
        prog["show"] = rows[:, acc.SHOW].copy()
        prog["click"] = rows[:, acc.CLICK].copy()

    def after_pass(self, i: int, stats: dict) -> None:
        import jax
        if i == 0:                       # the check pass
            self.read_check_pass()
            self.t_warm0 = time.perf_counter()
            log("check pass done: %d steps, first full build" % stats["batches"])
        elif i == 1:                     # the warm pass is done: window
            jax.block_until_ready(self.trainer.table.slab)
            self.warm_s = time.perf_counter() - self.t_warm0
            self.compiles0 = instrumented_compiles()
            self.backend0 = self.watch.n
            self.counters0 = counters_now(self.spec["counters"])
            if self.args.trace:
                from paddlebox_tpu.utils.profiler import trace
                self.trace_dir = os.path.join(
                    self.cache_root, "trace-" + self.tag + str(self.seed))
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                self.trace_cm = trace(self.trace_dir)
                self.trace_cm.__enter__()
                self.annot = jax.profiler.TraceAnnotation("bench_window")
                self.annot.__enter__()
            self.t0 = time.perf_counter()
            log("set-up done (%.1f s); window opens"
                % (self.t0 - _T_PROCESS))
        else:
            self.pass_ends.append(time.perf_counter())

    # ------------------------------------------- after the window closed
    def read_program(self) -> None:
        """Counters, spans, the device's peak, and a sample of the host
        store's rows drawn from the seed (touched rows, and rows no batch
        drew) against what the passes must have left there; then the
        program's state is freed."""
        import gc
        import numpy as np
        from harness import traffic
        from paddlebox_tpu.embedding import accessor as acc
        from paddlebox_tpu.obs.tracer import get_tracer
        tf, passes = self.tf, self.passes
        compiles1 = instrumented_compiles()
        self.counters1 = counters_now(self.spec["counters"])
        self.new_compiles = {n: c - self.compiles0.get(n, 0)
                             for n, c in compiles1.items()
                             if c > self.compiles0.get(n, 0)}
        self.backend_compiles = self.watch.n - self.backend0
        self.device = device_info()
        self.spans = [(n, a, b) for n, _tid, _tn, a, b, _tr
                      in get_tracer().all_spans()
                      if a >= self.t0 and b <= self.t1 + 1e-3]
        file_mult = np.zeros(tf.pool_files)
        for i in range(passes + 1):                # warm pass + window
            np.add.at(file_mult, tf.pass_file_ids(i), 1)
        want_show, want_click, touched = tf.expected_counts(file_mult,
                                                            self.start)
        rng = np.random.default_rng([self.seed, 7])
        sample = np.unique(np.concatenate([
            rng.choice(touched, min(SAMPLE_ROWS, touched.size),
                       replace=False),
            rng.integers(0, tf.occupied, SAMPLE_ROWS // 4)]))
        rows = self.store_rows((sample + traffic.KEY_BASE).astype(np.uint64))
        keys_fed = ((passes + 1) * tf.examples_per_pass
                    + self.check_steps * tf.batch) * tf.num_slots
        self.mismatch = {
            "examples": abs(self.examples - passes * tf.examples_per_pass),
            "keys_parsed": abs(self.counters1["ingest_keys_parsed"]
                               - keys_fed),
            "store_rows": abs(len(self.trainer.table.store) - tf.occupied),
            "show_rows": int((rows[:, acc.SHOW] != want_show[sample]).sum()),
            "click_rows": int(
                (rows[:, acc.CLICK] != want_click[sample]).sum()),
        }
        self.trainer.close()
        self.trainer.table.invalidate_residency()
        del self.trainer, rows
        gc.collect()

    def follow(self, **kw) -> dict:
        from harness import reference
        ex = self.tf.check
        return reference.follow(self.cfg, self.cfg_mod, self.params0,
                                ex.rows, ex.labels, ex.dense,
                                self.check_steps, self.start, **kw)

    def judge(self, got: dict, ref: dict, exact: dict):
        """(compared, limits, correct) of what stands in the program's
        place (the program, the control or a planted fault): every number
        beside its limit, the configuration's gaps and the exact counts."""
        import numpy as np
        from harness import reference
        compared = reference.compare(got, ref)
        compared["count_mismatch"] = int(
            sum(exact.values())
            + (got["show"] != ref["show"]).sum()
            + (got["click"] != ref["click"]).sum())
        compared["compiles_in_window"] = int(sum(self.new_compiles.values()))
        limits = {**{k: float(v) for k, v in self.cfg["limits"].items()
                     if k in reference.GAPS}, **EXACT}
        correct = all(np.isfinite(compared[k]) and compared[k] <= limits[k]
                      for k in limits)
        return compared, limits, bool(correct)

    def controls(self, ref: dict) -> dict:
        """The control (the reference with matmul operands rounded to
        float8, the step below the stated bfloat16) and each planted fault,
        put in the program's place and judged as the program is."""
        from harness import reference
        stand_ins = {
            "control_fp8": {"mm": reference.mm_control},
            "fault_half_batch": {"keep_half": True},
            "fault_state_unchanged": {"unchanged": True}}
        out = {}
        for name, kw in stand_ins.items():
            compared, _limits, correct = self.judge(
                self.follow(**kw), ref, {})
            out[name] = dict(strip(compared), correct=correct)
        return out

    def compare(self) -> None:
        """The reference follows the check pass; each number compared
        gets its limit beside it."""
        from harness import reference
        t_ref0 = time.perf_counter()
        ref = self.follow()
        log("reference followed %d steps (%.1f s)"
            % (self.check_steps, time.perf_counter() - t_ref0))
        self.compared, self.limits, self.correct = self.judge(
            self.prog, ref, self.mismatch)
        log("check pass losses: program %s reference %s"
            % (["%.6f" % v for v in self.prog["loss"]],
               ["%.6f" % v for v in ref["loss"]]))
        # every gap the widest is taken of: which step, which leaf
        gaps = reference.leaf_gaps(self.prog, ref)
        self.leaf_gaps = {
            "loss": [float("%.3g" % g) for g in gaps["loss"]],
            **{k: {leaf: float("%.3g" % g) for leaf, g in gaps[k].items()}
               for k in ("grad", "change")}}
        log("check pass gaps: %s" % json.dumps(self.leaf_gaps))
        self.ref_s = time.perf_counter() - t_ref0
        self.control_verdicts = (self.controls(ref) if self.args.control
                                 else None)

    def end_to_end(self) -> dict:
        values = {
            "examples_per_s": self.examples / self.window_s,
            "hbm_bytes_per_row": (self.device["memory_peak_bytes"]
                                  / float(self.cfg["pass_capacity"])),
            "setup_s": self.setup_s}
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in self.spec["end_to_end"]}

    def per_layer(self):
        """(metrics, breakdown) from the traced slice: every metric file's
        reducer over the spans, counters and the device trace."""
        import numpy as np
        from harness import reducers
        from harness import trace_reduce as tr
        tf, ex = self.tf, self.tf.check
        trace = tr.load(tr.find_xplane(self.trace_dir))
        if not trace["devices"] and not self.args.rehearse:
            raise SystemExit("the trace holds no device plane")
        marks = [(s, e) for n, s, e in trace["host"] if n == "bench_window"]
        window = marks[0] if marks else tr.window_of(trace)
        # spans the program records after the fact (pass_begin, pass_end)
        # open no TraceAnnotation: put the ring's spans on the trace's
        # clock, whose bench_window mark opened at t0
        shift = window[0] - self.t0
        trace["host"] += [(n, a + shift, b + shift)
                          for n, a, b in self.spans]
        log_spans(self.spans, self.passes)
        uniq = np.mean([np.unique(ex.rows[i * tf.batch:(i + 1) * tf.batch]
                                  ).size for i in range(self.check_steps)])
        ctx = {"spans": self.spans, "passes": self.passes,
               "steps": self.steps, "examples": self.examples,
               "window_s": self.window_s, "trace": trace,
               "trace_window": window, "chips": int(self.cell["chips"]),
               "cfg": self.cfg, "cfg_mod": self.cfg_mod,
               "counters": {c: self.counters1[c] - self.counters0[c]
                            for c in self.counters1},
               "scope_maps": scope_maps(),
               "unique_rows_per_example": float(uniq / tf.batch),
               "peaks": peaks_for(self.device["kind"], self.args.rehearse)}
        breakdown = None
        if not trace["devices"]:        # a rehearsal off the chip
            ctx["trace"] = None
        else:
            log_trace(trace, window, tr)
            b = tr.busy(trace, window)
            self.device["busy_s"] = b["busy_s"]
            self.device["window_s"] = b["window_s"]
            breakdown = {"device_ops": tr.top_ops(trace, 10, window),
                         "idle_gaps": tr.idle_gaps(trace, HOST_SPANS, 10,
                                                   window)}
        metrics = {}
        for name, mspec in self.spec["layer"].items():
            value = reducers.reduce_metric(mspec, ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": mspec["unit"]}
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return metrics, breakdown

    def result(self) -> dict:
        metrics, breakdown = (self.per_layer() if self.args.trace
                              else (self.end_to_end(), None))
        device, compared, limits = self.device, self.compared, self.limits
        info = {"passes": self.passes, "steps": self.steps,
                "new_compiles": self.new_compiles,
                "mismatch": self.mismatch,
                "not_held": {k: v for k, v in compared.items()
                             if k not in limits},
                "leaf_gaps": self.leaf_gaps,
                "rehearse": bool(self.args.rehearse)}
        if self.control_verdicts is not None:
            info["controls"] = self.control_verdicts
        if self.args.rehearse:
            # a rehearsal proves the walk, not the chip: no rate, no
            # device metric, no time leaves it
            metrics, breakdown = {}, None
            device = {k: device[k] for k in ("platform", "kind", "count")}
        else:
            info.update({
                "window_s": self.window_s, "setup_s": self.setup_s,
                "reference_s": self.ref_s, "warm_pass_s": self.warm_s,
                "backend_compiles_in_window": self.backend_compiles})
        attempted = self.passes * self.tf.examples_per_pass
        out = {"correct": bool(self.correct), "attempted": attempted,
               "failed": attempted - self.examples, "metrics": metrics,
               "device": device}
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["info"] = info
        out["compared"] = {k: {"value": compared[k], "limit": limits[k]}
                           for k in limits}
        return out


def print_compared(compared: dict, limits: dict, who: str = "") -> None:
    for k in limits:
        print("compared %s%-20s %.6g  limit %.6g"
              % (who, k, compared[k], limits[k]), file=sys.stderr)
    sys.stderr.flush()


def run(args) -> int:
    r = Run(args)
    r.drive()
    r.read_program()
    r.compare()
    out = r.result()
    print_compared(r.compared, r.limits)
    print(json.dumps(out), flush=True)
    return 0


def run_controls_only(args) -> int:
    """--control 2: for each of --seeds, the reference of the check pass
    and the control and faults judged against it, at the cell's own size;
    no program, no window, one JSON line a seed."""
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = Run(args, seed)
        r.new_compiles = {}
        r.make_traffic()
        r.start = r.tf.table()
        verdicts = r.controls(r.follow())
        log("CONTROLS seed %d %s" % (seed, json.dumps(verdicts)))
        print(json.dumps({"seed": seed, "controls": verdicts}), flush=True)
    return 0


def log_trace(trace: dict, window, tr) -> None:
    """What the trace holds, for a look by hand (standard error)."""
    log("trace: planes %s; step programs %s" % (
        {p: {ln: len(e) for ln, e in ls.items()}
         for p, ls in trace["devices"].items()},
        tr.busy_in_programs(trace, "scan_steps|_step_impl|train_step",
                            window)))
    mods = {}
    for ls in trace["devices"].values():
        for n, s0, e0 in ls.get(tr.MODULES_LINE, []):
            mods[n] = mods.get(n, 0.0) + (e0 - s0)
    log("trace: module seconds %s" % sorted(
        mods.items(), key=lambda kv: -kv[1])[:12])


def log_spans(spans, passes: int) -> None:
    """Every span of the slice, ms a pass, for a look by hand (standard
    error): a span that no metric file names is read here."""
    totals = {}
    for n, a, b in spans:
        totals[n] = totals.get(n, 0.0) + (b - a)
    log("spans, ms a pass: %s" % json.dumps(
        {n: round(1000.0 * t / max(passes, 1), 3)
         for n, t in sorted(totals.items())}))


def strip(compared: dict) -> dict:
    return {k: (v if isinstance(v, str) else float("%.6g" % v))
            for k, v in compared.items()}


def peaks_for(kind: str, rehearse: bool) -> dict:
    table = load_json(os.path.join(HERE, "harness", "peaks.json"))
    if kind not in table:
        if rehearse:                 # the numbers are thrown away anyway
            return next(iter(table.values()))
        raise SystemExit("no peaks for device kind %r in peaks.json" % kind)
    return table[kind]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, default=0, choices=(0, 1, 2))
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "paddlebox_tpu")):
        print("no program to measure beside the benchmark",
              file=sys.stderr)
        return 3
    return run_controls_only(args) if args.control == 2 else run(args)


if __name__ == "__main__":
    sys.exit(main())
