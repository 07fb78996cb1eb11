"""Micro-batch pipeline parallelism over a `stage` mesh axis.

TPU-native re-design of the reference's pipeline training (BoxPSOptimizer
cut_list program splitting, python/paddle/fluid/optimizer.py:7496-7575 →
SectionWorker micro-batch section loop, framework/section_worker.cc,
device_worker.h:639; also the actor-style FleetExecutor pipeline,
distributed/fleet_executor/). Where the reference moves micro-batch scopes
between section workers over queues, here the WHOLE schedule is one SPMD
program: every device holds one stage's params, activations circulate with
`lax.ppermute` on the ICI ring, and `lax.scan` runs the M + S - 1 GPipe
ticks. Backward needs no hand-written schedule — jax.grad transposes the
scan+ppermute into the reverse pipeline automatically.

Stages must be shape-homogeneous (same activation width in/out) so stage
params stack on the leading axis; in/out projections live in replicated
pre/post layers of the wrapping model.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.obs import beat as obs_beat
from paddlebox_tpu.obs import make_step_reporter
from paddlebox_tpu.obs.tracer import step_trace_id, trace_ctx
from paddlebox_tpu.obs import span as obs_span

STAGE_AXIS = "stage"


def init_stage_params(rng: jax.Array, n_stages: int, d_model: int,
                      layers_per_stage: int = 1,
                      scale: float = 0.1) -> Dict[str, jax.Array]:
    """[S, L, d, d] MLP blocks — one row of L dense layers per stage."""
    w = scale * jax.random.normal(
        rng, (n_stages, layers_per_stage, d_model, d_model), jnp.float32)
    b = jnp.zeros((n_stages, layers_per_stage, d_model), jnp.float32)
    return {"w": w, "b": b}


def mlp_stage_apply(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """One stage's block: L × (dense + relu). params: [L, d, d] / [L, d]."""
    L = params["w"].shape[0]
    for i in range(L):
        x = jax.nn.relu(x @ params["w"][i] + params["b"][i])
    return x


def _spmd_pipeline(stage_apply: Callable, n_stages: int, n_micro: int,
                   axis: str, ingest: Optional[Callable] = None,
                   emit: Optional[Callable] = None):
    """Per-device GPipe schedule — the ONE implementation of the
    clamped-ingest / masked-emit / ppermute-ring scan (keep fixes here;
    both the toy MLP runner and the CTR program split use it).

    inputs: a pytree with leading micro axis [M, ...] (default: the array
    of stage-0 activations). ingest(stage_params, inputs, tm) -> [mb, d]
    builds stage 0's injection for micro tm (the CTR embedding section);
    emit(stage_params, y) maps the last stage's block output to the
    collected per-micro output (default identity; the CTR head).
    Returns replicated [M, *emit_shape]."""

    ingest_fn = ingest or (lambda p, inp, tm: inp[tm])
    emit_fn = emit or (lambda p, y: y)

    def run(stage_params, inputs):
        S, M = n_stages, n_micro
        idx = jax.lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == S - 1
        x_sh = jax.eval_shape(ingest_fn, stage_params, inputs, 0)
        state0 = jnp.zeros(x_sh.shape, x_sh.dtype)
        y_sh = jax.eval_shape(stage_apply, stage_params, state0)
        e_sh = jax.eval_shape(emit_fn, stage_params,
                              jnp.zeros(y_sh.shape, y_sh.dtype))
        out0 = jnp.zeros((M,) + e_sh.shape, e_sh.dtype)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, out_buf = carry
            # stage 0 ingests micro-batch t (clamped; extra ticks are
            # pipeline drain and their stage-0 output is never collected)
            x_in = ingest_fn(stage_params, inputs, jnp.minimum(t, M - 1))
            state = jnp.where(is_first, x_in, state)
            y = stage_apply(stage_params, state)
            # last stage emits micro-batch t-(S-1) once the pipe is full
            widx = jnp.maximum(t - (S - 1), 0)
            emit_now = (t >= S - 1) & is_last
            out_buf = out_buf.at[widx].set(
                jnp.where(emit_now, emit_fn(stage_params, y),
                          out_buf[widx]))
            state = jax.lax.ppermute(y, axis, perm)
            return (state, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(M + S - 1))
        # replicate the last stage's outputs to every stage (transposes to
        # routing output-grads back to the last stage in backward)
        out_buf = jax.lax.psum(
            jnp.where(is_last, out_buf, jnp.zeros_like(out_buf)), axis)
        return out_buf

    return run


@dataclasses.dataclass
class PipelineConfig:
    n_stages: int = 4
    n_micro: int = 8            # micro-batches per step (= cut_list sections)
    d_model: int = 64
    layers_per_stage: int = 2
    lr: float = 1e-3


class GPipeRunner:
    """Holds stage-sharded params and the jitted pipelined fwd/train step.

    Params live as [S, ...] arrays sharded over the stage axis — each
    device materialises only its own stage (ZeRO-like by construction,
    matching how each SectionWorker owns only its section's program).
    """

    def __init__(self, cfg: PipelineConfig, mesh: Optional[Mesh] = None,
                 stage_apply: Callable = mlp_stage_apply,
                 init_fn: Optional[Callable] = None, seed: int = 0):
        self.cfg = cfg
        if mesh is None:
            devs = np.array(jax.devices()[:cfg.n_stages])
            mesh = Mesh(devs, (STAGE_AXIS,))
        if mesh.devices.size != cfg.n_stages:
            raise ValueError("mesh size %d != n_stages %d"
                             % (mesh.devices.size, cfg.n_stages))
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.stage_apply = stage_apply
        init = init_fn or (lambda rng: init_stage_params(
            rng, cfg.n_stages, cfg.d_model, cfg.layers_per_stage))
        sh = NamedSharding(mesh, P(self.axis))
        self.params = jax.tree.map(
            lambda x: jax.device_put(x, sh), init(jax.random.PRNGKey(seed)))
        self.opt = optax.adam(cfg.lr)
        # optimizer state shards with the params it tracks (scalars like the
        # adam count stay replicated)
        host_opt = self.opt.init(jax.tree.map(np.asarray, self.params))
        self.opt_state = jax.tree.map(
            lambda x: (jax.device_put(jnp.asarray(x), sh)
                       if getattr(x, "ndim", 0) else jnp.asarray(x)),
            host_opt)
        self._fwd = self._build_fwd(stage_apply)
        self._step = self._build_step(stage_apply)

    # ------------------------------------------------------------------ fwd
    def _build_fwd(self, stage_apply):
        cfg = self.cfg
        pipe = _spmd_pipeline(stage_apply, cfg.n_stages, cfg.n_micro,
                              self.axis)

        def fwd(params, micro_inputs):
            local = jax.tree.map(lambda x: x[0], params)  # [1,...] → [...]
            return pipe(local, micro_inputs)

        from paddlebox_tpu.obs.device import instrument_jit
        return instrument_jit(jax.shard_map(
            fwd, mesh=self.mesh, in_specs=(P(self.axis), P()),
            out_specs=P(), check_vma=False), "pipe_fwd")

    def forward(self, x: np.ndarray) -> jax.Array:
        """x: [M*mb, d] → pipelined output [M*mb, d]."""
        cfg = self.cfg
        m = x.reshape(cfg.n_micro, -1, cfg.d_model)
        out = self._fwd(self.params, jnp.asarray(m))
        return out.reshape(x.shape[0], cfg.d_model)

    # ----------------------------------------------------------------- train
    def _build_step(self, stage_apply):
        cfg = self.cfg
        pipe = _spmd_pipeline(stage_apply, cfg.n_stages, cfg.n_micro,
                              self.axis)
        opt = self.opt

        def step(params, opt_state, micro_inputs, micro_targets):
            local = jax.tree.map(lambda x: x[0], params)
            local_opt = jax.tree.map(
                lambda x: x[0] if getattr(x, "ndim", 0) else x, opt_state)

            def loss_fn(p):
                out = pipe(p, micro_inputs)
                return jnp.mean(jnp.square(out - micro_targets))

            loss, grads = jax.value_and_grad(loss_fn)(local)
            # each device owns its stage: update with LOCAL grads only —
            # there is nothing to allreduce across stages
            updates, local_opt = opt.update(grads, local_opt, local)
            local = optax.apply_updates(local, updates)
            params = jax.tree.map(lambda x: x[None], local)
            opt_state = jax.tree.map(
                lambda x: x[None] if getattr(x, "ndim", 0) else x, local_opt)
            return params, opt_state, loss

        spec_sh = P(self.axis)
        opt_spec = jax.tree.map(
            lambda x: spec_sh if getattr(x, "ndim", 0) else P(),
            self.opt_state,
            is_leaf=lambda x: hasattr(x, "ndim") or np.isscalar(x))
        from paddlebox_tpu.obs.device import instrument_jit
        return instrument_jit(jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(spec_sh, opt_spec, P(), P()),
            out_specs=(spec_sh, opt_spec, P()), check_vma=False),
            "pipe_step", donate_argnums=(0, 1))

    def train_step(self, x: np.ndarray, y: np.ndarray) -> float:
        cfg = self.cfg
        mi = jnp.asarray(x.reshape(cfg.n_micro, -1, cfg.d_model))
        mt = jnp.asarray(y.reshape(cfg.n_micro, -1, cfg.d_model))
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, mi, mt)
        return float(loss)

    # ------------------------------------------------------------- reference
    def sequential_forward(self, x: np.ndarray) -> jax.Array:
        """Unpipelined oracle: run this runner's stages in order on one
        device."""
        params_host = jax.tree.map(np.asarray, self.params)
        out = jnp.asarray(x)
        for s in range(self.cfg.n_stages):
            p = jax.tree.map(lambda a: jnp.asarray(a[s]), params_host)
            out = self.stage_apply(p, out)
        return out


def _grouped_train_pass(runner, dataset, begin_pass, end_pass,
                        allgather=None, n_groups_cap=None
                        ) -> Dict[str, float]:
    """The ONE pass-cadence driver both CTR pipeline runners share: feed
    pass → slab build (begin_pass hook) → full dp×n_micro-group steps →
    EndPass write-back (end_pass hook). Trailing batches short of a full
    micro-batch group are dropped (the reference's section pipeline also
    only runs full pipelines). allgather: cross-process feed-key union;
    n_groups_cap(n) -> n': cross-process step-group equalization (every
    process must dispatch the same number of collective steps)."""
    runner.table.begin_feed_pass()
    dataset.load_into_memory(add_keys_fn=runner.table.add_keys)
    if allgather is not None:
        runner.table.end_feed_pass(allgather=allgather)
    else:
        runner.table.end_feed_pass()
    begin_pass()
    batches = dataset.split_batches(num_workers=1)[0]
    M = runner.batches_per_step
    n_groups = len(batches) // M
    if n_groups_cap is not None:
        n_groups = n_groups_cap(n_groups)
    losses = []
    # a group is a slice of the split's plan and packs nothing here: the
    # runners walk a group several times, so it is listed where it is used
    groups = [batches[lo:lo + M] for lo in range(0, n_groups * M, M)]
    from paddlebox_tpu.config import flags
    depth = max(0, int(flags.get_flag("stream_depth")))
    if depth and len(groups) > 1:
        # bounded prefetch stager (round-5 verdict item 7): group i+1's
        # device_batch (routing + dedup + device_put) runs on a producer
        # thread while group i's step trains — the same overlap the
        # sharded trainer's shard_batches stream has. Multi-process is
        # safe: ONE stager thread per process stages groups in the same
        # deterministic order, so any cross-process staging collectives
        # stay lockstep.
        import queue as _q
        import threading as _t
        out: "_q.Queue" = _q.Queue(maxsize=depth)
        stop = _t.Event()

        def produce():
            try:
                for g in groups:
                    with obs_span("pipe_stage"):
                        g = list(g)
                        staged = runner.device_batch(g)
                    while not stop.is_set():
                        try:
                            out.put((g, staged), timeout=0.2)
                            break
                        except _q.Full:
                            continue
                    else:
                        return
            except BaseException as e:
                out.put(e)

        th = _t.Thread(target=produce, daemon=True, name="pipe-prefetch")
        th.start()
        try:
            for _ in groups:
                item = out.get()
                if isinstance(item, BaseException):
                    raise item
                g, staged = item
                # trace id off the PERSISTENT step counter (+1: noted
                # after the step) — a per-pass counter would repeat ids
                # across passes and stitch unrelated steps into one flow
                with trace_ctx(step_trace_id(
                        getattr(runner, "_obs_rank", 0),
                        getattr(runner, "_step_count", 0) + 1)), \
                        obs_span("pipe_step"):
                    losses.append(runner.train_step_staged(staged, g))
                obs_beat("pipeline_step")
                _pipe_note_step(runner, len(losses))
        finally:
            stop.set()
            deadline = time.monotonic() + 120.0
            while th.is_alive():
                # keep draining so a producer blocked in out.put unblocks
                try:
                    while True:
                        out.get_nowait()
                except _q.Empty:
                    pass
                th.join(timeout=1.0)
                if th.is_alive() and time.monotonic() > deadline:
                    # a zombie stager would race the next pass's route
                    # index teardown and interleave fleet collectives —
                    # never return control with it alive unless an
                    # exception is already propagating (don't mask it)
                    import sys as _sys
                    if _sys.exc_info()[1] is not None:
                        import logging
                        logging.getLogger("paddlebox_tpu").error(
                            "pipeline prefetch stager failed to stop "
                            "within 120s while unwinding %r",
                            _sys.exc_info()[1])
                        break
                    raise RuntimeError(
                        "pipeline prefetch stager failed to stop within "
                        "120s — it may still hold the route index / "
                        "fleet store; not returning with a live stager")
    else:
        for g in groups:
            with trace_ctx(step_trace_id(
                    getattr(runner, "_obs_rank", 0),
                    getattr(runner, "_step_count", 0) + 1)), \
                    obs_span("pipe_step"):
                losses.append(runner.train_step(list(g)))
            obs_beat("pipeline_step")
            _pipe_note_step(runner, len(losses))
    end_pass()
    reporter = getattr(runner, "reporter", None)
    if reporter is not None:
        extra = {"event": "pass_end",
                 "loss": round(float(np.mean(losses)), 6)
                 if losses else 0.0}
        from paddlebox_tpu.metrics.quality import attach_pass_extras
        attach_pass_extras(extra, getattr(runner, "quality", None),
                           ship_state=getattr(runner, "multiprocess",
                                              False))
        reporter.maybe_report(
            getattr(runner, "_step_count", len(losses)), force=True,
            extra=extra)
    return {"loss": float(np.mean(losses)) if losses else 0.0,
            "steps": len(losses),
            "dropped_batches": len(batches) - n_groups * M}


def _pipe_note_step(runner, step_in_pass: int) -> None:
    """Per-step telemetry hook for the shared pipeline drivers: feeds the
    runner's StepReporter (when it has one) with monotone step counts."""
    reporter = getattr(runner, "reporter", None)
    if reporter is None:
        return
    runner._step_count = getattr(runner, "_step_count", 0) + 1
    reporter.note_examples(getattr(runner, "_examples_per_step", 0))
    reporter.maybe_report(runner._step_count)


def _feed_pipeline_metrics(runner, preds, packed_batches) -> None:
    """Stream one step group's predictions into the runner's registry
    (host path — the Metric::add_data role) and its DumpField writer.
    preds: [dp·M, mb] global (dp-sharded on a 2D mesh); multi-process
    feeds only this process's addressable rows, which align with its own
    packed_batches; the cross-process reduction stays in get_metric_msg's
    allreduce hook."""
    dump = getattr(runner, "dump_writer", None)
    quality = getattr(runner, "quality", None)
    if (not runner.metrics.metric_names() and dump is None
            and quality is None):
        return
    if getattr(runner, "multiprocess", False):
        # preds is dp-sharded but STAGE-REPLICATED: addressable_shards
        # yields one entry per local device, i.e. n_stages copies of each
        # dp row — keep exactly one shard per distinct index
        by_start = {}
        for sh in preds.addressable_shards:
            pos = sh.index[0] if sh.index else slice(0, None)
            start = (pos.start or 0) if isinstance(pos, slice) else int(pos)
            by_start.setdefault(start, np.asarray(sh.data))
        arr = np.concatenate([by_start[s] for s in sorted(by_start)])
    else:
        arr = np.asarray(preds)
    names = getattr(runner, "task_names", ("ctr",))
    if dump is not None:
        # one DumpField line per real instance (this process's rows)
        from paddlebox_tpu.train.dump import build_dump_tensors
        rows = arr.reshape((len(packed_batches), -1) + arr.shape[2:])
        for j, b in enumerate(packed_batches):
            per_task = ({t: rows[j][..., ti]
                         for ti, t in enumerate(names)}
                        if len(names) > 1 else {names[0]: rows[j]})
            tens = build_dump_tensors(runner.dump_fields, b.labels,
                                      per_task, names[0])
            if tens:
                dump.dump_batch(tens, ins_ids=b.ins_ids, mask=b.ins_valid)
    if not runner.metrics.metric_names() and quality is None:
        return
    labels = np.concatenate([b.labels for b in packed_batches])
    mask = np.concatenate([b.ins_valid for b in packed_batches])
    tensors = {"label": labels, "mask": mask}
    if len(names) > 1:
        # per-task prediction/label columns (metrics.h MultiTask naming)
        for ti, t in enumerate(names):
            tensors["pred_" + t] = arr[..., ti].reshape(-1)
            tensors["label_" + t] = np.concatenate(
                [_task_label_of(b, t) for b in packed_batches])
        tensors["pred"] = tensors["pred_" + names[0]]
    else:
        tensors["pred"] = arr.reshape(-1)
    runner.metrics.add_batch(tensors)
    if quality is not None:
        quality.add_batch(tensors)
        # per-slot ctr: same feed the box trainers give it (a pipeline
        # job's /metrics must not silently lack the pbtpu_slot_* series)
        num_slots = getattr(runner, "num_slots", 0)
        if num_slots:
            preds_by_batch = tensors["pred"].reshape(
                len(packed_batches), -1)
            for j, b in enumerate(packed_batches):
                quality.add_slot_batch(
                    preds_by_batch[j], b.labels, b.slots, b.segments,
                    b.valid, num_slots)
        from paddlebox_tpu.metrics import drift as _drift
        _drift.observe_preds(tensors["pred"], mask=mask)


def _pipeline_predict(runner, dataset, begin_pass, end_pass, slab_of):
    """Shared test-mode inference cadence for the pipeline runners:
    feed pass (no creation) → eval steps over full groups → (preds,
    labels) of the covered valid instances. Single-process (the eval
    output must be fully addressable)."""
    if getattr(runner, "multiprocess", False):
        raise TypeError("predict_batches is single-process; multi-process "
                        "jobs evaluate per-rank training preds via the "
                        "metric registry")
    runner.table.set_test_mode(True)
    opened = False
    try:
        runner.table.begin_feed_pass()
        if len(dataset) == 0:
            dataset.load_into_memory()
        runner.table.add_keys(dataset.all_keys())
        runner.table.end_feed_pass()
        begin_pass()
        opened = True
        batches = dataset.split_batches(num_workers=1)[0]
        M = runner.batches_per_step
        preds_all, labels_all = [], []
        for lo in range(0, len(batches) - M + 1, M):
            group = list(batches[lo:lo + M])
            batch = runner.device_batch(group)
            preds = np.asarray(runner._eval(runner.params, slab_of(),
                                            batch))
            if getattr(runner, "multi_task", False):
                preds = preds[..., 0]   # main task (task_names[0])
            preds = preds.reshape(-1)
            labels = np.concatenate([b.labels for b in group])
            mask = np.concatenate([b.ins_valid for b in group])
            preds_all.append(preds[mask])
            labels_all.append(labels[mask])
    finally:
        # ALWAYS close the pass — a mid-eval error must not wedge every
        # later train_pass with "pass already open"
        if opened:
            end_pass()
        runner.table.set_test_mode(False)
    if not preds_all:
        return np.empty(0, np.float32), np.empty(0, np.int32)
    return np.concatenate(preds_all), np.concatenate(labels_all)


def _make_dump_writer(dump_fields, dump_fields_path, dump_thread_num):
    """DumpField writers for the pipeline runners (boxps_worker.cc
    DumpField): rank-tagged so multi-process dumps stay distinguishable;
    (fields, writer) — writer None unless both fields and path are set."""
    fields = tuple(dump_fields or ())
    if not (fields and dump_fields_path):
        return fields, None
    from paddlebox_tpu.train.dump import DumpWriter
    return fields, DumpWriter(dump_fields_path, dump_thread_num,
                              rank=jax.process_index())


def _task_label_of(b, t):
    """The ONE per-task label fallback rule: tasks without a label slot
    in the feed train/stream on the primary click label."""
    return (b.task_labels or {}).get(t, b.labels)


def ctr_pipeline_loss(logits, labels, ins_valid, task_labels, task_names):
    """The ONE loss both pipeline runners share. Single task: masked-mean
    bce on [M, mb] logits. Multi-task: per-task bce over the [M, mb, T]
    head summed (the trainers' _multi_task_loss 'sum' mode; tasks absent
    from the feed fall back to the click label at batch build)."""
    denom = jnp.maximum(ins_valid.sum(), 1.0)
    if len(task_names) == 1:
        bce = optax.sigmoid_binary_cross_entropy(
            logits, labels.astype(jnp.float32))
        return (jnp.where(ins_valid, bce, 0.0).sum() / denom,
                jax.nn.sigmoid(logits))
    loss = 0.0
    for ti, t in enumerate(task_names):
        lab = task_labels[t].astype(jnp.float32)
        bce = optax.sigmoid_binary_cross_entropy(logits[..., ti], lab)
        loss = loss + jnp.where(ins_valid, bce, 0.0).sum() / denom
    return loss, jax.nn.sigmoid(logits)


def ctr_pipeline_sections(mb: int, num_slots: int, use_cvm: bool, E: int,
                          use_data_norm: bool = False,
                          dn_slot_dim: int = 0):
    """The ONE definition of the CTR pipeline's program sections —
    (blocks, embed_section, head, proj_input) closures shared by the
    replicated and sharded runners (their parity tests rely on
    byte-identical math). embed_section consumes inputs = (emb_all,
    exp_all, segments, key_valid); exp_all is None when E == 0.
    proj_input assembles stage 0's pre-projection features for micro tm
    — embed_section normalizes it (data_norm over stop_gradient'ed
    summary leaves dn_size/dn_sum/dn_sqsum when use_data_norm) and the
    runners reuse it for the running-sums summary update (XLA CSEs the
    duplicate assembly, the dn_update_params pattern)."""
    from paddlebox_tpu.ops.data_norm import DataNormState, data_norm
    from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm, seqpool_sum

    def blocks(p, state):
        y = state
        for i in range(p["blk_w"].shape[0]):
            y = jax.nn.relu(y @ p["blk_w"][i] + p["blk_b"][i])
        return y

    def proj_input_all(emb_all, exp_all, segments, key_valid):
        """ALL M micros' pre-projection features [M, mb, in_dim],
        assembled ONCE outside the GPipe scan — the in-scan ingest would
        otherwise re-run seqpool+concat on every tick including the S-1
        drain ticks whose stage-0 output is discarded. Gradients flow to
        emb/exp through this trace; the dn summary update reuses the
        same tensor."""
        M = emb_all.shape[0]
        xs = []
        for t in range(M):
            pooled = fused_seqpool_cvm(
                emb_all[t], segments[t], key_valid[t], mb, num_slots,
                use_cvm, sorted_segments=True)
            x = pooled.reshape(mb, -1)
            if E:
                # expand block: plain per-slot sum pool (the
                # pull_box_extended_sparse consumer pattern)
                pexp = seqpool_sum(exp_all[t], segments[t], key_valid[t],
                                   mb, num_slots)
                x = jnp.concatenate([x, pexp.reshape(mb, -1)], axis=-1)
            xs.append(x)
        return jnp.stack(xs)

    def embed_section(p, x_all, tm):
        x = x_all[tm]
        if use_data_norm:
            st = DataNormState(
                jax.lax.stop_gradient(p["dn_size"]),
                jax.lax.stop_gradient(p["dn_sum"]),
                jax.lax.stop_gradient(p["dn_sqsum"]))
            x = data_norm(x, st, slot_dim=dn_slot_dim)
        return jax.nn.relu(x @ p["proj_w"] + p["proj_b"])

    def head(p, y):
        return y @ p["head_w"] + p["head_b"]

    return blocks, embed_section, head, proj_input_all


def dn_summary_apply(local, x_all, dn_decay: float, dn_slot_dim: int,
                     dp_axis):
    """The ONE running-sums summary update both runners share: fold every
    micro's pre-projection features into the dn leaves (the optimizer's
    zero-grad update on them was a no-op); dp rows pmean the result —
    ratio-preserving, the sharded trainer's documented dn rule."""
    from paddlebox_tpu.ops.data_norm import (DataNormState,
                                             data_norm_summary_update)
    st = data_norm_summary_update(
        DataNormState(local["dn_size"], local["dn_sum"],
                      local["dn_sqsum"]),
        x_all.reshape(-1, x_all.shape[-1]).astype(jnp.float32),
        decay=dn_decay, slot_dim=dn_slot_dim)
    if dp_axis is not None:
        st = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), st)
    return dict(local, dn_size=st.batch_size, dn_sum=st.batch_sum,
                dn_sqsum=st.batch_square_sum)


def ctr_stage_host_params(seed: int, n_stages: int, layers_per_stage: int,
                          pooled_dim: int, d_model: int,
                          scale: float = 0.1, n_tasks: int = 1,
                          use_data_norm: bool = False
                          ) -> Dict[str, np.ndarray]:
    """The ONE init of the CTR pipeline's stage-stacked params — shared by
    the replicated-slab and sharded-slab runners so same-seed runs are
    bit-identical (the parity tests rely on it). n_tasks > 1 grows the
    head to [d_model, T] (multi-task logits per micro-batch); n_tasks=1
    keeps the historical scalar-head shapes."""
    S, L = n_stages, layers_per_stage
    rng = np.random.RandomState(seed)
    head_shape = (S, d_model) if n_tasks == 1 else (S, d_model, n_tasks)
    head_b = (S,) if n_tasks == 1 else (S, n_tasks)
    p = {
        # stacked [S, ...]: each device materialises one stage's slice;
        # proj is live on stage 0 only, head on the last only (their
        # other slices get zero grads and never influence the logits)
        "proj_w": (scale * rng.randn(S, pooled_dim, d_model)
                   ).astype(np.float32),
        "proj_b": np.zeros((S, d_model), np.float32),
        "blk_w": (scale * rng.randn(S, L, d_model, d_model)
                  ).astype(np.float32),
        "blk_b": np.zeros((S, L, d_model), np.float32),
        "head_w": (scale * rng.randn(*head_shape)).astype(np.float32),
        "head_b": np.zeros(head_b, np.float32),
    }
    if use_data_norm:
        # running-summary leaves (DataNormState.init defaults): updated
        # by the running-sums rule, never by the optimizer (zero grads
        # via stop_gradient in the embed section)
        p["dn_size"] = np.full((S, pooled_dim), 1e4, np.float32)
        p["dn_sum"] = np.zeros((S, pooled_dim), np.float32)
        p["dn_sqsum"] = np.full((S, pooled_dim), 1e4, np.float32)
    return p


class CtrPipelineRunner:
    """Pipeline-parallel training of a REAL CTR model (program split).

    The capability the toy GPipeRunner only sketches: the reference cuts
    the actual training program into sections (BoxPSOptimizer cut_list,
    python/paddle/fluid/optimizer.py:7496-7575) and runs them as a
    micro-batch pipeline (section_worker.cc; HeterPipelineTrainer,
    trainer.h:341). Here the cut is:

      stage 0        sparse pull view → fused seqpool+CVM → input
                     projection (the embedding section)
      every stage    its own block of the deep relu tower
      last stage     sigmoid head + loss

    One SPMD scan+ppermute program runs the M+S-1 GPipe ticks; jax.grad
    transposes it into the reverse pipeline, so the loss gradient flows
    back across the stages into stage 0's pull and from there into the
    in-table sparse optimizer — the single-chip fused step's push
    semantics (build_push_grads + push_sparse_dedup), now fed through a
    multi-stage pipeline.

    Pass-table composition: the slab rides the step REPLICATED over the
    stage axis. Only stage 0's pull carries gradient; the psum of the
    embedding cotangent makes every device apply the identical push, so
    the slab replicas never diverge (tests assert parity with a
    sequential single-chip oracle).
    """

    def __init__(self, table_cfg, feed, n_stages: int = 2,
                 d_model: int = 32, layers_per_stage: int = 1,
                 lr: float = 1e-2, n_micro: Optional[int] = None,
                 use_cvm: bool = True, mesh: Optional[Mesh] = None,
                 seed: int = 0, task_names=("ctr",),
                 use_data_norm: bool = False, dn_slot_dim: int = 0,
                 dn_decay: float = 0.9999999, dump_fields=None,
                 dump_fields_path: Optional[str] = None,
                 dump_thread_num: int = 1):
        """task_names: >1 entries grow the last stage's head to T logits
        per instance trained on per-task labels (feed.task_label_slots;
        absent tasks fall back to the click label) — ESMM/MMoE-style
        multi-task through the pipeline.

        use_data_norm: streaming input normalization of stage 0's
        projection input by running summaries updated with the
        running-sums rule (the CtrDnn(use_data_norm) semantics through
        the pipeline; boxps_worker.cc:89-95 summary params)."""
        from paddlebox_tpu.embedding.pass_table import PassTable
        self.task_names = tuple(task_names)
        self.multi_task = len(self.task_names) > 1
        self.use_data_norm = use_data_norm
        self.dn_slot_dim = dn_slot_dim
        self.dn_decay = dn_decay
        self.dump_fields, self.dump_writer = _make_dump_writer(
            dump_fields, dump_fields_path, dump_thread_num)
        self.table = PassTable(table_cfg, seed=seed)
        self.table_cfg = table_cfg
        self.feed = feed
        self.layout = self.table.layout
        self.num_slots = len(feed.used_sparse_slots())
        self.mb = feed.batch_size          # one PackedBatch = one micro-batch
        self.use_cvm = use_cvm
        self.n_micro = n_micro or 2 * n_stages
        if mesh is None:
            devs = np.array(jax.devices()[:n_stages])
            mesh = Mesh(devs, (STAGE_AXIS,))
        # 1D (stage,) mesh = pure pipeline; 2D (dp, stage) mesh composes
        # DATA parallelism over the pipeline: each dp row pipelines its
        # own micro-batch group, dense grads pmean over dp (per stage),
        # and every row's sparse push grads allgather so the replicated
        # slab applies one identical combined update (the multi-worker
        # push-merge of the reference, pipelined)
        if len(mesh.axis_names) == 1:
            self.dp = 1
        elif len(mesh.axis_names) == 2:
            self.dp = int(mesh.shape[mesh.axis_names[0]])
        else:
            raise ValueError("CtrPipelineRunner meshes are (stage,) or "
                             f"(dp, stage); got axes {mesh.axis_names}")
        if int(mesh.shape[mesh.axis_names[-1]]) != n_stages:
            raise ValueError("mesh stage axis %d != n_stages %d"
                             % (mesh.shape[mesh.axis_names[-1]], n_stages))
        self.mesh = mesh
        self.axis = mesh.axis_names[-1]        # the stage (pipeline) axis
        self.dp_axis = (mesh.axis_names[0] if len(mesh.axis_names) == 2
                        else None)
        D = table_cfg.embedx_dim
        slot_dim = (3 + D) if use_cvm else (1 + D)
        # expand (NN-cross) blocks sum-pool per slot and concat after the
        # CVM-pooled features into the projection input
        pooled_dim = self.num_slots * (slot_dim + table_cfg.expand_embed_dim)
        host_params = ctr_stage_host_params(
            seed, n_stages, layers_per_stage, pooled_dim, d_model,
            n_tasks=len(self.task_names),
            use_data_norm=self.use_data_norm)
        sh = NamedSharding(mesh, P(self.axis))
        self.params = {k: jax.device_put(v, sh)
                       for k, v in host_params.items()}
        self.opt = optax.adam(lr)
        host_opt = self.opt.init(host_params)
        self.opt_state = jax.tree.map(
            lambda x: (jax.device_put(jnp.asarray(x), sh)
                       if getattr(x, "ndim", 0) else jnp.asarray(x)),
            host_opt)
        self._prng = jax.random.PRNGKey(seed + 31)
        from paddlebox_tpu.metrics.auc import MetricRegistry
        self.metrics = MetricRegistry()
        from paddlebox_tpu.metrics import quality as _pbtpu_quality
        self.quality = _pbtpu_quality.make_from_flags()
        # telemetry plane (round 10): per-step cadence fed by the shared
        # pass drivers (_pipe_note_step)
        self._step_count = 0
        self._examples_per_step = feed.batch_size * self.batches_per_step
        self.reporter = make_step_reporter()
        self._step, self._eval = self._build_step()

    # ------------------------------------------------------------- jit step
    def _build_step(self):
        from paddlebox_tpu.embedding.optimizers import push_sparse_dedup
        from paddlebox_tpu.ops.sparse import (build_push_grads,
                                              build_push_grads_extended,
                                              pull_sparse,
                                              pull_sparse_extended)

        S = int(self.mesh.shape[self.axis])
        M, mb = self.n_micro, self.mb
        num_slots, use_cvm = self.num_slots, self.use_cvm
        layout, conf = self.layout, self.table_cfg.optimizer
        E = layout.expand_dim
        task_names = self.task_names
        axis = self.axis
        dp_axis = self.dp_axis
        opt = self.opt
        pad_id = self.table_cfg.pass_capacity - 1
        # which opt-state leaves carry the [S, ...] stage axis (rank>=1;
        # scalars like the adam count stay replicated) — rank AFTER the
        # stage slice can hit 0 (head_b moments), so the decision must be
        # made here, not on the sliced value
        opt_sharded = jax.tree.map(
            lambda x: getattr(x, "ndim", 0) > 0, self.opt_state)

        # the three program sections hung on the ONE shared GPipe schedule
        # (_spmd_pipeline): ingest = the embedding section (stage 0 only —
        # other stages compute-and-discard via the schedule's where, so
        # grads only flow to the selected branch), stage_apply = this
        # stage's tower blocks, emit = the head on the last stage
        blocks, embed_section, head, proj_input_all = ctr_pipeline_sections(
            mb, num_slots, use_cvm, E,
            use_data_norm=self.use_data_norm,
            dn_slot_dim=self.dn_slot_dim)
        use_dn, dn_decay, dn_sd = (self.use_data_norm, self.dn_decay,
                                   self.dn_slot_dim)
        pipe_run = _spmd_pipeline(blocks, S, M, axis,
                                  ingest=embed_section, emit=head)

        def pipe(p, emb_all, exp_all, batch):
            x_all = proj_input_all(emb_all, exp_all, batch["segments"],
                                   batch["key_valid"])
            return pipe_run(p, x_all), x_all

        def step(params, opt_state, slab, batch, prng):
            local = jax.tree.map(lambda x: x[0], params)
            local_opt = jax.tree.map(
                lambda x, s: x[0] if s else x, opt_state, opt_sharded)
            if dp_axis is not None:
                # [dp, M, ...] sharded over dp → this row's [M, ...]
                batch = jax.tree.map(lambda x: x[0], batch)
            prng, sub = jax.random.split(prng)
            K = batch["ids"].shape[-1]
            ids_flat = batch["ids"].reshape(-1)
            # key validity is DERIVED on device (ids == trash row), like
            # the single-chip trainer's _key_valid — no redundant H2D leaf
            batch = dict(batch, key_valid=batch["ids"] != pad_id)
            if E:
                base, exp = pull_sparse_extended(slab, ids_flat, layout)
                emb_all = base.reshape(M, K, -1)
                exp_all = exp.reshape(M, K, E)
            else:
                emb_all = pull_sparse(slab, ids_flat, layout
                                      ).reshape(M, K, -1)
                exp_all = None

            task_labels = {t: batch["labels_" + t] for t in task_names
                           } if len(task_names) > 1 else None

            def loss_fn(p, emb_all, exp_all=None):
                logits, x_all = pipe(p, emb_all, exp_all, batch)
                loss, preds = ctr_pipeline_loss(
                    logits, batch["labels"], batch["ins_valid"],
                    task_labels, task_names)
                return loss, (preds, x_all)

            if E:
                (loss, (preds, x_all)), (dparams, demb, dexp) = \
                    jax.value_and_grad(
                        loss_fn, argnums=(0, 1, 2), has_aux=True)(
                        local, emb_all, exp_all)
                dexp = jax.lax.psum(dexp, axis)
            else:
                (loss, (preds, x_all)), (dparams, demb) = \
                    jax.value_and_grad(
                        loss_fn, argnums=(0, 1), has_aux=True)(
                        local, emb_all)
                dexp = None
            # the pull lives on stage 0 — every other device's demb is
            # zero; the psum hands stage 0's cotangent to all so the
            # replicated push below is bit-identical everywhere
            demb = jax.lax.psum(demb, axis)
            if dp_axis is not None:
                # data parallel across the dp rows: each stage's block
                # grads average over its replicas (per-step NCCL sync)
                dparams = jax.lax.pmean(dparams, dp_axis)
                loss = jax.lax.pmean(loss, dp_axis)
            # per-stage params update with LOCAL grads (each device owns
            # its section; nothing to allreduce across stages)
            updates, local_opt = opt.update(dparams, local_opt, local)
            local = optax.apply_updates(local, updates)
            if use_dn:
                local = dn_summary_apply(local, x_all, dn_decay, dn_sd,
                                         dp_axis)
            # single-chip push semantics over all M micro-batches at once
            ins = batch["segments"] // num_slots          # [M, K]
            m_off = (jnp.arange(M, dtype=ins.dtype) * mb)[:, None]
            # per-key click stat = FIRST task's label (the trainers'
            # convention, trainer.py _sparse_push)
            click_src = (batch["labels_" + task_names[0]]
                         if len(task_names) > 1 else batch["labels"])
            clicks = click_src.reshape(-1)[(ins + m_off).reshape(-1)]
            slots = (batch["segments"] % num_slots).reshape(-1)
            kv = batch["key_valid"].reshape(-1)
            if E:
                pg = build_push_grads_extended(
                    demb.reshape(M * K, -1), dexp.reshape(M * K, E),
                    slots, clicks, kv)
            else:
                pg = build_push_grads(demb.reshape(M * K, -1), slots,
                                      clicks, kv)
            if dp_axis is not None:
                # every dp row's grads combine into ONE push (the dedup
                # merge handles cross-row duplicate keys) so the
                # replicated slab applies the identical update everywhere
                ids_flat = jax.lax.all_gather(ids_flat, dp_axis, tiled=True)
                pg = jax.lax.all_gather(pg, dp_axis, tiled=True)
            slab = push_sparse_dedup(slab, ids_flat, pg, sub, layout, conf)
            params = jax.tree.map(lambda x: x[None], local)
            opt_state = jax.tree.map(
                lambda x, s: x[None] if s else x, local_opt, opt_sharded)
            return params, opt_state, slab, loss, preds, prng

        def eval_step(params, slab, batch):
            # test-mode inference (SetTestMode): same pipelined forward,
            # no push, no dense update
            local = jax.tree.map(lambda x: x[0], params)
            if dp_axis is not None:
                batch = jax.tree.map(lambda x: x[0], batch)
            ids_flat = batch["ids"].reshape(-1)
            K_e = batch["ids"].shape[-1]
            batch = dict(batch, key_valid=batch["ids"] != pad_id)
            if E:
                base, exp = pull_sparse_extended(slab, ids_flat, layout)
                emb_all = base.reshape(M, K_e, -1)
                exp_all = exp.reshape(M, K_e, E)
            else:
                emb_all = pull_sparse(slab, ids_flat, layout).reshape(
                    M, K_e, -1)
                exp_all = None
            logits, _x = pipe(local, emb_all, exp_all, batch)
            return jax.nn.sigmoid(logits)

        spec_sh = P(self.axis)
        opt_spec = jax.tree.map(
            lambda x: spec_sh if getattr(x, "ndim", 0) else P(),
            self.opt_state,
            is_leaf=lambda x: hasattr(x, "ndim") or np.isscalar(x))
        dp_spec = P(self.dp_axis) if dp_axis is not None else P()
        fn = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(spec_sh, opt_spec, P(), dp_spec, P()),
            out_specs=(spec_sh, opt_spec, P(), P(), dp_spec, P()),
            check_vma=False)
        efn = jax.shard_map(
            eval_step, mesh=self.mesh,
            in_specs=(spec_sh, P(), dp_spec), out_specs=dp_spec,
            check_vma=False)
        from paddlebox_tpu.obs.device import instrument_jit
        return (instrument_jit(fn, "ctr_pipe_step", donate_argnums=(2,)),
                instrument_jit(efn, "ctr_pipe_eval"))

    # ----------------------------------------------------------- host driver
    @property
    def batches_per_step(self) -> int:
        """PackedBatches one train_step consumes: dp rows × n_micro."""
        return self.dp * self.n_micro

    def device_batch(self, packed_batches) -> Dict[str, jnp.ndarray]:
        """dp × n_micro PackedBatches (each one micro-batch / section
        scope; row-major by dp row) → stacked [dp, M, ...] device leaves
        ([M, ...] on a pure-pipeline 1D mesh)."""
        if len(packed_batches) != self.batches_per_step:
            raise ValueError(
                "need exactly dp*n_micro=%d batches, got %d"
                % (self.batches_per_step, len(packed_batches)))

        def stack(arrs):
            out = np.stack(arrs)
            if self.dp_axis is not None:   # incl. dp=1 on a 2D mesh
                out = out.reshape(self.dp, self.n_micro, *out.shape[1:])
            return jnp.asarray(out)

        ids = stack([self.table.lookup_ids(b.keys, b.valid)
                     for b in packed_batches])
        out = {
            "ids": ids,
            "segments": stack([b.segments for b in packed_batches]),
            "labels": stack([b.labels for b in packed_batches]),
            "ins_valid": stack([b.ins_valid for b in packed_batches]),
        }
        if self.multi_task:
            for t in self.task_names:
                out["labels_" + t] = stack(
                    [_task_label_of(b, t) for b in packed_batches])
        return out

    def train_step(self, packed_batches) -> float:
        """ONE pipelined train step over dp × n_micro micro-batches."""
        return self.train_step_staged(self.device_batch(packed_batches),
                                      packed_batches)

    def train_step_staged(self, batch, packed_batches) -> float:
        """Dispatch a step whose host staging (device_batch) already
        happened — the consumer half of the pass driver's prefetch
        stager (_grouped_train_pass)."""
        (self.params, self.opt_state, slab, loss, preds,
         self._prng) = self._step(self.params, self.opt_state,
                                  self.table.slab, batch, self._prng)
        self.table.set_slab(slab)
        _feed_pipeline_metrics(self, preds, packed_batches)
        return float(loss)

    def predict_batches(self, dataset):
        """Test-mode inference (SetTestMode: no creation, no push) over
        full micro-batch groups; returns (preds, labels) of the covered
        valid instances."""
        return _pipeline_predict(self, dataset, self.table.begin_pass,
                                 self.table.end_pass,
                                 lambda: self.table.slab)

    def close(self) -> None:
        """Flush and stop the dump writers + telemetry sinks."""
        if self.dump_writer is not None:
            self.dump_writer.close()
            self.dump_writer = None
        if getattr(self, "reporter", None) is not None:
            self.reporter.close()
            self.reporter = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails;
            # close() is the loud path, this is the last-resort guard
            pass

    def train_pass(self, dataset) -> Dict[str, float]:
        """BoxPS pass cadence around the pipelined step (the shared
        _grouped_train_pass driver)."""
        return _grouped_train_pass(self, dataset, self.table.begin_pass,
                                   self.table.end_pass)


class ShardedCtrPipelineRunner:
    """Pipeline parallelism COMPOSED with the key-mod sharded pass table —
    per-device table memory is O(pass/P), not O(pass).

    The round-3 CtrPipelineRunner replicates the pass slab on every stage
    device, so pipeline parallelism could not be applied to exactly the
    configs that need it (a 100B-key pass). The reference's section
    programs run `pull_box_sparse` against the FULL sharded PS
    (section_worker.cc op loop; device_worker.h:639; heter_comm_inl.h:
    1296-1445 walk_to_src). The TPU shape of that composition:

      * the slab shards over ALL mesh devices (stage devices double as
        table shards; on a (dp, stage) mesh the table axis is the
        flattened device set, key % P routing — split_input_to_shard,
        heter_comm_inl.h:1117);
      * each device pulls the keys of ITS n_micro/S micro-batches
        through the id/value all_to_all pair (ShardedPassTable routing),
        then one all_gather over the STAGE axis assembles the dp row's
        [M, K, D'] embedding block — the gather/a2a work of the
        embedding section spreads across the pipeline's devices instead
        of duplicating;
      * the GPipe schedule (_spmd_pipeline, unchanged) runs the tower;
      * push reverses: stage 0's embedding cotangent (psum over stage)
        is sliced back per device, scattered into per-shard buckets,
        a2a'd, and merged into each shard with the in-table optimizer.
        On a (dp, stage) mesh, cross-row duplicate keys merge in the
        shard-side dedup — the routing subsumes the replicated runner's
        push all_gather.
    """

    def __init__(self, table_cfg, feed, n_stages: int = 2,
                 d_model: int = 32, layers_per_stage: int = 1,
                 lr: float = 1e-2, n_micro: Optional[int] = None,
                 use_cvm: bool = True, mesh: Optional[Mesh] = None,
                 bucket_cap: Optional[int] = None, seed: int = 0,
                 fleet=None, store_factory=None, task_names=("ctr",),
                 use_data_norm: bool = False, dn_slot_dim: int = 0,
                 dn_decay: float = 0.9999999, dump_fields=None,
                 dump_fields_path: Optional[str] = None,
                 dump_thread_num: int = 1):
        """task_names: >1 grows the head to T logits per instance;
        use_data_norm: streaming input normalization (see
        CtrPipelineRunner for both).

        fleet: REQUIRED in a multi-process job — unions feed-pass keys
        and equalizes the per-process step-group counts. Multi-process
        topology: the dp axis must span the processes in whole rows (each
        process feeds its own dp rows' micro-batches; a pipeline row's
        stage devices need the same data, so a row cannot straddle
        processes).

        store_factory: overrides the shard store backend — pass
        embedding.ps_store.ps_store_factory(client, table_id) to run the
        GPUPS composition (pipeline sections over pass slabs built from /
        dumped to the distributed CPU PS — the reference's section
        programs against the full PS, section_worker.cc +
        ps_gpu_wrapper.cc:337-955)."""
        from paddlebox_tpu.parallel.sharded_table import ShardedPassTable
        self.task_names = tuple(task_names)
        self.multi_task = len(self.task_names) > 1
        self.use_data_norm = use_data_norm
        self.dn_slot_dim = dn_slot_dim
        self.dn_decay = dn_decay
        self.dump_fields, self.dump_writer = _make_dump_writer(
            dump_fields, dump_fields_path, dump_thread_num)
        self.table_cfg = table_cfg
        self.feed = feed
        self.num_slots = len(feed.used_sparse_slots())
        self.mb = feed.batch_size
        self.use_cvm = use_cvm
        self.n_stages = n_stages
        self.n_micro = n_micro or 2 * n_stages
        if self.n_micro % n_stages:
            raise ValueError(
                f"n_micro={self.n_micro} must divide by n_stages="
                f"{n_stages} (each stage device pulls an equal micro "
                "slice)")
        self.m_local = self.n_micro // n_stages
        if mesh is None:
            devs = np.array(jax.devices()[:n_stages])
            mesh = Mesh(devs, (STAGE_AXIS,))
        if len(mesh.axis_names) == 1:
            self.dp = 1
        elif len(mesh.axis_names) == 2:
            self.dp = int(mesh.shape[mesh.axis_names[0]])
        else:
            raise ValueError("meshes are (stage,) or (dp, stage); got "
                             f"axes {mesh.axis_names}")
        if int(mesh.shape[mesh.axis_names[-1]]) != n_stages:
            raise ValueError("mesh stage axis %d != n_stages %d"
                             % (mesh.shape[mesh.axis_names[-1]], n_stages))
        self.mesh = mesh
        self.axis = mesh.axis_names[-1]
        self.dp_axis = (mesh.axis_names[0] if len(mesh.axis_names) == 2
                        else None)
        self.flat_axes = tuple(mesh.axis_names)   # the table axis
        self.P = int(mesh.devices.size)
        self.fleet = fleet
        self._pool = None  # lazy stager thread pool
        self.multiprocess = jax.process_count() > 1
        mesh_devs = list(self.mesh.devices.flat)
        pid = jax.process_index()
        self.local_positions = [i for i, d in enumerate(mesh_devs)
                                if d.process_index == pid]
        self.n_local = len(self.local_positions)
        if self.multiprocess:
            if fleet is None:
                raise ValueError("multi-process ShardedCtrPipelineRunner "
                                 "needs fleet=")
            rows = {p // n_stages for p in self.local_positions}
            want = sorted(r * n_stages + s for r in rows
                          for s in range(n_stages))
            if want != sorted(self.local_positions):
                raise ValueError(
                    "a pipeline row must live whole in one process (the "
                    "dp axis spans processes); this process owns mesh "
                    f"positions {sorted(self.local_positions)}")
            self.local_rows = sorted(rows)
        else:
            self.local_rows = list(range(self.dp))
        # 2-D sparse sharding policy (round 13; see ShardedBoxTrainer)
        from paddlebox_tpu.parallel.sharding import (
            resolve_sharding_policy, validate_policy_agreement)
        self.policy = resolve_sharding_policy(self.P)
        # p2p host data plane (round 9; see ShardedBoxTrainer): None =
        # the store-allgather plane (flag 'store' or collective fallback)
        from paddlebox_tpu.fleet.mesh_comm import resolve_hostplane
        self.host_mesh = (
            fleet.make_mesh_comm(self.local_positions,
                                 policy_id=self.policy.describe())
            if self.multiprocess and resolve_hostplane() == "p2p"
            else None)
        if self.multiprocess and self.host_mesh is None:
            # store plane never rendezvouses — validate the policy
            # identity across ranks here instead
            validate_policy_agreement(fleet, self.policy)
        kcap = feed.key_capacity()
        self.bucket_cap = bucket_cap or max(
            16, (2 * self.m_local * kcap) // self.P)
        self.table = ShardedPassTable(
            table_cfg, self.P, self.bucket_cap, seed=seed,
            owned_shards=(self.local_positions if self.multiprocess
                          else None),
            store_factory=store_factory, policy=self.policy)
        # resolved ONCE — per-batch re-resolution would let a mid-pass flag
        # flip change the batch pytree (retrace of the shard_map step) and
        # mix write modes inside one pass (same policy as the trainers)
        from paddlebox_tpu.train.trainer import resolve_push_write_sharded
        self._push_write = resolve_push_write_sharded(
            self.table.shard_cap, self.P, self.bucket_cap,
            self.multiprocess)
        self.layout = self.table.layout
        D = table_cfg.embedx_dim
        slot_dim = (3 + D) if use_cvm else (1 + D)
        # expand (NN-cross) blocks sum-pool per slot and concat after the
        # CVM-pooled features into the projection input
        pooled_dim = self.num_slots * (slot_dim + table_cfg.expand_embed_dim)
        host_params = ctr_stage_host_params(
            seed, n_stages, layers_per_stage, pooled_dim, d_model,
            n_tasks=len(self.task_names),
            use_data_norm=self.use_data_norm)
        sh = NamedSharding(mesh, P(self.axis))

        def put_stage(v):
            # stage axis is within-process by the whole-row topology rule,
            # so each process's addressable stage shards cover the full
            # [S, ...] array (replicated over the dp axis)
            v = np.asarray(v)
            if not self.multiprocess:
                return jax.device_put(v, sh)
            return jax.make_array_from_process_local_data(sh, v, v.shape)

        self.params = {k: put_stage(v) for k, v in host_params.items()}
        self.opt = optax.adam(lr)
        host_opt = self.opt.init(host_params)
        self.opt_state = jax.tree.map(
            lambda x: (put_stage(x) if getattr(x, "ndim", 0)
                       else jnp.asarray(x)),
            host_opt)
        self._prng = jax.random.PRNGKey(seed + 31)
        self._slabs = None
        from paddlebox_tpu.metrics.auc import MetricRegistry
        self.metrics = MetricRegistry()
        from paddlebox_tpu.metrics import quality as _pbtpu_quality
        self.quality = _pbtpu_quality.make_from_flags()
        # telemetry plane (round 10): rank-tagged reporter; the shared
        # pass drivers feed the cadence (_pipe_note_step); multi-process,
        # reports piggyback to rank 0 for the merged cluster view
        self._step_count = 0
        self._examples_per_step = feed.batch_size * self.batches_per_step
        from paddlebox_tpu.obs import (make_cluster_aggregator,
                                       obs_rank_world)
        obs_rank, obs_world = (obs_rank_world(self.host_mesh, fleet)
                               if self.multiprocess else (0, 1))
        aggregator = (make_cluster_aggregator(
            mesh=self.host_mesh, fleet=fleet, rank=obs_rank,
            world=obs_world) if self.multiprocess else None)
        self._obs_rank = obs_rank   # per-step trace ids (round 14)
        self.reporter = make_step_reporter(rank=obs_rank,
                                           aggregator=aggregator)
        self._step, self._eval = self._build_step()

    # ------------------------------------------------------------- jit step
    def _build_step(self):
        from paddlebox_tpu.embedding.optimizers import (
            push_sparse_dedup, push_sparse_hostdedup, push_sparse_rebuild,
            push_sparse_uidwire)
        from paddlebox_tpu.ops.sparse import (build_push_grads,
                                              build_push_grads_extended,
                                              pull_sparse,
                                              pull_sparse_extended)

        push_write = self._push_write   # uid-wire write strategy (static)
        S, M, Ml, mb = self.n_stages, self.n_micro, self.m_local, self.mb
        num_slots, use_cvm = self.num_slots, self.use_cvm
        layout, conf = self.layout, self.table_cfg.optimizer
        E = layout.expand_dim
        task_names = self.task_names
        base_w = (3 + layout.embedx_dim)   # pull-view width before expand
        axis, dp_axis, flat = self.axis, self.dp_axis, self.flat_axes
        opt = self.opt
        opt_sharded = jax.tree.map(
            lambda x: getattr(x, "ndim", 0) > 0, self.opt_state)

        def local_pull(slab, req):
            # expand mode: base + expand blocks ride ONE value a2a
            # concatenated (the sharded trainer's wire layout) and split
            # after the restore
            if E:
                b, x = pull_sparse_extended(slab, req.reshape(-1), layout)
                return jnp.concatenate([b, x], axis=1)
            return pull_sparse(slab, req.reshape(-1), layout)

        blocks, embed_section, head, proj_input_all = ctr_pipeline_sections(
            mb, num_slots, use_cvm, E,
            use_data_norm=self.use_data_norm,
            dn_slot_dim=self.dn_slot_dim)
        use_dn, dn_decay, dn_sd = (self.use_data_norm, self.dn_decay,
                                   self.dn_slot_dim)
        pipe_run = _spmd_pipeline(blocks, S, M, axis,
                                  ingest=embed_section, emit=head)

        def step(params, opt_state, slab, batch, prng):
            local = jax.tree.map(lambda x: x[0], params)
            local_opt = jax.tree.map(
                lambda x, s: x[0] if s else x, opt_state, opt_sharded)
            slab = slab[0]
            batch = jax.tree.map(lambda x: x[0], batch)
            prng, sub = jax.random.split(prng)
            sub = jax.random.fold_in(sub, jax.lax.axis_index(flat))
            buckets = batch["buckets"]                     # [P, KB]
            Pn, KB = buckets.shape
            K = batch["segments"].shape[-1]

            # ---- pull: a2a ids → local shard gather → a2a values →
            # restore THIS device's micro slice, then assemble the dp
            # row's full [M, K, D'(+E)] block over the stage axis
            req = jax.lax.all_to_all(buckets, flat, 0, 0, tiled=True)
            vals = local_pull(slab, req)
            resp = jax.lax.all_to_all(
                vals.reshape(Pn, KB, -1), flat, 0, 0, tiled=True)
            emb_loc = resp.reshape(Pn * KB, -1)[batch["restore"]]
            emb_cat = jax.lax.all_gather(
                emb_loc.reshape(Ml, K, -1), axis, tiled=True)
            if E:
                emb_all = emb_cat[..., :base_w]
                exp_all = emb_cat[..., base_w:]
            else:
                emb_all, exp_all = emb_cat, None
            segments = jax.lax.all_gather(batch["segments"], axis,
                                          tiled=True)           # [M, K]
            key_valid = jax.lax.all_gather(batch["valid"], axis, tiled=True)
            labels = jax.lax.all_gather(batch["labels"], axis, tiled=True)
            ins_valid = jax.lax.all_gather(batch["ins_valid"], axis,
                                           tiled=True)          # [M, mb]
            task_labels = ({t: jax.lax.all_gather(batch["labels_" + t],
                                                  axis, tiled=True)
                            for t in task_names}
                           if len(task_names) > 1 else None)

            def loss_fn(p, emb_all, exp_all=None):
                x_all = proj_input_all(emb_all, exp_all, segments,
                                       key_valid)
                logits = pipe_run(p, x_all)
                loss, preds = ctr_pipeline_loss(logits, labels, ins_valid,
                                                task_labels, task_names)
                return loss, (preds, x_all)

            if E:
                (loss, (preds, x_all)), (dparams, demb, dexp) = \
                    jax.value_and_grad(
                        loss_fn, argnums=(0, 1, 2), has_aux=True)(
                        local, emb_all, exp_all)
                dexp = jax.lax.psum(dexp, axis)
            else:
                (loss, (preds, x_all)), (dparams, demb) = \
                    jax.value_and_grad(
                        loss_fn, argnums=(0, 1), has_aux=True)(
                        local, emb_all)
                dexp = None
            # stage 0 owns the pull — psum hands its cotangent to all
            demb = jax.lax.psum(demb, axis)
            if dp_axis is not None:
                dparams = jax.lax.pmean(dparams, dp_axis)
                loss = jax.lax.pmean(loss, dp_axis)
            updates, local_opt = opt.update(dparams, local_opt, local)
            local = optax.apply_updates(local, updates)
            if use_dn:
                local = dn_summary_apply(local, x_all, dn_decay, dn_sd,
                                         dp_axis)

            # ---- push: MY micro slice of the cotangent goes back through
            # the reverse a2a into the shard-side merge + in-table update
            sidx = jax.lax.axis_index(axis)
            demb_loc = jax.lax.dynamic_slice_in_dim(
                demb, sidx * Ml, Ml, axis=0)                   # [Ml, K, D']
            ins = batch["segments"] // num_slots               # [Ml, K]
            # per-key click stat = FIRST task's label (trainers' rule)
            click_src = (batch["labels_" + task_names[0]]
                         if len(task_names) > 1 else batch["labels"])
            clicks = jnp.take_along_axis(click_src, ins, axis=1)
            slots = batch["segments"] % num_slots
            kv = batch["valid"].reshape(-1)
            if E:
                dexp_loc = jax.lax.dynamic_slice_in_dim(
                    dexp, sidx * Ml, Ml, axis=0)
                pg = build_push_grads_extended(
                    demb_loc.reshape(Ml * K, -1),
                    dexp_loc.reshape(Ml * K, E), slots.reshape(-1),
                    clicks.reshape(-1), kv)
            else:
                pg = build_push_grads(demb_loc.reshape(Ml * K, -1),
                                      slots.reshape(-1),
                                      clicks.reshape(-1), kv)
            bucket_g = jnp.zeros((Pn * KB, pg.shape[1]), pg.dtype
                                 ).at[batch["restore"]].add(
                jnp.where(kv[:, None], pg, 0.0))
            recv_g = jax.lax.all_to_all(
                bucket_g.reshape(Pn, KB, -1), flat, 0, 0, tiled=True)
            if "push_pos" in batch:
                # scatter-free shard write: host-staged pos map turns the
                # slab write into gather+select (push_write=rebuild)
                slab = push_sparse_rebuild(
                    slab, batch["push_uids"], batch["push_pos"],
                    batch["push_perm"], batch["push_inv"],
                    recv_g.reshape(Pn * KB, -1), sub, layout, conf)
            elif "push_perm" in batch:
                # incoming ids are host-known in a single process, so the
                # shard-side dedup was precomputed (device_batch) — no
                # per-step on-device jnp.unique sort (the dominant
                # fused-step cost the sharded trainer's host-dedup path
                # removed)
                slab = push_sparse_hostdedup(
                    slab, batch["push_uids"], batch["push_perm"],
                    batch["push_inv"], recv_g.reshape(Pn * KB, -1), sub,
                    layout, conf)
            elif "push_uids" in batch:
                # uid wire (h2d_uid_wire, round 8): only the sorted uid
                # vector staged — the incoming ids are the a2a'd buckets
                # (req) and the maps derive by searchsorted in the step
                slab = push_sparse_uidwire(
                    slab, batch["push_uids"], req.reshape(-1),
                    recv_g.reshape(Pn * KB, -1), sub, layout, conf,
                    write=push_write)
            else:
                # multi-process: incoming ids live on peers — device dedup
                slab = push_sparse_dedup(slab, req.reshape(-1),
                                         recv_g.reshape(Pn * KB, -1), sub,
                                         layout, conf)

            params = jax.tree.map(lambda x: x[None], local)
            opt_state = jax.tree.map(
                lambda x, s: x[None] if s else x, local_opt, opt_sharded)
            return params, opt_state, slab[None], loss, preds, prng

        def eval_step(params, slab, batch):
            # test-mode inference: the same a2a pull + pipelined forward,
            # no push, no dense update
            local = jax.tree.map(lambda x: x[0], params)
            slab = slab[0]
            batch = jax.tree.map(lambda x: x[0], batch)
            buckets = batch["buckets"]
            Pn, KB = buckets.shape
            K = batch["segments"].shape[-1]
            req = jax.lax.all_to_all(buckets, flat, 0, 0, tiled=True)
            vals = local_pull(slab, req)
            resp = jax.lax.all_to_all(
                vals.reshape(Pn, KB, -1), flat, 0, 0, tiled=True)
            emb_loc = resp.reshape(Pn * KB, -1)[batch["restore"]]
            emb_cat = jax.lax.all_gather(
                emb_loc.reshape(Ml, K, -1), axis, tiled=True)
            if E:
                emb_all, exp_all = emb_cat[..., :base_w], \
                    emb_cat[..., base_w:]
            else:
                emb_all, exp_all = emb_cat, None
            segments = jax.lax.all_gather(batch["segments"], axis,
                                          tiled=True)
            key_valid = jax.lax.all_gather(batch["valid"], axis,
                                           tiled=True)
            x_all = proj_input_all(emb_all, exp_all, segments, key_valid)
            return jax.nn.sigmoid(pipe_run(local, x_all))

        spec_stage = P(self.axis)
        spec_flat = P(self.flat_axes)
        opt_spec = jax.tree.map(
            lambda x: spec_stage if getattr(x, "ndim", 0) else P(),
            self.opt_state,
            is_leaf=lambda x: hasattr(x, "ndim") or np.isscalar(x))
        preds_spec = P(self.dp_axis) if dp_axis is not None else P()
        fn = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(spec_stage, opt_spec, spec_flat, spec_flat, P()),
            out_specs=(spec_stage, opt_spec, spec_flat, P(), preds_spec,
                       P()),
            check_vma=False)
        efn = jax.shard_map(
            eval_step, mesh=self.mesh,
            in_specs=(spec_stage, spec_flat, spec_flat),
            out_specs=preds_spec, check_vma=False)
        from paddlebox_tpu.obs.device import instrument_jit
        return (instrument_jit(fn, "tower_pipe_step", donate_argnums=(2,)),
                instrument_jit(efn, "tower_pipe_eval"))

    # ----------------------------------------------------------- host driver
    @property
    def batches_per_step(self) -> int:
        """PackedBatches one train_step consumes FROM THIS PROCESS (its
        dp rows × n_micro; every row in a single process)."""
        return len(self.local_rows) * self.n_micro

    def _put_flat(self, host_local: np.ndarray,
                  sharding=None) -> jnp.ndarray:
        """Local [L, ...] per-device rows → global [P, ...] on the
        flattened table axis (plain device_put in a single process).
        sharding overrides the default P(flat) placement (the slab put
        rides the policy's layout)."""
        sh = sharding or NamedSharding(self.mesh, P(self.flat_axes))
        if not self.multiprocess:
            return jax.device_put(host_local, sh)
        return jax.make_array_from_process_local_data(
            sh, host_local, (self.P,) + host_local.shape[1:])

    def _stager_pool(self):
        """Routing thread pool (flag stager_threads): per-(row, stage)
        bucketize and per-destination push dedup fan out — the native
        calls release the GIL (the 20/30 reader/merge-thread role,
        flags.cc:966-968; round-5 verdict item 7)."""
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            from paddlebox_tpu.config import flags
            n = max(1, int(flags.get_flag("stager_threads")))
            self._pool = ThreadPoolExecutor(
                n, thread_name_prefix="pipe-stager")
        return self._pool

    def device_batch(self, packed_batches) -> Dict[str, jnp.ndarray]:
        """This process's dp rows × n_micro PackedBatches (row-major) →
        per-device leaves stacked [P, ...] globally: device (r, s) routes
        the keys of row r's micro slice [s·Ml, (s+1)·Ml). Per-(row,
        stage) routing and per-destination dedup run on the stager pool."""
        if len(packed_batches) != self.batches_per_step:
            raise ValueError(
                "need exactly local_rows*n_micro=%d batches, got %d"
                % (self.batches_per_step, len(packed_batches)))
        leaves: Dict[str, list] = {k: [] for k in (
            "buckets", "restore", "valid", "segments", "labels",
            "ins_valid")}
        Ml = self.m_local
        pool = self._stager_pool()

        def route_one(item):
            ri, s = item
            row = packed_batches[ri * self.n_micro:(ri + 1) * self.n_micro]
            sub = row[s * Ml:(s + 1) * Ml]
            K = sub[0].keys.shape[0]
            keys = np.concatenate([b.keys for b in sub])
            valid = np.concatenate([b.valid for b in sub]).copy()
            idx = self.table.bucketize(keys, valid)
            one = {
                "buckets": idx.buckets,
                "restore": idx.restore,
                "valid": valid.reshape(Ml, K),
                "segments": np.stack([b.segments for b in sub]),
                "labels": np.stack([b.labels for b in sub]),
                "ins_valid": np.stack([b.ins_valid for b in sub]),
            }
            if self.multi_task:
                for t in self.task_names:
                    one["labels_" + t] = np.stack(
                        [_task_label_of(b, t) for b in sub])
            return one

        items = [(ri, s) for ri in range(len(self.local_rows))
                 for s in range(self.n_stages)]
        for one in pool.map(route_one, items):
            for k, v in one.items():
                leaves.setdefault(k, []).append(v)
        if not self.table.test_mode:
            # every shard's incoming a2a ids are host-known — directly in
            # a single process, via the per-step bucket exchange across
            # processes — so the push dedup (+ rebuild pos maps) stages
            # for every owned destination and no deployment shape runs
            # the on-device jnp.unique sort (round-5 verdict item 2; ONE
            # shared implementation with the sharded trainer; reference
            # cluster-wide routing, heter_comm_inl.h:2231/1117). Eval
            # never pushes.
            from paddlebox_tpu.config import flags
            from paddlebox_tpu.parallel.sharded_table import stage_push_dedup
            leaves.update(stage_push_dedup(
                leaves["buckets"], self.local_positions, self.P,
                self.table.shard_cap, self.multiprocess,
                self.fleet.all_gather if self.multiprocess else None,
                rebuild=self._push_write == "rebuild", pool=pool,
                note_touched=self.table.note_touched,
                uid_only=bool(flags.get_flag("h2d_uid_wire")),
                mesh=self.host_mesh,
                policy=self.policy))
        return {k: self._put_flat(np.stack(v)) for k, v in leaves.items()}

    def begin_pass(self) -> None:
        """BeginPass: promote the feed pass's key set into the sharded
        [P, C, W] slab stack on the mesh (owned shards only in a
        multi-process job). The slab's device layout is the sharding
        policy's decision (c) — P(flat) for every policy on the
        (dp, stage) meshes this runner builds."""
        self._slabs = self._put_flat(
            self.table.build_owned_slabs() if self.multiprocess
            else self.table.build_slabs(),
            sharding=self.policy.slab_sharding(self.mesh,
                                               self.flat_axes))

    def end_pass(self) -> None:
        """EndPass: device slabs → shard stores, then the spill check.
        Multi-process: each process dumps only its addressable shards."""
        if self.multiprocess:
            self.table.write_back_addressable(self._slabs)
        else:
            # touched-row delta D2H when the incremental lifecycle ran
            self.table.end_pass_write_back(self._slabs)
        self._slabs = None
        self.table.check_need_limit_mem()

    def train_step(self, packed_batches) -> float:
        return self.train_step_staged(self.device_batch(packed_batches),
                                      packed_batches)

    def train_step_staged(self, batch, packed_batches) -> float:
        """Dispatch with staging done (see _grouped_train_pass's stager)."""
        (self.params, self.opt_state, self._slabs, loss, preds,
         self._prng) = self._step(self.params, self.opt_state, self._slabs,
                                  batch, self._prng)
        _feed_pipeline_metrics(self, preds, packed_batches)
        return float(loss)

    def predict_batches(self, dataset):
        """Test-mode inference over the sharded slabs (single process)."""
        return _pipeline_predict(self, dataset, self.begin_pass,
                                 self.end_pass, lambda: self._slabs)

    def close(self) -> None:
        """Flush and stop the dump writers + stager pool + telemetry
        sinks (the reporter also closes the rank-0 aggregator sink)."""
        if self.dump_writer is not None:
            self.dump_writer.close()
            self.dump_writer = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        if getattr(self, "reporter", None) is not None:
            self.reporter.close()
            self.reporter = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails;
            # close() is the loud path, this is the last-resort guard
            pass

    def train_pass(self, dataset) -> Dict[str, float]:
        """Pass cadence with the sharded table (the shared
        _grouped_train_pass driver; begin/end build and write back the
        sharded slab stack). Multi-process: feed keys union across the
        cluster and every process runs the SAME number of step groups
        (collectives stay lockstep)."""
        allgather = (self.fleet.all_gather if self.multiprocess else None)
        cap = None
        if self.multiprocess:
            def cap(n):
                return int(self.fleet.all_reduce(
                    np.asarray([n], np.int64), "min")[0])
        return _grouped_train_pass(self, dataset, self.begin_pass,
                                   self.end_pass, allgather=allgather,
                                   n_groups_cap=cap)
