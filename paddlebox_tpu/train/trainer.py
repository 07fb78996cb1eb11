"""Pass-cadenced trainer: the TPU-native BoxPSTrainer/BoxPSWorker runtime.

Re-design of the reference hot loop (BoxPSWorker::TrainFiles,
paddle/fluid/framework/boxps_worker.cc:1256-1335) for XLA: instead of an op
list interpreted per batch, ONE jitted train step fuses
pull → seqpool+CVM → model fwd/bwd → dense optimizer → push, and the pass
loop around it reproduces the BoxHelper cadence
(begin_feed_pass → load/AddKeys → end_feed_pass → begin_pass →
train batches → metrics → end_pass), box_wrapper.h:1032-1284.

The dense optimizer is optax (adam/sgd); sparse updates live inside the push
(in-table optimizer, like the PS). Metrics are streamed per batch
(AddAucMonitor analog, boxps_worker.cc:1245-1255). Nan/inf guard mirrors
FLAGS_check_nan_inf + CheckBatchNanOrInfRet (boxps_worker.cc:1303-1314).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu.config.configs import (DataFeedConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data.dataset import BoxDataset
from paddlebox_tpu.data.packer import PackedBatch
from paddlebox_tpu.embedding.accessor import ValueLayout
from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                push_sparse_rebuild)
from paddlebox_tpu.embedding.pass_table import (PassTable,
                                                occurrence_uid_slots,
                                                push_domain)
from paddlebox_tpu.metrics.auc import MetricRegistry
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.obs import beat as obs_beat
from paddlebox_tpu.obs import log as obs_log
from paddlebox_tpu.obs import make_step_reporter
from paddlebox_tpu.obs import span as obs_span
from paddlebox_tpu.obs.device import (account_h2d, instrument_jit,
                                      register_owner, tree_nbytes)
from paddlebox_tpu.obs.tracer import (current_trace, set_trace,
                                      step_trace_id, trace_ctx,
                                      with_current_trace)
from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm, seqpool_sum
from paddlebox_tpu.ops.sparse import (build_push_grads,
                                      build_push_grads_extended,
                                      pull_sparse, pull_sparse_extended,
                                      pull_sparse_unique)
from paddlebox_tpu.utils.stats import stat_add
from paddlebox_tpu.utils.timer import Timer


@dataclasses.dataclass
class TrainStepFns:
    """The jitted step + its static metadata."""

    step: Callable
    eval_step: Callable
    batch_size: int
    num_slots: int
    # scan_steps(slab, params, opt_state, stacked_batches, prng) runs a
    # whole chunk of batches inside ONE dispatch (lax.scan over the leading
    # axis), amortizing dispatch overhead (its size on the chip is not
    # measured)
    scan_steps: Optional[Callable] = None


def make_scan(step_fn: Callable, extra_carry: int = 0) -> Callable:
    """Wrap a (slab, params, opt_state, batch, prng, *extra) step into a
    jitted megastep scanning a leading chunk axis of `stacked` — one
    dispatch runs the whole chunk back-to-back on device, hiding per-step
    dispatch latency.

    extra_carry: number of additional state leaves threaded through the
    scan after prng (the sharded trainer's device metric state rides here;
    they are donated like the slab).

    The slab, the dense weights and the optimizer's state are DONATED: a
    dense tower's weights and moments are then resident once (12 B a
    parameter under adam, not 24 while a chunk runs). Their input buffers
    are dead after the call: rebind all three from the outputs before any
    further read."""

    def scan_steps(slab, params, opt_state, stacked, prng, *extra):
        def body(carry, batch):
            slab, params, opt_state, prng, *extra = carry
            slab, params, opt_state, loss, preds, prng, *extra = step_fn(
                slab, params, opt_state, batch, prng, *extra)
            return (slab, params, opt_state, prng, *extra), (loss, preds)

        carry = (slab, params, opt_state, prng, *extra)
        carry, (losses, preds) = jax.lax.scan(body, carry, stacked)
        slab, params, opt_state, prng, *extra = carry
        return (slab, params, opt_state, losses, preds, prng, *extra)

    return instrument_jit(
        scan_steps, "scan_steps",
        donate_argnums=(0, 1, 2, *range(5, 5 + extra_carry)))


def run_scan_chunks(scan_call: Callable, items, chunk: int,
                    stack_fn: Callable, carry: Tuple,
                    on_chunk: Callable, timer=None,
                    n_items: Optional[int] = None,
                    prefetch_depth: int = 0,
                    first: Optional[Callable] = None):
    """Drive the megastep over full chunks of `items`, double-buffered:
    chunk i+1 is host-stacked and dispatched BEFORE chunk i's results are
    pulled to host, so H2D staging and metric extraction overlap device
    compute (the MiniBatchGpuPack pinned-async-copy role,
    data_feed.h:519-680 — one chunk of pipelining, bounded memory).

    items: a sequence (a list, or a BatchPlan of BoxDataset.split_batches,
    whose batches are PACKED AT THE PULL), or a bounded iterator (the
    sharded trainer's streamed input) with n_items passed explicitly.
    A chunk's items are pulled under the span ingest_pack, a sibling of
    host_stage and before it, on the thread that stages: the chunk-stager
    when prefetch_depth > 0, so a plan's packing runs one chunk ahead of
    the device and under its steps; the caller's thread otherwise. Exactly
    n_consumed items are pulled either way, so the caller's per-step loop
    may continue from the same iterator (or from items[n_consumed:]).

    scan_call(carry, stacked) -> (carry, losses_dev, preds_dev) dispatches
    one chunk; the carry tuple is opaque to this driver (each trainer
    threads whatever state its scan needs). on_chunk(lo, group, losses_np,
    preds) handles metrics/dump/nan per trainer.

    prefetch_depth > 0 stages up to that many chunks AHEAD on a producer
    thread (the sharded trainer's shard_batches stager role for the
    single-host path): stack_fn then runs concurrently with device
    compute instead of serially between dispatches. stack_fn must be
    safe to call off-thread (the table is read-only during a pass). Peak
    extra memory = prefetch_depth staged chunks. The bounded queue's two
    edges are spans, one each a chunk: chunk_stage_wait on the caller's
    thread (a sibling of scan_dispatch and chunk_drain: the consumer waits
    for the stager) and stage_queue_full on the stager (it waits for the
    consumer: its slack). Which of the two is wide says who sets the pace.

    first(), when given, hands back the first full chunk as (group,
    stacked), staged elsewhere (BoxTrainer's, under the pass before): it
    is taken under chunk_stage_wait on the caller's thread, at any
    prefetch depth, and dispatched first; the chunks staged here start at
    items[chunk], so ``items`` is then a sequence, and n_consumed counts
    the first chunk's items too.
    Returns (carry, losses, n_consumed)."""
    losses_all: List[float] = []
    if n_items is None:
        n_items = len(items)
    # chunk=1 means "megastep off": everything falls to the per-step path
    n_full = (n_items // chunk) * chunk if chunk > 1 else 0
    lo0 = chunk if first is not None and n_full else 0
    it = iter(items[lo0:] if lo0 else items)
    pending = None  # (lo, group, losses_dev, preds_dev)

    def drain(p):
        lo, group, losses_dev, preds_dev = p
        losses_np = np.asarray(losses_dev)      # sync point for chunk i
        losses_all.extend(float(l) for l in losses_np)
        on_chunk(lo, group, losses_np, preds_dev)

    def chunks():
        # the ONE definition of chunk grouping + staging, shared by both
        # paths (a grouping change applied to only one would silently
        # diverge prefetch-on and prefetch-off runs)
        for lo in range(lo0, n_full, chunk):
            if stop is not None and stop.is_set():
                # consumer already exited — bail BEFORE the next stack_fn,
                # not just between queue puts (a long native dedup here
                # would otherwise keep reading the caller's table)
                return
            with obs_span("ingest_pack"):
                group = [next(it) for _ in range(chunk)]
            with obs_span("host_stage"):
                staged = stack_fn(group)
            yield lo, group, staged

    stop = None
    producer = None
    if prefetch_depth > 0 and n_full > lo0:
        import queue as _queue
        import threading as _threading
        q: "_queue.Queue" = _queue.Queue(maxsize=prefetch_depth)
        stop = _threading.Event()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except _queue.Full:
                    continue
            return False

        def produce():
            try:
                for item in chunks():
                    # the stager is a chunk ahead and blocked: its slack
                    with obs_span("stage_queue_full"):
                        put = _put(item)
                    if not put:
                        return
            except BaseException as e:   # surfaced at the consumer's get
                _put(e)

        # the stager's ingest_pack and host_stage spans carry the pass it
        # works for
        producer = _threading.Thread(target=with_current_trace(produce),
                                     daemon=True, name="chunk-stager")
        producer.start()

        def staged_chunks():
            for _ in range(lo0, n_full, chunk):
                # nothing to dispatch: a device that has drained idles here
                with obs_span("chunk_stage_wait"):
                    item = q.get()
                if isinstance(item, BaseException):
                    raise item
                yield item
        source = staged_chunks()
    else:
        source = chunks()
    if lo0:
        def with_first(rest):
            with obs_span("chunk_stage_wait"):
                group, stacked = first()
            yield 0, group, stacked
            yield from rest
        source = with_first(source)

    try:
        for lo, group, stacked in source:
            if timer is not None:
                timer.start()
            with obs_span("scan_dispatch"):
                carry, losses, preds = scan_call(carry, stacked)
            if timer is not None:
                timer.pause()
            obs_beat("scan_chunk")
            if pending is not None:
                with obs_span("chunk_drain"):
                    drain(pending)
            pending = (lo, group, losses, preds)
        if pending is not None:
            with obs_span("chunk_drain"):
                drain(pending)
    finally:
        if stop is not None:
            # consumer exit (normal or raising): stop the stager so it
            # cannot keep reading the table into the caller's NEXT pass
            # (the zombie-stager race shard_batches guards the same way),
            # then unblock and join it. A stager mid-stack_fn finishes
            # that one item, sees the stop flag, and exits — so keep
            # draining + joining until it does; if it outlives a long
            # grace (wedged native call), returning would hand the caller
            # a live thread racing end_pass(), so raise instead.
            stop.set()
            deadline = time.monotonic() + 60.0
            while producer.is_alive():
                try:
                    while True:
                        q.get_nowait()
                except _queue.Empty:
                    pass
                producer.join(timeout=1.0)
                if producer.is_alive() and time.monotonic() > deadline:
                    import sys as _sys
                    if _sys.exc_info()[1] is not None:
                        # an exception is already propagating (e.g. the
                        # nan guard) — don't replace the root cause, just
                        # record the zombie stager and let it through
                        import logging
                        logging.getLogger("paddlebox_tpu").error(
                            "chunk-stager thread failed to stop within "
                            "60s while unwinding %r — it may still be "
                            "reading the pass table",
                            _sys.exc_info()[1])
                        break
                    raise RuntimeError(
                        "chunk-stager thread failed to stop within 60s — "
                        "it may still be reading the pass table; not "
                        "returning control with a live stager")
    return carry, losses_all, n_full


def check_expand_config(model, layout: ValueLayout, use_expand: bool) -> None:
    """Both directions of the expand contract fail LOUDLY at build time —
    a mismatch otherwise surfaces as an opaque broadcast/dot shape error
    deep inside the first jitted step."""
    if use_expand:
        if not layout.expand_dim:
            raise ValueError(
                "model pulls the expand embedding but the table has "
                "expand_embed_dim == 0 (set TableConfig.expand_embed_dim)")
        mdim = getattr(model, "expand_dim", layout.expand_dim)
        if mdim != layout.expand_dim:
            raise ValueError(
                f"model.expand_dim={mdim} != "
                f"TableConfig.expand_embed_dim={layout.expand_dim}")
    elif layout.expand_dim:
        raise ValueError(
            "table has expand_embed_dim="
            f"{layout.expand_dim} but the model does not consume the "
            "expand embedding (use an use_expand model, e.g. "
            "CtrDnnExpand, or set expand_embed_dim=0)")


def resolve_push_write(capacity: Optional[int] = None,
                       batch_keys: Optional[int] = None) -> str:
    """'scatter' | 'rebuild' from the push_write flag.

    * rebuild — full slab gather/select driven by a pos map; cost ~ slab
      bytes. 'auto' selects it on the TPU where capacity <= 16 x
      batch_keys: the sequence towers' cells (16k-32k slab rows against
      8k-16k keys a batch) run it on the chip.
    * scatter — donated in-step row scatter; cost ~ touched rows. 'auto'
      selects it beyond that regime, and ALWAYS off the TPU:
      deepfm-criteo's cells (67.1M rows against 79,872 keys) run it.

    The 16x crossover itself was never measured against the other write
    at either shape. 'log' and 'blocked' were deleted (no cell selected
    either); naming one is an error.
    """
    from paddlebox_tpu.config import flags
    mode = flags.get_flag("push_write")
    if mode == "auto":
        if jax.default_backend() != "tpu":
            return "scatter"
        if capacity and batch_keys and capacity > 16 * batch_keys:
            return "scatter"
        return "rebuild"
    if mode not in ("scatter", "rebuild"):
        hint = (f" — '{mode}' was deleted" if mode in ("log", "blocked")
                else "")
        raise ValueError(f"push_write flag: unknown mode {mode!r}{hint}")
    return mode


def resolve_push_write_sharded(shard_cap: int, num_shards: int,
                               bucket_cap: int,
                               multiprocess: bool) -> str:
    """ONE shard-regime policy for every sharded runner (trainer +
    pipeline): per-shard slab rows vs the padded incoming a2a key budget
    (num_shards buckets of bucket_cap land on every shard). Multi-process
    runs the same policy since round 5: the per-step bucket exchange
    (sharded_table.exchange_outgoing_buckets) makes every shard's
    incoming ids host-known cluster-wide, so host dedup + rebuild pos
    maps stage identically to single-process."""
    del multiprocess  # kept in the signature for call-site clarity
    return resolve_push_write(capacity=shard_cap,
                              batch_keys=num_shards * bucket_cap)


def make_dense_optimizer(cfg: TrainerConfig) -> optax.GradientTransformation:
    if cfg.dense_optimizer == "adam":
        opt = optax.adam(cfg.dense_lr)
    elif cfg.dense_optimizer == "sgd":
        opt = optax.sgd(cfg.dense_lr)
    elif cfg.dense_optimizer == "adagrad":
        opt = optax.adagrad(cfg.dense_lr)
    else:
        raise ValueError(cfg.dense_optimizer)
    from paddlebox_tpu.config import flags
    if flags.get_flag("flatten_dense_opt"):
        # one fused update over the concatenated parameter vector instead of
        # an op chain per parameter tensor — identical numbers (these
        # optimizers are elementwise), fewer dispatches
        opt = _flatten_small(opt)
    return opt


# dense parameters past which the update is left per tensor: optax.flatten
# concatenates every gradient and splits every update, a second copy of
# both, which pays where the dense side is many small tensors and costs a
# sequence tower of 746M parameters 6 GB of a step's memory
FLATTEN_DENSE_MAX = 1 << 24


def _flatten_small(opt: optax.GradientTransformation
                   ) -> optax.GradientTransformation:
    """optax.flatten(opt) for a tree of at most FLATTEN_DENSE_MAX values,
    ``opt`` itself for a larger one: picked from the tree's shapes, so the
    same tree always gets the same state."""
    flat = optax.flatten(opt)

    def pick(tree):
        n = sum(int(np.prod(np.shape(leaf))) for leaf in jax.tree.leaves(tree))
        return flat if n <= FLATTEN_DENSE_MAX else opt

    def update(updates, state, params=None):
        return pick(updates).update(updates, state, params)
    return optax.GradientTransformation(
        lambda params: pick(params).init(params), update)


def _multi_task_loss(logits, labels_dict, ins_valid, loss_mode: str = "sum"):
    """Masked mean BCE over tasks.

    loss_mode="sum": independent per-task BCE (MMoE-style).
    loss_mode="esmm": entire-space loss — BCE(click, pCTR) +
        BCE(conversion, pCTCVR) with pCTCVR = pCTR·pCVR, so the cvr tower
        trains over all impressions; labels_cvr carries the conversion/pay
        label (defaults to click when the data has no second label)."""
    denom = jnp.maximum(ins_valid.sum(), 1.0)
    preds = {t: jax.nn.sigmoid(lg) for t, lg in logits.items()}
    if loss_mode == "esmm":
        pctr = preds["ctr"]
        pctcvr = jnp.clip(pctr * preds["cvr"], 1e-7, 1.0 - 1e-7)
        click = labels_dict["ctr"].astype(jnp.float32)
        conv = labels_dict["cvr"].astype(jnp.float32)
        bce_ctr = optax.sigmoid_binary_cross_entropy(logits["ctr"], click)
        bce_ctcvr = -(conv * jnp.log(pctcvr)
                      + (1.0 - conv) * jnp.log1p(-pctcvr))
        total = (jnp.where(ins_valid, bce_ctr + bce_ctcvr, 0.0).sum() / denom)
        preds = dict(preds, ctcvr=pctcvr)
        return total, preds
    total = 0.0
    for task, lg in logits.items():
        lab = labels_dict[task].astype(jnp.float32)
        bce = optax.sigmoid_binary_cross_entropy(lg, lab)
        total = total + jnp.where(ins_valid, bce, 0.0).sum() / denom
    return total, preds


def dn_update_params(model, params, emb, segments, valid, batch_size: int,
                     num_slots: int, use_cvm: bool, dense) -> Dict:
    """The ONE data_norm summary update used by every trainer: recompute the
    pooled features exactly as the forward does (XLA CSEs the duplicate) and
    apply the model's running-sums rule. Keeping this in one place means the
    stats can never normalize against a different pooled assembly than the
    forward used."""
    pooled = fused_seqpool_cvm(emb, segments, valid, batch_size, num_slots,
                               use_cvm=use_cvm, sorted_segments=True)
    return model.update_summary(params, pooled, dense)


def _flat_summary_mask(params) -> Optional[np.ndarray]:
    """Flat bool mask marking data_norm summary leaves in the raveled param
    vector (AsyncDenseTable applies raw running-sum deltas there instead of
    adam); None when the model has no summary state."""
    if not (isinstance(params, dict) and "dn_summary" in params):
        return None
    marked = {k: jax.tree.map(
        lambda x, _k=k: jnp.full(jnp.shape(x),
                                 1.0 if _k == "dn_summary" else 0.0), v)
        for k, v in params.items()}
    flat = jax.flatten_util.ravel_pytree(marked)[0]
    return np.asarray(flat) > 0.5


def model_accepts_rank_offset(model) -> bool:
    """Join-phase models take the pv rank matrix as a keyword arg."""
    import inspect
    try:
        return "rank_offset" in inspect.signature(model.apply).parameters
    except (TypeError, ValueError):
        return False


def resolve_compute_dtype(name: str, field: str = "compute_dtype"
                          ) -> jnp.dtype:
    """Validated compute/wire dtype: f32 or bf16 only — the
    no-loss-scaling mixed-precision contract relies on bf16's f32-sized
    exponent range (f16 would need loss scaling this path doesn't
    implement). `field` names the config field in the error."""
    d = jnp.dtype(name)
    if d not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"{field} must be float32 or bfloat16, got {name!r}")
    return d


def cast_for_compute(tree, dtype, preserve=("dn_summary",)):
    """Mixed precision: float leaves → compute dtype (grads flow back
    through the cast to the f32 master copies). Top-level subtrees named in
    ``preserve`` stay f32 — data_norm summary stats (magnitudes ~1e4) must
    normalize at full precision, which an 8-bit-mantissa cast would defeat."""
    def _cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    if isinstance(tree, dict) and any(k in tree for k in preserve):
        return {k: (v if k in preserve else jax.tree.map(_cast, v))
                for k, v in tree.items()}
    return jax.tree.map(_cast, tree)


def strong_typed(tree):
    """``tree`` with no weakly typed leaf. A step's outputs are strongly
    typed, so a weight that came weakly typed (``jnp.where(c, -1.0, 1.0)``,
    ``jnp.ones(n) * 2``; an optimizer's zeros_like of one) would compile
    the step once for the caller's arrays and once more for its own
    outputs. Strongly typed leaves pass through untouched, uncopied."""
    return jax.tree.map(
        lambda a: (jax.lax.convert_element_type(a, a.dtype)
                   if getattr(a, "weak_type", False) else a), tree)


def apply_mixed_precision(params, pooled, dense_in, cdtype, f32_params=()):
    """The one casting contract both trainers share: inputs+params to the
    compute dtype (logits are cast back by mixed_logits_to_f32). A model's
    ``f32_params`` (top-level leaves it computes with in float32: norms, a
    router) stay uncast, as the data_norm summary does."""
    pooled = pooled.astype(cdtype)
    params = cast_for_compute(params, cdtype,
                              preserve=("dn_summary", *f32_params))
    if dense_in is not None:
        dense_in = dense_in.astype(cdtype)
    return params, pooled, dense_in


def mixed_logits_to_f32(logits):
    return jax.tree.map(lambda x: x.astype(jnp.float32), logits)


def make_train_step(model, layout: ValueLayout, table: TableConfig,
                    dense_opt: optax.GradientTransformation,
                    batch_size: int, num_slots: int,
                    use_cvm: bool = True,
                    async_dense: bool = False,
                    compute_dtype: str = "float32",
                    uid_write: str = "scatter") -> TrainStepFns:
    # uid_write: accepted and ignored; the push's write follows the batch
    # (a push_pos leaf selects rebuild, its absence the scatter)
    del uid_write
    conf = table.optimizer
    multi_task = len(getattr(model, "task_names", ("ctr",))) > 1
    wants_rank_offset = model_accepts_rank_offset(model)
    cdtype = resolve_compute_dtype(compute_dtype)
    mixed = cdtype != jnp.float32
    padding_id = table.pass_capacity - 1
    # NN-cross models (use_expand contract, models/nn_cross.py): dual-output
    # extended pull + expand-grad push (pull_box_extended_sparse_op.cc;
    # user API contrib/layers/nn.py:1678)
    use_expand = bool(getattr(model, "use_expand", False))
    check_expand_config(model, layout, use_expand)
    # data_norm summary params (boxps_worker.cc:89-95) update by the
    # running-sums rule, not the optimizer (their grads are zero — the model
    # stop_gradients the state in apply)
    has_summary = (getattr(model, "use_data_norm", False)
                   and hasattr(model, "update_summary"))
    if use_expand and has_summary:
        raise ValueError("expand embedding + data_norm summary is not "
                         "supported in one model")
    wants_aux = bool(getattr(model, "use_aux_input", False))
    # counts a model's apply puts into the ``counters`` dict it is handed:
    # a train step hands them back beside its predictions and train_pass
    # adds them to utils/stats at the chunk's drain (_take_step_counters)
    step_counters = tuple(getattr(model, "step_counters", ()))
    f32_params = tuple(getattr(model, "f32_params", ()))

    # per-key slots/valid are DERIVED on device, not transferred: the packer
    # guarantees segments = ins*num_slots + slot and lookup_ids maps every
    # invalid occurrence (and only those) to the trash row — 5 bytes/key less
    # H2D on the input path
    def _key_valid(batch):
        return batch["ids"] != padding_id

    def _key_slots(batch):
        return batch["segments"] % num_slots

    def forward(params, emb, batch, dn_extra):
        expand_emb = None
        if use_expand:
            emb, expand_emb = emb
        # packer/columnar batches carry nondecreasing segments by contract
        pooled = fused_seqpool_cvm(
            emb, batch["segments"], _key_valid(batch), batch_size,
            num_slots, use_cvm=use_cvm, sorted_segments=True)
        dense_in = batch.get("dense")
        if mixed:
            # matmuls ride the MXU in bf16; logits return to f32 for the
            # loss (master params/opt state stay f32 outside)
            params, pooled, dense_in = apply_mixed_precision(
                params, pooled, dense_in, cdtype, f32_params)
        counts = {}
        if use_expand:
            pooled_exp = seqpool_sum(expand_emb, batch["segments"],
                                     _key_valid(batch), batch_size,
                                     num_slots)
            if mixed:
                pooled_exp = pooled_exp.astype(cdtype)
            logits = model.apply(params, pooled, dense_in,
                                 expand=pooled_exp)
        elif wants_rank_offset and "rank_offset" in batch:
            logits = model.apply(params, pooled, dense_in,
                                 rank_offset=batch["rank_offset"])
        elif wants_aux:
            # side-table consumer (lookup_input / pull_cache_value): the
            # model gathers its frozen aux rows by the feed-translated
            # offsets; apply raises loudly if the feed lacks the leaf
            logits = model.apply(params, pooled, dense_in,
                                 aux_offset=batch.get("aux_offset"))
        elif step_counters:
            logits = model.apply(params, pooled, dense_in, counters=counts)
        else:
            logits = model.apply(params, pooled, dense_in)
        if mixed:
            logits = mixed_logits_to_f32(logits)
        ins_valid = batch["ins_valid"]
        if multi_task:
            labels = {t: batch["labels_" + t] for t in model.task_names}
            loss, preds = _multi_task_loss(
                logits, labels, ins_valid,
                getattr(model, "loss_mode", "sum"))
            main_pred = preds[model.task_names[0]]
        else:
            lab = batch["labels"].astype(jnp.float32)
            bce = optax.sigmoid_binary_cross_entropy(logits, lab)
            denom = jnp.maximum(ins_valid.sum(), 1.0)
            loss = jnp.where(ins_valid, bce, 0.0).sum() / denom
            main_pred = jax.nn.sigmoid(logits)
            preds = {"ctr": main_pred}
        return loss, (preds, counts)

    def _pull(state, batch):
        """(emb_view, rows_u): where the wire carries the push's dedup
        (a train batch: uids, occ_uid) the slab is gathered once a
        distinct row and rows_u, the rows of uids, go on to the push;
        else (eval, predict) the occurrence gather and None, as on the
        expand path, which pulls a dual view."""
        if use_expand:
            return pull_sparse_extended(state, batch["ids"], layout), None
        if "occ_uid" in batch:
            return pull_sparse_unique(state, batch["uids"],
                                      batch["occ_uid"], layout)
        return pull_sparse(state, batch["ids"], layout), None

    def _sparse_push(slab, demb, batch, sub, pulled_rows=None):
        # per-key click = its instance's label (first task's label)
        key_label_src = batch["labels_" + model.task_names[0]] if multi_task \
            else batch["labels"]
        with jax.named_scope("push_grads"):
            clicks = key_label_src[batch["segments"] // num_slots]
            if use_expand:
                d_base, d_exp = demb
                push_grads = build_push_grads_extended(
                    d_base, d_exp, _key_slots(batch), clicks,
                    _key_valid(batch))
            else:
                push_grads = build_push_grads(demb, _key_slots(batch),
                                              clicks, _key_valid(batch))
        if "perm" not in batch:
            # never fall back to the on-device jnp.unique sort silently —
            # that is the dominant step cost this path exists to remove
            raise KeyError(
                "train batch lacks host dedup (perm/inv) — host_batch must "
                "run dedup_for_push for train batches")
        # pulled_rows: the rows of uids as the pull gathered them from
        # this same pre-update slab (None: the push gathers its own)
        if "push_pos" in batch:
            return push_sparse_rebuild(slab, batch["uids"],
                                       batch["push_pos"], batch["perm"],
                                       batch["inv"], push_grads, sub,
                                       layout, conf,
                                       pulled_rows=pulled_rows)
        return push_sparse_hostdedup(slab, batch["uids"], batch["perm"],
                                     batch["inv"], push_grads, sub, layout,
                                     conf, pulled_rows=pulled_rows)

    # The slab is DONATED into the step: at production pass capacities the
    # slab is hundreds of MB and the pass holds exactly one live copy, so
    # non-donated steps would double peak HBM. The dense weights and the
    # optimizer's state go with it (a tower of 600M parameters is 7 GB of
    # them). Donation is honored on every backend incl. CPU: the input
    # buffers are DEAD after the call: rebind (set_slab/carry, self.params,
    # self.opt_state) before any further read.
    def _step_impl(slab, params, opt_state, batch, prng):
        # split on device: host-side per-step RNG dispatch costs more than
        # the whole compiled step (2 sync dispatches ≈ 200us)
        prng, sub = jax.random.split(prng)

        def loss_fn(params, emb):
            return forward(params, emb, batch, None)

        emb, rows = _pull(slab, batch)
        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        with jax.named_scope("fwd_bwd"):
            (loss, (preds, counts)), (dparams, demb) = grad_fn(params, emb)
        with jax.named_scope("dense_opt"):
            updates, opt_state = dense_opt.update(dparams, opt_state, params)
            params = optax.apply_updates(params, updates)
            if has_summary:
                params = dn_update_params(
                    model, params, emb, batch["segments"], _key_valid(batch),
                    batch_size, num_slots, use_cvm, batch.get("dense"))
        slab = _sparse_push(slab, demb, batch, sub, rows)
        return slab, params, opt_state, loss, {**preds, **counts}, prng

    step = instrument_jit(_step_impl, "train_step",
                          donate_argnums=(0, 1, 2),
                          example_count=batch_size)
    scan_steps = make_scan(_step_impl)

    def step_async(slab, params, batch, prng):
        """Async-dense variant: dense grads come back flat for the host
        table; only the sparse push happens on device
        (boxps_worker.cc:1278-1296 pull/push around the op loop)."""
        prng, sub = jax.random.split(prng)

        def loss_fn(params, emb):
            return forward(params, emb, batch, None)

        emb, rows = _pull(slab, batch)
        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        with jax.named_scope("fwd_bwd"):
            (loss, (preds, _)), (dparams, demb) = grad_fn(params, emb)
        if has_summary:
            # the host adam thread sees zero grads for the summary leaves;
            # their running-sums update happens here on device and rides
            # back to the host table through the flat grad vector as a
            # DELTA the summary mask applies raw: params += grad
            # (async_dense.py:119-122)
            new_params = dn_update_params(
                model, params, emb, batch["segments"], _key_valid(batch),
                batch_size, num_slots, use_cvm, batch.get("dense"))
            dparams = dict(dparams, dn_summary=jax.tree.map(
                lambda old, new: new - old,
                params["dn_summary"], new_params["dn_summary"]))
        flat_g = jax.flatten_util.ravel_pytree(dparams)[0]
        slab = _sparse_push(slab, demb, batch, sub, rows)
        return slab, flat_g, loss, preds, prng

    step_async = instrument_jit(step_async, "train_step_async",
                                donate_argnums=(0,),
                                example_count=batch_size)

    def eval_step(slab, params, batch):
        emb, _ = _pull(slab, batch)
        _, (preds, _) = forward(params, emb, batch, None)
        return preds

    eval_step = instrument_jit(eval_step, "eval_step",
                               example_count=batch_size)

    return TrainStepFns(step=step_async if async_dense else step,
                        eval_step=eval_step,
                        batch_size=batch_size, num_slots=num_slots,
                        scan_steps=None if async_dense else scan_steps)


class StagedAhead:
    """A pass's shuffle, split and first scan chunk, made under the pass
    before it (BoxTrainer.stage_ahead). ``seed`` is the pass's shuffle
    seed, drawn when this is made, on the thread that drives the passes
    and in their order. Once the pass's feed plan is finished, run(plan)
    (preload.FeedAhead, on the feed-ahead thread) shuffles and splits the
    dataset, then packs the first ``scan_chunk`` batches and stages them
    as any chunk is staged, but looked up in the plan's map
    (PassTable.lookup_in, which marks nothing) and kept on the host: the
    consuming pass makes the device copy, so that no chunk of the next
    pass lives on the device under this one's steps and boundary. skip()
    settles one that no plan came for. The consuming train_pass waits on
    ``split`` for the batches and on ``done`` for the chunk; an error
    raised here is raised there."""

    def __init__(self, trainer: "BoxTrainer", dataset: BoxDataset,
                 seed: int) -> None:
        self._trainer = trainer
        self.dataset = dataset
        self.seed = seed
        self.rows = None         # the plan's RowMap, where a plan came
        self.push_write: Optional[str] = None
        self.batches = None      # the pass's BatchPlan
        self.group: Optional[List[PackedBatch]] = None
        self.host: Optional[Dict[str, np.ndarray]] = None
        self.err: Optional[BaseException] = None
        self.split = threading.Event()
        self.done = threading.Event()

    def run(self, plan) -> None:
        tr = self._trainer
        try:
            try:
                self.rows, self.push_write = plan.rows, tr._push_write
                self.dataset.local_shuffle(self.seed)
                self.batches = self.dataset.split_batches(num_workers=1)[0]
            finally:
                self.split.set()
            chunk = tr._scan_chunk()
            if chunk and len(self.batches) >= chunk:
                pool = tr._host_pool()
                with obs_span("ingest_pack"):
                    # the native pack releases the GIL: this chunk's
                    # batches pack on the staging pool, as they are then
                    # looked up, since its stage is on the critical path
                    # from the plan to the pass's first dispatch
                    pull = self.batches.__getitem__
                    group = (list(pool.map(pull, range(chunk)))
                             if pool is not None
                             else [pull(i) for i in range(chunk)])
                with obs_span("host_stage"):
                    self.host = tr._stack_batches_host(
                        group, functools.partial(tr.table.lookup_in, plan))
                self.group = group
        except BaseException as e:  # raised by the consuming train_pass
            self.err = e
        finally:
            self.done.set()

    def skip(self) -> None:
        self.split.set()
        self.done.set()


class BoxTrainer:
    """Single-host trainer over one PassTable + model. The sharded multi-chip
    variant lives in parallel/ (same pass cadence, pjit-compiled step)."""

    def __init__(self, model, table_cfg: TableConfig, feed: DataFeedConfig,
                 trainer_cfg: Optional[TrainerConfig] = None,
                 seed: int = 0, use_cvm: bool = True,
                 aux_source=None) -> None:
        """aux_source: a ReplicaCache or InputTable whose frozen rows an
        aux-consuming model (use_aux_input, e.g. CtrDnnAux) gathers on
        device — refreshed into params['aux_rows'] at every pass start at
        the model's fixed aux_capacity (static shapes, no recompile)."""
        self.model = model
        self.cfg = trainer_cfg or TrainerConfig()
        self.aux_source = aux_source
        if aux_source is not None and not getattr(model, "use_aux_input",
                                                  False):
            raise ValueError("aux_source given but the model does not "
                             "consume aux rows (use_aux_input)")
        if self.cfg.sync_mode in ("k_step", "sharding") or self.cfg.sharding:
            raise ValueError(
                "sync_mode=%r / sharding=%r need the multi-device "
                "ShardedBoxTrainer" % (self.cfg.sync_mode, self.cfg.sharding))
        self.feed = feed
        self.table = PassTable(table_cfg, seed=seed)
        self.metrics = MetricRegistry()
        # tagged quality plane (round 18, flag quality_metrics): per-tag
        # masked AUC / COPC / actual-vs-predicted CTR streamed from the
        # same host tensors _add_metrics builds; None when flagged off
        from paddlebox_tpu.metrics import quality as _quality
        self.quality = _quality.make_from_flags()
        self.async_mode = (self.cfg.async_mode
                           or self.cfg.sync_mode == "async")
        # resolved once here and refreshed at pass start — never per batch,
        # so one scan chunk can't mix rebuild and scatter host dicts (and an
        # invalid flag value fails at construction, not in a staging thread)
        self._push_write = resolve_push_write(
            capacity=table_cfg.pass_capacity,
            batch_keys=feed.key_capacity())
        self.dense_opt = make_dense_optimizer(self.cfg)
        rng = jax.random.PRNGKey(seed)
        self.params = model.init(rng)
        # made at its first read (the opt_state property): a caller that
        # installs its own weights and a fresh state for them (a benchmark,
        # a restore) never holds two states of a large tower at once
        self._opt_state = None
        self.num_slots = len(feed.used_sparse_slots())
        self.fns = make_train_step(
            model, self.table.layout, table_cfg, self.dense_opt,
            feed.batch_size, self.num_slots, use_cvm,
            async_dense=self.async_mode,
            compute_dtype=self.cfg.compute_dtype)
        self.async_table = None
        self._unravel = None
        if self.async_mode:
            if self.cfg.dense_optimizer != "adam":
                raise ValueError(
                    "async dense table implements adam only; got "
                    + self.cfg.dense_optimizer)
            from paddlebox_tpu.train.async_dense import AsyncDenseTable
            flat, self._unravel = jax.flatten_util.ravel_pytree(self.params)
            self.async_table = AsyncDenseTable(
                np.asarray(flat), lr=self.cfg.dense_lr,
                summary_mask=_flat_summary_mask(self.params))
        self.timers = {n: Timer() for n in ("step", "pass")}
        # telemetry plane (round 10): flag-configured StepReporter +
        # tracer sync + (flag-gated) stall watchdog — one line per runner
        self.reporter = make_step_reporter(timers=self.timers)
        # device plane (round 20): HBM-ledger owners, weakref'd so
        # registration never extends the trainer's lifetime (the ledger
        # must not CAUSE the leaks it detects)
        import weakref
        _w = weakref.ref(self)
        register_owner("slab", lambda: getattr(
            getattr(_w(), "table", None), "_slab", None))
        register_owner("dense_params", lambda: getattr(_w(), "params", None))
        register_owner("opt_state", lambda: getattr(_w(), "opt_state", None))
        # two stagers may run at once (a pass's chunk-stager and the next
        # pass's StagedAhead): the pool and the mark are theirs in turn
        self._stage_lock = threading.RLock()
        self._stage_pool = None  # guarded-by: _stage_lock
        # largest unique-row domain staged so far, by a batch's occurrence
        # count K: never shrinks, so the step compiles once a bucket
        # (_trim_push_domain)
        self._push_domain_mark: Dict[int, int] = {}  # guarded-by: _stage_lock
        self._step_count = 0
        self._shuffle_rng = np.random.RandomState(seed + 1)
        self.multi_task = len(getattr(model, "task_names", ("ctr",))) > 1
        self.dump_writer = None
        if self.cfg.dump_fields and self.cfg.dump_fields_path:
            from paddlebox_tpu.train.dump import DumpWriter
            self.dump_writer = DumpWriter(self.cfg.dump_fields_path,
                                          self.cfg.dump_thread_num)

    def _dump_batch(self, preds: Dict[str, jnp.ndarray],
                    b: PackedBatch) -> None:
        """DumpField per batch: one line per real instance with the
        requested fields (boxps_worker.cc DumpField)."""
        from paddlebox_tpu.train.dump import build_dump_tensors
        main = (self.model.task_names[0] if self.multi_task
                else list(preds)[0])
        tensors = build_dump_tensors(self.cfg.dump_fields, b.labels, preds,
                                     main)
        if tensors:
            self.dump_writer.dump_batch(tensors, ins_ids=b.ins_ids,
                                        mask=b.ins_valid)

    def close(self) -> None:
        """Stop the async dense optimizer thread, staging pool and dump
        writers."""
        if self.async_table is not None:
            self.async_table.stop()
            self.async_table = None
        if self.dump_writer is not None:
            self.dump_writer.close()
            self.dump_writer = None
        with self._stage_lock:
            if self._stage_pool and self._stage_pool[1] is not None:
                self._stage_pool[1].shutdown(wait=False)
            self._stage_pool = None
        if getattr(self, "reporter", None) is not None:
            self.reporter.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails;
            # close() is the loud path, this is the last-resort guard
            pass

    # ---------------------------------------------------------- batch utils
    def _host_pool(self):
        """Thread pool for per-batch host staging (lookup + dedup): the
        native rt_lookup/rt_dedup calls and numpy ops release the GIL, so
        batches of a chunk stage in parallel — the 30-feed-thread role of
        the reference (box_wrapper.h:862). Sized by the stack_threads flag,
        re-read on every chunk so a live set_flag takes effect; <=1 runs
        serial."""
        from paddlebox_tpu.config import flags
        n = int(flags.get_flag("stack_threads"))
        with self._stage_lock:
            cur_n, pool = self._stage_pool or (0, None)
            if n != cur_n:
                if pool is not None:
                    pool.shutdown(wait=False)
                if n > 1:
                    from concurrent.futures import ThreadPoolExecutor
                    pool = ThreadPoolExecutor(
                        n, thread_name_prefix="pbtpu-stage")
                else:
                    pool = None
                self._stage_pool = (n, pool)
        return pool

    def _stage_one(self, b: PackedBatch, lookup: Optional[Callable] = None
                   ) -> Tuple[Dict[str, np.ndarray], Optional[int]]:
        lookup = self.table.lookup_ids if lookup is None else lookup
        return self._host_batch(b, lookup(b.keys, b.valid))

    def _trim_push_domain(self, hosts: List[Dict[str, np.ndarray]],
                          n_us: List[Optional[int]]) -> None:
        """Cut the per-unique-row leaf (uids) of staged train dicts to ONE
        static domain U (pass_table.push_domain of the largest real count
        among them): uids[:U] holds every real uid, so the pull gathers
        and the push merges, updates and writes the same rows to the same
        bits over U index slots instead of one an occurrence. perm, inv,
        occ_uid and ids are per occurrence and keep their [K] (every
        occ_uid value is below its dict's real count, so below U); the
        rebuild map names real uids only and is the same either way. The
        mark makes every dict of a trainer's life agree on U (tail
        batches too) until a batch outgrows the bucket."""
        if not hosts:
            return
        if n_us[0] is None:
            # eval batches carry no push leaves: an occurrence gather
            stat_add("pull_index_slots",
                     hosts[0]["ids"].shape[0] * len(hosts))
            return
        K = hosts[0]["uids"].shape[0]
        with self._stage_lock:
            U = push_domain(max(n_us), K, self._push_domain_mark.get(K, 0))
            self._push_domain_mark[K] = U
        stat_add("push_index_slots", U * len(hosts))
        stat_add("push_unique_rows", sum(n_us))
        stat_add("pull_index_slots",
                 (U if "occ_uid" in hosts[0] else K) * len(hosts))
        for h in hosts:
            h["uids"] = h["uids"][:U]

    def _stack_batches_host(self, group: List[PackedBatch],
                            lookup: Optional[Callable] = None
                            ) -> Dict[str, np.ndarray]:
        """Stack a chunk of packed batches on a leading scan axis as HOST
        arrays (the device conversion is _stack_batches). ``lookup``
        replaces table.lookup_ids (StagedAhead: the next pass's plan)."""
        pool = self._host_pool()
        stage = (self._stage_one if lookup is None
                 else functools.partial(self._stage_one, lookup=lookup))
        if pool is not None and len(group) > 1:
            staged = list(pool.map(stage, group))
        else:
            staged = [stage(b) for b in group]
        hosts = [h for h, _ in staged]
        self._trim_push_domain(hosts, [n for _, n in staged])
        return {k: np.stack([h[k] for h in hosts]) for k in hosts[0]}

    @staticmethod
    def _to_device(staged: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        account_h2d(tree_nbytes(staged))  # device transfer ledger
        return {k: jnp.asarray(v) for k, v in staged.items()}

    def _stack_batches(self, group: List[PackedBatch]
                       ) -> Dict[str, jnp.ndarray]:
        """Host-stack + one H2D per leaf."""
        return self._to_device(self._stack_batches_host(group))

    def host_batch(self, b: PackedBatch,
                   ids: np.ndarray) -> Dict[str, np.ndarray]:
        """One batch's host dict as the one-step program takes it (tail
        batches, eval): the push's unique-row domain cut like a chunk's."""
        out, n_u = self._host_batch(b, ids)
        self._trim_push_domain([out], [n_u])
        return out

    def _host_batch(self, b: PackedBatch, ids: np.ndarray
                    ) -> Tuple[Dict[str, np.ndarray], Optional[int]]:
        """The ONE producer of a batch's wire: (host dict, the push
        dedup's real unique count; None for an eval batch, which carries
        no push leaves). A train batch ships ids, segments, ins_valid,
        labels [, dense, rank_offset, aux_offset, labels_<task>] plus the
        host dedup: uids, perm[K], inv[K] for _sparse_push
        [, push_pos[capacity]] and, unless the model pulls the expand
        view, occ_uid[K], each occurrence's slot in uids, by which _pull
        gathers the slab once a uid. uids is still [K] here: the caller
        cuts it (_trim_push_domain)."""
        # per-key slots/valid are derived on device (make_train_step).
        # Touched-row accounting for the incremental EndPass happens in
        # table.lookup_ids (the `ids` passed here already marked the pass
        # bitmap), or, for a chunk staged ahead against a plan, where the
        # pass that runs the plan takes it (_first_ahead).
        out = {
            "ids": ids,
            "segments": b.segments,
            "ins_valid": b.ins_valid,
            "labels": b.labels,
        }
        n_u = None
        if not self.table.test_mode:
            # train batches carry the host-precomputed push dedup (uids
            # included: rebuilding them on device is a scatter); eval
            # batches never push, so skip the dedup + extra transfers
            uids, perm, inv, n_u = self.table.dedup_for_push(ids)
            out.update(perm=perm, inv=inv, uids=uids)
            if not getattr(self.model, "use_expand", False):
                # the expand path pulls a dual view by occurrence and
                # never consumes it, so don't compute/transfer it there
                out["occ_uid"] = occurrence_uid_slots(perm, inv)
            if self._push_write == "rebuild":
                # the largest transfer: it buys removing the slab scatter
                # from the step
                out["push_pos"] = self.table.pos_for_rebuild(uids)
        if b.dense is not None:
            out["dense"] = b.dense
        if b.rank_offset is not None:
            out["rank_offset"] = b.rank_offset
        if b.aux_offset is not None:
            out["aux_offset"] = b.aux_offset
        if self.multi_task:
            # per-task labels from the packer (task_label_slots config);
            # tasks without a packed label train on the click label
            packed = b.task_labels or {}
            for t in self.model.task_names:
                out["labels_" + t] = packed.get(t, b.labels)
        return out, n_u

    def device_batch(self, b: PackedBatch,
                     ids: np.ndarray) -> Dict[str, jnp.ndarray]:
        return self._to_device(self.host_batch(b, ids))

    def _refresh_aux(self) -> None:
        """ToHBM cadence (box_wrapper.h:83): freeze the side table's
        current rows into the non-trained aux_rows leaf — shared by ALL
        pass drivers (train_pass, predict_batches)
        so none runs on stale or init-zero rows."""
        if self.aux_source is not None:
            self.params = dict(self.params, aux_rows=self.aux_source
                               .to_device(self.model.aux_capacity))

    # ---------------------------------------------------------- pass cadence
    def _scan_chunk(self) -> int:
        """Batches a scan dispatch takes; 0 where the megastep is off."""
        chunk = max(1, self.cfg.scan_chunk)
        return chunk if self.fns.scan_steps is not None and chunk > 1 else 0

    def stage_ahead(self, dataset: BoxDataset) -> StagedAhead:
        """The StagedAhead of a pass about to be preloaded: its shuffle
        seed is drawn now (run_preloaded_passes asks at each preload, so in
        pass order); the preloader runs it once the pass's plan is done,
        and train_pass(dataset, ahead=...) consumes it."""
        return StagedAhead(self, dataset, self._shuffle_rng.randint(1 << 31))

    def _split_ahead(self, dataset: BoxDataset, ahead: StagedAhead):
        """The pass's batches from its StagedAhead: shuffled and split
        there, or here with its seed where no plan came for it."""
        if ahead.dataset is not dataset:
            raise ValueError("train_pass given another dataset's StagedAhead")
        ahead.split.wait()
        if ahead.batches is not None:
            return ahead.batches
        if ahead.err is not None:
            raise ahead.err
        dataset.local_shuffle(ahead.seed)
        return dataset.split_batches(num_workers=1)[0]

    def _first_ahead(self, ahead: StagedAhead) -> Optional[Callable]:
        """What run_scan_chunks takes the pass's first chunk from, where it
        was staged ahead against the map this pass runs (not one redone on
        the boundary), with this pass's push write, out of test mode; else
        None, and the chunk is staged as any other (a staged one dropped:
        counter stage_ahead_dropped)."""
        if ahead.rows is None:
            return None
        if not (self.table.runs(ahead.rows) and not self.table.test_mode
                and ahead.push_write == self._push_write):
            ahead.done.wait()    # nothing of it runs beside the pass
            if ahead.host is not None:
                stat_add("stage_ahead_dropped")
            return None

        def take():
            ahead.done.wait()
            if ahead.err is not None:
                raise ahead.err
            host, group = ahead.host, ahead.group
            ahead.host = ahead.group = None
            # looked up in the plan before this pass began: its rows are
            # marked now, so end_pass writes back what it always did
            self.table.note_touched(host["ids"])
            # work done ahead is the consuming pass's
            stat_add("stage_ahead_chunks")
            return group, self._to_device(host)
        return take

    def train_pass(self, dataset: BoxDataset, preloaded: bool = False,
                   ahead: Optional[StagedAhead] = None) -> Dict[str, float]:
        """One full pass: feed → build → train → metrics → end. ``ahead``:
        the pass's StagedAhead (run_preloaded_passes), whose seed, split
        and first chunk it takes."""
        # live set_flag takes effect at pass boundaries only (mid-pass flips
        # would mix rebuild/scatter host dicts inside one scan chunk)
        self._push_write = resolve_push_write(
            capacity=self.table.capacity,
            batch_keys=self.feed.key_capacity())
        with obs_span("train_pass"):
            return self._train_pass(dataset, preloaded, ahead)

    def _train_pass(self, dataset: BoxDataset, preloaded: bool,
                    ahead: Optional[StagedAhead]) -> Dict[str, float]:
        from paddlebox_tpu.config import flags
        t_pass = self.timers["pass"]
        t_pass.start()
        if not preloaded:
            self.table.begin_feed_pass()
            dataset.load_into_memory(add_keys_fn=self.table.add_keys)
            self.table.end_feed_pass()
        self._refresh_aux()
        # a caller may have put weights of its own in the program's place
        self.params, self.opt_state = strong_typed(
            (self.params, self.opt_state))
        self.table.begin_pass()
        with obs_span("pass_split_batches"):
            # the shuffle and the plan of the split: a batch is packed when
            # the stager (or the tail loop below) takes it. Staged ahead,
            # both are done and this waits for them
            if ahead is None:
                dataset.local_shuffle(self._shuffle_rng.randint(1 << 31))
                pending = dataset.split_batches(num_workers=1)[0]
            else:
                pending = self._split_ahead(dataset, ahead)
                ahead.batches = None
        n_batches = len(pending)
        losses = []
        prng = self.table.next_prng()
        chunk = self._scan_chunk()
        state = self.table.slab
        if chunk and len(pending) >= chunk:
            # megastep path: scan whole chunks in one dispatch each; the
            # remainder falls through to the per-step loop below

            def on_chunk(lo, group, chunk_losses, preds):
                self._step_count += len(group)
                obs_beat("step")
                self.reporter.note_examples(
                    len(group) * self.fns.batch_size)
                self.reporter.maybe_report(self._step_count)
                if self.cfg.check_nan_inf and not np.isfinite(
                        chunk_losses).all():
                    raise FloatingPointError(
                        f"nan/inf loss by step {self._step_count}")
                preds = self._take_step_counters(preds)
                # ONE D2H per task per chunk, sliced on host — per-batch
                # device slices would each pay a full transfer round-trip.
                # Skipped entirely when nothing consumes preds.
                if not (self.metrics.metric_names()
                        or self.quality is not None
                        or self.dump_writer is not None):
                    return
                preds_np = {t: np.asarray(p) for t, p in preds.items()}
                for j, b in enumerate(group):
                    preds_j = {t: p[j] for t, p in preds_np.items()}
                    self._add_metrics(preds_j, b)
                    if self.dump_writer is not None:
                        self._dump_batch(preds_j, b)

            def scan_call(carry, stacked):
                slab, params, opt_state, losses, preds, prng = \
                    self.fns.scan_steps(carry[0], carry[1], carry[2],
                                        stacked, carry[3])
                return (slab, params, opt_state, prng), losses, preds

            carry = (state, self.params, self.opt_state, prng)
            carry, chunk_losses, n_done = run_scan_chunks(
                scan_call, pending, chunk, self._stack_batches,
                carry, on_chunk, timer=self.timers["step"],
                prefetch_depth=max(0, int(
                    flags.get_flag("chunk_prefetch_depth"))),
                first=None if ahead is None else self._first_ahead(ahead))
            state, self.params, self.opt_state, prng = carry
            self.table.set_slab(state)
            losses.extend(chunk_losses)
            pending = pending[n_done:]
        # a step's id must not leak onto the boundary or eval spans, also
        # when a step raises: on exit the id found here (the pass's, under
        # run_preloaded_passes) is back for end_pass
        with trace_ctx(current_trace()):
            for i in range(len(pending)):
                # per-step 64-bit trace id (round 14): the pack, host_stage
                # and the dispatch spans of one step share it in the
                # exported trace
                set_trace(step_trace_id(0, self._step_count + 1))
                with obs_span("ingest_pack"):
                    b = pending[i]
                with obs_span("host_stage"):
                    ids = self.table.lookup_ids(b.keys, b.valid)
                    batch = self.device_batch(b, ids)
                self.timers["step"].start()
                if self.async_table is not None:
                    # pull a fresh dense snapshot, run the device step, queue the
                    # grads for the host optimizer thread (PullDense/PushDense
                    # around the op loop, boxps_worker.cc:1278-1296)
                    self.params = self._unravel(jnp.asarray(
                        self.async_table.pull()))
                    slab, flat_g, loss, preds, prng = self.fns.step(
                        self.table.slab, self.params, batch, prng)
                    self.async_table.push(np.asarray(flat_g))  # boxlint: BX931 ok (async dense handoff: the host optimizer thread consumes the gradient, so the D2H is the queue boundary)
                    self.table.set_slab(slab)
                else:
                    (state, self.params, self.opt_state, loss, preds,
                     prng) = self.fns.step(
                        self.table.slab, self.params, self.opt_state, batch,
                        prng)
                    self.table.set_slab(state)
                    preds = self._take_step_counters(preds)
                self.timers["step"].pause()
                self._step_count += 1
                obs_beat("step")
                self.reporter.note_examples(self.fns.batch_size)
                self.reporter.maybe_report(self._step_count)
                if self.cfg.check_nan_inf:
                    # the opt-in guard forces a per-step sync by design:
                    # it must see THIS step's loss before dispatching the
                    # next one
                    losses.append(float(loss))  # boxlint: BX931 ok (check_nan_inf opts into a per-step sync: the guard must observe the loss before the next dispatch)
                    if not np.isfinite(losses[-1]):
                        raise FloatingPointError(
                            f"nan/inf loss at step {self._step_count}")
                else:
                    # device scalar: np.mean at the pass boundary pays
                    # the D2H once
                    losses.append(loss)
                self._add_metrics(preds, b)
                if self.dump_writer is not None:
                    self._dump_batch(preds, b)
        self.table.end_pass()
        if self.async_table is not None:
            # pass boundary is a sync point: drain the host optimizer and
            # refresh the local params for eval/checkpoint
            self.async_table.wait_drained()
            self.params = self._unravel(jnp.asarray(self.async_table.pull()))
        t_pass.pause()
        mean_loss = float(np.mean(losses)) if losses else 0.0
        # pass boundary is always a report boundary: the window closes
        # with the pass stats + the streaming metrics' last computed AUC
        with obs_span("pass_report"):
            extra = {"event": "pass_end", "loss": round(mean_loss, 6),
                     "auc": {m.name: float(m.calculator.auc())
                             for m in self.metrics.messages()}}
            from paddlebox_tpu.metrics.quality import attach_pass_extras
            attach_pass_extras(extra, self.quality)
            self.reporter.maybe_report(self._step_count, force=True,
                                       extra=extra)
        if self.cfg.profile:
            from paddlebox_tpu.utils.profiler import timer_report
            obs_log.info(timer_report(self.timers, prefix="trainer."))
        return {"loss": mean_loss,
                "batches": n_batches,
                "instances": len(dataset)}

    @property
    def opt_state(self):
        """The dense optimizer's state, initialised from ``params`` when
        first read."""
        if self._opt_state is None:
            self._opt_state = self.dense_opt.init(self.params)
        return self._opt_state

    @opt_state.setter
    def opt_state(self, state) -> None:
        self._opt_state = state

    def _take_step_counters(self, preds: Dict[str, jnp.ndarray]
                            ) -> Dict[str, jnp.ndarray]:
        """Add the counts a train step handed back beside its predictions
        (model.step_counters; a chunk's are stacked) to utils/stats and
        return the predictions alone. A D2H a counter: at a chunk's drain
        the host already waits; a tail step of a model that counts waits
        for its own step here (a model without counters pays nothing)."""
        names = getattr(self.model, "step_counters", ())
        for name in names:
            stat_add(name, int(np.asarray(preds[name]).sum()))
        return ({t: p for t, p in preds.items() if t not in names}
                if names else preds)

    def _add_metrics(self, preds: Dict[str, jnp.ndarray],
                     b: PackedBatch) -> None:
        if not (self.metrics.metric_names() or self.quality is not None):
            return
        mask = b.ins_valid
        tensors = {"label": b.labels, "mask": mask}
        if b.cmatch_rank is not None:
            tensors["cmatch_rank"] = b.cmatch_rank
        for task, lab in (b.task_labels or {}).items():
            tensors["label_" + task] = lab
        for task, p in preds.items():
            tensors["pred_" + task] = np.asarray(p)
        # jit returns pytree dicts key-sorted: name the main task, don't
        # take it positionally
        main = (self.model.task_names[0] if self.multi_task
                else list(preds)[0])
        tensors["pred"] = tensors["pred_" + main]
        self.metrics.add_batch(tensors)
        if self.quality is not None:
            self.quality.add_batch(tensors)
            self.quality.add_slot_batch(
                tensors["pred"], b.labels, b.slots, b.segments, b.valid,
                self.num_slots)
            from paddlebox_tpu.metrics import drift as _drift
            _drift.observe_preds(tensors["pred"], mask=mask)

    # ------------------------------------------------------------- eval
    def predict_batches(self, dataset: BoxDataset) -> Tuple[np.ndarray, np.ndarray]:
        """Test-mode inference over a loaded dataset (SetTestMode pulls)."""
        self.table.set_test_mode(True)
        self.table.begin_feed_pass()
        self.table.add_keys(dataset.all_keys())
        self.table.end_feed_pass()
        self._refresh_aux()
        self.table.begin_pass()
        preds_all, labels_all = [], []
        for b in dataset.split_batches(num_workers=1)[0]:
            ids = self.table.lookup_ids(b.keys, b.valid)
            batch = self.device_batch(b, ids)
            preds = self.fns.eval_step(self.table.slab, self.params, batch)
            key = (self.model.task_names[0] if self.multi_task
                   else list(preds)[0])
            main = np.asarray(preds[key])  # boxlint: BX931 ok (predict returns host preds; per-batch D2H bounds device memory over the pass)
            preds_all.append(main[b.ins_valid])
            labels_all.append(b.labels[b.ins_valid])
        self.table.end_pass()
        self.table.set_test_mode(False)
        if not preds_all:
            return np.empty(0, np.float32), np.empty(0, np.int32)
        return np.concatenate(preds_all), np.concatenate(labels_all)
