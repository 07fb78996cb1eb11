"""Multi-chip trainer: ONE shard_map'd step fusing the whole BoxPS hot loop.

The device program per step (the TPU re-design of BoxPSWorker::TrainFiles +
HeterComm pull/push + NCCL dense allreduce):

    a2a(id buckets)        ← walk_to_dest (heter_comm_inl.h:273)
    local slab gather      ← HashTable::get
    a2a(values)            ← walk_to_src (inl:1296-1445)
    restore → seqpool+CVM → model fwd/bwd (MXU)
    psum(dense grads)      ← c_allreduce_sum / SyncParam NCCL
    optax dense update (replicated, deterministic)
    scatter grads → a2a    ← push walk_to_dest
    local dedup + in-table optimizer ← HashTable::update(sgd)

Batches are data-parallel over the same 1D axis that shards the table
(BoxPS's one-worker-per-GPU + key-mod-sharding topology). All shapes are
static; XLA overlaps the collectives with dense compute.
"""

from __future__ import annotations

import functools
import queue
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.config.configs import (DataFeedConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data.dataset import BoxDataset
from paddlebox_tpu.data.packer import PackedBatch
from paddlebox_tpu.embedding.optimizers import (push_sparse_dedup,
                                                push_sparse_hostdedup,
                                                push_sparse_rebuild,
                                                push_sparse_uidwire)
from paddlebox_tpu.embedding.pass_table import dedup_ids
from paddlebox_tpu.metrics.auc import MetricRegistry
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.obs import beat as obs_beat
from paddlebox_tpu.obs import log as obs_log
from paddlebox_tpu.obs import (make_cluster_aggregator, make_step_reporter,
                               obs_rank_world)
from paddlebox_tpu.obs import span as obs_span
from paddlebox_tpu.obs.tracer import step_trace_id, trace_ctx
from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm
from paddlebox_tpu.ops.sparse import (build_push_grads,
                                      build_push_grads_extended,
                                      pull_sparse, pull_sparse_extended)
from paddlebox_tpu.parallel.mesh import BOX_AXIS, device_mesh_1d
from paddlebox_tpu.parallel.sharded_table import (ShardedBatchIndex,
                                                  ShardedPassTable)
from paddlebox_tpu.train.trainer import (_multi_task_loss,
                                         make_dense_optimizer)
from paddlebox_tpu.utils.timer import Timer


class ShardedBoxTrainer:
    def __init__(self, model, table_cfg: TableConfig, feed: DataFeedConfig,
                 trainer_cfg: Optional[TrainerConfig] = None,
                 mesh: Optional[Mesh] = None, bucket_cap: Optional[int] = None,
                 seed: int = 0, use_cvm: bool = True, fleet=None,
                 store_factory=None) -> None:
        """fleet: the host-collective facade (fleet.fleet) — REQUIRED in a
        multi-process job (jax.process_count() > 1): it unions feed-pass
        keys, equalizes batch counts across hosts (data_set.cc:2690-2755)
        and reduces metrics. Single process ignores it except for metric
        reduction.

        store_factory: overrides the shard store backend — pass
        embedding.ps_store.ps_store_factory(client, table_id) to run the
        GPUPS composition (pass slabs built from / dumped to the
        distributed CPU PS, ps_gpu_wrapper.cc:337-760,907-955)."""
        self.model = model
        self.cfg = trainer_cfg or TrainerConfig()
        self.feed = feed
        self.mesh = mesh or device_mesh_1d()
        self.P = self.mesh.devices.size
        # 1D mesh: flat BoxPS topology. 2D ("node","chip") mesh
        # (device_mesh_2d): data/table parallelism over ALL devices, but
        # dense sync goes hierarchical — reduce-scatter on the chip (ICI)
        # axis, psum on the node (DCN) axis, allgather back on chip — so
        # DCN carries 1/chips_per_node of the gradient bytes instead of
        # the full allreduce (SyncParam, boxps_worker.cc:1169-1236).
        self.axes = tuple(self.mesh.axis_names)
        self.hier = len(self.axes) > 1
        if len(self.axes) > 2:
            raise ValueError("ShardedBoxTrainer meshes are 1D or 2D "
                             f"(node, chip); got axes {self.axes}")
        # collectives over the whole device set use the flattened axis
        # tuple; routing/batches shard dim 0 over it either way
        self.axis = self.axes if self.hier else self.axes[0]
        self.chips = int(self.mesh.shape[self.axes[-1]])
        self.fleet = fleet
        # multi-process topology: this process owns the mesh positions whose
        # device it hosts (per-node PS shard layout, box_wrapper.h:433-436)
        self.multiprocess = jax.process_count() > 1
        mesh_devs = list(self.mesh.devices.flat)
        pid = jax.process_index()
        self.local_positions = [i for i, d in enumerate(mesh_devs)
                                if d.process_index == pid]
        self.n_local = len(self.local_positions)
        if self.multiprocess and fleet is None:
            raise ValueError("multi-process ShardedBoxTrainer needs fleet=")
        if self.multiprocess and not self.n_local:
            raise ValueError("mesh has no devices for this process")
        # p2p host data plane (round 9): the per-step bucket/uid exchange
        # rides a persistent socket mesh rendezvous'd ONCE through the
        # store (fleet/mesh_comm.py); None = the store-allgather plane
        # (hostplane=store, or the collective loud fallback on a failed
        # bring-up — make_mesh_comm warns and every rank reverts together)
        # 2-D sparse sharding policy (round 13, parallel/sharding.py):
        # owns key->shard routing, the p2p dest plan and the device slab
        # layout; key-mod (default) is bit-identical to the pre-policy
        # path. Resolved ONCE — the policy identity also rides the p2p
        # rendezvous so a split flag across ranks fails at bring-up.
        from paddlebox_tpu.parallel.sharding import (
            resolve_sharding_policy, validate_policy_agreement)
        self.policy = resolve_sharding_policy(self.P)
        from paddlebox_tpu.fleet.mesh_comm import resolve_hostplane
        self.host_mesh = (
            fleet.make_mesh_comm(self.local_positions,
                                 policy_id=self.policy.describe())
            if self.multiprocess and resolve_hostplane() == "p2p"
            else None)
        if self.multiprocess and self.host_mesh is None:
            # store plane (flag or collective fallback) never
            # rendezvouses — validate the policy identity here instead
            validate_policy_agreement(fleet, self.policy)
        kcap = feed.key_capacity()
        # bucket slack over the uniform K/P expectation (hash imbalance)
        self.bucket_cap = bucket_cap or max(16, (2 * kcap) // self.P)
        self.table = ShardedPassTable(
            table_cfg, self.P, self.bucket_cap, seed=seed,
            owned_shards=self.local_positions if self.multiprocess else None,
            store_factory=store_factory, policy=self.policy)
        self.metrics = MetricRegistry()
        # tagged quality plane (round 18, flag quality_metrics): same
        # host-tensor feed as BoxTrainer; in device-collect mode the
        # pass's device bucket table folds in instead (add_bucket_table)
        from paddlebox_tpu.metrics import quality as _pbtpu_quality
        self.quality = _pbtpu_quality.make_from_flags()
        # scatter-free slab write (push_write flag; see BoxTrainer)
        from paddlebox_tpu.train.trainer import resolve_push_write_sharded
        self._push_write = resolve_push_write_sharded(
            self.table.shard_cap, self.P, self.bucket_cap,
            self.multiprocess)
        self.dense_opt = make_dense_optimizer(self.cfg)
        rng = jax.random.PRNGKey(seed)
        self.params = model.init(rng)
        # dense sync modes (§2.8: step = per-step allreduce; k_step = K local
        # steps then param sync, boxps_worker.cc:1169-1236; sharding = ZeRO-1
        # partitioned optimizer, boxps_worker.cc:582-751)
        self.sharding_mode = (self.cfg.sharding
                              or self.cfg.sync_mode == "sharding")
        self.k_step = (max(1, self.cfg.sync_weight_step)
                       if self.cfg.sync_mode == "k_step" else 1)
        if self.sharding_mode and self.k_step > 1:
            raise ValueError("sharding and k_step dense sync are exclusive")
        if self.cfg.async_mode or self.cfg.sync_mode == "async":
            raise ValueError(
                "async dense mode is single-host: use BoxTrainer")
        if self.sharding_mode and self.cfg.dense_optimizer != "adam":
            raise ValueError(
                "ZeRO-1 sharding implements adam only; got dense_optimizer="
                + self.cfg.dense_optimizer)
        Pn = self.mesh.devices.size
        if self.sharding_mode:
            flat, _ = jax.flatten_util.ravel_pytree(self.params)
            self._n_dense = int(flat.size)
            # hier: moments partition over the chip axis only (per-rank-
            # owned state within a node, boxps_worker.cc:582-751); nodes
            # hold identical copies kept in sync by the node-psum'd grads
            self._n_shard = -(-self._n_dense // (self.chips if self.hier
                                                 else Pn))  # ceil
            sh = NamedSharding(self.mesh, P(self.axis))
            # hand-rolled Adam moments, partitioned [P, n/shards]
            self.opt_state = (
                jax.device_put(np.zeros((Pn, self._n_shard), np.float32), sh),
                jax.device_put(np.zeros((Pn, self._n_shard), np.float32), sh),
                jnp.zeros((), jnp.int32))
        elif self.k_step > 1:
            # per-device param/optimizer replicas that diverge between syncs
            sh = NamedSharding(self.mesh, P(self.axis))
            stack = lambda x: jax.device_put(
                np.broadcast_to(np.asarray(x)[None],
                                (Pn,) + np.asarray(x).shape).copy(), sh)
            self.opt_state = jax.tree.map(
                stack, self.dense_opt.init(self.params))
            self.params = jax.tree.map(stack, self.params)
        else:
            self.opt_state = self.dense_opt.init(self.params)
        self.num_slots = len(feed.used_sparse_slots())
        self.use_cvm = use_cvm
        self.multi_task = len(getattr(model, "task_names", ("ctr",))) > 1
        # NN-cross models: extended pull + expand-grad push through the a2a
        from paddlebox_tpu.train.trainer import (check_expand_config,
                                                 resolve_compute_dtype)
        self.use_expand = bool(getattr(model, "use_expand", False))
        check_expand_config(model, self.table.layout, self.use_expand)
        # wire format of the two VALUE a2as — resolved ONCE; both the pull
        # and push builders read these
        self.a2a_dtype = resolve_compute_dtype(self.cfg.a2a_dtype,
                                               field="a2a_dtype")
        self.a2a_cast = self.a2a_dtype != jnp.float32
        self._slabs: Optional[jax.Array] = None
        #: slab_placement() of the last finished pass, taken after its
        #: write-back and before the slab stack is dropped
        self.last_slab_placement: Optional[dict] = None
        self._prng = jax.random.PRNGKey(seed + 17)
        self._shuffle_rng = np.random.RandomState(seed + 1)
        self._step_count = 0
        self.timers = {n: Timer() for n in ("step", "pass", "build")}
        # telemetry plane (round 10): rank-tagged StepReporter; in multi-
        # process jobs non-zero ranks piggyback their reports to rank 0
        # (over the p2p mesh when it is up, else the fleet store) and
        # rank 0 emits the merged per-rank min/med/max cluster view
        # through the same sink as its own reports
        self._obs_rank, _obs_world = (
            obs_rank_world(self.host_mesh, fleet) if self.multiprocess
            else (0, 1))
        obs_log.set_rank(self._obs_rank)
        self.aggregator = (make_cluster_aggregator(
            mesh=self.host_mesh, fleet=fleet, rank=self._obs_rank,
            world=_obs_world) if self.multiprocess else None)
        self.reporter = make_step_reporter(
            rank=self._obs_rank, timers=self.timers,
            aggregator=self.aggregator)
        # device plane (round 20): HBM-ledger owners, weakref'd (the
        # ledger must not extend the runner's lifetime)
        import weakref
        from paddlebox_tpu.obs.device import register_owner
        _w = weakref.ref(self)
        register_owner("slab", lambda: getattr(_w(), "_slabs", None))
        register_owner("dense_params", lambda: getattr(_w(), "params", None))
        register_owner("opt_state", lambda: getattr(_w(), "opt_state", None))
        self._pool = None   # routing thread pool, lazy (_stager_pool)
        # DumpField debug writers (boxps_worker.cc DumpField): each
        # process dumps its OWN workers' rows (the per-node dump files of
        # the reference)
        self.dump_writer = None
        if self.cfg.dump_fields and self.cfg.dump_fields_path:
            from paddlebox_tpu.train.dump import DumpWriter
            self.dump_writer = DumpWriter(self.cfg.dump_fields_path,
                                          self.cfg.dump_thread_num,
                                          rank=jax.process_index())
        # device-side metric collection (metrics.h:776): decided per pass
        # from the registered metrics' mode_collect_in_device flags; the
        # step is rebuilt when the mode flips (_sync_collect_mode)
        self._collect_T: Optional[int] = None
        self._eval_step = None  # built lazily on first predict_batches
        self._param_sync = (self._build_param_sync() if self.k_step > 1
                            else None)
        self._steps_since_sync = 0
        self._rebuild_fns()

    def _rebuild_fns(self) -> None:
        """(Re)build the jitted step + megastep for the current device-
        collect mode. Megastep: scan a chunk of steps inside one dispatch
        (k_step mode keeps per-step dispatch so the host can interleave
        param syncs; multi-process keeps per-step dispatch so metrics read
        only addressable shards). The metric state rides the scan carry
        (extra_carry=2) so collect mode costs no extra dispatches."""
        from paddlebox_tpu.train.trainer import make_scan
        self._step = self._build_step()
        self._scan_steps = (make_scan(self._step, extra_carry=2)
                            if self.k_step == 1 and not self.multiprocess
                            else None)

    def make_metric_state(self):
        """Per-pass device metric state (mtab, mstats) for the CURRENT
        collect mode — the one source of truth for its layout (train_pass
        and the driver dryrun both build it here).

        mtab  [L, 2, T] int32: per-device neg/pos bucket counts (int32 —
              exact to 2^31; float32 would silently saturate at 2^24).
        mstats [L, 2, 5] float32: Kahan-compensated (sum, c) running sums
              of (abserr, sqrerr, pred_sum, label_sum, count) — the
              compensation keeps a pass-long f32 accumulation within ~2
              ulps where a plain f32 sum loses all sub-2^-24 increments.
        Dummy T=1 tables when collection is off (the step passes them
        through)."""
        sharding = NamedSharding(self.mesh, P(self.axis))
        L = self.n_local if self.multiprocess else self.P
        T = self._collect_T or 1
        mtab = self._put_sharded(np.zeros((L, 2, T), np.int32), sharding)
        mstats = self._put_sharded(np.zeros((L, 2, 5), np.float32),
                                   sharding)
        return mtab, mstats

    def _device_collect_size(self) -> Optional[int]:
        """table_size when EVERY registered metric can be collected on
        device: plain single-task AUC over the standard (pred, label,
        mask) tensors, all-phase, with mode_collect_in_device set — else
        None and the host path serves everything (a mixed mode would
        double-count the collectable subset)."""
        from paddlebox_tpu.metrics.auc import MetricMsg
        msgs = self.metrics.messages()
        if not msgs or self.multi_task:
            return None
        if self.dump_writer is not None:
            # DumpField needs per-instance predictions on host every step
            return None
        sizes = set()
        for m in msgs:
            c = getattr(m, "calculator", None)
            if (type(m) is not MetricMsg or m.kind != "auc"
                    or m.sample_scale_var or m.uid_var
                    or m.metric_phase != -1
                    or m.label_var != "label" or m.pred_var != "pred"
                    or m.mask_var != "mask"
                    or c is None or not c.mode_collect_in_device):
                return None
            sizes.add(c.table_size)
        return sizes.pop() if len(sizes) == 1 else None

    def _sync_collect_mode(self) -> None:
        T = self._device_collect_size()
        if T != self._collect_T:
            self._collect_T = T
            self._rebuild_fns()

    # ------------------------------------------------------------ jit step
    def _pull_and_forward(self):
        """The ONE pull+forward contract shared by the train step and the
        eval step: (pull_emb, forward_logits, preds_of). Changing the a2a
        pull, mixed precision, or rank-offset handling here changes both
        paths together."""
        model = self.model
        layout = self.table.layout
        B = self.feed.batch_size
        S = self.num_slots
        use_cvm = self.use_cvm
        axis = self.axis
        from paddlebox_tpu.train.trainer import (apply_mixed_precision,
                                                 mixed_logits_to_f32,
                                                 model_accepts_rank_offset,
                                                 resolve_compute_dtype)
        wants_rank_offset = model_accepts_rank_offset(model)
        cdtype = resolve_compute_dtype(self.cfg.compute_dtype)
        mixed = cdtype != jnp.float32
        # wire format of the two VALUE a2as (walk_to_src/walk_to_dest
        # traffic): bf16 halves the ICI bytes; values upcast to f32 right
        # after transport so pooling/merging/in-table updates stay f32
        a2a_dtype, a2a_cast = self.a2a_dtype, self.a2a_cast
        use_expand = self.use_expand
        base_w = 3 + layout.embedx_dim

        def pull_emb(slab, batch):
            # a2a ids → local gather → a2a values → restore. Expand mode:
            # the local gather is the dual-output extended pull; base +
            # expand blocks ride ONE a2a concatenated and split after the
            # restore (pull_box_extended_sparse over HeterComm semantics).
            buckets = batch["buckets"]                       # [P, KB]
            KB = buckets.shape[1]
            Pn = buckets.shape[0]
            req = jax.lax.all_to_all(buckets, axis, 0, 0, tiled=True)
            if use_expand:
                base, exp = pull_sparse_extended(slab, req.reshape(-1),
                                                 layout)
                vals = jnp.concatenate([base, exp], axis=1)
            else:
                vals = pull_sparse(slab, req.reshape(-1), layout)
            if a2a_cast:
                vals = vals.astype(a2a_dtype)
            resp = jax.lax.all_to_all(
                vals.reshape(Pn, KB, -1), axis, 0, 0, tiled=True)
            emb = resp.reshape(Pn * KB, -1)[batch["restore"]]  # [K, Dp(+E)]
            if a2a_cast:
                emb = emb.astype(jnp.float32)
            if use_expand:
                emb = (emb[:, :base_w], emb[:, base_w:])
            return emb, req

        def forward_logits(params, emb, batch):
            expand_emb = None
            if use_expand:
                emb, expand_emb = emb
            # packer batches carry nondecreasing segments by contract
            pooled = fused_seqpool_cvm(
                emb, batch["segments"], batch["valid"], B, S, use_cvm,
                sorted_segments=True)
            dense_in = batch.get("dense")
            if mixed:
                # bf16 matmul path; f32 master params — the same shared
                # contract as the single-host trainer
                params, pooled, dense_in = apply_mixed_precision(
                    params, pooled, dense_in, cdtype)
            if use_expand:
                from paddlebox_tpu.ops.seqpool import seqpool_sum
                pooled_exp = seqpool_sum(expand_emb, batch["segments"],
                                         batch["valid"], B, S)
                if mixed:
                    pooled_exp = pooled_exp.astype(cdtype)
                logits = model.apply(params, pooled, dense_in,
                                     expand=pooled_exp)
            elif wants_rank_offset and "rank_offset" in batch:
                logits = model.apply(params, pooled, dense_in,
                                     rank_offset=batch["rank_offset"])
            else:
                logits = model.apply(params, pooled, dense_in)
            if mixed:
                logits = mixed_logits_to_f32(logits)
            return logits

        def preds_of(logits):
            if self.multi_task:
                return {t: jax.nn.sigmoid(lg) for t, lg in logits.items()}
            return {"ctr": jax.nn.sigmoid(logits)}

        return pull_emb, forward_logits, preds_of

    def _build_step(self):
        model = self.model
        layout = self.table.layout
        conf = self.table.config.optimizer
        S = self.num_slots
        B = self.feed.batch_size
        use_cvm = self.use_cvm
        multi_task = self.multi_task
        axis = self.axis
        hier = self.hier
        chip_axis = self.axes[-1]          # ICI axis (the only axis in 1D)
        node_axis = self.axes[0] if hier else None
        chips = self.chips
        sharding_mode = self.sharding_mode
        k_step = self.k_step
        one_ring = self.cfg.sync_one_ring
        lr = self.cfg.dense_lr
        has_summary = (getattr(model, "use_data_norm", False)
                       and hasattr(model, "update_summary"))
        use_expand = self.use_expand
        if use_expand and has_summary:
            raise ValueError("expand embedding + data_norm summary is not "
                             "supported in one model")
        collect_T = self._collect_T
        a2a_dtype, a2a_cast = self.a2a_dtype, self.a2a_cast
        push_write = self._push_write   # uid-wire write strategy (static)
        pull_emb, forward_logits, preds_of = self._pull_and_forward()

        def shard_step(slab, params, opt_state, batch, prng, mtab, mstats):
            # per-device views: slab [1, C, W]; batch leaves [1, ...]
            slab = slab[0]
            batch = jax.tree.map(lambda x: x[0], batch)
            if sharding_mode:
                mu, nu, t = opt_state
                mu, nu = mu[0], nu[0]
            elif k_step > 1:
                params = jax.tree.map(lambda x: x[0], params)
                opt_state = jax.tree.map(lambda x: x[0], opt_state)
            prng, next_prng = jax.random.split(prng)
            prng = jax.random.fold_in(prng, jax.lax.axis_index(axis))
            KB = batch["buckets"].shape[1]
            Pn = batch["buckets"].shape[0]
            emb, req = pull_emb(slab, batch)

            def loss_fn(params, emb):
                logits = forward_logits(params, emb, batch)
                ins_valid = batch["ins_valid"]
                if multi_task:
                    labels = {t: batch["labels_" + t] for t in model.task_names}
                    loss, preds = _multi_task_loss(
                        logits, labels, ins_valid,
                        getattr(model, "loss_mode", "sum"))
                else:
                    lab = batch["labels"].astype(jnp.float32)
                    bce = optax.sigmoid_binary_cross_entropy(logits, lab)
                    denom = jnp.maximum(ins_valid.sum(), 1.0)
                    loss = jnp.where(ins_valid, bce, 0.0).sum() / denom
                    preds = {"ctr": jax.nn.sigmoid(logits)}
                return loss, preds

            grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
            (loss, preds), (dparams, demb) = grad_fn(params, emb)
            # data_norm summary delta from THIS device's batch (running-sums
            # rule; grads are zero by stop_gradient). Applied after the mode
            # branch; pmean sync keeps the ratios exact (see CtrDnn docs).
            dn_new = None
            if has_summary:
                from paddlebox_tpu.train.trainer import dn_update_params
                dn_new = dn_update_params(
                    model, params, emb, batch["segments"], batch["valid"],
                    B, S, use_cvm, batch.get("dense"))["dn_summary"]

            def reduce_scatter_mean(flat_g):
                """Grad sum → this device's 1/shards slice, averaged over
                all Pn devices. Flat mesh: one psum_scatter over the axis.
                Hierarchical: psum_scatter over chips (ICI), psum over
                nodes — DCN carries only the scattered 1/chips slice (the
                2-level SyncParam shape, boxps_worker.cc:1169-1236).
                Returns (g_shard [n_shard], n_shard, pad)."""
                n = flat_g.size
                shards = chips if hier else Pn
                n_shard = -(-n // shards)
                pad = shards * n_shard - n
                g_shard = jax.lax.psum_scatter(
                    jnp.pad(flat_g, (0, pad)), chip_axis,
                    scatter_dimension=0, tiled=True)
                if hier:
                    g_shard = jax.lax.psum(g_shard, node_axis)
                return g_shard / Pn, n_shard, pad

            # ---- dense sync by mode
            loss = jax.lax.pmean(loss, axis)
            if sharding_mode:
                # ZeRO-1: reduce-scatter grads → shard-local Adam →
                # all-gather params (the TPU shape of the reference's
                # reduce-scatter + SyncDense + allgather, boxps_worker.cc:
                # 1194-1218, with per-rank-owned optimizer state, cc:582-751)
                flat_g, _ = jax.flatten_util.ravel_pytree(dparams)
                flat_p, unravel = jax.flatten_util.ravel_pytree(params)
                n = flat_p.size
                g_shard, n_shard, pad = reduce_scatter_mean(flat_g)
                i = jax.lax.axis_index(chip_axis)
                ppad = jnp.pad(flat_p, (0, pad))
                p_shard = jax.lax.dynamic_slice(ppad, (i * n_shard,),
                                                (n_shard,))
                t = t + 1
                tf = t.astype(jnp.float32)
                mu = 0.9 * mu + 0.1 * g_shard
                nu = 0.999 * nu + 0.001 * jnp.square(g_shard)
                mhat = mu / (1.0 - jnp.power(0.9, tf))
                vhat = nu / (1.0 - jnp.power(0.999, tf))
                p_shard = p_shard - lr * mhat / (jnp.sqrt(vhat) + 1e-8)
                flat_new = jax.lax.all_gather(p_shard, chip_axis,
                                              tiled=True)[:n]
                params = unravel(flat_new)
                opt_state = (mu[None], nu[None], t)
            elif k_step > 1:
                # K-step mode: local update now, param allreduce every K
                # steps from the host loop (DenseKStep*, boxps_worker.cc:
                # 389-391,1297-1302)
                updates, opt_state = self.dense_opt.update(
                    dparams, opt_state, params)
                params = optax.apply_updates(params, updates)
                params = jax.tree.map(lambda x: x[None], params)
                opt_state = jax.tree.map(lambda x: x[None], opt_state)
            else:
                if hier and not one_ring:
                    # 2-level grad mean (numerically identical to the flat
                    # pmean): scatter → node psum → allgather over chips
                    flat_g, unravel_g = jax.flatten_util.ravel_pytree(
                        dparams)
                    n = flat_g.size
                    g_sh, _, _ = reduce_scatter_mean(flat_g)
                    flat_g = jax.lax.all_gather(
                        g_sh, chip_axis, tiled=True)[:n]
                    dparams = unravel_g(flat_g)
                else:
                    # per-step data-parallel allreduce (SyncParam/NCCL;
                    # sync_one_ring forces this flat ring on a 2D mesh)
                    dparams = jax.lax.pmean(dparams, axis)
                updates, opt_state = self.dense_opt.update(
                    dparams, opt_state, params)
                params = optax.apply_updates(params, updates)

            if dn_new is not None:
                # overwrite the summary leaves with the running-sums result
                # (the optimizer's zero-grad update on them is a no-op).
                # Replicated-params modes must pmean the per-device results
                # (decay·state is common; the per-batch deltas average,
                # which preserves the normalization ratios exactly);
                # k_step replicas diverge by design until the param sync.
                if k_step > 1 and not sharding_mode:
                    params = dict(params, dn_summary=jax.tree.map(
                        lambda x: x[None], dn_new))
                else:
                    params = dict(params, dn_summary=jax.lax.pmean(
                        dn_new, axis))

            # ---- push: per-key grads → bucket merge → a2a → local update
            label_src = (batch["labels_" + model.task_names[0]] if multi_task
                         else batch["labels"])
            clicks = label_src[batch["segments"] // S]
            if use_expand:
                pg = build_push_grads_extended(
                    demb[0], demb[1], batch["slots"], clicks, batch["valid"])
            else:
                pg = build_push_grads(demb, batch["slots"], clicks,
                                      batch["valid"])
            bucket_g = jnp.zeros((Pn * KB, pg.shape[1]), pg.dtype
                                 ).at[batch["restore"]].add(
                jnp.where(batch["valid"][:, None], pg, 0.0))
            if a2a_cast:
                # the first 3 push columns (slot, merged show, merged click)
                # are EXACT integers the table stores verbatim — bf16 only
                # represents integers to 256, so hot-key counts / slot ids
                # would silently round. Ship them f32 on their own small a2a
                # (6B/row) and cast only the gradient columns to the wire
                # dtype; XLA overlaps the two independent collectives.
                meta = jax.lax.all_to_all(
                    bucket_g[:, :3].reshape(Pn, KB, 3), axis, 0, 0,
                    tiled=True)
                gwire = jax.lax.all_to_all(
                    bucket_g[:, 3:].astype(a2a_dtype).reshape(Pn, KB, -1),
                    axis, 0, 0, tiled=True)
                recv_g = jnp.concatenate(
                    [meta, gwire.astype(jnp.float32)], axis=-1)
            else:
                recv_g = jax.lax.all_to_all(
                    bucket_g.reshape(Pn, KB, -1), axis, 0, 0, tiled=True)
            if "push_pos" in batch:
                # single-process mesh, scatter-free write: host-staged
                # per-shard pos map turns the slab write into gather+select
                slab = push_sparse_rebuild(
                    slab, batch["push_uids"], batch["push_pos"],
                    batch["push_perm"], batch["push_inv"],
                    recv_g.reshape(Pn * KB, -1), prng, layout, conf)
            elif "push_perm" in batch:
                # full host wire: the incoming-id dedup was precomputed
                # on the host (shard_batches) — no device sort
                slab = push_sparse_hostdedup(
                    slab, batch["push_uids"], batch["push_perm"],
                    batch["push_inv"], recv_g.reshape(Pn * KB, -1), prng,
                    layout, conf)
            elif "push_uids" in batch:
                # uid wire (h2d_uid_wire, round 8): the shard's incoming
                # ids ARE the a2a'd buckets already on device (req), so
                # only the sorted uid vector staged — perm/inv (and the
                # rebuild pos) derive by searchsorted in the step
                slab = push_sparse_uidwire(
                    slab, batch["push_uids"], req.reshape(-1),
                    recv_g.reshape(Pn * KB, -1), prng, layout, conf,
                    write=push_write)
            else:
                slab = push_sparse_dedup(slab, req.reshape(-1),
                                         recv_g.reshape(Pn * KB, -1), prng,
                                         layout, conf)

            if collect_T is not None:
                # device-side AUC collection (mode_collect_in_gpu,
                # metrics.h:776): bucket this device's preds into its
                # int32 [2, T] table + Kahan-compensated error sums —
                # preds never leave the device; the host merges ONE table
                # per pass (see make_metric_state for the layout/precision
                # rationale)
                tab, st = mtab[0], mstats[0]
                praw = preds["ctr"].astype(jnp.float32)
                # a NaN pred would survive the clip into a backend-defined
                # int32 bucket; the host add_data path raises on it — mirror
                # that signal by excluding non-finite preds from every
                # accumulator (the count shortfall is the blowup indicator)
                ok = batch["ins_valid"] & jnp.isfinite(praw)
                p = jnp.clip(praw, 0.0, 1.0)
                lab = batch["labels"].astype(jnp.int32)
                w = ok.astype(jnp.float32)
                wi = ok.astype(jnp.int32)
                pos = jnp.minimum((p * collect_T).astype(jnp.int32),
                                  collect_T - 1)
                tab = tab.at[lab, pos].add(wi)
                labf = lab.astype(jnp.float32)
                err = p - labf
                batch_sums = jnp.stack([
                    (jnp.abs(err) * w).sum(), (err * err * w).sum(),
                    (p * w).sum(), (labf * w).sum(), w.sum()])
                s, c = st[0], st[1]
                y = batch_sums - c
                t_sum = s + y
                c = (t_sum - s) - y
                mtab, mstats = tab[None], jnp.stack([t_sum, c])[None]
            return (slab[None], params, opt_state, loss, preds, next_prng,
                    mtab, mstats)

        spec_sh = P(self.axis)
        spec_rep = P()
        # prefix specs: spec_sh applies to every leaf of the batch dict /
        # preds dict
        if self.sharding_mode:
            opt_in = opt_out = (spec_sh, spec_sh, spec_rep)
            par_in = par_out = spec_rep
        elif self.k_step > 1:
            opt_in = opt_out = spec_sh
            par_in = par_out = spec_sh
        else:
            opt_in = opt_out = spec_rep
            par_in = par_out = spec_rep
        fn = jax.shard_map(
            shard_step, mesh=self.mesh,
            in_specs=(spec_sh, par_in, opt_in, spec_sh, spec_rep, spec_sh,
                      spec_sh),
            out_specs=(spec_sh, par_out, opt_out, spec_rep, spec_sh,
                       spec_rep, spec_sh, spec_sh),
            check_vma=False)
        # slabs + metric state donated: one live copy each on device
        from paddlebox_tpu.obs.device import instrument_jit
        return instrument_jit(fn, "shard_step", donate_argnums=(0, 5, 6))

    def _build_param_sync(self):
        """K-step dense sync: allreduce-mean the diverged per-device param
        and optimizer replicas (SyncParam, boxps_worker.cc:1169-1236 —
        scale 1/(dev×node))."""
        axis = self.axis

        def _avg(x):
            # int leaves (e.g. adam count) are identical replicas: pass through
            if jnp.issubdtype(x.dtype, jnp.floating):
                return jax.lax.pmean(x, axis)
            return x

        def sync(params, opt_state):
            params = jax.tree.map(lambda x: x[0], params)
            opt_state = jax.tree.map(lambda x: x[0], opt_state)
            params = jax.tree.map(_avg, params)
            opt_state = jax.tree.map(_avg, opt_state)
            return (jax.tree.map(lambda x: x[None], params),
                    jax.tree.map(lambda x: x[None], opt_state))

        spec_sh = P(self.axis)
        from paddlebox_tpu.obs.device import instrument_jit
        return instrument_jit(jax.shard_map(
            sync, mesh=self.mesh, in_specs=(spec_sh, spec_sh),
            out_specs=(spec_sh, spec_sh), check_vma=False),
            "shard_param_sync", donate_argnums=(0, 1))

    # -------------------------------------------------------------- batches
    def _put_sharded(self, host_local: np.ndarray, sharding) -> jax.Array:
        """Local [L, ...] rows → global [P, ...] array on the mesh axis.
        Single process: L == P and this is a plain device_put."""
        from paddlebox_tpu.obs.device import account_h2d
        account_h2d(getattr(host_local, "nbytes", 0))  # staging transfer
        if not self.multiprocess:
            return jax.device_put(host_local, sharding)
        global_shape = (self.P,) + host_local.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, host_local, global_shape)

    def _stager_pool(self):
        """Shared routing thread pool (flag stager_threads). The native
        bucketize/dedup calls drop the GIL for their whole run (ctypes
        releases it around foreign calls), so W workers route W batches
        genuinely in parallel — the reference runs 20/30 reader/merge
        threads for exactly this stage (flags.cc:966-968,
        box_wrapper.h:862); a single-thread stager at the reference's
        per-batch key budget (~3.69M keys, 12.9M keys/s native) would
        bound a pod's step rate at ~290ms."""
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            from paddlebox_tpu.config import flags
            n = max(1, int(flags.get_flag("stager_threads")))
            self._pool = ThreadPoolExecutor(
                n, thread_name_prefix="shard-stager")
        return self._pool

    def _step_host_arrays(self, per_worker: List[List[PackedBatch]],
                          i: int) -> Dict[str, np.ndarray]:
        """Bucketize + stack ONE step's local per-worker batches into host
        arrays [L, ...] (L = local workers) with the table routing index.
        Per-worker routing and per-destination push dedup fan out on the
        stager pool."""
        n_workers = len(per_worker)
        pool = self._stager_pool()

        def route_one(w):
            b = per_worker[w][i]
            valid = b.valid.copy()
            return b, valid, self.table.bucketize(b.keys, valid)

        routed = list(pool.map(route_one, range(n_workers)))
        stacked: Dict[str, List[np.ndarray]] = {}
        for b, valid, idx in routed:
            leaves = {
                "buckets": idx.buckets, "restore": idx.restore,
                "slots": b.slots, "segments": b.segments, "valid": valid,
                "ins_valid": b.ins_valid, "labels": b.labels,
            }
            if b.dense is not None:
                leaves["dense"] = b.dense
            if b.rank_offset is not None:
                leaves["rank_offset"] = b.rank_offset
            if self.multi_task:
                packed = b.task_labels or {}
                for t in self.model.task_names:
                    leaves["labels_" + t] = packed.get(t, b.labels)
            for k, v in leaves.items():
                stacked.setdefault(k, []).append(v)
        if not self.table.test_mode:
            # the ids each shard RECEIVES through the a2a are host-known
            # — directly in a single process, via the per-step bucket
            # exchange in a multi-process job — so the push dedup and
            # the scatter-free pos maps are precomputed for every owned
            # destination shard; no runner is left on the on-device
            # jnp.unique sort path (round-5 verdict item 2; ONE shared
            # implementation with the pipeline runner)
            from paddlebox_tpu.config import flags
            from paddlebox_tpu.parallel.sharded_table import stage_push_dedup
            stacked.update(stage_push_dedup(
                stacked["buckets"], self.local_positions, self.P,
                self.table.shard_cap, self.multiprocess,
                self.fleet.all_gather if self.multiprocess else None,
                rebuild=self._push_write == "rebuild", pool=pool,
                note_touched=self.table.note_touched,
                uid_only=bool(flags.get_flag("h2d_uid_wire")),
                mesh=self.host_mesh,
                policy=self.policy))
        return {k: np.stack(v) for k, v in stacked.items()}

    def shard_batches(self, per_worker: List[List[PackedBatch]],
                      depth: Optional[int] = None):
        """STREAM each step's local per-worker batches as [P, ...] global
        device arrays with the mesh sharding + the table routing index.
        per_worker has P lists in single process, n_local in multi-process
        (each process feeds the rows of its own mesh positions).

        Bounded generator (round-2 verdict weak #3): a staging thread
        bucketizes and device_puts step i+1 while step i trains — the
        device_reader_->Next() per-batch cadence (boxps_worker.cc:1274)
        with MiniBatchGpuPack-style double buffering (data_feed.h:519-680).
        Peak live routed steps = depth (queued, flag stream_depth) + 1 in
        the consumer's hands + 1 in flight on the producer — O(depth+2)
        batch memory for a pass of ANY length instead of O(n_steps); a
        real pass at reference scale (thousands of batches × [P, KB]
        buckets) no longer materializes whole on host+HBM. (The scan path
        additionally holds one chunk per dispatch plus the double-buffered
        previous chunk — the intended 2-chunk bound.)"""
        n_steps = len(per_worker[0])
        if depth is None:
            from paddlebox_tpu.config import flags
            depth = max(1, int(flags.get_flag("stream_depth")))
        sharding = NamedSharding(self.mesh, P(self.axis))
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        stop = threading.Event()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for i in range(n_steps):
                    if stop.is_set():
                        return
                    arrs = self._step_host_arrays(per_worker, i)
                    dev = {k: self._put_sharded(v, sharding)
                           for k, v in arrs.items()}
                    if not _put(dev):
                        return
            except BaseException as e:  # surfaced at the consumer's get()
                _put(e)

        producer = threading.Thread(target=produce, daemon=True,
                                    name="shard-batch-stager")
        producer.start()
        self.stream_high_water = 0
        try:
            for _ in range(n_steps):
                item = q.get()
                if isinstance(item, BaseException):
                    raise item
                # staged-ahead steps live right now: queue + this one
                self.stream_high_water = max(self.stream_high_water,
                                             q.qsize() + 1)
                yield item
        finally:
            stop.set()
            while True:  # unblock a producer stuck on a full queue
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            producer.join(timeout=10.0)

    # ---------------------------------------------------------- pass cadence
    def slab_placement(self) -> Optional[dict]:
        """Where the live pass slab stack sits: its global shape and, per
        addressable shard, the device id, the shard's shape and what that
        device's allocator holds right now (None where the backend keeps
        no memory_stats, i.e. the CPU). None between passes."""
        if self._slabs is None:
            return None
        shards = []
        for s in self._slabs.addressable_shards:
            ms = s.device.memory_stats()
            shards.append({"device": s.device.id,
                           "shape": list(s.data.shape),
                           "bytes_in_use": int(ms["bytes_in_use"])
                           if ms else None})
        shards.sort(key=lambda d: d["device"])
        return {"shape": list(self._slabs.shape), "shards": shards}

    def train_pass(self, dataset: BoxDataset,
                   preloaded: bool = False) -> Dict[str, float]:
        t_pass = self.timers["pass"]
        t_pass.start()
        self._sync_collect_mode()
        allgather = (self.fleet.all_gather if self.multiprocess else None)
        if not preloaded:
            self.table.begin_feed_pass()
            dataset.load_into_memory(add_keys_fn=self.table.add_keys)
            self.table.end_feed_pass(allgather=allgather)
        self.timers["build"].start()
        # slab device layout is the policy's decision (c): key-mod (and
        # every policy on a flat/hier mesh) = P(axis), the pre-policy
        # layout; the 2d grid expresses itself over (table, row) axes
        # where a mesh declares them
        sharding = self.policy.slab_sharding(self.mesh, self.axis)
        self._slabs = self._put_sharded(
            self.table.build_owned_slabs() if self.multiprocess
            else self.table.build_slabs(), sharding)
        self.timers["build"].pause()
        dataset.local_shuffle(self._shuffle_rng.randint(1 << 31))
        # packed here, once: the metrics read raw_steps and the stager
        # reads per_worker[w][i], and a plan packs every time it is asked
        per_worker = [list(plan) for plan in dataset.split_batches(
            num_workers=self.n_local if self.multiprocess else self.P,
            equalize=(self.fleet.equalize_batches()
                      if self.multiprocess else None))]
        losses = []
        raw_steps = list(zip(*per_worker)) if per_worker[0] else []
        n_steps = len(raw_steps)
        # per-device metric state for THIS pass (dummies when device
        # collection is off — the step passes them through)
        mtab, mstats = self.make_metric_state()
        # examples consumed per raw step (one batch per worker)
        ex_per_step = self.feed.batch_size * len(per_worker)
        # bounded stream: the stager routes + device_puts ahead of training
        # (never the whole pass) — see shard_batches. close() on ANY exit
        # stops the stager thread; an abandoned one would race the next
        # pass's table mutations from the daemon thread.
        stream = self.shard_batches(per_worker)
        try:
            start_i = 0
            chunk = max(1, self.cfg.scan_chunk)
            if (self._scan_steps is not None and chunk > 1
                    and n_steps >= chunk):
                from paddlebox_tpu.train.trainer import run_scan_chunks

                def on_chunk(lo, group, chunk_losses, preds):
                    self._step_count += len(group)
                    obs_beat("step")
                    self.reporter.note_examples(
                        len(group) * ex_per_step)
                    self.reporter.maybe_report(self._step_count)
                    if self.cfg.check_nan_inf and not np.isfinite(
                            chunk_losses).all():
                        raise FloatingPointError("nan/inf loss in scan chunk")
                    # per-step device slices: _add_metrics makes one
                    # GATED host copy per task via _local_rows (device-
                    # collect mode transfers nothing; multiprocess preds
                    # span non-addressable devices and MUST go through
                    # the addressable-shards path, not np.asarray)
                    for j in range(len(group)):
                        self._add_metrics(
                            {t: p[j] for t, p in preds.items()},
                            raw_steps[lo + j])

                def scan_call(carry, stacked):
                    (slabs, params, opt_state, losses_d, preds, prng, mt,
                     ms) = self._scan_steps(carry[0], carry[1], carry[2],
                                            stacked, carry[3], carry[4],
                                            carry[5])
                    return ((slabs, params, opt_state, prng, mt, ms),
                            losses_d, preds)

                carry = (self._slabs, self.params, self.opt_state,
                         self._prng, mtab, mstats)
                carry, chunk_losses, start_i = run_scan_chunks(
                    scan_call, stream, chunk,
                    lambda group: {k: jnp.stack([d[k] for d in group])
                                   for k in group[0]},
                    carry, on_chunk, timer=self.timers["step"],
                    n_items=n_steps)
                (self._slabs, self.params, self.opt_state, self._prng,
                 mtab, mstats) = carry
                losses.extend(chunk_losses)
            for i, batch in enumerate(stream, start=start_i):
                self.timers["step"].start()
                # per-step 64-bit trace id (round 14): every span this
                # step records on this thread carries it, correlating
                # the step across the stitched cluster timeline
                with trace_ctx(step_trace_id(self._obs_rank,
                                             self._step_count + 1)), \
                        obs_span("shard_step"):
                    (self._slabs, self.params, self.opt_state, loss, preds,
                     self._prng, mtab, mstats) = self._step(
                        self._slabs, self.params, self.opt_state, batch,
                        self._prng, mtab, mstats)
                self.timers["step"].pause()
                self._step_count += 1
                obs_beat("step")
                self.reporter.note_examples(ex_per_step)
                self.reporter.maybe_report(self._step_count)
                # device scalar: float() here would stall the dispatch
                # stream every step — np.mean at the pass boundary pays
                # the D2H once
                losses.append(loss)
                if self._param_sync is not None:
                    self._steps_since_sync += 1
                    if self._steps_since_sync >= self.k_step:
                        self.params, self.opt_state = self._param_sync(
                            self.params, self.opt_state)
                        self._steps_since_sync = 0
                self._add_metrics(preds, raw_steps[i])
        finally:
            stream.close()
        if self._collect_T:
            # ONE D2H per pass: sum this process's device tables and merge
            # into every (device-collectable) calculator; cross-process
            # reduction stays in get_metric_msg's allreduce. Kahan pairs
            # resolve as s - c (c holds the uncorrected excess of the last
            # add).
            tab = self._local_rows(mtab).sum(axis=0).astype(np.float64)
            st = self._local_rows(mstats).astype(np.float64)
            sums = (st[:, 0, :] - st[:, 1, :]).sum(axis=0)
            for m in self.metrics.messages():
                m.calculator.add_bucket_stats(tab, *sums)
            if self.quality is not None:
                # the device table folds down to the quality table size
                # — same counts, coarser pred buckets (tag streams need
                # host preds; device-collect mode keeps them on device)
                try:
                    self.quality.add_bucket_table(tab, *sums)
                except ValueError as e:
                    obs_log.warning(
                        "quality plane skipped device table",
                        error=repr(e)[:200])
        if self._param_sync is not None and self._steps_since_sync:
            # pass boundary is always a sync point
            self.params, self.opt_state = self._param_sync(
                self.params, self.opt_state)
            self._steps_since_sync = 0
        if self.multiprocess:
            # each process dumps only its addressable shards (EndPass
            # HBM→host per node, ps_gpu_wrapper.cc:983+)
            self.table.write_back_addressable(self._slabs)
        else:
            # touched-row delta D2H when the incremental lifecycle ran
            # (the pre-round-6 full np.asarray rode here every pass)
            self.table.end_pass_write_back(self._slabs)
        self.table.check_need_limit_mem()
        self.last_slab_placement = self.slab_placement()
        self._slabs = None
        t_pass.pause()
        mean_loss = float(np.mean(losses)) if losses else 0.0
        # pass boundary closes the report window (and on rank 0, emits a
        # merged cluster view of whatever peer snapshots have arrived)
        extra = {"event": "pass_end", "loss": round(mean_loss, 6),
                 "auc": {m.name: float(m.calculator.auc())
                         for m in self.metrics.messages()}}
        from paddlebox_tpu.metrics.quality import attach_pass_extras
        # multi-process ranks ship the raw sum-mergeable state so the
        # rank-0 merge computes the CLUSTER-wide tagged quality report
        attach_pass_extras(extra, self.quality,
                           ship_state=self.multiprocess)
        self.reporter.maybe_report(self._step_count, force=True,
                                   extra=extra)
        if self.cfg.profile:
            from paddlebox_tpu.utils.profiler import timer_report
            # rank-tagged so multiprocess reports stay distinguishable
            obs_log.info(timer_report(
                self.timers, prefix=f"sharded.r{jax.process_index()}."))
        return {"loss": mean_loss,
                "batches": n_steps, "instances": len(dataset)}

    # ------------------------------------------------------------- eval
    def _build_eval_step(self):
        """Forward-only shard_map step (the SetTestMode inference path —
        no push, no dense update) over the SAME pull+forward closures as
        the train step."""
        pull_emb, forward_logits, preds_of = self._pull_and_forward()
        k_step = self.k_step

        def shard_eval(slab, params, batch):
            slab = slab[0]
            batch = jax.tree.map(lambda x: x[0], batch)
            if k_step > 1:
                params = jax.tree.map(lambda x: x[0], params)
            emb, _req = pull_emb(slab, batch)
            return preds_of(forward_logits(params, emb, batch))

        spec_sh = P(self.axis)
        par_in = spec_sh if self.k_step > 1 else P()
        from paddlebox_tpu.obs.device import instrument_jit
        return instrument_jit(jax.shard_map(
            shard_eval, mesh=self.mesh,
            in_specs=(spec_sh, par_in, spec_sh), out_specs=spec_sh,
            check_vma=False), "shard_eval")

    def predict_batches(self, dataset: BoxDataset):
        """Test-mode inference over a loaded dataset (SetTestMode,
        box_wrapper.cc:183): no feature creation, no write-back. Returns
        (preds, labels) over this process's valid instances."""
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        if len(dataset) == 0:
            dataset.load_into_memory()
        allgather = (self.fleet.all_gather if self.multiprocess else None)
        self.table.set_test_mode(True)
        try:
            self.table.begin_feed_pass()
            self.table.add_keys(dataset.all_keys())
            self.table.end_feed_pass(allgather=allgather)
            sharding = self.policy.slab_sharding(self.mesh, self.axis)
            slabs = self._put_sharded(
                self.table.build_owned_slabs() if self.multiprocess
                else self.table.build_slabs(), sharding)
            nw = self.n_local if self.multiprocess else self.P
            per_worker = [list(plan) for plan in dataset.split_batches(
                num_workers=nw,
                equalize=(self.fleet.equalize_batches()
                          if self.multiprocess else None))]
            raw_steps = list(zip(*per_worker)) if per_worker[0] else []
            # equalization pads short workers with WRAPPED (duplicate)
            # batches so collectives stay lockstep; those batches still run
            # but their predictions are excluded from the returned set
            n = len(dataset)
            per_w = (n + nw - 1) // nw
            bs = self.feed.batch_size
            real_batches = [
                -(-max(0, min(per_w, n - w * per_w)) // bs)
                for w in range(nw)]
            main_task = (self.model.task_names[0] if self.multi_task
                         else None)
            preds_all, labels_all = [], []
            stream = self.shard_batches(per_worker)
            try:
                for i, batch in enumerate(stream):
                    preds = self._eval_step(slabs, self.params, batch)
                    key = (main_task if main_task is not None
                           else list(preds)[0])
                    main = self._local_rows(preds[key]).reshape(nw, -1)  # boxlint: BX931 ok (predict returns host preds; per-batch D2H bounds device memory over the pass)
                    for w, b in enumerate(raw_steps[i]):
                        if i >= real_batches[w]:
                            continue  # wrapped duplicate batch
                        preds_all.append(main[w][b.ins_valid])
                        labels_all.append(b.labels[b.ins_valid])
            finally:
                stream.close()
        finally:
            self.table.set_test_mode(False)
        if not preds_all:
            return np.empty(0, np.float32), np.empty(0, np.int32)
        return np.concatenate(preds_all), np.concatenate(labels_all)

    def merged_params(self):
        """Single-copy dense params for eval/checkpoint (k_step mode keeps
        per-device replicas; others are already one copy)."""
        if self.k_step > 1:
            return jax.tree.map(lambda x: np.asarray(x).mean(0), self.params)
        return self.params

    def merged_opt_state(self):
        """Single-copy optimizer state for checkpoints — the k_step merge
        merged_params applies, on the moments (float leaves average, int
        leaves like the adam count are identical replicas: take one), so
        a base model never bakes the mesh size into dense.pkl."""
        if self.k_step > 1:
            def _merge(x):
                a = np.asarray(x)
                if a.ndim and np.issubdtype(a.dtype, np.floating):
                    return a.mean(0)
                return a[0] if a.ndim else a
            return jax.tree.map(_merge, self.opt_state)
        return self.opt_state

    def _local_rows(self, arr: jax.Array) -> np.ndarray:
        """Host copy of this process's piece of a mesh-sharded output
        (shard_map out_specs P(axis) concatenates per-device values on axis
        0, so preds are globally [P*B]), local shards in ascending global
        offset = local-worker order. Single process: the whole array."""
        if not self.multiprocess:
            return np.asarray(arr)
        shards = []
        for sh in arr.addressable_shards:
            pos = sh.index[0] if sh.index else slice(0, None)
            start = (pos.start or 0) if isinstance(pos, slice) else int(pos)
            shards.append((start, np.asarray(sh.data)))
        shards.sort(key=lambda t: t[0])
        return np.concatenate([d for _, d in shards], axis=0)

    def _dump_step(self, rows, step_batches) -> None:
        """DumpField per worker batch (one line per real instance with the
        requested fields), this process's rows only. rows: the per-task
        host copies [n_local, B] _add_metrics already made."""
        from paddlebox_tpu.train.dump import build_dump_tensors
        main = (self.model.task_names[0] if self.multi_task
                else list(rows)[0])
        for w, b in enumerate(step_batches):
            tensors = build_dump_tensors(
                self.cfg.dump_fields, b.labels,
                {t: arr[w] for t, arr in rows.items()}, main)
            if tensors:
                self.dump_writer.dump_batch(tensors, ins_ids=b.ins_ids,
                                            mask=b.ins_valid)

    def close(self) -> None:
        """Flush and stop the dump writers + the stager pool + telemetry
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

    def _add_metrics(self, preds, step_batches: Tuple[PackedBatch, ...]) -> None:
        """Streams this process's rows only; cross-process reduction happens
        in get_metric_msg via the fleet allreduce hook (the reference's
        box MPI allreduce in Metric::calculate)."""
        need_dump = self.dump_writer is not None
        need_metrics = ((bool(self.metrics.metric_names())
                         or self.quality is not None)
                        and not self._collect_T)
        # device-collect mode: the jitted step already bucketed this
        # batch on device — touching preds here would D2H them
        if not (need_dump or need_metrics):
            return
        nw = len(step_batches)
        # ONE host copy per task, shared by dump and metrics
        rows = {t: self._local_rows(p).reshape(nw, -1)
                for t, p in preds.items()}
        if need_dump:
            self._dump_step(rows, step_batches)
        if not need_metrics:
            return
        # pytree dicts come back key-SORTED across the jit boundary, so
        # the main task is named explicitly, not taken positionally
        main = (self.model.task_names[0] if self.multi_task
                else list(rows)[0])
        labels = np.stack([b.labels for b in step_batches])
        mask = np.stack([b.ins_valid for b in step_batches])
        tensors = {"pred": rows[main].reshape(-1),
                   "label": labels.reshape(-1),
                   "mask": mask.reshape(-1)}
        if step_batches[0].cmatch_rank is not None:
            tensors["cmatch_rank"] = np.stack(
                [b.cmatch_rank for b in step_batches]).reshape(-1)
        for t in (step_batches[0].task_labels or {}):
            tensors["label_" + t] = np.stack(
                [b.task_labels[t] for b in step_batches]).reshape(-1)
        for t, arr in rows.items():
            tensors["pred_" + t] = arr.reshape(-1)
        self.metrics.add_batch(tensors)
        if self.quality is not None:
            self.quality.add_batch(tensors)
            for w, b in enumerate(step_batches):
                self.quality.add_slot_batch(
                    rows[main][w], b.labels, b.slots, b.segments,
                    b.valid, self.num_slots)
            from paddlebox_tpu.metrics import drift as _drift
            _drift.observe_preds(tensors["pred"], mask=tensors["mask"])
