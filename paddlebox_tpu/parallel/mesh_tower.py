"""Mesh trainer for model-parallel towers (TP wide layers / EP experts).

The consumer the TP/EP primitives lacked (round-3 verdict): a trainer that
runs the full sparse hot loop — pull → seqpool+CVM → MODEL-PARALLEL tower
→ push — with the tower's wide/expert leaves sharded over a `mp` mesh axis
and the TP autodiff contracts enforced IN CODE:

  * the per-device replicated loss is scaled by 1/P (tp_loss_scale);
  * every replicated leaf's gradient — post-psum params, the MoE gate,
    and the embedding cotangent feeding the sparse push — is psum'd
    across the axis (tp_fix_grads), so no caller can silently train on a
    partial gradient (the footgun ep_experts_apply documents).

The pass slab and batch stay replicated over the axis: model parallelism
here buys tower WIDTH (per-device tower memory O(d_wide/P)), not table
capacity — compose with ShardedBoxTrainer's topology when both are needed.
Every device computes the identical push (psum'd demb, shared prng), so
the slab replicas never diverge (same invariant as CtrPipelineRunner's
replicated slab, tested against the dense oracle).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.config.configs import (DataFeedConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data.packer import PackedBatch
from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                push_sparse_rebuild,
                                                rebuild_uids)
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm
from paddlebox_tpu.ops.sparse import build_push_grads, pull_sparse
from paddlebox_tpu.parallel.tensor_parallel import (tp_fix_grads,
                                                    tp_loss_scale)

MP_AXIS = "mp"


class MeshTowerTrainer:
    """Single-table CTR training with a model-parallel tower.

    model: a mesh-aware zoo entry (models/wide_tower.py contract:
    host_init(seed) -> (host_params, sharded_mask); apply_local(p, pooled,
    axis) -> [B] logits)."""

    def __init__(self, model, table_cfg: TableConfig, feed: DataFeedConfig,
                 trainer_cfg: Optional[TrainerConfig] = None,
                 mesh: Optional[Mesh] = None, use_cvm: bool = True,
                 seed: int = 0) -> None:
        self.model = model
        self.cfg = trainer_cfg or TrainerConfig()
        self.feed = feed
        if mesh is None:
            devs = np.array(jax.devices()[:model.n_shards])
            mesh = Mesh(devs, (MP_AXIS,))
        if len(mesh.axis_names) != 1:
            raise ValueError("MeshTowerTrainer meshes are 1D (mp,)")
        if int(mesh.devices.size) != model.n_shards:
            raise ValueError("mesh size %d != model.n_shards %d"
                             % (mesh.devices.size, model.n_shards))
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.table = PassTable(table_cfg, seed=seed)
        from paddlebox_tpu.train.trainer import resolve_push_write
        self._push_write = resolve_push_write(
            capacity=table_cfg.pass_capacity,
            batch_keys=feed.key_capacity())
        self.layout = self.table.layout
        self.num_slots = len(feed.used_sparse_slots())
        self.use_cvm = use_cvm
        host_params, self.sharded = model.host_init(seed)
        sh = NamedSharding(mesh, P(self.axis))
        rep = NamedSharding(mesh, P())
        self.params = {
            k: jax.device_put(v, sh if self.sharded[k] else rep)
            for k, v in host_params.items()}
        self.opt = optax.adam(self.cfg.dense_lr)
        host_opt = self.opt.init(host_params)
        # moments partition exactly like the params they track: adam's
        # mu/nu mirror the params dict, so the model's sharded mask joins
        # STRUCTURALLY (shape heuristics would misclassify a replicated
        # leaf that happens to share a sharded leaf's shape)
        self._opt_sharded = self._opt_mask(host_opt)
        self.opt_state = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), sh if s else rep),
            host_opt, self._opt_sharded)
        self._prng = jax.random.PRNGKey(seed + 13)
        from paddlebox_tpu.metrics.auc import MetricRegistry
        self.metrics = MetricRegistry()
        self._step, self._eval = self._build_step()

    def _opt_mask(self, node):
        """Structural sharded-mask for an optax state tree: dict nodes
        whose keys mirror the params dict take the model's mask per key;
        everything else (count scalars, empty states) is replicated."""
        if isinstance(node, dict) and set(node) == set(self.sharded):
            return {k: bool(self.sharded[k]) for k in node}
        if isinstance(node, tuple):
            parts = [self._opt_mask(c) for c in node]
            return (type(node)(*parts) if hasattr(node, "_fields")
                    else tuple(parts))
        if isinstance(node, list):
            return [self._opt_mask(c) for c in node]
        return False

    # ------------------------------------------------------------- jit step
    def _build_step(self):
        model = self.model
        layout, conf = self.layout, self.table.config.optimizer
        B = self.feed.batch_size
        S = self.num_slots
        use_cvm = self.use_cvm
        axis = self.axis
        sharded = self.sharded
        opt_sharded = self._opt_sharded
        opt = self.opt
        pad_base = self.table.config.pass_capacity

        def step(params, opt_state, slab, batch, prng):
            local = {k: (v[0] if sharded[k] else v)
                     for k, v in params.items()}
            local_opt = jax.tree.map(
                lambda x, s: x[0] if s else x, opt_state, opt_sharded)
            prng, sub = jax.random.split(prng)
            key_valid = batch["ids"] != pad_base - 1
            emb = pull_sparse(slab, batch["ids"], layout)

            def loss_fn(p, emb):
                pooled = fused_seqpool_cvm(
                    emb, batch["segments"], key_valid, B, S, use_cvm,
                    sorted_segments=True)
                logits = model.apply_local(p, pooled, axis)
                lab = batch["labels"].astype(jnp.float32)
                iv = batch["ins_valid"]
                bce = optax.sigmoid_binary_cross_entropy(logits, lab)
                denom = jnp.maximum(iv.sum(), 1.0)
                loss = jnp.where(iv, bce, 0.0).sum() / denom
                # contract half 1: replicated loss scales by 1/P pre-grad
                return tp_loss_scale(loss, axis), jax.nn.sigmoid(logits)

            grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1),
                                         has_aux=True)
            (loss, preds), (dparams, demb) = grad_fn(local, emb)
            # contract half 2: replicated leaves (and the embedding
            # cotangent) psum their partial grads; sharded leaves are exact
            dparams = tp_fix_grads(dparams, sharded, axis)
            demb = jax.lax.psum(demb, axis)
            loss = loss * jax.lax.axis_size(axis)   # report the true loss
            updates, local_opt = opt.update(dparams, local_opt, local)
            local = optax.apply_updates(local, updates)

            clicks = batch["labels"][batch["segments"] // S]
            pg = build_push_grads(demb, batch["segments"] % S, clicks,
                                  key_valid)
            uids = batch.get("uids")
            if uids is None:
                uids = rebuild_uids(batch["ids"], batch["perm"],
                                    batch["inv"], pad_base)
            # shared prng + psum'd demb → bit-identical push everywhere;
            # the replicated slab cannot diverge
            if "push_pos" in batch:
                slab = push_sparse_rebuild(slab, uids, batch["push_pos"],
                                           batch["perm"], batch["inv"],
                                           pg, sub, layout, conf)
            else:
                slab = push_sparse_hostdedup(
                    slab, uids, batch["perm"], batch["inv"], pg, sub,
                    layout, conf)
            params = {k: (v[None] if sharded[k] else v)
                      for k, v in local.items()}
            opt_state = jax.tree.map(
                lambda x, s: x[None] if s else x, local_opt, opt_sharded)
            return slab, params, opt_state, loss, preds, prng

        def eval_step(params, slab, batch):
            # test-mode inference: same model-parallel forward, no push
            local = {k: (v[0] if sharded[k] else v)
                     for k, v in params.items()}
            key_valid = batch["ids"] != pad_base - 1
            emb = pull_sparse(slab, batch["ids"], layout)
            pooled = fused_seqpool_cvm(
                emb, batch["segments"], key_valid, B, S, use_cvm,
                sorted_segments=True)
            return jax.nn.sigmoid(model.apply_local(local, pooled, axis))

        spec_p = {k: (P(self.axis) if self.sharded[k] else P())
                  for k in self.sharded}
        opt_spec = jax.tree.map(
            lambda s: P(self.axis) if s else P(), opt_sharded)
        fn = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(spec_p, opt_spec, P(), P(), P()),
            out_specs=(P(), spec_p, opt_spec, P(), P(), P()),
            check_vma=False)
        efn = jax.shard_map(
            eval_step, mesh=self.mesh, in_specs=(spec_p, P(), P()),
            out_specs=P(), check_vma=False)
        from paddlebox_tpu.obs.device import instrument_jit
        return (instrument_jit(fn, "mesh_tower_step", donate_argnums=(2,)),
                instrument_jit(efn, "mesh_tower_eval"))

    # ----------------------------------------------------------- host driver
    def host_batch(self, b: PackedBatch) -> Dict[str, jnp.ndarray]:
        from paddlebox_tpu.obs.device import account_h2d, tree_nbytes
        ids = self.table.lookup_ids(b.keys, b.valid)
        host = {
            "ids": ids,
            "segments": b.segments,
            "labels": b.labels,
            "ins_valid": b.ins_valid,
        }
        if not self.table.test_mode:
            # eval never pushes — skip the dedup + transfers; uids ride the
            # host stage (device reconstruction is a scatter), and rebuild
            # mode stages the pos map for the scatter-free slab write
            uids, perm, inv, _n_u = self.table.dedup_for_push(ids)
            host.update(perm=perm, inv=inv, uids=uids)
            if self._push_write == "rebuild":
                host["push_pos"] = self.table.pos_for_rebuild(uids)
        account_h2d(tree_nbytes(host))  # everything staged below
        return {k: jnp.asarray(v) for k, v in host.items()}

    def train_batch(self, b: PackedBatch) -> float:
        from paddlebox_tpu.train.eval_driver import feed_simple_metrics
        batch = self.host_batch(b)
        (slab, self.params, self.opt_state, loss, preds,
         self._prng) = self._step(self.params, self.opt_state,
                                  self.table.slab, batch, self._prng)
        self.table.set_slab(slab)
        feed_simple_metrics(self.metrics, preds, b)
        return float(loss)

    def train_pass(self, dataset) -> Dict[str, float]:
        """BoxPS pass cadence: feed pass → slab → per-batch steps →
        write-back."""
        self.table.begin_feed_pass()
        dataset.load_into_memory(add_keys_fn=self.table.add_keys)
        self.table.end_feed_pass()
        self.table.begin_pass()
        losses = [self.train_batch(b)
                  for b in dataset.split_batches(num_workers=1)[0]]
        self.table.end_pass()
        return {"loss": float(np.mean(losses)) if losses else 0.0,
                "batches": len(losses)}

    def predict_batches(self, dataset):
        """Test-mode inference (SetTestMode: no creation, no push) —
        (preds, labels) over the dataset's valid instances."""
        from paddlebox_tpu.train.eval_driver import simple_predict_batches
        return simple_predict_batches(self, dataset)
