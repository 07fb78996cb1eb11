"""Sequence-parallel CTR training: the behavior-sequence hot loop.

The long-context capability as a TRAINED path, not a bare primitive: a
designated slot's feasign history keeps its order, embeds through the same
pass slab as every pooled slot, and self-attends with the sequence axis
sharded over an `sp` mesh — ring attention's ppermute ring (or Ulysses'
all_to_all) carries the K/V traffic on ICI while each device holds only
T/P positions (O(T/P) activation memory: histories longer than one
device's HBM train by adding devices).

Gradient contracts (the measured shard_map rules, parallel/
tensor_parallel.py): the loss is computed replicated from psum'd
activations, so it scales by 1/P before grad; every REPLICATED leaf's
grad (all params, the pooled-path embedding cotangent) psums back, while
the SEQUENCE embedding cotangent is shard-local and exact. The push
all_gathers the sequence chunks so every device applies one identical
combined update to the replicated slab — host-precomputed dedup, no
device sort."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.config.configs import (DataFeedConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data.packer import PackedBatch
from paddlebox_tpu.embedding.optimizers import (push_sparse_hostdedup,
                                                rebuild_uids)
from paddlebox_tpu.embedding.pass_table import PassTable
from paddlebox_tpu.ops.seqpool import fused_seqpool_cvm
from paddlebox_tpu.ops.sparse import build_push_grads, pull_sparse
from paddlebox_tpu.parallel.tensor_parallel import tp_loss_scale

SP_AXIS = "sp"


class SeqCtrTrainer:
    """Single-table trainer for BstSeqCtr-contract models.

    seq_slot: index (in used-sparse-slot order) of the history slot whose
    keys feed the attention sequence. That slot ALSO rides the pooled
    path (its CVM-pooled summary joins the tower like any slot); the
    sequence view is additive, mirroring how join-phase models consume
    rank_offset alongside the pooled features."""

    def __init__(self, model, table_cfg: TableConfig, feed: DataFeedConfig,
                 trainer_cfg: Optional[TrainerConfig] = None,
                 seq_slot: int = 0, mesh: Optional[Mesh] = None,
                 use_cvm: bool = True, seed: int = 0) -> None:
        self.model = model
        self.cfg = trainer_cfg or TrainerConfig()
        self.feed = feed
        self.seq_slot = seq_slot
        if mesh is None:
            devs = np.array(jax.devices()[:model.n_shards])
            mesh = Mesh(devs, (SP_AXIS,))
        if len(mesh.axis_names) != 1:
            raise ValueError("SeqCtrTrainer meshes are 1D (sp,)")
        if int(mesh.devices.size) != model.n_shards:
            raise ValueError("mesh size %d != model.n_shards %d"
                             % (mesh.devices.size, model.n_shards))
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.P = int(mesh.devices.size)
        self.table = PassTable(table_cfg, seed=seed)
        self.layout = self.table.layout
        self.num_slots = len(feed.used_sparse_slots())
        if not (0 <= seq_slot < self.num_slots):
            raise ValueError(f"seq_slot {seq_slot} out of range "
                             f"[0, {self.num_slots})")
        self.use_cvm = use_cvm
        self.T = model.seq_len
        host_params, _sharded = model.host_init(seed)
        rep = NamedSharding(mesh, P())
        self.params = {k: jax.device_put(v, rep)
                       for k, v in host_params.items()}
        self.opt = optax.adam(self.cfg.dense_lr)
        self.opt_state = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), rep),
            self.opt.init(host_params))
        self._prng = jax.random.PRNGKey(seed + 29)
        from paddlebox_tpu.metrics.auc import MetricRegistry
        self.metrics = MetricRegistry()
        self._step, self._eval = self._build_step()

    # ------------------------------------------------------------- jit step
    def _build_step(self):
        model = self.model
        layout, conf = self.layout, self.table.config.optimizer
        B = self.feed.batch_size
        S = self.num_slots
        T, Pn = self.T, self.P
        Tl = T // Pn
        use_cvm = self.use_cvm
        axis = self.axis
        opt = self.opt
        pad_id = self.table.config.pass_capacity - 1
        pad_base = self.table.config.pass_capacity
        seq_slot = self.seq_slot

        def step(params, opt_state, slab, batch, prng):
            # batch: pooled leaves replicated; seq_ids/seq_valid [B, T/P]
            # sharded over sp (this device's chunk)
            prng, sub = jax.random.split(prng)
            key_valid = batch["ids"] != pad_id
            emb_pool = pull_sparse(slab, batch["ids"], layout)
            emb_seq = pull_sparse(
                slab, batch["seq_ids"].reshape(-1), layout
            ).reshape(B, Tl, -1)

            def loss_fn(p, emb_pool, emb_seq):
                pooled = fused_seqpool_cvm(
                    emb_pool, batch["segments"], key_valid, B, S, use_cvm,
                    sorted_segments=True)
                feat = model.seq_feature_local(p, emb_seq,
                                               batch["seq_valid"], axis)
                logits = model.head_apply(p, pooled, feat)
                lab = batch["labels"].astype(jnp.float32)
                iv = batch["ins_valid"]
                bce = optax.sigmoid_binary_cross_entropy(logits, lab)
                denom = jnp.maximum(iv.sum(), 1.0)
                loss = jnp.where(iv, bce, 0.0).sum() / denom
                # replicated loss from psum'd activations: 1/P pre-grad
                return tp_loss_scale(loss, axis), jax.nn.sigmoid(logits)

            grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1, 2),
                                         has_aux=True)
            (loss, preds), (dparams, demb_pool, demb_seq) = grad_fn(
                params, emb_pool, emb_seq)
            # replicated leaves psum their partial grads; the SEQ chunk
            # cotangent is shard-local and already exact
            dparams = jax.tree.map(lambda g: jax.lax.psum(g, axis),
                                   dparams)
            demb_pool = jax.lax.psum(demb_pool, axis)
            loss = loss * Pn                      # report the true loss
            updates, opt_state = opt.update(dparams, opt_state, params)
            params = optax.apply_updates(params, updates)

            # ---- push: pooled rows + the all_gathered sequence rows form
            # ONE identical update on every device (replicated slab)
            clicks = batch["labels"][batch["segments"] // S]
            pg_pool = build_push_grads(demb_pool, batch["segments"] % S,
                                       clicks, key_valid)
            demb_seq_all = jax.lax.all_gather(
                demb_seq, axis, axis=1, tiled=True)      # [B, T, Din]
            seq_valid_all = jax.lax.all_gather(
                batch["seq_valid"], axis, axis=1, tiled=True)   # [B, T]
            seq_clicks = jnp.broadcast_to(batch["labels"][:, None],
                                          (B, T)).reshape(-1)
            pg_seq = build_push_grads(
                demb_seq_all.reshape(B * T, -1),
                jnp.full((B * T,), seq_slot, jnp.int32), seq_clicks,
                seq_valid_all.reshape(-1))
            # the history slot's occurrences already count show/click once
            # through their POOLED rows — the sequence rows contribute
            # gradient only (the expand-path precedent: two gradient
            # consumers, one show per data occurrence), else the slot's
            # statistics double per occurrence
            pg_seq = pg_seq.at[:, 1:3].set(0.0)
            pg = jnp.concatenate([pg_pool, pg_seq], axis=0)
            uids = rebuild_uids(batch["push_ids"], batch["perm"],
                                batch["inv"], pad_base)
            slab = push_sparse_hostdedup(slab, uids, batch["perm"],
                                         batch["inv"], pg, sub, layout,
                                         conf)
            return slab, params, opt_state, loss, preds, prng

        def eval_step(params, slab, batch):
            # test-mode inference: pooled + attended forward, no push
            key_valid = batch["ids"] != pad_id
            emb_pool = pull_sparse(slab, batch["ids"], layout)
            emb_seq = pull_sparse(
                slab, batch["seq_ids"].reshape(-1), layout
            ).reshape(B, Tl, -1)
            pooled = fused_seqpool_cvm(
                emb_pool, batch["segments"], key_valid, B, S, use_cvm,
                sorted_segments=True)
            feat = model.seq_feature_local(params, emb_seq,
                                           batch["seq_valid"], axis)
            return jax.nn.sigmoid(model.head_apply(params, pooled, feat))

        seq_spec = P(None, self.axis)
        specs = {"ids": P(), "segments": P(), "labels": P(),
                 "ins_valid": P(), "push_ids": P(), "perm": P(),
                 "inv": P(), "seq_ids": seq_spec, "seq_valid": seq_spec}
        eval_specs = {k: specs[k] for k in (
            "ids", "segments", "labels", "ins_valid", "seq_ids",
            "seq_valid")}
        fn = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(P(), P(), P(), specs, P()),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False)
        efn = jax.shard_map(
            eval_step, mesh=self.mesh,
            in_specs=(P(), P(), eval_specs), out_specs=P(),
            check_vma=False)
        from paddlebox_tpu.obs.device import instrument_jit
        return (instrument_jit(fn, "seq_step", donate_argnums=(2,)),
                instrument_jit(efn, "seq_eval"))

    # ----------------------------------------------------------- host driver
    def seq_ids_of(self, b: PackedBatch, ids: np.ndarray):
        """Extract the history slot's pass-local ids IN ORDER → [B, T]
        (+ valid mask). The packer writes keys instance-major
        slot-ascending, so each (ins, seq_slot) run is contiguous and
        ordered; histories longer than T truncate, shorter pad with the
        trash row. Fully vectorized (rank-within-instance via bincount
        prefix sums)."""
        B, S, T = self.feed.batch_size, self.num_slots, self.T
        pad = self.table.config.pass_capacity - 1
        out = np.full((B, T), pad, dtype=np.asarray(ids).dtype)
        order = np.nonzero((b.slots == self.seq_slot) & b.valid)[0]
        if order.size:
            ins = b.segments[order] // S
            counts = np.bincount(ins, minlength=B)
            starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
            rank = np.arange(order.size) - starts[ins]
            keep = rank < T
            out[ins[keep], rank[keep]] = ids[order[keep]]
        return out, out != pad

    def host_batch(self, b: PackedBatch) -> Dict[str, jnp.ndarray]:
        ids = self.table.lookup_ids(b.keys, b.valid)
        seq_ids, seq_valid = self.seq_ids_of(b, ids)
        out = {
            "ids": jnp.asarray(ids),
            "segments": jnp.asarray(b.segments),
            "labels": jnp.asarray(b.labels),
            "ins_valid": jnp.asarray(b.ins_valid),
            "seq_ids": jnp.asarray(seq_ids),
            "seq_valid": jnp.asarray(seq_valid),
        }
        if not self.table.test_mode:
            # host dedup over the CONCATENATED push id vector (pooled
            # rows then B*T sequence rows — the device builds pg in that
            # order); eval never pushes
            push_ids = np.concatenate([ids, seq_ids.reshape(-1)]).astype(
                np.asarray(ids).dtype)
            from paddlebox_tpu.embedding.pass_table import dedup_ids
            _uids, perm, inv, _n_u = dedup_ids(
                push_ids, self.table.config.pass_capacity)
            out.update(push_ids=jnp.asarray(push_ids),
                       perm=jnp.asarray(perm), inv=jnp.asarray(inv))
        return out

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
