"""Vectorized in-table sparse optimizers.

Numeric-parity re-implementation of the HeterPS in-hashtable optimizers
(paddle/fluid/framework/fleet/heter_ps/optimizer.cuh.h): SparseAdagradOptimizer
(cuh:31-145), SparseAdamOptimizer (cuh:148-330), SparseAdamSharedOptimizer,
plus a naive SGD. Where the reference updates one feature per CUDA thread via
pointer arithmetic, here the whole deduped batch updates as one fused XLA
computation over a [N, width] row matrix — gather → update → scatter, all
static-shaped, which is how the MXU/VPU wants it.

Update semantics (dy_mf_update_value, cuh:209-303):
  slot        = g_slot
  show       += g_show ; click += g_click
  delta_score += nonclk_coeff*(g_show-g_click) + clk_coeff*g_click
  embed_w     adagrad/adam step with scale = g_show
  embedx      lazily created when show/click score crosses
              mf_create_thresholds (uniform [0, mf_initial_range)), else
              stepped like embed_w
Rows whose merged g_show == 0 (padding) are returned unchanged.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from paddlebox_tpu.config.configs import SparseOptimizerConfig
from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.accessor import (PushLayout, ValueLayout,
                                              decode_slab_rows,
                                              encode_slab_rows)


def _adagrad_step(w, g2sum, g, scale, lr, initial_g2sum, min_b, max_b):
    """update_value_work (optimizer.cuh.h:42-72). w:[N,n] g:[N,n] g2sum:[N,1]."""
    scaled = g / scale
    ratio = lr * jnp.sqrt(initial_g2sum / (initial_g2sum + g2sum))
    neww = jnp.clip(w + scaled * ratio, min_b, max_b)
    new_g2sum = g2sum + jnp.mean(scaled * scaled, axis=-1, keepdims=True)
    return neww, new_g2sum


def _adam_step(w, m, v, b1p, b2p, g, scale, lr, beta1, beta2, min_b, max_b,
               eps=1e-8):
    """update_lr/update_mf (optimizer.cuh.h:159-238). Moments per-column of w;
    b1p/b2p are [N,1] power accumulators, multiplied after the step."""
    scaled = g / scale
    ratio = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
    new_m = beta1 * m + (1.0 - beta1) * scaled
    new_v = beta2 * v + (1.0 - beta2) * scaled * scaled
    neww = jnp.clip(w + ratio * (new_m / (jnp.sqrt(new_v) + eps)), min_b, max_b)
    return neww, new_m, new_v, b1p * beta1, b2p * beta2


def _fresh_uniform(prng: jax.Array, row_ids, shape, dtype,
                   maxval: float, stream: int = 0) -> jnp.ndarray:
    """Lazy-creation randoms. With row_ids: CONTENT-ADDRESSED — each row's
    draw is a pure function of (prng, its slab id), so created embeddings
    are identical no matter how a batch was deduped, routed, or merged
    (host vs device dedup, sharded vs single-chip). Without: positional."""
    if stream:
        prng = jax.random.fold_in(prng, stream)
    if row_ids is None:
        return jax.random.uniform(prng, shape, dtype, 0.0, maxval)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(prng, row_ids)
    return jax.vmap(
        lambda k: jax.random.uniform(k, shape[1:], dtype, 0.0, maxval))(keys)


def apply_push(values: jnp.ndarray, grads: jnp.ndarray, prng: jax.Array,
               layout: ValueLayout, conf: SparseOptimizerConfig,
               row_ids=None) -> jnp.ndarray:
    """Apply merged per-key gradients to their value rows.

    values: [N, layout.width]  — gathered rows of the deduped keys
    grads:  [N, push.width]    — show/click-merged gradients (g_show = number
                                 of occurrences merged into the row)
    prng:   key for lazy embedx init
    row_ids: [N] optional slab ids per row — when given, lazy-creation
            randoms are content-addressed (order/route independent)
    Returns updated rows; rows with g_show == 0 are passed through untouched.
    """
    push = PushLayout(layout.embedx_dim, layout.expand_dim)
    D = layout.embedx_dim
    g_show = grads[:, push.SHOW:push.SHOW + 1]
    g_click = grads[:, push.CLICK:push.CLICK + 1]
    active = g_show > 0
    # avoid div-by-zero on padding rows; their results are masked out anyway
    scale = jnp.where(active, g_show, 1.0)

    out = values
    out = out.at[:, acc.SLOT:acc.SLOT + 1].set(
        jnp.where(active, grads[:, push.SLOT:push.SLOT + 1],
                  values[:, acc.SLOT:acc.SLOT + 1]))
    show = values[:, acc.SHOW:acc.SHOW + 1] + g_show
    click = values[:, acc.CLICK:acc.CLICK + 1] + g_click
    out = out.at[:, acc.SHOW:acc.SHOW + 1].set(show)
    out = out.at[:, acc.CLICK:acc.CLICK + 1].set(click)
    out = out.at[:, acc.DELTA_SCORE:acc.DELTA_SCORE + 1].add(
        conf.nonclk_coeff * (g_show - g_click) + conf.clk_coeff * g_click)
    # a pushed key was seen this pass
    out = out.at[:, acc.UNSEEN_DAYS:acc.UNSEEN_DAYS + 1].set(
        jnp.where(active, 0.0, values[:, acc.UNSEEN_DAYS:acc.UNSEEN_DAYS + 1]))

    w = values[:, acc.EMBED_W:acc.EMBED_W + 1]
    g = grads[:, push.EMBED_G:push.EMBED_G + 1]
    es = layout.embed_state
    xw0 = layout.embedx_w
    xs = layout.embedx_state
    xg = grads[:, push.embedx_g:push.embedx_g + D]
    embedx = values[:, xw0:xw0 + D]

    if layout.optimizer == "adagrad":
        lr = jnp.where(
            values[:, acc.SLOT:acc.SLOT + 1] == float(conf.nodeid_slot),
            conf.mf_learning_rate, conf.feature_learning_rate)
        neww, newg2 = _adagrad_step(
            w, values[:, es:es + 1], g, scale, lr,
            conf.mf_initial_g2sum, conf.mf_min_bound, conf.mf_max_bound)
        out = out.at[:, acc.EMBED_W:acc.EMBED_W + 1].set(neww)
        out = out.at[:, es:es + 1].set(newg2)
        newx, newxg2 = _adagrad_step(
            embedx, values[:, xs:xs + 1], xg, scale,
            jnp.full_like(w, conf.mf_learning_rate),
            conf.mf_initial_g2sum, conf.mf_min_bound, conf.mf_max_bound)
        embedx_updated = (newx, {xs: newxg2})
    elif layout.optimizer in ("adam", "adam_shared"):
        m, v = values[:, es:es + 1], values[:, es + 1:es + 2]
        b1p, b2p = values[:, es + 2:es + 3], values[:, es + 3:es + 4]
        neww, newm, newv, nb1, nb2 = _adam_step(
            w, m, v, b1p, b2p, g, scale, conf.learning_rate,
            conf.beta1_decay_rate, conf.beta2_decay_rate,
            conf.mf_min_bound, conf.mf_max_bound, conf.ada_epsilon)
        out = out.at[:, acc.EMBED_W:acc.EMBED_W + 1].set(neww)
        out = out.at[:, es:es + 1].set(newm)
        out = out.at[:, es + 1:es + 2].set(newv)
        out = out.at[:, es + 2:es + 3].set(nb1)
        out = out.at[:, es + 3:es + 4].set(nb2)
        if layout.optimizer == "adam":
            xm = values[:, xs:xs + D]
            xv = values[:, xs + D:xs + 2 * D]
            xb1 = values[:, xs + 2 * D:xs + 2 * D + 1]
            xb2 = values[:, xs + 2 * D + 1:xs + 2 * D + 2]
            newx, nxm, nxv, nxb1, nxb2 = _adam_step(
                embedx, xm, xv, xb1, xb2, xg, scale, conf.learning_rate,
                conf.mf_beta1_decay_rate, conf.mf_beta2_decay_rate,
                conf.mf_min_bound, conf.mf_max_bound, conf.mf_ada_epsilon)
            embedx_updated = (newx, {xs: nxm, xs + D: nxv,
                                     xs + 2 * D: nxb1, xs + 2 * D + 1: nxb2})
        else:  # adam_shared: scalar moments = mean over dims (cuh.h:332+)
            xm = values[:, xs:xs + 1]
            xv = values[:, xs + 1:xs + 2]
            xb1 = values[:, xs + 2:xs + 3]
            xb2 = values[:, xs + 3:xs + 4]
            scaled = xg / scale
            gm = jnp.mean(scaled, axis=-1, keepdims=True)
            ratio = (conf.learning_rate * jnp.sqrt(1.0 - xb2) / (1.0 - xb1))
            nxm = conf.mf_beta1_decay_rate * xm + (1 - conf.mf_beta1_decay_rate) * gm
            nxv = (conf.mf_beta2_decay_rate * xv
                   + (1 - conf.mf_beta2_decay_rate)
                   * jnp.mean(scaled * scaled, axis=-1, keepdims=True))
            newx = jnp.clip(
                embedx + ratio * (nxm / (jnp.sqrt(nxv) + conf.mf_ada_epsilon)),
                conf.mf_min_bound, conf.mf_max_bound)
            embedx_updated = (newx, {
                xs: nxm, xs + 1: nxv,
                xs + 2: xb1 * conf.mf_beta1_decay_rate,
                xs + 3: xb2 * conf.mf_beta2_decay_rate})
    elif layout.optimizer == "naive":
        out = out.at[:, acc.EMBED_W:acc.EMBED_W + 1].set(
            jnp.clip(w + conf.learning_rate * (g / scale),
                     conf.min_bound, conf.max_bound))
        embedx_updated = (
            jnp.clip(embedx + conf.mf_learning_rate * (xg / scale),
                     conf.mf_min_bound, conf.mf_max_bound), {})
    else:
        raise ValueError(layout.optimizer)

    # lazy embedx creation vs update (dy_mf_update_value, cuh.h:105-133)
    mf_size = values[:, acc.MF_SIZE:acc.MF_SIZE + 1]
    score = conf.nonclk_coeff * (show - click) + conf.clk_coeff * click
    create = (mf_size == 0) & (score >= conf.mf_create_thresholds) & active
    fresh = _fresh_uniform(prng, row_ids, embedx.shape, embedx.dtype,
                           conf.mf_initial_range)
    newx, state_updates = embedx_updated
    has_mf = mf_size > 0
    out = out.at[:, xw0:xw0 + D].set(
        jnp.where(create, fresh, jnp.where(has_mf & active, newx, embedx)))
    for col, newstate in state_updates.items():
        wdt = newstate.shape[-1]
        oldstate = values[:, col:col + wdt]
        out = out.at[:, col:col + wdt].set(
            jnp.where(has_mf & active, newstate, oldstate))
    out = out.at[:, acc.MF_SIZE:acc.MF_SIZE + 1].set(
        jnp.where(create, float(D), mf_size))

    # expand-embedding block (pull_box_extended_sparse backward): shares the
    # embedx lazy-creation gate, shared-g2sum adagrad or naive update
    E = layout.expand_dim
    if E:
        ew0 = layout.expand_w
        expand = values[:, ew0:ew0 + E]
        eg = grads[:, push.expand_g:push.expand_g + E]
        if layout.optimizer == "adagrad":
            es2 = layout.expand_state
            newe, newe_g2 = _adagrad_step(
                expand, values[:, es2:es2 + 1], eg, scale,
                jnp.full_like(w, conf.mf_learning_rate),
                conf.mf_initial_g2sum, conf.mf_min_bound, conf.mf_max_bound)
            out = out.at[:, es2:es2 + 1].set(
                jnp.where(has_mf & active, newe_g2, values[:, es2:es2 + 1]))
        else:  # naive
            newe = jnp.clip(expand + conf.mf_learning_rate * (eg / scale),
                            conf.mf_min_bound, conf.mf_max_bound)
        fresh_e = _fresh_uniform(prng, row_ids, expand.shape, expand.dtype,
                                 conf.mf_initial_range, stream=1)
        out = out.at[:, ew0:ew0 + E].set(
            jnp.where(create, fresh_e,
                      jnp.where(has_mf & active, newe, expand)))

    # padding / zero-show rows pass through untouched
    return jnp.where(active, out, values)


def push_sparse_dedup(slab: jnp.ndarray, ids: jnp.ndarray,
                      grads: jnp.ndarray, prng: jax.Array,
                      layout: ValueLayout,
                      conf: SparseOptimizerConfig) -> jnp.ndarray:
    """Per-batch id-dedup → gradient merge → optimizer → scatter, on a full
    pass slab. The fused-train-step building block (PushSparseGradCaseGPU:
    CopyForPush merge + PushSparseGPU, box_wrapper_impl.h:373-522).

    ids: [K] pass-local ids, padding = slab.shape[0]-1 (trash row).
    grads: [K, push.width]; padding rows must be all-zero (g_show=0).
    """
    K = ids.shape[0]
    trash = slab.shape[0] - 1
    with jax.named_scope("push_merge"):
        uids, inv = jnp.unique(ids, size=K, fill_value=trash,
                               return_inverse=True)
        merged = jnp.zeros((K, grads.shape[1]),
                           grads.dtype).at[inv].add(grads)
    with jax.named_scope("push_opt"):
        rows = decode_slab_rows(slab[uids], layout)
        new_rows = apply_push(rows, merged, prng, layout, conf,
                              row_ids=uids)
    with jax.named_scope("push_write"):
        return slab.at[uids].set(encode_slab_rows(new_rows, layout))


def rebuild_uids(ids: jnp.ndarray, perm: jnp.ndarray, inv: jnp.ndarray,
                 pad_base: int) -> jnp.ndarray:
    """Reconstruct dedup_ids' uids on device from (ids, perm, inv) — cheaper
    than transferring them: out-of-slab defaults (pad_base+i, unique, drop at
    the scatter), then each group's id scatter-set from its permuted
    occurrences (duplicate indices all write the same value)."""
    K = ids.shape[0]
    return (jnp.arange(K, dtype=jnp.int32) + pad_base).at[inv].set(ids[perm])


def push_sparse_hostdedup(slab: jnp.ndarray, uids: jnp.ndarray,
                          perm: jnp.ndarray, inv_sorted: jnp.ndarray,
                          grads: jnp.ndarray, prng: jax.Array,
                          layout: ValueLayout,
                          conf: SparseOptimizerConfig,
                          pulled_rows: Optional[jnp.ndarray] = None
                          ) -> jnp.ndarray:
    """Push with HOST-precomputed dedup (PassTable.dedup_for_push): no
    on-device sort. jnp.unique in push_sparse_dedup lowers to an XLA sort of
    the whole key vector per step — measured as the dominant cost of the
    fused step on v5e — while the host already walks the batch's keys to
    assign pass-local ids, so the dedup rides the (overlapped) host stage
    instead (DedupKeysAndFillIdx done host-side, box_wrapper_impl.h:129).

    uids:       [U] unique ids, U <= K the push's unique-row domain
                (pass_table.push_domain; K when the host stages one slot
                an occurrence); tail padded with ids >= capacity, which
                drop at the scatter
    perm:       [K] occurrence indices grouped by unique id
    inv_sorted: [K] nondecreasing merged-row index per permuted occurrence
    grads:      [K, push.width] per-occurrence push rows (padding all-zero)
    pulled_rows: [U, width] optional pull-gather reuse (see _merged_new_rows)
    The write is the donated row scatter; the rebuild twin lives in
    push_sparse_rebuild.
    """
    new_rows = _merged_new_rows(slab, uids, perm, inv_sorted, grads, prng,
                                layout, conf, pulled_rows)
    with jax.named_scope("push_write"):
        # out-of-range padding ids drop; in-range ids are unique by
        # construction
        return slab.at[uids].set(encode_slab_rows(new_rows, layout),
                                 mode="drop", unique_indices=True)


def _merged_new_rows(slab, uids, perm, inv_sorted, grads, prng, layout,
                     conf, pulled_rows=None) -> jnp.ndarray:
    """Shared push prologue: occurrence gather → sorted segment-sum merge →
    row gather → in-table optimizer. Both slab-write strategies (scatter /
    rebuild) consume these rows — keep them in one place so merge or
    lazy-init fixes can't diverge between the two.

    pulled_rows [U, width]: the rows of ``uids`` as the step's pull
    already gathered them (ops/sparse.pull_sparse_unique: DECODED f32
    under the bf16 slab diet, from this same pre-update slab, an
    out-of-slab padding uid clipped onto the trash row exactly as the
    gather below clips it); when given they ARE the push's rows and the
    slab is not read a second time.
    The merge and the update run over uids.shape[0] slots, padding
    included: the staged domain is their whole cost. A padding slot's
    g_show == 0 row passes through untouched and is never written back."""
    with jax.named_scope("push_merge"):
        sorted_grads = jnp.take(grads, perm, axis=0,
                                indices_are_sorted=False,
                                unique_indices=True)
        merged = jax.ops.segment_sum(sorted_grads, inv_sorted,
                                     num_segments=uids.shape[0],
                                     indices_are_sorted=True)
    with jax.named_scope("push_opt"):
        rows = pulled_rows
        if rows is None:
            rows = decode_slab_rows(
                jnp.take(slab, uids, axis=0, mode="clip"), layout)
        return apply_push(rows, merged, prng, layout, conf, row_ids=uids)


def push_sparse_uidwire(slab: jnp.ndarray, uids: jnp.ndarray,
                        ids: jnp.ndarray, grads: jnp.ndarray,
                        prng: jax.Array, layout: ValueLayout,
                        conf: SparseOptimizerConfig,
                        pulled_rows: Optional[jnp.ndarray] = None,
                        write: str = "scatter") -> jnp.ndarray:
    """Uid-wire push (round 8; the sharded runners' staging under
    h2d_uid_wire): the host ships ONLY the SORTED deduped uid vector
    ([K] int32); every other dedup product derives on device —

      inv    binary search of each occurrence's id against the sorted
             uids (jnp.searchsorted: ~log2 K gather/compare rounds, no
             full device sort, no jnp.unique with a padded size=)
      merge  segment scatter-add over inv — same per-unique ascending-
             occurrence addition order as push_sparse_hostdedup's sorted
             segment-sum, so the merged grads are bit-identical
      first  scatter-min of occurrence indices (the index into the
             occurrence-domain pulled_rows; BoxTrainer's pull hands the
             push the rows of uids themselves and needs none)
      pos    (write='rebuild') one [capacity] int32 scatter — the map
             pos_for_rebuild stages host-side, at 4 bytes/slab-row H2D

    uids: [K] NONDECREASING unique ids, tail padded with out-of-slab ids
          (pass_table.dedup_uids_sorted — NOT dedup_ids, whose native
          fast path returns hash order; sortedness is load-bearing here).
    ids:  [K] the batch's per-occurrence ids (already on the wire for the
          pull); every entry must be present in uids.
    pulled_rows: optional pull-gather reuse. A caller staging IN-RANGE
          padding uids must pass None: an inactive row's pass-through
          value then comes from a real slab gather, never from an
          arbitrary occurrence's row.
    Reference work shape: PushSparseGradCaseGPU merge + update
    (box_wrapper_impl.h:373-522); dedup never skipped (impl.h:129).
    """
    K = ids.shape[0]
    U = uids.shape[0]
    if write not in ("scatter", "rebuild"):
        raise ValueError(f"uid-wire write strategy {write!r} "
                         "(scatter or rebuild)")
    with jax.named_scope("push_merge"):
        inv = jnp.searchsorted(uids, ids).astype(jnp.int32)
        merged = jax.ops.segment_sum(grads, inv, num_segments=U)
    with jax.named_scope("push_opt"):
        if pulled_rows is not None:
            first = jnp.full((U,), K - 1, jnp.int32).at[inv].min(
                jnp.arange(K, dtype=jnp.int32))
            rows = jnp.take(pulled_rows, first, axis=0)
        else:
            rows = decode_slab_rows(
                jnp.take(slab, uids, axis=0, mode="clip"), layout)
        new_rows = encode_slab_rows(
            apply_push(rows, merged, prng, layout, conf, row_ids=uids),
            layout)
    with jax.named_scope("push_write"):
        if write == "rebuild":
            pos = jnp.full((slab.shape[0],), -1, jnp.int32).at[uids].set(
                jnp.arange(U, dtype=jnp.int32), mode="drop",
                unique_indices=True)
            sel = jnp.take(new_rows, jnp.clip(pos, 0, U - 1), axis=0)
            return jnp.where((pos >= 0)[:, None], sel, slab)
        return slab.at[uids].set(new_rows, mode="drop", unique_indices=True)


def push_sparse_rebuild(slab: jnp.ndarray, uids: jnp.ndarray,
                        pos: jnp.ndarray, perm: jnp.ndarray,
                        inv_sorted: jnp.ndarray, grads: jnp.ndarray,
                        prng: jax.Array, layout: ValueLayout,
                        conf: SparseOptimizerConfig,
                        pulled_rows: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """push_sparse_hostdedup with the final row SCATTER replaced by a
    full-slab gather-rebuild: out[r] = new_rows[pos[r]] if pos[r] >= 0 else
    slab[r], with pos ([capacity] int32, -1 = untouched) precomputed on the
    host next to the dedup (PassTable.pos_for_rebuild).

    Same alternative lowering, identical results; exists because scatter
    cost scales with index count while this rebuild is one gather + one
    select at flat cost ~ slab bytes / copy bandwidth — the better trade
    whenever touched-row count is large relative to the slab (big batches,
    merged chunks). resolve_push_write picks between the two.
    Reference work shape: PushSparseGradCaseGPU merge + update
    (box_wrapper_impl.h:373-522); the write strategy is ours.
    """
    if uids.shape[0] == 0:
        # the clip below would otherwise build the inverted range [0, -1];
        # an empty dedup touches nothing by definition
        return slab
    new_rows = _merged_new_rows(slab, uids, perm, inv_sorted, grads, prng,
                                layout, conf, pulled_rows)
    with jax.named_scope("push_write"):
        new_rows = encode_slab_rows(new_rows, layout)
        sel = jnp.take(new_rows, jnp.clip(pos, 0, new_rows.shape[0] - 1),
                       axis=0)
        return jnp.where((pos >= 0)[:, None], sel, slab)


def make_push_fn(layout: ValueLayout,
                 conf: SparseOptimizerConfig) -> Callable:
    """jit-compiled closure over static layout/conf. Operates on DECODED
    f32 rows on both sides: the slab codec boundary (bf16 dtype diet)
    lives at the slab gather/write sites inside the push_sparse_* entry
    points, never inside the optimizer math — callers holding an encoded
    slab decode rows first (accessor.decode_slab_rows) and encode the
    result back."""
    from paddlebox_tpu.obs.device import instrument_jit
    return instrument_jit(
        functools.partial(apply_push, layout=layout, conf=conf),
        "apply_push")
