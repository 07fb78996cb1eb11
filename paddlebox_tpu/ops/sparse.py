"""Differentiable sparse pull/push ops.

TPU-native pull_box_sparse / push_box_sparse
(paddle/fluid/operators/pull_box_sparse_op.{cc,h,cu}): the forward is a row
gather from the pass slab producing the per-key pull view
[show, click, embed_w, embedx...]; the backward is NOT a dense slab gradient
but a push-gradient construction (the grad-op-maker wires push as the
backward, pull_box_sparse_op.cc:128-141).

Two integration styles:
  * explicit (recommended, mirrors the reference worker loop): the train step
    calls pull_sparse(), differentiates the dense model w.r.t. the pulled
    embeddings, then builds push grads with build_push_grads() and applies
    them via the table's push kernel. Keeps the slab out of autodiff.
  * full-graph: pull_sparse_differentiable() is a custom_vjp whose cotangent
    w.r.t. the slab is a scatter-add — lets jax.grad flow end-to-end when a
    model wants that (costs a dense slab-shaped cotangent).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.accessor import (PushLayout, ValueLayout,
                                              decode_slab_rows)


@jax.named_scope("pull")
def pull_view_from_rows(rows: jnp.ndarray,
                        layout: ValueLayout) -> jnp.ndarray:
    """Pull view [N, 3+D] (show, click, embed_w, embedx) from already
    gathered full rows — split out so a step can keep the full rows and
    hand them to the push (which needs the state columns too) without a
    second slab-wide gather."""
    D = layout.embedx_dim
    xw0 = layout.embedx_w
    return jnp.concatenate([
        rows[:, acc.SHOW:acc.SHOW + 1],
        rows[:, acc.CLICK:acc.CLICK + 1],
        rows[:, acc.EMBED_W:acc.EMBED_W + 1],
        rows[:, xw0:xw0 + D],
    ], axis=1)


@jax.named_scope("pull")
def gather_slab_rows(slab: jnp.ndarray, ids: jnp.ndarray,
                     layout: ValueLayout) -> jnp.ndarray:
    """[K, width] DECODED f32 rows gathered from the device slab — the
    one gather idiom every pull/push row-reuse site shares. Identity
    passthrough of slab[ids] for f32 layouts; under the bf16 slab diet
    (layout.embed_dtype) the gathered uint16 rows decode to f32 here, so
    downstream math (pull views, optimizer, pulled-row reuse) never sees
    encoded bits."""
    return decode_slab_rows(slab[ids], layout)


def pull_sparse(slab: jnp.ndarray, ids: jnp.ndarray,
                layout: ValueLayout) -> jnp.ndarray:
    """Gather per-key pull view [K, 3+D]: show, click, embed_w, embedx."""
    return pull_view_from_rows(gather_slab_rows(slab, ids, layout), layout)


@jax.named_scope("pull")
def pull_sparse_unique(slab: jnp.ndarray, uids: jnp.ndarray,
                       occ_uid: jnp.ndarray, layout: ValueLayout):
    """pull_sparse over the push's unique-row domain: (pull view [K, 3+D],
    DECODED rows of uids [U, width]). The slab is gathered once a distinct
    row of the batch (U slots; an out-of-slab padding uid clips onto the
    trash row), the view is made from those U rows, and each occurrence
    takes its view from that small block by its slot in uids
    (occ_uid [K], every value below the dedup's real count). Same bits as
    pull_sparse(slab, ids): a gather from the slab costs an index three
    (8,128) tiles of a multi-GB array, and a batch repeats two rows in
    three. The rows go on to the push as its rows of uids
    (optimizers._merged_new_rows pulled_rows)."""
    rows_u = decode_slab_rows(jnp.take(slab, uids, axis=0, mode="clip"),
                              layout)
    view_u = pull_view_from_rows(rows_u, layout)
    return jnp.take(view_u, occ_uid, axis=0, mode="clip"), rows_u


@jax.named_scope("push_grads")
def build_push_grads(d_emb: jnp.ndarray, slots: jnp.ndarray,
                     clicks: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Per-key push rows [K, 4+D] from the model's embedding cotangent.

    d_emb:  [K, 3+D] cotangent of the pull view (cols 0/1 — show/click CVM
            inputs — are dropped, as PushCopy skips the cvm offset,
            box_wrapper.cu:344-…)
    slots:  [K] slot id per key
    clicks: [K] the instance label each key occurrence belongs to
    valid:  [K] bool — False for padding key slots
    g_show is 1 per occurrence; the table's push kernel segment-sums
    duplicates so a key seen in k instances gets g_show=k (PushMergeCopy).
    The embedding cotangent goes in NEGATED, PushCopy's -1: the in-table
    rules add what they are pushed (update_value_work: w += ratio * g /
    show), so a pushed -dL/d(emb) is descent. PushCopy's further factor,
    the batch size, is left to the learning rate: d_emb is the gradient of
    the batch's MEAN loss and is pushed at that scale.

    The rows are assembled K-minor, as one flat [(4+D) * K] vector, and
    transposed once at the end. A flat vector has one layout, the
    occurrence on the lanes, so the three per-key vectors enter as they
    are; built as [K, 1] columns of a [K, 4+D] concatenate, the compiler
    is free to lay every column out row-major, one value a 128-lane line
    (40 MB and a relayout copy each at K = 79,872: +0.7 ms a step on a
    v5e once no K-minor block, the occurrence pull's, anchored them;
    PERF.md section 6, PR 42). Same values either way.
    """
    K, n = d_emb.shape[0], d_emb.shape[1] - 2
    v = valid.astype(d_emb.dtype)
    flat = jnp.concatenate([
        slots.astype(d_emb.dtype),
        v,                                     # show = 1 per occurrence
        clicks.astype(d_emb.dtype) * v,
        -d_emb[:, 2:].T.reshape(-1) * jnp.tile(v, n),   # -(embed_g, embedx_g)
    ])
    return flat.reshape(3 + n, K).T


@jax.named_scope("pull")
def pull_sparse_extended(slab: jnp.ndarray, ids: jnp.ndarray,
                         layout: ValueLayout):
    """pull_box_extended_sparse (operators/pull_box_extended_sparse_op.*):
    dual-output lookup — the base pull view [K, 3+D] plus the expand
    (NN-cross) embedding [K, E]. Requires layout.expand_dim > 0."""
    if not layout.expand_dim:
        raise ValueError("layout has no expand block (expand_dim == 0)")
    rows = gather_slab_rows(slab, ids, layout)
    ew0 = layout.expand_w
    base = jnp.concatenate([
        rows[:, acc.SHOW:acc.SHOW + 1],
        rows[:, acc.CLICK:acc.CLICK + 1],
        rows[:, acc.EMBED_W:acc.EMBED_W + 1],
        rows[:, layout.embedx_w:layout.embedx_w + layout.embedx_dim],
    ], axis=1)
    return base, rows[:, ew0:ew0 + layout.expand_dim]


@jax.named_scope("push_grads")
def build_push_grads_extended(d_emb: jnp.ndarray, d_expand: jnp.ndarray,
                              slots: jnp.ndarray, clicks: jnp.ndarray,
                              valid: jnp.ndarray) -> jnp.ndarray:
    """Push rows [K, 4+D+E] including the expand-block gradient
    (push_box_extended_sparse backward), negated as build_push_grads'."""
    v = valid.astype(d_emb.dtype)[:, None]
    return jnp.concatenate([
        slots.astype(d_emb.dtype)[:, None],
        v,
        clicks.astype(d_emb.dtype)[:, None] * v,
        -d_emb[:, 2:] * v,
        -d_expand * v,
    ], axis=1)


# ---------------------------------------------------------------- full graph
import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def pull_sparse_differentiable(slab, ids, layout: ValueLayout):
    if layout.embed_dtype != "float32":
        # the full-graph path's cotangent is a slab-shaped f32 scatter-add
        # — meaningless against an encoded uint16 slab. The explicit
        # pull/push integration (what the trainers run) supports the diet.
        raise ValueError(
            "pull_sparse_differentiable requires a float32 slab layout; "
            "the bf16 slab diet (slab_embed_dtype) is explicit-path only")
    return pull_sparse(slab, ids, layout)


def _pull_fwd(slab, ids, layout):
    return pull_sparse(slab, ids, layout), (ids, slab.shape)


def _pull_bwd(layout, res, d_out):
    ids, slab_shape = res
    D = layout.embedx_dim
    d_slab = jnp.zeros(slab_shape, d_out.dtype)
    # scatter-add only the trainable columns; show/click cotangents dropped
    d_slab = d_slab.at[ids, acc.EMBED_W].add(d_out[:, 2])
    xw0 = layout.embedx_w
    d_slab = d_slab.at[jnp.expand_dims(ids, 1),
                       jnp.arange(xw0, xw0 + D)[None, :]].add(d_out[:, 3:])
    return d_slab, None


pull_sparse_differentiable.defvjp(_pull_fwd, _pull_bwd)
