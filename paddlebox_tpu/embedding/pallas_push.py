"""Pallas TPU kernel: in-table sparse-adagrad row update.

The hand-written-kernel tier of the push path (SURVEY.md §2.2 maps the
reference's in-hashtable `SparseAdagradOptimizer` CUDA functor,
heter_ps/optimizer.cuh.h:31-145, to "vectorized update in a Pallas
kernel"): deduped+merged gradient rows update their gathered value rows —
show/click/delta bookkeeping, adagrad with shared-g2sum embedx, and lazy
mf creation drawn from the on-core PRNG — in VMEM tiles on the VPU.

Semantics match `apply_push` (embedding/optimizers.py) for the adagrad
layout with no expand block; `push_sparse_dedup` routes here when the
`use_pallas_push` flag is on (XLA path otherwise; the kernel exists for
the wide-embedx configs where XLA's fusion of the 20+ column updates might
splinter — which of the two is faster on the chip is not measured).
Both kernels here compile through Mosaic on the v5e and match their XLA
oracles there (chip_smoke.py's kernel leg).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.config.configs import SparseOptimizerConfig
from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout

_TILE = 256


def pallas_interpret() -> bool:
    """The ONE rule both kernels dispatch by: compiled by Mosaic on ``tpu``,
    interpreted on ``cpu`` (the test platform, where interpret mode is the
    only way the kernel body runs at all), an error anywhere else — a
    backend nobody recognised must not quietly run a python-rate kernel."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "pallas push kernels are compiled on 'tpu' and interpreted on "
        f"'cpu' only; default backend is {backend!r}")


def _adagrad(w, g2sum, scaled, lr, conf):
    add_g2 = jnp.mean(scaled * scaled, axis=-1, keepdims=True)
    ratio = lr * jnp.sqrt(conf.mf_initial_g2sum
                          / (conf.mf_initial_g2sum + g2sum))
    neww = jnp.clip(w + ratio * scaled, conf.mf_min_bound, conf.mf_max_bound)
    return neww, g2sum + add_g2


def _push_kernel(seed_ref, vals_ref, grads_ref, rid_ref, out_ref, *, layout,
                 conf):
    vals = vals_ref[:]
    grads = grads_ref[:]
    push = PushLayout(layout.embedx_dim)
    D = layout.embedx_dim
    es = layout.embed_state
    xw0 = layout.embedx_w
    xs = layout.embedx_state

    g_show = grads[:, push.SHOW:push.SHOW + 1]
    g_click = grads[:, push.CLICK:push.CLICK + 1]
    active = g_show > 0
    scale = jnp.where(active, g_show, 1.0)

    slot = jnp.where(active, grads[:, push.SLOT:push.SLOT + 1],
                     vals[:, acc.SLOT:acc.SLOT + 1])
    show = vals[:, acc.SHOW:acc.SHOW + 1] + g_show
    click = vals[:, acc.CLICK:acc.CLICK + 1] + g_click
    delta = (vals[:, acc.DELTA_SCORE:acc.DELTA_SCORE + 1]
             + conf.nonclk_coeff * (g_show - g_click)
             + conf.clk_coeff * g_click)
    unseen = jnp.where(active, 0.0,
                       vals[:, acc.UNSEEN_DAYS:acc.UNSEEN_DAYS + 1])

    # embed_w: per-feature-lr adagrad (optimizer.cuh.h update_lr)
    lr = jnp.where(slot == float(conf.nodeid_slot),
                   conf.mf_learning_rate, conf.feature_learning_rate)
    w = vals[:, acc.EMBED_W:acc.EMBED_W + 1]
    neww, newg2 = _adagrad(w, vals[:, es:es + 1],
                           grads[:, push.EMBED_G:push.EMBED_G + 1] / scale,
                           lr, conf)

    # embedx: shared-g2sum adagrad (dy_mf_update_value)
    embedx = vals[:, xw0:xw0 + D]
    newx, newxg2 = _adagrad(embedx, vals[:, xs:xs + 1],
                            grads[:, push.embedx_g:push.embedx_g + D] / scale,
                            jnp.full_like(w, conf.mf_learning_rate), conf)

    # lazy mf creation: uniform [0, mf_initial_range). CONTENT-ADDRESSED:
    # bits are a Weyl/LCG mix of (slab row id, col, seed) — NOT row position
    # or tile id — so a created key draws the same values however the batch
    # was deduped, ordered, or routed (the same contract as apply_push's
    # fold_in(prng, row_id); the hardware PRNG can't be keyed per row)
    mf_size = vals[:, acc.MF_SIZE:acc.MF_SIZE + 1]
    score = conf.nonclk_coeff * (show - click) + conf.clk_coeff * click
    create = (mf_size == 0) & (score >= conf.mf_create_thresholds) & active
    rid = rid_ref[:].astype(jnp.uint32)                    # [TILE, 1]
    r = jnp.broadcast_to(rid, embedx.shape)
    c = jax.lax.broadcasted_iota(jnp.uint32, embedx.shape, 1)
    s = seed_ref[0].astype(jnp.uint32)
    bits = (r * jnp.uint32(2654435761) ^ (c * jnp.uint32(40503) + s))
    bits = bits * jnp.uint32(747796405) + jnp.uint32(2891336453)
    bits ^= bits >> 16
    # >>8 keeps 24 bits, which fit int32 exactly (Mosaic has no u32→f32)
    u01 = ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
           * (1.0 / (1 << 24)))
    fresh = u01 * conf.mf_initial_range
    has_mf = mf_size > 0
    out_x = jnp.where(create, fresh,
                      jnp.where(has_mf & active, newx, embedx))
    out_xg2 = jnp.where(has_mf & active, newxg2, vals[:, xs:xs + 1])
    out_mf = jnp.where(create, float(D), mf_size)

    out = jnp.concatenate([
        slot, show, click, delta, unseen, out_mf, neww, newg2, out_x, out_xg2,
    ], axis=1)
    out_ref[:] = jnp.where(active, out, vals)


def _blocked_write_kernel(bidx_ref, slab_ref, tiles_ref, rmap_ref, out_ref):
    """One grid step = one touched slab block: read the CURRENT aliased
    block, overlay the rows this block's tile carries (row_map >= 0), write
    back. Revisit safety is the CALLER's job, not this read's: under
    Mosaic grid pipelining the aliased input window for step i+1 may be
    fetched before step i's store lands, so a sentinel slot revisiting an
    already-written block could copy back pre-update bits. The caller
    (push_blocked_write) therefore orders every sentinel slot BEFORE the
    real write of the block it clamps onto — a revisit-before-update is an
    identity write of the block's original bits, which is pipeline-safe."""
    out_ref[:] = jnp.where(rmap_ref[0] >= 0, tiles_ref[0], slab_ref[:])


def pallas_blocked_write(slab: jnp.ndarray, tiles: jnp.ndarray,
                         row_map: jnp.ndarray, blk_idx: jnp.ndarray,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Blocked slab placement (round 11, `push_blocked_pallas`): the grid
    runs over the NB touched blocks with the block ids SCALAR-PREFETCHED —
    each step's in/out BlockSpec index maps through blk_idx[i], so the
    kernel streams exactly the touched [B, W] tiles through VMEM and the
    slab stays in place (input_output_aliases). This is the hand-written
    tier of the blocked scatter: same tile shapes as push_blocked_write's
    fori_loop, but the placement loop is the Mosaic grid instead of NB
    sequential XLA dynamic_update_slices.

    slab:    [C, W] (any dtype — pure placement, the encoded-row codec
             already ran); C % B == 0
    tiles:   [NB, B, W] gather-assembled source rows (garbage where
             row_map < 0 — those lanes keep the slab's bits)
    row_map: [NB, B] int32, >= 0 marks lanes to overwrite
    blk_idx: [NB] int32 block ids in [0, C//B) (padding slots clamped by
             the caller; their row_map is all -1 so the write is a no-op
             — and the caller must schedule them BEFORE the real write of
             the clamped block, see _blocked_write_kernel)
    interpret: None = pallas_interpret()'s backend rule
    """
    NB, B, W = tiles.shape
    C = slab.shape[0]
    if C % B:
        raise ValueError("pallas_blocked_write: block rows %d must divide "
                         "capacity %d" % (B, C))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NB,),
        in_specs=[
            pl.BlockSpec((B, W), lambda i, b: (b[i], 0)),
            pl.BlockSpec((1, B, W), lambda i, b: (i, 0, 0)),
            # row_map rides as [NB, B, 1]: the TPU lowering wants a block's
            # last two dims divisible by (8, 128) or equal to the array's,
            # which a (1, B) slice of [NB, B] is not; the (B, 1) column
            # lane-broadcasts against the (B, W) tiles in the kernel
            pl.BlockSpec((1, B, 1), lambda i, b: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, W), lambda i, b: (b[i], 0)),
    )
    return pl.pallas_call(
        _blocked_write_kernel,
        out_shape=jax.ShapeDtypeStruct(slab.shape, slab.dtype),
        grid_spec=grid_spec,
        # operand 0 is the scalar-prefetch vector; the slab (operand 1)
        # aliases the output so untouched blocks keep their bits
        input_output_aliases={1: 0},
        interpret=pallas_interpret() if interpret is None else interpret,
    )(blk_idx, slab, tiles, row_map.reshape(NB, B, 1))


def pallas_apply_push(values: jnp.ndarray, grads: jnp.ndarray, seed,
                      layout: ValueLayout,
                      conf: SparseOptimizerConfig,
                      interpret: Optional[bool] = None,
                      row_ids=None) -> jnp.ndarray:
    """Drop-in for apply_push (adagrad, no expand block). values padded to
    a _TILE multiple by the caller-invisible grid; seed: int32 scalar;
    row_ids: [n] slab ids keying the creation randoms (positional arange
    fallback when the caller has none); interpret: None = pallas_interpret()'s
    backend rule."""
    if layout.optimizer != "adagrad" or layout.expand_dim:
        raise ValueError("pallas push kernel supports the adagrad layout "
                         "without expand block")
    n, width = values.shape
    if row_ids is None:
        row_ids = jnp.arange(n, dtype=jnp.int32)
    row_ids = row_ids.astype(jnp.int32).reshape(n, 1)
    pad = (-n) % _TILE
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        grads = jnp.pad(grads, ((0, pad), (0, 0)))
        row_ids = jnp.pad(row_ids, ((0, pad), (0, 0)))
    n_pad = values.shape[0]
    seed_arr = jnp.asarray([seed], jnp.int32).astype(jnp.int32)

    kernel = functools.partial(_push_kernel, layout=layout, conf=conf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // _TILE,),
        in_specs=[
            pl.BlockSpec((_TILE, width), lambda i, s: (i, 0)),
            pl.BlockSpec((_TILE, grads.shape[1]), lambda i, s: (i, 0)),
            pl.BlockSpec((_TILE, 1), lambda i, s: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE, width), lambda i, s: (i, 0)),
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, width), values.dtype),
        grid_spec=grid_spec,
        interpret=pallas_interpret() if interpret is None else interpret,
    )(seed_arr, values, grads, row_ids)
    return out[:n]
