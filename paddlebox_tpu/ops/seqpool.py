"""Fused per-slot sequence pooling + CVM transform.

TPU-native fused_seqpool_cvm (paddle/fluid/operators/fused/
fused_seqpool_cvm_op.*): the reference fuses "sum-pool each slot's
variable-length key list, then handle the CVM (show/click) columns" across
all slots in one CUDA kernel — the main dense-side fusion in CTR models.
Here the same fusion is one XLA segment-sum over the flattened key axis
followed by the CVM log transform; XLA fuses the rest into the surrounding
matmuls. The batch packer pre-computes segment ids (instance*num_slots+slot),
which replaces the LoD machinery with static shapes.

CVM columns follow cvm_op.h: y0 = log(show+1), y1 = log(click+1) - y0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cvm_transform(pooled: jnp.ndarray, use_cvm: bool = True) -> jnp.ndarray:
    """pooled: [..., 2+E] with cols [show, click, emb...] → CVM columns
    (cvm_op.h semantics). use_cvm=False drops the two counter columns
    (CVMOpKernel's else-branch keeps dims-2)."""
    show = pooled[..., 0:1]
    click = pooled[..., 1:2]
    rest = pooled[..., 2:]
    if not use_cvm:
        return rest
    log_show = jnp.log(show + 1.0)
    log_ctr = jnp.log(click + 1.0) - log_show
    return jnp.concatenate([log_show, log_ctr, rest], axis=-1)


@jax.named_scope("pool")
def fused_seqpool_cvm(emb: jnp.ndarray, segments: jnp.ndarray,
                      valid: jnp.ndarray, batch_size: int, num_slots: int,
                      use_cvm: bool = True,
                      pad_empty_zero: bool = True,
                      sorted_segments: bool = False) -> jnp.ndarray:
    """emb: [K, 2+E] per-key pull view; segments: [K] = ins*num_slots+slot;
    valid: [K] bool. Returns [batch, num_slots, out_dim] where out_dim is
    2+E with CVM or E without.

    Empty slots pool to zero (need_filter/padding_value=0 behavior of the
    reference kernel).

    sorted_segments=True asserts `segments` is nondecreasing — true for
    BatchPacker output (CSR order, padding tail pinned to the last segment)
    — letting XLA lower the pool as a sorted segment reduction instead of a
    random scatter-add (the TPU analog of the reference's one-kernel fusion,
    fused_seqpool_cvm_op.cu)."""
    masked = jnp.where(valid[:, None], emb, 0.0)
    pooled = jax.ops.segment_sum(
        masked, segments, num_segments=batch_size * num_slots,
        indices_are_sorted=sorted_segments)
    pooled = pooled.reshape(batch_size, num_slots, emb.shape[-1])
    return cvm_transform(pooled, use_cvm)


def cvm_conv_transform(pooled: jnp.ndarray, use_cvm: bool = True,
                       show_filter: bool = False) -> jnp.ndarray:
    """Conv variant (fused_seqpool_cvm_with_conv_op.cu FusedCVMWithConvKernel*):
    counter cols are [show, click, conv]; output cols
    [log(show+1), log(click+1), log(conv+1)-log(click+1), emb...].
    show_filter drops the show column (KernelWithOutShow)."""
    show = pooled[..., 0:1]
    click = pooled[..., 1:2]
    conv = pooled[..., 2:3]
    rest = pooled[..., 3:]
    if not use_cvm:
        return rest
    log_show = jnp.log(show + 1.0)
    log_click = jnp.log(click + 1.0)
    log_convr = jnp.log(conv + 1.0) - log_click
    cols = ([log_click, log_convr] if show_filter
            else [log_show, log_click, log_convr])
    return jnp.concatenate(cols + [rest], axis=-1)


@jax.named_scope("pool")
def seqpool_sum(emb: jnp.ndarray, segments: jnp.ndarray, valid: jnp.ndarray,
                batch_size: int, num_slots: int) -> jnp.ndarray:
    """Plain per-slot sum pooling with NO cvm columns — the
    sequence_pool-SUM the extended (expand/NN-cross) embedding outputs
    feed (pull_box_extended_sparse's consumer pattern). The ONE
    implementation both trainers' expand paths share."""
    pooled = jax.ops.segment_sum(
        jnp.where(valid[:, None], emb, 0.0), segments,
        num_segments=batch_size * num_slots, indices_are_sorted=True)
    return pooled.reshape(batch_size, num_slots, emb.shape[-1])


def fused_seqpool_cvm_with_conv(
        emb: jnp.ndarray, segments: jnp.ndarray, valid: jnp.ndarray,
        batch_size: int, num_slots: int, use_cvm: bool = True,
        need_filter: bool = False, show_coeff: float = 0.2,
        clk_coeff: float = 1.0, threshold: float = 0.96,
        show_filter: bool = False) -> jnp.ndarray:
    """fused_seqpool_cvm_with_conv_op: pull view is [show, click, conv, emb...]
    per key. need_filter drops keys whose show/click score
    (show-click)*show_coeff + click*clk_coeff falls under threshold before
    pooling (FusedSeqpoolWithConvKernelFilter, with_conv_op.cu:58-88)."""
    keep = valid
    if need_filter:
        show = emb[:, 0]
        click = emb[:, 1]
        keep = keep & ((show - click) * show_coeff + click * clk_coeff
                       >= threshold)
    masked = jnp.where(keep[:, None], emb, 0.0)
    pooled = jax.ops.segment_sum(
        masked, segments, num_segments=batch_size * num_slots)
    pooled = pooled.reshape(batch_size, num_slots, emb.shape[-1])
    return cvm_conv_transform(pooled, use_cvm, show_filter)


def _segpool(emb: jnp.ndarray, segments: jnp.ndarray, keep: jnp.ndarray,
             batch_size: int, num_slots: int) -> jnp.ndarray:
    # no indices_are_sorted hint: the packer's trailing PADDING slots carry
    # segment 0 after larger ids, so the ids are not globally sorted (and
    # the hint measured no win on v5e anyway)
    masked = jnp.where(keep[:, None], emb, 0.0)
    pooled = jax.ops.segment_sum(
        masked, segments, num_segments=batch_size * num_slots)
    return pooled.reshape(batch_size, num_slots, emb.shape[-1])


def fused_seqpool_cvm_with_credit(
        emb: jnp.ndarray, segments: jnp.ndarray, valid: jnp.ndarray,
        batch_size: int, num_slots: int, use_cvm: bool = True,
        show_filter: bool = False) -> jnp.ndarray:
    """fused_seqpool_cvm_with_credit_op (with_credit_op.cu:53-110): per-key
    cols [show, click, conv, credit, emb...]; each of the 4 counters maps to
    log(x+1) independently (no ctr-smooth subtraction); show_filter drops
    the show column (KernelWithOutShow); use_cvm=False drops all four."""
    pooled = _segpool(emb, segments, valid, batch_size, num_slots)
    if not use_cvm:
        return pooled[..., 4:]
    counters = jnp.log(pooled[..., :4] + 1.0)
    if show_filter:
        counters = counters[..., 1:]
    return jnp.concatenate([counters, pooled[..., 4:]], axis=-1)


def fused_seqpool_cvm_tradew(
        emb: jnp.ndarray, segments: jnp.ndarray, valid: jnp.ndarray,
        batch_size: int, num_slots: int, trade_num: int,
        trade_id: int = None, use_cvm: bool = True) -> jnp.ndarray:
    """fused_seqpool_cvm_tradew_op (tradew_op.cu:34-131): per-key cols
    [show, click, trade_w[trade_num], emb...]. The embedding part pools
    weighted by the selected trade's weight column (KernelWithTradeId,
    cu:63-88); without a trade_id the trade block is simply skipped
    (KernelNormal). CVM columns follow the standard transform."""
    cvm_part = emb[:, :2]
    emb_part = emb[:, 2 + trade_num:]
    if trade_id is not None:
        w = emb[:, 2 + trade_id:3 + trade_id]
        emb_part = emb_part * w
    pooled = _segpool(jnp.concatenate([cvm_part, emb_part], axis=1),
                      segments, valid, batch_size, num_slots)
    return cvm_transform(pooled, use_cvm)


def fused_seqpool_cvm_with_diff_thres(
        emb: jnp.ndarray, segments: jnp.ndarray, valid: jnp.ndarray,
        slots: jnp.ndarray, batch_size: int, num_slots: int,
        slot_thresholds: jnp.ndarray, use_cvm: bool = True,
        show_coeff: float = 0.2, clk_coeff: float = 1.0,
        xbox_diff_thres_filter: bool = True,
        threshold: float = 0.96) -> jnp.ndarray:
    """fused_seqpool_cvm_with_diff_thres_op (with_diff_thres_op.cu:87-131):
    the base fused op with a PER-SLOT filter threshold vector — keys whose
    show/click score falls under threshold_vec[slot] are dropped before
    pooling (xbox_diff_thres_filter=False falls back to the scalar)."""
    show, click = emb[:, 0], emb[:, 1]
    score = (show - click) * show_coeff + click * clk_coeff
    thres = (jnp.asarray(slot_thresholds)[slots]
             if xbox_diff_thres_filter else threshold)
    keep = valid & (score >= thres)
    pooled = _segpool(emb, segments, keep, batch_size, num_slots)
    return cvm_transform(pooled, use_cvm)


def fused_seqpool_cvm_with_pcoc(
        emb: jnp.ndarray, segments: jnp.ndarray, valid: jnp.ndarray,
        batch_size: int, num_slots: int, pclk_num: int,
        use_cvm: bool = True) -> jnp.ndarray:
    """fused_seqpool_cvm_with_pcoc_op (with_pcoc_op.cu:122-160): per-key
    cols [show, click, show2, clk2, pclk_1..pclk_n, emb...]; output
    counters [log(show+1), log(click+1)-log(show+1),
    (log(pclk_i+1)-log(show2+1))_i, (log(pclk_i+1)-log(clk2+1))_i] then
    the embedding passthrough; use_cvm=False drops every counter col."""
    used = 4 + pclk_num
    pooled = _segpool(emb, segments, valid, batch_size, num_slots)
    if not use_cvm:
        return pooled[..., used:]
    log1p = jnp.log(pooled[..., :used] + 1.0)
    log_show, log_click = log1p[..., 0:1], log1p[..., 1:2]
    log_show2, log_clk2 = log1p[..., 2:3], log1p[..., 3:4]
    log_pclk = log1p[..., 4:used]
    return jnp.concatenate([
        log_show,
        log_click - log_show,
        log_pclk - log_show2,
        log_pclk - log_clk2,
        pooled[..., used:],
    ], axis=-1)
