"""Columnar record blocks: the zero-object data path.

The reference keeps per-instance SlotRecord objects pooled in a slab
allocator (SlotObjPool, data_feed.h:305) to dodge allocation churn. The
TPU-native pipeline goes further: the native parser emits whole files as
flat columnar arrays (keys + per-key slot/record ids, labels, dense), and
a batch's keys are packed by one native call (numpy where the library is
missing) — no per-record Python objects anywhere on the hot path.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from paddlebox_tpu.config.configs import DataFeedConfig
from paddlebox_tpu.data.packer import PackedBatch
from paddlebox_tpu.native.build import get_lib
from paddlebox_tpu.utils.stats import stat_add


@dataclasses.dataclass
class ColumnarBlock:
    """A set of records in struct-of-arrays form. Keys of record r live at
    keys[rec_offsets[r]:rec_offsets[r+1]] ordered by slot."""

    keys: np.ndarray        # [K] uint64
    key_slot: np.ndarray    # [K] int32
    labels: np.ndarray      # [N] int32
    rec_offsets: np.ndarray  # [N+1] int64
    dense: Optional[np.ndarray] = None  # [N, dense_dim] float32
    task_labels: Optional[dict] = None  # task → [N] int32

    @property
    def n_recs(self) -> int:
        return self.labels.shape[0]

    @property
    def n_keys(self) -> int:
        return self.keys.shape[0]

    @staticmethod
    def from_key_rec(keys, key_slot, key_rec, labels, dense=None,
                     task_labels=None) -> "ColumnarBlock":
        """From parser output where key_rec[i] is each key's record index
        (keys already grouped by record)."""
        n = labels.shape[0]
        counts = np.bincount(key_rec, minlength=n) if keys.size else \
            np.zeros(n, np.int64)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        return ColumnarBlock(keys=keys, key_slot=key_slot, labels=labels,
                             rec_offsets=offsets, dense=dense,
                             task_labels=task_labels)

    def select(self, rec_idx: np.ndarray) -> "ColumnarBlock":
        """Sub-block of the given records, fully vectorized (the
        fancy-index split primitive of the block shuffle and any other
        record-subset consumer). Column arrays are fresh copies."""
        rec_idx = np.asarray(rec_idx, np.int64)
        starts = self.rec_offsets[rec_idx]
        counts = self.rec_offsets[rec_idx + 1] - starts
        flat = np.repeat(starts, counts) + _run_aranges(counts)
        offsets = np.zeros(rec_idx.size + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        dense = None if self.dense is None else self.dense[rec_idx]
        task_labels = None
        if self.task_labels is not None:
            task_labels = {t: c[rec_idx]
                           for t, c in self.task_labels.items()}
        return ColumnarBlock(keys=self.keys[flat],
                             key_slot=self.key_slot[flat],
                             labels=self.labels[rec_idx],
                             rec_offsets=offsets, dense=dense,
                             task_labels=task_labels)

    @staticmethod
    def concat(blocks: Sequence["ColumnarBlock"]) -> "ColumnarBlock":
        blocks = [b for b in blocks if b.n_recs]
        if not blocks:
            return ColumnarBlock(np.empty(0, np.uint64), np.empty(0, np.int32),
                                 np.empty(0, np.int32),
                                 np.zeros(1, np.int64), None)
        keys = np.concatenate([b.keys for b in blocks])
        key_slot = np.concatenate([b.key_slot for b in blocks])
        labels = np.concatenate([b.labels for b in blocks])
        offs = [blocks[0].rec_offsets]
        shift = blocks[0].rec_offsets[-1]
        for b in blocks[1:]:
            offs.append(b.rec_offsets[1:] + shift)
            shift += b.rec_offsets[-1]
        rec_offsets = np.concatenate(offs)
        dense = None
        if blocks[0].dense is not None:
            dense = np.concatenate([b.dense for b in blocks])
        task_labels = None
        if blocks[0].task_labels is not None:
            task_labels = {t: np.concatenate([b.task_labels[t]
                                              for b in blocks])
                           for t in blocks[0].task_labels}
        return ColumnarBlock(keys, key_slot, labels, rec_offsets, dense,
                             task_labels)


def pack_columnar(block: ColumnarBlock, rec_idx: np.ndarray,
                  feed: DataFeedConfig, kcap: int, num_slots: int,
                  max_lens: np.ndarray) -> PackedBatch:
    """Pack selected records into one static-shaped batch.

    rec_idx: record indices for this batch (≤ batch_size).
    Truncates each (record, slot) run to the slot's max_len and the batch to
    kcap keys, counting drops (packer contract parity). The [kcap] key
    arrays are one native call with the GIL released (_pack_keys_native);
    where it cannot take the batch, _pack_keys_numpy packs the same bits.
    """
    B = feed.batch_size
    n = min(rec_idx.shape[0], B)
    rec_idx = rec_idx[:n]

    labels = np.zeros(B, dtype=np.int32)
    labels[:n] = block.labels[rec_idx]
    ins_valid = np.zeros(B, dtype=bool)
    ins_valid[:n] = True
    dense = None
    if block.dense is not None:
        dense = np.zeros((B, block.dense.shape[1]), np.float32)
        dense[:n] = block.dense[rec_idx]
    qvalues = np.zeros(B, dtype=np.float32)
    # presence keyed on the FEED config, not the block: a host whose file
    # shard parsed zero records must emit the same batch schema as its
    # peers (lockstep collectives; record-path packer parity)
    task_names = [t for t, _ in getattr(feed, "task_label_slots", ())]
    task_labels = None
    if task_names:
        task_labels = {}
        block_tl = block.task_labels or {}
        for t in task_names:
            arr = np.zeros(B, dtype=np.int32)
            col = block_tl.get(t)
            arr[:n] = col[rec_idx] if col is not None else labels[:n]
            task_labels[t] = arr

    stat_add("ingest_ins_packed", n)
    packed = _pack_keys_native(block, rec_idx, B, kcap, num_slots, max_lens)
    if packed is None:
        packed = _pack_keys_numpy(block, rec_idx, B, kcap, num_slots,
                                  max_lens)
    keys, slots, segments, valid = packed
    return PackedBatch(keys=keys, slots=slots, segments=segments, valid=valid,
                       labels=labels, ins_valid=ins_valid, dense=dense,
                       n_ins=n, qvalues=qvalues,
                       cmatch_rank=np.zeros(B, dtype=np.uint64),
                       task_labels=task_labels)


# the parser's columns (keys, key_slot, rec_offsets): the kernel's types
_BLOCK_DTYPES = (np.dtype(np.uint64), np.dtype(np.int32), np.dtype(np.int64))


def _pack_keys_native(block: ColumnarBlock, rec_idx: np.ndarray, B: int,
                      kcap: int, num_slots: int, max_lens: np.ndarray):
    """(keys, slots, segments, valid) of pack_columnar in one call of
    psr_pack_batch (native/slot_parser.cc), which holds no GIL and no
    shared scratch, so stager and pool threads pack side by side. None
    where the library is missing, the block's columns are not the
    parser's, or the kernel declines a kept segment below the one before
    it (a plugin's slot order: the numpy pack's stable sort repairs it).
    Counter ingest_batches_packed_native: +1 a batch packed here."""
    lib = get_lib()
    cols = (block.keys, block.key_slot, block.rec_offsets)
    if (lib is None or max_lens.shape != (num_slots,)
            or max_lens.dtype.kind not in "iu"
            or block.key_slot.shape != block.keys.shape
            or tuple(c.dtype for c in cols) != _BLOCK_DTYPES
            or not all(c.flags.c_contiguous for c in cols)):
        return None
    rec_idx = np.ascontiguousarray(rec_idx, np.int64)
    max_lens = np.ascontiguousarray(max_lens, np.int64)
    keys = np.zeros(kcap, dtype=np.uint64)
    slots = np.zeros(kcap, dtype=np.int32)
    segments = np.full(kcap, B * num_slots - 1, dtype=np.int32)
    valid = np.zeros(kcap, dtype=bool)
    dropped = lib.psr_pack_batch(
        block.keys.ctypes.data, block.key_slot.ctypes.data,
        block.rec_offsets.ctypes.data, block.rec_offsets.shape[0] - 1,
        block.keys.shape[0], rec_idx.ctypes.data, rec_idx.shape[0],
        max_lens.ctypes.data, int(num_slots), int(kcap), keys.ctypes.data,
        slots.ctypes.data, segments.ctypes.data, valid.ctypes.data)
    if dropped < 0:
        return None
    if dropped:
        stat_add("packer_keys_dropped", dropped)
    stat_add("ingest_batches_packed_native", 1)
    return keys, slots, segments, valid


def _pack_keys_numpy(block: ColumnarBlock, rec_idx: np.ndarray, B: int,
                     kcap: int, num_slots: int, max_lens: np.ndarray):
    """(keys, slots, segments, valid) of pack_columnar, vectorized numpy:
    the fallback of _pack_keys_native and its oracle."""
    n = rec_idx.shape[0]
    starts = block.rec_offsets[rec_idx]
    ends = block.rec_offsets[rec_idx + 1]
    counts = (ends - starts).astype(np.int64)
    total = int(counts.sum())
    keys = np.zeros(kcap, dtype=np.uint64)
    slots = np.zeros(kcap, dtype=np.int32)
    # padding tail pinned to the last segment id: the native parser emits
    # keys per record in used-slot-ordinal order (slot_parser.cc config-order
    # loop), so the whole vector stays nondecreasing and seqpool may declare
    # indices_are_sorted (zero-masked padding leaves the last pool untouched)
    segments = np.full(kcap, B * num_slots - 1, dtype=np.int32)
    valid = np.zeros(kcap, dtype=bool)

    if total:
        # gather each batch record's key run: flat index expansion
        flat = np.repeat(starts, counts) + _run_aranges(counts)
        bkeys = block.keys[flat]
        bslots = block.key_slot[flat]
        brec = np.repeat(np.arange(n, dtype=np.int64), counts)
        # per-(record, slot) ordinal for max_len truncation
        group = brec * num_slots + bslots
        ordinal = _group_cumcount(group)
        keep = ordinal < max_lens[bslots]
        dropped = int((~keep).sum())
        bkeys, bslots, brec = bkeys[keep], bslots[keep], brec[keep]
        w = bkeys.shape[0]
        if w > kcap:
            dropped += w - kcap
            bkeys, bslots, brec = bkeys[:kcap], bslots[:kcap], brec[:kcap]
            w = kcap
        if dropped:
            stat_add("packer_keys_dropped", dropped)
        seg = (brec * num_slots + bslots).astype(np.int32)
        # the sorted-segments contract is load-bearing (seqpool declares
        # indices_are_sorted): built-in parsers emit config order, but a
        # user plugin .so may not — repair with a stable group sort
        if seg.size and (np.diff(seg) < 0).any():
            order = np.argsort(seg, kind="stable")
            bkeys, bslots, seg = bkeys[order], bslots[order], seg[order]
        keys[:w] = bkeys
        slots[:w] = bslots
        segments[:w] = seg
        valid[:w] = True

    return keys, slots, segments, valid


def _run_aranges(counts: np.ndarray) -> np.ndarray:
    """[0..c0), [0..c1), ... concatenated (vectorized)."""
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64)
    ends = np.cumsum(counts)
    idx = np.arange(total, dtype=np.int64)
    return idx - np.repeat(ends - counts, counts)


def _group_cumcount(group: np.ndarray) -> np.ndarray:
    """Ordinal of each element within its (already contiguous) group."""
    if group.size == 0:
        return np.empty(0, np.int64)
    change = np.empty(group.size, dtype=bool)
    change[0] = True
    np.not_equal(group[1:], group[:-1], out=change[1:])
    starts = np.nonzero(change)[0]
    idx = np.arange(group.size, dtype=np.int64)
    return idx - np.repeat(starts, np.diff(np.append(starts, group.size)))
