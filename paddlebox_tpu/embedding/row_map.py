"""Which slab row a pass key occupies: the one owner of the assignment.

A PassTable asks this module for everything that depends on where a key's
row is: the native key -> row index of a pass, the resident probe of the
next pass's keys, the free rows, and the (key, row) pairs of a touched
bitmap for the write-back. A key that stays resident keeps its row from
pass to pass (the BoxPS HBM table's contract); rows of keys that left go
to the free list and keys that arrive take free rows, lowest first, so
one seed gives the same rows run after run. The map that succeeds
another is derived either from the next pass's sorted key set
(RowMap.succeed) or, a registered key chunk at a time and with no sort
of what stays, as a delta on it (KeyFold): the same map, field for field.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def sorted_member(sorted_keys: np.ndarray, keys: np.ndarray):
    """(pos, hit) membership probe of `keys` against a SORTED UNIQUE key
    array: pos[i] is the index of keys[i] in sorted_keys where hit[i],
    clamped garbage elsewhere. The ONE definition of the searchsorted+
    equality idiom every incremental-lifecycle diff uses (resident diff
    fallback, staged-promote matching, prefetcher known-sets)."""
    if sorted_keys.size == 0:
        return (np.zeros(keys.size, np.int64),
                np.zeros(keys.size, bool))
    pos = np.minimum(np.searchsorted(sorted_keys, keys),
                     sorted_keys.size - 1)
    return pos, sorted_keys[pos] == keys


def merge_sorted(a: np.ndarray, b: np.ndarray):
    """(merged, from_b): the sorted union of two sorted unique arrays with
    no element in common, and the mask of b's places in it. A merge, not a
    sort: linear in both, plus one binary search an element of b."""
    at = np.searchsorted(a, b) + np.arange(b.size)
    from_b = np.zeros(a.size + b.size, bool)
    from_b[at] = True
    merged = np.empty(from_b.size, a.dtype)
    merged[at] = b
    merged[~from_b] = a
    return merged, from_b


class _NativeIndex:
    """The one owner of a native key -> row hash index (route.cc). Maps
    that hold the same keys at the same rows share one; it is destroyed
    when the last of them, and the last fold probing it, lets go."""

    __slots__ = ("handle",)

    def __init__(self, handle) -> None:
        self.handle = handle

    def __del__(self):
        try:
            from paddlebox_tpu.native.build import destroy_route_index
            destroy_route_index(self.handle)
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails
            pass


class RowMap:
    """key -> slab row for ONE sorted unique key set, plus the free rows.

    keys[i] occupies slab row rows[i]; rows are distinct and < limit (the
    table's padding row is never assigned). Free rows are `holes` (sorted,
    all below `top`) and every row in [top, limit). `arrived` marks the
    keys (bool, aligned with keys) the map it succeeded did not hold and
    `freed` counts the rows that map's departed keys released; `dense`
    says rows is arange(n), so a slice does for an index. A map is not
    mutated after construction: succeed() returns the next pass's map and
    leaves this one valid, so a feed pass can be run again before
    begin_pass. Its native index is its own, or the one of the map it
    succeeded where nothing arrived and nothing left (index_like)."""

    def __init__(self, keys: np.ndarray, rows: np.ndarray,
                 holes: np.ndarray, top: int, limit: int,
                 arrived: np.ndarray, freed: int, dense: bool) -> None:
        self.keys = keys
        self.rows = rows
        self.holes = holes
        self.top = top
        self.limit = limit
        self.arrived = arrived
        self.freed = freed
        self.dense = dense
        self._index = None

    @classmethod
    def by_rank(cls, keys: np.ndarray, limit: int) -> "RowMap":
        """The assignment with no history: key i of the sorted set takes
        row i, every key arrives, nothing below the top is free."""
        n = keys.size
        return cls(keys, np.arange(n, dtype=np.int32),
                   np.empty(0, np.int32), n, limit, np.ones(n, bool), 0, True)

    def build_index(self) -> None:
        """Build the native key -> row hash index (~1 probe a key). Until
        then, and without the native library or for an empty set, lookup
        and probe take the searchsorted tier."""
        from paddlebox_tpu.native.build import create_route_index
        if self._index is None:
            handle = create_route_index([self.keys], [self.rows])
            if handle is not None:
                self._index = _NativeIndex(handle)

    def index_like(self, base: Optional["RowMap"]) -> bool:
        """Give this map its index: ``base``'s own, shared, where this map
        succeeded ``base`` and nothing arrived and nothing left (the same
        keys at the same rows, so the same index, key for key); built
        anew otherwise. True where it is shared."""
        shared = (base is not None and not self.freed
                  and not self.arrived.any())
        if shared:
            self._index = base._index
        else:
            self.build_index()
        return shared

    @property
    def free_rows(self) -> int:
        return int(self.holes.size) + self.limit - self.top

    def lookup(self, keys: np.ndarray, valid: Optional[np.ndarray],
               padding_id: int) -> np.ndarray:
        """[K] int32 slab row per key; positions where `valid` is False
        map to padding_id. KeyError for a valid key outside the set."""
        if self._index is not None:
            from paddlebox_tpu.native.build import route_lookup
            return route_lookup(self._index.handle, keys, valid, padding_id)
        pos, hit = sorted_member(self.keys, keys)
        ids = (self.rows[pos] if self.keys.size
               else np.zeros(keys.shape, np.int32))
        if valid is not None:
            ids = np.where(valid, ids, padding_id)
            hit = hit | ~valid
        if not hit.all():
            raise KeyError("keys not registered in feed pass (first few: "
                           f"{keys[~hit][:5]})")
        return ids.astype(np.int32)

    def probe(self, keys: np.ndarray) -> np.ndarray:
        """[K] int32 slab row per key, -1 for a key outside the set."""
        if self._index is not None:
            from paddlebox_tpu.native.build import route_lookup_serve
            return route_lookup_serve(self._index.handle, keys, -1)
        if not self.keys.size:
            return np.full(keys.size, -1, np.int32)
        pos, hit = sorted_member(self.keys, keys)
        return np.where(hit, self.rows[pos], -1).astype(np.int32)

    def succeed(self, keys: np.ndarray) -> "RowMap":
        """The map of the pass that follows this one on the same slab:
        `keys` (sorted unique) that are in this map keep their rows, the
        rows of this map's keys absent from `keys` are freed, and the
        keys that arrive take free rows, lowest row first in key order."""
        rows = self.probe(keys)
        arrived = rows < 0
        left = np.empty(0, np.int32)
        if self.keys.size - (keys.size - np.count_nonzero(arrived)):
            # both key arrays are sorted, so the keys that stayed are a
            # sorted subsequence of this map's: rank them, free the rest
            stayed = np.zeros(self.keys.size, bool)
            stayed[np.searchsorted(self.keys, keys[~arrived])] = True
            left = self.rows[~stayed]
        return self._followed_by(keys, rows, arrived, left)

    def _followed_by(self, keys: np.ndarray, rows: np.ndarray,
                     arrived: np.ndarray, left: np.ndarray) -> "RowMap":
        """The successor over ``keys`` (sorted unique) once the delta is
        known: rows[i] is the row keys[i] keeps, anything where
        arrived[i], which is filled in here (``rows`` is the caller's to
        give away); ``left`` holds the rows of this map's keys that are
        not among ``keys``."""
        n_new = int(np.count_nonzero(arrived))
        holes = self.holes
        if left.size:
            holes = np.sort(np.concatenate([holes, left]))
        take = min(n_new, int(holes.size))
        # top grows only once the holes are used up, and then equals
        # keys.size, which the table holds to limit
        top = self.top + n_new - take
        if n_new:
            rows[arrived] = np.concatenate(
                [holes[:take], np.arange(self.top, top, dtype=np.int32)])
        return RowMap(keys, rows, holes[take:], top, self.limit, arrived,
                      int(left.size),
                      self.dense and not n_new and not left.size)

    def touched(self, bitmap: np.ndarray):
        """(keys, rows) of the assigned rows a touched-row bitmap marks, in
        key order: the row -> key view the write-back needs. A mark on a
        free row (or the padding row) selects nothing."""
        marks = bitmap[:self.keys.size] if self.dense else bitmap[self.rows]
        sel = np.flatnonzero(marks)
        return self.keys[sel], self.rows[sel]


class KeyFold:
    """The key set of the pass that follows ``base`` on the same slab,
    taken one registered chunk at a time, in any order, with repeats
    inside and across chunks: each key is probed once against ``base``
    (its native index, in native code with the GIL released; the
    searchsorted tier without one). A key ``base`` holds marks its row
    seen, and ``stayed`` counts the rows marked, so "every key of base
    was seen" compares two integers; any other key joins ``arrived``, a
    side set kept sorted unique across chunks, the one thing sorted.
    successor() is then the map RowMap.succeed gives for the sorted
    unique union of the chunks, field for field, at the cost of the
    chunks and of the delta, not of a sort of the whole set. One thread
    at a time; ``base`` is only read, and kept alive, by the fold."""

    def __init__(self, base: RowMap) -> None:
        self.base = base
        # by slab row: every row base assigns lies below its top
        self._seen = np.zeros(base.top, np.uint8)
        self.stayed = 0
        self.folded = 0  # keys probed, repeats included
        self.arrived = np.empty(0, np.uint64)
        self._loose: list = []  # misses not yet in `arrived`, as they came

    def add(self, keys: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        base = self.base
        if base._index is not None:
            from paddlebox_tpu.native.build import route_fold
            miss, marked = route_fold(base._index.handle, keys, self._seen)
        else:
            pos, hit = sorted_member(base.keys, keys)
            rows = np.unique(base.rows[pos[hit]])
            rows = rows[self._seen[rows] == 0]
            self._seen[rows] = 1
            miss, marked = keys[~hit], int(rows.size)
        self.stayed += marked
        self.folded += int(keys.size)
        if miss.size:
            self._loose.append(miss)

    def take_arrivals(self) -> np.ndarray:
        """Settle the misses of the chunks added since the last call into
        ``arrived``; returns those no earlier call returned (sorted
        unique): what a promote prefetcher may read ahead."""
        if not self._loose:
            return self.arrived[:0]
        new = np.unique(np.concatenate(self._loose))
        self._loose = []
        new = new[~sorted_member(self.arrived, new)[1]]
        if new.size:
            self.arrived = merge_sorted(self.arrived, new)[0]
        return new

    @property
    def unsettled(self) -> bool:
        """Misses wait for take_arrivals."""
        return bool(self._loose)

    @property
    def size(self) -> int:
        """Keys in the set, once the arrivals are settled."""
        return self.stayed + int(self.arrived.size)

    def successor(self) -> RowMap:
        """The map of the folded key set: stayed are base's seen keys,
        which keep their rows; the rest of base's left and free theirs;
        the misses arrived and take free rows, lowest first in key
        order. With no delta it holds base's own arrays."""
        self.take_arrivals()
        base = self.base
        n = base.keys.size
        if self.stayed == n and not self.arrived.size:
            return RowMap(base.keys, base.rows, base.holes, base.top,
                          base.limit, np.zeros(n, bool), 0, base.dense)
        keys, rows, left = base.keys, base.rows, np.empty(0, np.int32)
        if self.stayed < n:
            seen = self._seen[:n] if base.dense else self._seen[base.rows]
            stays = seen.view(bool)
            keys, rows, left = keys[stays], rows[stays], rows[~stays]
        keys, arrived = merge_sorted(keys, self.arrived)
        kept = np.full(keys.size, -1, np.int32)
        kept[~arrived] = rows
        return base._followed_by(keys, kept, arrived, left)
