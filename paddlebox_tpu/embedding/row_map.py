"""Which slab row a pass key occupies: the one owner of the assignment.

A PassTable asks this module for everything that depends on where a key's
row is: the native key -> row index of a pass, the resident probe of the
next pass's keys, the free rows, and the (key, row) pairs of a touched
bitmap for the write-back. A key that stays resident keeps its row from
pass to pass (the BoxPS HBM table's contract); rows of keys that left go
to the free list and keys that arrive take free rows, lowest first, so
one seed gives the same rows run after run.
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
    begin_pass. The map owns its native index, which goes when it does."""

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
            self._index = create_route_index([self.keys], [self.rows])

    def __del__(self):
        try:
            from paddlebox_tpu.native.build import destroy_route_index
            destroy_route_index(self._index)
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails
            pass

    @property
    def free_rows(self) -> int:
        return int(self.holes.size) + self.limit - self.top

    def lookup(self, keys: np.ndarray, valid: Optional[np.ndarray],
               padding_id: int) -> np.ndarray:
        """[K] int32 slab row per key; positions where `valid` is False
        map to padding_id. KeyError for a valid key outside the set."""
        if self._index is not None:
            from paddlebox_tpu.native.build import route_lookup
            return route_lookup(self._index, keys, valid, padding_id)
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
            return route_lookup_serve(self._index, keys, -1)
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
        n_new = int(np.count_nonzero(arrived))
        holes = self.holes
        freed = self.keys.size - (keys.size - n_new)
        if freed:
            # both key arrays are sorted, so the keys that stayed are a
            # sorted subsequence of this map's: rank them, free the rest
            stayed = np.zeros(self.keys.size, bool)
            stayed[np.searchsorted(self.keys, keys[~arrived])] = True
            holes = np.sort(np.concatenate([holes, self.rows[~stayed]]))
        take = min(n_new, int(holes.size))
        # top grows only once the holes are used up, and then equals
        # keys.size, which the table holds to limit
        top = self.top + n_new - take
        if n_new:
            rows[arrived] = np.concatenate(
                [holes[:take], np.arange(self.top, top, dtype=np.int32)])
        return RowMap(keys, rows, holes[take:], top, self.limit, arrived,
                      int(freed), self.dense and not n_new and not freed)

    def touched(self, bitmap: np.ndarray):
        """(keys, rows) of the assigned rows a touched-row bitmap marks, in
        key order: the row -> key view the write-back needs. A mark on a
        free row (or the padding row) selects nothing."""
        marks = bitmap[:self.keys.size] if self.dense else bitmap[self.rows]
        sel = np.flatnonzero(marks)
        return self.keys[sel], self.rows[sel]
