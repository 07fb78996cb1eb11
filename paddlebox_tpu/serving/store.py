"""mmap-backed xbox store: the serving tier's view composition layer.

The training side writes a day as SaveBase + cadenced SaveDelta xbox
views (train/checkpoint.py, box_wrapper.cc:1286-1318). The serving side
must answer lookups over the COMPOSED view — base + deltas with the
freshest source winning per key — without materializing the table in
RAM: a serving box runs N processes against the same store files, and
page cache is the only copy of the row bytes any of them holds
(HierarchicalKV's cache-semantics store is the model, PAPERS.md).

Three layers, all numpy+mmap (importable with no jax anywhere in the
process — serving fleet children spawn in milliseconds):

  * columnar file   — ``write_xbox_columnar`` / ``MmapXboxStore``: one
                      binary per view (sorted key column + row matrix,
                      64-byte aligned), native hash index over the mmap'd
                      key column (~1 probe/key)
  * view compile    — ``compile_view_dir``: an xbox view dir's
                      embedding.pkl → ``view.xcol`` next to it, written
                      once (atomic, mtime-gated) and shared by every
                      serving process on the box
  * precedence stack— ``MmapViewStack``: the base+delta composition as a
                      newest-first probe chain over per-view stores —
                      per-key precedence IDENTICAL to the
                      XboxModelReader oracle (train/checkpoint.py), which
                      materializes the same composition in RAM on the
                      loader box

Source ordering is STRUCTURAL (day position, then base-after-deltas,
then delta id) with DONE timestamps only as a final tie-break, exactly
the XboxModelReader rule — clock skew between writer hosts can never
invert base/delta precedence (``discover_xbox_sources`` is the single
implementation both readers use).
"""

from __future__ import annotations

import glob
import os
import pickle
import re
import threading
import zlib
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

_XBOX_MAGIC = b"PBTXBOX1"
_HOT_MAGIC = b"PBTHOTK1"

#: compiled columnar twin of a view dir's embedding.pkl
VIEW_COLUMNAR_NAME = "view.xcol"


def write_xbox_columnar(path: str, keys: np.ndarray,
                        rows: np.ndarray) -> str:
    """Serving store file: 8-byte magic, int64 n, int64 dim, then the
    SORTED uint64 key column and the float32 [n, dim] row matrix, each
    64-byte aligned. Written atomically (tmp + rename) so concurrent
    compilers — other processes AND other threads of this one (the tmp
    name carries pid and thread id) — race harmlessly: last replace
    wins with identical bytes."""
    keys = np.ascontiguousarray(keys, np.uint64)
    rows = np.ascontiguousarray(rows, np.float32)
    if keys.ndim != 1 or rows.ndim != 2 or rows.shape[0] != keys.size:
        raise ValueError("keys must be [n], rows [n, dim]")
    if keys.size > 1 and not (keys[1:] > keys[:-1]).all():
        raise ValueError("keys must be strictly sorted")

    def align(off):
        return (off + 63) // 64 * 64

    key_off = align(8 + 8 + 8)
    row_off = align(key_off + keys.nbytes)
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    with open(tmp, "wb") as f:
        f.write(_XBOX_MAGIC)
        f.write(np.int64(keys.size).tobytes())
        f.write(np.int64(rows.shape[1]).tobytes())
        f.seek(key_off)
        keys.tofile(f)
        f.seek(row_off)
        rows.tofile(f)
        # an EMPTY view (a cadenced SaveDelta where nothing crossed the
        # threshold — routine right after a base save cleared delta
        # scores) writes no array bytes, and seek alone doesn't extend
        # the file: pad to the full layout so every reader can mmap the
        # (empty) regions without special-casing the file length
        f.truncate(row_off + rows.nbytes)
    os.replace(tmp, path)
    return path


class MmapXboxStore:
    """ONE columnar view file served via mmap (round-5 verdict item 8):
    no full-RAM ingest of the row matrix — the reference's external
    serving loader role over SaveBase/SaveDelta output.

    Key translation: a native open-addressing hash index over the key
    column (route.cc rt_lookup_serve, ~1 probe/key, misses → -1) — the
    same index tier the trainer's feed path uses at 31M keys/s. The
    index holds keys only (~16 B/key); the row matrix (the dominant
    bytes) stays on disk behind the page cache. Without the native lib,
    lookups fall back to searchsorted directly on the key mmap."""

    def __init__(self, path: str) -> None:
        self.path = path
        n, dim, key_off, row_off = _xbox_header(path)
        self._n, self._dim = n, dim
        if n:
            self._keys = np.memmap(path, np.uint64, "r", key_off, (n,))
            self._rows = np.memmap(path, np.float32, "r", row_off,
                                   (n, dim))
        else:
            # empty view (threshold-less SaveDelta): nothing to map —
            # files written before the round-12 padding fix are only
            # header-long, and mmap rejects zero-length maps anyway
            self._keys = np.empty(0, np.uint64)
            self._rows = np.empty((0, dim), np.float32)
        self._index = None
        from paddlebox_tpu.native.build import create_route_index
        self._index = create_route_index([self._keys]) if n else None

    def __len__(self) -> int:
        return self._n

    @property
    def dim(self) -> int:
        return self._dim

    def lookup_ids(self, keys: np.ndarray) -> np.ndarray:
        """[K] uint64 → [K] int32 row ids; -1 for keys absent from this
        view (the probe primitive the precedence stack composes)."""
        keys = np.ascontiguousarray(
            np.asarray(keys, np.uint64).reshape(-1))
        if not (self._n and keys.size):
            return np.full(keys.size, -1, np.int32)
        if self._index is not None:
            import ctypes

            from paddlebox_tpu.native.build import get_lib
            ids = np.empty(keys.size, np.int32)
            get_lib().rt_lookup_serve(
                self._index,
                keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                keys.size, -1,
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            return ids
        pos = np.searchsorted(self._keys, keys)
        pos = np.minimum(pos, self._n - 1)
        ids = pos.astype(np.int32)
        ids[self._keys[pos] != keys] = -1
        return ids

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """[K] uint64 → [K, dim]; unknown keys are zero rows."""
        keys = np.asarray(keys, np.uint64).reshape(-1)
        out = np.zeros((keys.size, self._dim), np.float32)
        ids = self.lookup_ids(keys)
        hit = ids >= 0
        out[hit] = self._rows[ids[hit]]
        return out

    def close(self) -> None:
        from paddlebox_tpu.native.build import destroy_route_index
        destroy_route_index(self._index)
        self._index = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails;
            # close() is the loud path, this is the last-resort guard
            pass


# ---------------------------------------------------------------------------
# Source discovery (the ONE precedence rule)
# ---------------------------------------------------------------------------


class XboxSource(NamedTuple):
    """One completed xbox view, sortable into APPLY order (oldest
    precedence first): structural position first — day index in the
    cadence, base AFTER the day's deltas (run_day writes the base at day
    end, covering them), deltas by id — and the DONE timestamp only as a
    final tie-break, so writer-host clock skew can never invert
    base/delta precedence."""
    day_index: int
    is_base: int          # 1 = the day's base (sorts after its deltas)
    delta_id: int
    done_ts: float
    path: str


def _done_ts(dirpath: str) -> float:
    with open(os.path.join(dirpath, "DONE")) as f:
        return float(f.read().strip())


def discover_xbox_sources(xbox_model_dir: str,
                          days: Sequence[str]) -> List[XboxSource]:
    """Enumerate completed views (DONE present) for `days` (cadence
    order, oldest first) under the xbox model root, sorted into apply
    order. The last day's base need not exist yet — that's the mid-day
    consumer scenario (a prior day's base plus streaming deltas).
    Raises FileNotFoundError when no base exists at all."""
    sources: List[XboxSource] = []
    have_base = False
    for di, day in enumerate(days):
        root = os.path.join(xbox_model_dir, day)
        if os.path.exists(os.path.join(root, "DONE")):
            have_base = True
            sources.append(XboxSource(di, 1, 0, _done_ts(root), root))
        for d in glob.glob(os.path.join(root, "delta-*")):
            m = re.fullmatch(r"delta-(\d+)", os.path.basename(d))
            if m and os.path.exists(os.path.join(d, "DONE")):
                sources.append(
                    XboxSource(di, 0, int(m.group(1)), _done_ts(d), d))
    if not have_base:
        raise FileNotFoundError(
            f"no completed xbox base under {xbox_model_dir} for "
            f"{tuple(days)}")
    return sorted(sources)


def discover_days(xbox_model_dir: str) -> List[str]:
    """Day directories that have at least one completed view, in LEXICAL
    order. The serving watcher uses this when no explicit day list is
    given — day names must sort lexically in cadence order (day0, day1,
    … or date stamps like 20260803); jobs with other naming pass
    ``days=`` explicitly."""
    out = []
    try:
        entries = sorted(os.listdir(xbox_model_dir))
    except FileNotFoundError:
        return out
    for day in entries:
        root = os.path.join(xbox_model_dir, day)
        if not os.path.isdir(root):
            continue
        if os.path.exists(os.path.join(root, "DONE")) or glob.glob(
                os.path.join(root, "delta-*", "DONE")):
            out.append(day)
    return out


# ---------------------------------------------------------------------------
# View compilation
# ---------------------------------------------------------------------------


def _xbox_header(path: str) -> Tuple[int, int, int, int]:
    """(n, dim, key_off, row_off) of one columnar view file — the ONE
    reader-side twin of write_xbox_columnar's framing (both mmap
    consumers parse through here, so the offsets can't drift apart)."""
    with open(path, "rb") as f:
        if f.read(8) != _XBOX_MAGIC:
            raise ValueError(f"{path}: not an xbox columnar store")
        n = int(np.frombuffer(f.read(8), np.int64)[0])
        dim = int(np.frombuffer(f.read(8), np.int64)[0])
    key_off = (8 + 8 + 8 + 63) // 64 * 64
    row_off = (key_off + n * 8 + 63) // 64 * 64
    return n, dim, key_off, row_off


def read_xbox_columnar(path: str) -> Tuple[np.ndarray, np.ndarray]:
    """Header-parse + mmap one columnar view file → (keys [n] uint64,
    rows [n, dim] f32) read-only views — the one-shot read (no native
    index build; MmapXboxStore is the serving-lookup tier)."""
    n, dim, key_off, row_off = _xbox_header(path)
    if n == 0:
        return np.empty(0, np.uint64), np.empty((0, dim), np.float32)
    return (np.memmap(path, np.uint64, "r", key_off, (n,)),
            np.memmap(path, np.float32, "r", row_off, (n, dim)))


def read_xbox_view(view_dir: str) -> Tuple[np.ndarray, np.ndarray]:
    """(keys, embedding rows) of ONE view dir in either format: the
    legacy ``embedding.pkl`` the pre-round-15 trainer wrote, or the
    directly-emitted columnar file (``view.xcol``). The shared read
    every composition-side consumer (XboxModelReader, tests, examples)
    goes through, so mixed-format day histories compose fine."""
    pkl = os.path.join(view_dir, "embedding.pkl")
    if os.path.exists(pkl):
        with open(pkl, "rb") as f:
            blob = pickle.load(f)
        return (np.asarray(blob["keys"], np.uint64).ravel(),
                np.asarray(blob["embedding"], np.float32))
    xcol = os.path.join(view_dir, VIEW_COLUMNAR_NAME)
    if os.path.exists(xcol):
        keys, rows = read_xbox_columnar(xcol)
        return np.asarray(keys), np.asarray(rows, np.float32)
    raise FileNotFoundError(
        f"{view_dir}: neither embedding.pkl nor {VIEW_COLUMNAR_NAME}")


def compile_view_dir(view_dir: str, force: bool = False) -> str:
    """Compile one view dir's embedding.pkl into its columnar twin
    (``view.xcol``) and return the columnar path. Skipped when an
    up-to-date twin already exists (mtime >= the pkl's), so N serving
    processes on one box compile once and share the file — and its page
    cache — thereafter. NEW-FORMAT dirs (the round-15 checkpoint plane
    writes ``view.xcol`` directly, no pkl at all) detect-and-skip: the
    pickle→columnar re-encode and its staleness window are gone. Keys
    are sorted here (the pkl carries store iteration order); duplicate
    keys in ONE view are a writer bug and raise."""
    src = os.path.join(view_dir, "embedding.pkl")
    out = os.path.join(view_dir, VIEW_COLUMNAR_NAME)
    if not os.path.exists(src):
        if os.path.exists(out):
            return out  # already-columnar view: nothing to compile
        raise FileNotFoundError(
            f"{view_dir}: neither embedding.pkl nor {VIEW_COLUMNAR_NAME}")
    if (not force and os.path.exists(out)
            and os.path.getmtime(out) >= os.path.getmtime(src)):
        return out
    with open(src, "rb") as f:
        blob = pickle.load(f)
    keys = np.asarray(blob["keys"], np.uint64).ravel()
    rows = np.asarray(blob["embedding"], np.float32)
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    if keys.size > 1 and not (keys[1:] > keys[:-1]).all():
        raise ValueError(f"{src}: duplicate keys inside one view")
    return write_xbox_columnar(out, keys, rows[order])


# ---------------------------------------------------------------------------
# Fleet data partition (round 21: N boxes, each serving its shard + hot)
# ---------------------------------------------------------------------------


def write_hot_keys(path: str, keys: np.ndarray) -> str:
    """The fleet's replicated hot set as a tiny binary artifact (8-byte
    magic, int64 n, sorted unique uint64 keys) — written once by the
    bring-up side, read by every box AND every client, so both sides
    agree bit-exactly on which keys any box may answer. Atomic like the
    columnar views (tmp + rename)."""
    keys = np.unique(np.ascontiguousarray(keys, np.uint64))
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    with open(tmp, "wb") as f:
        f.write(_HOT_MAGIC)
        f.write(np.int64(keys.size).tobytes())
        keys.tofile(f)
    os.replace(tmp, path)
    return path


def read_hot_keys(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        if f.read(8) != _HOT_MAGIC:
            raise ValueError(f"{path}: not a hot-key set")
        n = int(np.frombuffer(f.read(8), np.int64)[0])
        keys = np.fromfile(f, np.uint64, n)
    if keys.size != n:
        raise ValueError(f"{path}: truncated hot-key set")
    return keys


class ShardSpec:
    """One box's slice of the fleet's data partition: the keys the
    sharding policy routes to ``index``, plus the replicated HOT set
    (which every box serves, so the client can answer head keys from
    any box without a cross-shard hop — the serving twin of the 2-D
    grid's ReplicatedHotTier).

    ``filter_view`` compiles a view's columnar file down to this box's
    subset (owned ∪ hot) next to the original — mtime-gated and atomic
    like ``compile_view_dir``, so M replicas of one box compile once
    and share the file. Filtering preserves per-view key membership,
    so the precedence chain over filtered views is bit-identical to
    the full-view chain for every key this box serves."""

    def __init__(self, index: int, policy,
                 hot_keys: Optional[np.ndarray] = None) -> None:
        if not 0 <= int(index) < policy.num_shards:
            raise ValueError(
                f"shard index {index} outside policy range "
                f"[0, {policy.num_shards})")
        self.index = int(index)
        self.policy = policy
        self.hot = (np.unique(np.asarray(hot_keys, np.uint64))
                    if hot_keys is not None and len(hot_keys)
                    else np.empty(0, np.uint64))
        # identity token in the filtered file NAME: a policy or hot-set
        # change must never reuse a stale filtered view
        ident = "%s#%d" % (policy.describe(), self.index)
        self._tag = "s%dof%d-%08x" % (
            self.index, policy.num_shards,
            zlib.crc32(ident.encode() + self.hot.tobytes()))

    def describe(self) -> str:
        """Stable identity string (policy identity + shard index) the
        routing validation compares across the client/server boundary."""
        return "%s#%d" % (self.policy.describe(), self.index)

    def mask(self, keys: np.ndarray) -> np.ndarray:
        """[K] bool: keys this box serves (owned by the policy or in
        the replicated hot set)."""
        keys = np.asarray(keys, np.uint64).reshape(-1)
        m = self.policy.shard_of(keys) == self.index
        if self.hot.size:
            pos = np.searchsorted(self.hot, keys)
            pos = np.minimum(pos, self.hot.size - 1)
            m |= self.hot[pos] == keys
        return m

    def filter_view(self, columnar_path: str) -> str:
        out = f"{columnar_path}.{self._tag}"
        if (os.path.exists(out) and os.path.getmtime(out)
                >= os.path.getmtime(columnar_path)):
            return out
        keys, rows = read_xbox_columnar(columnar_path)
        keep = self.mask(keys)
        return write_xbox_columnar(
            out, np.asarray(keys[keep]), np.asarray(rows[keep]))


# ---------------------------------------------------------------------------
# Precedence stack
# ---------------------------------------------------------------------------


class MmapViewStack:
    """The composed base+delta serving view as a newest-first probe
    chain over per-view mmap stores.

    lookup(keys): each key takes its row from the FRESHEST view that
    contains it; keys in no view read as zero rows (the serving default
    for never-trained features) — exactly the XboxModelReader
    composition, without ever materializing the union in RAM. Deltas are
    small next to the base, so the extra probes ride arrays that live in
    a few pages; the base probe is the same ~1-hash-probe/key the
    columnar store serves at 10.75M keys/s.

    A stack is IMMUTABLE once built: the delta-refresh watcher swaps a
    whole new stack into the view manager and in-flight requests keep
    the old object alive until their lookups return (refresh.py)."""

    def __init__(self, sources: Sequence[XboxSource],
                 shard_spec: Optional[ShardSpec] = None,
                 extra_files: Sequence[str] = ()) -> None:
        """``shard_spec`` (round 21): serve only this box's slice of
        the partition — every view compiles to its filtered twin first.
        ``extra_files``: pre-compiled columnar files stacked FRESHEST
        (after the newest source) — the journal-fed overlay rides here;
        they are filtered too when a spec is set."""
        if not (sources or extra_files):
            raise ValueError("need at least one source")
        self.sources = tuple(sources)
        paths = [compile_view_dir(s.path) for s in self.sources]
        paths += list(extra_files)
        if shard_spec is not None:
            paths = [shard_spec.filter_view(p) for p in paths]
        self._open_views(paths)

    @classmethod
    def from_files(cls, paths: Sequence[str]) -> "MmapViewStack":
        """Stack pre-compiled columnar files directly (probes, synthetic
        bases built on disk) — apply order oldest first, like sources."""
        self = cls.__new__(cls)
        self.sources = ()
        self._open_views(list(paths))
        return self

    def _open_views(self, columnar_paths: Sequence[str]) -> None:
        """Open apply-ordered columnar files newest-precedence-first
        and pin the shared dim (empty views carry their header dim but
        don't vote)."""
        if not columnar_paths:
            raise ValueError("need at least one view")
        self._views = [MmapXboxStore(p) for p in reversed(columnar_paths)]
        dims = {v.dim for v in self._views if len(v)}
        if len(dims) > 1:
            raise ValueError(f"views disagree on dim: {sorted(dims)}")
        self._dim = dims.pop() if dims else self._views[0].dim

    @property
    def dim(self) -> int:
        return self._dim

    @property
    def total_rows(self) -> int:
        """Sum of per-view rows (an upper bound on distinct keys — a key
        updated by k views counts k times)."""
        return sum(len(v) for v in self._views)

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """[K] uint64 feasigns → [K, dim] float32, freshest view wins."""
        keys = np.asarray(keys, np.uint64).reshape(-1)
        out = np.zeros((keys.size, self._dim), np.float32)
        pending = np.arange(keys.size)
        for v in self._views:
            if not pending.size:
                break
            if not len(v):
                continue
            ids = v.lookup_ids(keys[pending])
            hit = ids >= 0
            if hit.any():
                out[pending[hit]] = v._rows[ids[hit]]
                pending = pending[~hit]
        return out

    def close(self) -> None:
        for v in self._views:
            v.close()


def build_stack(xbox_model_dir: str,
                days: Optional[Sequence[str]] = None,
                shard_spec: Optional[ShardSpec] = None
                ) -> Tuple[MmapViewStack, Tuple[XboxSource, ...]]:
    """Discover + compile + open the current composed view. Returns the
    stack and its source tuple (the refresh watcher's change key)."""
    days = list(days) if days else discover_days(xbox_model_dir)
    sources = discover_xbox_sources(xbox_model_dir, days)
    return MmapViewStack(sources, shard_spec=shard_spec), tuple(sources)
