"""Lazy g++ build + ctypes loader for the native components."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_SOURCES = ["slot_parser.cc", "host_store.cc", "route.cc"]
# portable codegen (no -march=native: the .so may outlive the host that
# compiled it)
_CXX = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]

# RLock: get_lib is reachable from __del__ paths (destroy_route_index via
# store/table finalizers) — a GC-triggered finalizer on the thread that is
# mid-build must re-enter, not self-deadlock (boxlint BX801)
_lock = threading.RLock()
_lib: Optional[ctypes.CDLL] = None
_failed = False


def _lib_path() -> str:
    """``_build/libpbtpu_native.<hash>.so`` where the hash covers the bytes
    of every source and the compile command: a library left behind by other
    sources (``_build/`` is git-ignored and travels with copied trees, where
    mtimes say nothing) has another name and can never be loaded."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    for src in _SOURCES:
        with open(os.path.join(_DIR, src), "rb") as fh:
            h.update(fh.read())
    return os.path.join(_BUILD,
                        "libpbtpu_native.%s.so" % h.hexdigest()[:16])


def _build() -> str:
    so_path = _lib_path()
    if not os.path.exists(so_path):
        os.makedirs(_BUILD, exist_ok=True)
        srcs = [os.path.join(_DIR, s) for s in _SOURCES]
        # per-process tmp name so concurrent first-import builds can't
        # clobber each other's output before os.replace
        tmp = f"{so_path}.{os.getpid()}.tmp"
        # bounded: a wedged toolchain must fail loudly into the degraded
        # pure-python tier, not hang import/teardown forever (BX802)
        subprocess.run([*_CXX, "-o", tmp, *srcs], check=True,
                       capture_output=True, timeout=600)
        os.replace(tmp, so_path)
    return so_path


def _bind_parser(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Bind only the parser ABI — the contract user plugin .so files
    implement (they need not export the store/router symbols)."""
    c = ctypes
    P = c.POINTER
    lib.psr_parse_file.restype = c.c_void_p
    lib.psr_parse_file.argtypes = [c.c_char_p, P(c.c_int32), P(c.c_int32),
                                   P(c.c_int32), c.c_int32, c.c_int32]
    for name, res in [("psr_n_keys", c.c_int64), ("psr_n_recs", c.c_int64),
                      ("psr_n_bad", c.c_int64), ("psr_dense_dim", c.c_int32),
                      ("psr_keys", P(c.c_uint64)),
                      ("psr_key_slot", P(c.c_int32)),
                      ("psr_key_rec", P(c.c_int64)),
                      ("psr_labels", P(c.c_int32)),
                      ("psr_dense", P(c.c_float))]:
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = [c.c_void_p]
    lib.psr_free.restype = None
    lib.psr_free.argtypes = [c.c_void_p]
    # optional extended entry (older plugin .so files may lack it)
    if hasattr(lib, "psr_parse_file2"):
        lib.psr_parse_file2.restype = c.c_void_p
        lib.psr_parse_file2.argtypes = [c.c_char_p, P(c.c_int32),
                                        P(c.c_int32), P(c.c_int32),
                                        c.c_int32, c.c_int32,
                                        P(c.c_int32), c.c_int32]
        lib.psr_n_tasks.restype = c.c_int32
        lib.psr_n_tasks.argtypes = [c.c_void_p]
        lib.psr_task_labels.restype = P(c.c_int32)
        lib.psr_task_labels.argtypes = [c.c_void_p]
    return lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    P = c.POINTER
    _bind_parser(lib)
    # a batch's columnar pack (data/columnar.pack_columnar): pointers as
    # plain addresses, so a call costs no pointer objects; the GIL is
    # released for the call, as for every function of a CDLL
    vp, i64 = c.c_void_p, c.c_int64
    lib.psr_pack_batch.restype = i64
    lib.psr_pack_batch.argtypes = [vp, vp, vp, i64, i64, vp, i64, vp,
                                   c.c_int32, i64, vp, vp, vp, vp]
    # host store
    lib.hs_create.restype = c.c_void_p
    lib.hs_create.argtypes = [c.c_int32, c.c_double]
    lib.hs_destroy.restype = None
    lib.hs_destroy.argtypes = [c.c_void_p]
    lib.hs_size.restype = c.c_uint64
    lib.hs_size.argtypes = [c.c_void_p]
    lib.hs_width.restype = c.c_int32
    lib.hs_width.argtypes = [c.c_void_p]
    lib.hs_lookup.restype = None
    lib.hs_lookup.argtypes = [c.c_void_p, P(c.c_uint64), c.c_int64,
                              P(c.c_int64)]
    lib.hs_lookup_or_create.restype = None
    lib.hs_lookup_or_create.argtypes = [c.c_void_p, P(c.c_uint64), c.c_int64,
                                        P(c.c_int64), P(c.c_uint8)]
    lib.hs_gather.restype = None
    lib.hs_gather.argtypes = [c.c_void_p, P(c.c_int64), c.c_int64,
                              P(c.c_float)]
    lib.hs_scatter.restype = None
    lib.hs_scatter.argtypes = [c.c_void_p, P(c.c_int64), c.c_int64,
                               P(c.c_float)]
    lib.hs_erase.restype = c.c_int64
    lib.hs_erase.argtypes = [c.c_void_p, P(c.c_uint64), c.c_int64]
    lib.hs_add_col.restype = c.c_int64
    lib.hs_add_col.argtypes = [c.c_void_p, c.c_int32, c.c_float]
    lib.hs_items.restype = c.c_int64
    lib.hs_items.argtypes = [c.c_void_p, P(c.c_uint64), P(c.c_int64)]
    lib.hs_arena.restype = P(c.c_float)
    lib.hs_arena.argtypes = [c.c_void_p]
    lib.hs_arena_rows.restype = c.c_int64
    lib.hs_arena_rows.argtypes = [c.c_void_p]
    lib.hs_coldest.restype = c.c_int64
    lib.hs_coldest.argtypes = [c.c_void_p, c.c_int64, c.c_int32,
                               P(c.c_uint64), P(c.c_int64)]
    # round 16 (optional: user plugin .so files may predate it) — fused
    # single-probe lookup+gather for the read-mostly store paths
    if hasattr(lib, "hs_lookup_gather"):
        lib.hs_lookup_gather.restype = c.c_int64
        lib.hs_lookup_gather.argtypes = [c.c_void_p, P(c.c_uint64),
                                         c.c_int64, P(c.c_float),
                                         P(c.c_uint8)]
    # batch key routing
    lib.rt_index_create.restype = c.c_void_p
    lib.rt_index_create.argtypes = [P(c.c_uint64), P(c.c_int64), c.c_int32,
                                    P(c.c_int32)]
    lib.rt_index_destroy.restype = None
    lib.rt_index_destroy.argtypes = [c.c_void_p]
    lib.rt_bucketize.restype = c.c_int64
    lib.rt_bucketize.argtypes = [c.c_void_p, P(c.c_uint64), P(c.c_uint8),
                                 c.c_int64, c.c_int32, c.c_int32,
                                 P(c.c_int32), P(c.c_int32), P(c.c_uint64)]
    # round 13 (optional: user plugin .so files may predate it) — the
    # policy-parameterized router: per-key shard from the caller's
    # pre-mixed array instead of the baked-in key % P
    if hasattr(lib, "rt_bucketize_sharded"):
        lib.rt_bucketize_sharded.restype = c.c_int64
        lib.rt_bucketize_sharded.argtypes = [
            c.c_void_p, P(c.c_uint64), P(c.c_int32), P(c.c_uint8),
            c.c_int64, c.c_int32, c.c_int32, P(c.c_int32), P(c.c_int32),
            P(c.c_uint64)]
    lib.rt_lookup.restype = c.c_int64
    lib.rt_lookup.argtypes = [c.c_void_p, P(c.c_uint64), P(c.c_uint8),
                              c.c_int64, c.c_int32, P(c.c_int32),
                              P(c.c_uint64)]
    lib.rt_lookup_serve.restype = c.c_int64
    lib.rt_lookup_serve.argtypes = [c.c_void_p, P(c.c_uint64), c.c_int64,
                                    c.c_int32, P(c.c_int32)]
    lib.rt_fold.restype = c.c_int64
    lib.rt_fold.argtypes = [c.c_void_p, P(c.c_uint64), c.c_int64,
                            P(c.c_uint8), c.c_int64, P(c.c_uint64),
                            P(c.c_int64)]
    lib.rt_dedup.restype = c.c_int64
    lib.rt_dedup.argtypes = [P(c.c_int32), c.c_int64, c.c_int32,
                             P(c.c_int32), P(c.c_int32), P(c.c_int32),
                             P(c.c_int64)]
    # round 11 (optional: user plugin .so files may predate it) — sorted
    # uid-wire dedup, hash probe + radix sort over the uniques only
    if hasattr(lib, "rt_dedup_sorted"):
        lib.rt_dedup_sorted.restype = c.c_int64
        lib.rt_dedup_sorted.argtypes = [P(c.c_int32), c.c_int64, c.c_int32,
                                        P(c.c_int32), P(c.c_int64)]
    return lib


def create_route_index(shard_keys, ids=None) -> Optional[int]:
    """Build the native pass key→id hash index from per-shard SORTED key
    arrays (rt_index_create copies the keys into its own table). Returns the
    opaque handle, or None when the native lib is unavailable or the pass is
    empty. The single-shard PassTable is just the P=1 case. ids, when
    given, holds one int32 array per shard aligned with its keys: the id
    each key maps to (a slab row); without it a key maps to its position
    in its shard's array."""
    import numpy as np
    lib = get_lib()
    shard_keys = [np.ascontiguousarray(k, dtype=np.uint64)
                  for k in shard_keys]
    total = sum(k.size for k in shard_keys)
    if lib is None or not total:
        return None
    if total > 2**31 - 1:
        # rt_* position outputs are int32; beyond that the index would
        # silently truncate — callers fall back to their numpy tier
        import logging
        logging.getLogger("paddlebox_tpu").warning(
            "native route index disabled: %d keys exceeds the int32 "
            "position space — searchsorted fallback active", total)
        return None
    # single-shard: avoid np.concatenate's copy (a serving-scale mmap key
    # column must not be copied into RAM just to build the index;
    # ascontiguousarray on an already-contiguous mmap is a no-op view)
    flat = (np.ascontiguousarray(shard_keys[0]) if len(shard_keys) == 1
            else np.ascontiguousarray(np.concatenate(shard_keys)))
    off = np.zeros(len(shard_keys) + 1, np.int64)
    np.cumsum([k.size for k in shard_keys], out=off[1:])
    flat_ids = None
    if ids is not None:
        ids = [np.ascontiguousarray(i, dtype=np.int32) for i in ids]
        if [i.size for i in ids] != [k.size for k in shard_keys]:
            raise ValueError("create_route_index: ids not aligned with keys")
        flat_ids = (ids[0] if len(ids) == 1
                    else np.ascontiguousarray(np.concatenate(ids)))
    return lib.rt_index_create(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(shard_keys),
        None if flat_ids is None
        else flat_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))


def destroy_route_index(handle) -> None:
    if handle is None:
        return
    lib = get_lib()
    if lib is not None:
        lib.rt_index_destroy(handle)


def route_lookup(handle, keys, valid, padding_id: int):
    """Translate keys → pass-local ids via the native index (rt_lookup).
    valid may be None (all positions valid); invalid positions map to
    padding_id. Raises KeyError for an unregistered valid key."""
    import numpy as np
    lib = get_lib()
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    v = None if valid is None else np.ascontiguousarray(valid, np.uint8)
    out = np.empty(keys.shape[0], np.int32)
    missing = np.zeros(1, np.uint64)
    rc = lib.rt_lookup(
        handle, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)) if v is not None
        else None,
        keys.shape[0], padding_id,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        missing.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    if rc == -1:
        raise KeyError(f"key not registered in feed pass: {missing[0]}")
    return out


def route_lookup_serve(handle, keys, miss_id: int):
    """Translate keys → pass-local ids via the native index, mapping keys
    ABSENT from the index to miss_id instead of raising (rt_lookup_serve).
    This is the hash-probe diff the incremental begin_pass uses: probing
    the PREVIOUS pass's index with the new pass's keys yields each key's
    resident slab row, or miss_id for keys that must be promoted."""
    import numpy as np
    lib = get_lib()
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    out = np.empty(keys.shape[0], np.int32)
    lib.rt_lookup_serve(
        handle, keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        keys.shape[0], miss_id,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out


# keys a thread of route_fold takes at least: under it one thread's probe
# is ~20 ms and a second thread's start buys nothing
_FOLD_SLICE = 1 << 20


def route_fold(handle, keys, seen):
    """One key chunk of the next pass against the index of the map it
    succeeds (rt_fold): marks, in ``seen`` (uint8, one byte a row of that
    map's slab up to its top), the row of every key the index holds, and
    returns (the keys it does not hold, as they came; how many rows this
    call marked first). The call releases the GIL; a chunk of several
    _FOLD_SLICE is folded a slice a thread, on up to half the host's
    cores: the probes are cache misses one thread cannot keep enough of
    in flight."""
    import numpy as np
    lib = get_lib()
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    if seen.dtype != np.uint8 or not seen.flags.c_contiguous:
        raise ValueError("route_fold: seen must be contiguous uint8")
    u8p, u64p = ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint64)

    def fold(part):
        miss = np.empty(part.shape[0], np.uint64)
        marked = np.zeros(1, np.int64)
        n_miss = lib.rt_fold(
            handle, part.ctypes.data_as(u64p), part.shape[0],
            seen.ctypes.data_as(u8p), seen.shape[0],
            miss.ctypes.data_as(u64p),
            marked.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if n_miss < 0:
            raise ValueError("route_fold: the index holds a row outside "
                             "the %d marks" % seen.shape[0])
        # a copy: the view would keep the chunk-sized buffer alive
        return miss[:n_miss].copy(), int(marked[0])

    threads = min(keys.shape[0] // _FOLD_SLICE, (os.cpu_count() or 2) // 2)
    if threads < 2:
        return fold(keys)
    from concurrent.futures import ThreadPoolExecutor
    # two slices may hold one key and both count its mark: count here
    before = int(np.count_nonzero(seen))
    with ThreadPoolExecutor(threads) as pool:
        misses = [m for m, _ in pool.map(fold, np.array_split(keys, threads))]
    return np.concatenate(misses), int(np.count_nonzero(seen)) - before


def load_lib(path: str) -> ctypes.CDLL:
    """Bind a user-supplied shared object honoring the parser C ABI
    (the DLManager dlopen path for custom parser plugins). Plugins only
    implement psr_*; the internal store/router symbols are not required.

    Ordering contract: within each record, emit keys grouped by used-slot
    ordinal in ascending (config) order — downstream pooling assumes
    nondecreasing segment ids. pack_columnar detects and repairs violations
    with a stable sort, at a per-batch host cost plugins can avoid by
    honoring the order."""
    return _bind_parser(ctypes.CDLL(path))


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _failed
    if _lib is not None or _failed:
        return _lib
    with _lock:
        if _lib is not None or _failed:
            return _lib
        try:
            # the lock IS the build serializer: exactly one thread may g++
            # the .so; contenders legitimately wait on the (bounded,
            # first-call-only) compile
            _lib = _bind(ctypes.CDLL(_build()))  # boxlint: disable=BX601
        except Exception as e:
            # LOUD degraded mode: every consumer (host store, router,
            # parser) silently drops to a ~10× slower pure-python path —
            # warn once and bump a stat so CI / dashboards notice a broken
            # native build instead of a mystery slowdown
            _failed = True
            _lib = None
            import logging
            from paddlebox_tpu.utils.stats import stat_add
            detail = e.stderr.decode()[-500:] if isinstance(
                e, subprocess.CalledProcessError) and e.stderr else repr(e)
            logging.getLogger("paddlebox_tpu").warning(
                "native library build/load FAILED — falling back to "
                "pure-python host store/router/parser (order-of-magnitude "
                "slower). Cause: %s", detail)
            stat_add("native_lib_unavailable")
    return _lib


def available() -> bool:
    return get_lib() is not None
