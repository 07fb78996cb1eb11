"""What the columnar pack costs a chunk, without a trace: pack_columnar of
one chunk of batches (the stager's `ingest_pack`) at each benchmark cell's
shape, through the numpy pack and through the native one (psr_pack_batch,
GIL released), each serially and on 4 threads, in ms a chunk.

    python -m tools.pack_probe               # every cell's shape
    python -m tools.pack_probe --cells deepfm-criteo.micro-pass --reps 50
    python -m tools.pack_probe --small       # tiny blocks: walks it, times nothing worth reading

A block of the cell's pass (records of one-key slots in order, random keys)
is packed in chunks of `scan_chunk` batches drawn from a permutation, as
BoxDataset.split_batches plans them. The two packs are checked equal on
the first chunk before anything is timed. Prints one JSON line a cell."""

import argparse
import contextlib
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# cell -> (records in the pass's block, one-key slots, batch); 8 a chunk
CELLS = {
    "deepfm-criteo.micro-pass": (16_384, 39, 2048),
    "deepfm-criteo.long-pass": (1_048_576, 39, 2048),
    "trinity-mini.seq-pass": (128, 4096, 4),
    "granite-4-h-micro.seq-pass-2x4096": (64, 4096, 2),
    "nemotron-3-super.seq-pass-2x4096": (64, 4096, 2),
    "olmo-hybrid-7b.seq-pass-2x4096": (64, 4096, 2),
}
CHUNK = 8
THREADS = 4


def block_of(records: int, slots: int, seed: int):
    from paddlebox_tpu.data.columnar import ColumnarBlock
    rng = np.random.default_rng(seed)
    n_keys = records * slots
    return ColumnarBlock(
        keys=rng.integers(1, 1 << 63, n_keys, dtype=np.uint64),
        key_slot=np.tile(np.arange(slots, dtype=np.int32), records),
        labels=rng.integers(0, 2, records, dtype=np.int32),
        rec_offsets=np.arange(0, n_keys + 1, slots, dtype=np.int64))


def probe(cell: str, records: int, slots: int, batch: int, reps: int,
          seed: int) -> dict:
    from paddlebox_tpu.data import columnar
    from paddlebox_tpu.utils.stats import stat_get
    block = block_of(records, slots, seed)
    feed = type("Feed", (), {"batch_size": batch, "task_label_slots": ()})()
    kcap, max_lens = batch * slots, np.ones(slots, np.int64)
    perm = np.random.default_rng(seed + 1).permutation(records)
    starts = range(0, max(records - CHUNK * batch + 1, 1), CHUNK * batch)
    chunks = [[perm[lo + b * batch:lo + (b + 1) * batch]
               for b in range(CHUNK)] for lo in starts]

    def pack(rec_idx):
        return columnar.pack_columnar(block, rec_idx, feed, kcap, slots,
                                      max_lens)

    def numpy_side():
        return mock.patch.object(columnar, "get_lib", lambda: None)

    with numpy_side():
        want = [pack(r) for r in chunks[0]]
    native0 = stat_get("ingest_batches_packed_native")
    got = [pack(r) for r in chunks[0]]
    if stat_get("ingest_batches_packed_native") - native0 != CHUNK:
        raise SystemExit("%s: the native pack did not take the chunk" % cell)
    for g, w in zip(got, want):
        for f in ("keys", "slots", "segments", "valid", "labels"):
            if not np.array_equal(getattr(g, f), getattr(w, f)):
                raise SystemExit("%s: the packs differ in %s" % (cell, f))

    out = {"probe": "pack", "cell": cell, "records": records, "slots": slots,
           "batch": batch, "chunk": CHUNK}
    with ThreadPoolExecutor(THREADS) as pool:
        for side in ("numpy", "native"):
            for mode in ("serial", "threads%d" % THREADS):
                ms = []
                for i in range(reps):
                    group = chunks[i % len(chunks)]
                    with (numpy_side() if side == "numpy"
                          else contextlib.nullcontext()):
                        t0 = time.perf_counter()
                        if mode == "serial":
                            [pack(r) for r in group]
                        else:
                            list(pool.map(pack, group))
                        ms.append(1e3 * (time.perf_counter() - t0))
                out["%s_%s_ms" % (side, mode)] = round(
                    statistics.median(ms), 3)
    out["gain_serial"] = round(out["numpy_serial_ms"]
                               / out["native_serial_ms"], 2)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--small", action="store_true")
    a = ap.parse_args()
    from paddlebox_tpu.native import available
    if not available():
        print(json.dumps({"probe": "pack", "error": "no native library"}))
        return 1
    for cell in a.cells.split(","):
        records, slots, batch = CELLS[cell]
        if a.small:
            records, batch, a.reps = min(records, 8 * CHUNK), 4, 2
        print(json.dumps(probe(cell, records, slots, batch, a.reps, a.seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
