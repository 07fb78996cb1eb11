"""The one traffic generator: a mix file's parameters + a configuration's
shapes + a seed -> a pool of MultiSlot text files, the working set's keys,
the table the run starts from, and the arrays the comparison needs.
Vectorised numpy; no per-token Python.

Every seed gives the same sizes (tables, files, lines); the seed changes
only which ids, labels, dense values and starting rows are drawn. A
configuration's slots draw from disjoint tables, one a slot, unless it
says ``"slot_tables": "shared"``: then one table of ``occupied_rows`` ids
lies under every slot (the positions of a sequence over one vocabulary),
and an example may hold a key more than once. A file's
lines depend on (seed, file index) alone, so files are drawn and written
by a few threads.

Line format (what data/generator.write_synthetic_ctr_files writes, so the
native columnar parser reads it): ``1 <click>`` then ``1 <key>`` per sparse
slot then ``<dense_dim> <v> ...``. Every token has a fixed width, so a file
is one uint8 matrix written in one call.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

KEY_BASE = 100_000_000          # keys are KEY_BASE + global row index:
                                # always 9 digits, never 0 (the pad value)
DENSE_SCALE = 10_000            # dense values carry 4 decimals, d.dddd
THREADS = 6                     # a one-chip machine has 13 shared cores
POOLS_KEPT = 8                  # pools of other seeds kept on disk per cell
_MIX_DEFAULTS = {"zipf_a": 1.05, "smallest_table": 10,
                 "batches_per_file": 1}
_POOL, _CHECK, _TABLE = 0x5EED, 0xC4EC, 0x7AB1E     # rng streams
# three decimal digits of 0..999 as ASCII, for rendering keys by thousands
_LUT = np.array([[48 + i // 100, 48 + i // 10 % 10, 48 + i % 10]
                 for i in range(1000)], np.uint8)


def table_sizes(num_slots: int, occupied: int, smallest: int) -> np.ndarray:
    """One table per slot, log-spaced from ``smallest`` ids upward and
    scaled so that they sum to ``occupied`` (Criteo's span 3 .. 40M)."""
    if num_slots == 1:
        return np.array([occupied], np.int64)
    lo, hi = 1.0, 1e6

    def total(r):
        try:
            return sum(smallest * r ** i for i in range(num_slots))
        except OverflowError:   # r ** i past a float: too large a ratio
            return float("inf")
    for _ in range(200):
        mid = (lo * hi) ** 0.5
        if total(mid) < occupied:
            lo = mid
        else:
            hi = mid
    sizes = np.maximum(
        np.floor(smallest * lo ** np.arange(num_slots)), 1).astype(np.int64)
    sizes[-1] += occupied - sizes.sum()
    if sizes[-1] < 1:
        raise ValueError("occupied rows too few for %d tables" % num_slots)
    return sizes


def _spread(n: int) -> int:
    """A multiplier coprime to n: rank -> id = rank * m mod n scatters the
    hot ranks over the whole table, so touched rows interleave with
    untouched ones in sorted key order."""
    for m in (2_654_435_761, 40_503, 7_919, 101, 7, 1):
        if np.gcd(m, n) == 1:
            return m % n if n > 1 else 0
    return 1


def key_weight(keys: np.ndarray) -> np.ndarray:
    """Hidden per-key logit in [-1, 1): a hash, so no table is kept."""
    h = (keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(40)
    return h.astype(np.float64) / float(1 << 23) - 1.0


@dataclasses.dataclass
class Examples:
    rows: np.ndarray     # [n, S] int64 global row index (key - KEY_BASE)
    labels: np.ndarray   # [n] uint8
    dense: np.ndarray    # [n, dense_dim] float32, as the text carries them

    @property
    def keys(self) -> np.ndarray:
        return (self.rows + KEY_BASE).astype(np.uint64)


class Traffic:
    """Everything a cell's run needs from (config, mix, seed).
    ``check_steps`` is the length of the program's scan chunk: the check
    pass is one chunk of batches, in one file."""

    def __init__(self, cfg: dict, mix: dict, seed: int,
                 check_steps: int) -> None:
        mix = {**_MIX_DEFAULTS, **mix}
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.num_slots = int(cfg["num_sparse_slots"])
        self.dense_dim = int(cfg.get("dense_dim", 0))
        self.embedx_dim = int(cfg["embedx_dim"])
        self.batch = int(cfg["batch_size"])
        self.occupied = int(cfg["occupied_rows"])
        tables = cfg.get("slot_tables", "per_slot")
        if tables not in ("per_slot", "shared"):
            raise ValueError("slot_tables is %r, not per_slot or shared"
                             % (tables,))
        # one entry under "shared": the draw broadcasts it over the slots
        self.sizes = table_sizes(
            1 if tables == "shared" else self.num_slots, self.occupied,
            int(mix["smallest_table"]))
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)[:-1]])
        self.mults = np.array([_spread(int(n)) for n in self.sizes], np.int64)
        self.lines = self.batch * int(mix["batches_per_file"])
        self.pool_files = int(mix["pool_files"])
        self.files_per_pass = int(mix["files_per_pass"])
        self.stride = int(mix["stride"])
        self.check_steps = int(check_steps)
        self.check = self._draw([_CHECK], self.check_steps * self.batch)
        self._pool: List[Examples] = []

    # -------------------------------------------------------------- drawing
    def _draw(self, stream: List[int], n: int) -> Examples:
        rng = np.random.default_rng([self.seed] + stream)
        a = float(self.mix["zipf_a"])
        u = rng.random((n, self.num_slots))
        sizes = self.sizes[None, :].astype(np.float64)
        # bounded continuous power law ~ rank^-a, inverse CDF
        top = (sizes + 1.0) ** (1.0 - a)
        rank = np.floor((u * (top - 1.0) + 1.0) ** (1.0 / (1.0 - a))) - 1.0
        rank = np.clip(rank, 0, sizes - 1).astype(np.int64)
        ids = (rank * self.mults[None, :]) % self.sizes[None, :]
        rows = ids + self.offsets[None, :]
        logit = -0.7 + key_weight(rows + KEY_BASE).sum(1) * (
            2.0 / np.sqrt(self.num_slots))
        labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.uint8)
        dense = np.empty((n, 0), np.float32)
        if self.dense_dim:
            d = np.exp(rng.standard_normal((n, self.dense_dim)) * 0.8 - 0.5)
            q = np.minimum(np.rint(d * DENSE_SCALE), 10 * DENSE_SCALE - 1)
            dense = (q / DENSE_SCALE).astype(np.float32)
        return Examples(rows, labels, dense)

    def working_set(self) -> np.ndarray:
        """Every key of the (cut) tables: the pass's whole working set."""
        return np.arange(KEY_BASE, KEY_BASE + self.occupied, dtype=np.uint64)

    def table_chunks(self, chunk: int = 1_000_000):
        return [(lo, min(lo + chunk, self.occupied))
                for lo in range(0, self.occupied, chunk)]

    def table_chunk(self, lo: int, hi: int) -> Dict[str, np.ndarray]:
        """Rows [lo, hi) of the table the run starts from, a column each,
        made from (seed, lo): a trained table's counters and weights (the
        tower's inputs are then not a fresh table's zeros), a fifth of the
        rows without an embedding yet, the optimizer's g2sum fresh."""
        rng = np.random.default_rng([self.seed, _TABLE, lo])
        n, f32 = hi - lo, np.float32
        show = np.floor(np.exp(rng.random(n, f32) * f32(4.0)))
        click = np.floor(show * rng.random(n, f32) * f32(0.5))
        mf = rng.random(n, f32) < 0.8
        x = (rng.random((n, self.embedx_dim), f32) - f32(0.5)) * f32(0.1)
        x *= mf[:, None]
        slot = np.searchsorted(self.offsets, np.arange(lo, hi), "right") - 1
        return {"show": show, "click": click, "mf": mf,
                "w": (rng.random(n, f32) - f32(0.5)) * f32(0.2), "x": x,
                "slot": slot.astype(f32)}

    def table(self, each=None) -> Dict[str, np.ndarray]:
        """The whole starting table, chunks made by a few threads;
        ``each(lo, hi, chunk)`` sees every chunk as it is made (the harness
        writes it to the program's host store there). The reference reads
        the same columns."""
        chunks = self.table_chunks()

        def one(span):
            cols = self.table_chunk(*span)
            if each is not None:
                each(span[0], span[1], cols)
            return cols
        with ThreadPoolExecutor(THREADS) as tp:
            made = list(tp.map(one, chunks))
        return {k: np.concatenate([m[k] for m in made]) for k in made[0]}

    # ---------------------------------------------------------------- files
    def _render(self, ex: Examples) -> np.ndarray:
        n = ex.rows.shape[0]
        parts: List[np.ndarray] = []

        def lit(s: str) -> None:
            parts.append(np.broadcast_to(
                np.frombuffer(s.encode(), np.uint8), (n, len(s))))

        def digits(v: np.ndarray, width: int) -> np.ndarray:
            p = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
            return ((v[:, None] // p[None, :]) % 10 + 48).astype(np.uint8)

        lit("1 ")
        parts.append(digits(ex.labels.astype(np.int64), 1))
        keys = (ex.rows + KEY_BASE).astype(np.int32)
        for s in range(self.num_slots):
            lit(" 1 ")
            k = keys[:, s]
            parts += [_LUT[k // 1_000_000], _LUT[k // 1000 % 1000],
                      _LUT[k % 1000]]
        if self.dense_dim:
            lit(" %d" % self.dense_dim)
            q = np.rint(ex.dense.astype(np.float64) * DENSE_SCALE).astype(
                np.int64)
            for j in range(self.dense_dim):
                d = digits(q[:, j], 5)
                lit(" ")
                parts.append(d[:, :1])
                lit(".")
                parts.append(d[:, 1:])
        lit("\n")
        return np.concatenate(parts, axis=1)

    def write_files(self, out_dir: str):
        """(pool file paths, check file path). Draws the pool (the
        comparison needs its rows either way); files already written by an
        earlier run of the same (config, mix, seed) are kept, and only the
        newest POOLS_KEPT other pools of the cell stay on disk."""
        done = os.path.join(out_dir, "DONE")
        cached = os.path.exists(done)
        if not cached:
            shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)
        pool = [os.path.join(out_dir, "part-%05d.txt" % i)
                for i in range(self.pool_files)]
        check = os.path.join(out_dir, "check.txt")

        def one(i: int) -> Examples:
            ex = self._draw([_POOL, i], self.lines)
            if not cached:
                self._render(ex).tofile(pool[i])
            return ex
        with ThreadPoolExecutor(THREADS) as tp:
            self._pool = list(tp.map(one, range(self.pool_files)))
        if not cached:
            self._render(self.check).tofile(check)
            with open(done, "w") as f:
                f.write("ok\n")
        os.utime(done)
        _evict_pools(out_dir)
        return pool, check

    def pass_file_ids(self, i: int) -> np.ndarray:
        return (self.stride * i + np.arange(self.files_per_pass)
                ) % self.pool_files

    @property
    def examples_per_pass(self) -> int:
        return self.files_per_pass * self.lines

    @property
    def steps_per_pass(self) -> int:
        return self.examples_per_pass // self.batch

    def _trained(self, file_mult: np.ndarray):
        """(times trained, rows [n*S], labels [n*S]) per distinct count
        over the pool files, with the check pass (once)."""
        groups: Dict[float, List[Examples]] = {1.0: [self.check]}
        for mult, ex in zip(file_mult, self._pool):
            if mult:
                groups.setdefault(float(mult), []).append(ex)
        for mult, exs in groups.items():
            yield (mult, np.concatenate([e.rows.ravel() for e in exs]),
                   np.concatenate([np.repeat(e.labels, self.num_slots)
                                   for e in exs]))

    def expected_counts(self, file_mult: np.ndarray, table: dict):
        """(show, click, touched) per global row after the check pass and
        the given number of trainings of each pool file, on top of the
        table the run started from: what the store must hold."""
        show = table["show"].astype(np.float64)
        click = table["click"].astype(np.float64)
        touched = np.zeros(self.occupied, bool)
        for mult, rows, lab in self._trained(file_mult):
            touched[rows] = True
            show += mult * np.bincount(rows, minlength=self.occupied)
            click += mult * np.bincount(rows, weights=lab,
                                        minlength=self.occupied)
        return show, click, np.flatnonzero(touched)


def _evict_pools(out_dir: str) -> None:
    """Keep the newest POOLS_KEPT pools of this cell beside ``out_dir``:
    a check's two sets use the same few seeds, so the second set finds
    its files; a disk is not filled by a long series of seeds."""
    root, name = os.path.split(out_dir)
    tag = name.rsplit("-", 1)[0] + "-"
    others = [os.path.join(root, d) for d in os.listdir(root)
              if d.startswith(tag) and d != name
              and d[len(tag):].isdigit()]
    others.sort(key=lambda d: os.path.getmtime(os.path.join(d, "DONE"))
                if os.path.exists(os.path.join(d, "DONE")) else 0.0)
    for d in others[:max(0, len(others) - POOLS_KEPT)]:
        shutil.rmtree(d, ignore_errors=True)


def feed_config(cfg: dict):
    """The program's DataFeedConfig for a configuration's slots."""
    from paddlebox_tpu.config.configs import DataFeedConfig, SlotConfig
    slots = [SlotConfig("click", type="float", dim=1, is_used=False)]
    slots += [SlotConfig("slot_%d" % i, type="uint64", max_len=1)
              for i in range(int(cfg["num_sparse_slots"]))]
    if cfg.get("dense_dim"):
        slots.append(SlotConfig("dense", type="float",
                                dim=int(cfg["dense_dim"])))
    return DataFeedConfig(slots=tuple(slots),
                          batch_size=int(cfg["batch_size"]))


def make_dataset(feed, files, working_set: np.ndarray,
                 keep_order: bool = False):
    """A BoxDataset whose preload also registers the pass's whole working
    set: the keys this slice of batches does not draw travel through
    PassPreloader.wait -> begin_feed_pass / add_keys / end_feed_pass and
    the PromotePrefetcher like any parsed key. ``keep_order`` (the check
    pass: one file, so one reader) leaves the examples in the file's
    order, so that the reference knows which rows make each step."""
    from paddlebox_tpu.data import BoxDataset

    class WorkingSetDataset(BoxDataset):
        def preload_into_memory(self, add_keys_fn=None):
            if add_keys_fn is not None:
                add_keys_fn(working_set)
            super().preload_into_memory(add_keys_fn=add_keys_fn)

        def local_shuffle(self, seed=None):
            if not keep_order:
                super().local_shuffle(seed)

    ds = WorkingSetDataset(feed)
    ds.set_filelist(list(files))
    return ds
