"""On the chip: what the pull's gather costs an occurrence against a distinct
row. Times, inside one compiled scan of 8 steps, the occurrence form
(``slab[ids]`` over K) against the unique + expand form (``slab[uids]`` over
U, the view made from those rows, ``view_u[occ_uid]`` over K), both handing
back the view and the rows of ``uids`` as the train step uses them, and
prints the gathers each form compiled to.

    python -m tools.pull_gather_probe            # the deepfm-criteo cell's shapes
    python -m tools.pull_gather_probe --rows 32768 --width 2057 --slots 4096 --batch 2 --shared --occupied 25088
    JAX_PLATFORMS=cpu python -m tools.pull_gather_probe --small   # walks it, no time

ids are drawn by the benchmark's generator (benchmarks/harness/traffic.py:
a table a slot, or one under every slot with --shared; rank^-1.05)."""

import argparse
import json
import re
import sys
import time

import numpy as np

STEPS = 8


def draw_ids(a) -> np.ndarray:
    """[STEPS, K] slab rows, drawn by the benchmark's own generator."""
    sys.path.insert(0, "benchmarks")
    from harness.traffic import Traffic
    cfg = {"num_sparse_slots": a.slots, "embedx_dim": 0,
           "batch_size": a.batch, "occupied_rows": a.occupied,
           "slot_tables": "shared" if a.shared else "per_slot"}
    mix = {"pool_files": 1, "files_per_pass": 1, "stride": 1}
    return Traffic(cfg, mix, a.seed, STEPS).check.rows.reshape(
        STEPS, -1).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1 << 26)
    ap.add_argument("--width", type=int, default=19)
    ap.add_argument("--slots", type=int, default=39)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--shared", action="store_true")
    ap.add_argument("--occupied", type=int, default=12_500_000)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--small", action="store_true")
    a = ap.parse_args()
    if a.small:
        a.rows, a.batch, a.occupied, a.reps = 1 << 12, 16, 2000, 2
    K = a.slots * a.batch

    import jax
    import jax.numpy as jnp
    from paddlebox_tpu.embedding.accessor import ValueLayout
    from paddlebox_tpu.embedding.pass_table import (dedup_ids,
                                                    occurrence_uid_slots,
                                                    push_domain)
    from paddlebox_tpu.ops.sparse import (gather_slab_rows,
                                          pull_sparse_unique,
                                          pull_view_from_rows)

    layout = ValueLayout(embedx_dim=a.width - ValueLayout(0).width)
    ids, uids, occ, first = [], [], [], []
    n_us = []
    for i in draw_ids(a):
        u, perm, inv, n_u = dedup_ids(i, a.rows)
        f = np.zeros_like(perm)       # an occurrence of each uid: the
        f[inv[::-1]] = perm[::-1]     # parent's first_idx
        ids.append(i), uids.append(u), first.append(f), n_us.append(n_u)
        occ.append(occurrence_uid_slots(perm, inv))
    U = push_domain(max(n_us), K)
    batch = {"ids": np.stack(ids), "uids": np.stack(uids)[:, :U],
             "occ_uid": np.stack(occ), "first_idx": np.stack(first)[:, :U]}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    @jax.jit
    def make_slab():
        r = jax.lax.broadcasted_iota(jnp.float32, (a.rows, a.width), 0)
        c = jax.lax.broadcasted_iota(jnp.float32, (a.rows, a.width), 1)
        return r * 1e-3 + c

    def occurrence(slab, b):      # the step's pull through PR 41
        rows = gather_slab_rows(slab, b["ids"], layout)
        return (pull_view_from_rows(rows, layout),
                jnp.take(rows, b["first_idx"], axis=0))

    def unique_expand(slab, b):   # the program's own
        return pull_sparse_unique(slab, b["uids"], b["occ_uid"], layout)

    def scanned(fn):
        return jax.jit(lambda slab, bs: jax.lax.scan(
            lambda c, b: (c, fn(slab, b)), 0, bs)[1])

    slab = make_slab()
    out = {"device": jax.devices()[0].device_kind, "rows": a.rows,
           "width": a.width, "K": K, "U": U,
           "n_u": [int(n) for n in n_us]}
    got = {}
    for name, fn in (("occurrence", occurrence),
                     ("unique_expand", unique_expand)):
        f = scanned(fn)
        hlo = f.lower(slab, batch).compile().as_text()
        out[name + "_gathers"] = [
            re.sub(r", metadata=.*", "", ln.strip())[:400]
            for ln in hlo.splitlines() if re.search(r"\bgather\(", ln)]
        got[name] = jax.block_until_ready(f(slab, batch))
        t0 = time.perf_counter()
        for _ in range(a.reps):
            r = f(slab, batch)
        jax.block_until_ready(r)
        if not a.small:
            out[name + "_ms_per_step"] = (
                (time.perf_counter() - t0) / a.reps / STEPS * 1e3)
    view_o, rows_o = got["occurrence"]
    view_u, rows_u = got["unique_expand"]
    real = np.arange(U)[None, :] < np.asarray(n_us)[:, None]
    out["same_view"] = bool(jnp.array_equal(view_o, view_u))
    out["same_rows_of_real_uids"] = bool(
        np.array_equal(np.asarray(rows_o)[real], np.asarray(rows_u)[real]))
    print(json.dumps(out, indent=1))
    return 0 if out["same_view"] and out["same_rows_of_real_uids"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
