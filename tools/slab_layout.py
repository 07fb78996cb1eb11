"""Print how a described v5e lays out the pass slab, what the push's row
scatter compiles to and what the pull's gathers compile to, in the
occurrence form (``slab[ids]`` over K) and in the unique + expand form
(``slab[uids]`` over U, then ``view_u[occ_uid]`` over K: two gathers where XLA
kept them apart, the second from a block in memory space S(1)). No chip: the
`topologies` route, as benchmarks/compile_v5e.py. A compile, not a time.

    JAX_PLATFORMS=cpu python -m tools.slab_layout [--rows N] [--width W] [--indices U ...] [--keys K]

f32[C,19]{0,1:T(8,128)} reads: minor-to-major {0,1}, so the ROW id lies on
the 128 lanes and a row's 19 words run down the sublanes, tiled by 8: 24
words (96 B) a row on the chip, and any single-row write is a sub-tile
read-modify-write of three (8,128) tiles shared with 127 other rows."""

import argparse
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1 << 26)
    ap.add_argument("--width", type=int, default=19)
    ap.add_argument("--indices", type=int, nargs="+",
                    default=[79872, 32768])
    ap.add_argument("--keys", type=int, default=79872,
                    help="occurrences a step (the pull's K)")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    scatter = jax.jit(
        lambda slab, u, r: slab.at[u].set(r, mode="drop",
                                          unique_indices=True),
        donate_argnums=0)
    for U in a.indices:
        hlo = scatter.lower(spec((a.rows, a.width), jnp.float32),
                            spec((U,), jnp.int32),
                            spec((U, a.width), jnp.float32)
                            ).compile().as_text()
        slab = sorted(set(re.findall(
            r"f32\[%d,%d\]\{[^}]*\}" % (a.rows, a.width), hlo)))
        fusions = [ln.strip().split(", metadata=")[0]
                   for ln in hlo.splitlines()
                   if ln.lstrip().startswith(("ROOT %fusion", "%fusion"))
                   and "f32[%d,%d]" % (a.rows, a.width) in ln]
        print("indices", U, "slab", slab)
        for f in fusions:
            print("   ", f)

    from paddlebox_tpu.embedding.accessor import ValueLayout
    from paddlebox_tpu.ops.sparse import pull_view_from_rows
    layout = ValueLayout(embedx_dim=a.width - ValueLayout(0).width)

    def occurrence(slab, ids):
        return pull_view_from_rows(slab[ids], layout)

    def unique_expand(slab, uids, occ_uid):
        rows_u = jnp.take(slab, uids, axis=0, mode="clip")
        return jnp.take(pull_view_from_rows(rows_u, layout), occ_uid,
                        axis=0), rows_u

    slab = spec((a.rows, a.width), jnp.float32)
    K, U = a.keys, min(a.indices)
    for name, fn, args in (
            ("occurrence", occurrence, (spec((K,), jnp.int32),)),
            ("unique + expand", unique_expand,
             (spec((U,), jnp.int32), spec((K,), jnp.int32)))):
        hlo = jax.jit(fn).lower(slab, *args).compile().as_text()
        print("pull,", name, "K", K, "U", U)
        # the entry's gather fusions (kCustom) and layout copies, with the
        # memory space of every operand: S(1) is the fast memory
        for ln in hlo[hlo.index("ENTRY"):].splitlines():
            if re.search(r"kind=kCustom| copy\(", ln):
                print("   ", re.split(r", (metadata|backend_config)=",
                                      ln.strip())[0])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
