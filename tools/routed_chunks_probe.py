"""routed_experts with 1, 2 and 3 live chunks against every held expert on
every token, masked: values and the gradients of x, the weights and both
matrices, bfloat16 operands on both sides. Three readings a case:

    plain    the chunks one after another with no lax.cond and no loop
             (_chunk at a static offset): the chunk itself, values only
    looped   routed_experts as it runs (ops/routed_experts._looped)
    grouped  one grouped product over the second chunk's rows alone (its
             leading groups empty, rows past the last pair unwritten)
             against a product a group

    chiprun -- python tools/routed_chunks_probe.py      # nemotron-3-super's shapes
    JAX_PLATFORMS=cpu python tools/routed_chunks_probe.py --small

Written for PERF.md Open question 20i (a check pass whose gradients of one
layer's routed path read 8% off the reference).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddlebox_tpu.ops import routed_experts as module  # noqa: E402


def _error(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    size = np.linalg.norm(b)
    return {"norm_gap": float(abs(np.linalg.norm(a) - size) / size),
            "error": float(np.linalg.norm(a - b) / size)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args()
    if args.small:
        module.TILING = (32, 64, 32)
        T, D, F, E, H, K = 512, 128, 256, 64, 4, 8
        cases = (("even", H / E), ("two_chunks", 0.2), ("five_chunks", 0.55))
    else:
        T, D, F, E, H, K = 8192, 1024, 2688, 512, 16, 22
        cases = (("even", H / E), ("two_chunks", 0.10),
                 ("three_chunks", 0.15))
    off = H
    C = module.chunk_rows(T, K, H, E)
    n_chunks = -(-T * K // C)
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (T, D)).astype(bf16)
    wu = (jax.random.normal(ks[1], (H, D, F)) / np.sqrt(D)).astype(bf16)
    wd = (jax.random.normal(ks[2], (H, F, D)) / np.sqrt(F)).astype(bf16)
    t = jax.random.normal(ks[3], (T, D))
    for name, share in cases:
        # each of a token's K choices is a held expert with this probability
        held = jax.random.uniform(ks[4], (T, K)) < share
        experts = jnp.where(
            held, jax.random.randint(ks[5], (T, K), off, off + H),
            jax.random.randint(ks[6], (T, K), off + H, E)).astype(jnp.int32)
        w = jax.random.uniform(ks[7], (T, K), jnp.float32, 0.1, 0.4)

        def looped(x, w, wu, wd):
            y, sizes, _ = module.routed_experts(x, experts, w, None, wu,
                                                wd, off, E)
            return (y * t).sum(), (y, sizes)

        def dense(x, w, wu, wd):
            y = jnp.zeros((T, D), jnp.float32)
            for g in range(H):
                we = jnp.where(experts == off + g, w, 0.0).sum(
                    1, keepdims=True)
                u = jnp.dot(x, wu[g],
                            preferred_element_type=jnp.float32).astype(bf16)
                a = jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(
                    bf16)
                y = y + we * jnp.dot(a, wd[g],
                                     preferred_element_type=jnp.float32)
            return (y * t).sum(), y

        @jax.jit
        def plain(x, w, wu, wd):
            """(y of the chunks one after another, the second chunk's up
            product by one grouped call and by a product a group)."""
            local = experts.reshape(-1) - off
            key = jnp.where((local >= 0) & (local < H), local, H)
            key, order = jax.lax.sort(
                (key, jnp.arange(T * K, dtype=jnp.int32)), num_keys=1)
            sizes = (key[:, None] == jnp.arange(H, dtype=jnp.int32)
                     ).sum(axis=0, dtype=jnp.int32)
            ends = jnp.cumsum(sizes)
            index = (jnp.pad(order, (0, n_chunks * C - T * K)),
                     ends - sizes, ends)
            y = jnp.zeros((T, D), jnp.float32)
            for i in range(n_chunks):
                y = module._chunk(C, K, i * C, y, index, x, w.reshape(-1),
                                  None, wu, wd)
            at = index[0][C:2 * C]
            here = (jnp.clip(ends, C, 2 * C)
                    - jnp.clip(ends - sizes, C, 2 * C))
            rows = jnp.arange(C) < here.sum()
            xs = jnp.where(rows[:, None], x[at // K], 0)
            got = jnp.where(rows[:, None],
                            module._grouped(xs, wu, here, jnp.float32), 0)
            group = jnp.searchsorted(jnp.cumsum(here), jnp.arange(C),
                                     side="right")
            want = jnp.zeros((C, F), jnp.float32)
            for g in range(H):
                want = want + jnp.where(
                    (rows & (group == g))[:, None],
                    jnp.dot(xs, wu[g], preferred_element_type=jnp.float32),
                    0)
            return y, got, want

        (got, (y_got, sizes)), got_g = jax.jit(jax.value_and_grad(
            looped, argnums=(0, 1, 2, 3), has_aux=True))(x, w, wu, wd)
        (want, y_want), want_g = jax.jit(jax.value_and_grad(
            dense, argnums=(0, 1, 2, 3), has_aux=True))(x, w, wu, wd)
        y_plain, up_got, up_want = plain(x, w, wu, wd)
        pairs = int(sizes.sum())
        rec = {"pairs_held": pairs, "live_chunks": -(-pairs // C),
               "value": [float(got), float(want)],
               "y_looped": _error(y_got, y_want),
               "y_plain": _error(y_plain, y_want)}
        if pairs > C:
            rec["grouped_second_chunk"] = _error(up_got, up_want)
        for leaf, a, b in zip(("x", "weights", "w_up", "w_down"), got_g,
                              want_g):
            if leaf == "weights":   # an absent expert's weight moves nothing
                b = np.where(np.asarray(held), b, 0.0)
            rec[leaf] = _error(a, b)
        print(name, json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
