"""The routed layer's sums into tokens at the three routed cells' shapes:
today's scatter-add against gather rounds and ops/routed_experts'
_segment_sum kernel, each from the device trace; and, handed the module
of another commit, the whole routed layer's forward and backward in both.

    combine   acc + one chunk's weighted rows y [C, D] float32 summed into
              their tokens: scatter (acc.at[tok].add(y w)), rounds (the
              rows gathered in token order, then one gather of a row a
              token a round over the chunk's most rows a token) and kernel
              (the sort into token order, the gather and _segment_sum)
    dispatch  the gradient of x[tok] from the rows' bfloat16 gradients:
              scatter (zeros.at[tok].add), kernel (_segment_sum in float32,
              cast once)
    layer     route() + routed_experts() forward and backward (jax.grad of
              a sum of squares), this tree's against --old's module
              (another commit's ops/routed_experts.py), when given

Each form's device ms a call is the sum of its operations' device time in
a profiler trace of --reps calls; the routing is drawn as a router's: every
token's top_k of random scores over the published experts, so a chunk
holds the even share of pairs and a token its binomial count of them.

    python tools/moe_route_probe.py [--old <routed_experts.py>]  # on a TPU
    JAX_PLATFORMS=cpu python tools/moe_route_probe.py --small     # walks it

The result is one JSON line on stdout.
"""
import argparse
import collections
import glob
import importlib.util
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddlebox_tpu.ops import routed_experts as re_mod  # noqa: E402

# cell: tokens, width, top_k, experts held, published, expert offset,
# expert width, gated
CELLS = {
    "trinity-mini": (16384, 2048, 8, 16, 128, 32, 1024, True),
    "nemotron-3-super": (8192, 1024, 22, 16, 512, 16, 2688, False),
    "xing4-29b-a4b": (8192, 3584, 4, 8, 64, 8, 1024, True),
}
SMALL = {"small": (256, 128, 4, 4, 16, 4, 128, True)}


def device_ms(fn, args, reps):
    """(device ms a call, {operation: ms a call} of the heaviest six)."""
    jax.block_until_ready(fn(*args))
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    per = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                op = re.sub(r"\.\d+$", "", ev.name.split(" ")[0].lstrip("%"))
                per[op] += ev.duration_ns / 1e6 / reps
    return (round(sum(per.values()), 4),
            {k: round(v, 4) for k, v in per.most_common(6)})


def routing(T, top_k, E, seed=3):
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (T, E))
    return jax.lax.top_k(scores, top_k)[1].astype(jnp.int32)


def chunk_of(experts, held, E, off):
    """(at, live) of the first chunk of the sorted pairs, as
    routed_experts makes them."""
    T, top_k = experts.shape
    local = experts.reshape(-1) - off
    key = jnp.where((local >= 0) & (local < held), local, held)
    key, order = jax.lax.sort((key, jnp.arange(T * top_k, dtype=jnp.int32)),
                              num_keys=1)
    C = re_mod.chunk_rows(T, top_k, held, E)
    live = jnp.arange(C) < (key < held).sum()
    return order[:C], live


def _row_weights(weights, at, live):
    return jnp.where(live, weights.reshape(-1)[at], 0.0)[:, None]


def combine_scatter(acc, y, weights, at, live, top_k):
    return acc.at[at // top_k].add(y * _row_weights(weights, at, live))


def combine_rounds(acc, y, weights, at, live, top_k):
    T = acc.shape[0]
    tok_s, _, perm = re_mod._token_order(at, live, top_k, T)
    ys = jnp.where((tok_s < T)[:, None],
                   (y * _row_weights(weights, at, live))[perm], 0.0)
    first = jnp.searchsorted(tok_s, jnp.arange(T + 1, dtype=jnp.int32),
                             method="sort").astype(jnp.int32)
    count = first[1:] - first[:-1]

    def body(carry):
        r, out = carry
        row = jnp.minimum(first[:-1] + r, ys.shape[0] - 1)
        return r + 1, out + jnp.where((r < count)[:, None], ys[row], 0.0)
    return jax.lax.while_loop(lambda c: c[0] < count.max(), body,
                              (jnp.zeros((), jnp.int32), acc))[1]


def combine_kernel(acc, y, weights, at, live, top_k):
    order = re_mod._token_order(at, live, top_k, acc.shape[0])
    return re_mod._combine(top_k, acc, y, weights, at, live, order)


def dispatch_scatter(dxs, at, live, top_k, T):
    rows = jnp.where(live[:, None], dxs, 0)
    return jnp.zeros((T, dxs.shape[1]), dxs.dtype).at[at // top_k].add(rows)


def dispatch_kernel(dxs, at, live, top_k, T):
    order = re_mod._token_order(at, live, top_k, T)
    x = jnp.zeros((T, dxs.shape[1]), dxs.dtype)
    _, back = jax.vjp(lambda x: re_mod._dispatch(x, at // top_k, live, order),
                      x)
    return back(dxs)[0]


def layer_grad(module, T, D, top_k, held, E, off, F, gated):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    rw = jax.random.normal(ks[1], (D, E), jnp.float32) / np.sqrt(D)
    rb = jnp.zeros((E,), jnp.float32)
    mats = [jax.random.normal(k, s, jnp.float32).astype(jnp.bfloat16) / 8.0
            for k, s in ((ks[2], (held, D, F)), (ks[3], (held, D, F)),
                         (ks[4], (held, F, D)))]
    if not gated:
        mats[0] = None

    def loss(x, rw, mats):
        experts, weights = module.route(x, rw, rb, top_k, 2.5)
        y = module.routed_experts(x.astype(jnp.bfloat16), experts, weights,
                                  *mats, off, E)[0]
        return (y ** 2).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (x, rw, mats)


def cell(name, T, D, top_k, held, E, off, F, gated, reps, old):
    experts = routing(T, top_k, E)
    at, live = chunk_of(experts, held, E, off)
    C = at.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    y = jnp.where(live[:, None], jax.random.normal(ks[0], (C, D)), 0.0)
    weights = jax.random.uniform(ks[1], (T, top_k), jnp.float32, 0.1, 0.4)
    acc = jax.random.normal(ks[2], (T, D))
    out = {"cell": name, "rows": C, "width": D, "tokens": T,
           "live": int(live.sum())}
    tok_s = re_mod._token_order(at, live, top_k, T)[0]
    out["kernel_rows_fetched"] = int(re_mod._items(tok_s, T)[3])
    per_token = np.bincount(np.asarray(at[np.asarray(live)] // top_k),
                            minlength=T)
    out["most_rows_a_token"] = int(per_token.max())
    args = (acc, y, weights, at, live, top_k)
    want = combine_scatter(*args)
    for form, fn in (("scatter", combine_scatter), ("rounds", combine_rounds),
                     ("kernel", combine_kernel)):
        f = jax.jit(fn, static_argnums=5)
        out["combine_" + form] = device_ms(f, args, reps)
        out["combine_" + form + "_gap"] = float(
            jnp.abs(f(*args) - want).max() / jnp.abs(want).max())
    dxs = jax.random.normal(ks[0], (C, D)).astype(jnp.bfloat16)
    want = dispatch_scatter(dxs.astype(jnp.float32), at, live, top_k, T)
    for form, fn in (("scatter", dispatch_scatter),
                     ("kernel", dispatch_kernel)):
        f = jax.jit(fn, static_argnums=(3, 4))
        args = (dxs, at, live, top_k, T)
        got = f(*args).astype(jnp.float32)
        out["dispatch_" + form] = device_ms(f, args, reps)
        out["dispatch_" + form + "_gap"] = float(
            jnp.abs(got - want).max() / jnp.abs(want).max())
    for tag, module in (("new", re_mod), ("old", old)):
        if module is None:
            continue
        f, args = layer_grad(module, T, D, top_k, held, E, off, F, gated)
        out["layer_" + tag] = device_ms(f, args, max(2, reps // 2))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--old", default="")
    ap.add_argument("--cells", default="")
    args = ap.parse_args()
    old = None
    if args.old:
        spec = importlib.util.spec_from_file_location("old_routed", args.old)
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "cells": []}
    cells = SMALL if args.small else CELLS
    if args.small:
        re_mod.TILING = (32, 64, 64)
        if old is not None:
            old.TILING = re_mod.TILING
    for name, shape in cells.items():
        if args.cells and name not in args.cells.split(","):
            continue
        result["cells"].append(cell(name, *shape, reps=args.reps, old=old))
        print(json.dumps(result["cells"][-1]), file=sys.stderr, flush=True)
    if args.small:
        result["note"] = "CPU walk: the times are the interpreter's"
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
