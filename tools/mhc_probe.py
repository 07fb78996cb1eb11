"""The hyper-connection's four kernels (ops/mhc.py) at xing4-29b-a4b's
shapes: one sublayer's forward and backward, fused against the XLA form,
each kernel alone, and the precision of phi's products against float64.

    timings   ms of the sublayer's forward + backward (jax.vjp, the
              cotangent given, the sublayer the identity) in both forms,
              and of each kernel alone, at each token-block budget in
              --budgets (MiB of double-buffered [tb, n C] blocks); the
              median of --reps calls, each waited for, so a few tens of
              microseconds of dispatch lie in every reading
    device_ms the device time of each operation of the fused sublayer's
              forward + backward, ms a call, from a profiler trace of --reps
              calls at the committed token blocks (the kernels are named
              mhc_maps, mhc_mix, mhc_mix_bwd, mhc_maps_bwd)
    precision z = r v phi, phi's gradient and dx against numpy float64 on
              the same inputs (max |error| / max |float64|): float32-exact
              products read ~1e-6, one bfloat16 pass ~1e-3 (the XLA form
              at DEFAULT precision is printed beside them for scale)

    python tools/mhc_probe.py                              # on a TPU
    JAX_PLATFORMS=cpu python tools/mhc_probe.py --small    # walks it; no times

The result is one JSON line on stdout.
"""
import argparse
import collections
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddlebox_tpu.ops import mhc  # noqa: E402

HC = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)


def _ms(fn, args, reps, fresh=None):
    """Median ms of ``reps`` waited calls; ``fresh`` remakes the donated
    argument before each call, outside the clock."""
    if fresh is not None:
        args = fresh(args)
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        if fresh is not None:
            args = fresh(args)
            jax.block_until_ready(args)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def inputs(T, n, C, seed=5):
    M = 2 * n + n * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, n * C))
    phi = jax.random.normal(ks[1], (n * C, M)) / np.sqrt(n * C)
    alpha = 0.5 * jnp.sign(jax.random.normal(ks[2], (3,)))
    bias = jnp.sign(jax.random.normal(ks[3], (M,)))
    g = jax.random.normal(ks[4], (T, n * C))
    return x, phi, alpha, bias, g


def sublayer_vjp(x, phi, alpha, bias, g, n):
    """(out, grads): one hyper-connection around the identity, forward and
    backward with g as the cotangent."""
    def fn(x, phi, alpha, bias):
        return mhc.hyper_connection(x, phi, alpha, bias,
                                    lambda u: (u, None), n=n, **HC)[0]
    out, back = jax.vjp(fn, x, phi, alpha, bias)
    return out, back(g)


def timings(T, n, C, reps, budget):
    mhc._BLOCK_BYTES = budget << 20
    nC, M = n * C, 2 * n + n * n
    x, phi, alpha, bias, g = inputs(T, n, C)
    out = {"budget_mib": budget, "block_fwd": mhc._block(T, 2, nC),
           "block_bwd": mhc._block(T, 3, nC)}
    try:
        fused = jax.jit(lambda *a: sublayer_vjp(*a, n))
        out["sublayer_fused_ms"] = _ms(fused, (x, phi, alpha, bias, g), reps)
        phi_t = phi.T
        first = jnp.arange(M) < n
        ab = jnp.stack([jnp.where(first, alpha[0], 0.0),
                        jnp.where(first, bias, 0.0)])
        a = jax.jit(lambda x, p, ab: mhc._maps_call(x, p, ab, n, 1e-6))
        z, r, u = a(x, phi_t, ab)
        out["a_ms"] = _ms(a, (x, phi_t, ab), reps)
        mix = jnp.concatenate([jnp.full((T, n * n), 1.0 / n),
                               jnp.ones((T, n))], axis=1)
        b = jax.jit(lambda x, f, m: mhc._mix_post_add(x, f, m, n))
        out["b_ms"] = _ms(b, (x, u, mix), reps)
        bt = jax.jit(lambda g, x, f, m: mhc._mix_post_add_bwd(
            n, (x, f, m), g))
        out["b_bwd_ms"] = _ms(bt, (g, x, u, mix), reps)
        at = jax.jit(lambda x, p, ab, z, r, dz, du, dxb:
                     mhc._maps_combine_bwd(n, 1e-6, (x, p, ab, z, r),
                                           (dz, du, dxb)),
                     donate_argnums=7)
        dz = jnp.ones((T, M)) * 1e-3
        out["a_bwd_ms"] = _ms(at, (x, phi_t, ab, z, r, dz, u, g), reps,
                              fresh=lambda a: a[:7] + (g + 0.0,))
        stream = T * nC * 4
        out["fused_reads_writes_gb"] = {
            "a": (stream + T * C * 4) / 1e9, "b": (2 * stream + T * C * 4)
            / 1e9, "b_bwd": (3 * stream + 2 * T * C * 4) / 1e9,
            "a_bwd": (3 * stream + T * C * 4) / 1e9}
    except Exception as e:  # a budget the chip's compiler refuses
        out["error"] = "%s: %s" % (type(e).__name__, str(e)[:400])
    return out


def device_ms(T, n, C, reps):
    """{device operation: ms a call} of the fused sublayer's forward +
    backward (a name's numbered copies summed), and their total."""
    args = inputs(T, n, C)
    fused = jax.jit(lambda *a: sublayer_vjp(*a, n))
    jax.block_until_ready(fused(*args))
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    for _ in range(reps):
        jax.block_until_ready(fused(*args))
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
    return {"ops": dict(per.most_common(12)), "total": sum(per.values())}


def precision(T, n, C):
    nC = n * C
    x, phi, alpha, bias, g = inputs(T, n, C, seed=6)
    M = 2 * n + n * n
    first = jnp.arange(M) < n
    ab = jnp.stack([jnp.where(first, alpha[0], 0.0),
                    jnp.where(first, bias, 0.0)])
    z, r, u = jax.jit(lambda x, p, ab: mhc._maps_call(
        x, p, ab, n, 1e-6))(x, phi.T, ab)
    x64, phi64 = np.asarray(x, np.float64), np.asarray(phi, np.float64)
    r64 = 1.0 / np.sqrt((x64 ** 2).mean(1, keepdims=True) + 1e-6)
    z64 = r64 * (x64 @ phi64)
    xla = {p: jax.jit(lambda x, p_=p: jax.lax.rsqrt(
        (x * x).mean(1, keepdims=True) + 1e-6) * jnp.dot(
        x, phi, precision=p_))(x) for p in ("highest", "default")}
    dz = np.array(jax.random.normal(jax.random.PRNGKey(9), (T, M)))
    dz[:, :n] = 0.0         # the pre part: du is zero below
    du = jnp.zeros((T, C))
    dx, dphi_t, _ = jax.jit(lambda *a: mhc._maps_combine_bwd(
        n, 1e-6, a[:5], a[5:]))(x, phi.T, ab, z, r, jnp.asarray(dz), du,
                                jnp.zeros((T, nC)))
    dphi64 = ((r64 * dz).T @ x64)
    dx64 = (r64 * (dz @ phi64.T) - r64 ** 2 * (z64 * dz).sum(
        1, keepdims=True) / nC * x64)
    return {"z_kernel": _gap(z, z64), "z_xla_highest": _gap(xla["highest"],
                                                           z64),
            "z_xla_default": _gap(xla["default"], z64),
            "dphi_kernel": _gap(dphi_t, dphi64), "dx_kernel": _gap(dx, dx64)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--budgets", default=str(mhc._BLOCK_BYTES >> 20))
    args = ap.parse_args()
    dev = jax.devices()[0]
    T, n, C = (256, 4, 256) if args.small else (8192, 4, 3584)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "tokens": T, "streams": n, "width": C}
    result["precision"] = precision(T, n, C)
    real = mhc.fused_block
    mhc.fused_block = lambda *a: 0
    x, phi, alpha, bias, g = inputs(T, n, C)
    result["sublayer_xla_ms"] = _ms(jax.jit(
        lambda *a: sublayer_vjp(*a, n)), (x, phi, alpha, bias, g),
        args.reps)
    mhc.fused_block = real
    del x, phi, alpha, bias, g
    result["device_ms"] = device_ms(T, n, C, args.reps)
    result["fused"] = [timings(T, n, C, args.reps, int(b))
                       for b in args.budgets.split(",")]
    if args.small:
        result["note"] = "CPU walk: the times are the interpreter's"
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
