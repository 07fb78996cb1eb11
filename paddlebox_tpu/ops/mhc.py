"""A manifold-constrained hyper-connection (mHC; DeepSeek, arXiv
2512.24880): a residual stream of n streams of C values, mixed by a
doubly stochastic matrix, around a sublayer F that reads one combination
of the streams and writes into each with its own weight.

With x the [n, C] streams of a token and x_hat = vec(x) / rms(vec(x))
over all n C values (no weight: phi takes any scale of its own),

    H_pre  = sigmoid(a_pre  x_hat phi_pre + b_pre)          [1, n]
    H_post = 2 sigmoid(a_post x_hat phi_post + b_post)      [1, n]
    H_res  = Sinkhorn(a_res mat(x_hat phi_res) + b_res)     [n, n]
    x'     = H_res x + H_post^T F(H_pre x)

phi = [phi_pre | phi_post | phi_res] is one [n C, 2 n + n^2] matrix and
b = [b_pre | b_post | vec(b_res)] one vector; a = (a_pre, a_post, a_res).
Sinkhorn takes exp of the logits clamped to [lo, hi], then ``iters``
rounds of a row step and a column step, each dividing by its sums + eps:
the columns sum to 1 after the last round, the rows to within the
rounds' convergence. The rounds are one loop (a loop written out compiles
40 divisions a sublayer, forward and backward); its backward pass is
autodiff through the loop, which keeps a [T, n, n] array a round.

``hyper_connection`` takes the streams as [..., n C]: stream j is the
column band [j C, (j + 1) C), lane-aligned where C is a multiple of 128.
There, and where the T tokens split into whole token blocks
(``fused_block``), the streams' traffic is four Pallas kernels, each one
read of the [T, n C] streams (v = vec(x), r = rsqrt(mean(v^2) + eps),
z = r v phi, so x_hat is never formed):

    A   z [T, 2n + n^2], r [T, 1] and u = sum_j H_pre_j x_j [T, C]
        (the norm's sum of squares and v phi in the same read);
    B   x'_i = sum_j H_res_ij x_j + H_post_i f;
    B^T dx_j = sum_i H_res_ij g_i, dH_res_ij = <g_i, x_j>,
        dH_post_i = <g_i, f>, df = sum_i H_post_i g_i;
    A^T dx = dx_B + H_pre_j du + r dz phi^T - r^2 (z . dz) / (n C) v,
        dz taking dH_pre_j = <du, x_j> in the kernel; phi's gradient
        (r dz)^T v summed over the token blocks in an output block that
        stays in fast memory across the grid's one sequential axis (a
        later XLA product would read the streams once more).

A hands the streams on as a third output, which B reads, so B^T's dx
arrives in A^T as a cotangent and the two sum there, not in a pass of
their own. H_post, the res logits and Sinkhorn stay in XLA on [T, 2n + n^2]
arrays. Each kernel is compiled by Mosaic where the program is lowered for
a TPU and interpreted anywhere else (lax.platform_dependent, as
ops/attention.py). Any other shape takes the XLA form below
(``stream_maps``, ``pre_combine``, ``res_mix_post_add`` over [..., n, C]).

Everything here is float32, the maps' products at the highest matmul
precision, whatever the sublayer's compute dtype: the streams, their
norm, the three maps and the mixes (scope ``mhc``, the kernels' backward
rules included), and the Sinkhorn rounds (scope ``mhc_sinkhorn``).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_LANES = 128
# token blocks: powers of two from _MAX_BLOCK down to _MIN_BLOCK (bfloat16
# packs 16 rows a tile), the largest whose double-buffered [tb, n C]
# float32 blocks take at most _BLOCK_BYTES; _VMEM_BYTES leaves room beside
# them for the [tb, C] values a kernel holds
_MAX_BLOCK, _MIN_BLOCK = 256, 16
_BLOCK_BYTES = 32 << 20
_VMEM_BYTES = 96 << 20


def sinkhorn(logits: jnp.ndarray, iters: int, eps: float,
             clamp: Tuple[float, float]) -> jnp.ndarray:
    """[..., n, n] logits -> a doubly stochastic [..., n, n]: exp of the
    clamped logits, then ``iters`` rounds of rows, then columns, each
    divided by its sum + eps."""
    def round_(_, m):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        return m / (m.sum(axis=-2, keepdims=True) + eps)
    return jax.lax.fori_loop(
        0, iters, round_, jnp.exp(jnp.clip(logits.astype(F32), *clamp)))


def stream_maps(x: jnp.ndarray, phi: jnp.ndarray, alpha: jnp.ndarray,
                bias: jnp.ndarray, *, iters: int, eps: float,
                clamp: Tuple[float, float], norm_eps: float
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(H_pre [..., n], H_post [..., n], H_res [..., n, n]) of the streams
    x [..., n, C]; phi [n C, 2 n + n^2], alpha [3], bias [2 n + n^2].
    Under its own checkpoint: the backward pass keeps x (which the caller
    keeps anyway) and makes x_hat again, not a second [..., n C] float32
    array a sublayer."""
    n = x.shape[-2]

    @jax.checkpoint
    def maps(x, phi, alpha, bias):
        with jax.named_scope("mhc"):
            flat = x.reshape(x.shape[:-2] + (-1,)).astype(F32)
            x_hat = flat * jax.lax.rsqrt(
                (flat * flat).mean(-1, keepdims=True) + norm_eps)
            logits = jnp.dot(x_hat, phi.astype(F32), precision=_HI)
            alpha, bias = alpha.astype(F32), bias.astype(F32)
            pre = jax.nn.sigmoid(alpha[0] * logits[..., :n] + bias[:n])
            post = 2.0 * jax.nn.sigmoid(alpha[1] * logits[..., n:2 * n]
                                        + bias[n:2 * n])
            res = (alpha[2] * logits[..., 2 * n:] + bias[2 * n:]).reshape(
                x.shape[:-2] + (n, n))
        with jax.named_scope("mhc_sinkhorn"):
            res = sinkhorn(res, iters, eps, clamp)
        return pre, post, res
    return maps(x, phi, alpha, bias)


def pre_combine(x: jnp.ndarray, pre: jnp.ndarray) -> jnp.ndarray:
    """H_pre x: the sublayer's input [..., C] from the streams [..., n, C]
    (products of n terms a value, elementwise: no matrix unit)."""
    with jax.named_scope("mhc"):
        return (pre[..., :, None] * x).sum(axis=-2)


def res_mix_post_add(x: jnp.ndarray, res: jnp.ndarray, post: jnp.ndarray,
                     f: jnp.ndarray) -> jnp.ndarray:
    """H_res x + H_post^T f: the streams [..., n, C] after the sublayer
    wrote f [..., C], float32."""
    with jax.named_scope("mhc"):
        out = post[..., :, None] * f.astype(F32)[..., None, :]
        for j in range(x.shape[-2]):
            out = out + res[..., :, j, None] * x[..., None, j, :]
        return out


# ------------------------------------------------------------ fused form
def _block(tokens: int, wide: int, streams: int) -> int:
    """The token block of a kernel that holds ``wide`` [tb, streams]
    float32 blocks, double-buffered; 0 where none divides ``tokens``."""
    tb = _MAX_BLOCK
    while tb >= _MIN_BLOCK:
        if tokens % tb == 0 and 2 * wide * tb * streams * 4 <= _BLOCK_BYTES:
            return tb
        tb //= 2
    return 0


def fused_block(tokens: int, n: int, width: int) -> int:
    """The token block the kernels of a hyper-connection over ``tokens``
    tokens of n streams of ``width`` would take (the backward kernels
    hold three [tb, n width] blocks), or 0: then the XLA form."""
    if width % _LANES:
        return 0
    return _block(tokens, 3, n * width)


def _run(name, kernel, grid, in_specs, out_specs, out_shape, semantics,
         *args, aliases=None):
    """One pallas_call over the token blocks: compiled by Mosaic where the
    program is lowered for a TPU, interpreted anywhere else. ``name``
    names the kernel's operation in a device trace."""
    def call(interpret: bool):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, input_output_aliases=aliases or {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(semantics,),
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=interpret, name=name)
    return jax.lax.platform_dependent(*args, tpu=call(False),
                                      default=call(True))


def _col(a, k: int):
    """Column k of a [tb, m] value as [tb, 1] (a masked lane sum: no
    slice at an unaligned lane)."""
    at = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) == k
    return jnp.sum(jnp.where(at, a, 0.0), axis=1, keepdims=True)


def _cols(cols, m: int):
    """[tb, m] from m [tb, 1] columns."""
    at = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], m), 1)
    out = jnp.zeros(at.shape, F32)
    for k, c in enumerate(cols):
        out = jnp.where(at == k, c, out)
    return out


def _dot_t(a, b):
    """a [p, K] . b [q, K]^T -> [p, q], float32 exact."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI, preferred_element_type=F32)


def _maps_kernel(x_ref, phi_ref, ab_ref, z_ref, r_ref, u_ref, *, n, C,
                 norm_eps):
    """A: z, r and u of one token block; ab = [a_pre | 0; b_pre | 0]."""
    p = jnp.zeros(z_ref.shape, F32)
    ss = jnp.zeros(r_ref.shape, F32)
    for j in range(n):
        xj = x_ref[:, j * C:(j + 1) * C]
        ss = ss + jnp.sum(xj * xj, axis=1, keepdims=True)
        p = p + _dot_t(xj, phi_ref[:, j * C:(j + 1) * C])
    r = jax.lax.rsqrt(ss / (n * C) + norm_eps)
    z = r * p
    pre = jax.nn.sigmoid(z * ab_ref[0:1, :] + ab_ref[1:2, :])
    u = _col(pre, 0) * x_ref[:, 0:C]
    for j in range(1, n):
        u = u + _col(pre, j) * x_ref[:, j * C:(j + 1) * C]
    z_ref[...] = z
    r_ref[...] = r
    u_ref[...] = u


def _maps_bwd_kernel(x_ref, phi_ref, ab_ref, z_ref, r_ref, dz_ref, du_ref,
                     dxb_ref, dx_ref, dphi_ref, dlog_ref, *, n, C):
    """A^T: dx (dx_B's block aliased), phi^T's gradient summed over the
    blocks in dphi_ref, and the pre logits' gradient [tb, 2n + n^2]."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, F32)

    z, r, du = z_ref[...], r_ref[...], du_ref[...]
    pre = jax.nn.sigmoid(z * ab_ref[0:1, :] + ab_ref[1:2, :])
    dpre = _cols([jnp.sum(du * x_ref[:, j * C:(j + 1) * C], axis=1,
                          keepdims=True) for j in range(n)], z.shape[1])
    dlog = dpre * pre * (1.0 - pre)
    dz = dz_ref[...] + ab_ref[0:1, :] * dlog
    coef = r * r * jnp.sum(z * dz, axis=1, keepdims=True) / (n * C)
    rdz = r * dz
    for j in range(n):
        band = slice(j * C, (j + 1) * C)
        xj, phij = x_ref[:, band], phi_ref[:, band]
        w = jnp.dot(dz, phij, precision=_HI, preferred_element_type=F32)
        dx_ref[:, band] = (dxb_ref[:, band] + _col(pre, j) * du + r * w
                           - coef * xj)
        dphi_ref[:, band] += jax.lax.dot_general(
            rdz, xj, (((0,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=F32)
    dlog_ref[...] = dlog


def _mix_kernel(x_ref, f_ref, mix_ref, out_ref, *, n, C):
    """B: mix = [vec(H_res) | H_post] a token."""
    mix, f = mix_ref[...], f_ref[...].astype(F32)
    for i in range(n):
        o = _col(mix, n * n + i) * f
        for j in range(n):
            o = o + _col(mix, i * n + j) * x_ref[:, j * C:(j + 1) * C]
        out_ref[:, i * C:(i + 1) * C] = o


def _mix_bwd_kernel(g_ref, x_ref, f_ref, mix_ref, dx_ref, df_ref, dmix_ref,
                    *, n, C):
    """B^T: dx, df (f's dtype) and d[vec(H_res) | H_post]."""
    mix, f = mix_ref[...], f_ref[...].astype(F32)
    d_res, d_post = [None] * (n * n), []
    df = jnp.zeros(f.shape, F32)
    for i in range(n):
        gi = g_ref[:, i * C:(i + 1) * C]
        df = df + _col(mix, n * n + i) * gi
        d_post.append(jnp.sum(gi * f, axis=1, keepdims=True))
        for j in range(n):
            d_res[i * n + j] = jnp.sum(gi * x_ref[:, j * C:(j + 1) * C],
                                       axis=1, keepdims=True)
    for j in range(n):
        d = _col(mix, j) * g_ref[:, 0:C]
        for i in range(1, n):
            d = d + _col(mix, i * n + j) * g_ref[:, i * C:(i + 1) * C]
        dx_ref[:, j * C:(j + 1) * C] = d
    df_ref[...] = df.astype(df_ref.dtype)
    dmix_ref[...] = _cols(d_res + d_post, n * n + n)


def _rows(tb: int, cols: int):
    return pl.BlockSpec((tb, cols), lambda i: (i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _maps_call(x, phi_t, ab, n: int, norm_eps: float):
    T, nC = x.shape
    C, M = nC // n, phi_t.shape[0]
    tb = _block(T, 2, nC)
    return _run(
        "mhc_maps",
        functools.partial(_maps_kernel, n=n, C=C, norm_eps=norm_eps),
        (T // tb,), [_rows(tb, nC), _whole(phi_t.shape), _whole(ab.shape)],
        [_rows(tb, M), _rows(tb, 1), _rows(tb, C)],
        [jax.ShapeDtypeStruct((T, M), F32), jax.ShapeDtypeStruct((T, 1), F32),
         jax.ShapeDtypeStruct((T, C), F32)], "parallel", x, phi_t, ab)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _maps_combine(x, phi_t, ab, n: int, norm_eps: float):
    """(z [T, 2n + n^2], u [T, C], x): A, the streams handed on."""
    z, _, u = _maps_call(x, phi_t, ab, n, norm_eps)
    return z, u, x


def _maps_combine_fwd(x, phi_t, ab, n, norm_eps):
    z, r, u = _maps_call(x, phi_t, ab, n, norm_eps)
    return (z, u, x), (x, phi_t, ab, z, r)


def _maps_combine_bwd(n, norm_eps, saved, cts):
    x, phi_t, ab, z, r = saved
    dz, du, dx_b = cts
    T, nC = x.shape
    C, M = nC // n, z.shape[1]
    tb = _block(T, 3, nC)
    with jax.named_scope("mhc"):
        dx, dphi_t, dlog = _run(
            "mhc_maps_bwd", functools.partial(_maps_bwd_kernel, n=n, C=C),
            (T // tb,),
            [_rows(tb, nC), _whole(phi_t.shape), _whole(ab.shape),
             _rows(tb, M), _rows(tb, 1), _rows(tb, M), _rows(tb, C),
             _rows(tb, nC)],
            [_rows(tb, nC), _whole(phi_t.shape), _rows(tb, M)],
            [jax.ShapeDtypeStruct((T, nC), F32),
             jax.ShapeDtypeStruct(phi_t.shape, F32),
             jax.ShapeDtypeStruct((T, M), F32)], "arbitrary",
            x, phi_t, ab, z, r, dz, du, dx_b, aliases={7: 0})
        d_ab = jnp.stack([(dlog * z).sum(0), dlog.sum(0)])
    return dx, dphi_t, d_ab


_maps_combine.defvjp(_maps_combine_fwd, _maps_combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _mix_post_add(x, f, mix, n: int):
    """B: [T, n C] from the streams, f [T, C] and mix [T, n^2 + n]."""
    T, nC = x.shape
    tb = _block(T, 2, nC)
    return _run("mhc_mix", functools.partial(_mix_kernel, n=n, C=nC // n),
                (T // tb,),
                [_rows(tb, nC), _rows(tb, nC // n), _rows(tb, mix.shape[1])],
                _rows(tb, nC), jax.ShapeDtypeStruct((T, nC), F32),
                "parallel", x, f, mix)


def _mix_post_add_fwd(x, f, mix, n):
    return _mix_post_add(x, f, mix, n), (x, f, mix)


def _mix_post_add_bwd(n, saved, g):
    x, f, mix = saved
    T, nC = x.shape
    C, m = nC // n, mix.shape[1]
    tb = _block(T, 3, nC)
    with jax.named_scope("mhc"):
        return tuple(_run(
            "mhc_mix_bwd", functools.partial(_mix_bwd_kernel, n=n, C=C),
            (T // tb,),
            [_rows(tb, nC), _rows(tb, nC), _rows(tb, C), _rows(tb, m)],
            [_rows(tb, nC), _rows(tb, C), _rows(tb, m)],
            [jax.ShapeDtypeStruct((T, nC), F32),
             jax.ShapeDtypeStruct((T, C), f.dtype),
             jax.ShapeDtypeStruct((T, m), F32)], "parallel", g, x, f, mix))


_mix_post_add.defvjp(_mix_post_add_fwd, _mix_post_add_bwd)


def hyper_connection(x: jnp.ndarray, phi: jnp.ndarray, alpha: jnp.ndarray,
                     bias: jnp.ndarray, sublayer: Callable, *, n: int,
                     iters: int, eps: float, clamp: Tuple[float, float],
                     norm_eps: float):
    """(M(x; sublayer) [..., n C] float32, what the sublayer hands back
    beside its output): x [..., n C] the streams, stream j the band
    [j C, (j + 1) C); ``sublayer`` maps the combination u [..., C]
    (float32) to (f [..., C], aux). The kernels where ``fused_block``
    takes the shape, the XLA form otherwise."""
    lead, nC = x.shape[:-1], x.shape[-1]
    C = nC // n
    T = math.prod(lead)
    hc = dict(iters=iters, eps=eps, clamp=clamp, norm_eps=norm_eps)
    if not fused_block(T, n, C):
        xs = x.reshape(lead + (n, C))
        h_pre, h_post, h_res = stream_maps(xs, phi, alpha, bias, **hc)
        f, aux = sublayer(pre_combine(xs, h_pre))
        out = res_mix_post_add(xs, h_res, h_post, f)
        return out.reshape(lead + (nC,)), aux
    with jax.named_scope("mhc"):
        alpha, bias = alpha.astype(F32), bias.astype(F32)
        first = jnp.arange(bias.shape[0]) < n
        ab = jnp.stack([jnp.where(first, alpha[0], 0.0),
                        jnp.where(first, bias, 0.0)])
        z, u, xs = _maps_combine(x.reshape(T, nC).astype(F32),
                                 phi.astype(F32).T, ab, n, norm_eps)
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n]
                                    + bias[n:2 * n])
        res = (alpha[2] * z[:, 2 * n:] + bias[2 * n:]).reshape(T, n, n)
        u = u.reshape(lead + (C,))
    with jax.named_scope("mhc_sinkhorn"):
        res = sinkhorn(res, iters, eps, clamp)
    f, aux = sublayer(u)
    with jax.named_scope("mhc"):
        mix = jnp.concatenate([res.reshape(T, n * n), post], axis=1)
        out = _mix_post_add(xs, f.reshape(T, C), mix, n)
    return out.reshape(lead + (nC,)), aux
