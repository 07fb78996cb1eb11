"""A routed expert layer that is told which experts it holds.

The router scores every token over ALL the model's experts and picks
top_k of them; this chip computes the part of the result that the experts
it holds give (experts [expert_offset, expert_offset + held) of the
router's outputs) and leaves the rest out: what the absent experts would
have added is another chip's part, and nothing here stands in for it.

No token is dropped under any routing. The (token, choice) pairs are
sorted by expert, the pairs of held experts first; a chunk of the sorted
pairs gathers its tokens' rows, runs one grouped matrix product a
projection over them (the library's megablox kernel, whose grid follows
the pairs that are there, not the buffer) and adds the weighted rows back
to their tokens. An expert is a SwiGLU, (silu(x Wgate) * (x Wup)) Wdown,
or, handed no gate, the plain two-matrix relu(x Wup)^2 Wdown. Chunks hold
twice the pairs an even routing sends here; a chunk past the last held
pair is skipped (lax.cond), so the device time goes with the pairs routed
to held experts: not with held x tokens, and not with all top_k x tokens
pairs, though every one of them is served if the router sends them all
here. The chunks (4 where 16 of 128 experts are held, 16 where 16 of 512)
run as one loop whose backward pass is a loop of its own (_looped says why).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# rows, contraction and output tile of the grouped product on the chip
TILING = (256, 1024, 1024)


def route(x: jnp.ndarray, router_w: jnp.ndarray, router_b: jnp.ndarray,
          top_k: int, route_scale: float
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(experts [T, top_k] int32, weights [T, top_k] float32) of tokens x
    [T, D]: scores s = sigmoid(x Wr) in float32; the choice is the top_k
    of s + b (b is read by the choice alone and gets no gradient); the
    weight of a chosen expert is route_scale * s_e / (sum of s over the
    top_k chosen), held here or not."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router_w.astype(F32),
                               precision=_HI))
    _, experts = jax.lax.top_k(
        jax.lax.stop_gradient(s + router_b.astype(F32)), top_k)
    chosen = jnp.take_along_axis(s, experts, axis=1)
    weights = route_scale * chosen / chosen.sum(axis=1, keepdims=True)
    return experts.astype(jnp.int32), weights


def _tile(size: int, most: int) -> int:
    """The widest tile of whole lanes that is no wider than ``most`` and
    cuts ``size`` into whole tiles (2,688 columns: 896, not 1,024 with a
    third tile mostly padding); ``most`` where there is none."""
    most = min(most, size)
    whole = [t for t in range(128, most + 1, 128) if size % t == 0]
    return whole[-1] if whole else most


def _tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    return (min(TILING[0], m), _tile(k, TILING[1]), _tile(n, TILING[2]))


def _grouped(lhs, rhs, sizes, out_dtype):
    """lhs [m, k] rows sorted by group, rhs [groups, k, n], sizes
    [groups]: rows of group g times rhs[g]. Rows past the groups' total
    are not computed and come back unwritten."""
    tiling = _tiling(lhs.shape[0], rhs.shape[1], rhs.shape[2])

    def run(interpret: bool):
        return lambda lhs, rhs, sizes: _megablox.gmm(
            lhs, rhs, sizes, out_dtype, tiling, None, None, False,
            interpret)
    return jax.lax.platform_dependent(lhs, rhs, sizes, tpu=run(False),
                                      default=run(True))


def chunk_rows(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """Pairs a chunk holds: twice what an even routing sends to ``held``
    of ``num_experts`` experts, in whole row tiles, at most all pairs."""
    pairs = tokens * top_k
    even = -(-pairs * held // num_experts)
    tile = min(TILING[0], pairs)
    return min(-(-2 * even // tile) * tile, -(-pairs // tile) * tile)


def _chunk(C, top_k, lo, acc, index, x, flat_w, w_gate, w_up, w_down):
    """acc + what the sorted pairs [lo, lo + C) give: their tokens' rows
    gathered, the held experts' grouped products over them, the weighted
    rows added back to their tokens. index = (order, starts, ends) of the
    sorted pairs (routed_experts)."""
    order, starts, ends = index
    with jax.named_scope("moe_route"):
        at = jax.lax.dynamic_slice(order, (lo,), (C,))
        live = (lo + jnp.arange(C, dtype=jnp.int32) < ends[-1])[:, None]
        here = jnp.clip(ends, lo, lo + C) - jnp.clip(starts, lo, lo + C)
        tok = at // top_k
        # a row past the last held pair is never computed: keep what
        # it would carry, forward and backward, at nought
        xs = jnp.where(live, x[tok], 0)
        w = flat_w[at][:, None]
    with jax.named_scope("moe_experts"):
        if w_gate is None:
            u = jnp.where(live, _grouped(xs, w_up, here, x.dtype), 0)
            a = jnp.square(jax.nn.relu(u.astype(F32))).astype(x.dtype)
        else:
            g = jnp.where(live, _grouped(xs, w_gate, here, x.dtype), 0)
            u = jnp.where(live, _grouped(xs, w_up, here, x.dtype), 0)
            a = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(x.dtype)
        y = jnp.where(live, _grouped(a, w_down, here, F32), 0)
    with jax.named_scope("moe_route"):
        return acc.at[tok].add(y * w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _looped(C, top_k, n_chunks, index, x, flat_w, mats):
    """The chunks as ONE loop, a chunk past the last held pair skipped,
    with the backward pass written as a loop too: a live chunk is
    recomputed there and its gradients are added into one accumulator a
    leaf, a skipped chunk passes the accumulators on. (Autodiff of a
    lax.cond a chunk makes every chunk, skipped or not, hand back a copy
    of the held experts' matrices as what it kept and a gradient of their
    size, all of them held until they are added up: 0.35 + 0.18 GB a
    chunk at 16 experts of 1,024 x 2,688, 7.0 GB of temporaries a layer
    at 16 chunks, compiled for a described v5e, PR 41.) mats = (w_gate or
    None, w_up, w_down)."""
    def step(i, acc):
        return jax.lax.cond(
            i * C < index[2][-1],
            lambda acc: _chunk(C, top_k, i * C, acc, index, x, flat_w, *mats),
            lambda acc: acc, acc)
    return jax.lax.fori_loop(
        0, n_chunks, step, jnp.zeros((x.shape[0], mats[2].shape[-1]), F32))


def _looped_fwd(C, top_k, n_chunks, index, x, flat_w, mats):
    return (_looped(C, top_k, n_chunks, index, x, flat_w, mats),
            (index, x, flat_w, mats))


def _looped_bwd(C, top_k, n_chunks, kept, g):
    index, x, flat_w, mats = kept

    def grads(i):
        # acc enters a chunk's result as itself: its value is not read
        _, vjp = jax.vjp(lambda x, flat_w, mats: _chunk(
            C, top_k, i * C, g, index, x, flat_w, *mats), x, flat_w, mats)
        return vjp(g)

    def step(i, sums):
        return jax.lax.cond(
            i * C < index[2][-1],
            lambda sums: jax.tree.map(jnp.add, sums, grads(i)),
            lambda sums: sums, sums)
    # summed in the leaves' own dtype, as autodiff would sum chunks written
    # out (an even routing has one live chunk and nothing to sum).
    # The first chunk's gradients start the sums: with no held pair its
    # rows are all masked and they are nought
    return (None,) + jax.lax.fori_loop(1, n_chunks, step, grads(0))


_looped.defvjp(_looped_fwd, _looped_bwd)


def routed_experts(x: jnp.ndarray, experts: jnp.ndarray,
                   weights: jnp.ndarray, w_gate: Optional[jnp.ndarray],
                   w_up: jnp.ndarray, w_down: jnp.ndarray,
                   expert_offset: int, num_experts: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """sum over the chosen experts e HELD here of weight_e * E_e(x), E a
    SwiGLU, (silu(x Wgate_e) * (x Wup_e)) Wdown_e, or with ``w_gate``
    None the ungated relu(x Wup_e)^2 Wdown_e.

    x [T, D] in the compute dtype; experts, weights [T, top_k] from
    route(); w_gate, w_up [held, D, F], w_down [held, F, D] in the compute
    dtype: expert g of them is the router's output expert_offset + g.
    Returns (y [T, D] float32, the pairs routed to each held expert
    [held] int32: their sum is the rows the grouped products ran over,
    their largest the fullest expert's)."""
    T = x.shape[0]
    top_k = experts.shape[1]
    held = w_up.shape[0]
    pairs = T * top_k
    with jax.named_scope("moe_route"):
        local = experts.reshape(-1) - expert_offset
        key = jnp.where((local >= 0) & (local < held), local, held)
        key, order = jax.lax.sort(
            (key, jnp.arange(pairs, dtype=jnp.int32)), num_keys=1)
        sizes = (key[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
                 ).sum(axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        C = chunk_rows(T, top_k, held, num_experts)
        n_chunks = -(-pairs // C)
        order = jnp.pad(order, (0, n_chunks * C - pairs))
        flat_w = weights.reshape(-1)
    index = (order, starts, ends)
    return _looped(C, top_k, n_chunks, index, x, flat_w,
                   (w_gate, w_up, w_down)), sizes
