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
to their tokens. Chunks hold twice the pairs an even routing sends here;
a chunk past the last held pair is skipped (lax.cond), so the device time
goes with the pairs routed to held experts: not with held x tokens, and
not with all top_k x tokens pairs, though every one of them is served if
the router sends them all here.
"""

from __future__ import annotations

from typing import Tuple

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


def _tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    return (min(TILING[0], m), min(TILING[1], k), min(TILING[2], n))


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


def routed_experts(x: jnp.ndarray, experts: jnp.ndarray,
                   weights: jnp.ndarray, w_gate: jnp.ndarray,
                   w_up: jnp.ndarray, w_down: jnp.ndarray,
                   expert_offset: int, num_experts: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """sum over the chosen experts e HELD here of weight_e * E_e(x), E a
    SwiGLU: (silu(x Wgate_e) * (x Wup_e)) Wdown_e.

    x [T, D] in the compute dtype; experts, weights [T, top_k] from
    route(); w_gate, w_up [held, D, F], w_down [held, F, D] in the compute
    dtype: expert g of them is the router's output expert_offset + g.
    Returns (y [T, D] float32, pairs routed to held experts, int32)."""
    T, D = x.shape
    top_k = experts.shape[1]
    held = w_gate.shape[0]
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
        count = ends[-1]
        C = chunk_rows(T, top_k, held, num_experts)
        n_chunks = -(-pairs // C)
        order = jnp.pad(order, (0, n_chunks * C - pairs))
        flat_w = weights.reshape(-1)

    @jax.checkpoint
    def chunk(lo, acc, x, flat_w, w_gate, w_up, w_down):
        # under its own checkpoint: a chunk that the backward pass reaches
        # is recomputed there, so the rows of one chunk are alive at a
        # time and a skipped chunk holds nothing
        with jax.named_scope("moe_route"):
            at = jax.lax.dynamic_slice(order, (lo,), (C,))
            live = (lo + jnp.arange(C, dtype=jnp.int32) < count)[:, None]
            here = jnp.clip(ends, lo, lo + C) - jnp.clip(starts, lo, lo + C)
            tok = at // top_k
            # a row past the last held pair is never computed: keep what
            # it would carry, forward and backward, at nought
            xs = jnp.where(live, x[tok], 0)
            w = flat_w[at][:, None]
        with jax.named_scope("moe_experts"):
            g = jnp.where(live, _grouped(xs, w_gate, here, x.dtype), 0)
            u = jnp.where(live, _grouped(xs, w_up, here, x.dtype), 0)
            a = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(x.dtype)
            y = jnp.where(live, _grouped(a, w_down, here, F32), 0)
        with jax.named_scope("moe_route"):
            return acc.at[tok].add(y * w)

    acc = jnp.zeros((T, D), F32)
    for i in range(n_chunks):
        acc = jax.lax.cond(
            i * C < count,
            lambda acc, i=i: chunk(i * C, acc, x, flat_w, w_gate, w_up,
                                   w_down),
            lambda acc: acc, acc)
    return acc, count
