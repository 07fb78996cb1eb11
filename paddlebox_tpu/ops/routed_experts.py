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
the pairs that are there, not the buffer) and sums the weighted rows into
their tokens. An expert is a SwiGLU, (silu(x Wgate) * (x Wup)) Wdown,
or, handed no gate, the plain two-matrix relu(x Wup)^2 Wdown. Chunks hold
twice the pairs an even routing sends here; a chunk past the last held
pair is skipped (lax.cond), so the device time goes with the pairs routed
to held experts: not with held x tokens, and not with all top_k x tokens
pairs, though every one of them is served if the router sends them all
here. The chunks (4 where 16 of 128 experts are held, 16 where 16 of 512)
run as one loop whose backward pass is a loop of its own (_looped says why).

Nothing here scatters, forward or backward: XLA's scatter on a TPU v5e
costs ~0.1 us an index whatever the bytes (a chunk's 32,768-row weighted
scatter-add 3.2 ms against 0.32 ms for the gather of the same rows). A
chunk's rows are put in token order by a sort of their pair ids (pair
p = token * top_k + choice), and every sum into tokens is one kernel,
_segment_sum: a token block's rows are a run of the sorted rows, summed
by a one-hot product on the matrix unit in float32. It makes the combine
(the weighted rows into their tokens), the gradient of the dispatch x[tok]
(a token's rows' gradients, cast once to x's dtype) and the weights'
gradient, written in their own [T, top_k] layout. route() picks its
chosen scores by a one-hot compare and max, whose transpose is
elementwise.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

F32 = jnp.float32
BF16 = jnp.bfloat16
_HI = jax.lax.Precision.HIGHEST
# rows, contraction and output tile of the grouped product on the chip
TILING = (256, 1024, 1024)
# _segment_sum: tokens a block and sorted rows a group (the matrix unit's
# 128), the widest column tile, and the fast memory it may take
_SEG = 128
_SEG_COLS = 2048
_SEG_VMEM_BYTES = 48 << 20
# _segment_sum's item flags
_FIRST, _LAST, _LIVE = 1, 2, 4


def route(x: jnp.ndarray, router_w: jnp.ndarray, router_b: jnp.ndarray,
          top_k: int, route_scale: float
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(experts [T, top_k] int32, weights [T, top_k] float32) of tokens x
    [T, D]: scores s = sigmoid(x Wr) in float32; the choice is the top_k
    of s + b (b is read by the choice alone and gets no gradient); the
    weight of a chosen expert is route_scale * s_e / (sum of s over the
    top_k chosen), held here or not. The chosen scores are a one-hot
    compare and max over the E outputs: one term is not nought (s > 0),
    so they are s's own bits however the compiler orders the reduction,
    and their transpose is elementwise."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router_w.astype(F32),
                               precision=_HI))
    _, experts = jax.lax.top_k(
        jax.lax.stop_gradient(s + router_b.astype(F32)), top_k)
    hit = experts[:, :, None] == jnp.arange(s.shape[1], dtype=jnp.int32)
    chosen = jnp.where(hit, s[:, None, :], 0.0).max(axis=2)
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


# ------------------------------------------------- sums into tokens
def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _seg_shape(rows: int, tokens: int) -> Tuple[int, int, int, int]:
    """(token block, row group, padded tokens, padded rows)."""
    tb = min(_SEG, _round_up(tokens, 8))
    g = min(_SEG, _round_up(rows, 8))
    return tb, g, _round_up(tokens, tb), _round_up(rows, g)


def _padded(tok_s: jnp.ndarray, tokens: int) -> jnp.ndarray:
    """tok_s in whole row groups, the padding past every token block."""
    _, _, t_pad, c_pad = _seg_shape(tok_s.shape[0], tokens)
    return jnp.pad(tok_s, (0, c_pad - tok_s.shape[0]),
                   constant_values=t_pad)


def _items(tok_s: jnp.ndarray, tokens: int
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The grid of _segment_sum over rows whose tokens tok_s [rows] are
    sorted (a token past the last, ``tokens`` or more, marks a row that
    adds nothing): (block [n], group [n], flags [n], groups fetched) of
    its n = groups + blocks items. Item i sums row group group[i] into
    token block block[i]; a block's rows are the run of tok_s that falls
    in it, so its items are the groups that run touches, at least one, in
    order, and the blocks' items follow one another. Items past the last
    repeat it with no flag, so they fetch and do nothing."""
    tok_s = _padded(tok_s, tokens)
    tb, g, t_pad, c_pad = _seg_shape(tok_s.shape[0], tokens)
    nb, ng = t_pad // tb, c_pad // g
    n = ng + nb
    bounds = jnp.arange(nb + 1, dtype=jnp.int32) * tb
    # the first sorted row of each block, and the end of the last
    lo = (tok_s[None, :] < bounds[:, None]).sum(axis=1, dtype=jnp.int32)
    g_lo = jnp.minimum(lo[:-1] // g, ng - 1)
    g_hi = jnp.maximum(jnp.minimum((lo[1:] - 1) // g, ng - 1), g_lo)
    count = g_hi - g_lo + 1
    end = jnp.cumsum(count)
    start = end - count
    i = jnp.arange(n, dtype=jnp.int32)
    blk = jnp.minimum((end[None, :] <= i[:, None]).sum(axis=1,
                                                      dtype=jnp.int32),
                      nb - 1)
    past = i >= end[-1]
    grp = jnp.where(past, g_hi[-1], g_lo[blk] + i - start[blk])
    flags = jnp.where(past, 0, _LIVE
                      + _FIRST * (i == start[blk]).astype(jnp.int32)
                      + _LAST * (i == end[blk] - 1).astype(jnp.int32))
    fetched = 1 + (grp[1:] != grp[:-1]).sum(dtype=jnp.int32)
    return blk, grp, flags, fetched * g


def _parts(rows):
    """rows as the bfloat16 parts the matrix unit takes: float32 rows as
    three, hi + mid + lo = the row to the bit, so that a product with 0
    or 1 is exact in each."""
    if rows.dtype != F32:
        return (rows.astype(BF16),)
    hi = rows.astype(BF16)
    rest = rows - hi.astype(F32)
    mid = rest.astype(BF16)
    return hi, mid, (rest - mid.astype(F32)).astype(BF16)


def _segment_kernel(blk_ref, grp_ref, flag_ref, lo_ref, hi_ref, tok_ref,
                    rank_ref, rows_ref, *refs):
    acc_ref = refs[0] if len(refs) == 3 else None
    out_ref, sums = refs[-2], refs[-1]
    i = pl.program_id(1)
    flag = flag_ref[i]

    @pl.when((flag & _FIRST) != 0)
    def _():
        sums[...] = (jnp.zeros(sums.shape, F32) if acc_ref is None
                     else acc_ref[...].astype(F32))

    @pl.when((flag & _LIVE) != 0)
    def _():
        tb, g = sums.shape[0], rows_ref.shape[0]
        token = (blk_ref[i] * tb
                 + jax.lax.broadcasted_iota(jnp.int32, (tb, g), 0))
        match = token == tok_ref[0]
        parts = _parts(rows_ref[...])

        def add(rank, carry):
            # the rows of one rank: a token's one row at most, so the
            # parts' products and their sum are exact
            sel = (match & (rank_ref[0] == rank)).astype(BF16)
            got = [jax.lax.dot(sel, p, preferred_element_type=F32)
                   for p in parts]
            sums[...] += functools.reduce(jnp.add, got)
            return carry
        jax.lax.fori_loop(lo_ref[i], hi_ref[i] + 1, add, 0)

    @pl.when((flag & _LAST) != 0)
    def _():
        out_ref[...] = sums[...].astype(out_ref.dtype)


def _segment_sum(rows: jnp.ndarray, tok_s: jnp.ndarray, tokens: int,
                 acc: Optional[jnp.ndarray] = None, out_dtype=F32,
                 in_order: bool = False) -> jnp.ndarray:
    """out [tokens, w]: out[t] = acc[t] + the sum of the rows [rows, w]
    whose tok_s is t, in float32, cast once to ``out_dtype`` (acc's dtype
    where it is given). tok_s is sorted; a row whose token is ``tokens``
    or more adds nothing, and must be finite. One Pallas kernel over
    _items' grid, each column tile a pass of it: a token block's sums
    stay in fast memory while its row groups pass, so every row is read
    once (or twice, at a group two blocks share) and every token written
    once. A group's rows of the block go to the matrix unit as one
    one-hot product (none there: none); ``in_order``: as one a rank (a
    row's place among its token's, those the rows hold), so a
    token's rows are added one at a time in their order, as a loop
    would add them, and its sum is that loop's to the bit (the float32
    parts' products are exact). Compiled by Mosaic where the program is
    lowered for a TPU, interpreted anywhere else."""
    c, w = rows.shape
    tb, g, t_pad, c_pad = _seg_shape(c, tokens)
    rows = jnp.pad(rows, ((0, c_pad - c), (0, 0)))
    blk, grp, flags, _ = _items(tok_s, tokens)
    tok_s = _padded(tok_s, tokens)
    if in_order:
        at = jnp.arange(c_pad, dtype=jnp.int32)
        opens = jnp.concatenate([jnp.ones((1,), bool),
                                 tok_s[1:] != tok_s[:-1]])
        rank = jnp.where(tok_s < tokens,
                         at - jax.lax.cummax(jnp.where(opens, at, 0)), 0)
    else:
        rank = jnp.zeros((c_pad,), jnp.int32)
    ranks = rank.reshape(c_pad // g, g)
    # the ranks an item's rows of its own block hold (none: no product)
    mine = tok_s.reshape(c_pad // g, g)[grp] // tb == blk[:, None]
    lo = jnp.where(mine, ranks[grp], c_pad).min(axis=1)
    hi = jnp.where(mine, ranks[grp], -1).max(axis=1)
    tw = _tile(w, _SEG_COLS) if w % 128 == 0 else w
    lane = pl.BlockSpec((None, 1, g),
                        lambda d, i, b, r, f, lo, hi: (r[i], 0, 0))
    in_specs = [lane, lane,
                pl.BlockSpec((g, tw), lambda d, i, b, r, f, lo, hi: (r[i], d))]
    block = pl.BlockSpec((tb, tw), lambda d, i, b, r, f, lo, hi: (b[i], d))
    args = [blk, grp, flags, lo, hi, tok_s.reshape(c_pad // g, 1, g),
            ranks.reshape(c_pad // g, 1, g), rows]
    aliases = {}
    if acc is not None:
        out_dtype = acc.dtype
        if t_pad != tokens:
            acc = jnp.pad(acc, ((0, t_pad - tokens), (0, 0)))
        in_specs.append(block)
        args.append(acc)
        aliases = {len(args) - 1: 0}
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(w // tw, blk.shape[0]),
        in_specs=in_specs, out_specs=block,
        scratch_shapes=[pltpu.VMEM((tb, tw), F32)])

    def call(interpret: bool):
        return pl.pallas_call(
            _segment_kernel, grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct((t_pad, w), out_dtype),
            input_output_aliases=aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_SEG_VMEM_BYTES),
            interpret=interpret, name="moe_segment_sum")
    out = jax.lax.platform_dependent(*args, tpu=call(False),
                                     default=call(True))
    return out[:tokens] if t_pad != tokens else out


def _token_order(at: jnp.ndarray, live: jnp.ndarray, top_k: int,
                 tokens: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                       jnp.ndarray]:
    """(tok_s, k_s, perm): a chunk's rows in token order, by a sort of
    their pair ids at (token * top_k + choice). perm [C] takes the sorted
    rows from the chunk's; tok_s and k_s are the sorted rows' token and
    choice. A row past the last held pair sorts last, its token
    ``tokens``: it adds to no token."""
    key = jnp.where(live, at, tokens * top_k)
    key, perm = jax.lax.sort(
        (key, jnp.arange(at.shape[0], dtype=jnp.int32)), num_keys=1)
    return key // top_k, key % top_k, perm


@jax.custom_vjp
def _dispatch(x, tok, live, order):
    """xs [C, D] = x[tok], nought on a row past the last held pair. Its
    gradient is _segment_sum of the rows' gradients in token order
    (order = _token_order's), summed in float32 and cast once to x's
    dtype: no scatter-add."""
    return jnp.where(live[:, None], x[tok], 0)


def _dispatch_fwd(x, tok, live, order):
    # a [T, 0] array carries x's row count and dtype to the backward pass
    return _dispatch(x, tok, live, order), (x[:, :0], order)


def _dispatch_bwd(kept, dxs):
    like, (tok_s, _, perm) = kept
    tokens = like.shape[0]
    with jax.named_scope("moe_route"):
        rows = jnp.where((tok_s < tokens)[:, None], dxs[perm], 0)
        dx = _segment_sum(rows, tok_s, tokens, out_dtype=like.dtype)
    return dx, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(top_k, acc, y, weights, at, live, order):
    """acc (None: nought) + the rows y [C, D] (float32) weighted by their
    pairs' weights (weights [T, top_k]; row r is pair at[r]) and summed
    into their tokens: _segment_sum of (y w) in token order (order =
    _token_order's). Backward: dy[r] = w[r] g[tok r] (a gather), and
    dweights[t, k] = <g[t], y[row of pair (t, k)]>, summed into
    weights' own layout by _segment_sum of rows that carry <g[tok r],
    y[r]> at column k: no scatter-add."""
    return _combine_fwd(top_k, acc, y, weights, at, live, order)[0]


def _combine_fwd(top_k, acc, y, weights, at, live, order):
    tok_s, _, perm = order
    tokens = weights.shape[0]
    w = jnp.where(live, weights.reshape(-1)[at], 0.0)
    rows = jnp.where((tok_s < tokens)[:, None], (y * w[:, None])[perm], 0.0)
    out = _segment_sum(rows, tok_s, tokens, acc, in_order=True)
    # a [0, D] slice of acc tells the backward pass that acc was given
    return out, (y, w, at, live, order, None if acc is None else acc[:0])


def _combine_bwd(top_k, kept, g):
    y, w, at, live, (tok_s, k_s, perm), given = kept
    tokens = g.shape[0]
    with jax.named_scope("moe_route"):
        gt = g[at // top_k]
        dy = jnp.where(live[:, None], gt * w[:, None], 0.0)
        dot = jnp.where(live, (gt * y).sum(axis=1), 0.0)
        on = ((k_s[:, None] == jnp.arange(top_k, dtype=jnp.int32))
              & (tok_s < tokens)[:, None])
        dw = _segment_sum(jnp.where(on, dot[perm][:, None], 0.0), tok_s,
                          tokens)
    return None if given is None else g, dy, dw, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _chunk(C, top_k, lo, acc, index, x, weights, w_gate, w_up, w_down):
    """(acc + what the sorted pairs [lo, lo + C) give, the rows the
    combine fetched): their tokens' rows gathered, the held experts'
    grouped products over them, the weighted rows summed into their
    tokens (acc None: from nought). index = (order, starts, ends) of the
    sorted pairs (routed_experts)."""
    order, starts, ends = index
    tokens = x.shape[0]
    with jax.named_scope("moe_route"):
        at = jax.lax.dynamic_slice(order, (lo,), (C,))
        live = lo + jnp.arange(C, dtype=jnp.int32) < ends[-1]
        here = jnp.clip(ends, lo, lo + C) - jnp.clip(starts, lo, lo + C)
        by_token = _token_order(at, live, top_k, tokens)
        # a row past the last held pair is never computed: keep what
        # it would carry, forward and backward, at nought
        xs = _dispatch(x, at // top_k, live, by_token)
    live = live[:, None]
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
        out = _combine(top_k, acc, y, weights, at, live[:, 0], by_token)
        return out, _items(by_token[0], tokens)[3]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _looped(C, top_k, n_chunks, index, x, weights, mats):
    """(the chunks' sum, the rows their combines fetched) as ONE loop, a
    chunk past the last held pair skipped, with the backward pass written
    as a loop too: a live chunk is recomputed there and its gradients are
    added into one accumulator a leaf, a skipped chunk passes the
    accumulators on. (Autodiff of a lax.cond a chunk makes every chunk,
    skipped or not, hand back a copy of the held experts' matrices as what
    it kept and a gradient of their size, all of them held until they are
    added up: 0.35 + 0.18 GB a chunk at 16 experts of 1,024 x 2,688, 7.0
    GB of temporaries a layer at 16 chunks, compiled for a described
    v5e.) mats = (w_gate or None, w_up, w_down)."""
    def step(i, carry):
        def live(carry):
            out, fetched = _chunk(C, top_k, i * C, carry[0], index, x,
                                  weights, *mats)
            return out, carry[1] + fetched
        return jax.lax.cond(i * C < index[2][-1], live, lambda c: c, carry)
    # the first chunk starts the sum from nought (with no held pair its
    # rows are all masked)
    return jax.lax.fori_loop(1, n_chunks, step, _chunk(
        C, top_k, 0, None, index, x, weights, *mats))


def _looped_fwd(C, top_k, n_chunks, index, x, weights, mats):
    return (_looped(C, top_k, n_chunks, index, x, weights, mats),
            (index, x, weights, mats))


def _looped_bwd(C, top_k, n_chunks, kept, g):
    index, x, weights, mats = kept
    g = g[0]

    def grads(i):
        _, vjp = jax.vjp(lambda x, weights, mats: _chunk(
            C, top_k, i * C, None, index, x, weights, *mats)[0],
            x, weights, mats)
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
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """sum over the chosen experts e HELD here of weight_e * E_e(x), E a
    SwiGLU, (silu(x Wgate_e) * (x Wup_e)) Wdown_e, or with ``w_gate``
    None the ungated relu(x Wup_e)^2 Wdown_e.

    x [T, D] in the compute dtype; experts, weights [T, top_k] from
    route(); w_gate, w_up [held, D, F], w_down [held, F, D] in the compute
    dtype: expert g of them is the router's output expert_offset + g.
    Returns (y [T, D] float32, the pairs routed to each held expert
    [held] int32: their sum is the rows the grouped products ran over,
    their largest the fullest expert's, the rows the forward combine
    fetched int32: the pairs held and what its row groups read beyond
    them)."""
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
    index = (order, starts, ends)
    y, fetched = _looped(C, top_k, n_chunks, index, x, weights,
                         (w_gate, w_up, w_down))
    return y, sizes, fetched
