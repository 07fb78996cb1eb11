"""ops/routed_experts.py's sums into tokens without a scatter: the
segment-sum kernel, the dispatch and the combine with their gradients
against the scatter-add forms, route()'s chosen scores against
take_along_axis, and the gradient of a checkpointed routed layer holding
no scatter outside megablox's own grouped products."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from paddlebox_tpu.ops import routed_experts as module

F32 = jnp.float32


@pytest.fixture
def small(monkeypatch):
    """Token blocks and row groups of 8, so that a few dozen rows make
    several of each; megablox's tiles to the tiny widths."""
    monkeypatch.setattr(module, "_SEG", 8)
    monkeypatch.setattr(module, "TILING", (32, 64, 32))


def sorted_tokens(rng, tokens, rows, most):
    """tok_s [rows] sorted: each token holds 0 to ``most`` rows, the
    rest of the rows past the last token (they add nothing)."""
    counts = rng.integers(0, most + 1, tokens)
    tok = np.repeat(np.arange(tokens), counts)[:rows]
    return np.concatenate([tok, np.full(rows - tok.size, tokens)]).astype(
        np.int32)


# ------------------------------------------------------- the kernel alone
@pytest.mark.parametrize("tokens, rows, width, most", [
    (40, 64, 24, 3), (37, 50, 16, 4), (24, 96, 8, 8), (16, 32, 8, 0)],
    ids=["blocks-and-groups", "padded", "a-token-spans-groups", "none-held"])
@pytest.mark.parametrize("in_order", [False, True],
                         ids=["one-product", "rank-by-rank"])
def test_segment_sum_matches_a_loop(small, tokens, rows, width, most,
                                    in_order):
    """acc + each row added to its token, rows past the last token adding
    nothing. Rank by rank the sum is the loop's to the bit (a row added
    at a time, in order); as one product, to float32's rounding."""
    rng = np.random.default_rng(tokens + rows)
    tok = sorted_tokens(rng, tokens, rows, most)
    vals = rng.standard_normal((rows, width)).astype(np.float32)
    vals *= np.exp(2 * rng.standard_normal((rows, 1))).astype(np.float32)
    vals[tok >= tokens] = 0
    acc = rng.standard_normal((tokens, width)).astype(np.float32)
    want = acc.copy()
    for r, t in zip(vals, tok):
        if t < tokens:
            want[t] = want[t] + r
    got = np.asarray(module._segment_sum(jnp.asarray(vals), jnp.asarray(tok),
                                         tokens, jnp.asarray(acc),
                                         in_order=in_order))
    if in_order:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())
    fetched = int(module._items(jnp.asarray(tok), tokens)[3])
    assert (tok < tokens).sum() <= fetched <= rows + 8 * (tokens // 8 + 1)


def test_segment_sum_casts_once(small):
    """bfloat16 rows summed in float32 and cast once: the sum of three
    rows that bfloat16 would round after each add."""
    tok = jnp.asarray([0, 0, 0, 1], jnp.int32)
    vals = jnp.asarray([[1.0], [2.0 ** -8], [2.0 ** -8], [3.0]], jnp.bfloat16)
    got = module._segment_sum(vals, tok, 2, out_dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    # one add at a time in bfloat16 leaves 1.0; float32 keeps 1 + 2^-7
    np.testing.assert_array_equal(np.asarray(got, np.float32)[:, 0],
                                  [1.0 + 2.0 ** -7, 3.0])


# ------------------------------------------- dispatch and combine, VJPs
def chunk_case(seed, tokens, top_k, held_share, C):
    """(at, live, x, y, weights) of one chunk of sorted pairs: each pair
    held with probability held_share, the held ones first, as
    routed_experts sorts them."""
    rng = np.random.default_rng(seed)
    pairs = tokens * top_k
    held = rng.random(pairs) < held_share
    order = np.concatenate([np.flatnonzero(held), np.flatnonzero(~held)])
    order = np.pad(order, (0, max(0, C - pairs)))[:C].astype(np.int32)
    live = np.arange(C) < held.sum()
    x = rng.standard_normal((tokens, 16)).astype(np.float32)
    y = rng.standard_normal((C, 16)).astype(np.float32) * live[:, None]
    w = rng.uniform(0.1, 0.5, (tokens, top_k)).astype(np.float32)
    return (jnp.asarray(order), jnp.asarray(live), jnp.asarray(x),
            jnp.asarray(y), jnp.asarray(w))


def scatter_forms(top_k):
    def dispatch(x, at, live):
        return jnp.where(live[:, None], x[at // top_k], 0)

    def combine(acc, y, w, at, live):
        rw = jnp.where(live, w.reshape(-1)[at], 0.0)[:, None]
        return acc.at[at // top_k].add(y * rw)
    return dispatch, combine


@pytest.mark.parametrize("share, C", [(0.3, 48), (1.0, 48), (0.6, 72),
                                      (0.0, 48)],
                         ids=["some-held", "all-held-one-chunk",
                              "most-held", "none-held"])
def test_dispatch_and_combine_match_the_scatter_forms(small, share, C):
    """Values and the gradients of x, acc, y and the weights, float32:
    the kernel's sums against XLA's scatter-adds, tokens holding from 0
    to top_k of the chunk's rows."""
    tokens, top_k = 24, 3
    at, live, x, y, w = chunk_case(int(share * 10) + C, tokens, top_k, share,
                                   C)
    order = module._token_order(at, live, top_k, tokens)
    dispatch, combine = scatter_forms(top_k)
    g = jax.random.normal(jax.random.PRNGKey(1), (C, 16))
    got, back = jax.vjp(lambda x: module._dispatch(x, at // top_k, live,
                                                   order), x)
    want, back_want = jax.vjp(lambda x: dispatch(x, at, live), x)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(back(g)[0], back_want(g)[0], rtol=1e-6,
                               atol=1e-6)
    acc = jax.random.normal(jax.random.PRNGKey(2), (tokens, 16))
    h = jax.random.normal(jax.random.PRNGKey(3), (tokens, 16))
    got, back = jax.vjp(lambda acc, y, w: module._combine(
        top_k, acc, y, w, at, live, order), acc, y, w)
    want, back_want = jax.vjp(lambda acc, y, w: combine(acc, y, w, at, live),
                              acc, y, w)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for name, a, b in zip(("acc", "y", "weights"), back(h), back_want(h)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6, err_msg=name)
    if not share:
        assert not np.any(np.asarray(back(h)[2]))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
def test_two_live_chunks_and_the_gradient_dtypes(small, dtype):
    """routed_experts with every pair held (two live chunks) against the
    layer written out expert by expert, and the gradients' dtypes: x and
    the matrices in their own, the weights float32."""
    tokens, D, F, top_k, held, E = 48, 16, 8, 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (tokens, D))
    experts = jnp.stack([jnp.full((tokens,), 1), jnp.full((tokens,), 2)],
                        axis=1).astype(jnp.int32)
    w = jax.random.uniform(ks[1], (tokens, top_k), F32, 0.1, 0.5)
    up = jax.random.normal(ks[2], (held, D, F)) / 4.0
    down = jax.random.normal(ks[3], (held, F, D)) / 4.0
    assert module.chunk_rows(tokens, top_k, held, E) < tokens * top_k

    def program(x, w, up, down):
        return module.routed_experts(x, experts, w, None, up, down, 1, E)

    def plain(x, w, up, down):
        return sum(w[:, c:c + 1] * (jnp.square(jax.nn.relu(
            x @ up[c])) @ down[c]) for c in range(top_k))
    args = (x.astype(dtype), w, up.astype(dtype), down.astype(dtype))
    y, sizes, fetched = program(*args)
    np.testing.assert_array_equal(sizes, [tokens, tokens])
    assert int(fetched) >= tokens * top_k
    tol = 1e-5 if dtype == F32 else 3e-2
    want = plain(x, w, up, down)
    assert np.linalg.norm(y - want) <= tol * np.linalg.norm(want)
    got = jax.grad(lambda *a: (program(*a)[0] ** 2).sum(),
                   argnums=(0, 1, 2, 3))(*args)
    assert [a.dtype for a in got] == [dtype, F32, dtype, dtype]
    want = jax.grad(lambda *a: (plain(*a) ** 2).sum(), argnums=(0, 1, 2, 3))(
        x, w, up, down)
    for name, a, b in zip(("x", "weights", "up", "down"), got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 3 * tol * np.linalg.norm(b), name


# ---------------------------------------------------------------- route()
def route_by_take(x, router_w, router_b, top_k, scale):
    s = jax.nn.sigmoid(jnp.dot(x, router_w,
                               precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(s + router_b), top_k)
    chosen = jnp.take_along_axis(s, experts, axis=1)
    return experts, scale * chosen / chosen.sum(axis=1, keepdims=True)


@pytest.mark.parametrize("E, top_k", [(8, 3), (16, 5)])
def test_route_is_take_along_axis_to_the_bit(E, top_k):
    """The one-hot compare and max picks s's own bits: the weights and
    the gradients of x and the router's matrix equal the take_along_axis
    form's exactly."""
    ks = jax.random.split(jax.random.PRNGKey(E), 4)
    x = jax.random.normal(ks[0], (32, 16))
    rw = jax.random.normal(ks[1], (16, E))
    rb = 0.1 * jax.random.normal(ks[2], (E,))
    g = jax.random.normal(ks[3], (32, top_k))
    got = jax.jit(lambda x, rw: module.route(x, rw, rb, top_k, 2.5))(x, rw)
    want = jax.jit(lambda x, rw: route_by_take(x, rw, rb, top_k, 2.5))(x, rw)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])

    def grad(fn):
        return jax.jit(jax.grad(lambda x, rw: (fn(x, rw, rb, top_k, 2.5)[1]
                                               * g).sum(), argnums=(0, 1)))
    for a, b in zip(grad(module.route)(x, rw), grad(route_by_take)(x, rw)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ structure
def scatters(jaxpr, path=()):
    """The scatter primitives of a jaxpr and its sub-jaxprs, by path,
    those inside megablox's gmm / tgmm left out."""
    out = []
    for eqn in jaxpr.eqns:
        name = str(eqn.params.get("name", ""))
        if name in ("gmm", "tgmm"):
            continue
        here = path + (eqn.primitive.name,)
        if "scatter" in eqn.primitive.name:
            out.append("/".join(here))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    out += scatters(sub.jaxpr, here)
                elif isinstance(sub, jcore.Jaxpr):
                    out += scatters(sub, here)
    return out


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
def test_no_scatter_in_a_routed_layers_gradient(small, gated):
    """route() + routed_experts() under jax.checkpoint, differentiated in
    every input: no scatter or scatter-add anywhere but inside megablox's
    grouped products (their tile maps), forward, recompute or
    backward."""
    T, D, F, E, held, top_k = 32, 16, 8, 8, 3, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, D))
    rw = jax.random.normal(ks[1], (D, E))
    mats = [jax.random.normal(k, s) for k, s in (
        (ks[2], (held, D, F)), (ks[3], (held, D, F)), (ks[4], (held, F, D)))]
    if not gated:
        mats[0] = None

    def layer(x, rw, mats):
        def f(x, rw, mats):
            experts, weights = module.route(x, rw, jnp.zeros(E), top_k, 2.0)
            return module.routed_experts(x, experts, weights, *mats, 2, E)[0]
        return (jax.checkpoint(f)(x, rw, mats) ** 2).sum()
    jaxpr = jax.make_jaxpr(jax.grad(layer, argnums=(0, 1, 2)))(x, rw, mats)
    assert scatters(jaxpr.jaxpr) == []
    # the walk sees a scatter where there is one
    bad = jax.make_jaxpr(jax.grad(lambda x: (x[jnp.arange(T) % 5] ** 2).sum(
    )))(x)
    assert scatters(bad.jaxpr)
