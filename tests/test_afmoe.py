"""AfMoE behaviour-sequence tower (models/afmoe.py, ops/attention.py,
ops/routed_experts.py) against the plain float32 reference written from
the layer equations (tests/afmoe_reference.py), at small sizes on the CPU
with seeded weights; the benchmark's copy of the reference for the chip
(benchmarks/configs/trinity-mini.py) against the same."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import afmoe_reference as ref  # noqa: E402

from paddlebox_tpu.models.afmoe import AfMoE  # noqa: E402
from paddlebox_tpu.models.base import ModelSpec  # noqa: E402
from paddlebox_tpu.ops.attention import blocked_attention  # noqa: E402
from paddlebox_tpu.ops.routed_experts import (chunk_rows, route,  # noqa: E402
                                              routed_experts)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# window 8 at 24 positions, so that the window cuts; 8 router outputs of
# which experts 3 and 4 are held; a dense layer (sliding) and a routed one
# (full)
CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, sliding_window=8,
           layer_types=["sliding_attention", "full_attention"],
           num_dense_layers=1, intermediate_size=96,
           moe_intermediate_size=32, num_experts_published=8, num_experts=2,
           expert_offset=3, num_experts_per_tok=2, route_scale=2.826,
           rope_theta=10000.0, rms_norm_eps=1e-5, head_scale=4.0,
           num_sparse_slots=24, embedx_dim=64, dense_dim=0,
           router_bias_std=0.01)
B, S = 4, 24


def build(cfg):
    return AfMoE(
        ModelSpec(num_slots=cfg["num_sparse_slots"],
                  slot_dim=3 + cfg["hidden_size"]),
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"], hidden=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], intermediate=cfg["intermediate_size"],
        moe_intermediate=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts_published"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"], route_scale=cfg["route_scale"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        head_scale=cfg["head_scale"])


def config_module():
    spec = importlib.util.spec_from_file_location(
        "trinity_mini_config",
        os.path.join(ROOT, "benchmarks", "configs", "trinity-mini.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded(cfg, seed=0):
    """Weights as the benchmark draws them: a matrix 1 / sqrt(inputs), a
    norm's weight +-1, the router's bias a small normal."""
    model = build(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(model.shapes()))
    params = {}
    for (name, shape), key in zip(sorted(model.shapes().items()), keys):
        leaf = name.rpartition(".")[2]
        draw = jax.random.normal(key, shape, jnp.float32)
        if "norm" in leaf:
            params[name] = jnp.where(draw < 0, -1.0, 1.0)
        elif leaf == "router_b":
            params[name] = 0.01 * draw
        elif leaf == "b_out":
            params[name] = jnp.zeros(shape)
        else:
            params[name] = draw / np.sqrt(shape[-2] if len(shape) > 1
                                          else shape[0])
    pooled = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                      (B, S, 3 + cfg["hidden_size"]))
    labels = jnp.asarray([1.0, 0.0, 0.0, 1.0])
    return model, params, pooled, labels


def bce(logits, y):
    return (jnp.logaddexp(logits, 0.0) - logits * y).mean()


@pytest.fixture(scope="module")
def tower():
    model, params, pooled, labels = seeded(CFG)
    want = jax.jit(jax.value_and_grad(
        lambda p, x: bce(ref.forward(CFG, p, x), labels), argnums=(0, 1)))(
            params, pooled)
    return model, params, pooled, labels, want


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


# ----------------------------------------------- (a) apply vs the reference
def test_float32_matches_reference_tightly(tower):
    model, params, pooled, labels, (want_loss, (want_gp, want_gx)) = tower
    def loss_fn(p, x):
        counts = {}
        logits = model.apply(p, x, counters=counts)
        return bce(logits, labels), (logits, counts)
    (loss, (logits, counts)), (gp, gx) = jax.jit(jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True))(params, pooled)
    np.testing.assert_allclose(
        logits, jax.jit(lambda p, x: ref.forward(CFG, p, x))(params, pooled),
        rtol=2e-5, atol=2e-6)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert rel(gx, want_gx) < 1e-4
    for name in params:
        if name.endswith("router_b"):       # read by the choice alone
            assert not np.any(gp[name]) and not np.any(want_gp[name])
        else:
            assert rel(gp[name], want_gp[name]) < 1e-4, name
    want_pairs = sum(
        ref.pairs_held(CFG, ref.layer_params(params, i), x)
        for i, x in routed_inputs(params, pooled))
    assert int(counts["moe_pairs_held"]) == want_pairs


def routed_inputs(params, pooled):
    """(layer, N3(a)) of every routed layer, by the reference."""
    h = pooled[..., 3:] * np.sqrt(CFG["hidden_size"])
    for i in range(len(CFG["layer_types"])):
        p = ref.layer_params(params, i)
        if i >= CFG["num_dense_layers"]:
            sliding = CFG["layer_types"][i] == "sliding_attention"
            a = h + ref.norm(ref.attention(
                CFG, p, ref.norm(h, p["norm1"], 1e-5), sliding),
                p["norm2"], 1e-5)
            yield i, ref.norm(a, p["norm3"], 1e-5)
        h = ref.layer(CFG, i, p, h)


def test_bfloat16_within_tolerance(tower):
    """The trainer's mixed precision: matrices and pooled in bfloat16,
    f32_params uncast. bfloat16 keeps 8 bits: a product over 64 to 96
    inputs is good to ~1e-2, and a token whose 2nd and 3rd router scores
    lie closer than that may change expert, so the gradients are held by
    their norm-wise error, not element by element, and a held expert's
    own leaves (a handful of the 96 tokens each) get the wider band."""
    from paddlebox_tpu.train.trainer import apply_mixed_precision
    model, params, pooled, labels, (want_loss, (want_gp, want_gx)) = tower

    def loss_fn(p, x):
        p, x, _ = apply_mixed_precision(p, x, None, jnp.bfloat16,
                                        model.f32_params)
        return bce(model.apply(p, x).astype(jnp.float32), labels)
    loss, (gp, gx) = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(
        params, pooled)
    assert abs(float(loss) - float(want_loss)) < 0.02

    def norm_err(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
    assert norm_err(gx, want_gx) < 0.1
    for name in params:
        if not name.endswith("router_b"):
            band = 0.3 if ".e_" in name else 0.15
            assert norm_err(gp[name], want_gp[name]) < band, name


def test_chip_reference_copy_equals_plain_reference(tower):
    """benchmarks/configs/trinity-mini.py forward(): blocked, every
    product through mm."""
    _model, params, pooled, labels, (want_loss, (want_gp, want_gx)) = tower
    mod = config_module()
    mod.QUERY_BLOCK = 16        # 24 positions: a whole block and a padded one

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    loss, (gp, gx) = jax.jit(jax.value_and_grad(
        lambda p, x: bce(mod.forward(CFG, p, x, None, mm), labels),
        argnums=(0, 1)))(params, pooled)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert rel(gx, want_gx) < 1e-4
    for name in params:
        assert rel(gp[name], want_gp[name]) < 1e-4 or not np.any(
            want_gp[name]), name
    shapes = {k: v[0] for k, v in mod.param_init(CFG).items()}
    assert shapes == build(CFG).shapes()


# ------------------------------------------------ (b) the blocked attention
def naive_attention(q, k, v, window):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    n = q.shape[2]
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    see = (j <= i) & ((i - j < window) if window else True)
    scores = jnp.einsum("bhid,bhjd->bhij", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhij,bhjd->bhid",
                      jax.nn.softmax(jnp.where(see, scores, -jnp.inf), -1), v)


@pytest.mark.parametrize("window", [None, 40], ids=["full", "window"])
def test_blocked_attention_matches_naive(window, monkeypatch):
    """200 positions in blocks of 128: a length that is no multiple of the
    block, 8 query heads over 2 key-value heads; forward and gradient."""
    from paddlebox_tpu.ops import attention
    monkeypatch.setattr(attention, "BLOCK_Q", 128)
    monkeypatch.setattr(attention, "BLOCK_KV", 128)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (2, 8, 200, 16))
    k = jax.random.normal(keys[1], (2, 2, 200, 16))
    v = jax.random.normal(keys[2], (2, 2, 200, 16))
    t = jax.random.normal(keys[3], (2, 8, 200, 16))
    got = jax.jit(lambda q, k, v: blocked_attention(q, k, v, window))(q, k, v)
    np.testing.assert_allclose(got, naive_attention(q, k, v, window),
                               rtol=2e-5, atol=2e-5)
    g = jax.jit(jax.grad(lambda q, k, v: (blocked_attention(
        q, k, v, window) * t).sum(), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda q, k, v: (naive_attention(q, k, v, window)
                                     * t).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def scaled_naive(q, k, v, scale):
    """naive_attention with the scores times ``scale``: its own 1 /
    sqrt(D) is undone on q."""
    return naive_attention(q * (scale * np.sqrt(q.shape[-1])), k, v, None)


@pytest.mark.parametrize("heads, kv_heads, dim, scale", [
    (32, 8, 64, 1.0 / 64),      # granite-4.0-h-micro's attention layer
    (8, 2, 64, 1.0 / 64),
    (8, 2, 16, 0.5),
], ids=["32over8-of-64-scale-1/64", "8over2-of-64-scale-1/64",
        "8over2-of-16-scale-1/2"])
def test_blocked_attention_scale_matches_plain_softmax(heads, kv_heads, dim,
                                                       scale, monkeypatch):
    """``scale`` in place of 1 / sqrt(D): 200 positions in blocks of 128,
    heads of 64 grouped four to a key-value head; forward and gradient
    against the plain softmax of scale * q.k."""
    from paddlebox_tpu.ops import attention
    monkeypatch.setattr(attention, "BLOCK_Q", 128)
    monkeypatch.setattr(attention, "BLOCK_KV", 128)
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    # scores of a few units, so that the softmax is no flat average
    q = 4.0 * jax.random.normal(keys[0], (1, heads, 200, dim))
    k = 4.0 * jax.random.normal(keys[1], (1, kv_heads, 200, dim))
    v = jax.random.normal(keys[2], (1, kv_heads, 200, dim))
    t = jax.random.normal(keys[3], (1, heads, 200, dim))
    got = jax.jit(lambda q, k, v: blocked_attention(q, k, v, None, scale))(
        q, k, v)
    np.testing.assert_allclose(got, scaled_naive(q, k, v, scale),
                               rtol=2e-5, atol=2e-5)
    g = jax.jit(jax.grad(lambda q, k, v: (blocked_attention(
        q, k, v, None, scale) * t).sum(), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda q, k, v: (scaled_naive(q, k, v, scale) * t).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [None, 40], ids=["full", "window"])
def test_blocked_attention_scale_none_is_inverse_sqrt_bit_for_bit(
        window, monkeypatch):
    """scale=None is D ** -0.5, the arithmetic trinity-mini's program has
    always had: equal to the last bit to the same number handed over."""
    from paddlebox_tpu.ops import attention
    monkeypatch.setattr(attention, "BLOCK_Q", 128)
    monkeypatch.setattr(attention, "BLOCK_KV", 128)
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (2, 4, 200, 16), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, 2, 200, 16), jnp.bfloat16)
    v = jax.random.normal(keys[2], (2, 2, 200, 16), jnp.bfloat16)
    run = jax.jit(blocked_attention, static_argnums=(3, 4))
    np.testing.assert_array_equal(
        np.asarray(run(q, k, v, window, None), np.float32),
        np.asarray(run(q, k, v, window, 16 ** -0.5), np.float32))


# ------------------------------------- (c) the share ties to the model
def test_shares_add_up_to_the_uncut_layer(tower):
    """Eight chips hold one expert each of a layer's eight: their routed
    parts, with the shared expert counted once, add up to what the uncut
    reference (all eight held on one chip) gives for the layer's F."""
    _model, params, pooled, _labels, _want = tower
    key = jax.random.PRNGKey(9)
    H, F, E = CFG["hidden_size"], CFG["moe_intermediate_size"], 8
    ks = jax.random.split(key, 4)
    full = {"e_gate": jax.random.normal(ks[0], (E, H, F)) / 8.0,
            "e_up": jax.random.normal(ks[1], (E, H, F)) / 8.0,
            "e_down": jax.random.normal(ks[2], (E, F, H)) / np.sqrt(F)}
    p = dict(ref.layer_params(params, 1), **full)
    x = jax.random.normal(ks[3], (B * S, H))
    uncut = dict(CFG, num_experts=E, expert_offset=0)
    whole = ref.routed_part(uncut, p, x) + ref.swiglu(
        x, p["s_gate"], p["s_up"], p["s_down"])
    experts, weights = route(x, p["router_w"], p["router_b"], 2, 2.826)
    parts, pairs = [], 0
    for chip in range(E):
        y, n, _ = jax.jit(routed_experts, static_argnums=(6, 7))(
            x, experts, weights, full["e_gate"][chip:chip + 1],
            full["e_up"][chip:chip + 1], full["e_down"][chip:chip + 1],
            chip, E)
        parts.append(y)
        pairs += int(n.sum())
    assert pairs == B * S * 2               # every pair is some chip's
    total = sum(parts) + ref.swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


# ------------------------------------------- (d) routing under imbalance
@pytest.mark.parametrize("bias_at, held_pairs", [
    ((3, 4), B * S * 2),        # every token's two choices are held here
    ((3, 6), B * S),            # every token to ONE held expert
    ((0, 7), 0),                # no token to any held expert
], ids=["all-held", "one-held-expert", "none-held"])
def test_routing_under_imbalance_drops_nothing(tower, bias_at, held_pairs,
                                               monkeypatch):
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
    _model, params, _pooled, _labels, _want = tower
    p = dict(ref.layer_params(params, 1))
    p["router_b"] = jnp.zeros(8).at[jnp.asarray(bias_at)].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(11), (B * S, 64))
    experts, weights = route(x, p["router_w"], p["router_b"], 2, 2.826)
    y, n, _ = jax.jit(routed_experts, static_argnums=(6, 7))(
        x, experts, weights, p["e_gate"], p["e_up"], p["e_down"], 3, 8)
    assert int(n.sum()) == held_pairs == ref.pairs_held(CFG, p, x)
    # all pairs held is twice a chunk: the second chunk runs too
    assert chunk_rows(B * S, 2, 2, 8) == B * S
    np.testing.assert_allclose(y, ref.routed_part(CFG, p, x), rtol=2e-5,
                               atol=2e-5)
    g = jax.jit(jax.grad(lambda x: (routed_experts(
        x, experts, weights, p["e_gate"], p["e_up"], p["e_down"], 3, 8)[0]
        ** 2).sum()))(x)
    want = jax.grad(lambda x: (sum(
        weights[:, c:c + 1] * jnp.where(
            experts[:, c:c + 1] == 3 + e,
            ref.swiglu(x, p["e_gate"][e], p["e_up"][e], p["e_down"][e]), 0.0)
        for c in range(2) for e in range(2)) ** 2).sum())(x)
    assert rel(g, want) < 1e-5
