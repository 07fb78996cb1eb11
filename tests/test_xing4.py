"""Xing4.0 behaviour-sequence tower (models/xing4.py; ops/mhc.py; latent
attention through ops/attention.py with values narrower than the scores;
ops/routed_experts.py with SwiGLU experts) against the plain float32
reference written from the layer equations (tests/xing4_reference.py), at
small sizes on the CPU with seeded weights; the expert shares of a layer
against the uncut layer; the hyper-connection's maps; YaRN's numbers; the
benchmark's copy of the reference for the chip
(benchmarks/configs/xing4-29b-a4b.py) against the same."""

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import xing4_reference as ref  # noqa: E402

from paddlebox_tpu.models.afmoe import rms_norm  # noqa: E402
from paddlebox_tpu.ops.attention import blocked_attention  # noqa: E402
from paddlebox_tpu.ops.mhc import sinkhorn, stream_maps  # noqa: E402
from paddlebox_tpu.ops.routed_experts import chunk_rows  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
# the UNCUT small model: hidden 32 in 4 streams; 2 heads of 8 + 4 (rotary)
# for the scores and 8 for the values, q rank 16, kv rank 8; one dense
# layer of 48, then a routed layer: 8 router outputs, the top 3 a token,
# SwiGLU experts of 24 and a shared one; 40 positions; YaRN over an
# original context of 16, so that one of the two rotary pairs keeps its
# frequency and the other takes it over the factor
WHOLE = dict(hidden_size=32, num_hidden_layers=2, first_k_dense_replace=1,
             num_attention_heads=2, q_lora_rank=16, kv_lora_rank=8,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             intermediate_size=48, moe_intermediate_size=24,
             n_routed_experts=8, n_routed_experts_published=8,
             expert_offset=0, num_experts_per_tok=3,
             routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20,
             hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
             rope_theta=10000, rope_scaling=YARN, rms_norm_eps=1e-6,
             hc_alpha=0.5, head_scale=4.0, router_bias_std=0.01,
             num_sparse_slots=40, embedx_dim=32, dense_dim=0)
# one chip's share, as the cell cuts it: 2 of the 8 experts from the
# fifth on; attention, the maps, the router and the shared expert whole
CFG = dict(WHOLE, n_routed_experts=2, expert_offset=4)
B, S = 2, 40


def config_module():
    spec = importlib.util.spec_from_file_location(
        "xing4_config",
        os.path.join(ROOT, "benchmarks", "configs", "xing4-29b-a4b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(cfg):
    return config_module().build_model(cfg)


def draw(how, seed):
    """Weights as the benchmark draws them (param_init), in one program."""
    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(how))
        params = {}
        for (name, (shape, size, *sign)), k in zip(sorted(how.items()), keys):
            d = jax.random.normal(k, shape, jnp.float32)
            params[name] = (jnp.where(d < 0, -1.0, 1.0) if sign else d) * size
        return params
    return make(jax.random.PRNGKey(seed))


def seeded(cfg, seed=0):
    model = build(cfg)
    how = config_module().param_init(cfg)
    assert {k: v[0] for k, v in how.items()} == model.shapes()
    pooled = 0.5 * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                     (B, S, 3 + cfg["hidden_size"]))
    return model, draw(how, seed), pooled, jnp.asarray([1.0, 0.0])


def bce(logits, y):
    return (jnp.logaddexp(logits, 0.0) - logits * y).mean()


@pytest.fixture(scope="module")
def tower():
    model, params, pooled, labels = seeded(CFG, seed=1)
    want = jax.jit(jax.value_and_grad(
        lambda p, x: bce(ref.forward(CFG, p, x), labels), argnums=(0, 1)))(
            params, pooled)
    return model, params, pooled, labels, want


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


def norm_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def small_tiles(monkeypatch):
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 32, 32))


# ----------------------------------------------- (a) apply vs the reference
def test_float32_matches_reference_tightly(tower, monkeypatch):
    """Float32 on both sides: the blocked attention kernel against the
    whole scores, the grouped products over sorted pairs against a loop
    over experts: the order of the sums alone, 1e-4 of a leaf's largest
    gradient; every leaf is read. The router's bias is read by the choice
    alone: its gradient is nought on both sides. The pairs counted are
    the reference router's over each routed layer's own input."""
    small_tiles(monkeypatch)
    model, params, pooled, labels, want = tower
    counts = matches_reference(CFG, model, params, pooled, labels, want)
    assert int(counts["moe_pairs_held"]) == reference_pairs(params, pooled)
    assert int(counts["mhc_fused_tokens"]) == 0     # hidden 32: XLA form


def test_fused_hyper_connections_match_reference(monkeypatch):
    """Hidden 128, a lane-aligned stream: every hyper-connection takes
    ops/mhc.py's four kernels (interpreted here) and the tower reads as
    the plain reference does, to the tolerances of (a); the counter holds
    tokens x sublayers."""
    small_tiles(monkeypatch)
    cfg = dict(CFG, hidden_size=128, embedx_dim=128)
    model, params, pooled, labels = seeded(cfg, seed=2)
    want = jax.jit(jax.value_and_grad(
        lambda p, x: bce(ref.forward(cfg, p, x), labels), argnums=(0, 1)))(
            params, pooled)
    counts = matches_reference(cfg, model, params, pooled, labels, want)
    assert int(counts["mhc_fused_tokens"]) == (
        B * S * 2 * cfg["num_hidden_layers"])


def matches_reference(cfg, model, params, pooled, labels, want):
    """Logits, loss and every leaf's gradient against the reference's
    (want: its loss and gradients); the step counters handed back."""
    want_loss, (want_gp, want_gx) = want

    def loss_fn(p, x):
        counts = {}
        logits = model.apply(p, x, counters=counts)
        return bce(logits, labels), (logits, counts)
    (loss, (logits, counts)), (gp, gx) = jax.jit(jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True))(params, pooled)
    np.testing.assert_allclose(
        logits, jax.jit(lambda p, x: ref.forward(cfg, p, x))(params, pooled),
        rtol=2e-5, atol=2e-6)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert rel(gx, want_gx) < 1e-4
    for name in params:
        if name.endswith("router_b"):
            assert not np.any(gp[name]) and not np.any(want_gp[name])
            continue
        assert np.any(want_gp[name]), name
        assert rel(gp[name], want_gp[name]) < 1e-4, name
    return counts


def reference_pairs(params, pooled):
    """(token, choice) pairs the reference's routers send to the held
    experts, every routed layer, each over the layer's own input."""
    eps = CFG["rms_norm_eps"]

    @jax.jit
    def chosen(params, pooled):
        out, x = [], jnp.repeat(pooled[:, :, None, 3:], 4, axis=2)
        for i in range(CFG["num_hidden_layers"]):
            p = ref.layer_params(params, i)
            if i >= CFG["first_k_dense_replace"]:
                mid = ref.connected(CFG, p, "a_", x, lambda u: ref.mla(
                    CFG, p, ref.norm(u, p["attn_norm"], eps)))
                h_pre = ref.maps(CFG, p, "f_", mid)[0]
                u = ref.norm(jnp.einsum("...i,...ic->...c", h_pre, mid),
                             p["ffn_norm"], eps)
                out.append(ref.router(CFG, p, u)[1])
            x = ref.layer(CFG, i, p, x)
        return out
    lo = CFG["expert_offset"]
    held = sum(int(np.asarray(c)[..., lo:lo + CFG["n_routed_experts"]].sum())
               for c in chosen(params, pooled))
    assert held > 0
    return held


def test_bfloat16_within_tolerance(tower, monkeypatch):
    """The trainer's mixed precision: pooled in bfloat16, the layers cast
    their own matrices (every leaf is an f32_params leaf); the streams,
    maps and Sinkhorn stay float32. The gradients are held by their
    norm-wise error: 0.1 of a matrix's gradient, the band the other
    towers' tests give a leaf, and 0.25 of a leaf of fewer than 32 values
    (a map's three scales and 24 biases, a norm of 8 or 16): such a
    gradient is a batch's signed sum over 80 positions, nothing averages
    its rounding out (benchmarks/harness/reference.py holds such leaves to
    a limit of their own for the same reason)."""
    from paddlebox_tpu.train.trainer import apply_mixed_precision
    small_tiles(monkeypatch)
    model, params, pooled, labels, (want_loss, (want_gp, want_gx)) = tower
    assert set(model.f32_params) == set(params)

    def loss_fn(p, x):
        p, x, _ = apply_mixed_precision(p, x, None, jnp.bfloat16,
                                        model.f32_params)
        assert all(v.dtype == jnp.float32 for v in p.values())
        return bce(model.apply(p, x).astype(jnp.float32), labels)
    loss, (gp, gx) = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(
        params, pooled)
    assert abs(float(loss) - float(want_loss)) < 0.02
    assert norm_err(gx, want_gx) < 0.1
    for name in params:
        if not name.endswith("router_b"):
            band = 0.1 if params[name].size >= 32 else 0.25
            assert norm_err(gp[name], want_gp[name]) < band, name


def test_chip_reference_copy_equals_plain_reference(tower, monkeypatch):
    """benchmarks/configs/xing4-29b-a4b.py forward(): an example at a
    time, a layer and each sublayer under a checkpoint, attention over
    blocks of queries, an expert at a time, every product (the maps and
    the three mixes included) through mm: float32 against float32."""
    _model, params, pooled, labels, (want_loss, (want_gp, want_gx)) = tower
    mod = config_module()
    monkeypatch.setattr(mod, "QUERY_BLOCK", 16)  # 40: two blocks and a padded

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    loss, (gp, gx) = jax.jit(jax.value_and_grad(
        lambda p, x: bce(mod.forward(CFG, p, x, None, mm), labels),
        argnums=(0, 1)))(params, pooled)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert rel(gx, want_gx) < 1e-4
    for name in params:
        if not name.endswith("router_b"):
            assert rel(gp[name], want_gp[name]) < 1e-4, name


# --------------------------------- (b) the shares add up to the uncut layer
def test_expert_shares_add_up_to_the_uncut_layer(monkeypatch):
    """8 chips share a routed layer, one of its 8 experts each (the cell:
    8 of 64 a chip); attention, the maps, the router and the shared expert
    are whole on every chip and counted once. The attention sublayer is
    the same on every chip; chip c's part is its F sublayer less the same
    sublayer with its held expert silent (e_down nought). The eight parts
    and that silent sublayer add up to the uncut reference layer (the
    routed sum enters the streams through H_post alone, linearly), and
    each chip's layer is what the reference gives for its share."""
    small_tiles(monkeypatch)
    cfg = dict(WHOLE)
    how = {k[3:]: v for k, v in config_module().param_init(cfg).items()
           if k.startswith("l1.")}
    p = draw(how, 3)
    x = jax.random.normal(jax.random.PRNGKey(53), (B, S, 4, 32))
    want = ref.layer(cfg, 1, p, x)
    model = build(cfg)
    mid = jax.jit(lambda p, x: model._connected(p, "a_", x, lambda u: (
        model._mla(p, rms_norm(u, p["attn_norm"], cfg["rms_norm_eps"]),
                   jnp.float32), None))[0])(p, x.reshape(B, S, -1))
    total = None
    for chip in range(8):
        scfg = dict(cfg, n_routed_experts=1, expert_offset=chip)
        sp = dict(p, **{k: p[k][chip:chip + 1]
                        for k in ("e_gate", "e_up", "e_down")})
        share = build(scfg)
        ffn = jax.jit(lambda sp, mid: share._connected(
            sp, "f_", mid, lambda u: share._ffn(1, sp, u, jnp.float32))[0])
        got = ffn(sp, mid).reshape(x.shape)
        assert rel(got, ref.layer(scfg, 1, sp, x)) < 2e-5, chip
        silent = ffn(dict(sp, e_down=jnp.zeros_like(sp["e_down"])),
                     mid).reshape(x.shape)
        total = silent if total is None else total
        total = total + (got - silent)
    assert rel(total, want) < 2e-5
    assert rel(total - (got - silent), want) > 1e-3  # a share left out


# -------------------------------------- (c) the hyper-connection's maps
def test_maps_are_bounded_and_sinkhorn_is_doubly_stochastic():
    """At the cell's draws (scales +-0.5, biases +-1, phi 1 / sqrt(n C)):
    after the published 20 rounds every H_res's columns sum to 1 within
    1e-5 (a round ends on them) and its rows within 1e-4 (20 rounds are
    the published count, not convergence: logits that span +-2.5 contract
    the rows' error by no more than about 0.7 a round); after 40 rounds
    both within 1e-5. H_pre lies in (0, 1) and H_post in (0, 2); the maps
    are the reference's."""
    n, C = 4, 32
    keys = jax.random.split(jax.random.PRNGKey(8), 4)
    x = jax.random.normal(keys[0], (B, S, n, C))
    phi = jax.random.normal(keys[1], (n * C, 2 * n + n * n)) / np.sqrt(n * C)
    alpha = 0.5 * jnp.sign(jax.random.normal(keys[2], (3,)))
    bias = jnp.sign(jax.random.normal(keys[3], (2 * n + n * n,)))
    hc = dict(eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
    maps = jax.jit(lambda *a, iters: stream_maps(*a, iters=iters, **hc),
                   static_argnames="iters")
    h_pre, h_post, h_res = maps(x, phi, alpha, bias, iters=20)
    assert h_pre.shape == h_post.shape == (B, S, n)
    assert h_res.shape == (B, S, n, n)
    assert float(h_pre.min()) > 0 and float(h_pre.max()) < 1
    assert float(h_post.min()) > 0 and float(h_post.max()) < 2
    assert float(h_res.min()) >= 0
    np.testing.assert_allclose(h_res.sum(axis=-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(axis=-1), 1.0, atol=1e-4)
    more = maps(x, phi, alpha, bias, iters=40)[2]
    for axis in (-1, -2):
        np.testing.assert_allclose(more.sum(axis=axis), 1.0, atol=1e-5)
    p = {"a_phi": phi, "a_alpha": alpha, "a_bias": bias}
    for got, want in zip((h_pre, h_post, h_res), ref.maps(
            dict(WHOLE), p, "a_", x)):
        assert rel(got, want) < 1e-5
    logits = jnp.asarray([[0.0, 30.0], [60.0, -60.0]])    # past the clamp
    np.testing.assert_allclose(sinkhorn(logits, 20, 1e-6, (-30.0, 30.0)),
                               jnp.eye(2)[::-1], atol=1e-6)


# --------------------------------------------------------- (d) YaRN
def test_yarn_frequencies_and_attention_factor_by_hand():
    """The published rope_scaling over 64 rotary dims: pair i turns
    theta^(-i/32) a position; pairs 0-10 turn more than beta_fast 32
    times over the original 4,096 positions and keep it, pairs 23-31 turn
    fewer than beta_slow once and take it over factor 64, pairs 11-22 a
    ramp of (i - 10) / 13 between. m = 0.1 ln 64 + 1; the scores scale
    192^-1/2 m^2; cos and sin are unscaled (mscale = mscale_all_dim)."""
    from paddlebox_tpu.models.xing4 import yarn_inv_freq, yarn_mscale
    got = yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    assert got.shape == (32,)
    assert got[0] == 1.0
    assert math.isclose(got[10], 10 ** -1.25, rel_tol=1e-12)
    assert math.isclose(got[16], 0.01 * (7 / 13 + 6 / 13 / 64),
                        rel_tol=1e-12)
    assert math.isclose(got[23], 10 ** (-46 / 16) / 64, rel_tol=1e-12)
    assert math.isclose(got[31], 10 ** (-62 / 16) / 64, rel_tol=1e-12)
    np.testing.assert_allclose(got, ref.inv_freq(
        {"rope_scaling": dict(YARN, original_max_position_embeddings=4096),
         "qk_rope_head_dim": 64, "rope_theta": 10000}), rtol=1e-12)
    m = yarn_mscale(64.0, 1.0)
    assert math.isclose(m, 1.4158883083359672, rel_tol=1e-12)
    assert math.isclose(m * m, 2.0047396, rel_tol=1e-7)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4-29b-a4b.json")) as f:
        model = build(json.load(f))
    assert math.isclose(model.scale, 192 ** -0.5 * m * m, rel_tol=1e-12)
    assert model.rot_scale == 1.0
    np.testing.assert_array_equal(model.inv_freq, got)


# -------------------- (e) the blocked attention with values of their own
def naive(q, k, v, scale):
    n = q.shape[2]
    see = np.arange(n)[None, :] <= np.arange(n)[:, None]
    scores = jnp.einsum("bhid,bhjd->bhij", q, k) * scale
    return jnp.einsum("bhij,bhjd->bhid",
                      jax.nn.softmax(jnp.where(see, scores, -jnp.inf), -1), v)


def test_blocked_attention_values_narrower_than_the_scores(monkeypatch):
    """q and k of 192 (MLA's nope 128 + rope 64), v of 128, causal, 200
    positions in blocks of 128 (no multiple of the block), the scale the
    caller's: forward and every input's gradient against the plain
    softmax (the kernel interpreted)."""
    from paddlebox_tpu.ops import attention
    monkeypatch.setattr(attention, "BLOCK_Q", 128)
    monkeypatch.setattr(attention, "BLOCK_KV", 128)
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    q = jax.random.normal(keys[0], (1, 2, 200, 192))
    k = jax.random.normal(keys[1], (1, 2, 200, 192))
    v = jax.random.normal(keys[2], (1, 2, 200, 128))
    t = jax.random.normal(keys[3], (1, 2, 200, 128))
    scale = 0.1447
    got = jax.jit(lambda q, k, v: blocked_attention(q, k, v, None, scale))(
        q, k, v)
    assert got.shape == (1, 2, 200, 128)
    np.testing.assert_allclose(got, naive(q, k, v, scale), rtol=2e-5,
                               atol=2e-5)
    g = jax.jit(jax.grad(lambda q, k, v: (blocked_attention(
        q, k, v, None, scale) * t).sum(), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda q, k, v: (naive(q, k, v, scale) * t).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ------------------------------------------- (f) the cell's size
def test_published_widths_hold_641_9_million_parameters():
    """The configuration file at its published widths, from the shapes,
    nothing allocated: MLA 28,411,136 a layer, the two hyper-connections
    688,182, the two sublayers' norms 7,168; the dense layer's SwiGLU
    99,090,432; a routed layer's router 229,440, 8 held experts of
    11,010,048 and the shared one: 128,196,918 + 4 x 128,426,358 + the
    head's 7,169 = 641,909,519. Counts in thousands and a rest (boxlint
    BX951)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4-29b-a4b.json")) as f:
        cell = json.load(f)
    shapes = build(cell).shapes()
    per_layer = [sum(int(np.prod(v)) for k, v in shapes.items()
                     if k.startswith("l%d." % i)) for i in range(5)]
    mla = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
           + 512 * 32 * 256 + 32 * 128 * 3584)
    maps = 2 * (4 * 3584 * 24 + 3 + 24)
    expert = 3 * 3584 * 1024
    dense = mla + maps + 2 * 3584 + 3 * 3584 * 9216
    routed = mla + maps + 2 * 3584 + 3584 * 64 + 64 + 9 * expert
    assert [divmod(n, 1000) for n in (mla, maps, expert, dense, routed)] == [
        (28_411, 136), (688, 182), (11_010, 48), (128_196, 918),
        (128_426, 358)]
    assert per_layer == [dense] + [routed] * 4
    total = sum(int(np.prod(v)) for v in shapes.values())
    assert divmod(total, 1000) == (641_909, 519)
    assert cell["dense_parameters_held"] == total
    mod = config_module()
    assert mod._held(cell) == total
    assert {k: v[0] for k, v in mod.param_init(cell).items()} == shapes
    # the widths are the published ones
    assert shapes["l1.e_gate"] == (8, 3584, 1024)
    assert shapes["l1.router_w"] == (3584, 64)
    assert shapes["l0.q_b"] == (768, 32 * (128 + 64))
    assert shapes["l0.kv_a"] == (3584, 512 + 64)
    assert shapes["l0.kv_b"] == (512, 32 * (128 + 128))
    assert shapes["l0.a_phi"] == (4 * 3584, 4 + 4 + 16)
    # 4 chunks of 8,192 pairs a routed layer, of which an even routing
    # fills half of one (4,096 pairs to the 8 held experts)
    assert chunk_rows(8192, 4, 8, 64) == 8192 == 32768 // 4
