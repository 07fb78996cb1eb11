"""Granite 4.0-H behaviour-sequence tower (models/granite_hybrid.py,
ops/ssd.py, ops/attention.py's ``scale``) against the plain float32
reference written from the layer equations
(tests/granite_hybrid_reference.py: the SEQUENTIAL recurrence), at small
sizes on the CPU with seeded weights; the benchmark's copy of the
reference for the chip (benchmarks/configs/granite-4-h-micro.py) against
the same."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import granite_hybrid_reference as ref  # noqa: E402

from paddlebox_tpu.models.base import ModelSpec  # noqa: E402
from paddlebox_tpu.models.granite_hybrid import GraniteHybrid  # noqa: E402
from paddlebox_tpu.ops.ssd import (causal_conv, chunks_scanned,  # noqa: E402
                                   ssd_scan)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 40 positions in chunks of 16: two whole chunks and a padded one; 4
# state-space heads of 16 (expand 2 of hidden 32), state 8; 4 query heads
# over 2 key-value heads of 8, scores scaled 1/8 and not 8 ** -0.5
CFG = dict(hidden_size=32, intermediate_size=48,
           layer_types=["mamba", "attention", "mamba"],
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           attention_multiplier=0.125, mamba_n_heads=4, mamba_d_head=16,
           mamba_d_state=8, mamba_n_groups=1, mamba_d_conv=4,
           mamba_chunk_size=16, mamba_expand=2, embedding_multiplier=12.0,
           residual_multiplier=0.22, rms_norm_eps=1e-5, head_scale=4.0,
           num_sparse_slots=40, embedx_dim=32, dense_dim=0)
B, S = 2, 40


def config_module():
    spec = importlib.util.spec_from_file_location(
        "granite_4_h_micro_config",
        os.path.join(ROOT, "benchmarks", "configs", "granite-4-h-micro.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(cfg):
    return config_module().build_model(cfg)


def seeded(cfg, seed=0):
    """Weights as the benchmark draws them (param_init: a matrix 1 /
    sqrt(inputs), a norm's weight and D +-1, A_log and dt_bias normal)."""
    model = build(cfg)
    how = config_module().param_init(cfg)
    assert {k: v[0] for k, v in how.items()} == model.shapes()
    keys = jax.random.split(jax.random.PRNGKey(seed), len(how))
    params = {}
    for (name, (shape, size, *sign)), key in zip(sorted(how.items()), keys):
        draw = jax.random.normal(key, shape, jnp.float32)
        params[name] = (jnp.where(draw < 0, -1.0, 1.0) if sign
                        else draw) * size
    pooled = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                      (B, S, 3 + cfg["hidden_size"]))
    return model, params, pooled, jnp.asarray([1.0, 0.0])


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


def norm_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


# ----------------------------------------------- (a) apply vs the reference
def test_float32_matches_reference_tightly(tower):
    """Float32 on both sides: the chunked scan and the sequential
    recurrence differ by the order of their sums alone, a few float32
    roundings over 40 positions: 1e-4 of a leaf's largest gradient. A
    state or a softmax held in bfloat16 (8 bits) reads 1e-2 here and
    fails (test_tolerance_catches_a_bfloat16_state)."""
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
        assert np.any(want_gp[name]), name          # every leaf is read
        assert rel(gp[name], want_gp[name]) < 1e-4, name
    # 2 sequences x 3 chunks (the third padded) x 2 state-space layers
    assert int(counts["ssd_chunks_scanned"]) == 12 == 2 * chunks_scanned(
        B, S, 16)


def test_bfloat16_within_tolerance(tower):
    """The trainer's mixed precision: pooled in bfloat16, the layers cast
    their own matrices (every leaf is an f32_params leaf). bfloat16 keeps
    8 bits: a product over 32 to 64 inputs is good to ~1e-2 and ten such
    follow one another, so the gradients are held by their norm-wise
    error: 0.1 of a leaf's gradient, the band tests/test_afmoe.py gives a
    dense leaf."""
    from paddlebox_tpu.train.trainer import apply_mixed_precision
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
        assert norm_err(gp[name], want_gp[name]) < 0.1, name


def test_chip_reference_copy_equals_plain_reference(tower, monkeypatch):
    """benchmarks/configs/granite-4-h-micro.py forward(): an example at a
    time, the recurrence in checkpointed blocks of positions, attention
    over blocks of queries, every product through mm: float32 against
    float32, the same sums in the same order but for the blocks' edges."""
    _model, params, pooled, labels, (want_loss, (want_gp, want_gx)) = tower
    mod = config_module()
    monkeypatch.setattr(mod, "QUERY_BLOCK", 16)  # 40: two blocks and a padded
    monkeypatch.setattr(mod, "SCAN_BLOCK", 16)

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    loss, (gp, gx) = jax.jit(jax.value_and_grad(
        lambda p, x: bce(mod.forward(CFG, p, x, None, mm), labels),
        argnums=(0, 1)))(params, pooled)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert rel(gx, want_gx) < 1e-4
    for name in params:
        assert rel(gp[name], want_gp[name]) < 1e-4, name


# ---------------------------------------------------- (b) the chunked scan
def scan_inputs(S, seed=5, H=3, P=8, N=16):
    """dt and A in the published ranges: softplus(dt) over 0.001...0.1 and
    beyond, A over [-16, -1]: a state that outlives a chunk of 256 on the
    slow heads and dies inside one on the fast."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (2, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, H)) - 4.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                    maxval=np.log(16.0)))
    Bm = jax.random.normal(ks[3], (2, S, N))
    Cm = jax.random.normal(ks[4], (2, S, N))
    D = jax.random.normal(ks[5], (H,))
    t = jax.random.normal(ks[6], (2, S, H, P))
    return (x, dt, A, Bm, Cm, D), t


@pytest.mark.parametrize("chunk, S", [(64, 512), (128, 512), (256, 512),
                                      (128, 300)],
                         ids=["chunk64", "chunk128", "chunk256",
                              "chunk128-300-positions"])
def test_chunked_scan_matches_the_sequential_recurrence(chunk, S):
    """Values and every input's gradient, float32 both: the two differ by
    the order of sums of up to 512 terms: 2e-5 of the largest value."""
    args, t = scan_inputs(S)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
    want = jax.jit(ref.recurrence)(*args)
    assert got.shape == want.shape == (2, S, 3, 8)
    assert rel(got, want) < 2e-5
    g = jax.jit(jax.grad(lambda *a: (ssd_scan(*a, chunk=chunk) * t).sum(),
                         argnums=tuple(range(6))))(*args)
    w = jax.jit(jax.grad(lambda *a: (ref.recurrence(*a) * t).sum(),
                         argnums=tuple(range(6))))(*args)
    for name, a, b in zip("x dt A B C D".split(), g, w):
        assert rel(a, b) < 5e-5, name


def test_chunk_size_does_not_change_the_result():
    args, _t = scan_inputs(512)
    y64, y128, y256 = (jax.jit(lambda *a, c=c: ssd_scan(*a, chunk=c))(*args)
                       for c in (64, 128, 256))
    np.testing.assert_allclose(y64, y256, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y128, y256, rtol=2e-5, atol=2e-5)


def test_tolerance_catches_a_bfloat16_state():
    """The same recurrence with the carried state rounded to bfloat16 at
    every chunk's edge (and nothing else of it) lies 20 x outside the
    band the float32 scan is held to."""
    args, _t = scan_inputs(512)
    x, dt, A, Bm, Cm, D = args
    want = ref.recurrence(*args)
    pieces, h = [], None
    for c in range(0, 512, 64):     # the state handed on in bfloat16
        sl = slice(c, c + 64)
        xs = x[:, sl]
        y = ref.recurrence(xs, dt[:, sl], A, Bm[:, sl], Cm[:, sl], D)
        decay = jnp.exp(jnp.cumsum(dt[:, sl] * A, axis=1))
        if h is not None:
            y = y + decay[..., None] * jnp.einsum(
                "bhpn,bsn->bshp", h, Cm[:, sl])
        pieces.append(y)
        own = jnp.einsum("bshp,bsn,bsh->bhpn", xs * dt[:, sl, :, None],
                         Bm[:, sl], decay[:, -1:] / decay)
        h = own if h is None else decay[:, -1, :, None, None] * h + own
        h = h.astype(jnp.bfloat16).astype(jnp.float32)
    assert rel(jnp.concatenate(pieces, axis=1), want) > 20 * 2e-5


def test_convolution_reads_zeros_before_position_zero():
    """The first three positions: tap k of position t reads t - 3 + k, and
    a position before 0 gives nothing."""
    x = jnp.arange(1.0, 11.0).reshape(1, 5, 2)       # 5 positions, 2 channels
    w = jnp.asarray([[1000.0, 0.5], [100.0, 0.0], [10.0, 0.0], [1.0, 2.0]])
    b = jnp.asarray([0.0, 0.25])
    got = np.asarray(causal_conv(x, w, b))
    ch0 = x[0, :, 0]                                  # 1, 3, 5, 7, 9
    np.testing.assert_allclose(got[0, 0, 0], ch0[0])
    np.testing.assert_allclose(got[0, 1, 0], ch0[1] + 10 * ch0[0])
    np.testing.assert_allclose(got[0, 2, 0],
                               ch0[2] + 10 * ch0[1] + 100 * ch0[0])
    np.testing.assert_allclose(
        got[0, 3, 0], ch0[3] + 10 * ch0[2] + 100 * ch0[1] + 1000 * ch0[0])
    ch1 = np.asarray(x[0, :, 1])                      # 2, 4, 6, 8, 10
    np.testing.assert_allclose(
        got[0, :, 1], 0.25 + 2 * ch1 + 0.5 * np.concatenate(
            [[0, 0, 0], ch1[:2]]))
    np.testing.assert_allclose(got, ref.conv(x, w, b), rtol=1e-6)


# --------------------------------------------------- (c) the layer pattern
def test_layer_types_build_exactly_that_order():
    """One period as published: five state-space layers, the attention
    layer, four more; every layer with its SwiGLU and two norms."""
    order = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    model = build(dict(CFG, layer_types=order))
    shapes = model.shapes()
    for i, kind in enumerate(order):
        has = {k.split(".")[1] for k in shapes if k.startswith("l%d." % i)}
        shared = {"norm1", "norm2", "mlp_in", "mlp_out"}
        if kind == "mamba":
            assert has == shared | {"in_proj", "conv_w", "conv_b", "dt_bias",
                                    "A_log", "D", "gnorm", "out_proj"}, i
        else:
            assert has == shared | {"wq", "wk", "wv", "wo"}, i
    assert not any(k.startswith("l10.") for k in shapes)
    # [z | xBC | dt]: 64 + (64 + 2 x 8) + 4
    assert shapes["l0.in_proj"] == (32, 64 + 80 + 4)
    assert shapes["l0.conv_w"] == (4, 80) and shapes["l5.wk"] == (32, 16)
    counts = {}
    pooled = jnp.zeros((B, S, 3 + 32))
    model.apply(model.init(jax.random.PRNGKey(0)), pooled, counters=counts)
    assert int(counts["ssd_chunks_scanned"]) == B * 3 * 9
    with pytest.raises(ValueError):
        build(dict(CFG, layer_types=["mamba", "full_attention"]))


def test_published_sizes_hold_746_47_million_parameters():
    """The configuration file at its published widths: 9 state-space
    layers of 76.18M, the attention layer of 60.82M, the final norm and
    the head: counted from the shapes, nothing allocated."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4-h-micro.json")) as f:
        cfg = json.load(f)
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    shapes = build(cfg).shapes()
    per_layer = [sum(int(np.prod(v)) for k, v in shapes.items()
                     if k.startswith("l%d." % i)) for i in range(10)]
    mlp = 2048 * 16384 + 8192 * 2048 + 2 * 2048     # the SwiGLU, two norms
    mamba = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048
             + mlp)                                 # 76.18M
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp      # 60.82M
    assert per_layer == [mamba] * 5 + [attention] + [mamba] * 4
    total = sum(int(np.prod(v)) for v in shapes.values())
    assert total == 9 * mamba + attention + 2 * 2048 + 1
    assert round(total / 1e6, 2) == 746.47
    mod = config_module()
    assert mod._held(cfg) == total
    assert {k: v[0] for k, v in mod.param_init(cfg).items()} == shapes
