"""Nemotron-H behaviour-sequence tower (models/nemotron_h.py; ops/ssd.py
with groups of B and C; ops/routed_experts.py with ungated relu^2 experts)
against the plain float32 reference written from the layer equations
(tests/nemotron_h_reference.py: the SEQUENTIAL recurrence, a loop over
experts), at small sizes on the CPU with seeded weights; the shares of
every layer against the uncut layer; the benchmark's copy of the reference
for the chip (benchmarks/configs/nemotron-3-super.py) against the same."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import nemotron_h_reference as ref  # noqa: E402

from paddlebox_tpu.ops.routed_experts import (chunk_rows, route,  # noqa: E402
                                              routed_experts)
from paddlebox_tpu.ops.ssd import chunks_scanned, ssd_scan  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the UNCUT small model: hidden 64; 8 state-space heads of 16 in 4 groups,
# state 8; 4 query heads over 2 key-value heads of 16; 8 router outputs,
# the top 3 a token, experts of 48 in a latent of 32, a shared expert of
# 96; 40 positions in chunks of 16 (two whole chunks and a padded one)
WHOLE = dict(hidden_size=64, expand=2, hybrid_override_pattern="MEM*E",
             num_hidden_layers=5, mamba_num_heads=8,
             mamba_num_heads_published=8, mamba_head_dim=16,
             ssm_state_size=8, n_groups=4, n_groups_published=4,
             conv_kernel=4, chunk_size=16, num_attention_heads=4,
             num_attention_heads_published=4, attention_head_offset=0,
             num_key_value_heads=2, num_key_value_heads_published=2,
             head_dim=16, moe_latent_size=32, moe_intermediate_size=48,
             moe_shared_expert_columns_held=96, n_routed_experts=8,
             n_routed_experts_published=8, expert_offset=0,
             num_experts_per_tok=3, routed_scaling_factor=5.0,
             norm_eps=1e-5, head_scale=4.0, router_bias_std=0.01,
             num_sparse_slots=40, embedx_dim=64, dense_dim=0)
# one chip's share, as the cell cuts it: a quarter of every mixer and of
# the shared expert (1 group of 2 heads, 1 query head with the key-value
# head it reads, 24 columns), 2 of the 8 experts from the fourth on
CFG = dict(WHOLE, mamba_num_heads=2, n_groups=1, num_attention_heads=1,
           num_key_value_heads=1, attention_head_offset=3,
           moe_shared_expert_columns_held=24, n_routed_experts=2,
           expert_offset=4)
B, S = 2, 40


def config_module():
    spec = importlib.util.spec_from_file_location(
        "nemotron_3_super_config",
        os.path.join(ROOT, "benchmarks", "configs", "nemotron-3-super.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(cfg):
    return config_module().build_model(cfg)


def draw(how, seed):
    """Weights as the benchmark draws them (param_init: a matrix 1 /
    sqrt(inputs), a norm's weight and D +-1, A_log and dt_bias normal)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(how))
    params = {}
    for (name, (shape, size, *sign)), key in zip(sorted(how.items()), keys):
        d = jax.random.normal(key, shape, jnp.float32)
        params[name] = (jnp.where(d < 0, -1.0, 1.0) if sign else d) * size
    return params


def seeded(cfg, seed=0):
    model = build(cfg)
    how = config_module().param_init(cfg)
    assert {k: v[0] for k, v in how.items()} == model.shapes()
    pooled = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 100),
                                      (B, S, 3 + cfg["hidden_size"]))
    return model, draw(how, seed), pooled, jnp.asarray([1.0, 0.0])


def bce(logits, y):
    return (jnp.logaddexp(logits, 0.0) - logits * y).mean()


@pytest.fixture(scope="module")
def tower():
    # seed 2: the routers' top 3 of 8 are the same in bfloat16 as in
    # float32 on every token. On seed 0 seven of the 160 (token, layer)
    # choices flip at a near tie, one whole expert swapped for another,
    # and the residual stream then differs by 12% and not by the 1.2%
    # that rounding gives: a discrete step no tolerance of a rounding
    # describes (on the chip the limits are set from readings, PERF.md)
    model, params, pooled, labels = seeded(CFG, seed=2)
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
def test_float32_matches_reference_tightly(tower, monkeypatch):
    """Float32 on both sides: the chunked scan against the sequential
    recurrence, the grouped products over sorted pairs against a loop over
    experts: the order of the sums alone, 1e-4 of a leaf's largest
    gradient. The router's bias is read by the choice alone: its gradient
    is nought on both sides."""
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
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
        if name.endswith("router_b"):
            assert not np.any(gp[name]) and not np.any(want_gp[name])
            continue
        assert np.any(want_gp[name]), name          # every leaf is read
        assert rel(gp[name], want_gp[name]) < 1e-4, name
    # 2 sequences x 3 chunks (the third padded) x 2 state-space layers
    assert int(counts["ssd_chunks_scanned"]) == 12 == 2 * chunks_scanned(
        B, S, 16)
    # the pairs of each E layer, counted by the reference from the
    # router's choice over the layer's own input
    held, fullest, h = 0, 0, pooled[..., 3:]
    for i, kind in enumerate(CFG["hybrid_override_pattern"]):
        p = ref.layer_params(params, i)
        if kind == "E":
            by = ref.pairs_by_expert(CFG, p, ref.norm(h, p["norm"], 1e-5))
            held, fullest = held + by.sum(), fullest + by.max()
        h = ref.layer(CFG, i, p, h)
    assert int(counts["moe_pairs_held"]) == held > 0
    assert int(counts["moe_pairs_max_expert"]) == fullest
    assert held / 2 <= fullest < held


def test_bfloat16_within_tolerance(tower, monkeypatch):
    """The trainer's mixed precision: pooled in bfloat16, the layers cast
    their own matrices (every leaf is an f32_params leaf). The gradients
    are held by their norm-wise error: 0.1 of a leaf's gradient, the band
    tests/test_afmoe.py and tests/test_granite_hybrid.py give a leaf
    (relu^2 doubles a product's relative rounding; the band holds it)."""
    from paddlebox_tpu.ops import routed_experts as module
    from paddlebox_tpu.train.trainer import apply_mixed_precision
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
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
            assert norm_err(gp[name], want_gp[name]) < 0.1, name


def test_chip_reference_copy_equals_plain_reference(tower, monkeypatch):
    """benchmarks/configs/nemotron-3-super.py forward(): an example at a
    time, the recurrence in checkpointed blocks of positions, attention
    over blocks of queries, an expert at a time under a checkpoint, every
    product through mm: float32 against float32."""
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
        if not name.endswith("router_b"):
            assert rel(gp[name], want_gp[name]) < 1e-4, name


# --------------------------------- (b) the shares add up to the uncut layer
def whole_layer(kind, seed=3):
    """(cfg of one uncut layer, its parameters, its input)."""
    cfg = dict(WHOLE, hybrid_override_pattern=kind, num_hidden_layers=1)
    how = {k[3:]: v for k, v in config_module().param_init(cfg).items()
           if k.startswith("l0.")}
    u = jax.random.normal(jax.random.PRNGKey(seed + 50), (B, S, 64))
    return cfg, draw(how, seed), u


def program_mix(cfg, kind, p, u):
    """The program's mixer of ``kind`` alone (no norm, no residual), in
    float32, built for the share ``cfg`` describes."""
    model = build(cfg)
    mix = {"M": model._mamba, "*": model._attention,
           "E": lambda p, u, cdt: model._latent_moe(p, u, cdt)[0]}[kind]
    return jax.jit(lambda p, u: mix(p, u, jnp.float32))(p, u)


@pytest.mark.parametrize("kind", ["M", "*"], ids=["state-space", "attention"])
def test_four_head_shares_add_up_to_the_uncut_mixer(kind):
    """The 4 chips of a host: 1 of the 4 groups (2 of the 8 heads), or 1
    of the 4 query heads with the key-value head it reads, each; out_proj
    and Wo give partial sums, and the per-group norm needs no statistic of
    another share: the four parts add up to the uncut reference mixer."""
    cfg, p, u = whole_layer(kind)
    want = ref.MIX[kind](cfg, p, u)
    total = jnp.zeros_like(want)
    for s in range(4):
        share = dict(groups=(s, 1)) if kind == "M" else dict(heads=(s, 1))
        scfg, sp = ref.take_share(cfg, kind, p, **share)
        got = program_mix(scfg, kind, sp, u)
        # the share's reference gives the same part
        assert rel(got, ref.MIX[kind](scfg, sp, u)) < 2e-5, s
        total = total + got
    assert rel(total, want) < 2e-5
    assert rel(total - got, want) > 1e-2    # a share left out is missed


def test_expert_and_column_shares_add_up_to_the_uncut_layer(monkeypatch):
    """2 hosts x 4 chips (the cell: 8 x 4, 16 experts a chip): chip c holds
    router output c of the 8 and, as chip c % 4 of its host, 24 of the
    shared expert's 96 columns; router and latent projections whole on
    every chip. What every chip computes alike is counted once: the second
    host's copies of the four column shares are left out (their s_down
    nought). The eight parts add up to the uncut reference LatentMoE
    layer, and each is what the reference gives for the same share."""
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
    cfg, p, u = whole_layer("E")
    want = ref.latent_moe(cfg, p, u)
    total = jnp.zeros_like(want)
    for chip in range(8):
        scfg, sp = ref.take_share(cfg, "E", p, columns=(24 * (chip % 4), 24),
                                  experts=(chip, 1))
        if chip >= 4:
            sp["s_down"] = jnp.zeros_like(sp["s_down"])
        got = program_mix(scfg, "E", sp, u)
        assert norm_err(got, ref.latent_moe(scfg, sp, u)) < 2e-5, chip
        total = total + got
    assert rel(total, want) < 2e-5
    assert rel(total - got, want) > 1e-3    # a share left out is missed


# ------------------------------------------- (c) the chunked scan with groups
def scan_inputs(S, G, seed=5, H=4, P=8, N=16):
    """dt and A in the published ranges: a state that outlives a chunk on
    the slow heads and dies inside one on the fast; B and C a group."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (2, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, H)) - 4.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                    maxval=np.log(16.0)))
    Bm = jax.random.normal(ks[3], (2, S, G, N))
    Cm = jax.random.normal(ks[4], (2, S, G, N))
    D = jax.random.normal(ks[5], (H,))
    t = jax.random.normal(ks[6], (2, S, H, P))
    return (x, dt, A, Bm, Cm, D), t


@pytest.mark.parametrize("G, chunk, S", [(2, 128, 512), (4, 64, 512),
                                         (2, 128, 300), (1, 128, 512)],
                         ids=["2-groups", "4-groups-chunk64",
                              "2-groups-300-positions", "1-group-axis"])
def test_grouped_scan_matches_the_sequential_recurrence(G, chunk, S):
    """Values and every input's gradient, float32 both, a head reading its
    group's B and C: the two differ by the order of sums of up to 512
    terms: 2e-5 of the largest value."""
    args, t = scan_inputs(S, G)
    got = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
    want = jax.jit(ref.recurrence)(*args)
    assert got.shape == want.shape == (2, S, 4, 8)
    assert rel(got, want) < 2e-5
    g = jax.jit(jax.grad(lambda *a: (ssd_scan(*a, chunk=chunk) * t).sum(),
                         argnums=tuple(range(6))))(*args)
    w = jax.jit(jax.grad(lambda *a: (ref.recurrence(*a) * t).sum(),
                         argnums=tuple(range(6))))(*args)
    for name, a, b in zip("x dt A B C D".split(), g, w):
        assert rel(a, b) < 5e-5, name


def test_one_group_runs_the_code_it_ran_before_groups():
    """B and C of three axes (one group under every head:
    models/granite_hybrid.py's call) go straight to the chunked
    computation, not through the map over groups: bit for bit what that
    gives; and groups that all carry the same B and C give one group's
    result."""
    from paddlebox_tpu.ops import ssd
    (x, dt, A, Bm, Cm, D), _t = scan_inputs(512, 1)
    one = jax.jit(lambda *a: ssd_scan(*a, chunk=128))(
        x, dt, A, Bm[:, :, 0], Cm[:, :, 0], D)
    plain = jax.jit(lambda *a: ssd._chunked(*a, 128))(
        x, dt, A, Bm[:, :, 0], Cm[:, :, 0], D)
    np.testing.assert_array_equal(one, plain)
    same = jax.jit(lambda *a: ssd_scan(*a, chunk=128))(
        x, dt, A, jnp.repeat(Bm, 2, axis=2), jnp.repeat(Cm, 2, axis=2), D)
    np.testing.assert_allclose(same, one, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        ssd_scan(x, dt, A, jnp.repeat(Bm, 3, axis=2),
                 jnp.repeat(Cm, 3, axis=2), D, 128)


# ------------------------------------------------ (d) the ungated expert
def routed_setup(bias_at):
    cfg, p, _u = whole_layer("E")
    cfg = dict(cfg, n_routed_experts=2, expert_offset=3)
    p = dict(p, e_up=p["e_up"][3:5], e_down=p["e_down"][3:5],
             router_b=jnp.zeros(8).at[jnp.asarray(bias_at)].set(10.0))
    x = jax.random.normal(jax.random.PRNGKey(11), (B * S, 64))
    z = jax.random.normal(jax.random.PRNGKey(12), (B * S, 32))
    return cfg, p, x, z


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bias_at, held_pairs", [
    ([3, 4, 5], 2 * B * S),         # every token picks both held experts
    ([3, 0, 1], B * S),             # every token picks held expert 3 alone
    ([0, 1, 2], 0),                 # nothing is routed here
], ids=["both-held", "one-held-expert", "none-held"])
def test_relu2_experts_match_a_loop_over_experts(bias_at, held_pairs, dtype,
                                                 tol, monkeypatch):
    """No gate handed: relu(z W1)^2 W2, values and the gradients of the
    input, the weights and both matrices against every held expert on
    every token, weighted, in float32; the pairs each held expert got; no
    token dropped when all of them come here (a second chunk runs: the
    chunks are one loop with its own backward pass). In float32, and with
    the operands in bfloat16 as the trainer hands them (a second live
    chunk's gradients are added in the leaves' dtype)."""
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
    cfg, p, x, z = routed_setup(bias_at)
    experts, weights = route(x, p["router_w"], p["router_b"], 3, 5.0)
    w_all, _ = ref.router(cfg, p, x)
    mats = (p["e_up"].astype(dtype), p["e_down"].astype(dtype))
    y, sizes, _ = jax.jit(routed_experts, static_argnums=(6, 7))(
        z.astype(dtype), experts, weights, None, *mats, 3, 8)
    np.testing.assert_array_equal(sizes, ref.pairs_by_expert(cfg, p, x))
    assert int(sizes.sum()) == held_pairs
    assert chunk_rows(B * S, 3, 2, 8) < 2 * B * S     # so: two chunks
    assert rel(y, ref.routed_part(cfg, p, z, w_all)) < tol or not held_pairs
    if not held_pairs:
        assert not np.any(y)
        return
    got = jax.jit(jax.grad(lambda z, w, up, down: (routed_experts(
        z, experts, w, None, up, down, 3, 8)[0] ** 2).sum(),
        argnums=(0, 1, 2, 3)))(z.astype(dtype), weights, *mats)
    assert [g.dtype for g in got] == [dtype, jnp.float32, dtype, dtype]

    def plain(z, w, up, down):      # the chosen weights put at their experts
        dense = jnp.zeros((B * S, 8)).at[
            jnp.arange(B * S)[:, None], experts].set(w)
        return (ref.routed_part(cfg, dict(p, e_up=up, e_down=down), z,
                                dense) ** 2).sum()
    want = jax.grad(plain, argnums=(0, 1, 2, 3))(z, weights, p["e_up"],
                                                 p["e_down"])
    held = (experts >= 3) & (experts < 5)       # a weight of an absent
    assert not np.any(np.where(held, 0.0, got[1]))  # expert moves nothing
    for name, a, b in zip(("z", "weights", "e_up", "e_down"), got, want):
        b = np.where(held, b, 0.0) if name == "weights" else b
        assert rel(a, b) < tol, name


@pytest.mark.parametrize("bias_at, held_pairs", [
    ([3, 4, 5], 2 * B * S), ([3, 0, 1], B * S)],
    ids=["two-live-chunks", "one-live-chunk"])
def test_a_gate_makes_the_expert_a_swiglu(bias_at, held_pairs, monkeypatch):
    """The gate decides the expert's form: handed one, the path is
    models/afmoe.py's (silu(x Wg) * (x Wu)) Wd as tests/test_afmoe.py holds
    it, whatever the ungated path does: values, and the gradients of the
    input and the three matrices against a loop over experts."""
    from paddlebox_tpu.ops import routed_experts as module
    monkeypatch.setattr(module, "TILING", (32, 64, 32))
    _cfg, p, x, z = routed_setup(bias_at)
    gate = jax.random.normal(jax.random.PRNGKey(13), p["e_up"].shape) / 6.0
    experts, weights = route(x, p["router_w"], p["router_b"], 3, 5.0)
    y, sizes, _ = routed_experts(z, experts, weights, gate, p["e_up"],
                                 p["e_down"], 3, 8)
    def plain(z, mats):
        gate, up, down = mats
        return sum(weights[:, c:c + 1] * jnp.where(
            experts[:, c:c + 1] == 3 + e,
            ref.mm(jax.nn.silu(ref.mm(z, gate[e])) * ref.mm(z, up[e]),
                   down[e]), 0.0) for c in range(3) for e in range(2))
    mats = (gate, p["e_up"], p["e_down"])
    np.testing.assert_allclose(y, plain(z, mats), rtol=2e-5, atol=2e-5)
    assert int(sizes.sum()) == held_pairs
    got = jax.grad(lambda z, mats: (routed_experts(
        z, experts, weights, *mats, 3, 8)[0] ** 2).sum(), argnums=(0, 1))(
            z, mats)
    want = jax.grad(lambda z, mats: (plain(z, mats) ** 2).sum(),
                    argnums=(0, 1))(z, mats)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 2e-5


def test_tiles_cut_a_width_into_whole_tiles():
    from paddlebox_tpu.ops.routed_experts import _tiling
    # trinity-mini's products as before; 2,688 columns in three tiles of 896
    assert _tiling(16384, 2048, 1024) == (256, 1024, 1024)
    assert _tiling(16384, 1024, 2048) == (256, 1024, 1024)
    assert _tiling(11264, 1024, 2688) == (256, 1024, 896)
    assert _tiling(11264, 2688, 1024) == (256, 896, 1024)
    assert _tiling(64, 32, 48) == (64, 32, 48)


# ------------------------------------------- (e) the cell's size and order
@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-super.json")) as f:
        return json.load(f)


def test_layer_order_is_the_first_eleven_published_layers(cell):
    """Layers 0-10 of the published pattern, in the source's own order: 5
    state-space, 5 LatentMoE, 1 attention (40 : 40 : 8 of 88); a layer is
    ONE part under ONE norm."""
    assert cell["hybrid_override_pattern"] == "MEMEMEM*EME" == cell[
        "hybrid_override_pattern_published"][:11]
    published = cell["hybrid_override_pattern_published"]
    assert (len(published), published.count("M"), published.count("E"),
            published.count("*")) == (88, 40, 40, 8)
    shapes = build(cell).shapes()
    leaves = {"M": {"in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                    "gnorm", "out_proj"},
              "*": {"wq", "wk", "wv", "wo"},
              "E": {"router_w", "router_b", "fc1", "fc2", "e_up", "e_down",
                    "s_up", "s_down"}}
    for i, kind in enumerate("MEMEMEM*EME"):
        has = {k.split(".")[1] for k in shapes if k.startswith("l%d." % i)}
        assert has == leaves[kind] | {"norm"}, i
    assert not any(k.startswith("l11.") for k in shapes)
    with pytest.raises(ValueError):
        build(dict(cell, hybrid_override_pattern="MEMEMEM-EME"))
    with pytest.raises(ValueError):     # heads 12-19 straddle two kv heads
        build(dict(cell, attention_head_offset=12))


def test_published_widths_hold_694_42_million_parameters(cell):
    """The configuration file at its published widths and the chip's
    share of the counts, from the shapes, nothing allocated: a state-space
    layer 27,413,088, the attention layer 9,441,280, a LatentMoE layer
    21,500,416 + 16 x 5,505,024 = 109,580,800; the final norm, w_out and
    b_out 8,193: 694,418,913 (ISSUE 41 counts 4,097 of the last:
    694,414,817). Counts in thousands and a rest: a literal of ten
    million marks a scale test (boxlint BX951)."""
    shapes = build(cell).shapes()
    per_layer = [sum(int(np.prod(v)) for k, v in shapes.items()
                     if k.startswith("l%d." % i)) for i in range(11)]
    mamba = (4096 * (2048 + 2560 + 32) + 4 * 2560 + 2560 + 3 * 32 + 2048
             + 2048 * 4096 + 4096)
    attention = 2 * 4096 * 1024 + 2 * 4096 * 128 + 4096
    expert = 2 * 1024 * 2688
    moe = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 1344 + 4096
           + 16 * expert)
    assert [divmod(n, 1000) for n in (mamba, attention, moe, expert)] == [
        (27_413, 88), (9_441, 280), (109_580, 800), (5_505, 24)]
    kinds = {"M": mamba, "*": attention, "E": moe}
    assert per_layer == [kinds[k] for k in "MEMEMEM*EME"]
    total = sum(int(np.prod(v)) for v in shapes.values())
    assert total == 5 * mamba + attention + 5 * moe + 2 * 4096 + 1
    assert divmod(total, 1000) == (694_418, 913)
    assert cell["dense_parameters_held"] == total
    mod = config_module()
    assert mod._held(cell) == total
    assert {k: v[0] for k, v in mod.param_init(cell).items()} == shapes
    # the widths are the published ones
    assert shapes["l1.e_up"] == (16, 1024, 2688)
    assert shapes["l1.router_w"] == (4096, 512)
    assert shapes["l0.in_proj"] == (4096, 2048 + 2560 + 32)
    assert shapes["l7.wk"] == (4096, 128)
    assert cell["moe_shared_expert_intermediate_size"] == 5376 == 4 * shapes[
        "l1.s_up"][1]
    # 16 chunks of 11,264 pairs a LatentMoE layer; 320 chunks of the scan
    assert chunk_rows(8192, 22, 16, 512) == 11264 == 180224 // 16
    assert 5 * chunks_scanned(2, 4096, cell["chunk_size"]) == 320
