"""Pallas in-table adagrad kernel vs the XLA apply_push oracle (interpret
mode on the CPU mesh; the compiled kernel runs against the same oracle on
the chip in chip_smoke.py's kernel leg)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.config.configs import SparseOptimizerConfig
from paddlebox_tpu.embedding import accessor as acc
from paddlebox_tpu.embedding.accessor import PushLayout, ValueLayout
from paddlebox_tpu.embedding.optimizers import apply_push
from paddlebox_tpu.embedding.pallas_push import pallas_apply_push

D = 8


def conf(create_thres=1e9):
    return SparseOptimizerConfig(mf_create_thresholds=create_thres,
                                 mf_initial_range=1e-3,
                                 feature_learning_rate=0.1,
                                 mf_learning_rate=0.05)


def _rows_and_grads(n, seed=0, with_mf=True):
    layout = ValueLayout(embedx_dim=D, optimizer="adagrad")
    push = PushLayout(D)
    rng = np.random.RandomState(seed)
    rows = layout.new_rows(n, rng, conf())
    rows[:, acc.SLOT] = rng.randint(0, 5, n)
    rows[:, acc.SHOW] = rng.randint(1, 30, n)
    rows[:, acc.CLICK] = rng.randint(0, 5, n)
    rows[:, acc.UNSEEN_DAYS] = rng.randint(0, 3, n)
    if with_mf:
        rows[:, acc.MF_SIZE] = D
        rows[:, layout.embedx_w:layout.embedx_w + D] = (
            rng.randn(n, D).astype(np.float32) * 0.01)
        rows[:, layout.embedx_state] = rng.rand(n)
    grads = np.zeros((n, push.width), np.float32)
    grads[:, push.SLOT] = rows[:, acc.SLOT]
    grads[:, push.SHOW] = rng.randint(0, 4, n)  # zero-show rows included
    grads[:, push.CLICK] = np.minimum(grads[:, push.SHOW],
                                      rng.randint(0, 2, n))
    grads[:, push.EMBED_G] = rng.randn(n).astype(np.float32) * 0.2
    grads[:, push.embedx_g:push.embedx_g + D] = (
        rng.randn(n, D).astype(np.float32) * 0.2)
    return layout, rows.astype(np.float32), grads


def test_pallas_push_matches_xla_no_create():
    """mf already exists everywhere and creation threshold is huge, so the
    PRNG never matters — the update must be bit-comparable to apply_push."""
    layout, rows, grads = _rows_and_grads(300, with_mf=True)
    c = conf(create_thres=1e9)
    want = np.asarray(apply_push(jnp.asarray(rows), jnp.asarray(grads),
                                 jax.random.PRNGKey(0), layout, c))
    got = np.asarray(pallas_apply_push(jnp.asarray(rows), jnp.asarray(grads),
                                       7, layout, c, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pallas_push_lazy_create_range():
    """Fresh rows past the score threshold get embedx drawn in
    [0, mf_initial_range) and mf_size set; inactive rows untouched."""
    layout, rows, grads = _rows_and_grads(300, seed=3, with_mf=False)
    c = conf(create_thres=0.0)
    got = np.asarray(pallas_apply_push(jnp.asarray(rows), jnp.asarray(grads),
                                       11, layout, c, interpret=True))
    push = PushLayout(D)
    active = grads[:, push.SHOW] > 0
    xw = layout.embedx_w
    created = got[active]
    assert (created[:, acc.MF_SIZE] == D).all()
    x = created[:, xw:xw + D]
    assert (x >= 0).all() and (x < c.mf_initial_range).all()
    # at least some spread (PRNG actually ran)
    assert np.unique(np.round(x / c.mf_initial_range, 4)).size > 10
    np.testing.assert_allclose(got[~active], rows[~active], rtol=1e-6)


def test_pallas_push_rejects_unsupported_layout():
    layout = ValueLayout(embedx_dim=D, optimizer="adam")
    with pytest.raises(ValueError):
        pallas_apply_push(jnp.zeros((8, layout.width)),
                          jnp.zeros((8, PushLayout(D).width)), 0, layout,
                          conf(), interpret=True)


def test_pallas_create_randoms_content_addressed():
    """The same slab row must draw the same creation randoms regardless of
    its position in the batch (row_ids keying, not positional)."""
    from paddlebox_tpu.embedding.pallas_push import pallas_apply_push
    layout, rows, grads = _rows_and_grads(32, seed=9, with_mf=False)
    c = conf(create_thres=0.0)
    ids = np.arange(32, dtype=np.int32)
    fwd = pallas_apply_push(jnp.asarray(rows), jnp.asarray(grads), 7, layout,
                            c, interpret=True, row_ids=jnp.asarray(ids))
    perm = np.random.RandomState(0).permutation(32)
    rev = pallas_apply_push(jnp.asarray(rows[perm]), jnp.asarray(grads[perm]),
                            7, layout, c, interpret=True,
                            row_ids=jnp.asarray(ids[perm]))
    np.testing.assert_array_equal(np.asarray(fwd)[perm], np.asarray(rev))


def test_flagged_push_sparse_dedup_roundtrip():
    """End-to-end through push_sparse_dedup with the flag on (interpreted
    pallas on CPU)."""
    from paddlebox_tpu.config import flags
    from paddlebox_tpu.embedding.optimizers import push_sparse_dedup
    layout, rows, grads = _rows_and_grads(64, seed=5, with_mf=True)
    c = conf(create_thres=1e9)
    slab = jnp.asarray(np.vstack([rows, np.zeros((1, layout.width),
                                                 np.float32)]))
    ids = jnp.asarray(np.arange(64, dtype=np.int64))
    flags.set_flag("use_pallas_push", True)
    try:
        # the dispatch site passes no interpret: pallas_interpret()'s rule
        # picks interpret mode on the cpu backend by itself
        out = push_sparse_dedup(slab, ids, jnp.asarray(grads),
                                jax.random.PRNGKey(0), layout, c)
    finally:
        flags.set_flag("use_pallas_push", False)
    want = push_sparse_dedup(slab, ids, jnp.asarray(grads),
                             jax.random.PRNGKey(0), layout, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_interpret_rule_cpu_tpu_or_error(monkeypatch):
    """One rule for both kernels' dispatch: compiled on tpu, interpreted on
    cpu, an ERROR on a backend nobody recognised (never a quiet python-rate
    kernel)."""
    from paddlebox_tpu.embedding import pallas_push as pp
    assert pp.pallas_interpret() is True            # the test platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pp.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="interpreted on 'cpu' only"):
        pp.pallas_interpret()


def test_apply_push_kernel_lowers_for_tpu():
    """Cross-lower the COMPILED kernel for the TPU at production shapes
    (W=17, a 4,096-row update): a BlockSpec the TPU lowering refuses fails
    here, on the CPU, instead of on the chip."""
    layout = ValueLayout(embedx_dim=D, optimizer="adagrad")
    n, c = 4096, conf(create_thres=0.0)

    def push(vals, grads, rid):
        return pallas_apply_push(vals, grads, jnp.int32(7), layout, c,
                                 interpret=False, row_ids=rid)

    exp = jax.export.export(jax.jit(push), platforms=["tpu"])(
        jax.ShapeDtypeStruct((n, layout.width), jnp.float32),
        jax.ShapeDtypeStruct((n, PushLayout(D).width), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    assert "tpu_custom_call" in exp.mlir_module()
