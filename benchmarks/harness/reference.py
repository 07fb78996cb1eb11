"""The plain reference of the timed step, and the comparison that decides
`correct`. Imports nothing of the program and takes nothing it has made.

One training step as the configuration states it, for one-valued slots:
pull (show, click, embed_w, embedx) -> CVM columns -> the configuration's
forward -> mean BCE -> gradients -> adam on the dense leaves -> the in-table
adagrad on the touched rows (show/click counters, embed_w, lazily created
embedx with one shared g2sum). float32, matmuls at the highest precision.

The same code computes the control (matmul operands rounded to a lower
type) and the planted faults (half of the batch left out; a state left
unchanged), put in the program's place: follow() returns what the harness
reads of the program after its check pass, in the same shape.

What it holds on the device (benchmarks/README.md, "What the reference
needs"): _step donates the weights, adam's moments and the row columns,
so they are updated in place and only the gradients are extra: 16 B a
dense parameter, and one device copy of the touched rows (their starting
embed_w and embedx stay on the host).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=_HI)


def round_e4m3(a):
    """Round to the nearest float8_e4m3fn value (4 exponent bits, 3 of
    mantissa, largest 448, subnormals down to 2**-9), worked in float32
    arithmetic: a chip's compiler need not honour a cast to a type the
    chip does not have (the v5e's did not, PERF.md section 2). Straight
    through: the value is rounded and the gradient passes as if it were
    not, as a float8 training path has it (a plain jnp.round has the
    derivative nought and trains nothing)."""
    c = jnp.clip(a, -448.0, 448.0)
    _, e = jnp.frexp(c)
    step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(F32))
    return a + jax.lax.stop_gradient(jnp.round(c / step) * step - a)


def mm_control(x, w):
    """The control's matmul: operands rounded to float8_e4m3, the step
    below the stated bfloat16, products summed in float32."""
    return jnp.matmul(round_e4m3(x), round_e4m3(w), precision=_HI)


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_params(param_init: Dict, seed: int) -> Dict:
    """The dense weights, made on the device in one jitted call from the
    seed; handed to the program and to the reference alike. A leaf is
    (shape, std) for a normal draw, or (shape, size, "sign") for +-size
    with the sign drawn: a leaf of a few values that sets how much of
    the model each part is gets the same sizes from every seed."""
    names = sorted(param_init)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for n, k in zip(names, keys):
            shape, size, *how = param_init[n]
            draw = jax.random.normal(k, shape, F32)
            out[n] = (jnp.where(draw < 0, -1.0, 1.0) if how == ["sign"]
                      else draw) * size
        return out
    return make(seed_key(seed))


def _bce(logits, y):
    # softplus(l) - l*y: smooth at l == 0, where a fresh table starts
    return jnp.logaddexp(logits, 0.0) - logits * y


@functools.partial(jax.jit, static_argnames=("fwd", "mm", "num_rows", "sp"),
                   donate_argnames=("params", "adam", "table"))
def _step(fwd, mm, num_rows, sp, params, adam, table, idx, labels, dense,
          keep):
    sp = dict(sp)
    B, S = idx.shape
    show, click = table["show"][idx], table["click"][idx]
    w, x = table["w"][idx], table["x"][idx]
    y = labels.astype(F32)
    kf = keep.astype(F32)

    def loss_fn(params, w, x):
        log_show = jnp.log(show + 1.0)
        pooled = jnp.concatenate(
            [log_show[..., None], (jnp.log(click + 1.0) - log_show)[..., None],
             w[..., None], x], axis=-1)
        logits = fwd(params, pooled, dense, mm)
        return (_bce(logits, y) * kf).sum() / jnp.maximum(kf.sum(), 1.0)

    loss, (gp, gw, gx) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        params, w, x)

    # dense leaves: adam as optax.adam(lr) states it
    t = adam["t"] + 1.0
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                      adam["mu"], gp)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      adam["nu"], gp)
    new_params = jax.tree.map(
        lambda p, m, v: p - sp["dense_lr"] * (m / (1 - ADAM_B1 ** t))
        / (jnp.sqrt(v / (1 - ADAM_B2 ** t)) + ADAM_EPS), params, mu, nu)

    # touched rows: merge the occurrences of a key, then the in-table rule
    flat = idx.reshape(-1)
    occ = jnp.repeat(kf, S)

    def merge(v):
        return jax.ops.segment_sum(v, flat, num_segments=num_rows)
    g_show = merge(occ)
    g_click = merge(occ * jnp.repeat(y, S))
    g_w = merge(gw.reshape(-1) * occ)
    g_x = merge(gx.reshape(B * S, -1) * occ[:, None])
    active = g_show > 0
    scale = jnp.where(active, g_show, 1.0)
    new_show = table["show"] + g_show
    new_click = table["click"] + g_click
    ig2, bound = sp["initial_g2sum"], sp["bound"]

    def adagrad(wv, g2, g, lr):
        scaled = g / scale.reshape((-1,) + (1,) * (g.ndim - 1))
        ratio = lr * jnp.sqrt(ig2 / (ig2 + g2))
        ratio = ratio.reshape(scale.shape + (1,) * (g.ndim - 1))
        # descent, as adagrad is published; the program's rows were found
        # to move the other way (PERF.md, Open question 0)
        neww = jnp.clip(wv - scaled * ratio, -bound, bound)
        sq = scaled * scaled
        return neww, g2 + (sq if g.ndim == 1 else sq.mean(axis=-1))

    nw, nw_g2 = adagrad(table["w"], table["w_g2"], g_w, sp["sparse_lr"])
    nx, nx_g2 = adagrad(table["x"], table["x_g2"], g_x, sp["sparse_lr"])
    score = (sp["nonclk_coeff"] * (new_show - new_click)
             + sp["clk_coeff"] * new_click)
    has = table["mf"] & active
    create = (~table["mf"]) & (score >= sp["mf_create_thresholds"]) & active
    new_table = {
        "show": new_show, "click": new_click,
        "w": jnp.where(active, nw, table["w"]),
        "w_g2": jnp.where(active, nw_g2, table["w_g2"]),
        # a created embedding starts at zero (mf_initial_range 0) and its
        # first gradient is dropped, as the in-table rule has it
        "x": jnp.where(has[:, None], nx, table["x"]),
        "x_g2": jnp.where(has, nx_g2, table["x_g2"]),
        "mf": table["mf"] | create,
    }
    return loss, new_params, {"t": t, "mu": mu, "nu": nu}, new_table


def sparse_settings(cfg: dict) -> tuple:
    return tuple(sorted({
        "dense_lr": float(cfg["dense_lr"]),
        "sparse_lr": float(cfg["sparse_learning_rate"]),
        "initial_g2sum": float(cfg.get("sparse_initial_g2sum", 3.0)),
        "bound": float(cfg.get("sparse_weight_bound", 10.0)),
        "nonclk_coeff": float(cfg.get("nonclk_coeff", 0.1)),
        "clk_coeff": float(cfg.get("clk_coeff", 1.0)),
        "mf_create_thresholds": float(cfg["mf_create_thresholds"]),
    }.items()))


def check_rows(rows: np.ndarray) -> np.ndarray:
    """The distinct global rows of the check pass, sorted: the order of
    every per-row array the comparison reads."""
    return np.unique(rows)


def first_only(rows: np.ndarray, steps: int) -> np.ndarray:
    """Over check_rows(rows): the rows that the first of ``steps`` steps
    touches and no later one. Their change is the first step's push and
    nothing else: the first gradient, before any update has fed back."""
    B = rows.shape[0] // steps
    uniq = np.unique(rows)
    return np.isin(uniq, rows[:B]) & ~np.isin(uniq, rows[B:])


def follow(cfg: dict, cfg_mod, params0: Dict, rows: np.ndarray,
           labels: np.ndarray, dense: np.ndarray, steps: int, table0: Dict,
           mm=mm_f32, keep_half: bool = False,
           unchanged: bool = False) -> dict:
    """Follow the check pass: ``steps`` steps from ``params0`` and the rows
    of ``table0`` (columns over all occupied rows, traffic.Traffic.table).
    rows [steps*B, S] global row indices. Returns what the harness reads
    of the program after its check pass: each step's loss; per leaf the
    norm of adam's first moment (the gradients as the optimizer got them)
    and of the change; per check row the change of embed_w and embedx,
    the g2sums, show and click, and whether only the first step touches
    it; the dense leaves' sizes. ``keep_half`` and ``unchanged`` plant the
    faults: half of every batch left out, the mean taken over the rest;
    a step that returns its state as it got it."""
    B = rows.shape[0] // steps
    uniq, inv = np.unique(rows, return_inverse=True)
    idx = inv.reshape(rows.shape).astype(np.int32)
    # tables padded to the most rows the steps could touch, which is never
    # more than the vocabulary (one table shared by thousands of slots):
    # both are the configuration's, so the shapes, and the compiled step,
    # are the same for every seed
    R = min(int(rows.size), int(cfg["occupied_rows"]))
    D, n = int(cfg["embedx_dim"]), uniq.size

    def col(name, shape, dtype=F32):
        out = np.zeros((R,) + shape, dtype)
        if name in table0:
            out[:n] = table0[name][uniq]
        return out
    # the rows' starting embed_w and embedx stay on the host: the device
    # holds one copy of the rows, which _step updates in place
    w0, x0 = col("w", ()), col("x", (D,))
    table = {"show": col("show", ()), "click": col("click", ()),
             "w": w0, "w_g2": col("w_g2", ()),
             "x": x0, "x_g2": col("x_g2", ()),
             "mf": col("mf", (), bool)}
    # jnp.array copies: _step donates what it is given, and neither the
    # caller's params0 nor w0/x0 may go with it
    table = {k: jnp.array(v) for k, v in table.items()}
    params = jax.tree.map(lambda a: jnp.array(a, F32), params0)
    adam = {"t": jnp.zeros((), F32),
            "mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params)}
    keep = np.ones(B, bool)
    if keep_half:
        keep[B // 2:] = False
    fwd = functools.partial(cfg_mod.forward, _Frozen(cfg))
    sp = sparse_settings(cfg)
    losses = []
    for s in range(steps):
        sl = slice(s * B, (s + 1) * B)
        state = (params, adam, table)
        if unchanged:       # the fault keeps its state: the step gets copies
            state = jax.tree.map(jnp.copy, state)
        loss, *new_state = _step(
            fwd, mm, R, sp, *state, jnp.asarray(idx[sl]),
            jnp.asarray(labels[sl]), jnp.asarray(dense[sl], F32),
            jnp.asarray(keep))
        losses.append(float(loss))
        if not unchanged:
            params, adam, table = new_state
        del state, new_state    # the fault's unused new state is freed

    def norm(a):
        return float(jnp.linalg.norm(a))

    def rows_of(a):
        return np.asarray(a)[:n]
    return {"loss": losses,
            "first_only": first_only(rows, steps),
            "sizes": {k: int(np.size(v)) for k, v in params0.items()},
            "mu": {k: norm(v) for k, v in adam["mu"].items()},
            "change": {k: norm(params[k] - jnp.asarray(params0[k], F32))
                       for k in params},
            "w_delta": rows_of(table["w"]) - w0[:n],
            "x_delta": rows_of(table["x"]) - x0[:n],
            "w_g2": rows_of(table["w_g2"]), "x_g2": rows_of(table["x_g2"]),
            "show": rows_of(table["show"]), "click": rows_of(table["click"])}


class _Frozen(dict):
    """A configuration dict that can be a static jit argument."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


# ------------------------------------------------------------- comparison

GAPS = ("loss_gap", "grad_gap", "small_leaf_grad_gap", "change_gap",
        "sparse_axis_gap", "first_push_gap")
SMALL_LEAF = 32     # values; a dense leaf of fewer is a small leaf


def cosine(a: np.ndarray, b: np.ndarray) -> float:
    a, b = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
    d = np.linalg.norm(a) * np.linalg.norm(b)
    return float(a @ b / d) if d > 0 else 0.0


def _norm(a: np.ndarray) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64)))


def leaf_gap_table(prog: Dict[str, float], ref: Dict[str, float],
                   leaves=None) -> Dict[str, float]:
    """Per leaf |prog - ref| / max(ref of that leaf, ref of the median
    leaf): the gap between the two norms, not the norm of the
    difference."""
    leaves = sorted(leaves if leaves is not None else ref)
    med = float(np.median([ref[k] for k in leaves])) if leaves else 0.0
    out = {}
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def widest(table: Dict[str, float], leaves=None):
    """(gap, leaf) of the widest gap of a leaf_gap_table, over ``leaves``
    of it or all; (0.0, "") over none."""
    worst, at = 0.0, ""
    for k in sorted(table if leaves is None else leaves):
        if table[k] >= worst:
            worst, at = table[k], k
    return worst, at


def moving_leaves(g: Dict[str, float]):
    """Leaves whose gradient in the reference is not nought to rounding:
    at least a thousandth of the median non-zero leaf's."""
    nonzero = [v for v in g.values() if v > 0]
    med = float(np.median(nonzero)) if nonzero else 0.0
    return [k for k, v in g.items() if v > 0 and v >= 1e-3 * med]


def leaf_norms(got: dict):
    """(gradient, change) norm per leaf of what follow() or the harness
    read: the dense leaves from adam's first moment and the weights, the
    two sparse leaves from the rows. sqrt(sum of a leaf's g2sum) is the
    norm of the scaled gradients the in-table optimizer got over the
    steps (the table's g2sum starts at nought)."""
    grad = dict(got["mu"], **{
        "sparse.embed_w": float(np.sqrt(np.sum(got["w_g2"], dtype=np.float64))),
        "sparse.embedx": float(np.sqrt(np.sum(got["x_g2"], dtype=np.float64)))})
    change = dict(got["change"], **{"sparse.embed_w": _norm(got["w_delta"]),
                                    "sparse.embedx": _norm(got["x_delta"])})
    return grad, change


def _axis_gap(prog: np.ndarray, ref: np.ndarray, cos: float):
    """1 - |cosine| of one sparse leaf's change; None where the reference
    does not move the leaf and the program does not either, 1.0 where the
    program alone moves it."""
    if np.any(ref):
        return 1.0 - abs(cos)
    return 1.0 if np.any(prog) else None


def _relative_gap(p: float, r: float) -> float:
    """|p - r| / |r|; a reference of exactly nought (a saturated loss) has
    no relative gap: nought where the program agrees, else infinite."""
    if r:
        return abs(p - r) / abs(r)
    return 0.0 if p == r else float("inf")


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Every gap that compare() takes the widest of, for a look by hand:
    per step the loss's, per moving leaf the gradient's and the change's
    norm."""
    pg, pc = leaf_norms(prog)
    rg, rc = leaf_norms(ref)
    moving = moving_leaves(rg)
    return {"loss": [_relative_gap(p, r)
                     for p, r in zip(prog["loss"], ref["loss"])],
            "grad": leaf_gap_table(pg, rg, moving),
            "change": leaf_gap_table(pc, rc, moving)}


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers a configuration may hold to a limit (its file's
    `limits`; PERF.md section 2 gives the readings), each a relative gap
    of the check pass against the reference:
    loss_gap        the widest over the steps' losses
    grad_gap        the gradients' norm, by the worst leaf of SMALL_LEAF
                    values or more
    small_leaf_grad_gap  the same by the worst leaf of fewer values: a
                    gradient of one or three values is a batch's signed
                    sum, nothing averages its rounding out, and its gap
                    swings several times wider than a matrix's; its limit
                    is its own (such a leaf's change is in change_gap)
    change_gap      the change's norm after the steps, by the worst leaf
    sparse_axis_gap 1 - |cosine| of the rows' change, the wider of embed_w
                    and embedx: the axis, not the sign (the signed cosines
                    go beside it as `_cos_*`). A leaf that the reference
                    leaves where it was (the model does not read it) has
                    no axis and is left out; a program that moves such a
                    leaf reads 1.0.
    first_push_gap  the same over the rows that only the first step
                    touches: the axis of the first gradient per row, which
                    no update has fed back into. The steps' losses and the
                    leaves' norms swing from seed to seed with what the
                    first updates amplify; this one reads rounding alone
                    and is what separates the stated precision from the
                    one below it (PERF.md section 2)."""
    gaps = leaf_gaps(prog, ref)
    small = [k for k in gaps["grad"]
             if ref["sizes"].get(k, SMALL_LEAF) < SMALL_LEAF]
    grad, grad_at = widest(gaps["grad"], set(gaps["grad"]) - set(small))
    small_grad, small_at = widest(gaps["grad"], small)
    change, change_at = widest(gaps["change"])
    cos_w = cosine(prog["w_delta"], ref["w_delta"])
    cos_x = cosine(prog["x_delta"], ref["x_delta"])
    axis = [_axis_gap(prog[k], ref[k], c)
            for k, c in (("w_delta", cos_w), ("x_delta", cos_x))]
    one = ref["first_only"]
    first = [_axis_gap(prog[k][one], ref[k][one],
                       cosine(prog[k][one], ref[k][one]))
             for k in ("w_delta", "x_delta")]
    return {"loss_gap": float(max(gaps["loss"])), "grad_gap": float(grad),
            "small_leaf_grad_gap": float(small_grad),
            "change_gap": float(change),
            "sparse_axis_gap": max([g for g in axis if g is not None],
                                   default=0.0),
            "first_push_gap": max([g for g in first if g is not None],
                                  default=0.0),
            "_grad_leaf": grad_at, "_small_grad_leaf": small_at,
            "_change_leaf": change_at,
            "_cos_embed_w": cos_w, "_cos_embedx": cos_x}
