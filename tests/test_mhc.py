"""ops/mhc.py's fused hyper-connection (the four Pallas kernels, in
interpret mode on the CPU) against the XLA form it falls back to and
against a float64 numpy loop over the tokens: the forward values x', u
and z, and the gradients of x, f, phi, alpha and b; shapes the kernels
decline take the XLA form; every operation of the kernels and their
[T, 2n + n^2] glue lies under the scopes mhc / mhc_sinkhorn, on the CPU
and in a compile for a described TPU v5e at xing4-29b-a4b's widths."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddlebox_tpu.obs import device as obs_device
from paddlebox_tpu.ops import mhc
from test_pass_spans import fresh_compiles

HC = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
ARGS = ("x", "phi", "alpha", "bias", "f0")
# (streams, width, tokens): several token blocks each (32 and 256 tokens)
FUSED = [(4, 256, 96), (2, 128, 512)]
# a width off the lanes; a token count no block of 16 or more divides
DECLINED = [(4, 96, 64), (4, 128, 40)]


def draws(n, C, T, seed=0):
    """x [T, n C], phi, alpha, bias as the cell draws them (scales +-0.5,
    biases +-1, phi 1 / sqrt(n C)); the sublayer's W and f0; the loss's
    weights G."""
    M = 2 * n + n * n
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (T, n * C)),
        phi=jax.random.normal(k[1], (n * C, M)) / np.sqrt(n * C),
        alpha=0.5 * jnp.sign(jax.random.normal(k[2], (3,))),
        bias=jnp.sign(jax.random.normal(k[3], (M,))),
        f0=jax.random.normal(k[4], (T, C)),
        W=jax.random.normal(k[5], (C, C)) / np.sqrt(C),
        G=jax.random.normal(k[6], (T, n * C)))


def connect(d, n):
    """(loss, (x', u)), the gradients of ARGS: one hyper-connection around
    F(u) = tanh(u W) + f0, whose gradient in f0 is the one in f."""
    def loss(x, phi, alpha, bias, f0):
        out, u = mhc.hyper_connection(
            x, phi, alpha, bias, lambda u: (jnp.tanh(u @ d["W"]) + f0, u),
            n=n, **HC)
        return (out * d["G"]).sum(), (out, u)
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)),
                                      has_aux=True))(*(d[a] for a in ARGS))


def xla_form(d, n, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(mhc, "fused_block", lambda *a: 0)
        return connect(d, n)


def numpy_loop(d, n, x=None, phi=None, alpha=None, bias=None, f0=None):
    """(loss, x', u, z) in float64, one token at a time."""
    g = {a: np.asarray(d[a] if v is None else v, np.float64) for a, v in
         zip(ARGS + ("W", "G"), (x, phi, alpha, bias, f0, None, None))}
    a, b = g["alpha"], g["bias"]
    T, nC = g["x"].shape
    C = nC // n
    outs, us, zs = [], [], []
    for t in range(T):
        v = g["x"][t]
        z = (v @ g["phi"]) / np.sqrt((v * v).mean() + HC["norm_eps"])
        pre = 1.0 / (1.0 + np.exp(-(a[0] * z[:n] + b[:n])))
        post = 2.0 / (1.0 + np.exp(-(a[1] * z[n:2 * n] + b[n:2 * n])))
        m = np.exp(np.clip(a[2] * z[2 * n:] + b[2 * n:],
                           *HC["clamp"])).reshape(n, n)
        for _ in range(HC["iters"]):
            m = m / (m.sum(1, keepdims=True) + HC["eps"])
            m = m / (m.sum(0, keepdims=True) + HC["eps"])
        xs = v.reshape(n, C)
        u = pre @ xs
        f = np.tanh(u @ g["W"]) + g["f0"][t]
        outs.append((m @ xs + post[:, None] * f[None, :]).reshape(-1))
        us.append(u)
        zs.append(z)
    out = np.stack(outs)
    return (out * g["G"]).sum(), out, np.stack(us), np.stack(zs)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


def fused_z(d, n):
    M = d["bias"].shape[0]
    first = jnp.arange(M) < n
    ab = jnp.stack([jnp.where(first, d["alpha"][0], 0.0),
                    jnp.where(first, d["bias"], 0.0)])
    return jax.jit(lambda x, p, ab: mhc._maps_combine(
        x, p.T, ab, n, HC["norm_eps"])[0])(d["x"], d["phi"], ab)


@pytest.mark.parametrize("n,C,T", FUSED)
def test_fused_forward_matches_xla_form_and_float64(n, C, T, monkeypatch):
    assert mhc.fused_block(T, n, C) and T > mhc.fused_block(T, n, C)
    d = draws(n, C, T)
    (_, (out, u)), _ = connect(d, n)
    (_, (out_x, u_x)), _ = xla_form(d, n, monkeypatch)
    _, out64, u64, z64 = numpy_loop(d, n)
    assert rel(out, out64) < 1e-5 and rel(out, out_x) < 1e-5
    assert rel(u, u64) < 1e-5 and rel(u, u_x) < 1e-5
    z_x = jax.lax.rsqrt((d["x"] ** 2).mean(1, keepdims=True) + 1e-6) * (
        jnp.dot(d["x"], d["phi"], precision=jax.lax.Precision.HIGHEST))
    z = fused_z(d, n)
    assert rel(z, z64) < 1e-5 and rel(z, z_x) < 1e-5


@pytest.mark.parametrize("n,C,T", FUSED)
def test_fused_gradients_match_xla_form_and_float64(n, C, T, monkeypatch):
    """Each gradient against the XLA form's, leaf by leaf, and its
    inner product with a random direction against float64 central
    differences of the numpy loop."""
    d = draws(n, C, T, seed=1)
    _, grads = connect(d, n)
    _, want = xla_form(d, n, monkeypatch)
    rng = np.random.default_rng(3)
    for name, got, xla in zip(ARGS, grads, want):
        assert rel(got, xla) < 1e-5, name
        step = rng.standard_normal(np.shape(d[name]))
        h = 1e-4 * np.abs(np.asarray(d[name])).max()
        at = np.asarray(d[name], np.float64)
        up = numpy_loop(d, n, **{name: at + h * step})[0]
        down = numpy_loop(d, n, **{name: at - h * step})[0]
        slope = (up - down) / (2 * h)
        assert abs(np.vdot(np.asarray(got, np.float64), step) - slope) < (
            1e-5 * max(abs(slope), np.abs(np.asarray(got)).max())), name


@pytest.mark.parametrize("n,C,T", DECLINED)
def test_declined_shapes_take_the_xla_form(n, C, T, monkeypatch):
    assert mhc.fused_block(T, n, C) == 0

    def refuse(*a, **k):
        raise AssertionError("a kernel ran on a declined shape")
    monkeypatch.setattr(mhc, "_maps_combine", refuse)
    monkeypatch.setattr(mhc, "_mix_post_add", refuse)
    d = draws(n, C, T, seed=2)
    (loss, (out, u)), _ = connect(d, n)
    want, out64, u64, _ = numpy_loop(d, n)
    assert rel(out, out64) < 1e-5 and rel(u, u64) < 1e-5


def sublayer_text(n, C, T, sharding=None) -> str:
    """The compiled text of one sublayer's forward and backward as the
    train step holds it: everything under fwd_bwd, the sublayer under
    dense_mlp. Compiled afresh: a cached executable may carry other
    op_names, and a compile for a described TPU cannot be read back."""
    def loss(x, phi, alpha, bias, W):
        def sub(u):
            with jax.named_scope("dense_mlp"):
                return jnp.tanh(u @ W), None
        with jax.named_scope("fwd_bwd"):
            out, _ = mhc.hyper_connection(x, phi, alpha, bias, sub, n=n,
                                          **HC)
        return (out * out).sum()
    M = 2 * n + n * n
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
              for s in ((2, T // 2, n * C), (n * C, M), (3,), (M,), (C, C))]
    with fresh_compiles():
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            *shapes).compile().as_text()


def op_names(text: str):
    """{HLO instruction: its op_name path} where it carries one."""
    out = {}
    for line in text.splitlines():
        m = obs_device._HLO_INSTR.match(line)
        op = obs_device._HLO_OP_NAME.search(line)
        if m and op:
            out[m.group(1)] = op.group(1)
    return out


def test_every_operation_of_the_hyper_connection_lies_under_its_scopes():
    """CPU: the kernels run interpreted (platform_dependent's default
    branch, cond/branch_*), their grid loops inlined as HLO: every
    operation of the traced sublayer carries mhc, mhc_sinkhorn or the
    sublayer's dense_mlp, none fwd_bwd alone, the backward rules'
    kernels included."""
    text = sublayer_text(4, 128, 64)
    scopes = obs_device.scope_map(text)
    names = op_names(text)
    assert "fwd_bwd" not in scopes.values()
    kernels = [k for k, o in names.items() if "/cond/branch" in o]
    assert kernels and {scopes[k] for k in kernels} == {"mhc"}
    assert any("transpose(" in names[k] for k in kernels)   # A^T, B^T
    inside = {scopes[k] for k, o in names.items() if "fwd_bwd" in o}
    assert inside == {"mhc", "mhc_sinkhorn", "dense_mlp"}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe one here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_four_kernels_compile_for_a_v5e_under_mhc(one_chip):
    """xing4-29b-a4b's widths (2 x 4,096 tokens of 4 streams of 3,584)
    for a described v5e: the chip's compiler takes the four kernels'
    token blocks in its fast memory, each is one Mosaic custom call under
    mhc under its own name (what a device trace shows), and nothing of
    the hyper-connection is left under fwd_bwd."""
    text = sublayer_text(4, 3584, 8192, one_chip)
    scopes = obs_device.scope_map(text)
    kernels = [m.group(1) for m in map(obs_device._HLO_INSTR.match,
                                       text.splitlines())
               if m is not None and 'tpu_custom_call"' in m.string]
    assert sorted(k.rsplit(".", 1)[0] for k in kernels) == [
        "mhc_maps", "mhc_maps_bwd", "mhc_mix", "mhc_mix_bwd"]
    assert {scopes[k] for k in kernels} == {"mhc"}
    assert "fwd_bwd" not in scopes.values()
