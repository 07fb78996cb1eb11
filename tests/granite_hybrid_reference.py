"""Plain float32 reference of the Granite 4.0-H behaviour-sequence tower,
written from the layer equations (ISSUE 37; models/granite_hybrid.py's
docstring states the same) and not from the program: the state-space layer
as the SEQUENTIAL recurrence over positions (a lax.scan of h_t, one [P, N]
state a head), the convolution as four shifted adds, attention as the whole
[S, S] scores under a mask with a naive softmax. Nothing chunked, no
kernel, no checkpoint, nothing from paddlebox_tpu. Test sizes only.

cfg keys are the configuration file's (benchmarks/configs/
granite-4-h-micro.json): hidden_size, intermediate_size, layer_types,
num_attention_heads, num_key_value_heads, head_dim, attention_multiplier,
mamba_n_heads, mamba_d_head, mamba_d_state, mamba_d_conv,
embedding_multiplier, residual_multiplier, rms_norm_eps, head_scale.
Parameters: the flat dict models/granite_hybrid.py documents.
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_params(params, i):
    pre = "l%d." % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def conv(x, w, b):
    """out_t = b + sum_k w[k] * x_{t-3+k}, zeros before position 0; x
    [B, S, C], w [K, C]: the shifted adds, the oldest tap first."""
    K, S = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x) + b
    for k in range(K):
        back = K - 1 - k            # tap k reads ``back`` positions ago
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :S - back]], axis=1)
        out = out + w[k] * shifted
    return out


def recurrence(x, dt, A, Bm, Cm, D):
    """x [B, S, H, P], dt [B, S, H], A and D [H], Bm and Cm [B, S, N]:
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t + D x_t,
    position by position from h = 0."""
    Bsz, _, H, P = x.shape
    N = Bm.shape[-1]

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.sum(h * c_t[:, None, None, :], axis=-1)
        return h, y + D[:, None] * x_t
    _, y = jax.lax.scan(
        step, jnp.zeros((Bsz, H, P, N), jnp.float32),
        tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, Bm, Cm)))
    return jnp.swapaxes(y, 0, 1)


def mamba(cfg, p, x):
    B, S, _ = x.shape
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = H * P
    proj = mm(x, p["in_proj"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * N],
                  proj[..., 2 * inner + 2 * N:])
    xbc = jax.nn.silu(conv(xbc, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = (xbc[..., :inner], xbc[..., inner:inner + N],
                  xbc[..., inner + N:])
    y = recurrence(xs.reshape(B, S, H, P),
                   jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   Bm, Cm, p["D"]).reshape(B, S, inner)
    return mm(norm(y * jax.nn.silu(z), p["gnorm"], cfg["rms_norm_eps"]),
              p["out_proj"])


def attention(cfg, p, x):
    B, S, _ = x.shape
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm(x, p["wq"]).reshape(B, S, nq, D)
    k = jnp.repeat(mm(x, p["wk"]).reshape(B, S, nkv, D), nq // nkv, axis=2)
    v = jnp.repeat(mm(x, p["wv"]).reshape(B, S, nkv, D), nq // nkv, axis=2)
    scores = (jnp.einsum("bihd,bjhd->bhij", q, k, precision=HI)
              * cfg["attention_multiplier"])
    see = np.arange(S)[None, :] <= np.arange(S)[:, None]
    weights = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", weights, v, precision=HI)
    return mm(out.reshape(B, S, nq * D), p["wo"])


def mlp(cfg, p, x):
    F = cfg["intermediate_size"]
    both = mm(x, p["mlp_in"])
    return mm(jax.nn.silu(both[..., :F]) * both[..., F:], p["mlp_out"])


def layer(cfg, i, p, h):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mix = mamba if cfg["layer_types"][i] == "mamba" else attention
    a = h + r * mix(cfg, p, norm(h, p["norm1"], eps))
    return a + r * mlp(cfg, p, norm(a, p["norm2"], eps))


def forward(cfg, params, pooled):
    """pooled [B, S, 3 + hidden_size] -> logits [B]."""
    h = pooled[..., 3:] * cfg["embedding_multiplier"]
    for i in range(len(cfg["layer_types"])):
        h = layer(cfg, i, layer_params(params, i), h)
    mean = norm(h, params["norm_f"], cfg["rms_norm_eps"]).mean(axis=1)
    return cfg["head_scale"] * mm(mean, params["w_out"]) + params["b_out"]
