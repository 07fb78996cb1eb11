"""Plain float32 reference of the AfMoE behaviour-sequence tower, written
from the layer equations (ISSUE 34; models/afmoe.py's docstring states the
same) and not from the program: whole [S, S] scores under a mask, a naive
softmax, every held expert run on every token and masked, no kernel, no
blocking, no checkpoint. Test sizes only.

cfg keys are the configuration file's (benchmarks/configs/
trinity-mini.json): hidden_size, num_attention_heads, num_key_value_heads,
head_dim, sliding_window, layer_types, num_dense_layers,
num_experts_published (the router's outputs), num_experts (held here),
expert_offset, num_experts_per_tok, route_scale, rope_theta, rms_norm_eps,
head_scale. Parameters: the flat dict models/afmoe.py documents.
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, S, heads, D]: pairs (d, d + D/2) turned by pos * theta^(-2d/D)."""
    S, D = x.shape[1], x.shape[3]
    freq = theta ** (-np.arange(D // 2) * 2.0 / D)
    ang = np.arange(S)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    lo, hi = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(cfg, p, x, sliding):
    B, S, _ = x.shape
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = norm(mm(x, p["wq"]).reshape(B, S, nq, D), p["qnorm"], eps)
    k = norm(mm(x, p["wk"]).reshape(B, S, nkv, D), p["knorm"], eps)
    v = mm(x, p["wv"]).reshape(B, S, nkv, D)
    if sliding:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nq // nkv, axis=2)    # query head i reads kv i // g
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k, precision=HI) / np.sqrt(D)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    see = j <= i
    if sliding:
        see = see & (i - j < cfg["sliding_window"])
    scores = jnp.where(see, scores, -jnp.inf)
    out = jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HI).reshape(B, S, nq * D)
    return mm(out * jax.nn.sigmoid(mm(x, p["wg"])), p["wo"])


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def router(cfg, p, x):
    """(weights [.., E] with nought off the top_k, chosen mask): every
    expert of the router's outputs, held here or not."""
    s = jax.nn.sigmoid(mm(x, p["router_w"]))
    biased = jax.lax.stop_gradient(s + p["router_b"])
    kth = jnp.sort(biased, axis=-1)[..., -cfg["num_experts_per_tok"]]
    chosen = biased >= kth[..., None]
    total = jnp.sum(jnp.where(chosen, s, 0.0), axis=-1, keepdims=True)
    return jnp.where(chosen, cfg["route_scale"] * s / total, 0.0), chosen


def routed_part(cfg, p, x):
    """What the experts held here give: each on every token, masked."""
    w, _ = router(cfg, p, x)
    out = jnp.zeros_like(x)
    for g in range(cfg["num_experts"]):
        e = cfg["expert_offset"] + g
        out = out + w[..., e:e + 1] * swiglu(x, p["e_gate"][g], p["e_up"][g],
                                             p["e_down"][g])
    return out


def pairs_held(cfg, p, x):
    _, chosen = router(cfg, p, x)
    lo = cfg["expert_offset"]
    return int(np.asarray(chosen)[..., lo:lo + cfg["num_experts"]].sum())


def layer(cfg, i, p, h):
    eps = cfg["rms_norm_eps"]
    sliding = cfg["layer_types"][i] == "sliding_attention"
    a = h + norm(attention(cfg, p, norm(h, p["norm1"], eps), sliding),
                 p["norm2"], eps)
    x = norm(a, p["norm3"], eps)
    if i < cfg["num_dense_layers"]:
        f = swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    else:
        f = routed_part(cfg, p, x) + swiglu(x, p["s_gate"], p["s_up"],
                                            p["s_down"])
    return a + norm(f, p["norm4"], eps)


def layer_params(params, i):
    pre = "l%d." % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def forward(cfg, params, pooled, dense=None):
    h = pooled[..., 3:] * np.sqrt(cfg["hidden_size"])
    for i in range(len(cfg["layer_types"])):
        h = layer(cfg, i, layer_params(params, i), h)
    pooled_h = norm(h, params["norm_f"], cfg["rms_norm_eps"]).mean(axis=1)
    return cfg["head_scale"] * mm(pooled_h, params["w_out"]) + params["b_out"]
