"""Plain float32 reference of the Nemotron-H behaviour-sequence tower,
written from the layer equations (ISSUE 41; models/nemotron_h.py's
docstring states the same) and not from the program: a layer is ONE of
state-space mixer / attention / LatentMoE under ONE norm; the state-space
layer as the SEQUENTIAL recurrence over positions (a lax.scan of h_t, one
[P, N] state a head, a head reading its group's B and C), the convolution
as four shifted adds, attention as the whole [S, S] scores under a mask
with a plain softmax, the routed experts as a loop over experts on every
token, weighted by the router (nought off the chosen). Nothing chunked, no
kernel, no checkpoint, nothing from paddlebox_tpu. Test sizes only.

cfg keys are the configuration file's (benchmarks/configs/
nemotron-3-super.json), and they describe the SHARE held: mamba_num_heads
and n_groups (state-space heads and groups held), num_attention_heads and
num_key_value_heads (held), n_routed_experts (held) of
n_routed_experts_published router outputs from expert_offset on,
moe_shared_expert_columns_held; hidden_size, mamba_head_dim,
ssm_state_size, conv_kernel, head_dim, moe_latent_size,
moe_intermediate_size, num_experts_per_tok, routed_scaling_factor,
hybrid_override_pattern, norm_eps, head_scale. Parameters: the flat dict
models/nemotron_h.py documents. take_share() cuts an uncut layer's
parameters to one chip's.
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
    """x [B, S, H, P], dt [B, S, H], A and D [H], Bm and Cm [B, S, G, N],
    head h reading group h // (H // G):
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t + D x_t,
    position by position from h = 0."""
    Bsz, _, H, P = x.shape
    G, N = Bm.shape[-2:]
    # every head its own copy of its group's B and C: [B, S, H, N]
    Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (Bm, Cm))

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y = jnp.sum(h * c_t[:, :, None, :], axis=-1)
        return h, y + D[:, None] * x_t
    _, y = jax.lax.scan(
        step, jnp.zeros((Bsz, H, P, N), jnp.float32),
        tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, Bh, Ch)))
    return jnp.swapaxes(y, 0, 1)


def mamba(cfg, p, x):
    B, S, _ = x.shape
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg[
        "ssm_state_size"]
    G, inner = cfg["n_groups"], H * P
    proj = mm(x, p["in_proj"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * G * N],
                  proj[..., 2 * inner + 2 * G * N:])
    xbc = jax.nn.silu(conv(xbc, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = (xbc[..., :inner], xbc[..., inner:inner + G * N],
                  xbc[..., inner + G * N:])
    y = recurrence(xs.reshape(B, S, H, P),
                   jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N),
                   p["D"]).reshape(B, S, inner)
    # the gate, then an RMSNorm over each group's values by itself
    gated = (y * jax.nn.silu(z)).reshape(B, S, G, inner // G)
    normed = norm(gated, p["gnorm"].reshape(G, inner // G), cfg["norm_eps"])
    return mm(normed.reshape(B, S, inner), p["out_proj"])


def attention(cfg, p, x):
    B, S, _ = x.shape
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm(x, p["wq"]).reshape(B, S, nq, D)
    k = jnp.repeat(mm(x, p["wk"]).reshape(B, S, nkv, D), nq // nkv, axis=2)
    v = jnp.repeat(mm(x, p["wv"]).reshape(B, S, nkv, D), nq // nkv, axis=2)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k, precision=HI) / np.sqrt(D)
    see = np.arange(S)[None, :] <= np.arange(S)[:, None]
    scores = jnp.where(see, scores, -jnp.inf)
    e = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    weights = e / e.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhij,bjhd->bihd", weights, v, precision=HI)
    return mm(out.reshape(B, S, nq * D), p["wo"])


def relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def router(cfg, p, x):
    """(weights [.., all experts] with nought off the chosen, chosen mask):
    every output of the router, held here or not."""
    s = jax.nn.sigmoid(mm(x, p["router_w"]))
    biased = jax.lax.stop_gradient(s + p["router_b"])
    kth = jnp.sort(biased, axis=-1)[..., -cfg["num_experts_per_tok"]]
    chosen = biased >= kth[..., None]
    total = jnp.sum(jnp.where(chosen, s, 0.0), axis=-1, keepdims=True)
    return (jnp.where(chosen, cfg["routed_scaling_factor"] * s / total, 0.0),
            chosen)


def routed_part(cfg, p, z, w):
    """sum over the experts held here of w_e relu(z W1_e)^2 W2_e, in the
    latent: each held expert on every token, weighted (nought where the
    router did not choose it)."""
    out = jnp.zeros_like(z)
    for g in range(cfg["n_routed_experts"]):
        e = cfg["expert_offset"] + g
        out = out + w[..., e:e + 1] * mm(relu2(mm(z, p["e_up"][g])),
                                         p["e_down"][g])
    return out


def pairs_by_expert(cfg, p, x):
    _, chosen = router(cfg, p, x)
    lo = cfg["expert_offset"]
    held = np.asarray(chosen)[..., lo:lo + cfg["n_routed_experts"]]
    return held.reshape(-1, held.shape[-1]).sum(axis=0)


def latent_moe(cfg, p, x):
    w, _ = router(cfg, p, x)
    y = routed_part(cfg, p, mm(x, p["fc1"]), w)
    return mm(y, p["fc2"]) + mm(relu2(mm(x, p["s_up"])), p["s_down"])


MIX = {"M": mamba, "*": attention, "E": latent_moe}


def layer(cfg, i, p, h):
    mix = MIX[cfg["hybrid_override_pattern"][i]]
    return h + mix(cfg, p, norm(h, p["norm"], cfg["norm_eps"]))


def forward(cfg, params, pooled, dense=None):
    """pooled [B, S, 3 + hidden_size] -> logits [B]."""
    h = pooled[..., 3:]
    for i in range(len(cfg["hybrid_override_pattern"])):
        h = layer(cfg, i, layer_params(params, i), h)
    mean = norm(h, params["norm_f"], cfg["norm_eps"]).mean(axis=1)
    return cfg["head_scale"] * mm(mean, params["w_out"]) + params["b_out"]


# ------------------------------------------------- one chip's share of a layer

def take_share(cfg, kind, p, *, groups=None, heads=None, columns=None,
               experts=None):
    """(cfg of the share, its parameters) cut from an UNCUT layer's (cfg
    with every count whole): ``groups`` = (first, held) of a state-space
    mixer's groups, ``heads`` = (first, held) of the query heads with the
    key-value heads they read, ``columns`` = (first, held) of the shared
    expert's, ``experts`` = (first, held) of the routed experts. What a
    share holds whole (norm, router, latent projections) is copied."""
    cfg, p = dict(cfg), dict(p)
    if kind == "M":
        H, P, N, G = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                      cfg["ssm_state_size"], cfg["n_groups"])
        g0, g = groups
        per, inner = H // G, H * P
        h0, h = g0 * per, g * per
        heads_at = np.arange(h0, h0 + h)
        x_at = np.arange(h0 * P, (h0 + h) * P)
        b_at = inner + np.arange(g0 * N, (g0 + g) * N)
        c_at = inner + G * N + np.arange(g0 * N, (g0 + g) * N)
        xbc_at = np.concatenate([x_at, b_at, c_at])
        in_at = np.concatenate([x_at, inner + xbc_at,
                                2 * inner + 2 * G * N + heads_at])
        p.update(in_proj=p["in_proj"][:, in_at],
                 conv_w=p["conv_w"][:, xbc_at], conv_b=p["conv_b"][xbc_at],
                 dt_bias=p["dt_bias"][heads_at], A_log=p["A_log"][heads_at],
                 D=p["D"][heads_at], gnorm=p["gnorm"][x_at],
                 out_proj=p["out_proj"][x_at])
        cfg.update(mamba_num_heads=h, n_groups=g)
    elif kind == "*":
        nq, nkv, D = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
        q0, q = heads
        per = nq // nkv
        kv0, kv1 = q0 // per, (q0 + q - 1) // per + 1
        q_at = np.arange(q0 * D, (q0 + q) * D)
        kv_at = np.arange(kv0 * D, kv1 * D)
        p.update(wq=p["wq"][:, q_at], wk=p["wk"][:, kv_at],
                 wv=p["wv"][:, kv_at], wo=p["wo"][q_at])
        cfg.update(num_attention_heads=q, num_key_value_heads=kv1 - kv0,
                   attention_head_offset=q0)
    else:
        c0, c = columns
        e0, e = experts
        p.update(s_up=p["s_up"][:, c0:c0 + c], s_down=p["s_down"][c0:c0 + c],
                 e_up=p["e_up"][e0:e0 + e], e_down=p["e_down"][e0:e0 + e])
        cfg.update(moe_shared_expert_columns_held=c, n_routed_experts=e,
                   expert_offset=e0)
    return cfg, p
