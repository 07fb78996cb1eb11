"""nemotron-3-super: NVIDIA Nemotron-3-Super-120B-A12B's block stack
(model_type nemotron_h) as a behaviour-sequence tower: model builder, the
plain float32 reference's copy for the chip, work functions.

An example is a user's last S items, every position one key of one item
vocabulary, pulled as a row of hidden_size trained values; the label is the
click. With N an RMSNorm (norm_eps, a weight) and x0 = embedx(row), every
layer is ONE part under ONE norm,

    h' = h + Mix_t(N(h)),   t = hybrid_override_pattern[i]

and this chip holds a SHARE of every part (the file's `deployment`): the
counts below are the held ones, the published ones stand beside them in
the file. What the absent heads, columns and experts would add is left
out here as in the program: the partial sums go on to the next layer.

"M" (Mamba-2, n_groups groups of mamba_num_heads / n_groups heads):
[z | xBC | dt] = u in_proj; xBC = silu(conv(xBC)), conv_t = conv_b + sum_k
conv_w[k] * xBC_{t-3+k}; [x | B | C] = xBC, B and C [n_groups,
ssm_state_size]; dt = softplus(dt + dt_bias); A = -exp(A_log); h_t =
exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t[g]; y_t = h_t C_t[g] + D x_t, g
the head's group; out = N_g(y * silu(z)) out_proj, N_g an RMSNorm over
each group's values by itself.
"*": q = u Wq -> num_attention_heads x head_dim; k = u Wk, v = u Wv ->
num_key_value_heads x head_dim; no rotary, no norm, no bias; scores q.k /
sqrt(head_dim), position i sees j <= i; softmax; out = (softmax v) Wo.
"E" (LatentMoE): s = sigmoid(u Wr) over all n_routed_experts_published;
the choice is the top num_experts_per_tok of s + b (b read by the choice
alone, no gradient); w_e = routed_scaling_factor * s_e / (sum of s over
the chosen, held or not); z = u fc1; y = sum over chosen e in
[expert_offset, expert_offset + n_routed_experts) of w_e relu(z W1_e)^2
W2_e; out = y fc2 + relu(u s_up)^2 s_down (the shared expert reads u;
moe_shared_expert_columns_held of its columns).
Head (a departure: a click model has no next-token head, and no
multi-token prediction):
    logit = head_scale * (w_out . mean over positions of N(h_last)) + b_out

forward() is the plain reference (tests/nemotron_h_reference.py, checked
equal to it in tests/test_nemotron_h.py) arranged so that it fits the chip
beside 16 B a parameter: every product through the harness's mm, the
scores' two, the recurrence's two and the router's included, so that the
float8 control reaches them; an example at a time (a loop written out over
the batch: no product with a weight lies inside a device loop, PR 37), a
layer under jax.checkpoint; the recurrence walked position by position,
checkpointed in blocks of SCAN_BLOCK positions; attention over blocks of
queries; every held expert on every token, masked, an expert at a time. It
imports nothing of the program; only build_model() does. The program's
scan is chunked and its experts are grouped products over sorted pairs;
this is the sequential recurrence and a loop over experts: they share no
code.
"""

import jax
import jax.numpy as jnp
import numpy as np

# a checkout without the model ends here, at once
from paddlebox_tpu.models import nemotron_h as _program  # noqa: F401

QUERY_BLOCK = 128       # queries a block of the reference's attention
SCAN_BLOCK = 64         # positions a checkpointed block of the recurrence
_MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "router_w",
             "fc1", "fc2", "s_up", "s_down")     # a token meets each whole


def build_model(cfg):
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.nemotron_h import NemotronH
    spec = ModelSpec(num_slots=cfg["num_sparse_slots"],
                     slot_dim=3 + cfg["embedx_dim"],
                     dense_dim=cfg["dense_dim"])
    return NemotronH(
        spec, pattern=cfg["hybrid_override_pattern"],
        hidden=cfg["hidden_size"],
        ssm_heads=cfg["mamba_num_heads_published"],
        ssm_head_dim=cfg["mamba_head_dim"], ssm_state=cfg["ssm_state_size"],
        ssm_groups=cfg["n_groups_published"],
        ssm_groups_held=cfg["n_groups"], conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"],
        heads=cfg["num_attention_heads_published"],
        kv_heads=cfg["num_key_value_heads_published"],
        head_dim=cfg["head_dim"], heads_held=cfg["num_attention_heads"],
        head_offset=cfg["attention_head_offset"],
        latent=cfg["moe_latent_size"],
        moe_intermediate=cfg["moe_intermediate_size"],
        shared_held=cfg["moe_shared_expert_columns_held"],
        num_experts=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], top_k=cfg["num_experts_per_tok"],
        route_scale=cfg["routed_scaling_factor"], eps=cfg["norm_eps"],
        head_scale=cfg["head_scale"])


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg, i):
    H = cfg["hidden_size"]
    out = {"norm": (H,)}
    kind = cfg["hybrid_override_pattern"][i]
    if kind == "M":
        heads, N, G = (cfg["mamba_num_heads"], cfg["ssm_state_size"],
                       cfg["n_groups"])
        inner = heads * cfg["mamba_head_dim"]
        conv = inner + 2 * G * N
        out.update(in_proj=(H, inner + conv + heads),
                   conv_w=(cfg["conv_kernel"], conv), conv_b=(conv,),
                   dt_bias=(heads,), A_log=(heads,), D=(heads,),
                   gnorm=(inner,), out_proj=(inner, H))
    elif kind == "*":
        hd = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
        out.update(wq=(H, q), wk=(H, kv), wv=(H, kv), wo=(q, H))
    else:
        Z, F, E = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                   cfg["n_routed_experts"])
        C, R = (cfg["moe_shared_expert_columns_held"],
                cfg["n_routed_experts_published"])
        out.update(router_w=(H, R), router_b=(R,), fc1=(H, Z), fc2=(Z, H),
                   e_up=(E, Z, F), e_down=(E, F, Z), s_up=(H, C),
                   s_down=(C, H))
    return out


def param_init(cfg):
    """name -> (shape, std of the normal draw), or (shape, size, "sign")
    for +-size: a matrix 1 / sqrt(its inputs); a norm's weight and D +-1;
    the convolution's taps and bias 0.29 (the spread of a depthwise kernel
    of 4 drawn over +-1/2); A_log 1 and dt_bias 2 (the harness draws no
    offset: A = -exp(A_log) spreads over -7...-0.14 and softplus(dt +
    dt_bias) over 0.01...6, fast and slow heads side by side; the file's
    `assumed` says why); the router's bias a small normal; b_out nought."""
    if cfg["hidden_size"] != cfg["embedx_dim"]:
        raise SystemExit("a pulled row's embedx is the tower's input: "
                         "hidden_size must equal embedx_dim")
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise SystemExit("hybrid_override_pattern is not num_hidden_layers "
                         "long")
    if cfg["expand"] * cfg["hidden_size"] != (
            cfg["mamba_num_heads_published"] * cfg["mamba_head_dim"]):
        raise SystemExit("expand x hidden_size is not the published "
                         "mamba_num_heads x mamba_head_dim")
    sizes = {"conv_w": 0.29, "conv_b": 0.29, "A_log": 1.0, "dt_bias": 2.0,
             "router_b": float(cfg["router_bias_std"])}
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        for leaf, shape in _layer_shapes(cfg, i).items():
            if "norm" in leaf or leaf == "D":
                how = (shape, 1.0, "sign")
            elif leaf in sizes:
                how = (shape, sizes[leaf])
            else:
                how = (shape, float(1.0 / np.sqrt(shape[-2])))
            out["l%d.%s" % (i, leaf)] = how
    H = cfg["hidden_size"]
    out["norm_f"] = ((H,), 1.0, "sign")
    out["w_out"] = ((H,), float(1.0 / np.sqrt(H)))
    out["b_out"] = ((), 0.0)
    return out


# -------------------------------------------------------------- reference

def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def _conv(x, w, b):
    """x [S, C]: out_t = b + sum_k w[k] * x_{t-(K-1)+k}, zeros before 0."""
    K, S = w.shape[0], x.shape[0]
    out = jnp.zeros_like(x) + b
    for k in range(K):
        back = K - 1 - k
        out = out + w[k] * jnp.concatenate(
            [jnp.zeros_like(x[:back]), x[:S - back]], axis=0)
    return out


def _recurrence(x, dt, A, Bm, Cm, D, mm):
    """One example: x [S, H, P], dt [S, H], Bm and Cm [S, G, N], head h
    reading group h // (H // G); the state walked position by position,
    SCAN_BLOCK positions a checkpoint (the backward pass keeps the state
    at each block's start and rebuilds a block's own); a padded position
    has dt = 0: no decay, nothing added."""
    S, H, P = x.shape
    G, N = Bm.shape[1:]
    block = min(SCAN_BLOCK, S)
    pad = -S % block

    def blocks(a):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(((S + pad) // block, block) + a.shape[1:])

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        b_h, c_h = (jnp.repeat(a, H // G, axis=0) for a in (b_t, c_t))
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + mm((dt_t[:, None] * x_t)[..., None], b_h[:, None, :]))
        return h, mm(h, c_h[..., None])[..., 0] + D[:, None] * x_t

    @jax.checkpoint
    def walk(h, ats):
        return jax.lax.scan(step, h, ats)
    _, y = jax.lax.scan(walk, jnp.zeros((H, P, N), jnp.float32),
                        tuple(blocks(a) for a in (x, dt, Bm, Cm)))
    return y.reshape(S + pad, H, P)[:S]


def _mamba(cfg, p, x, mm):
    S = x.shape[0]
    H, P, N, G = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["ssm_state_size"], cfg["n_groups"])
    inner = H * P
    proj = mm(x, p["in_proj"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * G * N],
                  proj[:, 2 * inner + 2 * G * N:])
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    y = _recurrence(
        xbc[:, :inner].reshape(S, H, P), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), xbc[:, inner:inner + G * N].reshape(S, G, N),
        xbc[:, inner + G * N:].reshape(S, G, N), p["D"], mm).reshape(S, inner)
    gated = (y * jax.nn.silu(z)).reshape(S, G, inner // G)
    normed = _norm(gated, p["gnorm"].reshape(G, inner // G), cfg["norm_eps"])
    return mm(normed.reshape(S, inner), p["out_proj"])


def _attention(cfg, p, x, mm):
    S = x.shape[0]
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def heads(w, n):
        return mm(x, w).reshape(S, n, D).transpose(1, 0, 2)
    q, k, v = heads(p["wq"], nq), heads(p["wk"], nkv), heads(p["wv"], nkv)
    # query head i reads key-value head i // group: the group's heads
    # lie on an axis of their own, over which k and v broadcast
    group = nq // nkv
    kt, v = jnp.swapaxes(k, -1, -2)[:, None], v[:, None]
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    n_blocks = (S + pad) // block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(
        nkv, group, n_blocks, block, D).transpose(2, 0, 1, 3, 4)
    j = jnp.arange(S)[None, :]

    @jax.checkpoint
    def one(args):
        qb, first = args
        see = j <= first + jnp.arange(block)[:, None]
        scores = jnp.where(see, mm(qb, kt) / np.sqrt(D), -1e30)
        return mm(jax.nn.softmax(scores, axis=-1), v)
    out = jax.lax.map(one, (q, jnp.arange(n_blocks) * block))
    out = out.transpose(0, 3, 1, 2, 4).reshape(S + pad, nq * D)[:S]
    return mm(out, p["wo"])


def _latent_moe(cfg, p, x, mm):
    s = jax.nn.sigmoid(mm(x, p["router_w"]))
    biased = jax.lax.stop_gradient(s + p["router_b"])
    kth = jnp.sort(biased, axis=-1)[..., -cfg["num_experts_per_tok"]]
    chosen = biased >= kth[..., None]
    total = jnp.sum(jnp.where(chosen, s, 0.0), axis=-1, keepdims=True)
    w = jnp.where(chosen, cfg["routed_scaling_factor"] * s / total, 0.0)
    z = mm(x, p["fc1"])
    # an expert at a time, recomputed in the backward pass with its
    # weighting, so that no expert's [tokens, latent] output is kept
    expert = jax.checkpoint(
        lambda we, z, up, down: we * mm(_relu2(mm(z, up)), down))
    y = jnp.zeros_like(z)
    for g in range(cfg["n_routed_experts"]):
        e = cfg["expert_offset"] + g
        y = y + expert(w[..., e:e + 1], z, p["e_up"][g], p["e_down"][g])
    return mm(y, p["fc2"]) + mm(_relu2(mm(x, p["s_up"])), p["s_down"])


_MIX = {"M": _mamba, "*": _attention, "E": _latent_moe}


def _layer(cfg, i, mm, p, h):
    """One example's layer: h [S, hidden_size]."""
    mix = _MIX[cfg["hybrid_override_pattern"][i]]
    return h + mix(cfg, p, _norm(h, p["norm"], cfg["norm_eps"]), mm)


def forward(cfg, params, pooled, dense, mm):
    """pooled [B, S, 3 + hidden_size] -> logits [B], float32; mm(x, w) is
    the matmul (batched over leading axes for the scores and the state)."""
    h = pooled[..., 3:]
    for i in range(cfg["num_hidden_layers"]):
        pre = "l%d." % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        one = jax.checkpoint(lambda p, h, i=i: _layer(cfg, i, mm, p, h))
        h = jnp.stack([one(p, h[b]) for b in range(h.shape[0])])
    mean = _norm(h, params["norm_f"], cfg["norm_eps"]).mean(axis=1)
    return cfg["head_scale"] * mm(mean, params["w_out"]) + params["b_out"]


# --------------------------------------------------------- work functions

def _layer_params(cfg, names=None):
    """Parameters of the named leaves (of every leaf: None), summed over
    the layers."""
    return sum(int(np.prod(shape))
               for i in range(cfg["num_hidden_layers"])
               for leaf, shape in _layer_shapes(cfg, i).items()
               if names is None or leaf in names)


def _held(cfg):
    """Dense parameters held: every layer's, the final norm, the head."""
    return _layer_params(cfg) + 2 * cfg["hidden_size"] + 1


def _layers(cfg, kind):
    return cfg["hybrid_override_pattern"].count(kind)


def _experts_a_token(cfg):
    """Held experts a token meets in a layer at an even routing."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / float(cfg["n_routed_experts_published"]))


def attn_flops_per_example(cfg):
    """The scores' two products over the visible pairs only (j <= i),
    forward and backward (the backward twice the forward): 2 x 2 x
    head_dim FLOP a pair and held query head forward, the attention
    layer."""
    S = cfg["num_sparse_slots"]
    return (3.0 * 4 * cfg["head_dim"] * cfg["num_attention_heads"]
            * (S * (S + 1) // 2) * _layers(cfg, "*"))


def ssd_flops_per_example(cfg):
    """The recurrence itself, whatever implements it: a multiply-add an
    element of the held [heads, head_dim, state] state for the update and
    one for the output, a position and layer, forward, and twice that
    backward. The chunked algorithm's own products are not needed work."""
    return (6.0 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            * cfg["ssm_state_size"] * cfg["num_sparse_slots"]
            * _layers(cfg, "M"))


def ssm_proj_share_flops_per_example(cfg):
    """in_proj and out_proj of every state-space layer at this share's
    widths: 6 FLOP a parameter and position (scope ssm_proj)."""
    return 6.0 * cfg["num_sparse_slots"] * _layer_params(
        cfg, ("in_proj", "out_proj"))


def latent_proj_flops_per_example(cfg):
    """fc1 and fc2 of every LatentMoE layer: 6 FLOP a parameter and
    position (scope moe_latent)."""
    return 6.0 * cfg["num_sparse_slots"] * _layer_params(cfg, ("fc1", "fc2"))


def latent_expert_flops_per_example(cfg):
    """The held routed experts' two products at an even routing
    (num_experts_per_tok x held / published pairs a token and layer),
    forward and backward: the grouped products of scope moe_experts."""
    one = _layer_params(cfg, ("e_up", "e_down")) / cfg["n_routed_experts"]
    return 6.0 * cfg["num_sparse_slots"] * one * _experts_a_token(cfg)


def flops_per_example(cfg):
    """Forward + backward over every position: 6 FLOP a parameter a token
    multiplies by (a held routed expert at the even routing's share), the
    scores' visible pairs, the recurrence; recomputation under the
    checkpoints is not work the step needs and is not counted."""
    return (6.0 * cfg["num_sparse_slots"] * _layer_params(cfg, _MATRICES)
            + latent_expert_flops_per_example(cfg)
            + attn_flops_per_example(cfg) + ssd_flops_per_example(cfg))


def _compute_bytes(cfg):
    return jnp.dtype(cfg["compute_dtype"]).itemsize


def ssd_scan_grouped_bytes_per_example(cfg):
    """What the recurrence must move whatever implements it (scope
    ssd_scan), at this share's widths: x, each held group's B and C, and dt
    read and y written once a position in the compute dtype, forward, and
    the same again twice for the backward pass; the state never leaves
    the chip's fast memory."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    values = (2 * inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
              + cfg["mamba_num_heads"])
    return (3.0 * values * _compute_bytes(cfg) * cfg["num_sparse_slots"]
            * _layers(cfg, "M"))


def ssm_conv_share_bytes_per_example(cfg):
    """The causal convolution and its silu (scope ssm_conv) at this
    share's widths: every held channel (x and the held groups' B and C)
    read and written once a position in the compute dtype, forward, and
    once more each backward."""
    conv = (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            + 2 * cfg["n_groups"] * cfg["ssm_state_size"])
    return (2.0 * 2 * conv * _compute_bytes(cfg) * cfg["num_sparse_slots"]
            * _layers(cfg, "M"))


def bytes_per_example(cfg, unique_rows_per_example):
    """Touched rows read and written once at the row width; the dense
    weights, adam's m and v read and written once a step; each layer's
    input written forward and read backward in float32."""
    rows = 2.0 * unique_rows_per_example * cfg["row_f32"] * 4
    dense = 6.0 * 4 * _held(cfg) / cfg["batch_size"]
    acts = 2.0 * 4 * cfg["num_sparse_slots"] * cfg["hidden_size"] * (
        cfg["num_hidden_layers"] + 1)
    return rows + dense + acts


def push_write_bytes_per_example(cfg, unique_rows_per_example):
    """The push's write of the slab (scope push_write): each touched row
    read once and written once, at the row's logical width."""
    return 2.0 * unique_rows_per_example * cfg["row_f32"] * 4


def pull_bytes_per_example(cfg, unique_rows_per_example):
    """The pull (scope pull): every occurrence's row read once at the
    row's logical width and its view (show, click, embed_w, embedx)
    written once; a key an example holds twice is read twice."""
    return cfg["num_sparse_slots"] * 4.0 * (cfg["row_f32"]
                                            + 3 + cfg["embedx_dim"])
