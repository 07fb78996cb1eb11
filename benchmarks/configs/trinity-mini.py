"""trinity-mini: Arcee Trinity-Mini's block stack (model_type afmoe) as a
behaviour-sequence tower: model builder, the plain float32 reference's copy
for the chip, work functions.

An example is a user's last S items, every position one key of one item
vocabulary, pulled as a row of hidden_size trained values; the label is the
click. With N an RMSNorm (rms_norm_eps, a weight) and x0 = embedx(row) *
sqrt(hidden_size), every layer is

    a = h + N2(Attn(N1(h)));   h' = a + N4(F(N3(a)))

Attn(x): q = x Wq -> heads x head_dim; k = x Wk, v = x Wv -> kv heads x
head_dim; g = x Wg; q, k normed over head_dim; rotary (rotate-half,
rope_theta) on q, k on a sliding layer, none on a full one; query head i
reads key-value head i // (heads / kv heads); scores q.k / sqrt(head_dim),
position i sees j <= i and on a sliding layer only i - j < sliding_window;
softmax; out = ((softmax v) * sigmoid(g)) Wo. No bias.
F, leading dense layers: (silu(x Wgate) * (x Wup)) Wdown, intermediate_size.
F, the others: sum over e in top_k(s + b), e held here, of w_e E_e(x) +
E_shared(x); s = sigmoid(x Wr) over all num_experts_published; b is read
by the choice alone and gets no gradient; w_e = route_scale * s_e / (sum of
s over the top_k chosen, held or not); E a SwiGLU of moe_intermediate_size.
This chip holds experts [expert_offset, expert_offset + num_experts).
Head (a departure: a click model has no next-token head):
    logit = head_scale * (w_out . mean over positions of N(h_last)) + b_out

forward() is the plain reference (tests/afmoe_reference.py, checked equal
to it in tests/test_afmoe.py) arranged so that it fits the chip beside 16 B
a parameter: every product through the harness's mm, the scores' two
included, so that the float8 control reaches them; a layer under
jax.checkpoint; attention over blocks of queries (lax.map, each block
recomputed in the backward pass); every held expert on every token,
masked, an expert at a time. It imports nothing of the program; only
build_model() does.
"""

import jax
import jax.numpy as jnp
import numpy as np

# a checkout without the model ends here, at once
from paddlebox_tpu.models import afmoe as _program  # noqa: F401

QUERY_BLOCK = 128       # queries a block of the reference's attention


def build_model(cfg):
    from paddlebox_tpu.models.afmoe import AfMoE
    from paddlebox_tpu.models.base import ModelSpec
    spec = ModelSpec(num_slots=cfg["num_sparse_slots"],
                     slot_dim=3 + cfg["embedx_dim"],
                     dense_dim=cfg["dense_dim"])
    return AfMoE(
        spec, layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], intermediate=cfg["intermediate_size"],
        moe_intermediate=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts_published"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"], route_scale=cfg["route_scale"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        head_scale=cfg["head_scale"])


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg, i):
    H, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    out = {"norm1": (H,), "norm2": (H,), "norm3": (H,), "norm4": (H,),
           "qnorm": (hd,), "knorm": (hd,), "wq": (H, q), "wk": (H, kv),
           "wv": (H, kv), "wg": (H, q), "wo": (q, H)}
    if i < cfg["num_dense_layers"]:
        F = cfg["intermediate_size"]
        out.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
    else:
        F, E = cfg["moe_intermediate_size"], cfg["num_experts"]
        out.update(router_w=(H, cfg["num_experts_published"]),
                   router_b=(cfg["num_experts_published"],),
                   e_gate=(E, H, F), e_up=(E, H, F), e_down=(E, F, H),
                   s_gate=(H, F), s_up=(H, F), s_down=(F, H))
    return out


def param_init(cfg):
    """name -> (shape, std of the normal draw), or (shape, size, "sign")
    for +-size: a matrix 1 / sqrt(its inputs), a norm's weight +-1, the
    router's bias a small normal, b_out nought."""
    if cfg["hidden_size"] != cfg["embedx_dim"]:
        raise SystemExit("a pulled row's embedx is the tower's input: "
                         "hidden_size must equal embedx_dim")
    out = {}
    for i in range(len(cfg["layer_types"])):
        for leaf, shape in _layer_shapes(cfg, i).items():
            if "norm" in leaf:
                how = (shape, 1.0, "sign")
            elif leaf == "router_b":
                how = (shape, float(cfg["router_bias_std"]))
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


def _rope(x, theta):
    """x [B, heads, S, D]: pairs (d, d + D/2) turned by pos * theta^(-2d/D)."""
    S, D = x.shape[2], x.shape[3]
    ang = (np.arange(S)[:, None]
           * theta ** (-np.arange(D // 2) * 2.0 / D)[None, :])
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    lo, hi = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(cfg, p, x, sliding, mm):
    B, S, _ = x.shape
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]

    def heads(w, n):
        return mm(x, w).reshape(B, S, n, D).transpose(0, 2, 1, 3)
    q = _norm(heads(p["wq"], nq), p["qnorm"], eps)
    k = _norm(heads(p["wk"], nkv), p["knorm"], eps)
    v = heads(p["wv"], nkv)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    # query head i reads key-value head i // group: the group's heads
    # lie on an axis of their own, over which k and v broadcast
    group = nq // nkv
    kt, v = jnp.swapaxes(k, -1, -2)[:, :, None], v[:, :, None]
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_blocks = (S + pad) // block
    q = q.reshape(B, nkv, group, n_blocks, block, D).transpose(
        3, 0, 1, 2, 4, 5)
    j = jnp.arange(S)[None, :]

    @jax.checkpoint
    def one(args):
        qb, first = args
        i = first + jnp.arange(block)[:, None]
        see = j <= i
        if sliding:
            see = see & (i - j < cfg["sliding_window"])
        # a padded query past a window sees no key: a finite mask keeps
        # its (discarded) row, and its gradient, off NaN
        scores = jnp.where(see, mm(qb, kt) / np.sqrt(D), -1e30)
        return mm(jax.nn.softmax(scores, axis=-1), v)
    out = jax.lax.map(one, (q, jnp.arange(n_blocks) * block))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S + pad, nq * D)[:, :S]
    return mm(out * jax.nn.sigmoid(mm(x, p["wg"])), p["wo"])


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def _routed(cfg, p, x, mm):
    s = jax.nn.sigmoid(mm(x, p["router_w"]))
    biased = jax.lax.stop_gradient(s + p["router_b"])
    kth = jnp.sort(biased, axis=-1)[..., -cfg["num_experts_per_tok"]]
    chosen = biased >= kth[..., None]
    total = jnp.sum(jnp.where(chosen, s, 0.0), axis=-1, keepdims=True)
    w = jnp.where(chosen, cfg["route_scale"] * s / total, 0.0)
    # an expert at a time, recomputed in the backward pass with its
    # weighting, so that no expert's [tokens, hidden] output is kept
    expert = jax.checkpoint(
        lambda we, x, a, b, c: we * _swiglu(x, a, b, c, mm))
    out = _swiglu(x, p["s_gate"], p["s_up"], p["s_down"], mm)
    for g in range(cfg["num_experts"]):
        e = cfg["expert_offset"] + g
        out = out + expert(w[..., e:e + 1], x, p["e_gate"][g], p["e_up"][g],
                           p["e_down"][g])
    return out


def _layer(cfg, i, mm, p, h):
    eps = cfg["rms_norm_eps"]
    sliding = cfg["layer_types"][i] == "sliding_attention"
    a = h + _norm(_attention(cfg, p, _norm(h, p["norm1"], eps), sliding, mm),
                  p["norm2"], eps)
    x = _norm(a, p["norm3"], eps)
    if i < cfg["num_dense_layers"]:
        f = _swiglu(x, p["w_gate"], p["w_up"], p["w_down"], mm)
    else:
        f = _routed(cfg, p, x, mm)
    return a + _norm(f, p["norm4"], eps)


def forward(cfg, params, pooled, dense, mm):
    """pooled [B, S, 3 + hidden_size] -> logits [B], float32; mm(x, w) is
    the matmul (batched over leading axes for the scores)."""
    h = pooled[..., 3:] * np.sqrt(cfg["hidden_size"])
    for i in range(len(cfg["layer_types"])):
        pre = "l%d." % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        h = jax.checkpoint(lambda p, h, i=i: _layer(cfg, i, mm, p, h))(p, h)
    mean = _norm(h, params["norm_f"], cfg["rms_norm_eps"]).mean(axis=1)
    return cfg["head_scale"] * mm(mean, params["w_out"]) + params["b_out"]


# --------------------------------------------------------- work functions

def _sizes(cfg):
    """(parameters a token multiplies by in the matmuls, of them in the
    held routed experts at an even routing, dense parameters held)."""
    active = routed = held = 0
    per_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / float(
        cfg["num_experts_published"])
    for i in range(len(cfg["layer_types"])):
        for leaf, shape in _layer_shapes(cfg, i).items():
            n = int(np.prod(shape))
            held += n
            if leaf.startswith("e_"):
                active += n / cfg["num_experts"] * per_token
                routed += n / cfg["num_experts"] * per_token
            elif len(shape) == 2:
                active += n
    return active, routed, held + 2 * cfg["hidden_size"] + 1


def _visible_pairs(cfg):
    """(query, key) pairs a sequence's attention must score, all layers:
    j <= i, and i - j < sliding_window on a sliding layer."""
    S, W = cfg["num_sparse_slots"], cfg["sliding_window"]
    full = S * (S + 1) // 2
    w = min(W, S)
    sliding = w * (w + 1) // 2 + (S - w) * w
    return sum(sliding if t == "sliding_attention" else full
               for t in cfg["layer_types"])


def attn_flops_per_example(cfg):
    """The scores' two products over the visible pairs only, forward and
    backward (the backward twice the forward): 2 x 2 x head_dim FLOP a
    pair and query head forward. What blocked_attention's kernels must
    do (scopes attn_window, attn_full), whatever implements them; the
    checkpoint's recomputation is not counted."""
    return (3.0 * 4 * cfg["head_dim"] * cfg["num_attention_heads"]
            * _visible_pairs(cfg))


def moe_expert_flops_per_example(cfg):
    """The held routed experts' three products at an even routing
    (num_experts_per_tok x held / published pairs a token and layer),
    forward and backward: the grouped products of scope moe_experts."""
    return 6.0 * cfg["num_sparse_slots"] * _sizes(cfg)[1]


def flops_per_example(cfg):
    """Forward + backward over every position: 6 FLOP a parameter a token
    multiplies by (a held routed expert at the even routing's share), and
    the scores' visible pairs; recomputation under the checkpoints is not
    work the step needs and is not counted."""
    return (6.0 * cfg["num_sparse_slots"] * _sizes(cfg)[0]
            + attn_flops_per_example(cfg))


def bytes_per_example(cfg, unique_rows_per_example):
    """Touched rows read and written once at the row width; the dense
    weights, adam's m and v read and written once a step; each layer's
    input written forward and read backward in float32."""
    rows = 2.0 * unique_rows_per_example * cfg["row_f32"] * 4
    dense = 6.0 * 4 * _sizes(cfg)[2] / cfg["batch_size"]
    acts = 2.0 * 4 * cfg["num_sparse_slots"] * cfg["hidden_size"] * (
        len(cfg["layer_types"]) + 1)
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
