"""granite-4-h-micro: IBM Granite 4.0-H Micro's block stack (model_type
granitemoehybrid, no experts) as a behaviour-sequence tower: model builder,
the plain float32 reference's copy for the chip, work functions.

An example is a user's last S items, every position one key of one item
vocabulary, pulled as a row of hidden_size trained values; the label is the
click. With N an RMSNorm (rms_norm_eps, a weight), r = residual_multiplier
and x0 = embedx(row) * embedding_multiplier, every layer is

    a = h + r * Mix(N1(h));   h' = a + r * MLP(N2(a))
    MLP(x) = (silu(x Wg) * (x Wu)) Wd,  [Wg | Wu] = mlp_in

Mix, an `attention` layer: q = x Wq -> heads x head_dim; k = x Wk, v = x Wv
-> kv heads x head_dim; no rotary, no norm, no bias; query head i reads
key-value head i // (heads / kv heads); scores q.k * attention_multiplier,
position i sees j <= i; softmax; out = (softmax v) Wo.
Mix, a `mamba` layer (Mamba-2, one group): [z | xBC | dt] = x in_proj;
xBC = silu(conv(xBC)), conv_t = conv_b + sum_k conv_w[k] * xBC_{t-3+k};
[x | B | C] = xBC; dt = softplus(dt + dt_bias); A = -exp(A_log);
h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t + D x_t;
out = N_g(y * silu(z)) out_proj.
Head (a departure: a click model has no next-token head):
    logit = head_scale * (w_out . mean over positions of N(h_last)) + b_out

forward() is the plain reference (tests/granite_hybrid_reference.py,
checked equal to it in tests/test_granite_hybrid.py) arranged so that it
fits the chip beside 16 B a parameter: every product through the
harness's mm, the scores' two and the recurrence's two included, so that
the float8 control reaches them; an example at a time (a loop written
out over the batch: no product with a weight lies inside a device loop,
whose compiler would lift the weight's three bfloat16 parts out of the
loop and hold 6 B a parameter of every layer at once), a layer under
jax.checkpoint; the recurrence walked position by
position, checkpointed in blocks of SCAN_BLOCK positions; attention over
blocks of queries. It imports nothing of the program; only build_model()
does. The program's algorithm is chunked (ops/ssd.py); this one is the
sequential recurrence: they share no code.
"""

import jax
import jax.numpy as jnp
import numpy as np

# a checkout without the model ends here, at once
from paddlebox_tpu.models import granite_hybrid as _program  # noqa: F401

QUERY_BLOCK = 128       # queries a block of the reference's attention
SCAN_BLOCK = 64         # positions a checkpointed block of the recurrence
_MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "mlp_in",
             "mlp_out")


def build_model(cfg):
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.granite_hybrid import GraniteHybrid
    spec = ModelSpec(num_slots=cfg["num_sparse_slots"],
                     slot_dim=3 + cfg["embedx_dim"],
                     dense_dim=cfg["dense_dim"])
    return GraniteHybrid(
        spec, layer_types=cfg["layer_types"], hidden=cfg["hidden_size"],
        intermediate=cfg["intermediate_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attention_multiplier=cfg["attention_multiplier"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        conv_kernel=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        eps=cfg["rms_norm_eps"], head_scale=cfg["head_scale"])


# ------------------------------------------------------------- parameters

def _layer_shapes(cfg, i):
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"norm1": (H,), "norm2": (H,), "mlp_in": (H, 2 * F),
           "mlp_out": (F, H)}
    if cfg["layer_types"][i] == "mamba":
        heads, N = cfg["mamba_n_heads"], cfg["mamba_d_state"]
        inner = heads * cfg["mamba_d_head"]
        conv = inner + 2 * cfg["mamba_n_groups"] * N
        out.update(in_proj=(H, inner + conv + heads),
                   conv_w=(cfg["mamba_d_conv"], conv), conv_b=(conv,),
                   dt_bias=(heads,), A_log=(heads,), D=(heads,),
                   gnorm=(inner,), out_proj=(inner, H))
    else:
        hd = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
        out.update(wq=(H, q), wk=(H, kv), wv=(H, kv), wo=(q, H))
    return out


def param_init(cfg):
    """name -> (shape, std of the normal draw), or (shape, size, "sign")
    for +-size: a matrix 1 / sqrt(its inputs); a norm's weight and D +-1;
    the convolution's taps and bias 0.29 (the spread of a depthwise kernel
    of 4 drawn over +-1/2); A_log 1 and dt_bias 2 (the harness draws no
    offset: A = -exp(A_log) spreads over -7...-0.14 and softplus(dt +
    dt_bias) over 0.01...6, fast and slow heads side by side; the file's
    `assumed` says why); b_out nought."""
    if cfg["hidden_size"] != cfg["embedx_dim"]:
        raise SystemExit("a pulled row's embedx is the tower's input: "
                         "hidden_size must equal embedx_dim")
    if cfg["mamba_expand"] * cfg["hidden_size"] != (
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]):
        raise SystemExit("mamba_expand x hidden_size is not mamba_n_heads "
                         "x mamba_d_head")
    sizes = {"conv_w": 0.29, "conv_b": 0.29, "A_log": 1.0, "dt_bias": 2.0}
    out = {}
    for i in range(len(cfg["layer_types"])):
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
    """One example: x [S, H, P], dt [S, H], Bm and Cm [S, N]; the state
    walked position by position, SCAN_BLOCK positions a checkpoint (the
    backward pass keeps the state at each block's start and rebuilds a
    block's own); a padded position has dt = 0: no decay, nothing added."""
    S, H, P = x.shape
    N = Bm.shape[-1]
    block = min(SCAN_BLOCK, S)
    pad = -S % block

    def blocks(a):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(((S + pad) // block, block) + a.shape[1:])

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + mm((dt_t[:, None] * x_t)[..., None], b_t[None, :]))
        return h, mm(h, c_t) + D[:, None] * x_t

    @jax.checkpoint
    def walk(h, ats):
        return jax.lax.scan(step, h, ats)
    _, y = jax.lax.scan(walk, jnp.zeros((H, P, N), jnp.float32),
                        tuple(blocks(a) for a in (x, dt, Bm, Cm)))
    return y.reshape(S + pad, H, P)[:S]


def _mamba(cfg, p, x, mm):
    S = x.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = H * P
    proj = mm(x, p["in_proj"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * N],
                  proj[:, 2 * inner + 2 * N:])
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    y = _recurrence(
        xbc[:, :inner].reshape(S, H, P), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), xbc[:, inner:inner + N], xbc[:, inner + N:],
        p["D"], mm).reshape(S, inner)
    return mm(_norm(y * jax.nn.silu(z), p["gnorm"], cfg["rms_norm_eps"]),
              p["out_proj"])


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
        scores = jnp.where(see, mm(qb, kt) * cfg["attention_multiplier"],
                           -1e30)
        return mm(jax.nn.softmax(scores, axis=-1), v)
    out = jax.lax.map(one, (q, jnp.arange(n_blocks) * block))
    out = out.transpose(0, 3, 1, 2, 4).reshape(S + pad, nq * D)[:S]
    return mm(out, p["wo"])


def _layer(cfg, i, mm, p, h):
    """One example's layer: h [S, hidden_size]."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mix = _mamba if cfg["layer_types"][i] == "mamba" else _attention
    a = h + r * mix(cfg, p, _norm(h, p["norm1"], eps), mm)
    F = cfg["intermediate_size"]
    both = mm(_norm(a, p["norm2"], eps), p["mlp_in"])
    return a + r * mm(jax.nn.silu(both[:, :F]) * both[:, F:], p["mlp_out"])


def forward(cfg, params, pooled, dense, mm):
    """pooled [B, S, 3 + hidden_size] -> logits [B], float32; mm(x, w) is
    the matmul (batched over leading axes for the scores and the state)."""
    h = pooled[..., 3:] * cfg["embedding_multiplier"]
    for i in range(len(cfg["layer_types"])):
        pre = "l%d." % i
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        one = jax.checkpoint(lambda p, h, i=i: _layer(cfg, i, mm, p, h))
        h = jnp.stack([one(p, h[b]) for b in range(h.shape[0])])
    mean = _norm(h, params["norm_f"], cfg["rms_norm_eps"]).mean(axis=1)
    return cfg["head_scale"] * mm(mean, params["w_out"]) + params["b_out"]


# --------------------------------------------------------- work functions

def _layer_params(cfg, names=None):
    """Parameters of the named leaves (of every leaf: None), summed over
    the layers."""
    return sum(int(np.prod(shape))
               for i in range(len(cfg["layer_types"]))
               for leaf, shape in _layer_shapes(cfg, i).items()
               if names is None or leaf in names)


def _held(cfg):
    """Dense parameters held: every layer's, the final norm, the head."""
    return _layer_params(cfg) + 2 * cfg["hidden_size"] + 1


def _mamba_layers(cfg):
    return sum(t == "mamba" for t in cfg["layer_types"])


def attn_flops_per_example(cfg):
    """The scores' two products over the visible pairs only (j <= i),
    forward and backward (the backward twice the forward): 2 x 2 x
    head_dim FLOP a pair and query head forward, every attention layer."""
    S = cfg["num_sparse_slots"]
    layers = len(cfg["layer_types"]) - _mamba_layers(cfg)
    return (3.0 * 4 * cfg["head_dim"] * cfg["num_attention_heads"]
            * (S * (S + 1) // 2) * layers)


def ssd_flops_per_example(cfg):
    """The recurrence itself, whatever implements it: a multiply-add an
    element of the [heads, d_head, d_state] state for the update and one
    for the output, a position and layer, forward, and twice that
    backward: 6 x heads x d_head x d_state. The chunked algorithm's own
    products (the [chunk, chunk] masks) are not needed work."""
    return (6.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"] * cfg["num_sparse_slots"]
            * _mamba_layers(cfg))


def ssm_proj_flops_per_example(cfg):
    """in_proj and out_proj of every state-space layer: 6 FLOP a
    parameter and position, forward + backward (scope ssm_proj)."""
    return 6.0 * cfg["num_sparse_slots"] * _layer_params(
        cfg, ("in_proj", "out_proj"))


def dense_mlp_flops_per_example(cfg):
    """The SwiGLU of every layer: 6 FLOP a parameter and position,
    forward + backward (scope dense_mlp)."""
    return 6.0 * cfg["num_sparse_slots"] * _layer_params(
        cfg, ("mlp_in", "mlp_out"))


def flops_per_example(cfg):
    """Forward + backward over every position: 6 FLOP a parameter of a
    matrix, the scores' visible pairs, the recurrence; recomputation
    under the checkpoints is not work the step needs and is not
    counted."""
    return (6.0 * cfg["num_sparse_slots"] * _layer_params(cfg, _MATRICES)
            + attn_flops_per_example(cfg) + ssd_flops_per_example(cfg))


def _compute_bytes(cfg):
    return jnp.dtype(cfg["compute_dtype"]).itemsize


def ssd_scan_bytes_per_example(cfg):
    """What the recurrence must move whatever implements it (scope
    ssd_scan): x, B, C and dt read and y written once a position in the
    compute dtype, forward, and the same again twice for the backward
    pass (the inputs read again with y's cotangent, their cotangents
    written); the state never leaves the chip's fast memory."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    values = 2 * inner + 2 * cfg["mamba_d_state"] + cfg["mamba_n_heads"]
    return (3.0 * values * _compute_bytes(cfg) * cfg["num_sparse_slots"]
            * _mamba_layers(cfg))


def ssm_conv_bytes_per_example(cfg):
    """The causal convolution and its silu (scope ssm_conv): every
    channel read and written once a position in the compute dtype,
    forward, and once more each backward."""
    conv = (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])
    return (2.0 * 2 * conv * _compute_bytes(cfg) * cfg["num_sparse_slots"]
            * _mamba_layers(cfg))


def bytes_per_example(cfg, unique_rows_per_example):
    """Touched rows read and written once at the row width; the dense
    weights, adam's m and v read and written once a step; each layer's
    input written forward and read backward in float32."""
    rows = 2.0 * unique_rows_per_example * cfg["row_f32"] * 4
    dense = 6.0 * 4 * _held(cfg) / cfg["batch_size"]
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
