"""dlrm-mlperf: model builder, plain float32 reference, work functions.

The reference follows the published description (bottom MLP on the dense
features, pairwise dots between its output and the 26 embeddings, top MLP
on [pairs, bottom output]) in straightforward jax.numpy. It imports
nothing of the program; only build_model() does, for the system under test.
"""

import jax
import jax.numpy as jnp
import numpy as np


def build_model(cfg):
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.dlrm import DLRM
    spec = ModelSpec(num_slots=cfg["num_sparse_slots"],
                     slot_dim=3 + cfg["embedx_dim"],
                     dense_dim=cfg["dense_dim"])
    return DLRM(spec, bottom=tuple(cfg["bottom_mlp"][:-1]),
                top=tuple(cfg["top_mlp"][:-1]))


def _dims(cfg):
    vectors = cfg["num_sparse_slots"] + 1
    pairs = vectors * (vectors - 1) // 2
    bot = [cfg["dense_dim"], *cfg["bottom_mlp"]]
    top = [pairs + cfg["embedx_dim"], *cfg["top_mlp"]]
    return bot, top, pairs


def param_init(cfg):
    """name -> (shape, std of the normal draw; 0 = zeros). He init."""
    bot, top, _ = _dims(cfg)
    out = {}
    for name, dims in (("bot", bot), ("top", top)):
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            out["%s_w%d" % (name, i)] = ((a, b), float(np.sqrt(2.0 / a)))
            out["%s_b%d" % (name, i)] = ((b,), 0.0)
    return out


def forward(cfg, params, pooled, dense, mm):
    """pooled [B, S, 3+D] (log_show, log_ctr, embed_w, embedx), dense
    [B, 13] -> logits [B]. mm(x, w) is the matmul, so that the control can
    lower its precision."""
    bot, top, _ = _dims(cfg)
    x = dense
    for i in range(len(bot) - 1):
        x = jax.nn.relu(mm(x, params["bot_w%d" % i]) + params["bot_b%d" % i])
    feats = jnp.concatenate([pooled[:, :, 3:], x[:, None, :]], axis=1)
    inter = jax.vmap(lambda f: mm(f, f.T))(feats)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    h = jnp.concatenate([inter[:, iu, ju], x], axis=-1)
    n = len(top) - 1
    for i in range(n):
        h = mm(h, params["top_w%d" % i]) + params["top_b%d" % i]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h[:, 0]


def _macs(cfg):
    bot, top, pairs = _dims(cfg)
    mlp = sum(a * b for d in (bot, top) for a, b in zip(d[:-1], d[1:]))
    return mlp + pairs * cfg["embedx_dim"]


def _params(cfg):
    bot, top, _ = _dims(cfg)
    return sum(a * b + b for d in (bot, top) for a, b in zip(d[:-1], d[1:]))


def flops_per_example(cfg):
    """Forward + backward of the dense tower as the algorithm needs it
    (2 FLOP per multiply-add, backward twice the forward; the 351 pairs,
    not the 27 x 27 the einsum computes)."""
    return 6.0 * _macs(cfg)


def bytes_per_example(cfg, unique_rows_per_example):
    """HBM bytes a step must move, per example: each touched row read once
    and written once at the row width; dense params, adam m and v read and
    written once per step in float32; each layer's activations written in
    the forward and read in the backward pass at the compute width."""
    bot, top, _ = _dims(cfg)
    rows = 2.0 * unique_rows_per_example * cfg["row_f32"] * 4
    dense = 6.0 * 4 * _params(cfg) / cfg["batch_size"]
    acts = 2.0 * 2 * (sum(bot) + sum(top)
                      + (cfg["num_sparse_slots"] + 1) * cfg["embedx_dim"])
    return rows + dense + acts
