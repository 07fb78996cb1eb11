"""deepfm-criteo: model builder, plain float32 reference, work functions.

The reference follows the paper: first-order term (sum of the per-field
weights), second-order FM term over the field embeddings by the
(sum^2 - sum of squares) / 2 identity, a deep tower over the concatenated
field inputs, and a learned mix of the three. It imports nothing of the
program; only build_model() does, for the system under test.
"""

import jax
import jax.numpy as jnp
import numpy as np


def build_model(cfg):
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.models.deepfm import DeepFM
    spec = ModelSpec(num_slots=cfg["num_sparse_slots"],
                     slot_dim=3 + cfg["embedx_dim"],
                     dense_dim=cfg["dense_dim"])
    return DeepFM(spec, hidden=tuple(cfg["deep_mlp"][:-1]))


def _dims(cfg):
    return [cfg["num_sparse_slots"] * (3 + cfg["embedx_dim"])
            + cfg["dense_dim"], *cfg["deep_mlp"]]


def param_init(cfg):
    """name -> (shape, std of the normal draw; 0 = zeros)."""
    dims = _dims(cfg)
    out = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        out["deep_w%d" % i] = ((a, b), float(np.sqrt(2.0 / a)))
        out["deep_b%d" % i] = ((b,), 0.0)
    out["fm_out_w"] = ((3,), 0.1)
    out["fm_out_b"] = ((), 0.0)
    return out


def forward(cfg, params, pooled, dense, mm):
    """pooled [B, S, 3+D] -> logits [B]; mm(x, w) is the matmul."""
    B = pooled.shape[0]
    first = pooled[:, :, 2].sum(axis=1)
    v = pooled[:, :, 3:]
    sum_v = v.sum(axis=1)
    fm2 = 0.5 * (sum_v * sum_v - (v * v).sum(axis=1)).sum(axis=-1)
    h = pooled.reshape(B, -1)
    if dense is not None and dense.shape[-1]:
        h = jnp.concatenate([h, dense], axis=-1)
    n = len(_dims(cfg)) - 1
    for i in range(n):
        h = mm(h, params["deep_w%d" % i]) + params["deep_b%d" % i]
        if i < n - 1:
            h = jax.nn.relu(h)
    stack = jnp.stack([first, fm2, h[:, 0]], axis=-1)
    return mm(stack, params["fm_out_w"][:, None])[:, 0] + params["fm_out_b"]


def _macs(cfg):
    dims = _dims(cfg)
    fm = 2 * cfg["num_sparse_slots"] * cfg["embedx_dim"]
    return sum(a * b for a, b in zip(dims[:-1], dims[1:])) + fm + 3


def _params(cfg):
    dims = _dims(cfg)
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:])) + 4


def flops_per_example(cfg):
    """Forward + backward (2 FLOP per multiply-add, backward twice the
    forward)."""
    return 6.0 * _macs(cfg)


def bytes_per_example(cfg, unique_rows_per_example):
    """As dlrm-mlperf's: touched rows read and written once at the row
    width; dense params, adam m and v read and written once per step;
    activations written forward and read backward at the compute width."""
    rows = 2.0 * unique_rows_per_example * cfg["row_f32"] * 4
    dense = 6.0 * 4 * _params(cfg) / cfg["batch_size"]
    acts = 2.0 * 2 * sum(_dims(cfg))
    return rows + dense + acts
