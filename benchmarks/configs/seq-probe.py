"""seq-probe: a probe of the harness at a sequence tower's shapes, and no
public model: thousands of one-valued slots over ONE table (the positions
of a behaviour sequence over a vocabulary), rows of 2,048 floats, and a
compute-bound tower of some 600M dense parameters.

The tower is the stand-in that compile_v5e.py --reference compiles:
`tower_layers` residual pairs of matmuls over every position of `pooled`,

    h <- h + relu(h @ up_i) @ down_i        up_i [3 + D, F], down_i [F, 3 + D]

each pair under jax.checkpoint, and the logit `head_scale` times the mean
of the last h over positions and features. It is written twice: Tower.apply
for the system under test, on the models' init / apply protocol and in the
dtype the trainer hands it, and forward(), the plain float32 reference with
the harness's matmul. Neither imports the other's; only build_model()
touches the program (it needs nothing of it but the protocol).
"""

import jax
import jax.numpy as jnp
import numpy as np


def _shapes(cfg):
    width, hidden = 3 + cfg["embedx_dim"], cfg["tower_hidden"]
    return width, hidden, cfg["tower_layers"]


def param_init(cfg):
    """name -> (shape, std of the normal draw): He, both ways."""
    width, hidden, layers = _shapes(cfg)
    out = {}
    for i in range(layers):
        out["up%d" % i] = ((width, hidden), float(np.sqrt(2.0 / width)))
        out["down%d" % i] = ((hidden, width), float(np.sqrt(2.0 / hidden)))
    return out


class Tower:
    """The system under test's model: init(rng) -> params, apply(params,
    pooled [B, S, 3 + D], dense) -> logits [B]; the matmuls in pooled's
    dtype, the mean summed in float32."""

    def __init__(self, cfg):
        self.cfg = dict(cfg)

    def init(self, rng):
        # the harness puts the seed's weights in their place; the shapes
        # and names are what it checks
        return {k: jnp.zeros(shape, jnp.float32)
                for k, (shape, _std) in param_init(self.cfg).items()}

    def apply(self, params, pooled, dense):
        @jax.checkpoint
        def pair(h, up, down):
            return h + jnp.maximum(h @ up, 0) @ down
        h = pooled
        for i in range(self.cfg["tower_layers"]):
            h = pair(h, params["up%d" % i], params["down%d" % i])
        return self.cfg["head_scale"] * h.mean(axis=(1, 2),
                                               dtype=jnp.float32)


def build_model(cfg):
    return Tower(cfg)


def forward(cfg, params, pooled, dense, mm):
    """pooled [B, S, 3 + D] -> logits [B], float32; mm(x, w) is the
    matmul. A pair is recomputed in the backward pass, so that one pair's
    [B * S, F] activations are alive at a time."""
    @jax.checkpoint
    def pair(h, up, down):
        return h + mm(jax.nn.relu(mm(h, up)), down)
    h = pooled
    for i in range(cfg["tower_layers"]):
        h = pair(h, params["up%d" % i], params["down%d" % i])
    return cfg["head_scale"] * h.mean(axis=(1, 2))


def _params(cfg):
    width, hidden, layers = _shapes(cfg)
    return 2 * width * hidden * layers


def flops_per_example(cfg):
    """Forward + backward over every position (2 FLOP a multiply-add,
    backward twice the forward); the checkpoints' recomputation is not
    work the step needs and is not counted."""
    return 6.0 * cfg["num_sparse_slots"] * _params(cfg)


def bytes_per_example(cfg, unique_rows_per_example):
    """Touched rows read and written once at the row width; the dense
    weights, adam's m and v read and written once a step; each pair's
    input written forward and read backward at the compute width."""
    width, _hidden, layers = _shapes(cfg)
    rows = 2.0 * unique_rows_per_example * cfg["row_f32"] * 4
    dense = 6.0 * 4 * _params(cfg) / cfg["batch_size"]
    acts = 2.0 * 2 * cfg["num_sparse_slots"] * width * (layers + 1)
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
