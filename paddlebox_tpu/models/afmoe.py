"""AfMoE block stack as a behaviour-sequence tower (model_type ``afmoe``:
Arcee Trinity).

An example is a user's last S items, every position one key of one item
vocabulary, pulled from the pass table as a row of ``hidden`` trained
values; the tower over the positions is the language model's block stack
and the label is the click. The input embedding is the parameter server's
table; the blocks are the dense side, trained by the trainer's dense
optimizer.

    x0 = embedx(row) * sqrt(hidden)                        [B, S, hidden]
    a  = h + N2(Attn(N1(h)));   h' = a + N4(F(N3(a)))      every layer
    N  = RMSNorm (eps, a weight), in float32

Attn(x): q = x Wq -> heads x head_dim; k = x Wk, v = x Wv -> kv_heads x
head_dim; g = x Wg; q and k normed over head_dim; on a sliding layer
rotary (rotate-half, theta) on q and k, on a full layer none; query head
i reads key-value head i // (heads // kv_heads); position i sees j <= i,
and on a sliding layer only i - j < window; out = ((softmax v) *
sigmoid(g)) Wo. No bias anywhere. ops/attention.py never forms the
scores.

F on the leading dense layers is a SwiGLU of width ``intermediate``; on
the others  sum over e in top_k(s + b), e held here, of w_e E_e(x) +
E_shared(x)  (ops/routed_experts.py): the router scores all
``num_experts``, this chip holds experts [expert_offset, expert_offset +
experts_held) and computes their part and the shared expert's.

Head (a departure: a click model has no next-token head, so the
vocabulary-sized output matrix is not held):
    logit = head_scale * (w_out . mean over positions of N(h_last)) + b_out

Every layer runs under jax.checkpoint. The matrix products run in the
dtype ``pooled`` arrives in (bfloat16 under the trainer's mixed
precision) over float32 master weights; the residual stream, the norms,
the router (``f32_params``: the trainer leaves those leaves uncast) and
the softmax are float32.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.ops.attention import blocked_attention
from paddlebox_tpu.ops.routed_experts import route, routed_experts

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
# leaves computed with in float32 whatever the trainer's compute dtype
_F32_LEAVES = frozenset(("norm1", "norm2", "norm3", "norm4", "qnorm",
                         "knorm", "norm_f", "router_w", "router_b", "w_out",
                         "b_out"))


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """Rotate-half rotary embedding over positions; x [B, H, S, D]."""
    S, D = x.shape[-2:]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), F32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), F32)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


class AfMoE:
    """init(rng) -> params (a flat dict, ``l<i>.<leaf>`` a layer);
    apply(params, pooled [B, S, 3 + hidden], dense) -> logits [B]. A
    caller that hands ``counters`` (a dict of its own trace) gets
    ``step_counters`` put into it: "moe_pairs_held", the pairs routed to
    held experts over all layers, and "moe_combine_rows", the rows the
    layers' forward combines fetched (ops/routed_experts.routed_experts);
    the trainer adds them to utils/stats at a chunk's drain."""

    name = "afmoe"
    task_names = ("ctr",)
    step_counters = ("moe_pairs_held", "moe_combine_rows")

    def __init__(self, spec: ModelSpec, *, layer_types: Sequence[str],
                 num_dense_layers: int, hidden: int, heads: int,
                 kv_heads: int, head_dim: int, window: int,
                 intermediate: int, moe_intermediate: int, num_experts: int,
                 experts_held: int, expert_offset: int = 0, top_k: int = 8,
                 route_scale: float = 1.0, rope_theta: float = 10000.0,
                 eps: float = 1e-5, head_scale: float = 1.0) -> None:
        if spec.slot_dim != 3 + hidden:
            raise ValueError(f"a pulled row serves {spec.slot_dim - 3} "
                             f"values, the tower is {hidden} wide")
        if not 0 <= expert_offset <= num_experts - experts_held:
            raise ValueError("experts held lie outside the router's outputs")
        if any(t not in (SLIDING, FULL) for t in layer_types):
            raise ValueError(f"layer_types {layer_types!r}")
        self.spec = spec
        self.layer_types = tuple(layer_types)
        self.num_dense_layers = int(num_dense_layers)
        self.hidden, self.heads, self.kv_heads = hidden, heads, kv_heads
        self.head_dim, self.window = head_dim, window
        self.intermediate, self.moe_intermediate = (intermediate,
                                                    moe_intermediate)
        self.num_experts, self.experts_held = num_experts, experts_held
        self.expert_offset, self.top_k = expert_offset, top_k
        self.route_scale, self.rope_theta = float(route_scale), rope_theta
        self.eps, self.head_scale = float(eps), float(head_scale)
        # leaves the trainer's mixed precision leaves in float32
        self.f32_params = tuple(k for k in self.shapes()
                                if k.rpartition(".")[2] in _F32_LEAVES)

    # ------------------------------------------------------------ params
    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        H, hd = self.hidden, self.head_dim
        q, kv = self.heads * hd, self.kv_heads * hd
        out: Dict[str, Tuple[int, ...]] = {}
        for i in range(len(self.layer_types)):
            leaves = {"norm1": (H,), "wq": (H, q), "wk": (H, kv),
                      "wv": (H, kv), "wg": (H, q), "wo": (q, H),
                      "qnorm": (hd,), "knorm": (hd,), "norm2": (H,),
                      "norm3": (H,), "norm4": (H,)}
            if i < self.num_dense_layers:
                F = self.intermediate
                leaves.update(w_gate=(H, F), w_up=(H, F), w_down=(F, H))
            else:
                F, E = self.moe_intermediate, self.experts_held
                leaves.update(router_w=(H, self.num_experts),
                              router_b=(self.num_experts,),
                              e_gate=(E, H, F), e_up=(E, H, F),
                              e_down=(E, F, H), s_gate=(H, F),
                              s_up=(H, F), s_down=(F, H))
            out.update({"l%d.%s" % (i, k): v for k, v in leaves.items()})
        out.update(norm_f=(H,), w_out=(H,), b_out=())
        return out

    def init(self, rng):
        params = {}
        for (name, shape), key in zip(
                sorted(self.shapes().items()),
                jax.random.split(rng, len(self.shapes()))):
            leaf = name.rpartition(".")[2]
            if "norm" in leaf:
                params[name] = jnp.ones(shape, F32)
            elif leaf in ("router_b", "b_out"):
                params[name] = jnp.zeros(shape, F32)
            else:
                fan_in = shape[-2] if len(shape) > 1 else shape[0]
                params[name] = (jax.random.normal(key, shape, F32)
                                / np.sqrt(fan_in))
        return params

    # ------------------------------------------------------------- layers
    def _attention(self, p, x, cdt, sliding: bool):
        B, S, _ = x.shape
        xc = x.astype(cdt)

        def heads(w, n):
            return (xc @ w).reshape(B, S, n, self.head_dim).transpose(
                0, 2, 1, 3)
        q = rms_norm(heads(p["wq"], self.heads), p["qnorm"], self.eps)
        k = rms_norm(heads(p["wk"], self.kv_heads), p["knorm"], self.eps)
        v = heads(p["wv"], self.kv_heads)
        if sliding:
            q, k = rotary(q, self.rope_theta), rotary(k, self.rope_theta)
        with jax.named_scope("attn_window" if sliding else "attn_full"):
            o = blocked_attention(q.astype(cdt), k.astype(cdt), v,
                                  self.window if sliding else None)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
        gate = jax.nn.sigmoid((xc @ p["wg"]).astype(F32))
        return (o.astype(F32) * gate).astype(cdt) @ p["wo"]

    def _routed(self, p, x, cdt):
        B, S, H = x.shape
        flat = x.reshape(B * S, H)
        with jax.named_scope("moe_route"):
            experts, weights = route(flat, p["router_w"], p["router_b"],
                                     self.top_k, self.route_scale)
        y, sizes, fetched = routed_experts(
            flat.astype(cdt), experts, weights, p["e_gate"], p["e_up"],
            p["e_down"], self.expert_offset, self.num_experts)
        with jax.named_scope("dense_mlp"):
            shared = swiglu(flat.astype(cdt), p["s_gate"], p["s_up"],
                            p["s_down"])
        return ((y + shared.astype(F32)).reshape(B, S, H),
                jnp.stack([sizes.sum(), fetched]))

    def _layer(self, i: int, p, h, cdt):
        sliding = self.layer_types[i] == SLIDING
        a = h + rms_norm(
            self._attention(p, rms_norm(h, p["norm1"], self.eps), cdt,
                            sliding), p["norm2"], self.eps)
        x = rms_norm(a, p["norm3"], self.eps)
        if i < self.num_dense_layers:
            with jax.named_scope("dense_mlp"):
                f = swiglu(x.astype(cdt), p["w_gate"], p["w_up"],
                           p["w_down"])
            counts = jnp.zeros((2,), jnp.int32)
        else:
            f, counts = self._routed(p, x, cdt)
        return a + rms_norm(f, p["norm4"], self.eps), counts

    # -------------------------------------------------------------- apply
    def apply(self, params, pooled, dense=None, counters=None):
        cdt = pooled.dtype
        h = pooled[..., 3:].astype(F32) * np.sqrt(self.hidden)
        counts = jnp.zeros((2,), jnp.int32)   # pairs held, rows fetched
        for i in range(len(self.layer_types)):
            prefix = "l%d." % i
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
            h, n = jax.checkpoint(
                lambda p, h, i=i: self._layer(i, p, h, cdt))(p, h)
            counts = counts + n
        pooled_h = rms_norm(h, params["norm_f"], self.eps).mean(axis=1)
        logits = (self.head_scale * (pooled_h @ params["w_out"].astype(F32))
                  + params["b_out"].astype(F32))
        if counters is not None:
            counters["moe_pairs_held"] = counts[0]
            counters["moe_combine_rows"] = counts[1]
        return logits

