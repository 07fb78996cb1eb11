"""Granite 4.0-H block stack as a behaviour-sequence tower (model_type
``granitemoehybrid`` with no experts: IBM Granite 4.0-H Micro): Mamba-2
state-space layers with an attention layer without positions among them.

An example is a user's last S items, every position one key of one item
vocabulary, pulled from the pass table as a row of ``hidden`` trained
values; the tower over the positions is the language model's block stack
and the label is the click. The input embedding is the parameter server's
table; the blocks are the dense side, trained by the trainer's dense
optimizer.

    x0 = embedx(row) * embedding_multiplier                [B, S, hidden]
    a  = h + r * Mix(N1(h));   h' = a + r * MLP(N2(a))     every layer
    r  = residual_multiplier;  N = RMSNorm (eps, a weight), in float32
    MLP(x) = (silu(x Wg) * (x Wu)) Wd,  [Wg | Wu] = mlp_in [hidden, 2 F]

Mix on an ``attention`` layer: q = x Wq -> heads x head_dim; k = x Wk,
v = x Wv -> kv_heads x head_dim; no rotary, no norm on q or k, no bias;
query head i reads key-value head i // (heads // kv_heads); scores q.k *
attention_multiplier; position i sees j <= i; out = (softmax v) Wo.
ops/attention.py never forms the scores.

Mix on a ``mamba`` layer (Mamba-2, one group; d_inner = ssm_heads x
ssm_head_dim, N = ssm_state):
    [z | xBC | dt] = x in_proj          d_inner | d_inner + 2 N | ssm_heads
    xBC = silu(conv(xBC))               depthwise, causal, conv_w [K, C] + conv_b
    [x | B | C] = xBC                   B, C: N values, shared by every head
    dt = softplus(dt + dt_bias);  A = -exp(A_log)           a scalar a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;   y_t = h_t C_t + D x_t
    out = N_g(y * silu(z)) out_proj     N_g: an RMSNorm over all d_inner
ops/ssd.py computes the recurrence in chunks of ``chunk`` positions.

Head (the departure models/afmoe.py states: a click model has no
next-token head, so no vocabulary-sized output matrix is held):
    logit = head_scale * (w_out . mean over positions of N(h_last)) + b_out

Every layer runs under jax.checkpoint, and casts its matrices to the
compute dtype (the dtype ``pooled`` arrives in: bfloat16 under the
trainer's mixed precision) INSIDE it: every leaf is an ``f32_params`` leaf,
so the trainer hands the float32 master weights through and no second copy
of 746M weights lives from the forward pass to the backward. The residual
stream, the norms, the softmax, dt, A, the decays and the carried state
are float32.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.afmoe import rms_norm
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.ops.attention import blocked_attention
from paddlebox_tpu.ops.ssd import causal_conv, chunks_scanned, ssd_scan

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"
# the matrices: cast to the compute dtype inside a layer's checkpoint
_MATRICES = frozenset(("in_proj", "out_proj", "wq", "wk", "wv", "wo",
                       "mlp_in", "mlp_out"))


class GraniteHybrid:
    """init(rng) -> params (a flat dict, ``l<i>.<leaf>`` a layer);
    apply(params, pooled [B, S, 3 + hidden], dense) -> logits [B]. A
    caller that hands ``counters`` (a dict of its own trace) gets
    ``step_counters`` put into it: "ssd_chunks_scanned", the chunks the
    state-space layers carried a state across (sequences x chunks x
    layers); the trainer adds it to utils/stats at a chunk's drain."""

    name = "granite_hybrid"
    task_names = ("ctr",)
    step_counters = ("ssd_chunks_scanned",)

    def __init__(self, spec: ModelSpec, *, layer_types: Sequence[str],
                 hidden: int, intermediate: int, heads: int, kv_heads: int,
                 head_dim: int, attention_multiplier: float,
                 ssm_heads: int, ssm_head_dim: int, ssm_state: int,
                 ssm_groups: int = 1, conv_kernel: int = 4,
                 chunk: int = 256, embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0, eps: float = 1e-5,
                 head_scale: float = 1.0) -> None:
        if spec.slot_dim != 3 + hidden:
            raise ValueError(f"a pulled row serves {spec.slot_dim - 3} "
                             f"values, the tower is {hidden} wide")
        if any(t not in (MAMBA, ATTENTION) for t in layer_types):
            raise ValueError(f"layer_types {layer_types!r}")
        if ssm_groups != 1:
            raise ValueError("this tower's mixer splits one group of B and "
                             f"C and norms all of d_inner; {ssm_groups} "
                             "groups asked (models/nemotron_h.py has them)")
        self.spec = spec
        self.layer_types = tuple(layer_types)
        self.hidden, self.intermediate = hidden, intermediate
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.attention_multiplier = float(attention_multiplier)
        self.ssm_heads, self.ssm_head_dim = ssm_heads, ssm_head_dim
        self.ssm_state, self.conv_kernel = ssm_state, conv_kernel
        self.chunk = int(chunk)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.eps, self.head_scale = float(eps), float(head_scale)
        # every leaf: the layers cast their own matrices (module docstring)
        self.f32_params = tuple(self.shapes())

    # ------------------------------------------------------------ params
    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        H, F = self.hidden, self.intermediate
        inner = self.ssm_heads * self.ssm_head_dim
        conv = inner + 2 * self.ssm_state
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        out: Dict[str, Tuple[int, ...]] = {}
        for i, kind in enumerate(self.layer_types):
            if kind == MAMBA:
                leaves = {"in_proj": (H, inner + conv + self.ssm_heads),
                          "conv_w": (self.conv_kernel, conv),
                          "conv_b": (conv,), "dt_bias": (self.ssm_heads,),
                          "A_log": (self.ssm_heads,),
                          "D": (self.ssm_heads,), "gnorm": (inner,),
                          "out_proj": (inner, H)}
            else:
                leaves = {"wq": (H, q), "wk": (H, kv), "wv": (H, kv),
                          "wo": (q, H)}
            leaves.update(norm1=(H,), norm2=(H,), mlp_in=(H, 2 * F),
                          mlp_out=(F, H))
            out.update({"l%d.%s" % (i, k): v for k, v in leaves.items()})
        out.update(norm_f=(H,), w_out=(H,), b_out=())
        return out

    def init(self, rng):
        """Matrices normal / sqrt(inputs); the state-space scalars in the
        ranges Mamba-2 is published with: A = -exp(A_log) over [-16, -1],
        softplus(dt_bias) log-uniform over [0.001, 0.1], D = 1."""
        params = {}
        for (name, shape), key in zip(
                sorted(self.shapes().items()),
                jax.random.split(rng, len(self.shapes()))):
            leaf = name.rpartition(".")[2]
            if "norm" in leaf or leaf == "D":
                params[name] = jnp.ones(shape, F32)
            elif leaf in ("conv_b", "b_out"):
                params[name] = jnp.zeros(shape, F32)
            elif leaf == "A_log":
                params[name] = jnp.log(jax.random.uniform(
                    key, shape, F32, 1.0, 16.0))
            elif leaf == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    key, shape, F32, np.log(1e-3), np.log(1e-1)))
                params[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                fan_in = shape[-2] if len(shape) > 1 else shape[0]
                params[name] = (jax.random.normal(key, shape, F32)
                                / np.sqrt(fan_in))
        return params

    # ------------------------------------------------------------- layers
    def _attention(self, p, x, cdt):
        B, S, _ = x.shape
        xc = x.astype(cdt)

        def heads(w, n):
            return (xc @ w).reshape(B, S, n, self.head_dim).transpose(
                0, 2, 1, 3)
        q, k, v = (heads(p["wq"], self.heads), heads(p["wk"], self.kv_heads),
                   heads(p["wv"], self.kv_heads))
        with jax.named_scope("attn_full"):
            o = blocked_attention(q, k, v, None, self.attention_multiplier)
        return o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ p["wo"]

    def _mamba(self, p, x, cdt):
        B, S, _ = x.shape
        Hs, P, N = self.ssm_heads, self.ssm_head_dim, self.ssm_state
        inner = Hs * P
        with jax.named_scope("ssm_proj"):
            zxbcdt = x.astype(cdt) @ p["in_proj"]
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * N], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
        xs, Bm, Cm = jnp.split(xbc, [inner, inner + N], axis=-1)
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(xs.reshape(B, S, Hs, P),
                         jax.nn.softplus(dt.astype(F32) + p["dt_bias"]),
                         -jnp.exp(p["A_log"]), Bm, Cm, p["D"], self.chunk)
        with jax.named_scope("ssm_gate_norm"):
            y = y.reshape(B, S, inner).astype(F32)
            gated = rms_norm(y * jax.nn.silu(z.astype(F32)), p["gnorm"],
                             self.eps)
        with jax.named_scope("ssm_proj"):
            return gated.astype(cdt) @ p["out_proj"]

    def _layer(self, i: int, p, h, cdt):
        p = {k: (v.astype(cdt) if k in _MATRICES else v)
             for k, v in p.items()}
        mix = self._mamba if self.layer_types[i] == MAMBA else self._attention
        r = self.residual_multiplier
        a = h + r * mix(p, rms_norm(h, p["norm1"], self.eps),
                        cdt).astype(F32)
        x = rms_norm(a, p["norm2"], self.eps).astype(cdt)
        with jax.named_scope("dense_mlp"):
            gate, up = jnp.split(x @ p["mlp_in"], 2, axis=-1)
            f = (jax.nn.silu(gate) * up) @ p["mlp_out"]
        return a + r * f.astype(F32)

    # -------------------------------------------------------------- apply
    def apply(self, params, pooled, dense=None, counters=None):
        cdt = pooled.dtype
        h = pooled[..., 3:].astype(F32) * self.embedding_multiplier
        for i in range(len(self.layer_types)):
            prefix = "l%d." % i
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
            h = jax.checkpoint(
                lambda p, h, i=i: self._layer(i, p, h, cdt))(p, h)
        pooled_h = rms_norm(h, params["norm_f"], self.eps).mean(axis=1)
        logits = (self.head_scale * (pooled_h @ params["w_out"].astype(F32))
                  + params["b_out"].astype(F32))
        if counters is not None:
            B, S = pooled.shape[:2]
            counters["ssd_chunks_scanned"] = jnp.asarray(
                chunks_scanned(B, S, self.chunk)
                * self.layer_types.count(MAMBA), jnp.int32)
        return logits
