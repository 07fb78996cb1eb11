"""Nemotron-H block stack as a behaviour-sequence tower (model_type
``nemotron_h``: NVIDIA Nemotron-3): layers that are a mixer OR a
feed-forward part alone, each told what share of its heads, columns and
experts this chip holds.

An example is a user's last S items, every position one key of one item
vocabulary, pulled from the pass table as a row of ``hidden`` trained
values; the tower over the positions is the language model's block stack
and the label is the click. The input embedding is the parameter server's
table; the blocks are the dense side, trained by the trainer's dense
optimizer.

    x0 = embedx(row)                                       [B, S, hidden]
    h' = h + Mix_t(N(h))       every layer: ONE part, ONE norm
    N  = RMSNorm (eps, a weight), in float32
    t  = pattern[i]: "M" state-space, "*" attention, "E" LatentMoE

Mix "M" (Mamba-2 with groups; ``ssm_groups_held`` of the ``ssm_groups``
groups live here, each with its ssm_heads / ssm_groups heads; inner =
heads held x ssm_head_dim, G = groups held, N = ssm_state):
    [z | xBC | dt] = u in_proj          inner | inner + 2 G N | heads held
    xBC = silu(conv(xBC))           depthwise, causal, conv_w [K, C] + conv_b
    [x | B | C] = xBC               B, C: [G, N], a head reads its group's
    dt = softplus(dt + dt_bias);  A = -exp(A_log)           a scalar a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t[g]
    y_t = h_t C_t[g] + D x_t
    out = N_g(y * silu(z)) out_proj     N_g: an RMSNorm over EACH GROUP's
                                        inner / G values, so whole groups
                                        need nothing from another chip
ops/ssd.py computes the recurrence in chunks of ``chunk`` positions.

Mix "*": q = u Wq -> heads held x head_dim; k = u Wk, v = u Wv -> the
key-value heads those query heads read (query head i of the model reads
key-value head i // (heads / kv_heads)); no rotary, no norm on q or k, no
bias; scores q.k / sqrt(head_dim); position i sees j <= i; out =
(softmax v) Wo. ops/attention.py never forms the scores.

Mix "E" (LatentMoE): the router scores the full hidden state over ALL
``num_experts`` (ops/routed_experts.route: sigmoid, top_k of s + b,
weights route_scale x s_e / sum of the chosen s); the experts work in a
latent of ``latent`` values and are ungated:
    z   = u fc1                                   hidden -> latent
    y   = sum over chosen e held here of w_e relu(z W1_e)^2 W2_e
    out = y fc2 + relu(u s_up)^2 s_down          the shared expert reads u,
                                                 ``shared_held`` of its columns
This chip holds experts [expert_offset, expert_offset + experts_held).

What the absent heads, columns and experts would have added is another
chip's part: out_proj, Wo, s_down and the routed sum give PARTIAL sums,
the partial result goes on to the next layer, and nothing here stands in
for the other chips or their exchange.

Head (the departure models/afmoe.py states: a click model has no
next-token head, so no vocabulary-sized output matrix is held; no
multi-token prediction either):
    logit = head_scale * (w_out . mean over positions of N(h_last)) + b_out

Every layer runs under jax.checkpoint, and casts its matrices to the
compute dtype (the dtype ``pooled`` arrives in: bfloat16 under the
trainer's mixed precision) INSIDE it: every leaf is an ``f32_params`` leaf,
so the trainer hands the float32 master weights through and no second copy
of the weights lives from the forward pass to the backward. The residual
stream, the norms, the router, the softmax, dt, A, the decays and the
carried state are float32.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.afmoe import rms_norm
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.ops.attention import blocked_attention
from paddlebox_tpu.ops.routed_experts import route, routed_experts
from paddlebox_tpu.ops.ssd import causal_conv, chunks_scanned, ssd_scan

F32 = jnp.float32
MAMBA, ATTENTION, MOE = "M", "*", "E"
# the matrices: cast to the compute dtype inside a layer's checkpoint (the
# router's stays float32)
_MATRICES = frozenset(("in_proj", "out_proj", "wq", "wk", "wv", "wo", "fc1",
                       "fc2", "e_up", "e_down", "s_up", "s_down"))


def relu2(x):
    return jnp.square(jax.nn.relu(x.astype(F32))).astype(x.dtype)


class NemotronH:
    """init(rng) -> params (a flat dict, ``l<i>.<leaf>`` a layer);
    apply(params, pooled [B, S, 3 + hidden], dense) -> logits [B]. A
    caller that hands ``counters`` (a dict of its own trace) gets
    ``step_counters`` put into it: "ssd_chunks_scanned" (sequences x chunks
    x state-space layers), "moe_pairs_held" (the pairs routed to held
    experts, all LatentMoE layers) and "moe_pairs_max_expert" (the fullest
    held expert's pairs, summed over those layers: the imbalance the
    grouped products pay for) and "moe_combine_rows" (the rows those
    layers' forward combines fetched, ops/routed_experts.routed_experts);
    the trainer adds them to utils/stats at a chunk's drain."""

    name = "nemotron_h"
    task_names = ("ctr",)
    step_counters = ("ssd_chunks_scanned", "moe_pairs_held",
                     "moe_pairs_max_expert", "moe_combine_rows")

    def __init__(self, spec: ModelSpec, *, pattern: Sequence[str],
                 hidden: int, ssm_heads: int, ssm_head_dim: int,
                 ssm_state: int, ssm_groups: int, ssm_groups_held: int,
                 chunk: int, heads: int, kv_heads: int, head_dim: int,
                 heads_held: int, head_offset: int, latent: int,
                 moe_intermediate: int, shared_held: int, num_experts: int,
                 experts_held: int, expert_offset: int, top_k: int,
                 route_scale: float, conv_kernel: int = 4,
                 eps: float = 1e-5, head_scale: float = 1.0) -> None:
        if spec.slot_dim != 3 + hidden:
            raise ValueError(f"a pulled row serves {spec.slot_dim - 3} "
                             f"values, the tower is {hidden} wide")
        if any(t not in (MAMBA, ATTENTION, MOE) for t in pattern):
            raise ValueError(f"pattern {pattern!r}")
        if ssm_heads % ssm_groups or not 0 < ssm_groups_held <= ssm_groups:
            raise ValueError(f"{ssm_groups_held} of {ssm_groups} groups over "
                             f"{ssm_heads} state-space heads")
        if heads % kv_heads or not 0 <= head_offset <= heads - heads_held:
            raise ValueError("query heads held lie outside the model's")
        if not 0 <= expert_offset <= num_experts - experts_held:
            raise ValueError("experts held lie outside the router's outputs")
        self.spec = spec
        self.pattern = tuple(pattern)
        self.hidden = hidden
        self.ssm_groups_held = ssm_groups_held
        self.ssm_heads_held = ssm_groups_held * (ssm_heads // ssm_groups)
        self.ssm_head_dim, self.ssm_state = ssm_head_dim, ssm_state
        self.conv_kernel, self.chunk = conv_kernel, int(chunk)
        self.heads_held, self.head_dim = heads_held, head_dim
        # the key-value heads that query heads [offset, offset + held) read
        per_kv = heads // kv_heads
        self.kv_heads_held = ((head_offset + heads_held - 1) // per_kv
                              - head_offset // per_kv + 1)
        if self.kv_heads_held > 1 and (head_offset % per_kv
                                       or heads_held % per_kv):
            raise ValueError("query heads held over several key-value "
                             "heads must hold each one's whole group")
        self.latent, self.moe_intermediate = latent, moe_intermediate
        self.shared_held = shared_held
        self.num_experts, self.experts_held = num_experts, experts_held
        self.expert_offset, self.top_k = expert_offset, top_k
        self.route_scale = float(route_scale)
        self.eps, self.head_scale = float(eps), float(head_scale)
        # every leaf: the layers cast their own matrices (module docstring)
        self.f32_params = tuple(self.shapes())

    # ------------------------------------------------------------ params
    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        H, Hs = self.hidden, self.ssm_heads_held
        inner = Hs * self.ssm_head_dim
        conv = inner + 2 * self.ssm_groups_held * self.ssm_state
        q = self.heads_held * self.head_dim
        kv = self.kv_heads_held * self.head_dim
        Z, F, E = self.latent, self.moe_intermediate, self.experts_held
        kinds = {
            MAMBA: {"in_proj": (H, inner + conv + Hs),
                    "conv_w": (self.conv_kernel, conv), "conv_b": (conv,),
                    "dt_bias": (Hs,), "A_log": (Hs,), "D": (Hs,),
                    "gnorm": (inner,), "out_proj": (inner, H)},
            ATTENTION: {"wq": (H, q), "wk": (H, kv), "wv": (H, kv),
                        "wo": (q, H)},
            MOE: {"router_w": (H, self.num_experts),
                  "router_b": (self.num_experts,), "fc1": (H, Z),
                  "fc2": (Z, H), "e_up": (E, Z, F), "e_down": (E, F, Z),
                  "s_up": (H, self.shared_held),
                  "s_down": (self.shared_held, H)}}
        out: Dict[str, Tuple[int, ...]] = {}
        for i, kind in enumerate(self.pattern):
            out["l%d.norm" % i] = (H,)
            out.update({"l%d.%s" % (i, k): v
                        for k, v in kinds[kind].items()})
        out.update(norm_f=(H,), w_out=(H,), b_out=())
        return out

    def init(self, rng):
        """Matrices normal / sqrt(inputs); the state-space scalars in the
        ranges the model is published with (time_step_min / _max): A =
        -exp(A_log) over [-16, -1], softplus(dt_bias) log-uniform over
        [0.001, 0.1], D = 1."""
        params = {}
        for (name, shape), key in zip(
                sorted(self.shapes().items()),
                jax.random.split(rng, len(self.shapes()))):
            leaf = name.rpartition(".")[2]
            if "norm" in leaf or leaf == "D":
                params[name] = jnp.ones(shape, F32)
            elif leaf in ("conv_b", "router_b", "b_out"):
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
    def _mamba(self, p, u, cdt):
        B, S, _ = u.shape
        Hs, P, N = self.ssm_heads_held, self.ssm_head_dim, self.ssm_state
        G, inner = self.ssm_groups_held, Hs * P
        with jax.named_scope("ssm_proj"):
            zxbcdt = u.astype(cdt) @ p["in_proj"]
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * G * N],
                               axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
        xs, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(xs.reshape(B, S, Hs, P),
                         jax.nn.softplus(dt.astype(F32) + p["dt_bias"]),
                         -jnp.exp(p["A_log"]), Bm.reshape(B, S, G, N),
                         Cm.reshape(B, S, G, N), p["D"], self.chunk)
        with jax.named_scope("ssm_gate_norm"):
            y = y.reshape(B, S, inner).astype(F32)
            gated = rms_norm(
                (y * jax.nn.silu(z.astype(F32))).reshape(B, S, G, inner // G),
                p["gnorm"].reshape(G, inner // G), self.eps)
        with jax.named_scope("ssm_proj"):
            return gated.reshape(B, S, inner).astype(cdt) @ p["out_proj"]

    def _attention(self, p, u, cdt):
        B, S, _ = u.shape
        xc = u.astype(cdt)

        def heads(w, n):
            return (xc @ w).reshape(B, S, n, self.head_dim).transpose(
                0, 2, 1, 3)
        q, k, v = (heads(p["wq"], self.heads_held),
                   heads(p["wk"], self.kv_heads_held),
                   heads(p["wv"], self.kv_heads_held))
        with jax.named_scope("attn_full"):
            o = blocked_attention(q, k, v)
        return o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ p["wo"]

    def _latent_moe(self, p, u, cdt):
        B, S, H = u.shape
        flat = u.reshape(B * S, H)
        with jax.named_scope("moe_route"):
            experts, weights = route(flat, p["router_w"], p["router_b"],
                                     self.top_k, self.route_scale)
        xc = flat.astype(cdt)
        with jax.named_scope("moe_latent"):
            z = xc @ p["fc1"]
        y, sizes, fetched = routed_experts(
            z, experts, weights, None, p["e_up"], p["e_down"],
            self.expert_offset, self.num_experts)
        with jax.named_scope("moe_latent"):
            out = y.astype(cdt) @ p["fc2"]
        with jax.named_scope("moe_shared"):
            shared = relu2(xc @ p["s_up"]) @ p["s_down"]
        return ((out.astype(F32) + shared.astype(F32)).reshape(B, S, H),
                sizes, fetched)

    def _layer(self, i: int, p, h, cdt):
        """(h', pairs routed to each held expert, rows the combine
        fetched: nought off an E layer)."""
        p = {k: (v.astype(cdt) if k in _MATRICES else v)
             for k, v in p.items()}
        u = rms_norm(h, p["norm"], self.eps)
        sizes = jnp.zeros((self.experts_held,), jnp.int32)
        fetched = jnp.zeros((), jnp.int32)
        if self.pattern[i] == MOE:
            mixed, sizes, fetched = self._latent_moe(p, u, cdt)
        elif self.pattern[i] == MAMBA:
            mixed = self._mamba(p, u, cdt)
        else:
            mixed = self._attention(p, u, cdt)
        return h + mixed.astype(F32), sizes, fetched

    # -------------------------------------------------------------- apply
    def apply(self, params, pooled, dense=None, counters=None):
        cdt = pooled.dtype
        h = pooled[..., 3:].astype(F32)
        held = fullest = rows = jnp.zeros((), jnp.int32)
        for i in range(len(self.pattern)):
            prefix = "l%d." % i
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
            h, sizes, fetched = jax.checkpoint(
                lambda p, h, i=i: self._layer(i, p, h, cdt))(p, h)
            held, fullest = held + sizes.sum(), fullest + sizes.max()
            rows = rows + fetched
        pooled_h = rms_norm(h, params["norm_f"], self.eps).mean(axis=1)
        logits = (self.head_scale * (pooled_h @ params["w_out"].astype(F32))
                  + params["b_out"].astype(F32))
        if counters is not None:
            B, S = pooled.shape[:2]
            counters["ssd_chunks_scanned"] = jnp.asarray(
                chunks_scanned(B, S, self.chunk)
                * self.pattern.count(MAMBA), jnp.int32)
            counters["moe_pairs_held"] = held
            counters["moe_pairs_max_expert"] = fullest
            counters["moe_combine_rows"] = rows
        return logits
