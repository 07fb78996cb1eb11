"""Xing4.0 block stack as a behaviour-sequence tower (model_type
``xing4_0``): latent attention (MLA) with YaRN positions and a routed
SwiGLU feed-forward part, each inside a manifold-constrained
hyper-connection over a residual of ``hc_mult`` streams.

An example is a user's last S items, every position one key of one item
vocabulary, pulled from the pass table as a row of ``hidden`` trained
values; the tower over the positions is the language model's block stack
and the label is the click. The input embedding is the parameter server's
table; the blocks are the dense side, trained by the trainer's dense
optimizer.

    x0 = [e, e, ..., e]          e = embedx(row): n = hc_mult copies
    x  <- M_a(x; Attn o N_a);  x <- M_f(x; F o N_f)        every layer
    out = sum of the n streams                  (Hyper-Connections, 2024)
    N  = RMSNorm (eps, a weight), in float32

M(x; G), the hyper-connection around a sublayer G (ops/mhc.py states it):
with x the [n, C] streams of a token and x_hat = vec(x) / rms(vec(x)),
    H_pre  = sigmoid(a_pre  x_hat phi_pre + b_pre)          [1, n]
    H_post = 2 sigmoid(a_post x_hat phi_post + b_post)      [1, n]
    H_res  = Sinkhorn(a_res mat(x_hat phi_res) + b_res)     [n, n]
    M(x; G) = H_res x + H_post^T G(H_pre x)
Sinkhorn: exp of the logits clamped to ``hc_clamp``, then
``sinkhorn_iters`` rounds of rows then columns, each over its sum + hc_eps.
The streams are carried as [B, S, n hidden], stream j the column band
[j hidden, (j + 1) hidden): a lane-aligned band where hidden is a multiple
of 128, which is where ops/mhc.py's kernels take the hyper-connection.

Attn (DeepSeek-V2/V3's MLA, held whole: data-parallel attention):
    c_q = N(u W_qa)                          q_lora_rank
    q   = c_q W_qb -> heads x (nope + rope)
    [c_kv | k_r] = u W_kva                   kv_lora_rank | rope
    [k_nope | v] = N(c_kv) W_kvb -> heads x (nope + v_dim)
    q = [q_nope | R(q_rope)],  k = [k_nope | R(k_r)]   k_r one for all heads
    out = concat_h(softmax(q.k * (nope + rope)^-1/2 * m^2) v) W_o
R is rotary over pairs (2i, 2i + 1) (DeepSeek's interleaved layout) at
YaRN's frequencies (yarn_inv_freq), m = 0.1 mscale_all_dim ln(factor) + 1;
position i sees j <= i. ops/attention.py never forms the scores; q and k
are nope + rope wide, v v_dim.

F on the leading ``num_dense_layers`` layers is a SwiGLU of width
``intermediate``; on the others
    s = sigmoid(u W_r) over all num_experts;  chosen = top_k of s + b
    F(u) = sum over chosen e held here of w_e E_e(u) + E_shared(u),
    w_e = route_scale s_e / (sum of s over the chosen)
(ops/routed_experts.py): this chip holds experts [expert_offset,
expert_offset + experts_held); what the absent experts would add is
another chip's part, and nothing here stands in for it.

Head (the departure models/afmoe.py states: a click model has no
next-token head; no multi-token prediction either):
    logit = head_scale * (w_out . mean over positions of N(out)) + b_out

Every layer runs under jax.checkpoint and casts its matrices to the
compute dtype (the dtype ``pooled`` arrives in: bfloat16 under the
trainer's mixed precision) inside it: every leaf is an ``f32_params``
leaf. The streams, the norms, the three maps, Sinkhorn, the router and the
softmax are float32.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models.afmoe import rms_norm, swiglu
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.ops.attention import blocked_attention
from paddlebox_tpu.ops.mhc import fused_block, hyper_connection
from paddlebox_tpu.ops.routed_experts import route, routed_experts

F32 = jnp.float32
# the matrices: cast to the compute dtype inside a layer's checkpoint (the
# router's and the hyper-connections' phi stay float32)
_MATRICES = frozenset(("q_a", "q_b", "kv_a", "kv_b", "o", "w_gate", "w_up",
                       "w_down", "e_gate", "e_up", "e_down", "s_gate",
                       "s_up", "s_down"))
_SUBLAYERS = ("a_", "f_")       # the hyper-connections' leaves' prefixes


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor m: 0.1 mscale ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """[dim / 2] float64 inverse frequencies: theta^(-2i / dim) where a
    pair turns more than beta_fast times over ``original`` positions,
    that over ``factor`` where it turns fewer than beta_slow times, a
    linear ramp over the pairs between (DeepSeek-V3's form)."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair(turns):            # the pair that turns ``turns`` times
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(pair(beta_fast)), 0)
    hi = min(math.ceil(pair(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo)
                   / ((hi - lo) or 0.001), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: the pair keeps its frequency
    return extra / factor * (1.0 - keep) + extra * keep


class Xing4:
    """init(rng) -> params (a flat dict, ``l<i>.<leaf>`` a layer);
    apply(params, pooled [B, S, 3 + hidden], dense) -> logits [B]. A
    caller that hands ``counters`` (a dict of its own trace) gets
    ``step_counters`` put into it: "moe_pairs_held", the pairs routed to
    held experts over all routed layers, and "mhc_fused_tokens", tokens x
    sublayers whose hyper-connection took ops/mhc.py's kernels (a witness
    that they ran, decided by the shapes); the trainer adds them to
    utils/stats at a chunk's drain."""

    name = "xing4_0"
    task_names = ("ctr",)
    step_counters = ("moe_pairs_held", "mhc_fused_tokens")

    def __init__(self, spec: ModelSpec, *, num_layers: int,
                 num_dense_layers: int, hidden: int, heads: int,
                 q_lora_rank: int, kv_lora_rank: int, qk_nope_dim: int,
                 qk_rope_dim: int, v_dim: int, intermediate: int,
                 moe_intermediate: int, num_experts: int, experts_held: int,
                 expert_offset: int, top_k: int, route_scale: float,
                 hc_mult: int, sinkhorn_iters: int, hc_eps: float,
                 hc_clamp: Tuple[float, float], rope_theta: float,
                 yarn: Dict[str, float], eps: float = 1e-6,
                 head_scale: float = 1.0) -> None:
        if spec.slot_dim != 3 + hidden:
            raise ValueError(f"a pulled row serves {spec.slot_dim - 3} "
                             f"values, the tower is {hidden} wide")
        if not 0 <= expert_offset <= num_experts - experts_held:
            raise ValueError("experts held lie outside the router's outputs")
        if qk_rope_dim % 2:
            raise ValueError("rotary turns pairs: qk_rope_dim must be even")
        self.spec = spec
        self.num_layers, self.num_dense_layers = num_layers, num_dense_layers
        self.hidden, self.heads = hidden, heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.nope, self.rope, self.v_dim = qk_nope_dim, qk_rope_dim, v_dim
        self.intermediate, self.moe_intermediate = (intermediate,
                                                    moe_intermediate)
        self.num_experts, self.experts_held = num_experts, experts_held
        self.expert_offset, self.top_k = expert_offset, top_k
        self.route_scale = float(route_scale)
        self.n = int(hc_mult)
        self.hc = dict(iters=int(sinkhorn_iters), eps=float(hc_eps),
                       clamp=tuple(float(c) for c in hc_clamp),
                       norm_eps=float(eps))
        self.eps, self.head_scale = float(eps), float(head_scale)
        factor = float(yarn["factor"])
        self.inv_freq = yarn_inv_freq(
            qk_rope_dim, float(rope_theta), factor,
            int(yarn["original_max_position_embeddings"]),
            float(yarn["beta_fast"]), float(yarn["beta_slow"]))
        m_all = yarn_mscale(factor, float(yarn["mscale_all_dim"]))
        # cos and sin carry m(mscale) / m(mscale_all_dim); the scores m^2
        self.rot_scale = yarn_mscale(factor, float(yarn["mscale"])) / m_all
        self.scale = (qk_nope_dim + qk_rope_dim) ** -0.5 * m_all * m_all
        # every leaf: the layers cast their own matrices (module docstring)
        self.f32_params = tuple(self.shapes())

    # ------------------------------------------------------------ params
    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        H, n, nh = self.hidden, self.n, self.heads
        maps = 2 * n + n * n
        out: Dict[str, Tuple[int, ...]] = {}
        for i in range(self.num_layers):
            leaves = {"attn_norm": (H,), "q_a": (H, self.q_lora_rank),
                      "q_a_norm": (self.q_lora_rank,),
                      "q_b": (self.q_lora_rank, nh * (self.nope + self.rope)),
                      "kv_a": (H, self.kv_lora_rank + self.rope),
                      "kv_a_norm": (self.kv_lora_rank,),
                      "kv_b": (self.kv_lora_rank,
                               nh * (self.nope + self.v_dim)),
                      "o": (nh * self.v_dim, H), "ffn_norm": (H,)}
            for pre in _SUBLAYERS:
                leaves.update({pre + "phi": (n * H, maps),
                               pre + "alpha": (3,), pre + "bias": (maps,)})
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
        """Matrices normal / sqrt(inputs); norms 1; the maps' scales a
        0.01 and their biases 0 (every sublayer starts reading and
        writing each stream alike, H_res the flat average)."""
        params = {}
        for (name, shape), key in zip(
                sorted(self.shapes().items()),
                jax.random.split(rng, len(self.shapes()))):
            leaf = name.rpartition(".")[2]
            if "norm" in leaf:
                params[name] = jnp.ones(shape, F32)
            elif leaf.endswith("alpha"):
                params[name] = jnp.full(shape, 0.01, F32)
            elif leaf.endswith("bias") or leaf in ("router_b", "b_out"):
                params[name] = jnp.zeros(shape, F32)
            else:
                fan_in = shape[-2] if len(shape) > 1 else shape[0]
                params[name] = (jax.random.normal(key, shape, F32)
                                / np.sqrt(fan_in))
        return params

    # ------------------------------------------------------------- layers
    def _rotary(self, x, positions: int):
        """x [B, S, h, rope]: pair (2i, 2i + 1) turned by position x
        inv_freq[i]; the result laid out [evens | odds], as DeepSeek's
        apply_rotary_pos_emb leaves it (q and k alike: the scores are
        the same)."""
        ang = np.arange(positions, dtype=np.float64)[:, None] * self.inv_freq
        cos = jnp.asarray(np.cos(ang) * self.rot_scale, F32)[:, None, :]
        sin = jnp.asarray(np.sin(ang) * self.rot_scale, F32)[:, None, :]
        x = x.astype(F32)
        xe, xo = x[..., 0::2], x[..., 1::2]
        return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin],
                               axis=-1)

    def _mla(self, p, u, cdt):
        B, S, _ = u.shape
        nh, dn, dr = self.heads, self.nope, self.rope
        with jax.named_scope("mla_proj"):
            xc = u.astype(cdt)
            cq = rms_norm(xc @ p["q_a"], p["q_a_norm"], self.eps)
            q = (cq.astype(cdt) @ p["q_b"]).reshape(B, S, nh, dn + dr)
            kv = xc @ p["kv_a"]
            ckv = rms_norm(kv[..., :self.kv_lora_rank], p["kv_a_norm"],
                           self.eps)
            kvb = (ckv.astype(cdt) @ p["kv_b"]).reshape(
                B, S, nh, dn + self.v_dim)
            q_rot = self._rotary(q[..., dn:], S).astype(cdt)
            k_rot = self._rotary(kv[:, :, None, self.kv_lora_rank:],
                                 S).astype(cdt)
            q = jnp.concatenate([q[..., :dn], q_rot], axis=-1)
            k = jnp.concatenate(
                [kvb[..., :dn], jnp.broadcast_to(k_rot, (B, S, nh, dr))],
                axis=-1)
            q, k, v = (a.transpose(0, 2, 1, 3)
                       for a in (q, k, kvb[..., dn:]))
        with jax.named_scope("attn_mla"):
            o = blocked_attention(q, k, v, scale=self.scale)
        with jax.named_scope("mla_proj"):
            return o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ p["o"]

    def _routed(self, p, u, cdt):
        """(F(u) [B, S, H] float32, pairs routed to each held expert)."""
        B, S, H = u.shape
        flat = u.reshape(B * S, H)
        with jax.named_scope("moe_route"):
            experts, weights = route(flat, p["router_w"], p["router_b"],
                                     self.top_k, self.route_scale)
        xc = flat.astype(cdt)
        y, sizes = routed_experts(xc, experts, weights, p["e_gate"],
                                  p["e_up"], p["e_down"], self.expert_offset,
                                  self.num_experts)
        with jax.named_scope("moe_shared"):
            shared = swiglu(xc, p["s_gate"], p["s_up"], p["s_down"])
        return (y + shared.astype(F32)).reshape(B, S, H), sizes

    def _ffn(self, i: int, p, u, cdt):
        """(F(N_f(u)), pairs routed to each held expert: nought on a dense
        layer)."""
        u = rms_norm(u, p["ffn_norm"], self.eps)
        if i >= self.num_dense_layers:
            return self._routed(p, u, cdt)
        with jax.named_scope("dense_mlp"):
            f = swiglu(u.astype(cdt), p["w_gate"], p["w_up"], p["w_down"])
        return f, jnp.zeros((self.experts_held,), jnp.int32)

    def _connected(self, p, pre: str, x, sublayer):
        """(M(x; sublayer), what the sublayer hands back beside its
        output): the hyper-connection around one sublayer."""
        return hyper_connection(x, p[pre + "phi"], p[pre + "alpha"],
                                p[pre + "bias"], sublayer, n=self.n,
                                **self.hc)

    def _layer(self, i: int, p, x, cdt):
        """(x' [B, S, n H], pairs routed to each held expert). The first
        layer gets the embedding [B, S, H] and copies it into the n
        streams here, inside its checkpoint: what it keeps for the
        backward pass is the one copy."""
        p = {k: (v.astype(cdt) if k in _MATRICES else v)
             for k, v in p.items()}
        if i == 0:
            x = jnp.tile(x, (1, 1, self.n))
        x, _ = self._connected(p, "a_", x, lambda u: (self._mla(
            p, rms_norm(u, p["attn_norm"], self.eps), cdt), None))
        return self._connected(p, "f_", x,
                               lambda u: self._ffn(i, p, u, cdt))

    # -------------------------------------------------------------- apply
    def apply(self, params, pooled, dense=None, counters=None):
        cdt = pooled.dtype
        x = pooled[..., 3:].astype(F32)
        held = jnp.zeros((), jnp.int32)
        for i in range(self.num_layers):
            prefix = "l%d." % i
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
            x, sizes = jax.checkpoint(
                lambda p, x, i=i: self._layer(i, p, x, cdt))(p, x)
            held = held + sizes.sum()
        H = self.hidden
        out = sum(x[..., j * H:(j + 1) * H] for j in range(self.n))
        pooled_h = rms_norm(out, params["norm_f"], self.eps).mean(axis=1)
        logits = (self.head_scale * (pooled_h @ params["w_out"].astype(F32))
                  + params["b_out"].astype(F32))
        if counters is not None:
            B, S = pooled.shape[:2]
            fused = 2 * self.num_layers if fused_block(B * S, self.n, H) else 0
            counters["moe_pairs_held"] = held
            counters["mhc_fused_tokens"] = jnp.asarray(B * S * fused,
                                                       jnp.int32)
        return logits
