"""The Mamba-2 state-space recurrence over the positions one chip holds, as
the chunked algorithm (state-space duality), and the causal depthwise
convolution that runs before it.

The recurrence, a head at a time (x_t [P], dt_t and A scalars of the head,
h a [P, N] state, h_{-1} = 0; B_t and C_t [N] are those of the head's
GROUP: the heads are cut into ``groups`` runs of equal length and a run
shares one B and one C; one group, every head the same pair, is Mamba-2's
plain case):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t[g(head)]
    y_t = h_t C_t[g(head)] + D x_t

ssd_scan never walks it position by position and never holds a state per
position. Positions are cut into chunks of ``chunk``. Inside a chunk the
output is a masked product: (C B^T)[i, j] exp(sum_{j<k<=i} dt_k A) applied
to dt_j x_j, j <= i, which is matmul work; a chunk's own contribution to the
state is one more product; across chunks the [P, N] states are carried by a
lax.scan over the S / chunk chunks (16 at 4,096 positions and a chunk of
256, 32 at 128: the caller states its model's chunk), and what a chunk
gets from its predecessors is C_i applied to the carried state under the
decay since the chunk's start. The backward pass is autodiff's of
that form under jax.checkpoint: it holds a state per chunk, [S / chunk, H,
P, N], never [S, H, P, N]. The chunk size changes the order of the sums and
nothing else (tests/test_granite_hybrid.py holds 64 / 128 / 256 equal).
Groups share nothing, so several are one group's computation mapped over
the group axis (jax.vmap): one group runs exactly the code it ran before
groups were taken.

x, B and C arrive in the compute dtype and the products take them so,
summing in float32; dt, A, the decays and the carried state are float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                ) -> jnp.ndarray:
    """Depthwise causal convolution over positions, zeros before position
    0: out_t = b + sum_k w[k] * x_{t - (K - 1) + k}.

    x [B, S, C]; w [K, C] (tap k of every channel; the last tap reads the
    position itself); b [C]. Summed in float32, returned in x's dtype."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = b.astype(F32)
    for k in range(K):
        out = out + padded[:, k:k + S].astype(F32) * w[k].astype(F32)
    return out.astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _chunked(x, dt, A, Bm, Cm, D, L):
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // L
    cdt = x.dtype
    # log-decay of each step, and its running sum inside a chunk
    cum = jnp.cumsum((dt * A).reshape(Bsz, nc, L, H), axis=2)
    cum_h = cum.transpose(0, 1, 3, 2)                     # [B, nc, H, L]
    xs = (x.astype(F32) * dt[..., None]).reshape(Bsz, nc, L, H, P)
    Bc, Cc = Bm.reshape(Bsz, nc, L, N), Cm.reshape(Bsz, nc, L, N)

    # inside a chunk: position i reads j <= i under the decay between them
    # (masked before the exp: above the diagonal the sum is positive)
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    decay = jnp.exp(jnp.where(
        j <= i, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc, preferred_element_type=F32)
    y = jnp.einsum("bchij,bcjhp->bcihp",
                   (cb[:, :, None] * decay).astype(cdt), xs.astype(cdt),
                   preferred_element_type=F32)

    # what a chunk adds to the state by its end, and the chunk's own decay
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)             # [B, nc, L, H]
    states = jnp.einsum("bcjhp,bcjn->bchpn",
                        (xs * to_end[..., None]).astype(cdt), Bc,
                        preferred_element_type=F32)
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # [B, nc, H]

    # across chunks: the state each chunk starts from
    def carry(h, step):
        add, keep = step
        return keep[..., None, None] * h + add, h
    _, before = jax.lax.scan(
        carry, jnp.zeros((Bsz, H, P, N), F32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    before = before.transpose(1, 0, 2, 3, 4)              # [B, nc, H, P, N]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcin,bchpn->bcihp", Cc, before.astype(cdt),
        preferred_element_type=F32)
    y = y.reshape(Bsz, S, H, P)
    if D is not None:
        y = y + D[:, None] * x.astype(F32)
    return y.astype(cdt)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, D: Optional[jnp.ndarray],
             chunk: int) -> jnp.ndarray:
    """y of the recurrence above in chunks of ``chunk`` positions.

    x [B, S, H, P]; dt [B, S, H] float32 (positive: after its softplus); A
    [H] float32 (negative); D [H] or None (no skip). Bm, Cm [B, S, N]: one
    group, read by every head; or [B, S, G, N]: head h reads group
    h // (H // G), H a multiple of G. Returns [B, S, H, P] in x's dtype.
    S may be any length: the tail is padded to whole chunks with dt = 0,
    a step that neither decays nor adds, and cut off the result."""
    S, H = x.shape[1:3]
    pad = -S % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (x, dt, Bm, Cm))
    dt, A = dt.astype(F32), A.astype(F32)
    D = None if D is None else D.astype(F32)
    if Bm.ndim == 3:
        return _chunked(x, dt, A, Bm, Cm, D, chunk)[:, :S]
    G = Bm.shape[2]
    if H % G:
        raise ValueError(f"{H} heads over {G} groups of B and C")

    def by_group(a, axis):      # the head axis as [groups, heads a group]
        return a.reshape(a.shape[:axis] + (G, H // G) + a.shape[axis + 1:])
    y = jax.vmap(lambda x, dt, A, Bm, Cm, D: _chunked(
        x, dt, A, Bm, Cm, D, chunk), in_axes=(2, 2, 0, 2, 2, 0),
        out_axes=2)(by_group(x, 2), by_group(dt, 2), by_group(A, 0), Bm, Cm,
                    None if D is None else by_group(D, 0))
    return y.reshape(x.shape)[:, :S]


def chunks_scanned(batch: int, seq: int, chunk: int) -> int:
    """Chunks ssd_scan carries a state across for ``batch`` sequences of
    ``seq`` positions in chunks of ``chunk``, a layer (every group's heads
    walk the same chunks: groups do not multiply it)."""
    return batch * -(-seq // chunk)
