"""Causal attention over the positions one chip holds, never forming the
[B, heads, S, S] scores: blocked over queries and keys with a running
softmax, forward and backward, skipping every block that lies wholly
outside the causal band or the sliding window.

The kernel is the library's own (jax.experimental.pallas.ops.tpu
splash_attention): a block-sparse flash attention whose mask is worked out
on the host at trace time, so a block that no position may see is never
scheduled. It is compiled by Mosaic where the program is lowered for a
TPU and interpreted anywhere else (lax.platform_dependent: the choice
follows the platform a program is LOWERED for, so a compile for a
described chip from a CPU host gets the kernel, as a chip run does).

Grouped heads: query head i reads key-value head i // (Hq // Hkv); the
kernel's multi-query form runs once a key-value head over its group of
query heads. The softmax is in float32 whatever q, k, v are.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _kernel, splash_attention_mask as _mask)

# positions a block of queries / of keys holds on the chip (a multiple of
# the 128 lanes); a sequence is padded up to a whole number of blocks
BLOCK_Q = 1024
BLOCK_KV = 1024
_LANES = 128


def _blocks(seq: int):
    """(padded length, query block, key block) for ``seq`` positions."""
    padded = -(-seq // _LANES) * _LANES
    bq, bkv = min(BLOCK_Q, padded), min(BLOCK_KV, padded)
    step = max(bq, bkv)
    return -(-padded // step) * step, bq, bkv


def _splash(seq: int, group: int, window: Optional[int], bq: int, bkv: int,
            interpret: bool):
    """The multi-query kernel for one key-value head and its ``group``
    query heads over ``seq`` positions: position i sees j <= i, and under
    a window only i - j < window."""
    one = (_mask.CausalMask((seq, seq)) if window is None
           else _mask.LocalMask((seq, seq), (window - 1, 0), 0))
    sizes = _kernel.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv)
    return _kernel.make_splash_mqa(
        _mask.MultiHeadMask([one] * group), block_sizes=sizes,
        head_shards=1, q_seq_shards=1, interpret=interpret)


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      window: Optional[int] = None,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """softmax(scale * q k^T under the mask) v; ``scale`` None is
    1 / sqrt(D).

    q [B, Hq, S, D]; k, v [B, Hkv, S, D], Hq a multiple of Hkv. Position
    i attends to j <= i, and with ``window`` only to i - j < window.
    Returns [B, Hq, S, D] in q's dtype. S may be any length: the tail is
    padded to whole blocks, which the causal mask hides from every real
    query, and cut off the result."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads over {Hkv} key-value heads")
    group = Hq // Hkv
    if window is not None and window >= S:
        window = None           # the window never cuts: plain causal
    padded, bq, bkv = _blocks(S)
    if scale is None:
        scale = D ** -0.5
    q = (q * scale).astype(q.dtype).reshape(B, Hkv, group, S, D)
    if padded != S:
        pad = [(0, 0)] * 5
        pad[3] = (0, padded - S)
        q = jnp.pad(q, pad)
        k, v = (jnp.pad(a, pad[1:]) for a in (k, v))

    def run(interpret: bool):
        kern = _splash(padded, group, window, bq, bkv, interpret)
        return lambda q, k, v: jax.vmap(jax.vmap(kern))(q, k, v)

    out = jax.lax.platform_dependent(q, k, v, tpu=run(False),
                                     default=run(True))
    return out[:, :, :, :S].reshape(B, Hq, S, D)
