"""Jit'd public wrapper for the decode-attention kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_fwd


def decode_attention(q, k, v, pos, *, kv_pos=None, window: int = 0,
                     softcap: float = 0.0, bk: int = 512,
                     interpret: bool = False):
    if kv_pos is None:
        kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    return decode_attention_fwd(
        q, k, v, pos, kv_pos, window=window, softcap=softcap, bk=bk,
        interpret=interpret)
