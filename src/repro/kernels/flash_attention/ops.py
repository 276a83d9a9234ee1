"""Jit'd public wrapper for the flash-attention kernel.

It lowers to Mosaic for the TPU; ``interpret=True`` runs the Pallas
interpreter instead, which is how the tests check it on CPU.
``repro.models.attention.sdpa(impl="pallas")`` routes here.
"""

from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention_fwd


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_pos=None, kv_pos=None,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """Drop-in for sdpa(...): positions must be contiguous from 0."""
    del q_pos, kv_pos  # kernel assumes contiguous [0, S) positions
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, interpret=interpret)
