"""Single-token decode attention kernel for TPU (Pallas).

Serves the ``decode_32k`` / ``long_500k`` shapes: one new query token per
sequence attends over a (possibly ring-buffered) KV cache.

Design:
  * grid ``(batch, kv_head, kv_blocks)``, kv_blocks sequential; the G = Hq/Hkv
    query heads of one kv head are processed together as a (G, hd) tile, so
    the score matmul is (G x hd) @ (hd x BK) — MXU-friendly for GQA groups.
  * the current position ``pos`` is a prefetched scalar (SMEM); cached
    absolute positions ``kv_pos`` ride along as a (1, cap) int32 input so
    ring-buffer slots and unwritten slots (sentinel 2^30) mask naturally:
    keep = kv_pos <= pos (and window).
  * online softmax in VMEM scratch across kv blocks, f32 accumulation.

This kernel is memory-bound by design: it reads the whole cache once, in
the head-major layout its wrapper transposes the token-major cache to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, kvp_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, bk: int, window: int,
                   softcap: float, scale: float):
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[0]
    q = q_ref[0, 0, :, :]                      # (G, hd)
    k = k_ref[0, 0, :, :]                      # (BK, hd)
    v = v_ref[0, 0, :, :]                      # (BK, hd)
    kvp = kvp_ref[0, :]                        # (BK,) int32

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (G, BK)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    keep = kvp <= pos
    if window:
        keep = jnp.logical_and(keep, pos - kvp < window)
    s = jnp.where(keep[None, :], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "bk", "interpret"))
def decode_attention_fwd(q, k, v, pos, kv_pos, *, window: int = 0,
                         softcap: float = 0.0, bk: int = 512,
                         interpret: bool = False):
    """q: (B, 1, Hq, hd); k/v: (B, cap, Hkv, hd); kv_pos: (cap,) int32;
    pos: scalar int32. Returns (B, 1, Hq, hd)."""
    B, one, Hq, hd = q.shape
    _, cap, Hkv, _ = k.shape
    G = Hq // Hkv
    bk = min(bk, cap)
    assert cap % bk == 0, (cap, bk)
    qg = q.reshape(B, Hkv, G, hd)
    # head-major cache: a (1, 1, BK, hd) block keeps the kv-head axis out of
    # the last two block dims, which Mosaic tiles by (8, 128) or full extent
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kvp2 = kv_pos.reshape(1, cap).astype(jnp.int32)
    scale = hd ** -0.5

    kernel = functools.partial(_decode_kernel, bk=bk, window=window,
                               softcap=softcap, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, cap // bk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, kb, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, kb, pos: (b, h, kb, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, kb, pos: (b, h, kb, 0)),
            pl.BlockSpec((1, bk), lambda b, h, kb, pos: (0, kb)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, kb, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qg, kt, vt, kvp2)
    return out.reshape(B, 1, Hq, hd)
