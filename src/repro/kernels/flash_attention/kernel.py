"""FlashAttention forward kernel for TPU (Pallas).

TPU-native design (not a CUDA port):
  * 4-D grid ``(batch, q_head, q_blocks, kv_blocks)``; the last dimension is
    sequential ("arbitrary"), so the online-softmax state for one (b, h, qb)
    lives in VMEM scratch across kv steps — the canonical TPU flash layout.
  * BlockSpecs tile q/out by (BQ, hd) and k/v by (BK, hd) into VMEM; both
    matmuls are MXU-shaped (BQ x hd x BK and BQ x BK x hd) with f32
    accumulation via ``preferred_element_type``.
  * GQA folds into the index maps: q-head h reads kv-head ``h // group``.
  * causal + sliding-window masking from block-local iotas; fully-masked kv
    blocks are skipped with ``pl.when`` (no MXU work issued).

Validated against ``ref.sdpa_ref`` in interpret mode (tests/test_kernels.py)
and compiled for v5e at model widths (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, causal: bool, window: int,
                  softcap: float, scale: float, kv_len: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nkb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * bq
    k_start = kb * bk

    # Skip kv blocks that are entirely masked out.
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window:
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :]                     # (BQ, hd)
        k = k_ref[0, 0, :, :]                     # (BK, hd)
        v = v_ref[0, 0, :, :]                     # (BK, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = kpos < kv_len
        if causal:
            keep = jnp.logical_and(keep, kpos <= qpos)
        if window:
            keep = jnp.logical_and(keep, qpos - kpos < window)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[...]                       # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                    # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)           # (BQ, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # (BQ, hd)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "bq", "bk",
                              "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """q: (B, Sq, Hq, hd); k/v: (B, Skv, Hkv, hd) -> (B, Sq, Hq, hd)."""
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, hd_v = v.shape
    assert hd_v == hd and k.shape == v.shape, "flash kernel needs hd_k == hd_v"
    group = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    grid = (B, Hq, Sq // bq, Skv // bk)
    scale = hd ** -0.5

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        softcap=softcap, scale=scale, kv_len=Skv)

    # head-major operands: (1, 1, BQ|BK, hd) blocks keep the head axis out
    # of the last two block dims, which Mosaic tiles by (8, 128) or full
    # extent
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qb, kb, g=group: (b, h // g, kb, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qb, kb, g=group: (b, h // g, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
