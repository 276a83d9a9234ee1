"""Mamba2 SSD chunked-scan kernel for TPU (Pallas).

One grid step processes one (batch, head, chunk) tile entirely in VMEM:

    dA   = dt * A_h                      (cs, 1)   VPU
    L    = tril(exp(dAcs_i - dAcs_j))    (cs, cs)  VPU
    S    = C B^T                         (cs, cs)  MXU
    Ydiag = (S . L) (x dt)               (cs, P)   MXU
    Yoff  = (exp(dAcs) C) state^T        (cs, P)   MXU
    state = state * exp(dAcs[-1]) + (x dt * decay)^T B    (P, N) MXU

The chunk dimension is sequential ("arbitrary"); the (P, N) running state is
carried in f32 VMEM scratch — the inter-chunk recurrence never leaves the
core.  All matmul shapes (cs=128..256, P=64, N=128) are MXU-aligned.  B/C are
shared across heads (single SSD group), so their index maps drop ``h``.

Oracle: ``repro.models.ssm.ssd_chunked``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, cs: int):
    cb = pl.program_id(2)
    ncb = pl.num_programs(2)

    @pl.when(cb == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)              # (cs, P)
    dt = dtc_ref[0, 0].astype(jnp.float32)           # (cs, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)       # (1, cs)
    A = a_ref[pl.program_id(1)]                      # scalar (f32, SMEM)
    Bm = b_ref[0].astype(jnp.float32)                # (cs, N)
    Cm = c_ref[0].astype(jnp.float32)                # (cs, N)

    dA = dt * A                                      # (cs, 1), <= 0
    dA_row = dt_row * A                              # (1, cs)
    ii = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    # inclusive prefix sums as masked reductions (Mosaic has no cumsum),
    # once down each axis so neither needs a transpose
    dA_cs = jnp.sum(jnp.where(jj <= ii, dA_row, 0.0), axis=1,
                    keepdims=True)                   # (cs, 1)
    dA_cs_row = jnp.sum(jnp.where(ii <= jj, dA, 0.0), axis=0,
                        keepdims=True)               # (1, cs)

    # --- intra-chunk -----------------------------------------------------
    diff = dA_cs - dA_cs_row                         # (cs, cs)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (cs, cs)
    xdt = x * dt                                     # (cs, P)
    y_diag = jax.lax.dot_general(
        scores * L, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (cs, P)

    # --- off-diagonal (previous chunks' state) ------------------------------
    state = state_scr[...]                           # (P, N)
    c_scaled = Cm * jnp.exp(dA_cs)                   # (cs, N)
    y_off = jax.lax.dot_general(
        c_scaled, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (cs, P)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # --- state update --------------------------------------------------------
    last = jnp.sum(dA_row, axis=1, keepdims=True)    # (1, 1)
    decay_last = jnp.exp(last - dA_cs)               # (cs, 1)
    contrib = jax.lax.dot_general(
        xdt * decay_last, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (P, N)
    state_scr[...] = state * jnp.exp(last) + contrib

    @pl.when(cb == ncb - 1)
    def _fin():
        st_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_fwd(x, dt, A, Bm, Cm, *, chunk: int = 256,
                 interpret: bool = False):
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,N).

    Returns (y: (B,S,H,P), final_state: (B,H,P,N) f32).
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    cs = min(chunk, S)
    assert S % cs == 0, (S, cs)
    grid = (B, H, S // cs)
    # head-major operands keep the head axis out of the last two block
    # dims, which Mosaic tiles by (8, 128) or full extent; dt rides in both
    # orientations so the kernel never transposes a vector
    xt = x.transpose(0, 2, 1, 3)                     # (B, H, S, P)
    dtt = dt.transpose(0, 2, 1)                      # (B, H, S)

    kernel = functools.partial(_ssd_kernel, cs=cs)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, cs, P), lambda b, h, cb: (b, h, cb, 0)),
            pl.BlockSpec((1, 1, cs, 1), lambda b, h, cb: (b, h, cb, 0)),
            pl.BlockSpec((1, 1, 1, cs), lambda b, h, cb: (b, h, 0, cb)),
            pl.BlockSpec((1, cs, N), lambda b, h, cb: (b, cb, 0)),
            pl.BlockSpec((1, cs, N), lambda b, h, cb: (b, cb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cs, P), lambda b, h, cb: (b, h, cb, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, cb: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xt, dtt[..., None], dtt[:, :, None, :], Bm, Cm)
    return y.transpose(0, 2, 1, 3), st
