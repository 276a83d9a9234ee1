"""RG-LRU linear-recurrence scan kernel for TPU (Pallas).

Computes h_t = a_t * h_{t-1} + b_t over the sequence, given precomputed
gates a, b (the gate matmuls stay in XLA where the MXU already runs them
well — the *recurrence* is the memory-latency-bound part worth a kernel).

Design:
  * grid ``(batch, width_blocks, seq_blocks)``; the sequence dimension is
    sequential ("arbitrary") and the carried state h lives in a (1, BW) f32
    VMEM scratch — one HBM round-trip per (BS, BW) tile instead of one per
    timestep.
  * within a tile the recurrence steps over BS timesteps with VPU ops on
    (1, BW) lanes — W is the 128-lane dimension, so all 128 lanes advance
    per cycle.
  * the final state (for decode handoff) is written once per (b, wb).

Oracle: ``repro.models.rglru.rglru_ref`` (associative scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, hfin_ref, state_scr, *, bs: int):
    sb = pl.program_id(2)
    nsb = pl.num_programs(2)

    @pl.when(sb == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    def body(t, h):
        # one (1, BW) row per step, sliced on the refs: Mosaic has no
        # dynamic_slice of a loaded value
        row = pl.ds(t, 1)
        a = a_ref[0, row, :].astype(jnp.float32)
        b = b_ref[0, row, :].astype(jnp.float32)
        h = a * h + b
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, body, state_scr[...])
    state_scr[...] = h

    @pl.when(sb == nsb - 1)
    def _fin():
        hfin_ref[0, :] = h[0].astype(hfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "bw", "interpret"))
def rglru_scan_fwd(a, b, *, bs: int = 256, bw: int = 512,
                   interpret: bool = False):
    """a, b: (B, S, W) -> (h: (B, S, W), h_final: (B, W)) in f32."""
    B, S, W = a.shape
    bs = min(bs, S)
    bw = min(bw, W)
    assert S % bs == 0 and W % bw == 0, (S, bs, W, bw)
    grid = (B, W // bw, S // bs)

    kernel = functools.partial(_rglru_kernel, bs=bs)
    h, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bb, wb, sb: (bb, sb, wb)),
            pl.BlockSpec((1, bs, bw), lambda bb, wb, sb: (bb, sb, wb)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bw), lambda bb, wb, sb: (bb, sb, wb)),
            pl.BlockSpec((1, bw), lambda bb, wb, sb: (bb, wb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return h, h_final
