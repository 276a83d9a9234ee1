"""Pallas kernel validation (interpret mode) against pure-jnp oracles,
sweeping shapes/dtypes as required by the assignment."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.decode_attention.kernel import decode_attention_fwd
from repro.kernels.decode_attention.ref import decode_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import sdpa_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_fwd
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd
from repro.kernels.ssd_scan.ref import ssd_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,hd,causal,window", [
    (2, 128, 4, 2, 64, True, 0),
    (1, 128, 8, 8, 128, True, 0),
    (2, 128, 4, 1, 64, True, 32),
    (1, 64, 4, 2, 32, False, 0),
])
def test_flash_attention(B, S, Hq, Hkv, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * 7 + S), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              bq=64, bk=64, interpret=True)
    ref = sdpa_ref(q, k, v, causal=causal, window=window)
    d = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                              - ref.astype(jnp.float32))))
    assert d < TOL[dtype], d


@settings(max_examples=8, deadline=None)
@given(bq=st.sampled_from([32, 64, 128]),
       bk=st.sampled_from([32, 64, 128]))
def test_flash_attention_block_shape_sweep(bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=True, bq=bq, bk=bk,
                              interpret=True)
    ref = sdpa_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,cap,Hq,Hkv,hd,pos,window", [
    (2, 256, 8, 2, 64, 200, 0),
    (1, 256, 4, 4, 128, 255, 0),
    (2, 512, 8, 1, 64, 400, 128),
])
def test_decode_attention(B, cap, Hq, Hkv, hd, pos, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(cap + pos), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, cap, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, cap, Hkv, hd)).astype(dtype)
    kv_pos = jnp.arange(cap, dtype=jnp.int32).at[cap // 3].set(2 ** 30)
    out = decode_attention_fwd(q, k, v, pos, kv_pos, window=window, bk=128,
                               interpret=True)
    ref = decode_ref(q, k, v, pos, kv_pos, window=window)
    d = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                              - ref.astype(jnp.float32))))
    assert d < TOL[dtype], d


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    B=st.integers(1, 3),
    S=st.sampled_from([64, 128, 256]),
    W=st.sampled_from([128, 256]),
    bs=st.sampled_from([32, 64]),
)
def test_rglru_scan(B, S, W, bs):
    ks = jax.random.split(jax.random.PRNGKey(S + W), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.98
    b = jax.random.normal(ks[1], (B, S, W)) * 0.1
    h, hf = rglru_scan_fwd(a, b, bs=bs, bw=128, interpret=True)
    rh, rhf = rglru_scan_ref(a, b)
    assert float(jnp.max(jnp.abs(h - rh))) < 1e-4
    assert float(jnp.max(jnp.abs(hf - rhf))) < 1e-4


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,cs", [
    (2, 128, 4, 32, 64, 32),
    (1, 128, 8, 64, 128, 64),
    (2, 64, 2, 16, 32, 64),
])
def test_ssd_scan(B, S, H, P, N, cs):
    ks = jax.random.split(jax.random.PRNGKey(S * H), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y, st_ = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=cs, interpret=True)
    ry, rst = ssd_ref(x, dt, A, Bm, Cm, chunk=cs)
    rel = float(jnp.max(jnp.abs(y - ry))) / (float(jnp.max(jnp.abs(ry))) + 1e-9)
    assert rel < 1e-4
    assert float(jnp.max(jnp.abs(st_ - rst))) < 1e-3


def test_ssd_scan_chunk_invariance():
    """Different chunk sizes must give identical results (state passing)."""
    ks = jax.random.split(jax.random.PRNGKey(77), 5)
    B, S, H, P, N = 1, 128, 2, 16, 32
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y1, s1 = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    y2, s2 = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=128, interpret=True)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-3
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-3
