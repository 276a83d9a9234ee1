"""Attention correctness: blockwise == naive, MLA absorb == naive, hypothesis
shape sweeps."""

import jax
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.attention import sdpa_blockwise, sdpa_naive


def _qkv(key, B, Sq, Skv, Hq, Hkv, hd, hd_v=None):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd_v or hd), jnp.float32)
    return q, k, v


@settings(max_examples=20, deadline=None)
@given(
    B=st.integers(1, 3),
    S=st.sampled_from([16, 32, 64]),
    Hkv=st.sampled_from([1, 2, 4]),
    G=st.sampled_from([1, 2, 4]),
    hd=st.sampled_from([16, 32]),
    causal=st.booleans(),
    window=st.sampled_from([0, 8, 24]),
    chunk=st.sampled_from([8, 16, 32]),
)
def test_blockwise_matches_naive(B, S, Hkv, G, hd, causal, window, chunk):
    if window and not causal:
        window = 0
    q, k, v = _qkv(jax.random.PRNGKey(B * 1000 + S), B, S, S,
                   Hkv * G, Hkv, hd)
    ref = sdpa_naive(q, k, v, causal=causal, window=window)
    out = sdpa_blockwise(q, k, v, causal=causal, window=window,
                         chunk=chunk)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_mla_head_dim_mismatch_supported():
    # v head dim != qk head dim (MLA): both paths must handle it
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 32, 32, 4, 4, 24, hd_v=16)
    ref = sdpa_naive(q, k, v, causal=True)
    out = sdpa_blockwise(q, k, v, causal=True, chunk=8)
    assert ref.shape == (2, 32, 4, 16)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_softcap():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 16, 16, 2, 2, 16)
    a = sdpa_naive(q, k, v, causal=True, softcap=20.0)
    b = sdpa_blockwise(q, k, v, causal=True, softcap=20.0, chunk=8)
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5


def test_mla_absorb_equals_decompressed():
    from repro.configs import get_arch
    from repro.models.attention import (init_mla, mla_decode, mla_prefill)

    cfg = get_arch("deepseek-v3-671b-smoke")
    p = init_mla(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    _, cache = mla_prefill(cfg, p, x)
    xt = x[:, -1:, :]
    out_a, _ = mla_decode(cfg, p, xt, jnp.int32(15), cache, absorb=True)
    out_n, _ = mla_decode(cfg, p, xt, jnp.int32(15), cache, absorb=False)
    d = float(jnp.max(jnp.abs(out_a.astype(jnp.float32)
                              - out_n.astype(jnp.float32))))
    assert d < 0.05, d


def test_ring_buffer_decode_beyond_capacity():
    """Sliding-window cache: decoding past the window must match a fresh
    full-context computation restricted to the window."""
    from repro.configs import get_arch
    from repro.models.attention import (gqa_cache_init, gqa_decode, gqa_fwd,
                                        init_gqa)
    import dataclasses

    cfg = dataclasses.replace(get_arch("yi-9b-smoke"), sliding_window=8,
                              dtype="float32")
    p = init_gqa(cfg, jax.random.PRNGKey(4))
    B, S = 1, 24
    xs = jax.random.normal(jax.random.PRNGKey(5), (B, S, cfg.d_model))
    # sequential decode through a ring cache of capacity == window
    cache = gqa_cache_init(cfg, B, S, window=8)
    assert cache["k"].shape[1] == 8
    outs = []
    for t in range(S):
        o, cache = gqa_decode(cfg, p, xs[:, t:t + 1], jnp.int32(t), cache)
        outs.append(o[:, 0])
    got = jnp.stack(outs, axis=1)
    want = gqa_fwd(cfg, p, xs, impl="naive")
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
