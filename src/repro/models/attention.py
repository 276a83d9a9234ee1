"""Attention: GQA (+ qk-norm, sliding window, partial RoPE) and MLA.

Three scaled-dot-product implementations:

* ``naive``      — materializes (Sq, Skv) scores; fine for training at 4k with
                   remat (scores are recomputed in backward).
* ``blockwise``  — FlashAttention expressed in XLA: ``lax.scan`` over KV chunks
                   with an online-softmax carry.  O(Sq * chunk) live memory;
                   the default for prefill.
* ``pallas``     — the TPU kernel in ``repro.kernels.flash_attention`` (ops.py
                   wrapper); numerically validated against ``naive`` in tests.

MLA (DeepSeek-V3) keeps a *compressed* KV cache: one latent per token,
``ckv ‖ k_pe`` (kv_lora + rope dims, 576 wide for DeepSeek-V3, the layout
FlashMLA uses), so a paged pool stores one wide leaf per run of layers.
Decode supports two paths: ``absorb=False`` decompresses the cache every
step (faithful to the algebraic definition — our paper-faithful baseline)
and ``absorb=True`` folds the decompression matrices into the query and
output projections (the optimized path; see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import cdtype, rmsnorm_1d, rope_fwd, yarn_mscale

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Scaled dot-product attention cores
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int):
    """(…, Sq, Skv) additive bias in f32."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    keep = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        keep &= kp <= qp
    if window:
        keep &= qp - kp < window
    return jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)


def sdpa_naive(q, k, v, *, causal=True, window=0, q_pos=None, kv_pos=None,
               softcap: float = 0.0, scale: float | None = None):
    """q: (B,Sq,Hq,hd); k: (B,Skv,Hkv,hd); v: (B,Skv,Hkv,hd_v).

    hd_v may differ from hd (MLA). Scores scale by ``scale``, by default
    ``hd ** -0.5``.  Returns (B,Sq,Hq,hd_v).
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    hd_v = v.shape[-1]
    G = Hq // Hkv
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if kv_pos is None:
        kv_pos = jnp.arange(k.shape[1])
    qg = q.reshape(B, Sq, Hkv, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores *= hd ** -0.5 if scale is None else scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    scores += _mask_bias(q_pos, kv_pos, causal=causal, window=window)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, Hq, hd_v)


def sdpa_blockwise(q, k, v, *, causal=True, window=0, q_pos=None, kv_pos=None,
                   chunk: int = 1024, softcap: float = 0.0,
                   scale: float | None = None):
    """Online-softmax attention, scanning KV in chunks (flash in XLA)."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    G = Hq // Hkv
    chunk = min(chunk, Skv)
    assert Skv % chunk == 0, (Skv, chunk)
    nc = Skv // chunk
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if kv_pos is None:
        kv_pos = jnp.arange(Skv)

    qg = qf = q.reshape(B, Sq, Hkv, G, hd)
    ks = k.reshape(B, nc, chunk, Hkv, hd).swapaxes(0, 1)
    vs = v.reshape(B, nc, chunk, Hkv, hd_v).swapaxes(0, 1)
    kps = kv_pos.reshape(nc, chunk)

    sm_scale = hd ** -0.5 if scale is None else scale
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, hd_v), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, kp = inp
        s = jnp.einsum("bqkgh,bckh->bkgqc", qf, kc).astype(jnp.float32)
        s *= sm_scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s += _mask_bias(q_pos, kp, causal=causal, window=window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckh->bqkgh", p.astype(q.dtype), vc).astype(jnp.float32)
        acc_new = acc * scale.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype).reshape(B, Sq, Hq, hd_v)


def sdpa(q, k, v, *, impl="naive", **kw):
    if impl == "blockwise":
        return sdpa_blockwise(q, k, v, **kw)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        kw.pop("chunk", None)
        scale = kw.pop("scale", None)
        if scale is not None:       # the kernel scales by hd ** -0.5
            q = q * jnp.asarray(scale * q.shape[-1] ** 0.5, q.dtype)
        return fa_ops.flash_attention(q, k, v, **kw)
    kw.pop("chunk", None)
    return sdpa_naive(q, k, v, **kw)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def init_gqa(cfg: ModelConfig, key: jax.Array) -> dict:
    dt = cdtype(cfg)
    hd = cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = cfg.d_model ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (cfg.d_model, cfg.num_heads, hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (cfg.d_model, cfg.num_kv_heads, hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (cfg.d_model, cfg.num_kv_heads, hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (cfg.num_heads, hd, cfg.d_model))
               * (cfg.num_heads * hd) ** -0.5).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm_1d(q, p["q_norm"])
        k = rmsnorm_1d(k, p["k_norm"])
    q = rope_fwd(q, positions, cfg.rope_theta, cfg.rope_pct)
    k = rope_fwd(k, positions, cfg.rope_theta, cfg.rope_pct)
    return q, k, v


def gqa_fwd(cfg: ModelConfig, p: dict, x: jax.Array, *, window: int | None = None,
            causal: bool = True, impl: str = "naive", positions=None,
            chunk: int = 1024) -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, S, D)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _project_qkv(cfg, p, x, positions)
    w = cfg.sliding_window if window is None else window
    out = sdpa(q, k, v, impl=impl, causal=causal, window=w,
               q_pos=positions, kv_pos=positions, chunk=chunk,
               softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


_INVALID_POS = jnp.int32(2**30)  # masked-out slot sentinel (kv_pos > q_pos)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("stack", "layer"),
                   meta_fields=("shape", "axis", "flat"))
@dataclasses.dataclass(frozen=True)
class PagedLeaf:
    """One leaf of a lane's cache as read from a paged KV pool.

    ``stack`` holds the lane's rows of every layer of a layer-scanned
    stack, token first: ``(cap, layers, *merged)``, or where ``flat`` all
    layers side by side in one axis of whole lanes (an MLA latent).
    ``shape`` is one layer's dense cache leaf, its token axis ``axis``.
    The layer scan sets ``layer``; the attention reads that layer's rows
    with ``rows()`` and returns only the new token's row, which the caller
    writes into the pool.
    """

    stack: jax.Array
    shape: tuple
    axis: int
    flat: bool
    layer: jax.Array = None

    def rows(self):
        """This layer's rows of the lane's cache, in the dense cache's
        layout, sliced from ``stack``."""
        cap = self.shape[self.axis]
        rest = self.shape[:self.axis] + self.shape[self.axis + 1:]
        if self.flat:
            width = math.prod(rest)
            got = jax.lax.dynamic_slice_in_dim(self.stack, self.layer * width,
                                               width, axis=1)
        else:
            got = jax.lax.dynamic_index_in_dim(self.stack, self.layer, 1,
                                               keepdims=False)
        return jnp.moveaxis(got.reshape((cap,) + rest), 0, self.axis)


def _is_paged_leaf(x) -> bool:
    return isinstance(x, PagedLeaf)


def is_paged(cache) -> bool:
    """Whether a cache pytree is a paged view (``PagedLeaf`` leaves)."""
    return any(map(_is_paged_leaf,
                   jax.tree.leaves(cache, is_leaf=_is_paged_leaf)))


def at_layer(cache, layer):
    """A paged view whose leaves read layer ``layer`` of their stack."""
    return jax.tree.map(lambda c: dataclasses.replace(c, layer=layer), cache,
                        is_leaf=_is_paged_leaf)


def _with_row(cache, row, pos, axis: int):
    """A layer's cache with this step's row written at ring slot
    ``pos % cap`` of its token axis ``axis``.  A paged leaf's rows are
    sliced first into a buffer of the layer's own, so the write is in
    place and the gathered stack is not touched."""
    if isinstance(cache, PagedLeaf):
        cache = cache.rows()
    slot = jnp.asarray(pos, jnp.int32) % cache.shape[axis]
    start = (0,) * axis + (slot,) + (0,) * (cache.ndim - axis - 1)
    return jax.lax.dynamic_update_slice(cache, row, start)


def _ring_cache_from_prefill(entries: dict, S: int, cap: int) -> dict:
    """Place prefill entries at ring slots ``pos % cap`` so subsequent decode
    writes (slot = pos % cap) evict oldest-first; unfilled slots get the
    INVALID sentinel.

    The slots are static: the last ``n`` positions rotated by
    ``(S - n) % cap``, then padded to ``cap``.  A roll and a pad, not a
    scatter, which the TPU compiler rejects inside the layer scan."""
    n = min(S, cap)
    shift = (S - n) % cap
    pos = jnp.roll(jnp.arange(S - n, S, dtype=jnp.int32), shift)
    out = {}
    for name, arr in entries.items():
        pad = [(0, 0), (0, cap - n)] + [(0, 0)] * (arr.ndim - 2)
        out[name] = jnp.pad(jnp.roll(arr[:, S - n:], shift, axis=1), pad)
    out["kv_pos"] = jnp.pad(pos, (0, cap - n), constant_values=_INVALID_POS)
    return out


def gqa_prefill(cfg: ModelConfig, p: dict, x: jax.Array, *, window: int | None = None,
                impl: str = "blockwise", chunk: int = 1024, margin: int = 0):
    """Prefill: returns (out, cache).

    The cache is a *ring buffer* of capacity ``min(S + margin, window or inf)``
    holding post-RoPE k/v plus the absolute position of each slot
    (``kv_pos``) — sliding-window layers therefore decode 500k-token contexts
    with O(window) memory.  ``margin`` reserves headroom so decode extends the
    context instead of immediately evicting the oldest prefill entries.
    """
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _project_qkv(cfg, p, x, positions)
    w = cfg.sliding_window if window is None else window
    out = sdpa(q, k, v, impl=impl, causal=True, window=w,
               q_pos=positions, kv_pos=positions, chunk=chunk,
               softcap=cfg.attn_logit_softcap)
    cap = min(S + margin, w) if w else S + margin
    cache = _ring_cache_from_prefill({"k": k, "v": v}, S, cap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache


def gqa_decode(cfg: ModelConfig, p: dict, x: jax.Array, pos: jax.Array,
               cache: dict, *, window: int | None = None, impl: str = "xla"):
    """One-token decode. x: (B, 1, D); cache k/v: (B, cap, Hkv, hd).

    Writes the new k/v at ring slot ``pos % cap`` and attends over cached
    absolute positions <= pos (within the sliding window, if any).  Returns
    the new cache, or for a paged cache (``PagedLeaf`` leaves) only the new
    token's row of each leaf, which the caller writes into the pool.
    """
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    row = {"k": k_new, "v": v_new, "kv_pos": positions}
    k = _with_row(cache["k"], k_new, pos, 1)
    v = _with_row(cache["v"], v_new, pos, 1)
    kv_pos = _with_row(cache["kv_pos"], positions, pos, 0)
    w = cfg.sliding_window if window is None else window
    if impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops

        out = da_ops.decode_attention(q, k, v, pos, kv_pos=kv_pos, window=w,
                                      softcap=cfg.attn_logit_softcap)
    else:
        out = sdpa_naive(q, k, v, causal=True, window=w,
                         q_pos=positions, kv_pos=kv_pos,
                         softcap=cfg.attn_logit_softcap)
    new_cache = (row if is_paged(cache)
                 else {"k": k, "v": v, "kv_pos": kv_pos})
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int, *,
                   window: int | None = None) -> dict:
    """Abstract cache shapes (window-bounded for sliding-window layers)."""
    w = cfg.sliding_window if window is None else window
    cap = min(max_len, w) if w else max_len
    shp = (batch, cap, cfg.num_kv_heads, cfg.head_dim_)
    dt = cdtype(cfg)
    return {
        "k": jax.ShapeDtypeStruct(shp, dt),
        "v": jax.ShapeDtypeStruct(shp, dt),
        "kv_pos": jax.ShapeDtypeStruct((cap,), jnp.int32),
    }


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int, *,
                   window: int | None = None) -> dict:
    spec = gqa_cache_spec(cfg, batch, max_len, window=window)
    out = {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}
    out["kv_pos"] = jnp.full(spec["kv_pos"].shape, _INVALID_POS, jnp.int32)
    return out


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V3)
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key: jax.Array) -> dict:
    m = cfg.mla
    dt = cdtype(cfg)
    ks = jax.random.split(key, 6)
    D, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    s = D ** -0.5
    return {
        "wq_a": (jax.random.normal(ks[0], (D, m.q_lora_rank)) * s).astype(dt),
        "q_norm": jnp.ones((m.q_lora_rank,), dt),
        "wq_b": (jax.random.normal(ks[1], (m.q_lora_rank, H, qk))
                 * m.q_lora_rank ** -0.5).astype(dt),
        "wkv_a": (jax.random.normal(ks[2], (D, m.kv_lora_rank + m.qk_rope_head_dim))
                  * s).astype(dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "wk_b": (jax.random.normal(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim))
                 * m.kv_lora_rank ** -0.5).astype(dt),
        "wv_b": (jax.random.normal(ks[4], (m.kv_lora_rank, H, m.v_head_dim))
                 * m.kv_lora_rank ** -0.5).astype(dt),
        "wo": (jax.random.normal(ks[5], (H, m.v_head_dim, D))
               * (H * m.v_head_dim) ** -0.5).astype(dt),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``(qk_nope + qk_rope) ** -0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim) ** 2`` when the rotary is scaled."""
    m, y = cfg.mla, cfg.yarn
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y.enabled and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_rope(cfg: ModelConfig, x, positions):
    return rope_fwd(x, positions, cfg.rope_theta, yarn=cfg.yarn)


def _mla_q(cfg: ModelConfig, p: dict, x, positions):
    m = cfg.mla
    cq = rmsnorm_1d(x @ p["wq_a"], p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_pe = _mla_rope(cfg, q[..., m.qk_nope_head_dim:], positions)
    return q_nope, q_pe


def _mla_latent(cfg: ModelConfig, p: dict, x, positions):
    """(B, S, kv_lora + rope): the normed latent and the rotated rope key
    of each token, side by side."""
    m = cfg.mla
    ckv_full = x @ p["wkv_a"]
    ckv = rmsnorm_1d(ckv_full[..., : m.kv_lora_rank], p["kv_norm"])
    k_pe = ckv_full[..., m.kv_lora_rank:][:, :, None, :]  # single rope "head"
    k_pe = _mla_rope(cfg, k_pe, positions)[:, :, 0, :]
    return jnp.concatenate([ckv, k_pe], axis=-1)


def mla_fwd(cfg: ModelConfig, p: dict, x: jax.Array, *, positions=None,
            impl: str = "naive", chunk: int = 1024, lat=None) -> jax.Array:
    """Full-sequence MLA (train / prefill math, decompressed form)."""
    m = cfg.mla
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q_nope, q_pe = _mla_q(cfg, p, x, positions)
    if lat is None:
        lat = _mla_latent(cfg, p, x, positions)
    ckv, k_pe = lat[..., : m.kv_lora_rank], lat[..., m.kv_lora_rank:]
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"])
    k_pe_h = jnp.broadcast_to(k_pe[:, :, None, :], q_pe.shape[:1] + (S,) + q_pe.shape[2:])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_h], axis=-1)
    out = sdpa(q, k, v, impl=impl, causal=True, window=0,
               q_pos=positions, kv_pos=positions, chunk=chunk,
               scale=mla_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_prefill(cfg: ModelConfig, p: dict, x: jax.Array, *, impl="blockwise",
                chunk: int = 1024, margin: int = 0):
    B, S, _ = x.shape
    positions = jnp.arange(S)
    lat = _mla_latent(cfg, p, x, positions)
    out = mla_fwd(cfg, p, x, positions=positions, impl=impl, chunk=chunk,
                  lat=lat)
    cache = _ring_cache_from_prefill({"lat": lat}, S, S + margin)
    return out, cache


def mla_decode(cfg: ModelConfig, p: dict, x: jax.Array, pos: jax.Array,
               cache: dict, *, absorb: bool = True):
    """One-token MLA decode over the compressed cache.

    cache: {"lat": (B, Smax, kv_lora + rope), "kv_pos": (Smax,)}.
    ``absorb=False`` decompresses the whole cache each step (baseline);
    ``absorb=True`` runs attention in latent space (optimized): the query
    folded through ``wk_b`` meets the latent and the rope key in one
    product over the whole latent width.  A paged cache returns only the
    new token's rows, as ``gqa_decode``.
    """
    m = cfg.mla
    r = m.kv_lora_rank
    positions = jnp.full((1,), pos, jnp.int32)
    q_nope, q_pe = _mla_q(cfg, p, x, positions)          # (B,1,H,*)
    lat_new = _mla_latent(cfg, p, x, positions)
    row = {"lat": lat_new, "kv_pos": positions}
    lat = _with_row(cache["lat"], lat_new, pos, 1)
    kv_pos = _with_row(cache["kv_pos"], positions, pos, 0)
    scale = mla_scale(cfg)
    mask = jnp.where(kv_pos <= pos, 0.0, NEG_INF)[None, None, :]

    if absorb:
        # score = [q_nope Wk_b^T ‖ q_pe] . [ckv ‖ k_pe] — never decompress.
        q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])
        q_cat = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)
        s = jnp.einsum("bqhr,bsr->bhqs", q_cat, lat).astype(jnp.float32)
        s = s[:, :, 0, :] * scale + mask
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)       # (B,H,S)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs, lat[..., :r])
        out = jnp.einsum("bhr,rhk->bhk", o_lat, p["wv_b"])[:, None]
    else:
        ckv, k_pe = lat[..., :r], lat[..., r:]
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"])
        s = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope)
             + jnp.einsum("bqhk,bsk->bhqs", q_pe, k_pe)).astype(jnp.float32)
        s = s[:, :, 0, :] * scale + mask
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhs,bshk->bhk", probs, v)[:, None]
    new_cache = row if is_paged(cache) else {"lat": lat, "kv_pos": kv_pos}
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), new_cache


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    m = cfg.mla
    dt = cdtype(cfg)
    width = m.kv_lora_rank + m.qk_rope_head_dim
    return {
        "lat": jax.ShapeDtypeStruct((batch, max_len, width), dt),
        "kv_pos": jax.ShapeDtypeStruct((max_len,), jnp.int32),
    }


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    spec = mla_cache_spec(cfg, batch, max_len)
    out = {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}
    out["kv_pos"] = jnp.full((max_len,), _INVALID_POS, jnp.int32)
    return out
