"""Decoder-only LM composer.

Every architecture is described as a list of **segments**; a segment is
``count`` repetitions of a short tuple of **block specs** (one scan unit).
Examples:

    yi-9b:            [48 x ("attn+mlp",)]
    deepseek-v3:      [3 x ("mla+mlp",), 58 x ("mla+moe",)]
    mamba2:           [48 x ("ssm",)]
    recurrentgemma:   [12 x ("rec+mlp","rec+mlp","attn+mlp"), 1 x ("rec+mlp","rec+mlp")]

Each segment's parameters are stacked along a leading ``count`` axis and the
segment is applied with ``jax.lax.scan`` — HLO stays O(1 layer), which keeps
multi-billion-parameter dry-run compiles fast.  Remat (``jax.checkpoint``) is
applied to the scan body; policy is configurable.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import rglru, ssm
from repro.models.layers import (cross_entropy, embed_fwd, init_embed,
                                 init_mlp, init_norm, lm_head_fwd, mlp_fwd,
                                 norm_fwd)
from repro.models.moe import held_moe_fwd, init_held_moe, init_moe, moe_fwd
from repro.obs.scope import device_scope


@dataclass(frozen=True)
class BlockSpec:
    mixer: str            # attn | mla | rec | ssm
    ffn: str = "mlp"      # mlp | moe | none
    window: int = 0       # sliding-window for attn mixers (0 = full)
    d_ff: int = 0         # mlp hidden size


@dataclass(frozen=True)
class Segment:
    count: int
    blocks: Tuple[BlockSpec, ...]


def plan_segments(cfg: ModelConfig) -> list[Segment]:
    L = cfg.num_layers
    if cfg.family == "ssm":
        return [Segment(L, (BlockSpec("ssm", "none"),))]
    if cfg.family == "hybrid":
        pat = tuple(
            BlockSpec("rec", "mlp", d_ff=cfg.d_ff) if c == "r"
            else BlockSpec("attn", "mlp", window=cfg.sliding_window, d_ff=cfg.d_ff)
            for c in cfg.rec.block_pattern)
        reps, rem = divmod(L, len(pat))
        segs = [Segment(reps, pat)]
        if rem:
            segs.append(Segment(1, pat[:rem]))
        return segs
    if cfg.moe.enabled:
        mixer = "mla" if cfg.mla.enabled else "attn"
        segs = []
        nd = cfg.moe.n_dense_layers
        if nd:
            segs.append(Segment(nd, (BlockSpec(mixer, "mlp", d_ff=cfg.moe.dense_d_ff),)))
        segs.append(Segment(L - nd, (BlockSpec(mixer, "moe"),)))
        return segs
    # dense / vlm (and the per-stack halves of encdec reuse "attn" blocks)
    return [Segment(L, (BlockSpec("attn", "mlp", window=cfg.sliding_window,
                                  d_ff=cfg.d_ff),))]


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------

def init_block(cfg: ModelConfig, key: jax.Array, spec: BlockSpec) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"norm1": init_norm(cfg, cfg.d_model)}
    if spec.mixer == "attn":
        p["attn"] = attn.init_gqa(cfg, k1)
    elif spec.mixer == "mla":
        p["attn"] = attn.init_mla(cfg, k1)
    elif spec.mixer == "rec":
        p["rec"] = rglru.init_rec_block(cfg, k1)
    elif spec.mixer == "ssm":
        p["ssm"] = ssm.init_ssm_block(cfg, k1)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["norm2"] = init_norm(cfg, cfg.d_model)
        if spec.ffn == "mlp":
            p["mlp"] = init_mlp(cfg, k2, cfg.d_model, spec.d_ff)
        elif spec.ffn == "moe":
            p["moe"] = (init_held_moe(cfg, k3) if cfg.moe.experts_held
                        else init_moe(cfg, k3))
        else:
            raise ValueError(spec.ffn)
    return p


def block_apply(cfg: ModelConfig, p: dict, spec: BlockSpec, x: jax.Array, *,
                mode: str, cache: Optional[dict], pos, mesh, impl: str,
                prefill_chunk: int, mla_absorb: bool,
                dp_axes: Tuple[str, ...], cache_margin: int = 0):
    """mode: train | prefill | decode. Returns (x, aux_loss, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    h = norm_fwd(cfg, p["norm1"], x)
    new_cache = None
    if spec.mixer == "attn":
        if mode == "train":
            mix = attn.gqa_fwd(cfg, p["attn"], h, window=spec.window, impl=impl)
        elif mode == "prefill":
            mix, new_cache = attn.gqa_prefill(
                cfg, p["attn"], h, window=spec.window, impl=impl,
                chunk=prefill_chunk, margin=cache_margin)
        else:
            mix, new_cache = attn.gqa_decode(
                cfg, p["attn"], h, pos, cache, window=spec.window)
    elif spec.mixer == "mla":
        with device_scope("mla"):
            if mode == "train":
                mix = attn.mla_fwd(cfg, p["attn"], h, impl=impl)
            elif mode == "prefill":
                mix, new_cache = attn.mla_prefill(
                    cfg, p["attn"], h, impl=impl, chunk=prefill_chunk,
                    margin=cache_margin)
            else:
                mix, new_cache = attn.mla_decode(
                    cfg, p["attn"], h, pos, cache, absorb=mla_absorb)
    elif spec.mixer == "rec":
        if mode == "train":
            mix = rglru.rec_block_fwd(cfg, p["rec"], h, impl=impl)
        elif mode == "prefill":
            mix, new_cache = rglru.rec_block_prefill(cfg, p["rec"], h)
        else:
            mix, new_cache = rglru.rec_block_step(cfg, p["rec"], h, cache)
    elif spec.mixer == "ssm":
        if mode == "train":
            mix = ssm.ssm_block_fwd(cfg, p["ssm"], h, impl=impl)
        elif mode == "prefill":
            mix, new_cache = ssm.ssm_block_prefill(cfg, p["ssm"], h, impl=impl)
        else:
            mix, new_cache = ssm.ssm_block_step(cfg, p["ssm"], h, cache)
    else:
        raise ValueError(spec.mixer)
    x = x + mix
    if spec.ffn != "none":
        h2 = norm_fwd(cfg, p["norm2"], x)
        if spec.ffn == "mlp":
            x = x + mlp_fwd(cfg, p["mlp"], h2)
        elif cfg.moe.experts_held:
            with device_scope("moe"):
                x = x + held_moe_fwd(cfg, p["moe"], h2,
                                     fold=mode != "train",
                                     masked=mode == "decode")
        else:
            out, aux = moe_fwd(cfg, p["moe"], h2, mesh=mesh, dp_axes=dp_axes,
                               dispatch=cfg.moe_dispatch)
            x = x + out
    return x, aux, new_cache


def block_cache_spec(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     max_len: int):
    if spec.mixer == "attn":
        return attn.gqa_cache_spec(cfg, batch, max_len, window=spec.window)
    if spec.mixer == "mla":
        return attn.mla_cache_spec(cfg, batch, max_len)
    if spec.mixer == "rec":
        return rglru.rec_cache_spec(cfg, batch)
    if spec.mixer == "ssm":
        return ssm.ssm_cache_spec(cfg, batch)
    raise ValueError(spec.mixer)


# ---------------------------------------------------------------------------
# Segments (scan over stacked layer params)
# ---------------------------------------------------------------------------

def init_segment(cfg: ModelConfig, key: jax.Array, seg: Segment) -> dict:
    reps = []
    for k in jax.random.split(key, seg.count):
        bkeys = jax.random.split(k, len(seg.blocks))
        reps.append({"blocks": tuple(
            init_block(cfg, bk, spec) for bk, spec in zip(bkeys, seg.blocks))})
    return jax.tree.map(lambda *xs: jnp.stack(xs), *reps)


_POLICIES = {
    "none": None,
    "full": None,  # jax.checkpoint default: save nothing
    "dots": "dots_with_no_batch_dims_saveable",
}


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    policy = getattr(jax.checkpoint_policies, _POLICIES[remat])
    return jax.checkpoint(fn, policy=policy)


def segment_apply(cfg: ModelConfig, p_stacked: dict, seg: Segment,
                  x: jax.Array, *, mode: str, caches=None, pos=None, mesh,
                  impl: str, prefill_chunk: int, mla_absorb: bool,
                  dp_axes: Tuple[str, ...], remat: str,
                  scan_unroll: bool = False, cache_margin: int = 0):
    unroll = seg.count if scan_unroll else 1
    kw = dict(mode=mode, pos=pos, mesh=mesh, impl=impl,
              prefill_chunk=prefill_chunk, mla_absorb=mla_absorb,
              dp_axes=dp_axes, cache_margin=cache_margin)

    if mode == "train":
        def body(carry, rep_p):
            x, aux = carry
            for i, spec in enumerate(seg.blocks):
                x, a, _ = block_apply(cfg, rep_p["blocks"][i], spec, x,
                                      cache=None, **kw)
                aux = aux + a
            return (x, aux), None

        body = _maybe_remat(body, remat)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   p_stacked, unroll=unroll)
        return x, aux, None

    if mode == "prefill":
        def body(x, rep_p):
            caches_out = []
            for i, spec in enumerate(seg.blocks):
                x, _, c = block_apply(cfg, rep_p["blocks"][i], spec, x,
                                      cache=None, **kw)
                caches_out.append(c)
            return x, tuple(caches_out)

        x, caches_out = jax.lax.scan(body, x, p_stacked, unroll=unroll)
        return x, jnp.zeros((), jnp.float32), caches_out

    # decode: the scan slices a dense cache stack layer by layer; for a
    # paged view the scan hands each layer its index, the layer slices its
    # own rows and the scan stacks only the new token's
    paged = attn.is_paged(caches)

    def body(x, inp):
        rep_p, rep_cache = inp
        if paged:
            rep_cache = attn.at_layer(caches, rep_cache)
        caches_out = []
        for i, spec in enumerate(seg.blocks):
            x, _, c = block_apply(cfg, rep_p["blocks"][i], spec, x,
                                  cache=rep_cache[i], **kw)
            caches_out.append(c)
        return x, tuple(caches_out)

    xs = jnp.arange(seg.count, dtype=jnp.int32) if paged else caches
    x, caches_out = jax.lax.scan(body, x, (p_stacked, xs), unroll=unroll)
    return x, jnp.zeros((), jnp.float32), caches_out


def segment_cache_specs(cfg: ModelConfig, seg: Segment, batch: int,
                        max_len: int):
    per_block = tuple(block_cache_spec(cfg, spec, batch, max_len)
                      for spec in seg.blocks)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((seg.count,) + s.shape, s.dtype),
        per_block)


# ---------------------------------------------------------------------------
# Full decoder-only LM
# ---------------------------------------------------------------------------

def init_lm(cfg: ModelConfig, key: jax.Array) -> dict:
    segs = plan_segments(cfg)
    keys = jax.random.split(key, len(segs) + 2)
    return {
        "embed": init_embed(cfg, keys[0]),
        "segments": tuple(init_segment(cfg, k, s) for k, s in zip(keys[1:], segs)),
        "final_norm": init_norm(cfg, cfg.d_model),
    }


def lm_backbone(cfg: ModelConfig, params: dict, h: jax.Array, *, mode: str,
                caches=None, pos=None, mesh=None, impl="naive",
                prefill_chunk=1024, mla_absorb=True, dp_axes=("data",),
                remat="none", scan_unroll=False, cache_margin=0):
    """Run all segments over input embeddings h. Returns (h, aux, caches)."""
    from repro.models.layers import shard_batch_dim

    h = shard_batch_dim(h, mesh, dp_axes)
    segs = plan_segments(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    caches_out = []
    for i, seg in enumerate(segs):
        seg_cache = caches[i] if caches is not None else None
        h, aux, c = segment_apply(
            cfg, params["segments"][i], seg, h, mode=mode, caches=seg_cache,
            pos=pos, mesh=mesh, impl=impl, prefill_chunk=prefill_chunk,
            mla_absorb=mla_absorb, dp_axes=dp_axes, remat=remat,
            scan_unroll=scan_unroll, cache_margin=cache_margin)
        aux_total = aux_total + aux
        caches_out.append(c)
    h = norm_fwd(cfg, params["final_norm"], h)
    return h, aux_total, tuple(caches_out) if mode != "train" else None


def lm_loss(cfg: ModelConfig, params: dict, batch: dict, *, mesh=None,
            impl="naive", dp_axes=("data",), remat="none",
            scan_unroll=False):
    """batch: tokens (B,S) int32, targets (B,S) int32, optional loss_mask,
    optional img_emb (B,N,D) spliced in front (VLM)."""
    h = embed_fwd(cfg, params["embed"], batch["tokens"])
    mask = batch.get("loss_mask")
    if cfg.num_image_tokens and "img_emb" in batch:
        img = batch["img_emb"].astype(h.dtype)
        h = jnp.concatenate([img, h], axis=1)
    h, aux, _ = lm_backbone(cfg, params, h, mode="train", mesh=mesh,
                            impl=impl, dp_axes=dp_axes, remat=remat,
                            scan_unroll=scan_unroll)
    if cfg.num_image_tokens and "img_emb" in batch:
        h = h[:, cfg.num_image_tokens:, :]      # loss over text positions only
    logits = lm_head_fwd(cfg, params["embed"], h)
    from repro.models.layers import shard_logits

    logits = shard_logits(logits, mesh, dp_axes)
    loss = cross_entropy(logits, batch["targets"], mask)
    total = loss + cfg.moe.router_aux_weight * aux
    return total, {"loss": loss, "aux_loss": aux}


def lm_prefill(cfg: ModelConfig, params: dict, batch: dict, *, mesh=None,
               impl="blockwise", prefill_chunk=1024, dp_axes=("data",),
               scan_unroll=False, cache_margin=0):
    """Returns (last-token logits, caches)."""
    h = embed_fwd(cfg, params["embed"], batch["tokens"])
    if cfg.num_image_tokens and "img_emb" in batch:
        h = jnp.concatenate([batch["img_emb"].astype(h.dtype), h], axis=1)
    h, _, caches = lm_backbone(cfg, params, h, mode="prefill", mesh=mesh,
                               impl=impl, prefill_chunk=prefill_chunk,
                               dp_axes=dp_axes, scan_unroll=scan_unroll,
                               cache_margin=cache_margin)
    logits = lm_head_fwd(cfg, params["embed"], h[:, -1:, :])
    return logits[:, 0, :], caches


def lm_decode(cfg: ModelConfig, params: dict, token: jax.Array,
              pos: jax.Array, caches, *, mesh=None, mla_absorb=True,
              dp_axes=("data",), scan_unroll=False):
    """token: (B,) int32; pos: scalar int32. Returns (logits, caches)."""
    h = embed_fwd(cfg, params["embed"], token[:, None])
    h, _, caches = lm_backbone(cfg, params, h, mode="decode", caches=caches,
                               pos=pos, mesh=mesh, mla_absorb=mla_absorb,
                               dp_axes=dp_axes, scan_unroll=scan_unroll)
    logits = lm_head_fwd(cfg, params["embed"], h)
    return logits[:, 0, :], caches


def lm_cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    return tuple(segment_cache_specs(cfg, seg, batch, max_len)
                 for seg in plan_segments(cfg))
