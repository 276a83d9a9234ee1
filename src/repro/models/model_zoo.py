"""Model zoo: one ``build_model`` entry point for all 10 assigned archs.

``ModelBundle`` packages the functional API the rest of the framework uses:

    init(rng)                      -> params pytree
    loss_fn(params, batch)         -> (loss, metrics)      [train shapes]
    prefill_fn(params, batch)      -> (logits, caches)     [prefill shapes]
    decode_fn(params, tok, pos, caches) -> (logits, caches) [decode shapes]

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of a given (arch x shape) cell — the dry-run lowers against these
without allocating anything.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as _encdec
from repro.models import transformer as _tf
from repro.models.layers import cdtype


@dataclass
class ModelBundle:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    loss_fn: Callable[..., Any]
    prefill_fn: Callable[..., Any]
    decode_fn: Callable[..., Any]
    cache_specs: Callable[[int, int], Any]


def _default_dp_axes(mesh) -> tuple[str, ...]:
    if mesh is None:
        return ("data",)
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _trivial_mesh():
    """A one-device abstract mesh: the expert ``shard_map`` binds to the
    device its inputs live on, so a model built once serves any vSlice."""
    return jax.sharding.AbstractMesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def build_model(cfg: ModelConfig, *, mesh=None, impl: str = "naive",
                prefill_impl: str = "blockwise", remat: str = "none",
                dp_axes: tuple[str, ...] | None = None,
                mla_absorb: bool = True, prefill_chunk: int = 1024,
                scan_unroll: bool = False,
                cache_margin: int = 128) -> ModelBundle:
    if mesh is None and cfg.moe.enabled:
        mesh = _trivial_mesh()
    if dp_axes is None:
        dp_axes = _default_dp_axes(mesh)

    if cfg.family == "encdec":
        return ModelBundle(
            cfg=cfg,
            init=partial(_encdec.init_encdec, cfg),
            loss_fn=partial(_encdec.encdec_loss, cfg, mesh=mesh, impl=impl,
                            dp_axes=dp_axes, remat=remat,
                            scan_unroll=scan_unroll),
            prefill_fn=partial(_encdec.encdec_prefill, cfg, mesh=mesh,
                               impl=prefill_impl, prefill_chunk=prefill_chunk,
                               dp_axes=dp_axes, scan_unroll=scan_unroll,
                               cache_margin=cache_margin),
            decode_fn=partial(_encdec.encdec_decode, cfg, mesh=mesh,
                              dp_axes=dp_axes, scan_unroll=scan_unroll),
            cache_specs=lambda b, s: _encdec.encdec_cache_specs(cfg, b, s, s),
        )

    return ModelBundle(
        cfg=cfg,
        init=partial(_tf.init_lm, cfg),
        loss_fn=partial(_tf.lm_loss, cfg, mesh=mesh, impl=impl,
                        dp_axes=dp_axes, remat=remat,
                        scan_unroll=scan_unroll),
        prefill_fn=partial(_tf.lm_prefill, cfg, mesh=mesh, impl=prefill_impl,
                           prefill_chunk=prefill_chunk, dp_axes=dp_axes,
                           scan_unroll=scan_unroll,
                           cache_margin=cache_margin),
        decode_fn=partial(_tf.lm_decode, cfg, mesh=mesh,
                          mla_absorb=mla_absorb, dp_axes=dp_axes,
                          scan_unroll=scan_unroll),
        cache_specs=partial(_tf.lm_cache_specs, cfg),
    )


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs) per (arch x shape) cell
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Abstract inputs for the step function selected by ``shape.kind``.

    train   -> {"batch": {...}}
    prefill -> {"batch": {...}}
    decode  -> {"token", "pos", "caches"}
    """
    B, S = shape.global_batch, shape.seq_len
    tok = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    emb = lambda *s: jax.ShapeDtypeStruct(s, cdtype(cfg))

    if shape.kind == "train":
        if cfg.family == "encdec":
            T = int(S * cfg.tgt_ratio)
            batch = {"src_emb": emb(B, S, cfg.d_model),
                     "tgt_tokens": tok(B, T), "tgt_targets": tok(B, T)}
        elif cfg.family == "vlm":
            Stext = S - cfg.num_image_tokens
            batch = {"tokens": tok(B, Stext), "targets": tok(B, Stext),
                     "img_emb": emb(B, cfg.num_image_tokens, cfg.d_model)}
        else:
            batch = {"tokens": tok(B, S), "targets": tok(B, S)}
        return {"batch": batch}

    if shape.kind == "prefill":
        if cfg.family == "encdec":
            T = int(S * cfg.tgt_ratio)
            batch = {"src_emb": emb(B, S, cfg.d_model), "tgt_tokens": tok(B, T)}
        elif cfg.family == "vlm":
            batch = {"tokens": tok(B, S - cfg.num_image_tokens),
                     "img_emb": emb(B, cfg.num_image_tokens, cfg.d_model)}
        else:
            batch = {"tokens": tok(B, S)}
        return {"batch": batch}

    # decode: one new token against caches of capacity seq_len
    bundle_specs = (_encdec.encdec_cache_specs(cfg, B, S, S)
                    if cfg.family == "encdec"
                    else _tf.lm_cache_specs(cfg, B, S))
    return {
        "token": tok(B),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "caches": bundle_specs,
    }


# ---------------------------------------------------------------------------
# Analytic parameter counts (exact: derived from the abstract param pytree)
# ---------------------------------------------------------------------------

def analytic_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    bundle = build_model(cfg, mesh=_trivial_mesh() if cfg.moe.enabled else None)
    shapes = jax.eval_shape(bundle.init, jax.random.key(0))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    if active_only and cfg.moe.enabled:
        n_moe_layers = cfg.num_layers - cfg.moe.n_dense_layers
        inactive = (cfg.moe.num_experts - cfg.moe.top_k)
        per_expert = 3 * cfg.d_model * cfg.moe.d_ff
        total -= n_moe_layers * inactive * per_expert
    return total


def embedding_param_count(cfg: ModelConfig) -> int:
    n = cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        n *= 2
    return n
