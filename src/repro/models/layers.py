"""Common layers: norms, rotary embeddings, MLPs, token embeddings.

All modules are functional: ``init_*`` builds a params pytree (nested dicts of
jnp arrays), ``*_fwd`` applies it.  Norms and softmax run in float32; matmuls
run in the config compute dtype (bfloat16 for the full-size configs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int) -> dict:
    if cfg.norm_kind == "layernorm":
        return {"scale": jnp.ones((d,), cdtype(cfg)), "bias": jnp.zeros((d,), cdtype(cfg))}
    return {"scale": jnp.ones((d,), cdtype(cfg))}


def norm_fwd(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def rmsnorm_1d(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last axis with an explicit scale (qk-norm etc.)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_fwd(x: jax.Array, positions: jax.Array, theta: float,
             rope_pct: float = 1.0) -> jax.Array:
    """Apply RoPE.

    x: (..., S, H, hd), positions: broadcastable to (..., S).
    ``rope_pct`` < 1 rotates only the leading fraction of head dims
    (stablelm-style partial rotary).
    """
    hd = x.shape[-1]
    rot = int(hd * rope_pct)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, rot/2)
    angles = angles[..., None, :]                              # (..., S, 1, rot/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key: jax.Array, d_in: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d_in ** -0.5
    scale_ff = d_ff ** -0.5
    dt = cdtype(cfg)
    p = {
        "w_up": (jax.random.normal(k1, (d_in, d_ff)) * scale_in).astype(dt),
        "w_down": (jax.random.normal(k2, (d_ff, d_in)) * scale_ff).astype(dt),
    }
    if cfg.mlp_kind in ("silu_glu", "geglu"):
        p["w_gate"] = (jax.random.normal(k3, (d_in, d_ff)) * scale_in).astype(dt)
    return p


def mlp_fwd(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    up = x @ p["w_up"]
    if cfg.mlp_kind == "silu_glu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    elif cfg.mlp_kind == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * up
    else:  # plain gelu
        h = jax.nn.gelu(up)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------

def init_embed(cfg: ModelConfig, key: jax.Array) -> dict:
    dt = cdtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"embedding": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                        * cfg.d_model ** -0.5).astype(dt)
    return p


def embed_fwd(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    return p["embedding"][tokens]


def lm_head_fwd(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ p["embedding"].T
    return x @ p["lm_head"]


def shard_logits(logits: jax.Array, mesh, dp_axes) -> jax.Array:
    """Keep (B, S, V) logits vocab-sharded over the model axis.

    Without this constraint GSPMD tends to all-gather the full-vocab logits
    before the loss (a multi-GB f32 temp at 150k vocab); with it, the loss
    below reduces shard-locally + small all-reduces.
    """
    if (mesh is None or mesh.size == 1
            or "model" not in getattr(mesh, "axis_names", ())):
        return logits       # nothing to shard on one device
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    V = logits.shape[-1]
    bdim = (tuple(dp_axes)
            if dp_axes and logits.shape[0] % _axsize(mesh, dp_axes) == 0
            else None)
    tp = ("model" if V % mesh.shape["model"] == 0
          and "model" not in (bdim or ()) else None)
    spec = P(bdim, *([None] * (logits.ndim - 2)), tp)
    return jax.lax.with_sharding_constraint(
        logits, NamedSharding(mesh, spec))


def _axsize(mesh, axes) -> int:
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def shard_batch_dim(x: jax.Array, mesh, dp_axes) -> jax.Array:
    """Constrain dim0 (batch) over the data axes — anchors propagation so
    activations never silently replicate across data shards."""
    if mesh is None or mesh.size == 1 or not dp_axes:
        return x            # nothing to shard on one device
    if x.shape[0] % _axsize(mesh, dp_axes) != 0:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    spec = P(dp_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token cross-entropy, f32 accumulation, sharding-friendly.

    Formulated as elementwise ops + reductions over the vocab axis only —
    every op preserves a vocab-sharded layout (the gold-logit gather is a
    masked sum, not take_along_axis, so GSPMD never materializes full-vocab
    f32 logits per device).
    """
    V = logits.shape[-1]
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
    lse = jnp.log(sumexp)
    vocab = jnp.arange(V, dtype=targets.dtype)
    gold = jnp.sum(jnp.where(targets[..., None] == vocab, shifted, 0.0),
                   axis=-1)
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# Causal conv (SSM / RG-LRU input convolutions)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jax.Array, w: jax.Array, state: jax.Array | None = None):
    """Depthwise causal 1-D convolution.

    x: (B, S, C), w: (K, C).  Returns (y, new_state) where state carries the
    last K-1 inputs for single-step decoding.
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):, :] if k > 1 else jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)
    return y, new_state


def causal_conv1d_step(x: jax.Array, w: jax.Array, state: jax.Array):
    """One-token update. x: (B, C), state: (B, K-1, C) -> (y, new_state)."""
    k = w.shape[0]
    xp = jnp.concatenate([state.astype(x.dtype), x[:, None, :]], axis=1)  # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", xp, w.astype(x.dtype))
    return y, xp[:, 1:, :]
