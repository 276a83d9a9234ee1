"""Mixture-of-experts layer with expert parallelism over the "model" axis.

Dispatch strategy (DeepSeek-style fine-grained MoE, 64–256 experts):

GShard's dense one-hot dispatch tensor (tokens × experts × capacity) is
infeasible at this scale (it would be ~10^13 bytes for deepseek-v3 at
train_4k), so we use a *sort-based capacity dispatch* inside ``shard_map``:

1. router top-k per token (gates renormalized over the selected experts);
2. flatten (token, k) pairs, ``argsort`` by expert id;
3. position-within-expert via ``searchsorted``; pairs beyond the static
   per-expert capacity ``C = ceil(T*k/E * capacity_factor)`` are dropped
   (classic capacity-based routing);
4. every model-axis shard owns ``E/ep`` experts: it scatters *slot → token
   index* (cheap int ops), gathers only its local ``(E_local*C, D)`` activation
   block, runs the per-expert MLPs as one batched einsum, and scatter-adds the
   gated outputs back to token positions;
5. ``psum`` over the model axis combines contributions — the same all-reduce a
   tensor-parallel FFN would need, so EP costs no extra collective phase.

Activations enter replicated over "model" (standard TP layout), so no
all-to-all is required.  The router and its aux load-balancing loss are
computed identically on every shard.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import cdtype, init_mlp, mlp_fwd


def init_moe(cfg: ModelConfig, key: jax.Array) -> dict:
    m = cfg.moe
    dt = cdtype(cfg)
    D, E, F = cfg.d_model, m.num_experts, m.d_ff
    ks = jax.random.split(key, 5)
    s_in, s_ff = D ** -0.5, F ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (D, E)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, D, F)) * s_in).astype(dt),
        "w_up": (jax.random.normal(ks[2], (E, D, F)) * s_in).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, F, D)) * s_ff).astype(dt),
    }
    if m.num_shared_experts:
        import dataclasses

        shared_cfg = dataclasses.replace(cfg, mlp_kind="silu_glu")
        p["shared"] = init_mlp(shared_cfg, ks[4], D, F * m.num_shared_experts)
    return p


def _capacity(tokens: int, k: int, num_experts: int, cf: float) -> int:
    return max(8, int(math.ceil(tokens * k / num_experts * cf)))


def _expert_shard(x2d, router_w, wg, wu, wd, *, top_k: int, num_experts: int,
                  capacity: int, ep_axis: str, dp_axes: tuple[str, ...]):
    """Body run per model-axis shard. x2d: (T, D) replicated over ep_axis."""
    T, D = x2d.shape
    E_local = wg.shape[0]
    r = jax.lax.axis_index(ep_axis)
    e0 = r * E_local

    logits = (x2d.astype(jnp.float32) @ router_w)              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                  # (T, k)
    gates = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                                  # (T*k,)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)                                 # stable
    sorted_e = flat_e[order]
    sorted_tok = order // top_k
    sorted_g = flat_g[order]
    pos = jnp.arange(T * top_k) - jnp.searchsorted(sorted_e, sorted_e, side="left")

    local = (sorted_e >= e0) & (sorted_e < e0 + E_local) & (pos < capacity)
    slot = jnp.where(local, (sorted_e - e0) * capacity + pos, E_local * capacity)

    # slot -> token routing tables (int scatters; tiny).
    tok_for_slot = jnp.full((E_local * capacity + 1,), T, jnp.int32)
    tok_for_slot = tok_for_slot.at[slot].set(sorted_tok.astype(jnp.int32), mode="drop")
    gate_for_slot = jnp.zeros((E_local * capacity + 1,), jnp.float32)
    gate_for_slot = gate_for_slot.at[slot].set(sorted_g, mode="drop")
    tok_for_slot = tok_for_slot[:-1]
    gate_for_slot = gate_for_slot[:-1]

    # Gather local expert inputs: (E_local * C, D); OOB sentinel row = 0.
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    xs = x_pad[tok_for_slot].reshape(E_local, capacity, D)

    h = jnp.einsum("ecd,edf->ecf", xs, wg)
    u = jnp.einsum("ecd,edf->ecf", xs, wu)
    ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)     # (E_local,C,D)

    contrib = ys.reshape(E_local * capacity, D) * gate_for_slot[:, None].astype(ys.dtype)
    out = jnp.zeros((T, D), ys.dtype).at[tok_for_slot].add(contrib, mode="drop")
    out = jax.lax.psum(out, ep_axis)

    # Aux load-balancing loss (replicated — identical on all shards).
    f = jnp.zeros((num_experts,), jnp.float32).at[flat_e].add(1.0) / (T * top_k)
    p_mean = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(f * p_mean)
    aux = jax.lax.pmean(aux, dp_axes)   # replicate across data shards too
    return out, aux


def _expert_shard_a2a(x2d, router_w, wg, wu, wd, *, top_k: int,
                      num_experts: int, capacity: int,
                      ep_axes: tuple[str, ...]):
    """2D expert parallelism: tokens travel, weights stay resident.

    Runs with tokens sharded over *all* of ``ep_axes`` and ``E/n_ep`` experts
    resident per device.  Dispatch: sort-by-expert into an (E, C, D) buffer,
    ``all_to_all`` it so each device receives every source's slice for its
    own experts, run the local expert MLPs, reverse the all_to_all, combine.
    Unlike the weight-gathered path, expert *gradients* are complete on the
    owning device — no cross-shard gradient reduction for expert weights.
    """
    T, D = x2d.shape
    E_local = wg.shape[0]
    n_ep = num_experts // E_local
    r = jax.lax.axis_index(ep_axes)

    logits = (x2d.astype(jnp.float32) @ router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    gates = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    sorted_tok = order // top_k
    sorted_g = flat_g[order]
    pos = jnp.arange(T * top_k) - jnp.searchsorted(sorted_e, sorted_e,
                                                   side="left")
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos,
                     num_experts * capacity)

    tok_for_slot = jnp.full((num_experts * capacity + 1,), T, jnp.int32)
    tok_for_slot = tok_for_slot.at[slot].set(sorted_tok.astype(jnp.int32))
    gate_for_slot = jnp.zeros((num_experts * capacity + 1,), jnp.float32)
    gate_for_slot = gate_for_slot.at[slot].set(sorted_g)
    tok_for_slot = tok_for_slot[:-1]
    gate_for_slot = gate_for_slot[:-1]

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    send = x_pad[tok_for_slot]                        # (E*C, D)
    send = send.reshape(num_experts, capacity, D)

    # tokens -> expert owners: each device receives (n_ep src, E_local, C, D)
    recv = jax.lax.all_to_all(
        send.reshape(n_ep, E_local, capacity, D), ep_axes, 0, 0, tiled=False)
    xs = recv.transpose(1, 0, 2, 3).reshape(E_local, n_ep * capacity, D)

    h = jnp.einsum("ecd,edf->ecf", xs, wg)
    u = jnp.einsum("ecd,edf->ecf", xs, wu)
    ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)

    back = ys.reshape(E_local, n_ep, capacity, D).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, ep_axes, 0, 0, tiled=False)
    ret = ret.reshape(num_experts * capacity, D)

    contrib = ret * gate_for_slot[:, None].astype(ys.dtype)
    out = jnp.zeros((T, D), ys.dtype).at[tok_for_slot].add(
        contrib, mode="drop")

    f = jnp.zeros((num_experts,), jnp.float32).at[flat_e].add(1.0) / (T * top_k)
    p_mean = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(f * p_mean)
    aux = jax.lax.pmean(aux, ep_axes)
    return out, aux


def a2a_axes_for(cfg: ModelConfig, mesh) -> tuple[str, ...]:
    """Largest ep-axis set the expert count supports."""
    E = cfg.moe.num_experts
    for axes in (("data", "model"), ("model",)):
        if all(a in mesh.axis_names for a in axes):
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if E % n == 0:
                return axes
    return ()


def moe_fwd(cfg: ModelConfig, p: dict, x: jax.Array, *, mesh,
            dp_axes: tuple[str, ...] = ("data",), ep_axis: str = "model",
            dispatch: str = "local"):
    """x: (B, S, D) -> (out, aux_loss).

    dispatch="local": EP over the model axis, activations replicated there
    (no all-to-all; expert weights ZeRO-gathered if fsdp policy).
    dispatch="a2a":   2D EP — experts resident, tokens all-to-all'd.
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    x2d = x.reshape(T, D)

    ep_axes = a2a_axes_for(cfg, mesh) if dispatch == "a2a" else ()
    if dispatch == "a2a" and ep_axes:
        n_ep = 1
        for a in ep_axes:
            n_ep *= mesh.shape[a]
        tok_axes = tuple(dict.fromkeys(
            [a for a in dp_axes if a != "model"] + list(ep_axes)))
        n_tok = 1
        for a in tok_axes:
            n_tok *= mesh.shape[a]
        if T % n_tok == 0:
            local_T = T // n_tok
            capacity = _capacity(local_T, m.top_k, m.num_experts,
                                 m.capacity_factor)
            body = partial(_expert_shard_a2a, top_k=m.top_k,
                           num_experts=m.num_experts, capacity=capacity,
                           ep_axes=ep_axes)
            out2d, aux = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(tok_axes, None), P(None, None),
                          P(ep_axes, None, None), P(ep_axes, None, None),
                          P(ep_axes, None, None)),
                out_specs=(P(tok_axes, None), P()),
                check_vma=False,
            )(x2d, p["router"], p["w_gate"], p["w_up"], p["w_down"])
            out = out2d.reshape(B, S, D)
            if m.num_shared_experts:
                import dataclasses

                shared_cfg = dataclasses.replace(cfg, mlp_kind="silu_glu")
                out = out + mlp_fwd(shared_cfg, p["shared"], x)
            return out, aux
        # fall through to local dispatch when tokens don't divide

    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    local_T = T // dp
    capacity = _capacity(local_T, m.top_k, m.num_experts, m.capacity_factor)

    body = partial(
        _expert_shard, top_k=m.top_k, num_experts=m.num_experts,
        capacity=capacity, ep_axis=ep_axis, dp_axes=dp_axes)

    out2d, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp_axes, None), P(None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=(P(dp_axes, None), P()),
        check_vma=False,
    )(x2d, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    out = out2d.reshape(B, S, D)

    if m.num_shared_experts:
        import dataclasses

        shared_cfg = dataclasses.replace(cfg, mlp_kind="silu_glu")
        out = out + mlp_fwd(shared_cfg, p["shared"], x)
    return out, aux
