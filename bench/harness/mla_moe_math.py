"""Operations and bytes that a latent-attention, mixture-of-experts decoder
(DeepSeek-V3) needs, for one chip's share of its experts.

Computed from the configuration's shapes alone (``bench/configs/<name>.json``,
the catalog's key names) and never from what an implementation happens to
move, so a roofline share reads the same work whatever implements the
step.  A matrix product of ``m x k`` by ``k x n`` is ``2 m k n``
operations.  Decode attends in latent space (the absorbed form): per
position and layer ``2 H (r + rope)`` for the scores over the latent and the
rope key and ``2 H r`` for the weighted sum of latents, ``r`` the
kv_lora_rank.  Prefill attends in the decompressed form: ``2 H (nope +
rope)`` and ``2 H v`` per pair of positions.  The dense layers, the shared
expert, the router and the head count in full.  A routed pair lands on a
held expert with probability ``held / routed``, so the held experts compute
``k held / routed`` pairs per token and layer.  Bytes: every weight but the
embedding table once per step, of the held experts only those that a token
of the step chose, ``held (1 - (1 - k/routed) ** n)`` in expectation at
``n`` lanes, one embedding row per lane, and each token's latent cache
(``r + rope`` values per position and layer) over its context, its own
position included.  Values count ``dtype_bytes`` each.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "qr": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "rope": cfg["qk_rope_head_dim"], "nope": cfg["qk_nope_head_dim"],
            "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "V": cfg["vocab_size"],
            "E": cfg["experts_routed"], "held": cfg["experts_held"],
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "L": cfg["num_hidden_layers"],
            "nd": cfg["first_k_dense_replace"]}


def mla_params(cfg: dict) -> int:
    """One layer's MLA matrices (the two norms not included)."""
    m = _dims(cfg)
    d, h, qr, r, rope = m["d"], m["h"], m["qr"], m["r"], m["rope"]
    return (d * qr + qr * h * (m["nope"] + rope) + d * (r + rope)
            + r * h * m["nope"] + r * h * m["v"] + h * m["v"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert (SwiGLU of width ``moe_intermediate_size``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_counts(cfg: dict) -> dict:
    """Parameters this chip holds, by part."""
    m = _dims(cfg)
    d = m["d"]
    norms = 2 * d + m["qr"] + m["r"]
    attn = mla_params(cfg) + norms
    dense = attn + 3 * d * m["ff"]
    shared = m["shared"] * expert_params(cfg)
    moe_fixed = attn + d * m["E"] + m["E"] + shared     # router and bias
    moe = moe_fixed + m["held"] * expert_params(cfg)
    n_moe = m["L"] - m["nd"]
    layers = m["nd"] * dense + n_moe * moe
    embed = head = m["V"] * d
    return {"dense_layer": dense, "moe_layer": moe, "moe_fixed": moe_fixed,
            "layers": layers, "embed": embed, "head": head,
            "total": layers + embed + head + d}


def latent_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """Latent cache of one token over every layer."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * dtype_bytes)


def _token_matmul_flops(cfg: dict) -> float:
    """A token's products with weights, in every layer, head excluded."""
    m = _dims(cfg)
    d, n_moe = m["d"], m["L"] - m["nd"]
    attn = mla_params(cfg)
    dense = attn + 3 * d * m["ff"]
    moe = (attn + d * m["E"] + m["shared"] * expert_params(cfg)
           + m["k"] * m["held"] / m["E"] * expert_params(cfg))
    return 2.0 * (m["nd"] * dense + n_moe * moe)


def token_flops(cfg: dict, ctx: int, with_head: bool = True) -> float:
    """Decode operations for one token attending over ``ctx`` positions
    (itself included), in latent space."""
    m = _dims(cfg)
    f = _token_matmul_flops(cfg)
    f += 2.0 * m["L"] * m["h"] * (2 * m["r"] + m["rope"]) * ctx
    if with_head:
        f += 2.0 * m["d"] * m["V"]
    return f


def prefill_flops(cfg: dict, n: int) -> float:
    """A prompt of ``n`` tokens, causal, decompressed attention, with the
    head on its last token."""
    m = _dims(cfg)
    f = n * _token_matmul_flops(cfg)
    f += (2.0 * m["L"] * m["h"] * (m["nope"] + m["rope"] + m["v"])
          * n * (n + 1) / 2)
    return f + 2.0 * m["d"] * m["V"]


def held_experts_read(cfg: dict, lanes: int) -> float:
    """Held experts a step of ``lanes`` tokens reads, in expectation."""
    m = _dims(cfg)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["E"]) ** lanes)


def step_weight_bytes(cfg: dict, lanes: int, dtype_bytes: int = 2) -> float:
    """Weights one decode step over ``lanes`` tokens reads: all but the
    embedding table and the held experts no token chose, plus one
    embedding row per lane."""
    pc = param_counts(cfg)
    m = _dims(cfg)
    n_moe = m["L"] - m["nd"]
    fixed = pc["total"] - pc["embed"] - n_moe * m["held"] * expert_params(cfg)
    experts = n_moe * held_experts_read(cfg, lanes) * expert_params(cfg)
    return float(dtype_bytes * (fixed + experts + lanes * m["d"]))


def decode_step_cost(cfg: dict, ctxs, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one decode step over lanes whose new token
    attends over ``ctxs[i]`` positions."""
    flops = sum(token_flops(cfg, c) for c in ctxs)
    byts = step_weight_bytes(cfg, len(ctxs), dtype_bytes)
    byts += sum(c * latent_bytes_per_token(cfg, dtype_bytes) for c in ctxs)
    return flops, byts


def decode_steps_cost(cfg: dict, ctxs, steps: int,
                      dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of ``steps`` decode steps that produced tokens
    at contexts ``ctxs``, the lanes spread evenly over the steps."""
    if steps <= 0 or not ctxs:
        return 0.0, 0.0
    lanes = max(1, round(len(ctxs) / steps))
    flops = sum(token_flops(cfg, c) for c in ctxs)
    byts = steps * step_weight_bytes(cfg, lanes, dtype_bytes)
    byts += sum(c * latent_bytes_per_token(cfg, dtype_bytes) for c in ctxs)
    return flops, byts


def least_time(flops: float, byts: float, peaks: dict) -> tuple:
    """Least time the chip could take and the bound that sets it."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = byts / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
