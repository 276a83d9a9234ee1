"""Operations and bytes that a dense decoder's forward pass needs.

Computed from the configuration's shapes alone (``bench/configs/<name>.json``)
and never from what an implementation happens to move, so a roofline share
reads the same work whatever implements the step.  Counts are for the
algorithm: a matrix product of ``m x k`` by ``k x n`` is ``2 m k n``
operations; attention at context ``c`` costs ``4 c H hd`` per token and layer
(scores and the weighted sum of values); the input embedding is a lookup
and costs nothing.  Parameters and KV are counted at ``dtype_bytes`` each.
"""

from __future__ import annotations


def _glu(cfg: dict) -> bool:
    return cfg["mlp_kind"] in ("silu_glu", "geglu")


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix products (attention and MLP)."""
    d, h, kv, hd, ff = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (3 if _glu(cfg) else 2) * d * ff
    return attn + mlp


def norm_params(cfg: dict) -> int:
    """One norm's parameters (scale, and bias for a layer norm)."""
    return (2 if cfg["norm_kind"] == "layernorm" else 1) * cfg["d_model"]


def param_counts(cfg: dict) -> dict:
    L, d, V = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    layers = L * (layer_matmul_params(cfg) + 2 * norm_params(cfg))
    embed = V * d
    head = 0 if cfg["tie_embeddings"] else V * d
    total = layers + embed + head + norm_params(cfg)
    return {"layers": layers, "embed": embed, "head": head, "total": total}


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of one token over every layer."""
    return (2 * cfg["num_layers"] * cfg["num_kv_heads"] * cfg["head_dim"]
            * dtype_bytes)


def token_flops(cfg: dict, ctx: int, with_head: bool = True) -> float:
    """Forward operations for one token attending over ``ctx`` positions
    (itself included)."""
    L, d, V = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    f = 2.0 * L * layer_matmul_params(cfg)
    f += 4.0 * L * cfg["num_heads"] * cfg["head_dim"] * ctx
    if with_head:
        f += 2.0 * d * V
    return f


def prefill_flops(cfg: dict, n: int) -> float:
    """A prompt of ``n`` tokens, causal, with the head on its last token."""
    L, d, V = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    f = n * 2.0 * L * layer_matmul_params(cfg)
    f += 4.0 * L * cfg["num_heads"] * cfg["head_dim"] * n * (n + 1) / 2
    return f + 2.0 * d * V


def decode_step_cost(cfg: dict, ctxs, dtype_bytes: int = 2) -> tuple:
    """(operations, bytes) of one decode step over lanes whose new token
    attends over ``ctxs[i]`` positions.  Bytes: every weight but the
    embedding table once, one embedding row and the live KV (read, plus the
    new token's write) per lane."""
    pc = param_counts(cfg)
    read_params = pc["total"] - pc["embed"]
    if cfg["tie_embeddings"]:
        read_params += pc["embed"]          # the table is the head
    kvb = kv_bytes_per_token(cfg, dtype_bytes)
    flops = sum(token_flops(cfg, c) for c in ctxs)
    byts = dtype_bytes * (read_params + len(ctxs) * cfg["d_model"])
    byts += sum(c * kvb for c in ctxs)
    return flops, float(byts)


def least_time(flops: float, byts: float, peaks: dict) -> tuple:
    """Least time the chip could take and the bound that sets it."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = byts / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
