"""Model FLOP utilization of the whole step, in %, for a latent-attention,
mixture-of-experts decoder (DeepSeek-V3's share): the operations that the
prefill and decode tokens of the traced window need
(``bench/harness/mla_moe_math.py``; prompts unpadded, each decode token at
its own context, the held experts at their share of the routed pairs), over
the traced window's length times the chips times peak bf16 FLOP/s."""

from bench.harness import mla_moe_math


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    cfg = ctx.cfg
    flops = sum(mla_moe_math.prefill_flops(cfg, r.prompt_len)
                for r in ctx.admissions(ctx.traced_t1))
    flops += sum(mla_moe_math.token_flops(cfg, c)
                 for c in ctx.decode_contexts(ctx.traced_t1))
    if flops <= 0:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.trace["window_s"] * peak)
