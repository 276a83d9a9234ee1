"""The ``decode_step`` program's share of its roofline, in %, for a
latent-attention, mixture-of-experts decoder (DeepSeek-V3's share).

Least time over device time.  The device time is the program's time in the
profiler trace over the traced window.  The least time is the larger of
two bounds on the work the algorithm needs for the same steps, computed
from the configuration's shapes and the lanes' lengths
(``bench/harness/mla_moe_math.py``): its operations over peak bf16 FLOP/s,
or its bytes over HBM bytes/s.  The bytes are, per step, every weight but
the embedding table and the held experts that no token of the step chose,
plus each token's latent cache over its context.  At 64 lanes the bytes
bound applies."""

from bench.harness import mla_moe_math


def read(ctx):
    p = (ctx.trace or {}).get("programs", {}).get("decode_step")
    if not p or not p["count"] or p["seconds"] <= 0:
        return None
    ctxs = ctx.decode_contexts(ctx.traced_t1)
    if not ctxs:
        return None
    flops, byts = mla_moe_math.decode_steps_cost(ctx.cfg, ctxs, p["count"])
    least, _ = mla_moe_math.least_time(flops, byts, ctx.peaks)
    return 100.0 * least / p["seconds"]
