"""The ``decode_step`` program's share of its roofline, in %.

Least time over device time.  The device time is the program's time in the
profiler trace over the traced window.  The least time is the larger of
two bounds on the work the algorithm needs for the same steps, computed
from the configuration's shapes and the lanes' lengths
(``bench/harness/model_math.py``): its operations over peak bf16 FLOP/s,
or its bytes over HBM bytes/s.  The bytes are every weight but the
embedding table once per step plus the live KV of the occupied lanes, so a
decode step that stops gathering its whole context is credited for it.
At 8 lanes the bytes bound applies (about 8 operations per byte against a
ridge of 240)."""

from bench.harness import model_math


def read(ctx):
    p = (ctx.trace or {}).get("programs", {}).get("decode_step")
    if not p or not p["count"] or p["seconds"] <= 0:
        return None
    ctxs = ctx.decode_contexts(ctx.traced_t1)
    if not ctxs:
        return None
    flops, byts = model_math.decode_step_cost(ctx.cfg, ctxs)
    weights = model_math.decode_step_cost(ctx.cfg, [])[1]
    # the weights are read once per step, the KV once per token
    byts += (p["count"] - 1) * weights
    least, _ = model_math.least_time(flops, byts, ctx.peaks)
    return 100.0 * least / p["seconds"]
