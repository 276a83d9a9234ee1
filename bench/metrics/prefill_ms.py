"""Device milliseconds per admission: the ``prefill_admit`` programs' time
in the profiler trace over their executions in the traced window (one
execution per admission: prefill, first token, lane install)."""


def read(ctx):
    p = (ctx.trace or {}).get("programs", {}).get("prefill_admit")
    if not p or not p["count"]:
        return None
    return p["seconds"] / p["count"] * 1e3
