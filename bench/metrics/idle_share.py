"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window), from the
profiler trace, averaged over the chips that ran anything."""


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0 \
            or not ctx.trace["chips_traced"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
