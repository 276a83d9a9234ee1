"""Host microseconds per token of the engine and monitor path: the change
over the window of the engine's ``host_device_split()`` (host clock: each
iteration's wall time less the monitor-timed EXECUTE phases, which close
at ``block_until_ready``), summed over replicas."""


def read(ctx):
    if not ctx.split or ctx.split["tokens"] <= 0:
        return None
    return ctx.split["host_s"] / ctx.split["tokens"] * 1e6
