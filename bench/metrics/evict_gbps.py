"""Bytes the window's evictions saved to the host over the seconds they
took, in GB/s: the runtime's evict timeline events (``saved_bytes``,
``total_seconds``; the copy to the host is synchronous)."""


def read(ctx):
    secs = sum(e["total_seconds"] for e in ctx.evicts)
    if not ctx.evicts or secs <= 0:
        return None
    return sum(e["saved_bytes"] for e in ctx.evicts) / secs / 1e9
