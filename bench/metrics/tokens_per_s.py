"""Output tokens delivered to clients in the window over its seconds, on the
host clock; requests still in flight at the window's close count."""


def read(ctx):
    return len(ctx.window_tokens()) / ctx.seconds
