"""Share of the traced window, in %, in which the device was idle while a
``funky.monitor.*`` span was open on a host thread: the monitor launching
a program, moving bytes or syncing (``bench/harness/spans.py``, on the
profiler's clock)."""

from bench.harness import spans


def read(ctx):
    return spans.idle_share(ctx.trace, "launch")
