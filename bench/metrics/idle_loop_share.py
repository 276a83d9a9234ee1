"""Share of the traced window, in %, in which the device was idle while
only the runtime's driver loop or the router had a span open: router pop
and complete, the idle poll, outside the engine step
(``bench/harness/spans.py``, on the profiler's clock)."""

from bench.harness import spans


def read(ctx):
    return spans.idle_share(ctx.trace, "loop")
