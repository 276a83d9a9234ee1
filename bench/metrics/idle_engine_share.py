"""Share of the traced window, in %, in which the device was idle while a
``funky.engine.*`` span and no monitor span was open: the engine step's
host work (admission, page mapping, block-table flush, commit;
``bench/harness/spans.py``, on the profiler's clock)."""

from bench.harness import spans


def read(ctx):
    return spans.idle_share(ctx.trace, "engine")
