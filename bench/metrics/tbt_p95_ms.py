"""95th percentile of every gap between consecutive output tokens of a
request, as the client saw them (host clock, 1 ms polling), over every
token delivered in the window."""

import numpy as np


def read(ctx):
    g = ctx.gaps()
    if g.size == 0:
        return None
    return float(np.quantile(g, 0.95)) * 1e3
