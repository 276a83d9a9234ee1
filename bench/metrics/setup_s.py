"""Set-up seconds on the host clock: process start to the window's opening
(JAX start-up, deployment through the orchestrator, compilation or the
persistent cache's load, weights made on the device, the warm-up requests
and filling every lane)."""


def read(ctx):
    return ctx.setup_s
