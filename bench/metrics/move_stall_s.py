"""Mean over the window's replica moves of the time from the evict command
to the first token that replica delivered after its resume or migration
(host clock)."""


def read(ctx):
    stalls = [m.t_first - m.t_cmd for m in ctx.moves
              if ctx.in_window(m.t_cmd) and m.t_first is not None]
    if not stalls:
        return None
    return sum(stalls) / len(stalls)
