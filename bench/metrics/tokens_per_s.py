"""Tokens trained in the window over the window's seconds, summed over the
cell's chips: every step of the window counts, and the window runs from
the first step's dispatch to the last step's completion on the host
clock."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return ctx.tokens / ctx.window_s
