"""Rays of every completed call over the time from the window's start to
the last completion, on the host clock."""


def read(ctx):
    w = ctx.window
    return sum(c.rows for c in w.calls) / (w.end - w.start)
