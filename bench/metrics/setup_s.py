"""Set-up: process start to the end of warm-up, on the host clock."""


def read(ctx):
    return ctx.setup_s
