"""setup_s: process start to window start (load, build, compile or cache
load, warm dispatch).  Host clock."""


def read(ctx):
    return ctx.setup_s
