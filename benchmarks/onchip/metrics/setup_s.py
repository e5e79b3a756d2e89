"""setup_s: process start to the first timed operation (data from the seed,
the bulk load, every bucket's dispatch warmed)."""


def read(ctx):
    return ctx.setup_s
