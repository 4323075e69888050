"""Set-up time: process start to the first timed unit, compiles included."""


def read(ctx):
    return ctx.setup_s
