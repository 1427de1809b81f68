"""Set-up seconds: process start to the window, warm-up included."""


def read(ctx):
    return ctx.setup_s
