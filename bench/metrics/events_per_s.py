"""Events answered inside the window, over the window."""


def read(ctx):
    return ctx.answered_in_window / ctx.window_s
