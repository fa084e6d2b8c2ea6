"""Output tokens that reached the caller inside the window, over the window: all the work over all the time."""


def read(ctx):
    return ctx.tokens_in_window / ctx.seconds
