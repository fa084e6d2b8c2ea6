"""Process start to the first due request: loading, warming up, the reference comparison and, in a run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s
