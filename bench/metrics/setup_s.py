"""Process start to the end of the warm-up step (host clock)."""


def read(ctx):
    return ctx.run["setup_s"]
