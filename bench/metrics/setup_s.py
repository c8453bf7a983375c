"""Seconds from the start of the run to the start of the window: world
and pipeline from the seed, compilation, warm-up traffic."""


def read(ctx):
    return ctx.setup_s
