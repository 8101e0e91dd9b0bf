"""Seconds from process start to the first timed call: grid, seeded state,
compilation (or the compile cache's read), warm-up call."""


def read(ctx):
    return ctx.setup_s
