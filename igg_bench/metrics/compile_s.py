"""Host seconds around lowering and compiling the timed program in set-up
(a read of the in-checkout compile cache when it is warm)."""


def read(ctx):
    return ctx.compile_s
