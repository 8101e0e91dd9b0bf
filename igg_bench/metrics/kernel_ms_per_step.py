"""Device ms per step of the Mosaic kernel launches (tpu_custom_call)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.n_kernel_ops == 0:
        return None
    return t.kernel_s * 1e3 / (ctx.calls * ctx.units_per_call)
