"""Device ms per step of ops that are neither a Pallas kernel nor exchange
(copies, pads, slices, loop control: the cadence's glue)."""


def read(ctx):
    t = ctx.trace
    return None if t is None else t.glue_s * 1e3 / (ctx.calls * ctx.units_per_call)
