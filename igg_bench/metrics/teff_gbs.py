"""Effective memory throughput per chip (GB/s), the reference's convention:
A_eff per step (arrays the step must stream x local cells x dtype size; per
PT iteration for porous) x steps completed in the window / window seconds."""


def read(ctx):
    return ctx.a_eff_bytes * ctx.calls * ctx.units_per_call / ctx.window_s / 1e9
