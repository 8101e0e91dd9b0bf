"""Percent of the traced window in which no op runs on the device: one
minus the union of device-op intervals over the window, on the idlest chip."""


def read(ctx):
    t = ctx.trace
    return None if t is None else 100.0 * t.idle_share_max
