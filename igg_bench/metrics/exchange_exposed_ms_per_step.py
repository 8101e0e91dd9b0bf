"""Device ms per step of exchange time (`exchange_ms_per_step`) during which
no kernel runs on the same chip: the part the pipelined schedule has not
hidden under the interior kernel."""


def read(ctx):
    t = ctx.trace
    if t is None or t.exchange_s <= 0:
        return None
    return t.exchange_exposed_s * 1e3 / (ctx.calls * ctx.units_per_call)
