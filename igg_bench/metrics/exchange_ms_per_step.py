"""Device ms per step of the halo exchange: ops under the igg_halo_exchange /
igg_slab_exchange_* scopes and collectives, with the collectives' transfers
in flight (their union; read on the chips whose trace records transfers in
flight: on the v5e 2x2, chip 0)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.exchange_s <= 0:
        return None
    return t.exchange_s * 1e3 / (ctx.calls * ctx.units_per_call)
