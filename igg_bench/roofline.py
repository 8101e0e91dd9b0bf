"""A kernel's share of its HBM roofline, from the trace and the peak table.

The least bytes a kernel group must move (every input read once, every
output written once, `least_bytes_per_group` of the cell's solver) over the
chip's HBM bandwidth is the least time the group can take; its share is
that time over the group's measured kernel device time.  A group cannot
move its own inputs and outputs faster than the bandwidth peak, so the
share stays at or under 100%.  The bound is HBM alone: no f32 vector peak
is published for the v5e, so no compute bound is computed.
"""


def hbm_share(least_bytes, peak_bytes_per_s, kernel_s):
    """Percent of the HBM roofline for ``least_bytes`` moved in ``kernel_s``."""
    return 100.0 * least_bytes / peak_bytes_per_s / kernel_s


def read(ctx):
    t = ctx.trace
    if t is None or t.n_kernel_ops == 0 or t.kernel_s <= 0:
        return None
    groups = ctx.calls * ctx.groups_per_call
    return hbm_share(groups * ctx.least_bytes_per_group,
                     ctx.peaks["hbm_bytes_per_s"], t.kernel_s)
