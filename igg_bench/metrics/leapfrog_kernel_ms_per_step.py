"""Device ms per step of the staggered leapfrog kernel's launches
(`ops/pallas_leapfrog.py`): the Mosaic kernel ops whose name or scope path
names a leapfrog variant (``igg_leapfrog_k<k>_plain`` / ``_zpatch``), read
from the trace's per-op totals."""

import re

LEAPFROG = re.compile(r"igg_leapfrog_k\d+_(plain|zpatch)")


def kernel_s(trace):
    """Seconds of leapfrog kernel ops in the window, or None if none ran."""
    s = [sec for key, sec in trace.top_ops
         if key.endswith(", kernel)") and LEAPFROG.search(key)]
    return sum(s) if s else None


def read(ctx):
    if ctx.trace is None:
        return None
    s = kernel_s(ctx.trace)
    return None if s is None else s * 1e3 / (ctx.calls * ctx.units_per_call)
