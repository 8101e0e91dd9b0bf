"""Fused-kernel fallbacks the program counted up to the end of the run:
its ``fused.fallback`` telemetry counter, one per trace of a kernel path
the envelope rejected.  None where the program keeps no such counter."""

COUNTER = "fused.fallback"


def read(ctx):
    try:
        from implicitglobalgrid_tpu.utils import telemetry
    except ImportError:
        return None
    n = telemetry.snapshot()["counters"].get(COUNTER)
    return None if n is None else float(n)
