"""The program's own spans, read from its span ring once a run is over
(`implicitglobalgrid_tpu.utils.tracing.span_records`): one ``igg.multi_step``
span around each call of the timed callable, one ``igg.compile`` span per
backend compile (a compile-cache read included).

The window's calls are the last ``ctx.calls`` ``igg.multi_step`` spans (the
set-up's warm-up call comes before them); the window runs from the first of
them for ``ctx.window_s`` seconds.  A program that records no such spans
gives None to every reader here.
"""

from __future__ import annotations

MULTI_STEP, COMPILE = "igg.multi_step", "igg.compile"


def _program():
    try:
        from implicitglobalgrid_tpu.utils import telemetry, tracing
    except ImportError:
        return None, None
    return telemetry, tracing


def window_calls(ctx, records=None):
    """The ``igg.multi_step`` spans of the window's calls, or None."""
    if records is None:
        _, tracing = _program()
        records = tracing.span_records() if tracing else []
    calls = [r for r in records if r["name"] == MULTI_STEP]
    if ctx.calls <= 0 or len(calls) < ctx.calls:
        return None
    return calls[-ctx.calls:]


def compiles(ctx, records=None, counters=None):
    """``(set-up, window)``: the ``igg.compile`` spans that started before
    the window's first call, and those that started inside the window; None
    where the program counts no compiles (no ``compile.backend`` counter)
    or recorded no window calls."""
    telemetry, tracing = _program()
    if records is None:
        records = tracing.span_records() if tracing else []
    if counters is None:
        counters = telemetry.snapshot()["counters"] if telemetry else {}
    calls = window_calls(ctx, records)
    if "compile.backend" not in counters or calls is None:
        return None
    lo = calls[0]["t0"]
    hi = lo + ctx.window_s
    spans = [r for r in records if r["name"] == COMPILE]
    return ([r for r in spans if r["t0"] < lo],
            [r for r in spans if lo <= r["t0"] <= hi])
