"""Host ms per call inside the program's multi-step callable during the
window: the ``igg.multi_step`` spans' total over their count (the program's
span ring, `igg_bench/program_spans.py`)."""

from igg_bench import program_spans


def read(ctx):
    calls = program_spans.window_calls(ctx)
    if calls is None:
        return None
    return sum(r["dur"] for r in calls) * 1e3 / len(calls)
