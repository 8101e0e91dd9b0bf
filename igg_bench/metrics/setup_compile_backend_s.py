"""Seconds of backend compiles (compile-cache reads included) from process
start to the window: the program's ``igg.compile`` spans before the
window's first call (`igg_bench/program_spans.py`).  The rest of
`compile_s` is tracing and lowering."""

from igg_bench import program_spans


def read(ctx):
    split = program_spans.compiles(ctx)
    return None if split is None else sum(r["dur"] for r in split[0])
