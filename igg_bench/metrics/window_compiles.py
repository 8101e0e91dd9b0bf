"""Backend compiles that started inside the measured window: the program's
``igg.compile`` spans there (`igg_bench/program_spans.py`).  Set-up warms
up every shape, so this should read 0."""

from igg_bench import program_spans


def read(ctx):
    split = program_spans.compiles(ctx)
    return None if split is None else len(split[1])
