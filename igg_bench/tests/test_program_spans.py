"""The readers of the program's own spans (`igg_bench/program_spans.py` and
the three metrics built on it): on synthetic span records with known
answers, on a program that records none (as the commit before these spans
would), and on a real warm-up-then-window run of a model's callable."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from igg_bench import harness, program_spans

ROOT = harness.ROOT


def _read(name, ctx):
    return harness.load_module(ROOT, "metrics", name).read(ctx)


def _span(name, t0, dur, **args):
    return {"name": name, "t0": t0, "dur": dur, "args": args}


# Set-up: two compiles and the warm-up call; the window: three calls from
# t=10 for 3 s, one compile inside it; after it, the check's compile.
RECORDS = [
    _span("igg.compile", 1.0, 0.5, fun_name="jit(init)"),
    _span("igg.compile", 2.0, 1.5, fun_name="jit(step)"),
    _span("igg.multi_step", 5.0, 0.004, call=0),
    _span("igg.multi_step", 10.0, 0.001, call=1),
    _span("igg.compile", 10.5, 0.25, fun_name="jit(again)"),
    _span("igg.multi_step", 11.0, 0.002, call=2),
    _span("igg.multi_step", 12.0, 0.003, call=3),
    _span("igg.compile", 14.0, 0.75, fun_name="jit(gather)"),
]
COUNTERS = {"compile.backend": 4, "compile.backend_seconds": 3.0}


@pytest.fixture
def synthetic(monkeypatch):
    from implicitglobalgrid_tpu.utils import telemetry, tracing

    monkeypatch.setattr(tracing, "span_records", lambda: list(RECORDS))
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"counters": COUNTERS})
    return SimpleNamespace(calls=3, window_s=3.0)


def test_synthetic_spans(synthetic):
    ctx = synthetic
    assert [r["args"]["call"] for r in program_spans.window_calls(ctx)] == [1, 2, 3]
    assert _read("dispatch_ms_per_call", ctx) == pytest.approx(2.0)
    assert _read("window_compiles", ctx) == 1
    assert _read("setup_compile_backend_s", ctx) == pytest.approx(2.0)


def test_no_program_spans(monkeypatch):
    """A program without the spans or the compile counters: every reader
    gives None and none raises."""
    from implicitglobalgrid_tpu.utils import telemetry, tracing

    monkeypatch.setattr(tracing, "span_records", lambda: [])
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"counters": {}})
    ctx = SimpleNamespace(calls=3, window_s=3.0)
    for name in ("dispatch_ms_per_call", "window_compiles",
                 "setup_compile_backend_s"):
        assert _read(name, ctx) is None
    # spans but no compile counter: the compile readers still give None
    monkeypatch.setattr(tracing, "span_records", lambda: list(RECORDS))
    assert _read("window_compiles", ctx) is None
    assert _read("dispatch_ms_per_call", ctx) == pytest.approx(2.0)


def test_real_window(tiny):
    """A warm-up call in set-up and a window of back-to-back calls of the
    benchmark's own callable: no compile in the window, compiles in set-up."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.utils import telemetry, tracing

    root, spec = tiny
    cell = harness.load_cell(root, spec, "diffusion256.fused")
    tracing.reset()
    telemetry.reset()
    with force_tpu_interpret_mode():
        state, params = cell.solver.setup(cell.cfg, cell.grid, jax.devices()[:1])
        step = cell.solver.make_step(params, cell.cfg, cell.traffic)
        step.lower(*state).compile()
        state = step(*state)
        jax.block_until_ready(state)
        w0 = time.perf_counter()
        for _ in range(3):
            state = step(*state)
        jax.block_until_ready(state)
        ctx = SimpleNamespace(calls=3, window_s=time.perf_counter() - w0)
    igg.finalize_global_grid()
    assert _read("window_compiles", ctx) == 0
    assert _read("setup_compile_backend_s", ctx) > 0
    assert 0 < _read("dispatch_ms_per_call", ctx) < 1e3 * ctx.window_s
    assert jnp.isfinite(state[0]).all()
