"""Cross-rank observability plane tests (ISSUE 10; docs/observability.md).

Covers the span API (nesting, bounded ring, disabled-mode no-op identity),
the per-rank trace dump + merged Chrome-trace validity (the tier-1 pin:
valid JSON, one track per rank, per-track monotonic timestamps, alignment
metadata with its honesty bound), the straggler probe's single-process
skip, the crash flight recorder, the cost-model reconciliation report
(`analysis.reconcile`) and its reported — not yet gated — perf-gate keys.
The real 2-process gloo legs live in ``test_distributed.py`` /
``tests/_distributed_worker.py``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils import telemetry as tele
from implicitglobalgrid_tpu.utils import tracing

_here = os.path.dirname(os.path.abspath(__file__))
_repo = os.path.dirname(_here)


@pytest.fixture(autouse=True)
def _fresh_state():
    tele.reset()
    tracing.reset()
    yield
    tele.reset()
    tracing.reset()


# -- span API -----------------------------------------------------------------


def test_trace_span_records_nested_spans():
    with tracing.trace_span("outer", kind="test"):
        with tracing.trace_span("inner", step=1):
            pass
    recs = tracing.span_records()
    names = [r["name"] for r in recs]
    # the inner span EXITS first, so it lands in the ring first
    assert names == ["inner", "outer"]
    inner, outer = recs
    assert inner["args"] == {"step": 1}
    assert outer["args"] == {"kind": "test"}
    assert inner["dur"] >= 0 and outer["dur"] >= inner["dur"]
    # containment: the inner span lies within the outer one
    assert outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["dur"] <= outer["t0"] + outer["dur"] + 1e-9

    summary = tracing.span_summary()
    assert summary["inner"]["count"] == 1
    assert summary["outer"]["total_s"] == pytest.approx(outer["dur"])


def test_trace_span_disabled_returns_shared_noop(monkeypatch):
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    assert tracing.trace_span("x") is tracing.NOOP_SPAN
    with tracing.trace_span("x", a=1):
        pass
    monkeypatch.setenv("IGG_TELEMETRY", "1")
    monkeypatch.setenv("IGG_TRACE_RING", "0")
    assert tracing.trace_span("y") is tracing.NOOP_SPAN
    monkeypatch.delenv("IGG_TRACE_RING")
    assert tracing.span_records() == []


def test_trace_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("IGG_TRACE_RING", "8")
    for i in range(50):
        with tracing.trace_span("s", i=i):
            pass
    recs = tracing.span_records()
    assert len(recs) == 8
    # oldest evicted, newest kept, order preserved
    assert [r["args"]["i"] for r in recs] == list(range(42, 50))


# -- dump + merge -------------------------------------------------------------


def test_dump_trace_requires_dir_and_enabled(monkeypatch, tmp_path):
    monkeypatch.delenv("IGG_TELEMETRY_DIR", raising=False)
    assert igg.dump_trace() is None
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    assert igg.dump_trace(tmp_path) is None


def _synthetic_rank_file(tmp_path, rank, *, perf0, wall, spans,
                         barrier=True, uncertainty=1e-4):
    doc = {
        "schema": tracing.TRACE_SCHEMA,
        "rank": rank,
        "pid": 1000 + rank,
        "coords": [rank, 0, 0],
        "clock_sync": {
            "wall": wall,
            "perf": perf0,
            "uncertainty_s": uncertainty,
            "epoch": 1,
            "barrier": barrier,
        },
        "spans": spans,
    }
    path = tmp_path / tracing.trace_filename(rank)
    path.write_text(json.dumps(doc))
    return str(path)


def test_merge_aligns_ranks_on_the_barrier_instant(tmp_path):
    # Rank 0's perf clock reads 100.0 at the barrier; rank 1's reads 500.0
    # at the SAME instant.  A span 2s after the barrier on each rank must
    # land at the same merged timestamp despite the disjoint clock bases
    # (the in-tolerance NTP wall skew between the samples is ignored).
    f0 = _synthetic_rank_file(
        tmp_path, 0, perf0=100.0, wall=1_000_000.0,
        spans=[{"name": "igg.step", "t0": 102.0, "dur": 0.5,
                "args": {"step": 1}}],
    )
    f1 = _synthetic_rank_file(
        tmp_path, 1, perf0=500.0, wall=1_000_000.4,
        spans=[{"name": "igg.step", "t0": 502.0, "dur": 0.25}],
    )
    doc = tracing.merge_trace_files([f0, f1])
    assert tracing.validate_chrome_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_rank = {e["pid"]: e for e in spans}
    assert by_rank[0]["ts"] == pytest.approx(by_rank[1]["ts"])
    align = doc["otherData"]["clock_alignment"]
    assert align["anchor_rank"] == 0
    assert align["per_rank"]["1"]["barrier_aligned"] is True
    assert align["per_rank"]["1"]["uncertainty_s"] == pytest.approx(1e-4)
    # one process_name metadata track per rank
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["pid"] for e in meta} == {0, 1}


def test_merge_falls_back_to_wall_clock_without_barrier(tmp_path):
    f0 = _synthetic_rank_file(
        tmp_path, 0, perf0=10.0, wall=50.0,
        spans=[{"name": "a", "t0": 11.0, "dur": 0.1}],
    )
    f1 = _synthetic_rank_file(
        tmp_path, 1, perf0=70.0, wall=53.0, barrier=False,
        spans=[{"name": "b", "t0": 71.0, "dur": 0.1}],
    )
    doc = tracing.merge_trace_files([f0, f1])
    align = doc["otherData"]["clock_alignment"]
    assert align["per_rank"]["1"]["barrier_aligned"] is False
    spans = {e["pid"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    # rank 1's span sits 3s of wall time after rank 0's (1s past its sync
    # vs 1s past rank 0's sync + 3s wall offset)
    assert (spans[1]["ts"] - spans[0]["ts"]) / 1e6 == pytest.approx(3.0)


def test_merge_refuses_mismatched_barrier_anchors(tmp_path):
    """A stale per-rank dump from a PREVIOUS run in a reused telemetry dir
    must not merge into a fake 'barrier-aligned' timeline: barrier anchors
    from different barriers (wall samples far apart, or different grid
    epochs) are refused with a pointed error."""
    f0 = _synthetic_rank_file(
        tmp_path, 0, perf0=10.0, wall=1_000_000.0,
        spans=[{"name": "a", "t0": 11.0, "dur": 0.1}],
    )
    stale = _synthetic_rank_file(
        tmp_path, 1, perf0=70.0, wall=1_000_500.0,  # a run 500s earlier/later
        spans=[{"name": "b", "t0": 71.0, "dur": 0.1}],
    )
    with pytest.raises(ValueError, match="different runs/barriers"):
        tracing.merge_trace_files([f0, stale])
    # same wall instant but a different grid epoch is refused too
    doc = json.loads((tmp_path / tracing.trace_filename(1)).read_text())
    doc["clock_sync"]["wall"] = 1_000_000.1
    doc["clock_sync"]["epoch"] = 7
    (tmp_path / tracing.trace_filename(1)).write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="different runs/barriers"):
        tracing.merge_trace_files([f0, stale])


def test_merge_rejects_duplicate_ranks_and_bad_schema(tmp_path):
    f0 = _synthetic_rank_file(tmp_path, 0, perf0=0.0, wall=0.0, spans=[])
    dup = tmp_path / "dup.json"
    dup.write_text((tmp_path / tracing.trace_filename(0)).read_text())
    with pytest.raises(ValueError, match="duplicate rank"):
        tracing.merge_trace_files([f0, str(dup)])
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": 999, "rank": 1, "spans": [],
                               "clock_sync": {}}))
    with pytest.raises(ValueError, match="schema"):
        tracing.merge_trace_files([bad])


def test_validate_chrome_trace_catches_breakage():
    ok = {
        "traceEvents": [
            {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 0.0,
             "dur": 1.0},
            {"ph": "X", "name": "b", "pid": 0, "tid": 0, "ts": 2.0,
             "dur": 1.0},
        ],
        "otherData": {"clock_alignment": {}},
    }
    assert tracing.validate_chrome_trace(ok) == []
    nonmono = json.loads(json.dumps(ok))
    nonmono["traceEvents"].append(
        {"ph": "X", "name": "c", "pid": 0, "tid": 0, "ts": 1.0, "dur": 0.1}
    )
    assert any("monotonic" in p for p in tracing.validate_chrome_trace(nonmono))
    # NaN/inf timestamps must be rejected: Python's json writes them but
    # strict parsers and the trace viewers refuse the artifact (and a NaN
    # ts would silently pass the monotonicity comparison)
    for bad_ts in (float("nan"), float("inf")):
        doc = json.loads(json.dumps(ok))
        doc["traceEvents"][1]["ts"] = bad_ts  # json round-trip keeps them
        assert any(
            "non-finite" in p for p in tracing.validate_chrome_trace(doc)
        ), bad_ts
    bad_dur = json.loads(json.dumps(ok))
    bad_dur["traceEvents"][1]["dur"] = float("nan")
    assert any(
        "non-finite" in p for p in tracing.validate_chrome_trace(bad_dur)
    )
    assert tracing.validate_chrome_trace({}) == [
        "traceEvents is missing or not a list"
    ]
    no_meta = {"traceEvents": []}
    assert any(
        "clock_alignment" in p for p in tracing.validate_chrome_trace(no_meta)
    )


def test_real_run_dump_merges_into_valid_trace(monkeypatch, tmp_path):
    """Tier-1 pin of the end-to-end artifact on this process's mesh: an
    instrumented run's dumped spans merge into a valid Chrome trace whose
    ``igg.step`` spans carry their step tags in order."""
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.utils.resilience import RunGuard, \
        guarded_time_loop

    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    state, params = diffusion3d.setup(8, 8, 8, quiet=True)
    try:
        state = guarded_time_loop(
            diffusion3d.make_step(params), state, 3, guard=RunGuard(),
            sync_every_step=True, model="diffusion3d",
            bytes_per_step=tele.teff_bytes(state[:1]),
        )
        path = igg.dump_trace()
    finally:
        igg.finalize_global_grid()
    assert path == str(tmp_path / "trace.p0.json")
    doc = tracing.merge_trace_files([path])
    assert tracing.validate_chrome_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    steps = [e["args"]["step"] for e in spans if e["name"] == "igg.step"]
    assert steps == [1, 2, 3]
    # single process: the sync is exact by construction (no barrier needed)
    sync = json.load(open(path))["clock_sync"]
    assert sync["barrier"] is False
    assert sync["uncertainty_s"] == 0.0


def test_igg_trace_cli_merge_and_validate(tmp_path):
    f0 = _synthetic_rank_file(
        tmp_path, 0, perf0=1.0, wall=10.0,
        spans=[{"name": "igg.step", "t0": 2.0, "dur": 0.5}],
    )
    _synthetic_rank_file(
        tmp_path, 1, perf0=3.0, wall=10.0,
        spans=[{"name": "igg.step", "t0": 4.0, "dur": 0.5}],
    )
    out = tmp_path / "merged.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_repo, env.get("PYTHONPATH")) if p
    )
    script = os.path.join(_repo, "scripts", "igg_trace.py")
    r = subprocess.run(
        [sys.executable, script, "merge", str(tmp_path), "-o", str(out)],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    assert tracing.validate_chrome_trace(doc) == []
    r = subprocess.run(
        [sys.executable, script, "validate", str(out)],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert r.returncode == 0, r.stderr
    del f0


# -- straggler probe ----------------------------------------------------------


def test_skew_probe_skips_single_process():
    igg.init_global_grid(8, 8, 8, quiet=True)
    try:
        assert tracing.skew_probe(0.5) is None
    finally:
        igg.finalize_global_grid()
    snap = tele.snapshot()
    assert "skew.step_seconds_max_over_min" not in snap["gauges"]
    assert "skew.slowest_rank" not in snap["gauges"]


def test_skew_probe_without_grid_is_none():
    assert tracing.skew_probe(0.1) is None


# -- flight recorder ----------------------------------------------------------


def test_guard_trip_dumps_flight_bundle(monkeypatch, tmp_path):
    from implicitglobalgrid_tpu.utils.resilience import GuardError, RunGuard

    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    igg.init_global_grid(8, 8, 8, quiet=True)
    try:
        import jax.numpy as jnp

        with tracing.trace_span("pre.trip", step=0):
            pass
        Tg = igg.ones((8, 8, 8), "float64").at[2, 2, 2].set(jnp.nan)
        guard = RunGuard(guard_every=1, policy="raise", names=("T",))
        state, _ = guard.start((Tg,))
        with pytest.raises(GuardError):
            guard.on_step((Tg,), 1)
    finally:
        igg.finalize_global_grid()
    path = tmp_path / tracing.flight_filename(0)
    assert path.is_file(), list(tmp_path.iterdir())
    bundles = tracing.read_flight_bundles(path)
    assert len(bundles) == 1
    b = bundles[0]
    assert b["reason"] == "guard.trip"
    assert b["info"]["step"] == 1 and b["info"]["policy"] == "raise"
    # the three sections: active config, metrics snapshot, span ring
    assert b["config"]["env"]["IGG_TELEMETRY_DIR"] == str(tmp_path)
    assert b["config"]["grid"]["nprocs"] == 8
    assert b["metrics"]["counters"]["resilience.guard_trips"] == 1
    assert any(s["name"] == "pre.trip" for s in b["spans"])


def test_flight_recorder_disabled_or_dirless_is_none(monkeypatch, tmp_path):
    monkeypatch.delenv("IGG_TELEMETRY_DIR", raising=False)
    assert tracing.dump_flight_recorder("test") is None
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    assert tracing.dump_flight_recorder("test") is None
    assert list(tmp_path.iterdir()) == []


def test_flight_recorder_appends_complete_lines(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    p1 = tracing.dump_flight_recorder("first", detail=1)
    p2 = tracing.dump_flight_recorder("second", detail=2)
    assert p1 == p2
    bundles = tracing.read_flight_bundles(p1)
    assert [b["reason"] for b in bundles] == ["first", "second"]
    assert bundles[-1]["info"] == {"detail": 2}


# -- open-span tracking (ISSUE 11 satellite) ----------------------------------


def test_open_spans_tracks_the_executing_stack():
    assert tracing.open_spans() == []
    with tracing.trace_span("outer", step=4):
        with tracing.trace_span("inner"):
            open_ = tracing.open_spans()
            assert [s["name"] for s in open_] == ["outer", "inner"]
            assert all(s["open"] is True for s in open_)
            assert open_[0]["args"] == {"step": 4}
            assert all(s["dur"] >= 0 for s in open_)
        assert [s["name"] for s in tracing.open_spans()] == ["outer"]
    # everything closed: stack empty, no per-thread entry leaked
    assert tracing.open_spans() == []
    assert tracing._open_stacks == {}


def test_crash_inside_span_lands_in_flight_bundle(monkeypatch, tmp_path):
    """The ISSUE 11 satellite pin: the span you most want at crash time is
    the one CURRENTLY EXECUTING — the flight bundle must carry it with an
    ``open: true`` marker alongside the closed ring."""
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    with tracing.trace_span("before.crash"):
        pass
    with pytest.raises(RuntimeError, match="boom"):
        with tracing.trace_span("igg.step", model="m", step=9):
            tracing.dump_flight_recorder("test.crash", step=9)
            raise RuntimeError("boom")
    bundles = tracing.read_flight_bundles(
        tmp_path / tracing.flight_filename(0)
    )
    spans = bundles[-1]["spans"]
    closed = [s for s in spans if not s.get("open")]
    open_ = [s for s in spans if s.get("open")]
    assert [s["name"] for s in closed] == ["before.crash"]
    assert [s["name"] for s in open_] == ["igg.step"]
    assert open_[0]["args"] == {"model": "m", "step": 9}
    assert open_[0]["dur"] >= 0


def test_open_spans_disabled_mode_untouched(monkeypatch):
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    with tracing.trace_span("x"):
        assert tracing.open_spans() == []  # NOOP_SPAN touches no stack


# -- span_stats + the summarize subcommand (ISSUE 11 satellite) ---------------


def test_span_stats_aggregates_across_ranks():
    lists = [
        [
            {"name": "igg.step", "t0": 0.0, "dur": 0.001},
            {"name": "igg.step", "t0": 1.0, "dur": 0.003},
            {"name": "igg.gather", "t0": 2.0, "dur": 0.010},
            {"name": "stuck", "t0": 3.0, "dur": 99.0, "open": True},
        ],
        [{"name": "igg.step", "t0": 0.0, "dur": 0.002}],
    ]
    stats = tracing.span_stats(lists)
    assert list(stats) == ["igg.gather", "igg.step"]  # sorted
    st = stats["igg.step"]
    assert st["count"] == 3
    assert st["total_s"] == pytest.approx(0.006)
    assert st["p50_s"] == pytest.approx(0.002)
    assert st["p99_s"] == pytest.approx(0.003)
    assert st["max_s"] == pytest.approx(0.003)
    assert "stuck" not in stats  # open spans carry ages, not durations


#: the golden summarize table for `_summarize_fixture` — change the CLI
#: format deliberately and update this pin with it
_SUMMARIZE_GOLDEN = """\
# 4 span(s) across rank(s) [0, 1]
span                               count   total_ms   mean_ms    p50_ms    p99_ms    max_ms
-------------------------------------------------------------------------------------------
igg.gather                             1     10.000    10.000    10.000    10.000    10.000
igg.step                               3      6.000     2.000     2.000     3.000     3.000"""


def _summarize_fixture(tmp_path):
    _synthetic_rank_file(
        tmp_path, 0, perf0=0.0, wall=100.0,
        spans=[
            {"name": "igg.step", "t0": 0.0, "dur": 0.001},
            {"name": "igg.step", "t0": 1.0, "dur": 0.003},
            {"name": "igg.gather", "t0": 2.0, "dur": 0.010},
        ],
    )
    _synthetic_rank_file(
        tmp_path, 1, perf0=0.0, wall=100.0,
        spans=[{"name": "igg.step", "t0": 0.0, "dur": 0.002}],
    )


def test_igg_trace_cli_summarize_golden(tmp_path):
    _summarize_fixture(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (_repo, env.get("PYTHONPATH")) if p
    )
    script = os.path.join(_repo, "scripts", "igg_trace.py")
    r = subprocess.run(
        [sys.executable, script, "summarize", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert r.returncode == 0, r.stderr
    assert r.stdout.rstrip("\n") == _SUMMARIZE_GOLDEN
    # --json mode: machine-readable, equals the library aggregation
    r = subprocess.run(
        [sys.executable, script, "summarize", "--json", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert r.returncode == 0, r.stderr
    stats = json.loads(r.stdout)
    assert stats["igg.step"]["count"] == 3
    assert stats["igg.gather"]["total_s"] == pytest.approx(0.010)


# -- cost-model reconciliation ------------------------------------------------


def test_reconcile_report_from_committed_baseline():
    from implicitglobalgrid_tpu.analysis import reconcile

    report = reconcile.reconcile_report(source="baseline")
    assert set(report["models"]) == {"diffusion", "acoustic", "porous"}
    for model, rec in report["models"].items():
        frac = rec["achieved_fraction"]
        assert frac is not None, (model, rec)
        assert 0.0 < frac <= 1.0, (model, frac)
        assert rec["stream_bytes"] > 0
        assert rec["iterations"] >= 1
        assert rec["modeled_bytes_per_iteration"] >= rec["stream_bytes"]
    # porous counts its inner PT iterations (nt * npt)
    assert report["models"]["porous"]["iterations"] > \
        report["models"]["diffusion"]["iterations"] // 4


def test_reconcile_join_measured_math():
    from implicitglobalgrid_tpu.analysis.reconcile import join_measured

    report = {
        "source": "baseline",
        "note": "n",
        "models": {
            "diffusion": {"achieved_fraction": 0.25},
            "acoustic": {"achieved_fraction": None},
        },
    }
    joined = join_measured(report, {"diffusion": 100.0, "acoustic": 50.0})
    d = joined["models"]["diffusion"]
    assert d["measured_teff_gbs"] == 100.0
    assert d["modeled_actual_gbs"] == pytest.approx(400.0)
    a = joined["models"]["acoustic"]
    assert a["measured_teff_gbs"] == 50.0
    assert "modeled_actual_gbs" not in a


def test_perf_gate_reports_achieved_fraction():
    from implicitglobalgrid_tpu.analysis.perf import (
        gate_metrics,
        gate_summary,
        reported_metrics,
    )

    record = {
        "value": 100.0,
        "extras": {
            "diffusion_xla": {"teff": 100.0},
            "efficiency": {
                "models": {
                    "diffusion": {"achieved_fraction": 0.33,
                                  "measured_teff_gbs": 100.0},
                },
            },
        },
    }
    rep = reported_metrics(record)
    assert rep == {
        "efficiency.models.diffusion.achieved_fraction": 0.33
    }
    # reported keys are NOT gated: they never appear in gate_metrics
    assert not any("achieved_fraction" in k for k in gate_metrics(record))
    verdict = gate_summary(record, _repo)
    assert verdict["reported"] == rep


# -- spans on the profiler's clock --------------------------------------------


def _xplane_events(logdir):
    """``[(name, {stat: str(value)})]`` of every event in the one
    ``.xplane.pb`` a profiler session wrote under ``logdir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                        recursive=True)
    return [
        (e.name, {k: str(v) for k, v in e.stats})
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
    ]


def test_trace_span_lands_in_profiler_capture(tmp_path):
    """A span inside a live profiler session is an annotation of the same
    name in the capture's host plane, its scalar tags as arguments; a
    `spanned_calls` call's annotation carries its call count (a plain
    annotation, no step marker).  The ring records both as before."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.trace_span("igg.test_span", step=3, kind="probe",
                                trace_ids=["a", "b"]):
            pass
        calls = tracing.spanned_calls(lambda x: x + 1, "igg.test_calls",
                                      model="m")
        assert calls(1) == 2 and calls(2) == 3
    finally:
        jax.profiler.stop_trace()
    evs = _xplane_events(tmp_path)
    assert [s for n, s in evs if n == "igg.test_span"] == [
        {"step": "3", "kind": "probe"}  # a list tag is no annotation argument
    ]
    steps = [s for n, s in evs if n == "igg.test_calls"]
    assert steps == [{"call": "0", "model": "m"}, {"call": "1", "model": "m"}]
    recs = tracing.span_records()
    assert [r["name"] for r in recs] == [
        "igg.test_span", "igg.test_calls", "igg.test_calls"
    ]
    assert [r["args"]["call"] for r in recs[1:]] == [0, 1]


def test_disabled_span_writes_no_annotation(monkeypatch, tmp_path):
    """``IGG_TELEMETRY=0``: the shared no-op span, no ring record and no
    annotation in a live capture; the wrapper still calls through."""
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    jax.profiler.start_trace(str(tmp_path))
    try:
        span = tracing.trace_span("igg.off_span", step=1)
        assert span is tracing.NOOP_SPAN
        with span:
            pass
        assert tracing.spanned_calls(lambda x: 2 * x, "igg.off_calls")(4) == 8
    finally:
        jax.profiler.stop_trace()
    assert not [n for n, _ in _xplane_events(tmp_path) if n.startswith("igg.off")]
    assert tracing.span_records() == []


def _multi_step_case(case):
    """``(callable, state)`` of one return path of a model's
    ``make_multi_step``, at a tiny size, results not donated."""
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.models import (
        acoustic3d,
        diffusion3d,
        porous_convection3d,
    )
    from implicitglobalgrid_tpu.models._batched import batched_setup

    one = jax.devices()[:1]
    if case == "diffusion3d_stencil":
        state, params = diffusion3d.setup(8, 8, 8, quiet=True)
        return diffusion3d.make_multi_step(params, 2, donate=False), state
    if case == "diffusion3d_jit_kernel":
        # one block, no halo activity: the fused kernel under plain jax.jit
        state, params = diffusion3d.setup(16, 16, 128, dtype=jnp.float32,
                                          quiet=True, devices=one)
        return diffusion3d.make_multi_step(params, 2, donate=False,
                                           fused_k=2), state
    if case == "diffusion3d_batched":
        state, params = batched_setup(diffusion3d, 8, 8, 8, batch=2)
        return diffusion3d.make_multi_step(params, 2, donate=False,
                                           batch=True), state
    if case == "acoustic3d":
        state, params = acoustic3d.setup(8, 8, 8, quiet=True)
        return acoustic3d.make_multi_step(params, 2, donate=False), state
    state, params = porous_convection3d.setup(8, 8, 8, npt=2, quiet=True)
    return porous_convection3d.make_multi_step(params, 2, donate=False), state


@pytest.mark.parametrize("case", [
    "diffusion3d_stencil", "diffusion3d_jit_kernel", "diffusion3d_batched",
    "acoustic3d", "porous_convection3d",
])
def test_multi_step_records_one_span_per_call(case):
    """Every return path of every model's ``make_multi_step``: one
    ``igg.multi_step`` ring span per call numbered 0..n-1, ``.lower()``
    still there, results bit-identical to the bare callable's."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    with force_tpu_interpret_mode():
        step, state = _multi_step_case(case)
        assert step.lower(*state).compile() is not None
        outs = [step(*state) for _ in range(3)]
        bare = step._inner(*state)
    spans = [r for r in tracing.span_records() if r["name"] == "igg.multi_step"]
    model = case.split("_", 1)[0] if case.startswith("diffusion") else case
    assert [s["args"] for s in spans] == [
        {"call": i, "model": model, "nsteps": 2} for i in range(3)
    ]
    for out in outs:
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(bare)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
