"""Device-timeline profiling plane tests (ISSUE 15; docs/observability.md
"Device timeline").

Covers the ``IGG_PROFILE`` window grammar, the blessed op-name
classification vocabulary (`utils.hlo_analysis.classify_op_name`), the
attribution parser golden-pinned on a committed fixture trace
(``tests/data/profile_fixture.trace.json.gz``: scope table AND measured
overlap fraction), the malformed-trace structured-finding contract, the
``scripts/igg_prof.py`` CLI, the cross-run diff, and — in ONE real
XLA:CPU capture shared by a module fixture — the end-to-end windowed
capture through `guarded_time_loop` (meta file, ``profile.start/stop``
events, gauges) plus ``igg_trace.py merge --device`` producing one valid
Chrome trace with host AND device tracks.  The 2-process gloo leg lives
in ``test_distributed.py::test_two_process_device_merged_trace``.
"""

import gzip
import json
import os
import sys

import pytest

import jax

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils import hlo_analysis
from implicitglobalgrid_tpu.utils import profiling
from implicitglobalgrid_tpu.utils import telemetry as tele
from implicitglobalgrid_tpu.utils import tracing

_here = os.path.dirname(os.path.abspath(__file__))
_repo = os.path.dirname(_here)
FIXTURE = os.path.join(_here, "data", "profile_fixture.trace.json.gz")
#: a capture recorded on one TPU v5e (three calls of the periodic-z
#: diffusion cadence at 128^3, k=4, 8 steps a call, each call inside an
#: ``igg.step`` span), trimmed to its device op lines and program spans, and
#: the span ring the same process dumped (`tracing.dump_trace`)
V5E = os.path.join(_here, "data", "v5e_capture.trace.json.gz")
V5E_SPANS = os.path.join(_here, "data", "v5e_capture.p0.json")
V5E_T_START_PERF = 45.112375355  # perf_counter right after start_trace

sys.path.insert(0, os.path.join(_repo, "scripts"))
import igg_prof  # noqa: E402  (scripts/ CLI under test)
import igg_trace  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_state():
    profiling.reset()
    yield
    profiling.reset()


# -- window grammar -----------------------------------------------------------


def test_parse_profile_window():
    assert profiling.parse_profile_window("steps:20-40") == (20, 40)
    assert profiling.parse_profile_window("steps:5") == (1, 5)
    assert profiling.parse_profile_window("steps:3-3") == (3, 3)


@pytest.mark.parametrize(
    "bad", ["", "steps", "steps:", "steps:0-4", "steps:5-2", "steps:a-b",
            "window:2-3", "steps:2-3-4"]
)
def test_parse_profile_window_rejects(bad):
    with pytest.raises(ValueError, match="IGG_PROFILE"):
        profiling.parse_profile_window(bad)


def test_maybe_arm_invalid_spec_raises(monkeypatch):
    monkeypatch.setenv("IGG_PROFILE", "steps:banana")
    with pytest.raises(ValueError, match="IGG_PROFILE"):
        profiling.maybe_arm(0)


def test_maybe_arm_disabled_paths(monkeypatch):
    monkeypatch.delenv("IGG_PROFILE", raising=False)
    assert profiling.maybe_arm(0) is None
    monkeypatch.setenv("IGG_PROFILE", "steps:2-3")
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    assert profiling.maybe_arm(0) is None


# -- op-name vocabulary -------------------------------------------------------


def test_classify_op_name_vocabulary():
    cls = hlo_analysis.classify_op_name
    assert cls("collective-permute.14") == "collective"
    assert cls("collective-permute-start.3") == "collective"
    assert cls("all-reduce.1") == "collective"
    assert cls("pad_add_fusion") == "kernel"
    assert cls("select_dynamic-update-slice_fusion.1") == "kernel"
    assert cls("custom-call.7") == "kernel"
    assert cls("copy.17") == "glue"
    assert cls("slice.96") == "glue"
    assert cls("while.19") == "glue"
    assert cls("partition-id.7") == "glue"
    # a fused collective still occupies the fabric: collective wins
    assert cls("fusion_collective-permute.2") == "collective"


# -- fixture attribution (golden) ---------------------------------------------


def test_v5e_capture_attribution_golden(capsys):
    """On the chip's capture, device time lands in the program's names: the
    kernel variant (its instruction name and ``op_name``), the z-patch
    scopes (``op_name`` only), and XLA's own copies, which carry no scope,
    as glue.  Module events and async slices in flight are no ops."""
    rec = profiling.attribute_trace(V5E)
    assert rec["n_device_ops"] == 99
    assert rec["scope_seconds"] == pytest.approx({
        "glue": 0.000138862,
        "igg_diffusion_k4_zpatch": 0.000739652,
        "igg_zpatch_apply": 0.000448734,
        "igg_zpatch_from_export": 2.76e-07,
        "igg_zpatch_identity": 7.9265e-05,
        "kernels": 2e-09,
    })
    assert rec["overlap"]["fraction"] is None  # one chip: no collectives
    assert igg_prof.main(["attribute", V5E, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["scope_seconds"] == (
        rec["scope_seconds"])


def _v5e_merged(trace_path=V5E):
    doc = tracing.merge_trace_files([V5E_SPANS])
    sync = json.load(open(V5E_SPANS))["clock_sync"]
    meta = {"schema": profiling.PROFILE_SCHEMA, "rank": 0,
            "t_start_perf": V5E_T_START_PERF, "wall_start": sync["wall"],
            "trace_path": trace_path, "window": [1, 3]}
    return profiling.attach_device_tracks(doc, [meta])


def test_v5e_capture_aligns_on_a_program_span():
    """The device track is placed by the ``igg.step`` span that is both in
    the ring and in the capture's host plane: each device op keeps, to the
    microsecond, its distance from that span as the capture recorded it."""
    doc = _v5e_merged()
    assert tracing.validate_chrome_trace(doc) == []
    per = doc["otherData"]["device_alignment"]["per_rank"]["0"]
    assert per["anchor"] == "span" and per["anchor_span"] == "igg.step"
    assert per["n_ops"] == 99
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    step1 = next(e for e in xs if e["name"] == "igg.step"
                 and e["args"] == {"model": "probe", "step": 1})
    first = min(e["ts"] for e in xs if e["tid"] >= profiling.DEVICE_TID_BASE)
    cap = json.load(gzip.open(V5E, "rt"))["traceEvents"]
    cap_step1 = next(e["ts"] for e in cap if e.get("name") == "igg.step")
    cap_first = min(e["ts"] for e in cap if e.get("ph") == "X" and
                    "long_name" in (e.get("args") or {}))
    assert first - step1["ts"] == pytest.approx(cap_first - cap_step1, abs=1e-3)


def test_v5e_capture_without_program_spans_anchors_at_start(tmp_path):
    """A capture holding no program span falls back to the start_trace
    anchor, and says so."""
    cap = json.load(gzip.open(V5E, "rt"))
    cap["traceEvents"] = [e for e in cap["traceEvents"]
                          if not str(e.get("name", "")).startswith("igg.")]
    path = str(tmp_path / "nospans.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump(cap, f)
    doc = _v5e_merged(path)
    per = doc["otherData"]["device_alignment"]["per_rank"]["0"]
    assert per["anchor"] == "start_trace" and per["n_ops"] == 99
    assert tracing.validate_chrome_trace(doc) == []


def test_fixture_attribution_golden():
    rec = profiling.attribute_trace(FIXTURE)
    assert rec["n_device_ops"] == 7
    assert rec["device_seconds"] == pytest.approx(0.00117)
    assert rec["scope_seconds"] == pytest.approx(
        {
            "glue": 9e-05,
            "igg_halo_exchange": 1e-04,
            "igg_interior_pass": 5e-04,
            "igg_ring_pass": 1e-04,
            "igg_slab_exchange_begin": 3e-04,
            "kernels": 8e-05,
        }
    )
    assert rec["unattributed_seconds"] == pytest.approx(9e-05)
    ov = rec["overlap"]
    # comm = slab-begin [200,500] + halo [1000,1100]; kernels = ring
    # [0,100] + interior [150,650] + custom-call [820,900]; only the
    # slab-begin hop hides under the interior -> 300/400.
    assert ov["comm_seconds"] == pytest.approx(4e-04)
    assert ov["compute_seconds"] == pytest.approx(6.8e-04)
    assert ov["overlapped_seconds"] == pytest.approx(3e-04)
    assert ov["fraction"] == pytest.approx(0.75)


def test_fixture_attribution_table_golden():
    rec = profiling.attribute_trace(FIXTURE)
    table = profiling.render_attribution_table(rec)
    assert table == (
        "scope                           device_ms   share\n"
        "-------------------------------------------------\n"
        "glue                                0.090   7.7%\n"
        "igg_halo_exchange                   0.100   8.5%\n"
        "igg_interior_pass                   0.500  42.7%\n"
        "igg_ring_pass                       0.100   8.5%\n"
        "igg_slab_exchange_begin             0.300  25.6%\n"
        "kernels                             0.080   6.8%\n"
        "-------------------------------------------------\n"
        "total                               1.170         (7 device op(s))\n"
        "overlap: comm 0.400 ms, compute 0.680 ms, overlapped 0.300 ms "
        "-> fraction 0.7500"
    )


def test_attribution_zero_collectives_has_no_fake_fraction():
    # a capture without collectives must answer None, never 0.0
    doc = {
        "traceEvents": [
            {"ph": "X", "pid": 0, "tid": 0, "name": "fusion.1", "ts": 0.0,
             "dur": 10.0, "args": {"hlo_op": "fusion.1"}},
        ]
    }
    rec = profiling.attribute_trace(doc)
    assert rec["overlap"]["fraction"] is None
    assert rec["scope_seconds"] == {"kernels": 1e-05}


def test_host_only_trace_is_an_answer_not_an_error():
    doc = {"traceEvents": [
        {"ph": "X", "pid": 0, "tid": 0, "name": "python", "ts": 0.0,
         "dur": 5.0},
    ]}
    rec = profiling.attribute_trace(doc)
    assert rec["n_device_ops"] == 0
    assert rec["overlap"]["fraction"] is None


# -- malformed input: structured finding, not a traceback ---------------------


def test_malformed_trace_raises_valueerror(tmp_path):
    bad = tmp_path / "broken.trace.json.gz"
    bad.write_bytes(gzip.compress(b"{not json"))
    with pytest.raises(ValueError, match="malformed trace JSON"):
        profiling.load_trace(str(bad))
    truncated = tmp_path / "torn.trace.json.gz"
    whole = gzip.compress(b'{"traceEvents": []}')
    truncated.write_bytes(whole[: len(whole) // 2])
    with pytest.raises(ValueError):
        profiling.load_trace(str(truncated))
    notatrace = tmp_path / "other.trace.json.gz"
    notatrace.write_bytes(gzip.compress(b'{"foo": 1}'))
    with pytest.raises(ValueError, match="no traceEvents"):
        profiling.load_trace(str(notatrace))


def test_igg_prof_cli_malformed_trace_is_structured_finding(tmp_path, capsys):
    bad = tmp_path / "broken.trace.json.gz"
    bad.write_bytes(gzip.compress(b"{not json"))
    rc = igg_prof.main(["attribute", str(bad)])
    out = capsys.readouterr().out.strip()
    finding = json.loads(out)  # one parseable JSON finding, no traceback
    assert rc == 1
    assert finding["finding"] == "profile.parse_failed"
    assert "malformed" in finding["error"]


def test_igg_prof_cli_attribute_and_diff(capsys):
    assert igg_prof.main(["attribute", FIXTURE, "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["overlap"]["fraction"] == pytest.approx(0.75)
    assert igg_prof.main(["diff", FIXTURE, FIXTURE]) == 0
    table = capsys.readouterr().out
    assert "overlap fraction: A 0.7500 -> B 0.7500" in table
    assert "worst regression" not in table  # identical runs drift nowhere


def test_attribution_delta_names_the_scope_that_ate_it():
    a = {"scope_seconds": {"igg_interior_pass": 0.5, "glue": 0.1},
         "device_seconds": 0.6, "overlap": {"fraction": 0.8}}
    b = {"scope_seconds": {"igg_interior_pass": 0.5, "glue": 0.4},
         "device_seconds": 0.9, "overlap": {"fraction": 0.5}}
    delta = profiling.attribution_delta(a, b)
    assert delta["worst"] == "glue"
    assert delta["worst_delta_s"] == pytest.approx(0.3)
    assert delta["scopes"]["igg_interior_pass"]["delta_s"] == 0.0
    assert delta["overlap_fraction"] == {"a": 0.8, "b": 0.5}
    txt = profiling.render_delta_table(delta)
    assert "worst regression: glue" in txt


# -- capture degradations -----------------------------------------------------


def test_capture_without_directory_degrades_to_structured_failure(monkeypatch):
    monkeypatch.delenv("IGG_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("IGG_PROFILE_DIR", raising=False)
    monkeypatch.setenv("IGG_PROFILE", "steps:1-2")
    tele.reset()
    cap = profiling.maybe_arm(0)
    assert cap is not None and cap.done  # failed at start, disarmed
    snap = tele.snapshot()
    assert snap["counters"].get("profile.capture_failures") == 1
    # the pipeline keeps running: further steps are no-ops, not errors
    cap.on_step(1)
    cap.on_step(2)
    cap.close("test")


def test_window_past_run_end_never_starts(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("IGG_PROFILE", "steps:50-60")
    cap = profiling.maybe_arm(0)
    for it in range(1, 5):
        cap.on_step(it)
    cap.close("run_complete")
    assert not cap.started
    assert profiling.find_capture_metas(str(tmp_path)) == []


# -- the real XLA:CPU capture (one profiler session, shared) ------------------


@pytest.fixture(scope="module")
def captured_run(tmp_path_factory):
    """ONE windowed end-to-end capture through `guarded_time_loop` on the
    8-device mesh (profiler sessions cost seconds — every end-to-end
    assertion below reads this run's artifacts)."""
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.utils.resilience import (
        RunGuard,
        guarded_time_loop,
    )
    from implicitglobalgrid_tpu.utils.telemetry import teff_bytes

    tdir = str(tmp_path_factory.mktemp("profile_run"))
    saved = {
        k: os.environ.get(k)
        for k in ("IGG_TELEMETRY_DIR", "IGG_PROFILE", "IGG_PROFILE_DIR")
    }
    os.environ["IGG_TELEMETRY_DIR"] = tdir
    os.environ["IGG_PROFILE"] = "steps:2-3"
    os.environ.pop("IGG_PROFILE_DIR", None)
    tele.reset()
    tracing.reset()
    profiling.reset()
    try:
        igg.init_global_grid(8, 8, 8, quiet=True)
        state, params = diffusion3d.setup(8, 8, 8, init_grid=False)
        guarded_time_loop(
            diffusion3d.make_step(params, donate=False), state, 4,
            guard=RunGuard(), sync_every_step=True, model="diffusion3d",
            bytes_per_step=teff_bytes(state[:1]),
        )
        trace_path = igg.dump_trace(tdir)
        snap = tele.snapshot()
        events = tele.read_events(os.path.join(tdir, "events.jsonl"))
    finally:
        igg.finalize_global_grid()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "tdir": tdir,
        "host_trace": trace_path,
        "snapshot": snap,
        "events": events,
    }


def test_windowed_capture_end_to_end(captured_run):
    metas = profiling.find_capture_metas(captured_run["tdir"])
    assert len(metas) == 1 and metas[0].endswith("profile.p0.json")
    meta = json.load(open(metas[0]))
    assert meta["schema"] == profiling.PROFILE_SCHEMA
    assert meta["window"] == [2, 3]
    assert meta["started_at_step"] == 2 and meta["stopped_at_step"] == 3
    assert os.path.isfile(meta["trace_path"])
    assert meta["trace_path"].endswith(".trace.json.gz")
    assert meta["t_stop_perf"] > meta["t_start_perf"]
    attribution = meta["attribution"]
    assert "error" not in attribution
    assert attribution["n_device_ops"] > 0
    # the 8-device mesh's step has real collective-permutes: both comm and
    # kernel time exist, so the overlap fraction is a measured number
    assert attribution["scope_seconds"].get("collectives", 0) > 0
    assert attribution["scope_seconds"].get("kernels", 0) > 0
    assert attribution["overlap"]["fraction"] is not None
    assert 0.0 <= attribution["overlap"]["fraction"] <= 1.0


def test_capture_events_and_gauges(captured_run):
    types = [e["type"] for e in captured_run["events"]]
    assert "profile.start" in types and "profile.stop" in types
    start = next(
        e for e in captured_run["events"] if e["type"] == "profile.start"
    )
    stop = next(
        e for e in captured_run["events"] if e["type"] == "profile.stop"
    )
    assert start["window"] == [2, 3] and start["step"] == 2
    assert stop["step"] == 3 and stop["reason"] == "window"
    assert stop["trace"].endswith(".trace.json.gz")
    gauges = captured_run["snapshot"]["gauges"]
    assert gauges.get("profile.scope_seconds.collectives", 0) > 0
    assert "profile.overlap_fraction" in gauges
    assert captured_run["snapshot"]["counters"].get("profile.captures") == 1


def test_merge_device_produces_one_valid_trace(captured_run, tmp_path):
    """Acceptance: windowed capture -> parse -> attribution ->
    ``igg_trace.py merge --device`` = ONE valid Chrome trace with host +
    device tracks on the same rank pid."""
    out = str(tmp_path / "merged.json")
    rc = igg_trace.main(
        ["merge", captured_run["tdir"], "--device", "-o", out]
    )
    assert rc == 0
    doc = json.load(open(out))
    assert tracing.validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    host = [e for e in xs if not (e.get("args") or {}).get("hlo_op")]
    device = [e for e in xs if (e.get("args") or {}).get("hlo_op")]
    assert host and device
    assert {e["pid"] for e in device} == {0}  # the rank's own track
    assert all(e["tid"] >= profiling.DEVICE_TID_BASE for e in device)
    assert "igg.step" in {e["name"] for e in host}
    # every device event carries its attribution bucket for the viewer
    assert all((e["args"].get("igg_scope") or "") for e in device)
    align = doc["otherData"]["device_alignment"]
    assert "per_rank" in align and align["per_rank"]["0"]["n_ops"] > 0
    assert "start latency" in align["note"]  # the honesty bound, recorded


def test_merge_device_with_explicit_trace_files(captured_run, tmp_path):
    """--device must also work in the explicit-file form the stale-refusal
    remedy prescribes ('merge the current run's files explicitly'): metas
    are discovered next to the named trace files."""
    out = str(tmp_path / "merged_explicit.json")
    trace_file = os.path.join(captured_run["tdir"], "trace.p0.json")
    assert igg_trace.main(["merge", trace_file, "--device", "-o", out]) == 0
    doc = json.load(open(out))
    assert tracing.validate_chrome_trace(doc) == []
    assert any(
        (e.get("args") or {}).get("hlo_op")
        for e in doc["traceEvents"]
        if e.get("ph") == "X"
    )


def test_merge_device_without_metas_is_a_clear_error(tmp_path, capsys):
    # host trace but no capture meta: merge --device must say what to do
    tracing.reset()
    with tracing.trace_span("igg.step", step=1):
        pass
    path = tracing.dump_trace(str(tmp_path))
    assert path is not None
    rc = igg_trace.main(["merge", str(tmp_path), "--device", "-o", "-"])
    tracing.reset()
    assert rc == 2
    assert "profile.p*.json" in capsys.readouterr().err


def test_igg_prof_attribute_run_dir(captured_run, capsys):
    assert igg_prof.main(
        ["attribute", captured_run["tdir"], "--json"]
    ) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["n_device_ops"] > 0
    assert rec["per_rank"]["0"]["n_device_ops"] == rec["n_device_ops"]


def test_attach_device_tracks_degrades_on_missing_host_track(captured_run):
    """A capture meta whose rank never dumped a host trace (crashed before
    dump_trace — the post-mortem case) degrades to a per-rank note; the
    surviving ranks' device-merged timeline still builds and validates."""
    doc = tracing.merge_trace_files([captured_run["host_trace"]])
    meta = json.load(
        open(profiling.find_capture_metas(captured_run["tdir"])[0])
    )
    orphan = dict(meta, rank=7)  # no such host track in the merged doc
    profiling.attach_device_tracks(doc, [meta, orphan])
    assert tracing.validate_chrome_trace(doc) == []
    per = doc["otherData"]["device_alignment"]["per_rank"]
    assert per["0"]["n_ops"] > 0  # the surviving rank attached fine
    assert per["7"]["n_ops"] == 0
    assert "no host track" in per["7"]["note"]


def test_maybe_arm_fires_once_per_process(monkeypatch, tmp_path):
    """The documented contract is 'the NEXT instrumented run': a process
    running several instrumented loops must not pay a profiler session
    per run / overwrite the first capture's artifacts (`reset()`
    re-arms)."""
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("IGG_PROFILE", "steps:50-60")  # never starts: cheap
    cap = profiling.maybe_arm(0)
    assert cap is not None
    assert profiling.maybe_arm(0) is None  # second run: already consumed
    profiling.reset()
    assert profiling.maybe_arm(0) is not None


def test_attach_device_tracks_refuses_stale_meta(captured_run):
    """The device twin of merge_trace_files' same-barrier refusal: a
    capture meta left by a PREVIOUS run (wall clock before this run's
    sync anchor) must be refused, not silently joined with a dead
    process's perf anchor."""
    doc = tracing.merge_trace_files([captured_run["host_trace"]])
    meta = json.load(
        open(profiling.find_capture_metas(captured_run["tdir"])[0])
    )
    meta["wall_start"] -= 3600.0  # a capture from an hour-older run
    with pytest.raises(ValueError, match="stale"):
        profiling.attach_device_tracks(doc, [meta])


def test_attribution_survives_archived_run_dir(captured_run, tmp_path, capsys):
    """Cross-round diffing works on a COPIED run dir: the meta's absolute
    trace_path/logdir are dead there, so resolution must fall back to the
    meta's own directory (`resolve_trace_path`)."""
    import shutil

    archived = tmp_path / "roundA"
    archived.mkdir()
    src = captured_run["tdir"]
    shutil.copy(
        profiling.find_capture_metas(src)[0],
        archived / "profile.p0.json",
    )
    shutil.copytree(os.path.join(src, "profile.p0"), archived / "profile.p0")
    # poison the recorded absolute locations: only the archive remains
    meta_path = str(archived / "profile.p0.json")
    meta = json.load(open(meta_path))
    meta["trace_path"] = "/nonexistent/run/trace.json.gz"
    meta["logdir"] = "/nonexistent/run"
    json.dump(meta, open(meta_path, "w"))
    assert igg_prof.main(["attribute", str(archived), "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["n_device_ops"] > 0


def test_meta_lands_in_discoverable_base_dir_without_telemetry_dir(
    monkeypatch, tmp_path
):
    """IGG_PROFILE_DIR set, IGG_TELEMETRY_DIR unset: the meta must land in
    the BASE dir (where find_capture_metas globs), not nested inside the
    per-rank profile.p0/ capture dir."""
    caps = str(tmp_path / "caps")
    monkeypatch.setenv("IGG_PROFILE_DIR", caps)
    monkeypatch.delenv("IGG_TELEMETRY_DIR", raising=False)
    monkeypatch.setenv("IGG_PROFILE", "steps:1-1")
    tele.reset()
    cap = profiling.maybe_arm(0)
    assert cap is not None and cap.started
    import jax.numpy as jnp

    jax.jit(lambda a: a + 1)(jnp.ones((8,))).block_until_ready()
    cap.on_step(1)  # window [1,1] closes here
    assert cap.done
    metas = profiling.find_capture_metas(caps)
    assert len(metas) == 1 and metas[0].endswith("profile.p0.json")
    meta = json.load(open(metas[0]))
    assert meta["stopped_at_step"] == 1 and meta["reason"] == "window"


def test_close_records_last_completed_step(monkeypatch, tmp_path):
    """A scope-exit/run-complete stop records the LAST completed step, not
    the start step — the meta must not claim a 4-step capture covered
    one."""
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("IGG_PROFILE", "steps:2-10")
    tele.reset()
    cap = profiling.maybe_arm(0)
    for it in range(1, 6):  # run ends at step 5, window still open
        cap.on_step(it)
    assert cap.started
    cap.close("run_complete")
    meta = json.load(open(os.path.join(str(tmp_path), "profile.p0.json")))
    assert meta["started_at_step"] == 2
    assert meta["stopped_at_step"] == 5
    assert meta["reason"] == "run_complete"


# -- flight recorder + alias --------------------------------------------------


def test_flight_recorder_bundles_open_capture(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("IGG_PROFILE", "steps:1-100")
    tele.reset()
    cap = profiling.maybe_arm(0)  # window starts at step 1 immediately
    try:
        assert cap is not None and cap.started
        assert profiling.active_capture() == cap.info()
        path = tracing.dump_flight_recorder("test_crash", step=1)
        bundle = tracing.read_flight_bundles(path)[-1]
        assert bundle["profile"]["window"] == [1, 100]
        assert bundle["profile"]["started"] is True
        assert bundle["profile"]["logdir"].endswith("profile.p0")
    finally:
        profiling.close_open_capture("scope_exit")
    # the scope-exit stop landed the capture: meta written, reason recorded
    meta = json.load(open(os.path.join(str(tmp_path), "profile.p0.json")))
    assert meta["reason"] == "scope_exit"
    assert profiling.active_capture() is None


def test_profile_trace_alias_emits_parseable_capture(tmp_path):
    """Satellite: `igg.profile_trace` is the thin alias of the ONE capture
    implementation — its output must parse through the attribution
    pipeline (create_perfetto_trace now defaults on)."""
    import jax.numpy as jnp

    logdir = str(tmp_path / "alias")
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    with igg.profile_trace(logdir):
        f(x).block_until_ready()
    rec = profiling.attribute_capture(logdir)
    assert rec["n_device_ops"] > 0
    assert rec["device_seconds"] > 0
