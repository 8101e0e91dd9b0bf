"""Perf-regression gate: `analysis.perf` + ``scripts/check_perf.py`` +
``scripts/refresh_cost_baseline.py`` (docs/performance.md, ROADMAP item 5).

The acceptance bar of ISSUE 7: the gate exits nonzero on a doctored BENCH
record outside tolerance and 0 on a recorded trajectory — turning
the hand-run bench evidence into the same kind of invariant the collective
budget already is.  The refresh helper's audit contract (a ``--justify``
note per changed metric, mirroring ``analysis/baseline.json``) is pinned
here too; the committed cost baseline itself is pinned by
``tests/test_lint_suite.py`` (the full-suite ``hlo-cost`` comparison).
"""

import copy
import importlib.util
import json
import os

import pytest

from implicitglobalgrid_tpu.analysis import perf

_here = os.path.dirname(os.path.abspath(__file__))
_repo = os.path.dirname(_here)
# Rounds 1-5 of an earlier runtime, kept as gate fixtures (their parsed
# records only): r02-r04 parse, r01/r05 are unparseable, and r04 retired a
# porous config.  The repo itself commits no bench records.
_traj = os.path.join(_here, "data", "bench_trajectory")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_repo, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_perf = _load_script("check_perf")
refresh_cost_baseline = _load_script("refresh_cost_baseline")


# -- record parsing -----------------------------------------------------------


def test_trajectory_loads_and_skips_unrecoverable_rounds():
    """The recorded trajectory: r02-r04 parse (driver wrapper with
    ``parsed``), r01/r05 are truncated beyond recovery and must be SKIPPED
    with a report, never silently used."""
    records, skipped = perf.load_bench_records(_traj)
    rounds = [r for r, _ in records]
    assert rounds == sorted(rounds)
    assert len(records) >= 2, "the gate needs at least two parseable rounds"
    for _, rec in records:
        assert "extras" in rec
    assert all(s.startswith("BENCH_r") for s in skipped)


def test_parse_bench_file_accepts_wrapper_raw_and_rejects_garbage(tmp_path):
    raw = {"metric": "m", "value": 1.0, "extras": {"a": {"teff": 2.0}}}
    p = tmp_path / "raw.json"
    p.write_text(json.dumps(raw))
    assert perf.parse_bench_file(str(p))["value"] == 1.0

    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({"n": 1, "parsed": raw, "tail": ""}))
    assert perf.parse_bench_file(str(wrapped))["value"] == 1.0

    tail = tmp_path / "tail.json"
    tail.write_text(json.dumps({"n": 1, "tail": "log noise " + json.dumps(raw)}))
    assert perf.parse_bench_file(str(tail))["value"] == 1.0

    # trailing log text AFTER the record (a normal capture shape) must not
    # make a fully-present record "unparseable"
    trailing = tmp_path / "trailing.json"
    trailing.write_text(json.dumps(
        {"n": 1, "tail": "noise " + json.dumps(raw) + " exited 0\n"}
    ))
    assert perf.parse_bench_file(str(trailing))["value"] == 1.0

    trunc = tmp_path / "trunc.json"
    trunc.write_text(json.dumps({"n": 1, "tail": 'noise {"metric": "m", "va'}))
    assert perf.parse_bench_file(str(trunc)) is None

    # a file killed mid-write is not even valid top-level JSON: still a
    # skip-and-report, never a crash
    killed = tmp_path / "killed.json"
    killed.write_text('{"n": 6, "tail": "trunc')
    assert perf.parse_bench_file(str(killed)) is None


def test_registry_pass_flags_unparseable_rounds(tmp_path):
    """A committed round the gate cannot read is a blind spot — it must
    surface as an ERROR finding (baselined for the historical r01/r05),
    not vanish into a skipped list nobody reads: otherwise a regressed
    record merges wearing truncation as camouflage."""

    class _Ctx:
        repo_root = str(tmp_path)

    records, _ = perf.load_bench_records(_traj)
    for i, (_, rec) in enumerate(records[-2:], start=2):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(rec))
    (tmp_path / "BENCH_r06.json").write_text('{"n": 6, "tail": "trunc')

    findings = perf.run(_Ctx())
    bad = [f for f in findings if f.code == "unparseable-record"]
    assert [f.symbol for f in bad] == ["BENCH_r06.json"]
    assert all(f.severity == "ERROR" for f in bad)

    # the other escape hatch: a gated metric DELETED from the newest round
    # must fire (here: the porous config r04 retired from r03's set)
    vanished = [f for f in findings if f.code == "metric-vanished"]
    assert [f.anchor for f in vanished] == [
        "porous_256_pallas_fused.npt10_w2.teff"
    ]
    assert all(f.severity == "ERROR" for f in vanished)

    # the recorded trajectory's truncations AND the r04 config retirement
    # surface; the repo itself commits no records, so it has nothing to
    # audit
    from implicitglobalgrid_tpu.analysis.core import Context

    class _Traj:
        repo_root = _traj

    assert sorted((f.code, f.symbol) for f in perf.run(_Traj())) == [
        ("metric-vanished", "r04"),
        ("unparseable-record", "BENCH_r01.json"),
        ("unparseable-record", "BENCH_r05.json"),
    ]
    assert perf.run(Context()) == []


def test_gate_metrics_selects_throughput_not_wall_time():
    rec = {
        "value": 10.0,
        "extras": {
            "diffusion_xla": {"teff": 20.0, "t_it_ms": 5.0},
            "grad": {"teff_grad": 7.0, "t_fwd_ms": 1.0},
            "broken": {"error": "ValueError: boom"},
            "nested": {"inner": {"teff": 3.0}},
        },
    }
    assert perf.gate_metrics(rec) == {
        "headline": 10.0,
        "diffusion_xla.teff": 20.0,
        "grad.teff_grad": 7.0,
        "nested.inner.teff": 3.0,
    }


def test_gate_metrics_maps_batch_members_per_s():
    """ISSUE 8: the ``bench.py batch`` record's members/s/chip metrics are
    gated — every sweep row and the headline rate — so a batching
    regression fails the bench-regression pass like a bandwidth drop."""
    rec = {
        "extras": {
            "batch_ensemble": {
                "members_per_s": 12.0,
                "throughput_multiplier": 6.1,  # not a gated key
                "sweep": {
                    "B1": {"members_per_s": 2.0, "t_step_ms": 1.0},
                    "B8": {"members_per_s": 12.0, "t_step_ms": 1.3},
                },
            },
        },
    }
    assert perf.gate_metrics(rec) == {
        "batch_ensemble.members_per_s": 12.0,
        "batch_ensemble.sweep.B1.members_per_s": 2.0,
        "batch_ensemble.sweep.B8.members_per_s": 12.0,
    }
    assert "members_per_s" in perf.GATED_KEYS


# -- comparison + waivers -----------------------------------------------------


def test_compare_metrics_one_sided_band():
    ref = {"a.teff": 100.0, "b.teff": 100.0, "gone.teff": 1.0}
    cand = {"a.teff": 90.0, "b.teff": 80.0, "new.teff": 5.0}
    cmp = perf.compare_metrics(cand, ref, tol=0.15, waivers=[])
    assert [r["metric"] for r in cmp["regressions"]] == ["b.teff"]
    assert cmp["missing"] == ["gone.teff"]
    assert cmp["checked"] == 2
    # improvements never fail (one-sided: the reference simply rises)
    up = perf.compare_metrics({"a.teff": 500.0}, {"a.teff": 100.0},
                              waivers=[])
    assert up["regressions"] == []


def test_waivers_are_measured_concessions_not_mute_buttons(tmp_path):
    ref, cand = {"a.teff": 100.0}, {"a.teff": 50.0}
    waiver = {"metric": "a.teff", "justification": "chip tenancy drift",
              "max_drop": 0.6}
    cmp = perf.compare_metrics(cand, ref, waivers=[waiver])
    assert cmp["regressions"] == [] and len(cmp["waived"]) == 1
    assert cmp["waived"][0]["justification"] == "chip tenancy drift"

    # a drop beyond the waiver's own bound still fails
    tight = dict(waiver, max_drop=0.2)
    cmp = perf.compare_metrics(cand, ref, waivers=[tight])
    assert [r["metric"] for r in cmp["regressions"]] == ["a.teff"]

    # round-scoped waivers only cover their rounds
    scoped = dict(waiver, rounds=[9])
    cmp = perf.compare_metrics(cand, ref, waivers=[scoped],
                               candidate_round=4)
    assert len(cmp["regressions"]) == 1
    cmp = perf.compare_metrics(cand, ref, waivers=[scoped],
                               candidate_round=9)
    assert len(cmp["waived"]) == 1
    # ...and a FRESH record (no round) must not inherit a concession
    # granted to a historical dip
    cmp = perf.compare_metrics(cand, ref, waivers=[scoped],
                               candidate_round=None)
    assert len(cmp["regressions"]) == 1 and not cmp["waived"]

    # the audit contract: no justification = hard error
    bad = tmp_path / "waivers.json"
    bad.write_text(json.dumps(
        {"waivers": [{"metric": "a.teff", "justification": " "}]}
    ))
    with pytest.raises(ValueError, match="justification"):
        perf.load_waivers(str(bad))
    assert perf.load_waivers(str(tmp_path / "absent.json")) == []


def test_shipped_waiver_file_is_well_formed():
    for w in perf.load_waivers():
        assert w["justification"].strip()


# -- the bench.py hook --------------------------------------------------------


def test_gate_summary_verdict_for_fresh_records(tmp_path):
    records, _ = perf.load_bench_records(_traj)
    _, newest = records[-1]
    ok = perf.gate_summary(copy.deepcopy(newest), _traj)
    assert ok["ok"] is True and ok["reference_round"] == records[-1][0]

    doctored = copy.deepcopy(newest)
    doctored["value"] = float(doctored["value"]) * 0.5
    bad = perf.gate_summary(doctored, _traj)
    assert bad["ok"] is False
    assert any(r["metric"] == "headline" for r in bad["regressions"])

    # an empty trajectory cannot regress (first bench run of a repo)
    first = perf.gate_summary(copy.deepcopy(newest), str(tmp_path))
    assert first["ok"] is True and "note" in first


# -- check_perf CLI (the PR gate) ---------------------------------------------


def test_check_perf_passes_the_recorded_trajectory(monkeypatch, capsys):
    """Acceptance: exit 0 on the recorded rounds as they stand."""
    monkeypatch.setattr(check_perf, "REPO", _traj)
    assert check_perf.main([]) == 0
    out = capsys.readouterr().out
    assert "check_perf: OK" in out


def test_check_perf_without_records_is_impossible_not_ok(capsys):
    """The repo commits no bench records: the default comparison cannot
    run, which is exit 2 (impossible), never 0 (a pass)."""
    assert check_perf.main([]) == 2
    assert "no parseable committed BENCH records" in capsys.readouterr().err


def test_check_perf_fails_a_doctored_record(tmp_path, monkeypatch, capsys):
    """Acceptance: a candidate whose headline halved exits nonzero."""
    monkeypatch.setattr(check_perf, "REPO", _traj)
    records, _ = perf.load_bench_records(_traj)
    _, newest = records[-1]
    doctored = copy.deepcopy(newest)
    doctored["value"] = float(doctored["value"]) * 0.5
    p = tmp_path / "doctored.json"
    p.write_text(json.dumps(doctored))
    assert check_perf.main(["--candidate", str(p)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION headline" in out

    # within tolerance: a 5% dip is chip-tenancy noise, not a regression
    mild = copy.deepcopy(newest)
    mild["value"] = float(mild["value"]) * 0.95
    p.write_text(json.dumps(mild))
    assert check_perf.main(["--candidate", str(p)]) == 0


def test_check_perf_json_and_error_contracts(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(check_perf, "REPO", _traj)
    p = tmp_path / "garbage.json"
    p.write_text(json.dumps({"no": "record"}))
    assert check_perf.main(["--candidate", str(p)]) == 2

    # setup failures are exit 2 ("comparison impossible"), never 1: a CI
    # consumer must not read a typo'd path as a perf regression
    assert check_perf.main(
        ["--candidate", str(tmp_path / "no-such-file.json")]) == 2
    badw = tmp_path / "badw.json"
    badw.write_text(json.dumps(
        {"waivers": [{"metric": "m", "justification": ""}]}))
    assert check_perf.main(["--waivers", str(badw)]) == 2

    assert check_perf.main(["--json"]) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["ok"] is True and verdict["checked"] > 0

    # --strict-waivers: a waiver matching nothing fails the run
    stale = tmp_path / "waivers.json"
    stale.write_text(json.dumps({"waivers": [
        {"metric": "no.such.teff", "justification": "left over"}
    ]}))
    assert check_perf.main(["--waivers", str(stale)]) == 0
    assert check_perf.main(["--waivers", str(stale),
                            "--strict-waivers"]) == 1


def test_stale_waivers_tracked_per_entry_not_per_metric():
    """Two same-metric waivers where only one can fire: staleness must be
    keyed on the ENTRY that matched, or the dead round-scoped twin hides
    behind its sibling forever."""
    cand, ref = {"a.teff": 50.0}, {"a.teff": 100.0}
    live = {"metric": "a.teff", "justification": "covers round 9",
            "rounds": [9]}
    dead = {"metric": "a.teff", "justification": "covered round 3 only",
            "rounds": [3]}
    cmp = perf.compare_metrics(cand, ref, waivers=[dead, live],
                               candidate_round=9)
    assert len(cmp["waived"]) == 1
    assert cmp["waived"][0]["waiver_index"] == 1  # the live entry, by id
    used = {w["waiver_index"] for w in cmp["waived"]}
    stale = [w for i, w in enumerate([dead, live]) if i not in used]
    assert stale == [dead]


# -- refresh_cost_baseline CLI (the audit contract) ---------------------------


@pytest.fixture()
def _stub_census(monkeypatch):
    """Route the refresh script's census through a stub (the REAL census
    compiles the whole matrix — that run lives in the tier-1 full suite)."""
    from implicitglobalgrid_tpu.analysis import costmodel

    census = {"prog": {"flops": 1000, "kernel_launches": 3}}
    monkeypatch.setattr(costmodel, "cost_census", lambda ctx: census)
    monkeypatch.setattr(refresh_cost_baseline, "_ensure_devices",
                        lambda: None)
    return census


def test_refresh_requires_a_justify_note_per_changed_metric(
        tmp_path, _stub_census, capsys):
    out = tmp_path / "cost_baseline.json"

    # every metric is new -> every one needs a note
    assert refresh_cost_baseline.main(["--out", str(out)]) == 1
    assert "without a --justify note" in capsys.readouterr().err
    assert not out.exists()

    # a catch-all covers them; the file passes the loader's audit check
    assert refresh_cost_baseline.main(
        ["--out", str(out), "--justify-all", "initial pin"]
    ) == 0
    from implicitglobalgrid_tpu.analysis import costmodel

    data = costmodel.load_baseline(str(out))
    assert data["programs"]["prog"]["metrics"] == _stub_census["prog"]
    assert data["programs"]["prog"]["justifications"]["flops"] == (
        "initial pin"
    )

    # unchanged census: nothing to refresh, notes survive
    assert refresh_cost_baseline.main(["--out", str(out)]) == 0
    assert "nothing to refresh" in capsys.readouterr().out


def test_refresh_per_metric_note_wins_and_dry_run_writes_nothing(
        tmp_path, _stub_census, capsys):
    out = tmp_path / "cost_baseline.json"
    assert refresh_cost_baseline.main(
        ["--out", str(out), "--justify-all", "initial pin"]
    ) == 0

    _stub_census["prog"]["flops"] = 2000  # a real change

    assert refresh_cost_baseline.main(["--out", str(out), "--dry-run"]) == 0
    assert "prog::flops: 1000 -> 2000" in capsys.readouterr().out
    from implicitglobalgrid_tpu.analysis import costmodel

    assert costmodel.load_baseline(str(out))["programs"]["prog"][
        "metrics"]["flops"] == 1000  # dry run wrote nothing

    assert refresh_cost_baseline.main(["--out", str(out)]) == 1  # no note
    assert refresh_cost_baseline.main([
        "--out", str(out),
        "--justify", "prog::flops=PR 8 fuses the halo pack (bench +12%)",
    ]) == 0
    data = costmodel.load_baseline(str(out))
    assert data["programs"]["prog"]["metrics"]["flops"] == 2000
    assert "PR 8 fuses" in data["programs"]["prog"]["justifications"]["flops"]
    # the unchanged metric keeps its original note
    assert data["programs"]["prog"]["justifications"]["kernel_launches"] == (
        "initial pin"
    )

    with pytest.raises(SystemExit):
        refresh_cost_baseline.main(["--justify", "malformed-no-separator"])


def test_refresh_audits_vanished_metrics_too(tmp_path, _stub_census, capsys):
    """A baselined metric the census stopped producing is the gate LOSING
    a check — dropping it from the baseline needs the same --justify audit
    as changing it, and --dry-run must say so (not 'nothing to refresh')."""
    out = tmp_path / "cost_baseline.json"
    assert refresh_cost_baseline.main(
        ["--out", str(out), "--justify-all", "initial pin"]
    ) == 0

    del _stub_census["prog"]["kernel_launches"]

    assert refresh_cost_baseline.main(["--out", str(out), "--dry-run"]) == 0
    assert "prog::kernel_launches: 3 -> <removed>" in capsys.readouterr().out
    assert refresh_cost_baseline.main(["--out", str(out)]) == 1  # no note
    assert refresh_cost_baseline.main([
        "--out", str(out),
        "--justify", "prog::kernel_launches=toolchain stopped exposing it",
    ]) == 0
    from implicitglobalgrid_tpu.analysis import costmodel

    assert "kernel_launches" not in costmodel.load_baseline(
        str(out))["programs"]["prog"]["metrics"]

    # a WHOLE program leaving the matrix is audited the same way
    _stub_census.clear()
    assert refresh_cost_baseline.main(["--out", str(out)]) == 1
    assert "prog::*" in capsys.readouterr().err
    assert refresh_cost_baseline.main(
        ["--out", str(out), "--justify", "prog::*=config retired in PR 9"]
    ) == 0
    assert costmodel.load_baseline(str(out))["programs"] == {}
