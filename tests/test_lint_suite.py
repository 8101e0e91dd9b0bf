"""Tier-1 full-suite run of `igg.analysis` (docs/static-analysis.md).

The acceptance bar of ISSUE 6: the REAL package passes the full analyzer
suite with an empty finding list (modulo the justified baseline), in this
process, every tier-1 run — so a rank-divergent collective, a traced env
read, a bogus alias or a lost overlap pair introduced anywhere in the
package fails CI before it can hang a 9-minute gloo soak.  The CLI's
exit-code and selection contracts (`scripts/igg_lint.py`) are pinned here
too; per-analyzer seeded fixtures live in `tests/test_static_analysis.py`.
"""

import importlib.util
import os

import pytest

from implicitglobalgrid_tpu import analysis

_here = os.path.dirname(os.path.abspath(__file__))
_repo = os.path.dirname(_here)
_spec = importlib.util.spec_from_file_location(
    "igg_lint", os.path.join(_repo, "scripts", "igg_lint.py")
)
igg_lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(igg_lint)


@pytest.fixture(scope="module")
def full_report():
    """ONE full-suite run shared by the module's asserts (the traced-IR
    matrix is seconds-per-entry; keep_going=False so a crashed analyzer
    fails loudly with its real traceback, not an exit code)."""
    return analysis.run(keep_going=False)


def test_full_suite_runs_clean(full_report):
    assert full_report.errors == {}
    assert full_report.findings == [], (
        "igg-lint found unbaselined issues:\n" + full_report.human()
    )
    assert full_report.exit_code() == 0


def test_full_suite_ran_every_analyzer(full_report):
    assert full_report.ran == list(analysis.available_analyzers())
    assert full_report.skipped == []


def test_baseline_has_no_stale_suppressions(full_report):
    """A baseline entry matching no finding means the tree moved on — the
    suppression must be deleted, or it will silently mute a future
    regression that happens to collide."""
    assert full_report.stale_suppressions == []


def test_every_suppression_fired_with_a_justification(full_report):
    assert full_report.suppressed, "the shipped baseline matched nothing"
    for finding, justification in full_report.suppressed:
        assert finding.analyzer == "knob-binding"
        assert len(justification) > 40
    # the triaged set is exactly the 4 documented knob-binding contracts
    # (IGG_COALESCE / IGG_TELEMETRY / IGG_VMEM_MB / IGG_TRACE_RING); the
    # repo commits no bench records, so bench-regression has nothing to
    # suppress
    assert len(full_report.suppressed) == 4


def test_cli_exit_code_contract():
    """The cheap half of the CLI surface: --list enumerates the registry,
    an AST-only subset exits 0 (its findings are baselined), an unknown
    name is an argparse error.  (--all's exit code is test 1 via the
    in-process run; re-running the trace matrix through the CLI would
    double tier-1's cost for no new information.)"""
    assert igg_lint.main(["--list"]) == 0
    assert igg_lint.main(["knob-decl"]) == 0
    assert igg_lint.main(["knob-binding", "--json"]) == 0
    with pytest.raises(SystemExit):
        igg_lint.main(["no-such-analyzer"])
    with pytest.raises(SystemExit):
        igg_lint.main([])  # no names, no --all
    with pytest.raises(SystemExit):
        # the optional-REF ambiguity: a bare `--changed-only` followed by
        # an analyzer name must be refused, not silently treated as a ref
        igg_lint.main(["--changed-only", "knob-binding", "knob-decl"])
    # the literal `=` spelling is the escape hatch for a branch that
    # genuinely shares an analyzer's name: it passes the guard and fails
    # only because no such ref exists here (exit 2, not argparse exit)
    assert igg_lint.main(["--changed-only=knob-binding", "knob-decl"]) == 2


def test_cli_sarif_stdout_stays_pure_json(capsys):
    """`--sarif -` makes stdout the artifact: the human report must ride
    stderr or the SARIF log is unparseable by its consumer."""
    import json

    rc = igg_lint.main(["bench-regression", "--sarif", "-"])
    captured = capsys.readouterr()
    assert rc == 0
    log = json.loads(captured.out)  # whole stdout parses as one JSON doc
    assert log["version"] == "2.1.0"
    assert "1 analyzer(s) ran" in captured.err


def test_cli_changed_only_fast_mode(tmp_path):
    """--changed-only keys analyzer selection on git-status paths; with a
    doc-only change the trace-cost analyzers must be skipped."""
    report = analysis.run(
        names=None,
        changed_paths=["docs/usage.md"],
    )
    assert report.ran == ["knob-decl"]
    assert set(report.skipped) == set(analysis.available_analyzers()) - {
        "knob-decl"
    }


def test_ensure_cpu_devices_refuses_a_conflicting_prestaged_count(
        monkeypatch):
    """A pre-staged WRONG device count must fail loudly here, not later as
    a confusing mesh-size error (idempotent when the count matches)."""
    from implicitglobalgrid_tpu.analysis.core import ensure_cpu_devices

    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    with pytest.raises(RuntimeError, match="needs 8 devices"):
        ensure_cpu_devices()
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    ensure_cpu_devices()  # matching count: a no-op
    assert os.environ["XLA_FLAGS"].count(
        "--xla_force_host_platform_device_count") == 1


def test_cli_conflicting_device_count_is_a_crash_not_findings(
        monkeypatch, capsys):
    """An environment/setup failure must exit 2 (crash), never 1 — an
    exit-code-driven consumer reads 1 as 'lint findings'."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    assert igg_lint.main(["grad-soundness"]) == 2
    assert "needs 8 devices" in capsys.readouterr().err


def test_hlo_analysis_changes_select_the_census_consumers():
    """utils/hlo_analysis.py IS the byte census: --changed-only selection
    on a change there must re-run the gates that consume it."""
    selected = analysis.select_for_paths(
        ["implicitglobalgrid_tpu/utils/hlo_analysis.py"])
    assert {"hlo-cost", "collective-budget"} <= set(selected)


def test_knob_binding_subset_exits_nonzero_without_baseline(capsys):
    """The raw-findings contract: --no-baseline exposes the three triaged
    per-trace knob reads and the exit code says so."""
    rc = igg_lint.main(["knob-binding", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "env-read-in-trace" in out
