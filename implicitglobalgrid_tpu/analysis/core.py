"""Pass-registry framework of ``igg.analysis`` (docs/static-analysis.md).

The correctness story of the reference is implicit — ``update_halo!`` is only
safe because every rank issues the same MPI calls in the same order — and the
repo's one real distributed hang (the ~50%-flaky ``_gather_chunked``, PR 1)
was exactly a cross-rank collective-ordering divergence found by hand.  This
framework turns that bug class (and three more) into machine-checked
invariants that run at trace time, in the spirit of compiler-level SPMD
verification (GSPMD partitioner invariants; MPI deadlock detectors like
MUST): analyzers run over three IRs the codebase already produces — the
package's Python AST, traced jaxprs of the public entry points under a
config matrix, and optimized HLO via `utils.hlo_analysis` — and report
`Finding` records through one runner with a baseline/suppression file and
JSON + human reporters.

Layering: this module is IR-free and jax-free (import is cheap — the package
``__init__`` re-exports it); IR construction lives in `analysis.ir` and is
built lazily by `Context`; each analyzer lives in its own module and is
imported only when it runs.
"""

from __future__ import annotations

import fnmatch
import hashlib
import importlib
import json
import os
from dataclasses import dataclass, field

#: Finding severities, most severe first.  CRITICAL = a distributed-deadlock
#: class (cross-rank divergence); ERROR = must be fixed or explicitly
#: baselined with a justification; WARNING = reported, does not fail the
#: suite (unless ``strict``); INFO = notes/metrics carriers.
SEVERITIES = ("CRITICAL", "ERROR", "WARNING", "INFO")

#: Severities that make `Report.exit_code` nonzero (WARNING joins under
#: ``strict``).
FAILING = ("CRITICAL", "ERROR")


@dataclass(frozen=True)
class Finding:
    """One analyzer result.

    ``path``/``line`` locate the finding in the repo when it is source-
    anchored; ``symbol`` is the stable anchor (function qualname, traced
    entry name) that survives line drift; ``anchor`` disambiguates several
    findings of one rule in one symbol (a knob name, an alias pair).  The
    `fingerprint` — the baseline-file key — deliberately hashes only the
    stable parts (analyzer, code, path, symbol, anchor), never the message
    or line number, so suppressions survive refactors that move lines or
    reword diagnostics.
    """

    analyzer: str
    code: str
    severity: str
    message: str
    path: str = ""
    line: int = 0
    symbol: str = ""
    anchor: str = ""
    fix_hint: str = ""

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(
                f"Finding severity must be one of {SEVERITIES}, "
                f"got {self.severity!r}."
            )

    @property
    def fingerprint(self) -> str:
        key = "|".join(
            (self.analyzer, self.code, self.path, self.symbol, self.anchor)
        )
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    @property
    def location(self) -> str:
        if self.path and self.line:
            return f"{self.path}:{self.line}"
        return self.path or self.symbol or "<package>"

    def to_dict(self) -> dict:
        return {
            "analyzer": self.analyzer,
            "code": self.code,
            "severity": self.severity,
            "message": self.message,
            "location": self.location,
            "symbol": self.symbol,
            "anchor": self.anchor,
            "fix_hint": self.fix_hint,
            "fingerprint": self.fingerprint,
        }


# -- Analyzer registry --------------------------------------------------------


@dataclass(frozen=True)
class AnalyzerSpec:
    """Registry row: where the pass lives and when it is relevant.

    ``paths`` are repo-relative glob patterns used by ``--changed-only`` —
    the analyzer runs iff some changed file matches one of them (changes to
    the analysis framework or its scripts always select every analyzer).
    ``cost``: ``"ast"`` passes parse source only; ``"trace"`` passes build
    jaxprs on the 8-device virtual mesh (seconds, not milliseconds);
    ``"compile"`` passes additionally build optimized HLO through XLA:CPU
    (a few more seconds per program, cached per `Context`).
    """

    name: str
    module: str
    func: str
    title: str
    paths: tuple = ("implicitglobalgrid_tpu/**",)
    cost: str = "ast"

    def load(self):
        mod = importlib.import_module(self.module)
        return getattr(mod, self.func)


#: The shipped analyzer suite.  Order = run + report order.
REGISTRY: dict[str, AnalyzerSpec] = {
    s.name: s
    for s in (
        AnalyzerSpec(
            name="collective-consistency",
            module="implicitglobalgrid_tpu.analysis.collectives",
            func="run",
            title="cross-rank collective-consistency / SPMD-divergence "
            "detector (the _gather_chunked hang class)",
            paths=(
                "implicitglobalgrid_tpu/ops/**",
                "implicitglobalgrid_tpu/models/**",
                "implicitglobalgrid_tpu/parallel/**",
            ),
            cost="trace",
        ),
        AnalyzerSpec(
            name="knob-binding",
            module="implicitglobalgrid_tpu.analysis.knobs",
            func="run_knob_binding",
            title="trace-time knob-binding lint (env reads reachable from "
            "jit/shard_map/Pallas-traced code)",
            paths=("implicitglobalgrid_tpu/**",),
            cost="ast",
        ),
        AnalyzerSpec(
            name="pallas-aliasing",
            module="implicitglobalgrid_tpu.analysis.aliasing",
            func="run",
            title="Pallas input_output_aliases / donation declarations vs "
            "actual in-place use",
            paths=(
                "implicitglobalgrid_tpu/ops/**",
                "implicitglobalgrid_tpu/models/**",
            ),
            cost="trace",
        ),
        AnalyzerSpec(
            name="overlap-independence",
            module="implicitglobalgrid_tpu.analysis.overlap",
            func="run",
            title="structural kernel/collective overlap guarantee of the "
            "pipelined schedules (ISSUE 2), across all models",
            paths=(
                "implicitglobalgrid_tpu/ops/**",
                "implicitglobalgrid_tpu/models/**",
            ),
            cost="trace",
        ),
        AnalyzerSpec(
            name="collective-budget",
            module="implicitglobalgrid_tpu.analysis.budget",
            func="run",
            title="coalesced-exchange collective budget per (dimension, "
            "width group) (scripts/check_collectives.py)",
            paths=(
                "implicitglobalgrid_tpu/ops/**",
                "implicitglobalgrid_tpu/models/**",
                "implicitglobalgrid_tpu/parallel/**",
                # hlo_analysis.py IS the byte census — a change there must
                # re-run the gate that consumes it
                "implicitglobalgrid_tpu/utils/**",
            ),
            cost="trace",
        ),
        AnalyzerSpec(
            name="knob-decl",
            module="implicitglobalgrid_tpu.analysis.knobs",
            func="run_knob_decl",
            title="every IGG_* knob declared in utils/config.py and "
            "documented in docs/usage.md (scripts/check_knobs.py)",
            paths=("implicitglobalgrid_tpu/**", "docs/usage.md"),
            cost="ast",
        ),
        AnalyzerSpec(
            name="hlo-cost",
            module="implicitglobalgrid_tpu.analysis.costmodel",
            func="run",
            title="static HLO cost model of the production config matrix "
            "vs the versioned cost baseline (bytes, flops, payloads, "
            "launches, peak buffers)",
            paths=(
                "implicitglobalgrid_tpu/ops/**",
                "implicitglobalgrid_tpu/models/**",
                "implicitglobalgrid_tpu/parallel/**",
                # the cost census is produced BY utils/hlo_analysis.py —
                # the gate must re-run when its own parser changes
                "implicitglobalgrid_tpu/utils/**",
            ),
            cost="compile",
        ),
        AnalyzerSpec(
            name="grad-soundness",
            module="implicitglobalgrid_tpu.analysis.gradflow",
            func="run",
            title="cotangent-dropping primitives on the tangent path + "
            "backward-collective census of every differentiable entry "
            "point (the PR-5 zero-gradient-sink class)",
            paths=(
                "implicitglobalgrid_tpu/ops/**",
                "implicitglobalgrid_tpu/models/**",
            ),
            cost="trace",
        ),
        AnalyzerSpec(
            name="bench-regression",
            module="implicitglobalgrid_tpu.analysis.perf",
            func="run",
            title="committed bench trajectory within per-metric tolerance "
            "bands (scripts/check_perf.py; waivers in "
            "analysis/perf_waivers.json)",
            paths=("BENCH_*.json", "bench.py", "benchmarks/**"),
            cost="ast",
        ),
        AnalyzerSpec(
            name="tune-cache-valid",
            module="implicitglobalgrid_tpu.analysis.tunecache",
            func="run",
            title="committed autotuner seed entries parse against the "
            "schema and hold currently-admissible configs "
            "(tuning/entries, scripts/igg_tune.py)",
            paths=(
                "implicitglobalgrid_tpu/tuning/**",
                # the envelopes ARE the admissibility ladder — a kernel
                # constraint change must re-validate the committed winners
                "implicitglobalgrid_tpu/ops/**",
            ),
            cost="ast",
        ),
    )
}

#: Changes to the analysis subsystem itself select the whole suite.
_SELF_PATHS = (
    "implicitglobalgrid_tpu/analysis/**",
    "scripts/igg_lint.py",
    "scripts/check_collectives.py",
    "scripts/check_knobs.py",
    "scripts/check_perf.py",
    "scripts/refresh_cost_baseline.py",
)


def available_analyzers() -> tuple[str, ...]:
    return tuple(REGISTRY)


def select_for_paths(changed: list[str]) -> list[str]:
    """Analyzer names relevant to the given repo-relative changed paths
    (the ``--changed-only`` fast mode).  Framework changes select all."""
    changed = [p.replace(os.sep, "/") for p in changed]
    if any(
        fnmatch.fnmatch(p, pat) for p in changed for pat in _SELF_PATHS
    ):
        return list(REGISTRY)
    return [
        name
        for name, spec in REGISTRY.items()
        if any(
            fnmatch.fnmatch(p, pat) for p in changed for pat in spec.paths
        )
    ]


# -- Context: lazily-built shared IRs -----------------------------------------


class Context:
    """Shared state of one analysis run.

    IRs are built once and shared: the package AST parse (`module_asts`) and
    the traced-jaxpr entry matrix (`exchange_entries`/`cadence_entries`,
    built by `analysis.ir` — requires a jax runtime and manages its own
    grids).  ``package_root``/``repo_root`` are overridable so tests can
    point AST passes at fixture packages.
    """

    def __init__(self, repo_root: str | None = None,
                 package_root: str | None = None):
        here = os.path.dirname(os.path.abspath(__file__))
        default_pkg = os.path.dirname(here)
        self.repo_root = repo_root or os.path.dirname(default_pkg)
        self.package_root = package_root or default_pkg
        self._asts = None
        self._exchange = None
        self._cadence = None
        self._grad = None
        self._compiled = {}
        self._batched_census = None

    # AST IR ------------------------------------------------------------

    def module_asts(self) -> dict:
        """``{repo-relative path: (source, ast.Module)}`` for every ``.py``
        under the package (parsed once per context)."""
        if self._asts is None:
            import ast

            out = {}
            for dirpath, dirnames, filenames in os.walk(self.package_root):
                dirnames[:] = [
                    d for d in dirnames if d not in ("__pycache__",)
                ]
                for name in sorted(filenames):
                    if not name.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, name)
                    rel = os.path.relpath(path, self.repo_root).replace(
                        os.sep, "/"
                    )
                    with open(path, encoding="utf-8") as f:
                        src = f.read()
                    out[rel] = (src, ast.parse(src, filename=rel))
            self._asts = out
        return self._asts

    # Traced IR ----------------------------------------------------------

    def exchange_entries(self):
        """Traced halo-exchange entry points (all models x coalesce on/off
        x padded/slab variants on one periodic+PROC_NULL grid)."""
        if self._exchange is None:
            from . import ir

            self._exchange = ir.trace_exchange_entries()
        return self._exchange

    def batched_exchange_census(self):
        """The batched-exchange ppermute census (3 models x B∈{1, 4}),
        traced once per context like the other IRs (`analysis.budget`)."""
        if self._batched_census is None:
            from . import budget

            self._batched_census = budget.batched_exchange_census()
        return self._batched_census

    def cadence_entries(self):
        """Traced model multi-step cadences (3 models x pipelined on/off)."""
        if self._cadence is None:
            from . import ir

            self._cadence = ir.trace_cadence_entries()
        return self._cadence

    def grad_entries(self):
        """Traced VJP programs of the differentiable entry points (all
        models' coalesced exchange + fused cadences, `ir.trace_grad_entries`)."""
        if self._grad is None:
            from . import ir

            self._grad = ir.trace_grad_entries()
        return self._grad

    # Compiled IR (optimized HLO + toolchain stats) -----------------------

    def compiled_program(self, name: str):
        """One compiled program of `ir.COMPILED_MATRIX`, cached per config —
        the budget analyzer's HLO cross-check and the cost model's census
        share ONE compile of each program instead of rebuilding it."""
        if name not in self._compiled:
            from . import ir

            self._compiled[name] = ir.compile_program(name)
        return self._compiled[name]

    def compiled_programs(self) -> dict:
        """The full compiled matrix (`{name: ir.CompiledProgram}`)."""
        from . import ir

        return {n: self.compiled_program(n) for n in ir.COMPILED_MATRIX}

    def exchange_hlo(self) -> str:
        """Optimized-HLO text of the porous coalesced exchange (one small
        XLA:CPU build, shared with the cost model's census)."""
        from . import ir

        return self.compiled_program(ir.EXCHANGE_HLO_PROGRAM).text


# -- Baseline (suppression file) ----------------------------------------------

#: Default baseline location: versioned next to the analyzers.
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json"
)


@dataclass
class Baseline:
    """Fingerprint -> justification suppressions.

    Every entry MUST carry a non-empty justification — the file is the audit
    trail for "we looked at this finding and decided it is intentional",
    never a mute button (docs/static-analysis.md, baseline workflow).
    """

    suppressions: dict[str, dict] = field(default_factory=dict)
    path: str = ""

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not os.path.exists(path):
            return cls(path=path)
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        sup = {}
        for entry in data.get("suppressions", []):
            fp = entry.get("fingerprint", "")
            just = (entry.get("justification") or "").strip()
            if not fp:
                raise ValueError(
                    f"baseline {path}: suppression without a fingerprint: "
                    f"{entry!r}"
                )
            if not just:
                raise ValueError(
                    f"baseline {path}: suppression {fp} has no "
                    f"justification — every baselined finding must say WHY "
                    f"it is acceptable (see docs/static-analysis.md)."
                )
            sup[fp] = entry
        return cls(suppressions=sup, path=path)

    def match(self, finding: Finding) -> dict | None:
        return self.suppressions.get(finding.fingerprint)


# -- Report + runner ----------------------------------------------------------


@dataclass
class Report:
    """One run's outcome: active findings (severity-ordered), suppressed
    findings (baseline hits), stale suppressions (baseline entries that
    matched nothing — the tree moved on), per-analyzer stats, and the
    analyzers that ran/skipped."""

    findings: list = field(default_factory=list)
    suppressed: list = field(default_factory=list)
    stale_suppressions: list = field(default_factory=list)
    ran: list = field(default_factory=list)
    skipped: list = field(default_factory=list)
    stats: dict = field(default_factory=dict)
    errors: dict = field(default_factory=dict)

    def counts(self) -> dict:
        out = {s: 0 for s in SEVERITIES}
        for f in self.findings:
            out[f.severity] += 1
        return out

    def exit_code(self, strict: bool = False) -> int:
        if self.errors:
            return 2
        failing = FAILING + (("WARNING",) if strict else ())
        return 1 if any(f.severity in failing for f in self.findings) else 0

    def to_json(self) -> str:
        return json.dumps(
            {
                "findings": [f.to_dict() for f in self.findings],
                "suppressed": [
                    {**f.to_dict(), "justification": j}
                    for f, j in self.suppressed
                ],
                "stale_suppressions": self.stale_suppressions,
                "ran": self.ran,
                "skipped": self.skipped,
                "counts": self.counts(),
                "stats": self.stats,
                "errors": self.errors,
            },
            indent=2,
            sort_keys=True,
        )

    def human(self) -> str:
        lines = []
        order = {s: i for i, s in enumerate(SEVERITIES)}
        for f in sorted(
            self.findings, key=lambda f: (order[f.severity], f.location)
        ):
            lines.append(f"{f.severity:8s} [{f.analyzer}/{f.code}] "
                         f"{f.location}: {f.message}")
            if f.fix_hint:
                lines.append(f"         fix: {f.fix_hint}")
            lines.append(f"         fingerprint: {f.fingerprint}")
        if self.suppressed:
            lines.append(f"-- {len(self.suppressed)} baselined finding(s):")
            for f, j in self.suppressed:
                lines.append(
                    f"   {f.analyzer}/{f.code} @ {f.location} "
                    f"[{f.fingerprint}] — {j}"
                )
        for fp in self.stale_suppressions:
            lines.append(
                f"WARNING  baseline suppression {fp} matched no finding — "
                f"remove it (the tree moved on)."
            )
        for name, err in self.errors.items():
            lines.append(f"ERROR    analyzer {name} crashed: {err}")
        c = self.counts()
        summary = ", ".join(f"{c[s]} {s}" for s in SEVERITIES if c[s])
        lines.append(
            f"igg-lint: {len(self.ran)} analyzer(s) ran"
            + (f", {len(self.skipped)} skipped" if self.skipped else "")
            + (f" — {summary}" if summary else " — clean")
        )
        return "\n".join(lines)


def run(
    names=None,
    *,
    baseline: str | None = DEFAULT_BASELINE,
    changed_paths: list[str] | None = None,
    ctx: Context | None = None,
    keep_going: bool = False,
) -> Report:
    """Run analyzers and fold their findings through the baseline.

    ``names``: analyzer subset (None = all).  ``changed_paths``: restrict to
    analyzers whose declared paths intersect (the ``--changed-only`` mode) —
    applied on top of ``names``.  ``baseline``: suppression-file path (None
    = no suppression).  ``keep_going``: trap analyzer crashes into
    ``report.errors`` instead of raising (the CLI's behavior; the tier-1
    test raises so a broken analyzer fails loudly).
    """
    ctx = ctx or Context()
    wanted = list(names) if names else list(REGISTRY)
    unknown = [n for n in wanted if n not in REGISTRY]
    if unknown:
        raise ValueError(
            f"unknown analyzer(s) {unknown}; available: {list(REGISTRY)}"
        )
    if changed_paths is not None:
        relevant = set(select_for_paths(changed_paths))
        selected = [n for n in wanted if n in relevant]
    else:
        selected = wanted

    report = Report(skipped=[n for n in wanted if n not in selected])
    base = Baseline.load(baseline) if baseline else Baseline()
    used = set()
    for name in selected:
        spec = REGISTRY[name]
        try:
            found = list(spec.load()(ctx))
        except Exception as e:  # noqa: BLE001 — CLI surfaces, test raises
            if not keep_going:
                raise
            report.errors[name] = f"{type(e).__name__}: {e}"
            continue
        report.ran.append(name)
        for f in found:
            hit = base.match(f)
            if hit is not None:
                used.add(f.fingerprint)
                report.suppressed.append((f, hit["justification"]))
            else:
                report.findings.append(f)
    # Staleness is only decidable when EVERY registered analyzer ran and
    # none crashed — on a subset / --changed-only / keep_going-crash run,
    # an unmatched suppression usually belongs to an analyzer that never
    # produced its findings, and advising "remove it" would delete valid
    # entries.
    if not report.errors and set(report.ran) == set(REGISTRY):
        report.stale_suppressions = [
            fp for fp in base.suppressions if fp not in used
        ]
    return report


def ensure_cpu_devices(n: int = 8) -> None:
    """Stage an ``n``-device XLA:CPU mesh before first backend use.

    The one staging recipe shared by every CLI driver of the suite
    (``igg_lint.py``, ``refresh_cost_baseline.py``; the tier-1 tests do the
    same in conftest).  Call it before the first `jax.devices()`: the
    config cannot change once the backend is up.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is not None and int(m.group(1)) != n:
        # Silently keeping a pre-staged wrong count would surface later as
        # a confusing mesh-size error (or a census on the wrong mesh).
        raise RuntimeError(
            f"XLA_FLAGS already stages "
            f"--xla_force_host_platform_device_count={m.group(1)}, but the "
            f"analysis suite needs {n} devices — unset it (or set it to "
            f"{n}) before running."
        )
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def changed_files(repo_root: str | None = None,
                  ref: str | None = None) -> list[str]:
    """Repo-relative changed paths — the ``--changed-only`` census.

    ``ref=None`` (the default): `git status --porcelain` — staged, worktree
    and untracked changes vs HEAD; empty when git is unavailable (no fast
    mode, back-compat).  ``ref="main"`` (or any committish): the union of
    the merge-base diff against ``ref`` AND the status paths — what a PR
    branch changed even on a CLEAN CI checkout, where `git status` selects
    nothing.  In ref mode a git failure RAISES instead of returning empty:
    silently selecting zero analyzers on a bad ref would green-light a PR
    that was never linted.
    """
    import subprocess

    root = repo_root or Context().repo_root

    def _git(*args) -> str:
        return subprocess.run(
            ["git", *args], cwd=root, capture_output=True, text=True,
            timeout=30, check=True,
        ).stdout

    paths = []
    try:
        status = _git("status", "--porcelain")
    except Exception as e:  # noqa: BLE001 — no git
        if ref is not None:
            raise RuntimeError(
                f"--changed-only={ref}: git status failed in {root}: {e}"
            ) from e
        return []
    for line in status.splitlines():
        if len(line) < 4:
            continue
        p = line[3:].strip()
        if " -> " in p:  # renames list "old -> new"
            p = p.split(" -> ", 1)[1]
        paths.append(p.strip('"'))
    if ref is None:
        return paths
    try:
        base = _git("merge-base", "HEAD", ref).strip()
        diff = _git("diff", "--name-only", base, "HEAD")
    except Exception as e:  # noqa: BLE001 — bad ref must not select zero
        raise RuntimeError(
            f"--changed-only={ref}: merge-base diff failed in {root} "
            f"(is {ref!r} a valid ref?): {e}"
        ) from e
    seen = set(paths)
    for p in diff.splitlines():
        p = p.strip()
        if p and p not in seen:
            seen.add(p)
            paths.append(p)
    return paths
