"""Bench-regression analyzer (``bench-regression``) — the perf trajectory
as a gate.

The repo's perf evidence is the ``BENCH_r*.json`` trajectory (one record
per round, written by the bench driver around ``bench.py``'s single JSON
line).  Until this pass nothing READ it: a PR could halve a headline
number and tier-1 stayed green (ROADMAP item 5).  This module turns the
trajectory into a machine-checked invariant:

* `load_bench_records` parses every committed ``BENCH_r*.json`` (the
  driver wrapper ``{"n", "parsed", "tail", ...}`` or a raw ``bench.py``
  record), skipping rounds whose record is truncated beyond recovery
  (r05's ``tail`` is mid-JSON) — those are reported, never silently used;
* `gate_metrics` flattens a record to its gated metrics: the headline
  ``value`` plus every ``teff``/``teff_grad`` in ``extras`` (throughput,
  higher-is-better — wall-time columns drift with chip tenancy and are
  deliberately NOT gated);
* `compare_metrics` fails a candidate metric that DROPS more than ``tol``
  (default 15% — the real trajectory's worst cross-round drop is 7.9%,
  r02→r03 ``diffusion_512``, chip-tenancy drift) below the reference,
  unless a waiver in `analysis/perf_waivers.json` covers it.  Waivers
  mirror the justified-suppression baseline: every entry REQUIRES a
  justification, and stale waivers are reported.

Consumers: ``scripts/check_perf.py`` (CLI gate — nonzero on regression),
the ``bench-regression`` registry pass (tier-1: the committed trajectory
itself must be self-consistent), and ``bench.py`` (attaches an
``extras.perf_gate`` verdict to every fresh record).
"""

from __future__ import annotations

import glob
import json
import os
import re

from .core import Context, Finding

ANALYZER = "bench-regression"

#: Allowed fractional DROP per metric vs the reference record.  One-sided:
#: improvements never fail (the next round's reference simply rises).
DEFAULT_TOL = 0.15

#: Machine-readable waiver file, next to the analyzers like baseline.json.
PERF_WAIVERS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "perf_waivers.json"
)

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


# -- record discovery ---------------------------------------------------------


def parse_bench_file(path: str) -> dict | None:
    """The bench record inside one ``BENCH_*.json`` (None = unrecoverable).

    Accepts the driver wrapper (``parsed`` preferred, then a full-JSON
    ``tail``) and the raw ``bench.py`` record itself.
    """
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except ValueError:  # truncated mid-write: a skip-and-report, not a crash
        return None
    if not isinstance(data, dict):
        return None
    if "metric" in data and "extras" in data:
        return data
    rec = data.get("parsed")
    if isinstance(rec, dict) and "extras" in rec:
        return rec
    tail = data.get("tail", "")
    start = tail.find("{")  # the record is the line's first JSON object
    if start >= 0:
        try:
            # raw_decode: the record may be followed by trailing log text
            # (a normal capture shape) — only a TRUNCATED object fails
            rec, _ = json.JSONDecoder().raw_decode(tail[start:])
            if isinstance(rec, dict) and "extras" in rec:
                return rec
        except ValueError:
            pass
    return None


def load_bench_records(repo_root: str) -> tuple[list, list]:
    """``([(round, record)...] ascending, [unparseable paths])``."""
    records, skipped = [], []
    for path in sorted(glob.glob(os.path.join(repo_root, "BENCH_*.json"))):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        rec = parse_bench_file(path)
        if rec is None:
            skipped.append(os.path.basename(path))
        else:
            records.append((int(m.group(1)), rec))
    records.sort()
    return records, skipped


#: extras keys gated as higher-is-better throughput metrics.  ``teff`` /
#: ``teff_grad`` are GB/s; ``members_per_s`` is the batched-serving
#: members/s/chip record (``bench.py batch``, ISSUE 8) — same one-sided
#: drop semantics, so a batching regression fails like a bandwidth one.
#: ``rounds_per_s`` plus the INVERSE submit→result latencies
#: ``result_p50_per_s``/``result_p99_per_s`` are the front-door serving
#: record (``extras.frontdoor_serving``, ISSUE 12): inverting the latency
#: makes "p99 got slower" a one-sided DROP, so the existing gate catches
#: it without new comparison semantics (the raw seconds ride along as
#: `REPORTED_KEYS`).  ``tuned_speedup`` is the autotuner's closed loop
#: (``extras.tuned_vs_default``, ISSUE 13): t_default / t_tuned per model,
#: so a tuner that starts picking slower-than-default configs (or a
#: regression that erases a tuned win) drops the ratio and fails the gate
#: the way a bandwidth drop does.
GATED_KEYS = ("teff", "teff_grad", "members_per_s", "rounds_per_s",
              "result_p50_per_s", "result_p99_per_s", "tuned_speedup")


def gate_metrics(record: dict) -> dict:
    """Flatten one bench record to ``{metric path: value}`` for the gated
    throughput metrics (headline ``value`` + every nested `GATED_KEYS`
    entry under ``extras``; error-bearing extras contribute nothing —
    wall-time columns drift with chip tenancy and are deliberately not
    gated)."""
    out = {}
    if isinstance(record.get("value"), (int, float)):
        out["headline"] = float(record["value"])

    def walk(prefix: str, node) -> None:
        if not isinstance(node, dict):
            return
        for key, val in node.items():
            if key in GATED_KEYS and isinstance(val, (int, float)):
                out[f"{prefix}{key}"] = float(val)
            elif isinstance(val, dict):
                walk(f"{prefix}{key}.", val)

    walk("", record.get("extras", {}))
    return out


#: extras keys REPORTED alongside the gate verdict but not (yet) gated:
#: ``achieved_fraction`` is the cost-model reconciliation number
#: (`analysis.reconcile` — ``extras.efficiency``), carried per round so a
#: future gate has a trajectory to regress against before it starts
#: failing PRs on it; the ``submit_to_result_*`` seconds are the raw
#: front-door latencies whose inverses are gated (human-readable twins).
#: ``overlap_fraction`` is the MEASURED comm/compute overlap of the
#: device-timeline capture (``extras.profile_attribution``, ISSUE 15 —
#: `utils.profiling.overlap_measure`): the number ROADMAP item 1's
#: Pallas-native exchange must push up, on the same reported-first on-ramp
#: achieved_fraction took (promote to GATED once a chip-env round records
#: it).  The ``*_share`` keys are the request critical-path decomposition
#: (``extras.request_trace``, ISSUE 19 — `utils.tracing.critical_path`):
#: the traced request's latency attributed to queue-wait / admission /
#: rounds / exchange / checkpoint / re-route and the uncovered remainder —
#: reported per round so a latency regression names its segment before
#: anyone opens a trace viewer.
REPORTED_KEYS = ("achieved_fraction", "submit_to_result_p50_s",
                 "submit_to_result_p99_s", "overlap_fraction",
                 "queue_wait_share", "admission_share", "rounds_share",
                 "exchange_share", "checkpoint_share", "reroute_share",
                 "other_share")


def reported_metrics(record: dict) -> dict:
    """Flatten one bench record to ``{metric path: value}`` for the
    report-only keys (`REPORTED_KEYS`) — same walk as `gate_metrics`,
    no comparison semantics."""
    out = {}

    def walk(prefix: str, node) -> None:
        if not isinstance(node, dict):
            return
        for key, val in node.items():
            if key in REPORTED_KEYS and isinstance(val, (int, float)):
                out[f"{prefix}{key}"] = float(val)
            elif isinstance(val, dict):
                walk(f"{prefix}{key}.", val)

    walk("", record.get("extras", {}))
    return out


# -- waivers ------------------------------------------------------------------


def load_waivers(path: str = PERF_WAIVERS) -> list[dict]:
    """Waiver entries (``[]`` when the file is absent).  Schema::

        {"waivers": [{"metric": "...", "justification": "...",
                      "max_drop": 0.5, "rounds": [5]}]}

    ``metric`` names a `gate_metrics` path; ``max_drop`` bounds the waived
    drop (a waiver is a measured concession, not a blank check — default
    1.0 = any drop); ``rounds`` restricts the waiver to specific candidate
    rounds (omit = any).  A waiver without a justification is an error —
    same contract as the suppression baseline.
    """
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    waivers = data.get("waivers", [])
    for w in waivers:
        if not (w.get("metric") or "").strip():
            raise ValueError(f"perf waiver without a metric: {w!r}")
        if not (w.get("justification") or "").strip():
            raise ValueError(
                f"perf waiver for {w['metric']!r} has no justification — "
                f"every waived regression must say WHY it is acceptable."
            )
    return waivers


def _waiver_for(metric: str, drop: float, round_n, waivers) -> dict | None:
    for w in waivers:
        if w["metric"] != metric:
            continue
        rounds = w.get("rounds")
        if rounds is not None and round_n not in rounds:
            # A round-scoped waiver covers ONLY those committed rounds; a
            # fresh --candidate record has no round (None) and must not
            # inherit a concession granted to a historical dip.
            continue
        if drop <= float(w.get("max_drop", 1.0)):
            return w
    return None


# -- comparison ---------------------------------------------------------------


def compare_metrics(candidate: dict, reference: dict, *,
                    tol: float = DEFAULT_TOL, waivers=None,
                    candidate_round=None) -> dict:
    """Compare flattened metric maps.  Returns::

        {"regressions": [{metric, reference, candidate, drop}...],
         "waived":      [{..., "justification"}...],
         "missing":     [metrics in reference absent from candidate],
         "checked":     n}

    Only metrics present in BOTH records are compared (configs come and go
    across rounds); reference metrics the candidate lost entirely are
    listed in ``missing`` — the caller decides whether absence fails.
    """
    waivers = load_waivers() if waivers is None else waivers
    regressions, waived, missing = [], [], []
    checked = 0
    for metric, ref in sorted(reference.items()):
        if ref <= 0:
            continue
        if metric not in candidate:
            missing.append(metric)
            continue
        checked += 1
        cand = candidate[metric]
        drop = (ref - cand) / ref
        if drop <= tol:
            continue
        rec = {
            "metric": metric,
            "reference": ref,
            "candidate": cand,
            "drop": round(drop, 4),
        }
        w = _waiver_for(metric, drop, candidate_round, waivers)
        if w is not None:
            rec["justification"] = w["justification"]
            # which ENTRY matched (not just which metric): staleness
            # detection must see that a second, round-scoped waiver for
            # the same metric never fired
            rec["waiver_index"] = waivers.index(w)
            waived.append(rec)
        else:
            regressions.append(rec)
    return {
        "regressions": regressions,
        "waived": waived,
        "missing": missing,
        "checked": checked,
    }


def gate_summary(candidate_record: dict, repo_root: str, *,
                 tol: float = DEFAULT_TOL) -> dict:
    """The ``bench.py`` hook: compare a FRESH record against the newest
    committed round.  Returns a JSON-ready verdict (never raises on an
    empty trajectory — a first bench run has nothing to regress from)."""
    records, skipped = load_bench_records(repo_root)
    if not records:
        return {"ok": True, "note": "no committed BENCH records to compare",
                "reported": reported_metrics(candidate_record),
                "skipped_records": skipped}
    ref_round, ref_rec = records[-1]
    cmp = compare_metrics(
        gate_metrics(candidate_record), gate_metrics(ref_rec), tol=tol
    )
    return {
        "ok": not cmp["regressions"],
        "reference_round": ref_round,
        "tol": tol,
        **cmp,
        "reported": reported_metrics(candidate_record),
        "skipped_records": skipped,
    }


def run(ctx: Context) -> list[Finding]:
    """Registry pass: the COMMITTED trajectory must be self-consistent —
    the newest parseable round within tolerance of its predecessor (modulo
    waivers).  This is what keeps a PR from committing a regressed bench
    artifact; the live gate for fresh runs is ``scripts/check_perf.py``."""
    records, skipped = load_bench_records(ctx.repo_root)
    out = []
    for name in skipped:
        # An unparseable committed round is a gate blind spot: a regressed
        # record could merge wearing truncation as camouflage.  Known
        # historical truncations (r01/r05, damaged before this gate
        # existed) are baselined with justifications; a NEW one must be
        # looked at, not waved through.
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="unparseable-record",
                severity="ERROR",
                message=(
                    f"{name} holds no parseable bench record — the gate "
                    f"cannot audit it, so the round merges sight-unseen.  "
                    f"Re-emit the record, or baseline the truncation with "
                    f"a justification."
                ),
                symbol=name,
                anchor="unparseable",
            )
        )
    if len(records) < 2:
        return out  # one (or zero) records: nothing to regress from
    (prev_round, prev), (cand_round, cand) = records[-2], records[-1]
    cmp = compare_metrics(
        gate_metrics(cand), gate_metrics(prev),
        candidate_round=cand_round,
    )
    for reg in cmp["regressions"]:
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="perf-regression",
                severity="ERROR",
                message=(
                    f"BENCH_r{cand_round:02d}: {reg['metric']} dropped "
                    f"{reg['drop']:.1%} vs r{prev_round:02d} "
                    f"({reg['reference']:.2f} -> {reg['candidate']:.2f} "
                    f"GB/s, tolerance {DEFAULT_TOL:.0%}) — waive it in "
                    f"analysis/perf_waivers.json with a justification, or "
                    f"fix the regression."
                ),
                symbol=f"r{cand_round:02d}",
                anchor=reg["metric"],
            )
        )
    for metric in cmp["missing"]:
        # A gated metric that vanished from the newest round is the other
        # escape hatch: a regression can hide by deleting its benchmark.
        # Legit config retirements get a baseline entry saying WHY.
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="metric-vanished",
                severity="ERROR",
                message=(
                    f"BENCH_r{cand_round:02d}: gated metric {metric} "
                    f"(present in r{prev_round:02d}) is absent — a "
                    f"regression can hide by dropping its benchmark.  "
                    f"Re-measure the config, or baseline the retirement "
                    f"with a justification."
                ),
                symbol=f"r{cand_round:02d}",
                anchor=metric,
            )
        )
    return out
