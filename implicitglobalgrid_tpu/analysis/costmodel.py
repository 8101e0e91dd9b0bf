"""Static HLO cost-model analyzer (``hlo-cost``) — the perf-invariant gate.

The repo's entire value proposition is a performance invariant — halo
exchange O(surface), compute O(volume) — but until this pass the only perf
evidence was hand-run ``bench.py`` records: a PR could add a silent copy,
defuse a kernel, or widen a halo payload and still pass tier-1.  This pass
walks the OPTIMIZED HLO of the production config matrix (the porous 5-field
coalesced exchange + all three models' fused cadences,
`ir.COMPILED_MATRIX`, compiled once per run and cached on the `Context`)
and pins per-program invariants in a versioned baseline with tolerance
bands — so a structural perf regression fails tier-1 without touching a
chip:

* **collective_permutes / collective_payload_bytes** — the exchange budget
  in bytes, parsed per hop by `utils.hlo_analysis.collective_payloads` and
  cross-checked BYTE-EXACTLY against the traced-jaxpr twin of the same
  program (two IRs, one number — a widened payload cannot hide in either);
* **fusions / kernel_launches** — the fusion structure XLA kept (a defused
  extra kernel shows up as a count bump);
* **flops / bytes_accessed** — the toolchain's own cost analysis (HBM
  traffic proxy: an extra full-field copy moves the needle far beyond the
  band);
* **temp_bytes / argument_bytes / output_bytes** — buffer assignment (peak
  temp allocation catches a materialized intermediate).

Baseline: `analysis/cost_baseline.json` — refreshed ONLY through
``scripts/refresh_cost_baseline.py``, which requires a ``--justify`` note
per changed metric (the same audit contract as `analysis/baseline.json`).
Tolerances are per-metric: structural counts are exact, toolchain-derived
floats carry a small band (`TOLERANCES`).
"""

from __future__ import annotations

import json
import os

from .core import Context, Finding

ANALYZER = "hlo-cost"

#: Versioned cost baseline, next to the analyzers like `baseline.json`.
COST_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "cost_baseline.json"
)

#: Relative tolerance per metric (fraction of the baseline value; ``"*"``
#: is the default).  Structural counts are exact — a single extra
#: collective or kernel launch IS the regression this pass exists to
#: catch; the toolchain-derived floats get a small band for compiler
#: scheduling noise.
TOLERANCES = {
    "flops": 0.02,
    "bytes_accessed": 0.02,
    "temp_bytes": 0.05,
    "*": 0.0,
}


# -- census -------------------------------------------------------------------


def text_census(txt: str) -> dict:
    """The text-derived half of one program's census (pure over HLO text).

    Instruction classification goes through the ONE blessed HLO parser
    (`utils.hlo_analysis`: `parse_computations` + `_op_kind`, the module's
    "one parser ... cannot drift" contract) — a formatting fix landed
    there must not diverge from the counts this baseline gates on.
    ``kernel_launches`` counts ``custom-call`` instructions (Pallas kernels
    on a real backend; the Pallas interpreter lowers kernels to pure HLO,
    where the fusion count carries the structure instead).
    """
    from ..utils.hlo_analysis import (
        _INST_RE,
        _op_kind,
        collective_payloads,
        parse_computations,
    )

    kinds = {"collective-permute": 0, "collective-permute-start": 0,
             "fusion": 0, "custom-call": 0}
    for lines in parse_computations(txt).values():
        for line in lines:
            m = _INST_RE.match(line)
            if m:
                kind = _op_kind(m.group(2))
                if kind in kinds:
                    kinds[kind] += 1
    recs = collective_payloads(txt)
    return {
        "collective_permutes": kinds["collective-permute"]
        + kinds["collective-permute-start"],
        "collective_payload_bytes": sum(r["bytes"] for r in recs),
        "payload_fallbacks": sum(
            1 for r in recs if r.get("payload_fallback")
        ),
        "fusions": kinds["fusion"],
        "kernel_launches": kinds["custom-call"],
    }


def program_census(prog) -> dict:
    """Full metric census of one `ir.CompiledProgram` (text + toolchain
    stats).  Metrics a toolchain does not expose are simply absent — the
    baseline comparison reports them as LOST rather than silently passing."""
    out = text_census(prog.text)
    out.update(prog.memory)
    out.update(prog.cost)
    return out


def cost_census(ctx: Context) -> dict:
    """``{program name: metric census}`` over the compiled matrix."""
    return {
        name: program_census(prog)
        for name, prog in ctx.compiled_programs().items()
    }


# -- the traced-vs-compiled payload cross-check -------------------------------


def payload_crosscheck_findings(ctx: Context) -> list[Finding]:
    """The two-IR payload identity of the porous coalesced exchange.

    The traced jaxpr and the compiled HLO describe the SAME program
    (`ir.EXCHANGE_HLO_PROGRAM` shares its name and config with the traced
    entry), so their per-hop collective payloads must agree byte-exactly —
    hop count, byte multiset, and total.  Any daylight between the two
    means one census lost track of the exchange (and every downstream
    budget built on it is an estimate); a `collective_payloads` raw-sum
    fallback is the same failure declared by the parser itself.
    """
    from ..utils.hlo_analysis import collective_payloads
    from .ir import EXCHANGE_HLO_PROGRAM

    out = []
    entry = next(
        (e for e in ctx.exchange_entries() if e.name == EXCHANGE_HLO_PROGRAM),
        None,
    )
    if entry is None:
        return [
            Finding(
                analyzer=ANALYZER,
                code="crosscheck-broken",
                severity="ERROR",
                message=(
                    f"traced entry {EXCHANGE_HLO_PROGRAM} is missing from "
                    f"the exchange matrix — the payload cross-check has no "
                    f"jaxpr side to compare."
                ),
                symbol=EXCHANGE_HLO_PROGRAM,
                anchor="traced-missing",
            )
        ]
    traced = sorted(
        op.payload_bytes
        for op in entry.collectives()
        if op.kind == "ppermute"
    )
    recs = collective_payloads(ctx.exchange_hlo())
    compiled = sorted(r["bytes"] for r in recs)
    fallbacks = [r for r in recs if r.get("payload_fallback")]
    if fallbacks:
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="payload-fallback",
                severity="ERROR",
                message=(
                    f"{EXCHANGE_HLO_PROGRAM}: {len(fallbacks)} compiled "
                    f"collective payload(s) fell back to a raw operand sum "
                    f"— the byte census is an upper bound, not exact, and "
                    f"the cost baseline cannot gate on it."
                ),
                symbol=EXCHANGE_HLO_PROGRAM,
                anchor="fallback",
            )
        )
    if traced != compiled:
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="payload-mismatch",
                severity="ERROR",
                message=(
                    f"{EXCHANGE_HLO_PROGRAM}: traced jaxpr moves "
                    f"{sum(traced)} payload bytes across {len(traced)} "
                    f"hop(s) {traced} but the optimized HLO moves "
                    f"{sum(compiled)} across {len(compiled)} {compiled} — "
                    f"the compiler re-shaped the exchange (or a census "
                    f"lost track of it)."
                ),
                symbol=EXCHANGE_HLO_PROGRAM,
                anchor="bytes",
            )
        )
    return out


# -- baseline -----------------------------------------------------------------


def load_baseline(path: str = COST_BASELINE) -> dict:
    """The committed cost baseline.  Schema::

        {"version": 1,
         "tolerances": {"flops": 0.02, ..., "*": 0.0},
         "programs": {name: {"metrics": {metric: value},
                             "justifications": {metric: note}}}}

    Every metric value must carry a justification note (written by
    ``scripts/refresh_cost_baseline.py --justify``) — the file is an audit
    trail, not a snapshot dump.
    """
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if data.get("version") != 1:
        raise ValueError(
            f"cost baseline {path}: unsupported version "
            f"{data.get('version')!r} (expected 1)."
        )
    for name, prog in data.get("programs", {}).items():
        just = prog.get("justifications", {})
        missing = [
            m for m in prog.get("metrics", {})
            if not (just.get(m) or "").strip()
        ]
        if missing:
            raise ValueError(
                f"cost baseline {path}: program {name} has unjustified "
                f"metric(s) {missing} — refresh through "
                f"scripts/refresh_cost_baseline.py --justify."
            )
    return data


def _tolerance(metric: str, baseline: dict) -> float:
    tols = baseline.get("tolerances", TOLERANCES)
    return float(tols.get(metric, tols.get("*", 0.0)))


def within_band(old: float, new: float, tol: float) -> bool:
    return abs(float(new) - float(old)) <= tol * max(abs(float(old)), 1.0)


def compare_census(census: dict, baseline: dict) -> list[Finding]:
    """Findings of one census-vs-baseline comparison (empty = clean).

    Deviations in EITHER direction fail: an improvement outside the band is
    real news that belongs in the baseline (with a justification), not a
    silent drift that widens the next regression's headroom.
    """
    out = []
    programs = baseline.get("programs", {})
    for name, prog in programs.items():
        got = census.get(name)
        if got is None:
            out.append(
                Finding(
                    analyzer=ANALYZER,
                    code="program-missing",
                    severity="ERROR",
                    message=(
                        f"baselined program {name} is missing from the "
                        f"compiled matrix — the cost gate lost a config."
                    ),
                    symbol=name,
                    anchor="missing",
                )
            )
            continue
        for metric, old in prog.get("metrics", {}).items():
            if metric not in got:
                out.append(
                    Finding(
                        analyzer=ANALYZER,
                        code="metric-lost",
                        severity="ERROR",
                        message=(
                            f"{name}: baselined metric {metric} is absent "
                            f"from the fresh census — the toolchain "
                            f"stopped reporting it (gate has a blind spot)."
                        ),
                        symbol=name,
                        anchor=metric,
                    )
                )
                continue
            new = got[metric]
            tol = _tolerance(metric, baseline)
            if not within_band(old, new, tol):
                direction = "regressed" if new > old else "improved"
                if metric in ("collective_permutes", "fusions",
                              "kernel_launches"):
                    hint = (
                        "an extra collective/kernel usually means a "
                        "defused or re-serialized structure"
                    )
                else:
                    hint = "an extra copy or materialized intermediate"
                out.append(
                    Finding(
                        analyzer=ANALYZER,
                        code="cost-regression",
                        severity="ERROR",
                        message=(
                            f"{name}: {metric} {direction} "
                            f"{old} -> {new} (tolerance "
                            f"{tol:.0%} of baseline; {hint}).  If the "
                            f"change is intentional, refresh via "
                            f"scripts/refresh_cost_baseline.py --justify."
                        ),
                        symbol=name,
                        anchor=metric,
                    )
                )
        for metric in sorted(set(got) - set(prog.get("metrics", {}))):
            out.append(
                Finding(
                    analyzer=ANALYZER,
                    code="metric-unbaselined",
                    severity="WARNING",
                    message=(
                        f"{name}: census metric {metric}={got[metric]} has "
                        f"no baseline entry — refresh to start gating it."
                    ),
                    symbol=name,
                    anchor=metric,
                )
            )
    for name in sorted(set(census) - set(programs)):
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="program-unbaselined",
                severity="WARNING",
                message=(
                    f"compiled program {name} has no baseline entry — "
                    f"refresh scripts/refresh_cost_baseline.py to gate it."
                ),
                symbol=name,
                anchor="unbaselined",
            )
        )
    return out


def run(ctx: Context) -> list[Finding]:
    out = payload_crosscheck_findings(ctx)
    if not os.path.exists(COST_BASELINE):
        out.append(
            Finding(
                analyzer=ANALYZER,
                code="baseline-missing",
                severity="ERROR",
                message=(
                    f"cost baseline {COST_BASELINE} does not exist — run "
                    f"scripts/refresh_cost_baseline.py to create it."
                ),
                symbol="cost_baseline.json",
                anchor="missing",
            )
        )
        return out
    return out + compare_census(cost_census(ctx), load_baseline())
