"""Grad-soundness analyzer (``grad-soundness``) — zero-gradient sinks.

PR 5's bug class: `lax.bitcast_convert_type` has NO tangent rule — JAX
treats it like an integer-valued op and produces a zero cotangent — so a
bitcast-packed transport without a registered custom VJP makes ``jax.grad``
silently drop every cotangent that crosses a block boundary.  Nothing
crashes; the gradient is just wrong, and only a finite-difference oracle
notices.  This pass makes that class a static invariant along two legs:

1. **Dropper scan** (`dropper_findings`) — walk the traced jaxpr of every
   entry point in the config matrix and flag cotangent-dropping primitives
   on the tangent path: ``bitcast_convert_type`` and float→integer
   ``convert_element_type`` are CRITICAL, ``stop_gradient`` is a WARNING
   (often intentional, never invisible).  "On the tangent path" = at least
   one floating operand derived from the entry's differentiable inputs AND
   an output that feeds the entry's outputs.  Sub-programs under a
   ``custom_vjp``/``custom_jvp`` envelope are exempt — a registered VJP is
   exactly the documented fix (`_packed_transport`, `fused_with_xla_grad`)
   — and ``pallas_call`` bodies are kernel-internal, reached only through
   such envelopes.

2. **Backward-collective census** (`census_findings`) — trace the VJP of
   every differentiable entry point (`ir.trace_grad_entries`: the coalesced
   exchange per model + each fused cadence) and require the VJP program to
   issue MORE collectives than its primal: a cross-boundary cotangent must
   ride collectives backward, so a VJP trace with no backward collectives
   has dropped its cross-rank gradient even if no known dropper primitive
   was spotted.  This leg is detector-of-last-resort: it catches droppers
   the scan's list does not know about yet.

ROADMAP item 4 (adjoint inversion) builds directly on the gradient path;
this pass is the contract it builds on.
"""

from __future__ import annotations

import os

import numpy as np

from .core import Context, Finding

ANALYZER = "grad-soundness"

#: Envelope primitives whose sub-programs carry a REGISTERED derivative —
#: their internals may legally use non-differentiable transports.
_PROTECTED = ("custom_vjp", "custom_jvp")

#: Cotangent-dropping primitives and their severities.  ``stop_gradient``
#: warns rather than fails: cutting a gradient is sometimes the point, but
#: it must never be invisible on a production tangent path.
_DROPPERS = {
    "bitcast_convert_type": "CRITICAL",
    "stop_gradient": "WARNING",
}


def _inexact(v) -> bool:
    dt = getattr(getattr(v, "aval", None), "dtype", None)
    return dt is not None and np.issubdtype(dt, np.inexact)


def _eqn_location(eqn) -> tuple[str, int]:
    """Best-effort ``(file, line)`` of one equation (private API).

    Paths under the repo come back REPO-RELATIVE — the fingerprint hashes
    the path, so an absolute checkout prefix would pin baselines (and the
    SARIF ``artifactLocation.uri``) to one machine.  Foreign paths
    (site-packages) stay as-is: they are diagnostics, not suppressables.
    """
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            path = str(frame.file_name)
            if os.path.isabs(path) and path.startswith(repo + os.sep):
                path = os.path.relpath(path, repo)
            return path, int(frame.start_line)
    except Exception:  # noqa: BLE001 — source info is best-effort decoration
        pass
    return "", 0


def _is_float_to_int_cast(eqn) -> bool:
    if eqn.primitive.name != "convert_element_type":
        return False
    new = eqn.params.get("new_dtype")
    try:
        drops = np.issubdtype(np.dtype(new), np.integer) or np.issubdtype(
            np.dtype(new), np.bool_
        )
    except Exception:  # noqa: BLE001 — exotic target dtype: not our class
        return False
    return drops and any(_inexact(v) for v in eqn.invars)


def dropper_findings(jaxpr, entry_name: str) -> list[Finding]:
    """Cotangent-dropping primitives on the tangent path of one traced
    entry (empty = clean).  Scopes are analyzed independently and
    conservatively: within each (sub-)jaxpr, a variable is tainted when it
    derives from a floating input of that scope, and feeding when it
    reaches that scope's outputs — over-approximate across nesting, which
    errs toward reporting (the finding names file:line to triage)."""
    out = []
    _scan_scope(jaxpr, entry_name, (), out)
    return out


def _scan_scope(jaxpr, entry_name: str, path: tuple, out: list) -> None:
    tainted = {id(v) for v in jaxpr.invars if _inexact(v)}
    for eqn in jaxpr.eqns:
        if any(id(v) in tainted for v in eqn.invars):
            tainted.update(id(v) for v in eqn.outvars)
    feeding = {id(v) for v in jaxpr.outvars}
    for eqn in reversed(jaxpr.eqns):
        if any(id(v) in feeding for v in eqn.outvars):
            feeding.update(id(v) for v in eqn.invars)

    from .ir import _sub_jaxprs

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(p in name for p in _PROTECTED):
            continue  # registered derivative — the documented fix
        if name == "pallas_call":
            continue  # kernel-internal; reached via a custom-VJP envelope
        severity = _DROPPERS.get(name)
        if severity is None and _is_float_to_int_cast(eqn):
            severity = "CRITICAL"
        if severity is not None:
            on_path = any(
                _inexact(v) and id(v) in tainted for v in eqn.invars
            ) and any(id(v) in feeding for v in eqn.outvars)
            if on_path:
                fpath, line = _eqn_location(eqn)
                dtypes = ",".join(
                    str(getattr(getattr(v, "aval", None), "dtype", "?"))
                    for v in eqn.invars
                )
                out.append(
                    Finding(
                        analyzer=ANALYZER,
                        code="cotangent-dropper",
                        severity=severity,
                        message=(
                            f"{entry_name}: `{name}` on the tangent path "
                            f"(operands {dtypes}"
                            + (f", under {'/'.join(path)}" if path else "")
                            + ") has no derivative — jax.grad will "
                            "silently produce ZERO cotangents through it "
                            "(the PR-5 coalesced-transport class)."
                        ),
                        path=fpath,
                        line=line,
                        symbol=entry_name,
                        anchor=f"{name}[{dtypes}]",
                        fix_hint=(
                            "wrap the transport in jax.custom_vjp and "
                            "differentiate a value-identical per-field "
                            "twin (see ops/halo.py::_packed_transport), "
                            "or keep the op off the differentiable path"
                        ),
                    )
                )
            continue
        for _, sub in _sub_jaxprs(eqn):
            _scan_scope(sub, entry_name, path + (name,), out)


# -- backward-collective census ----------------------------------------------


def census_findings(grad_entries) -> list[Finding]:
    """The VJP-trace collective census (empty = clean).

    Every entry in the matrix communicates by construction, so its primal
    count must be positive (otherwise the census itself went blind) and
    its VJP trace — forward replay plus backward pass — must issue
    STRICTLY MORE collectives than the primal: the surplus is the backward
    transport of cross-boundary cotangents.
    """
    out = []
    for entry in grad_entries:
        grad_n, primal_n = entry.collective_counts()
        if primal_n == 0:
            out.append(
                Finding(
                    analyzer=ANALYZER,
                    code="census-broken",
                    severity="ERROR",
                    message=(
                        f"{entry.name}: primal trace shows ZERO collectives "
                        f"— the grad census has nothing to compare against "
                        f"(config no longer communicates?)."
                    ),
                    symbol=entry.name,
                    anchor="primal0",
                )
            )
            continue
        if grad_n <= primal_n:
            out.append(
                Finding(
                    analyzer=ANALYZER,
                    code="cotangent-sink",
                    severity="CRITICAL",
                    message=(
                        f"{entry.name}: the VJP trace issues {grad_n} "
                        f"collective(s) vs {primal_n} in the primal — no "
                        f"backward collectives means cross-boundary "
                        f"cotangents are NOT transported and jax.grad "
                        f"silently zeroes every gradient that crosses a "
                        f"rank boundary."
                    ),
                    symbol=entry.name,
                    anchor=f"{grad_n}<={primal_n}",
                    fix_hint=(
                        "a primitive on the tangent path lost its "
                        "derivative; register a custom VJP that "
                        "differentiates a value-identical transport "
                        "(ops/halo.py::_packed_transport is the pattern)"
                    ),
                )
            )
    return out


def run(ctx: Context) -> list[Finding]:
    out = []
    for entry in list(ctx.exchange_entries()) + list(ctx.cadence_entries()):
        out.extend(dropper_findings(entry.jaxpr, entry.name))
    out.extend(census_findings(ctx.grad_entries()))
    return out
