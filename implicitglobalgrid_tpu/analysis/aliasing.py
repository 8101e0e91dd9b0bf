"""Pallas aliasing / buffer-donation lint.

The fused kernels' carry steps are in-place: ``input_output_aliases`` tells
XLA (and the TPU Pallas interpreter, which honors it — the
contract the pipelined ring/mid combine relies on) that an input buffer IS
an output buffer.  A wrong declaration is silent corruption, not an error:
XLA happily reuses the buffer while the kernel still reads it.  Donation
(``donate_argnums``) has the same failure shape — a donated buffer that no
output can reuse is a silent perf lie, and a reused one that the caller
still holds is corruption.

Checks, over both IRs:

* **AST** — literal ``input_output_aliases`` dicts must map non-negative
  int constants injectively (a duplicated output index would alias two
  inputs onto one buffer); literal ``donate_argnums`` must be non-negative
  int constants.
* **traced** — every ``pallas_call`` equation in the cadence matrix carries
  its RESOLVED alias pairs; each pair must be in range and the aliased
  operand/result avals must match exactly (shape and dtype — the in-place
  contract).  Every ``pjit`` equation's donated operands must match some
  output aval, else the donation can never be honored (XLA drops it with a
  warning at best).
"""

from __future__ import annotations

import ast

from .core import Context, Finding
from .ir import iter_eqns

ANALYZER = "pallas-aliasing"


# -- shared validation core (unit-tested directly) ----------------------------


def validate_alias_pairs(pairs, in_avals, out_avals) -> list[str]:
    """Human-readable problems of resolved (input, output) alias pairs
    against operand/result avals (``(shape, dtype)`` tuples or jax avals).
    Empty list = valid."""

    def sig(a):
        return (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else a

    probs = []
    seen_in: set[int] = set()
    seen_out: set[int] = set()
    for i, o in pairs:
        if not (0 <= i < len(in_avals)):
            probs.append(f"alias input index {i} out of range "
                         f"(have {len(in_avals)} operands)")
            continue
        if not (0 <= o < len(out_avals)):
            probs.append(f"alias output index {o} out of range "
                         f"(have {len(out_avals)} results)")
            continue
        if i in seen_in:
            probs.append(f"input {i} aliased to two outputs")
        if o in seen_out:
            probs.append(f"output {o} aliased from two inputs")
        seen_in.add(i)
        seen_out.add(o)
        si, so = sig(in_avals[i]), sig(out_avals[o])
        if si != so:
            probs.append(
                f"alias pair ({i}, {o}) mismatches: operand {si} vs "
                f"result {so} — an in-place buffer must keep shape+dtype"
            )
    return probs


# -- AST pass -----------------------------------------------------------------


def _literal_alias_findings(rel: str, call: ast.Call, qual: str) -> list:
    out = []
    for kw in call.keywords:
        if kw.arg == "input_output_aliases" and isinstance(kw.value, ast.Dict):
            keys, vals = [], []
            ok = True
            for k, v in zip(kw.value.keys, kw.value.values):
                if not (
                    isinstance(k, ast.Constant) and isinstance(k.value, int)
                    and isinstance(v, ast.Constant)
                    and isinstance(v.value, int)
                ):
                    ok = False  # computed entries: the traced pass covers it
                    break
                keys.append(k.value)
                vals.append(v.value)
            if not ok:
                continue
            probs = []
            if any(x < 0 for x in keys + vals):
                probs.append("negative index")
            if len(set(keys)) != len(keys):
                probs.append(
                    "duplicate input index (later dict entry silently wins)"
                )
            if len(set(vals)) != len(vals):
                probs.append("duplicate output index (two inputs on one "
                             "output buffer)")
            for p in probs:
                out.append(
                    Finding(
                        analyzer=ANALYZER,
                        code="bad-alias-literal",
                        severity="ERROR",
                        message=(
                            f"pallas_call input_output_aliases "
                            f"{{{', '.join(f'{k}: {v}' for k, v in zip(keys, vals))}}}: {p}."
                        ),
                        path=rel,
                        line=kw.value.lineno,
                        symbol=qual,
                        anchor=f"aliases:{sorted(zip(keys, vals))}",
                    )
                )
        if kw.arg == "donate_argnums":
            elts = (
                kw.value.elts
                if isinstance(kw.value, (ast.Tuple, ast.List))
                else []
            )
            for e in elts:
                # -1 parses as UnaryOp(USub, Constant(1)), not Constant(-1)
                if (
                    isinstance(e, ast.UnaryOp)
                    and isinstance(e.op, ast.USub)
                    and isinstance(e.operand, ast.Constant)
                    and isinstance(e.operand.value, int)
                ):
                    e = ast.copy_location(
                        ast.Constant(value=-e.operand.value), e
                    )
                if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                        and e.value < 0:
                    out.append(
                        Finding(
                            analyzer=ANALYZER,
                            code="bad-donate-literal",
                            severity="ERROR",
                            message=(
                                f"donate_argnums contains {e.value}: "
                                f"donation indices are positional argument "
                                f"numbers and must be >= 0."
                            ),
                            path=rel,
                            line=e.lineno,
                            symbol=qual,
                            anchor=f"donate:{e.value}",
                        )
                    )
    return out


def ast_findings(ctx: Context) -> list:
    out = []
    for rel, (_src, tree) in ctx.module_asts().items():
        stack: list[str] = []

        class V(ast.NodeVisitor):
            def _f(self, node):
                stack.append(node.name)
                self.generic_visit(node)
                stack.pop()

            visit_FunctionDef = _f
            visit_AsyncFunctionDef = _f

            def visit_Call(self, node: ast.Call):
                name = node.func.attr if isinstance(
                    node.func, ast.Attribute
                ) else getattr(node.func, "id", "")
                if name in ("pallas_call", "jit", "stencil"):
                    out.extend(
                        _literal_alias_findings(
                            rel, node, ".".join(stack) or "<module>"
                        )
                    )
                self.generic_visit(node)

        V().visit(tree)
    return out


# -- traced pass --------------------------------------------------------------


def traced_findings(ctx: Context) -> list:
    out = []
    for entry in ctx.cadence_entries():
        for eqn, _path in iter_eqns(entry.jaxpr):
            if eqn.primitive.name == "pallas_call":
                pairs = [
                    tuple(p) for p in eqn.params.get(
                        "input_output_aliases", ()
                    )
                ]
                probs = validate_alias_pairs(
                    pairs,
                    [v.aval for v in eqn.invars],
                    [v.aval for v in eqn.outvars],
                )
                for p in probs:
                    out.append(
                        Finding(
                            analyzer=ANALYZER,
                            code="bad-alias-traced",
                            severity="CRITICAL",
                            message=(
                                f"entry `{entry.name}`: pallas_call "
                                f"aliases {pairs}: {p}."
                            ),
                            symbol=entry.name,
                            anchor=f"{pairs}:{p[:32]}",
                            fix_hint=(
                                "fix the input_output_aliases mapping in "
                                "the kernel builder — the aliased operand "
                                "must be the same logical buffer as the "
                                "result."
                            ),
                        )
                    )
            elif eqn.primitive.name == "jit":
                donated = eqn.params.get("donated_invars", ())
                if not any(donated):
                    continue
                out_sigs = {
                    (tuple(v.aval.shape), str(v.aval.dtype))
                    for v in eqn.outvars
                    if hasattr(v.aval, "shape")
                }
                for iv, don in zip(eqn.invars, donated):
                    if not don or not hasattr(iv.aval, "shape"):
                        continue
                    sig = (tuple(iv.aval.shape), str(iv.aval.dtype))
                    if sig not in out_sigs:
                        out.append(
                            Finding(
                                analyzer=ANALYZER,
                                code="unusable-donation",
                                severity="WARNING",
                                message=(
                                    f"entry `{entry.name}`: a donated "
                                    f"operand {sig} matches no result of "
                                    f"its jit — the buffer can never be "
                                    f"reused; the donation is a no-op and "
                                    f"the caller still loses the array."
                                ),
                                symbol=entry.name,
                                anchor=f"donate:{sig}",
                            )
                        )
    return out


def run(ctx: Context) -> list:
    return ast_findings(ctx) + traced_findings(ctx)
