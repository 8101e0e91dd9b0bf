"""Optimized-HLO dataflow analysis for the overlap-evidence checks.

Shared by `tests/test_stencil_overlap.py` (8-device CPU mesh, differential
control) and `scripts/verify_tpu.py` (AOT TPU topology program): one parser,
one transitive-closure walk, one fusion-size heuristic — so a fix to the
analyzer cannot drift between the test and the hardware check.

The schedulability criterion: a collective-permute whose transitive operand
closure contains the full-block interior fusion can only start AFTER the
interior finishes (a barrier); one whose closure holds only slab-sized ops is
free to fly while the interior computes — the structural freedom
`hide_communication` exists to create (the reference's analogous mechanism is
its max-priority streams, `/root/reference/src/update_halo.jl:424`).
"""

from __future__ import annotations

import re

# Instruction name + everything after '='.  The type is NOT captured as one
# token: TPU HLO tuple types contain spaces and nested parens
# (`(f32[1,16,16]{1,0,2:T(1,128)S(1)}, u32[]{:S(2)})`), so the op kind and
# operand refs are extracted from the remainder instead.
_INST_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")


def parse_computations(txt: str) -> dict[str, list[str]]:
    """Split optimized HLO text into {computation_name: [instruction lines]}."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in txt.splitlines():
        if line.rstrip().endswith("{") and "(" in line:
            cur = line.split("(")[0].strip()
            cur = cur[len("ENTRY "):] if cur.startswith("ENTRY ") else cur
            cur = cur.lstrip("%")
            comps[cur] = []
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def while_body_copies(txt: str, elems) -> list[tuple[str, str]]:
    """``(body computation, instruction)`` of every ``copy``/``copy-start``
    inside a while-loop body whose largest array has ``elems`` elements:
    at least ``elems`` where it is an int, one of the counts where it is a
    collection (the loop carry's arrays, padded layouts included, which may
    be larger or smaller than a field).

    A while loop keeps each carry element in one buffer; a body whose last
    writer of a carry cannot take that buffer pays a whole-array copy per
    iteration (the fused group schedule's carry copy,
    `models._fused.run_group_schedule`).
    """
    if isinstance(elems, int):
        hit = lambda n: n >= elems
    else:
        counts = frozenset(elems)
        hit = counts.__contains__
    comps = parse_computations(txt)
    bodies = {
        b for lines in comps.values() for l in lines
        for b in re.findall(r"\bbody=%?([\w\.\-]+)", l)
    }
    out = []
    for b in sorted(bodies):
        for l in comps.get(b, ()):
            m = _INST_RE.match(l)
            if not m:
                continue
            name, rest = m.groups()
            op = re.search(r"\s(copy|copy-start)\(", rest)
            if op and hit(_out_elems(rest[:op.start()])):
                out.append((b, name))
    return out


def _out_elems(typ: str) -> int:
    """Largest array size in an HLO type string (handles tuple types)."""
    best = 0
    for shp in re.findall(r"\[([\d,]*)\]", typ):
        if shp:
            p = 1
            for x in shp.split(","):
                p *= int(x)
            best = max(best, p)
    return best


def _op_kind(rest: str) -> str:
    """Classify the instruction from the text after '='."""
    if "collective-permute-start(" in rest:
        return "collective-permute-start"
    if "collective-permute-done(" in rest:
        return "collective-permute-done"
    if re.search(r"\bcollective-permute\(", rest):
        return "collective-permute"
    if re.search(r"\bfusion\(", rest):
        return "fusion"
    if re.search(r"\bcustom-call\(", rest):
        return "custom-call"
    return "other"


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "u64": 8, "s64": 8, "u32": 4, "s32": 4, "u16": 2, "s16": 2,
    "u8": 1, "s8": 1, "pred": 1,
}
_ARRAY_RE = re.compile(r"(f64|f32|bf16|f16|u64|s64|u32|s32|u16|s16|u8|s8|pred)\[([\d,]*)\]")


def collective_payloads(txt: str) -> list[dict]:
    """Per-hop payloads of every collective-permute in an optimized program.

    Returns one record per ``collective-permute``/``collective-permute-start``
    instruction: ``{"shape", "dtype", "bytes"}`` — every non-scalar array in
    the instruction's (possibly tuple) type is payload (a combined /
    multi-operand permute moves all of them in one hop; scalars are the
    async-start ops' u32 context, not payload).  The per-hop *byte* count is
    what a weak-scaling budget needs: payload ÷ link bandwidth + hop latency
    vs the measured step time.

    Async-start tuples list each moved buffer TWICE — ``(aliased
    operands..., results..., contexts...)`` — so the RESULT half is counted,
    verified by matching the two halves elementwise (ADVICE r5 low #3: the
    old blind ``total //= 2`` could silently skew the budget's per-hop
    bytes on any tuple shape drift).  A start op whose array list does not
    split into two identical halves falls back to the raw sum and flags it
    (``"payload_fallback": "raw-sum"``) so a budget consumer can see the
    number is an upper bound, not silently half-wrong.
    """
    out = []
    for lines in parse_computations(txt).values():
        for l in lines:
            m = _INST_RE.match(l)
            if not m:
                continue
            _, rest = m.groups()
            kind = _op_kind(rest)
            if kind not in ("collective-permute", "collective-permute-start"):
                continue
            head = rest.split("collective-permute")[0]
            arrays = []  # (type string, bytes) per non-scalar array
            for dt, shp in _ARRAY_RE.findall(head):
                if not shp:
                    continue
                elems = 1
                for x in shp.split(","):
                    elems *= int(x)
                arrays.append((f"{dt}[{shp}]", elems * _DTYPE_BYTES[dt]))
            if not arrays:
                continue
            fallback = None
            if kind == "collective-permute-start":
                half = len(arrays) // 2
                if len(arrays) % 2 == 0 and arrays[:half] == arrays[half:]:
                    arrays = arrays[half:]  # the results half
                else:
                    fallback = "raw-sum"
            rec = {
                "shape": ",".join(a[0] for a in arrays),
                "dtype": arrays[0][0].split("[")[0],
                "bytes": sum(a[1] for a in arrays),
            }
            if fallback:
                rec["payload_fallback"] = fallback
            out.append(rec)
    return out


def pipelined_overlap_evidence(txt: str) -> dict:
    """Structural evidence that a program schedules kernel launches across
    its collectives — the pipelined group schedule's HLO check.

    For every computation holding both collective-permutes and
    custom-calls (the Pallas kernel launches are ``custom-call``s in the
    optimized program), count the (collective, custom-call) pairs with NO
    transitive dependency in either direction: XLA's scheduler is free to
    run such a pair concurrently.  The serialized schedule has none (every
    kernel launch feeds or consumes every group-boundary exchange); the
    pipelined schedule's interior passes are exactly the launches built to
    be independent of the in-flight permutes.

    Returns ``{"n_collectives", "n_custom_calls", "independent_pairs",
    "overlappable_collectives"}`` (the last: collectives with at least one
    independent kernel launch).
    """
    n_cp, n_cc, pairs, overlappable = 0, 0, 0, 0
    for lines in parse_computations(txt).values():
        if not any("collective-permute" in l for l in lines):
            continue
        insts: dict[str, tuple[str, str, list[str]]] = {}
        for l in lines:
            m = _INST_RE.match(l)
            if m:
                name, rest = m.groups()
                insts[name] = (_op_kind(rest), rest, re.findall(r"%([\w\.\-]+)", rest))

        def closure(n):
            seen: set = set()
            stack = [n]
            while stack:
                for o in insts.get(stack.pop(), (None, None, []))[2]:
                    if o not in seen:
                        seen.add(o)
                        stack.append(o)
            return seen

        cps = [
            n
            for n, (op, _, _) in insts.items()
            if op in ("collective-permute", "collective-permute-start")
        ]
        ccs = [n for n, (op, _, _) in insts.items() if op == "custom-call"]
        if not ccs:
            continue
        n_cp += len(cps)
        n_cc += len(ccs)
        cc_closures = {c: closure(c) for c in ccs}
        # An async collective is "independent" of a launch when neither its
        # start nor its done reaches the launch (and vice versa); dones are
        # found as consumers via the start's name appearing in closures.
        for cp in cps:
            cp_clo = closure(cp)
            free = [
                cc
                for cc in ccs
                if cc not in cp_clo and cp not in cc_closures[cc]
            ]
            pairs += len(free)
            if free:
                overlappable += 1
    return {
        "n_collectives": n_cp,
        "n_custom_calls": n_cc,
        "independent_pairs": pairs,
        "overlappable_collectives": overlappable,
    }


#: HLO op-NAME vocabulary for device-timeline classification
#: (`utils.profiling`): profiler trace events carry instruction NAMES
#: (``collective-permute-start.3``, ``pad_add_fusion.1``, ``copy.17``), not
#: instruction text, so this is the name-based sibling of `_op_kind` — one
#: blessed vocabulary for both the HLO-text analyzers and the trace parser.
#: "collective" moves bytes over the fabric; "kernel" is real compute
#: (fusions, custom-calls — the Pallas launches — and the standalone
#: heavyweights); everything else is "glue": copies, slices, control flow,
#: layout shuffling — the cadence overhead per-op attribution exists to
#: localize.
COLLECTIVE_OP_TOKENS = (
    "collective-permute",
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-broadcast",
    # Instruction names XLA derives from the JAX primitive (the profiler's
    # op names on the CPU backend: ``ppermute.18``).
    "ppermute",
    "psum",
    "all_gather",
    "all_to_all",
    "reduce_scatter",
)

KERNEL_OP_TOKENS = ("fusion", "custom-call", "convolution", "dot")


def classify_op_name(name: str) -> str:
    """Classify one HLO instruction NAME as ``collective`` | ``kernel`` |
    ``glue``.

    Matches the vocabulary tokens as substrings of the name with any
    trailing ``.N`` suffix intact (XLA embeds the op kind in generated
    names: ``select_dynamic-update-slice_fusion.1`` is a fusion,
    ``collective-permute-start.3`` a collective).  A name holding both a
    collective and a kernel token classifies as collective — a fused
    collective still occupies the fabric.
    """
    low = name.lower()
    for tok in COLLECTIVE_OP_TOKENS:
        if tok in low:
            return "collective"
    for tok in KERNEL_OP_TOKENS:
        if tok in low:
            return "kernel"
    return "glue"


def collective_waits(txt: str, big_elems: int) -> tuple[int, list[bool], int]:
    """Analyze every HLO computation holding collective-permutes.

    Returns ``(n_collectives, waits, n_async)`` where ``waits[i]`` says
    whether collective ``i`` (sync ``collective-permute`` or async
    ``collective-permute-start``) transitively depends on a fusion with
    >= ``big_elems`` output elements, and ``n_async`` counts the async
    start ops (TPU backend; the CPU backend emits sync collectives only).
    Closures are computed within each computation — the collectives and the
    interior fusion always share one (the SPMD entry or a loop body).
    """
    n_total, waits_all, n_async = 0, [], 0
    for lines in parse_computations(txt).values():
        if not any("collective-permute" in l for l in lines):
            continue
        insts: dict[str, tuple[str, str, list[str]]] = {}
        for l in lines:
            m = _INST_RE.match(l)
            if m:
                name, rest = m.groups()
                insts[name] = (_op_kind(rest), rest, re.findall(r"%([\w\.\-]+)", rest))

        big = {
            n
            for n, (op, rest, _) in insts.items()
            if op == "fusion" and _out_elems(rest) >= big_elems
        }

        def closure(n, seen):
            stack = [n]
            while stack:  # iterative: deep programs exceed the recursion limit
                for o in insts.get(stack.pop(), (None, None, []))[2]:
                    if o not in seen:
                        seen.add(o)
                        stack.append(o)
            return seen

        cps = [
            n
            for n, (op, _, _) in insts.items()
            if op in ("collective-permute", "collective-permute-start")
        ]
        n_total += len(cps)
        waits_all += [bool(closure(c, set()) & big) for c in cps]
        n_async += sum(
            1 for op, _, _ in insts.values() if op == "collective-permute-start"
        )
    return n_total, waits_all, n_async
