"""From a profiler trace of the window to per-layer numbers.

Adapted from `implicitglobalgrid_tpu/utils/profiling.py` (device ops,
interval unions, scope attribution), not imported from it: the yardstick
lives with the benchmark.

A trace is first flattened to `Events`: the device ops of each chip (name,
start, end, class) and the host annotations of the thread that ran the
window.  Each device op falls in one class:

* ``kernel``   -- a Mosaic kernel launch (custom call ``tpu_custom_call``);
* ``exchange`` -- an op under the ``igg_halo_exchange`` / ``igg_slab_exchange_*``
  scopes, or a collective; with the collectives' transfers in flight (the
  ``Async XLA Ops`` line), which may overlap a kernel;
* ``glue``     -- any other op (copies, slices, pads, loop control).

On the v5e a TPU op event is named by its HLO instruction's text without
metadata (``%copy.13 = f32[...] copy(...)``): the opcode and custom-call
target come from that text, the ``op_name`` scope path from the same
instruction in the compiled program that the window drives.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

EXCHANGE_SCOPES = ("igg_halo_exchange", "igg_slab_exchange")
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather", "all-to-all",
               "reduce-scatter", "collective-broadcast", "send", "recv")
#: ops that only enclose other ops on the timeline
CONTAINERS = ("while", "conditional", "call")

_INST = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def _opcode(rest: str) -> str:
    """Opcode of one HLO instruction's right-hand side (``type opcode(...)``)."""
    depth, i = 0, 0
    # skip the result type, which may be a tuple with nested parentheses
    while i < len(rest):
        ch = rest[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
        i += 1
    m = re.match(r"\s*([a-z][\w\-]*)\(", rest[i:])
    return m.group(1) if m else ""


def _inst_info(rest: str) -> tuple:
    t = _TARGET.search(rest)
    s = _OP_NAME.search(rest)
    return (_opcode(rest), t.group(1) if t else "", s.group(1) if s else "")


def hlo_index(text: str) -> dict:
    """``{instruction name: (opcode, custom-call target, op_name)}`` of an
    optimized HLO module's text (`Compiled.as_text()`)."""
    out = {}
    for line in text.splitlines():
        m = _INST.match(line)
        if m:
            out[m.group(1)] = _inst_info(m.group(2))
    return out


def classify(info: tuple) -> str:
    """``kernel`` | ``exchange`` | ``glue`` | ``container`` of one device op
    from its ``(opcode, custom-call target, op_name)``."""
    opcode, target, op_name = info
    if target == "tpu_custom_call":
        return "kernel"
    if opcode in CONTAINERS:
        return "container"
    if any(s in op_name for s in EXCHANGE_SCOPES) or any(
            opcode.startswith(c) for c in COLLECTIVES):
        return "exchange"
    return "glue"


@dataclasses.dataclass
class Events:
    """Flattened trace: ``ops[device] = [(instruction, start_ns, end_ns)]``
    executed by the core, ``in_flight[device]`` the same for async
    collectives from their start to their done,
    ``insts[instruction] = (opcode, custom-call target, op_name)``,
    ``host = [(name, start_ns, end_ns)]`` on the window's thread, and the
    window ``(start_ns, end_ns)`` on the same clock."""

    ops: dict
    insts: dict
    host: list
    window: tuple
    in_flight: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "in_flight": {str(k): v for k, v in self.in_flight.items()},
                "insts": self.insts, "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, doc: dict) -> "Events":
        dev = lambda d: {int(k): [tuple(e) for e in v] for k, v in d.items()}
        return cls(dev(doc["ops"]), {k: tuple(v) for k, v in doc["insts"].items()},
                   [tuple(e) for e in doc["host"]], tuple(doc["window"]),
                   dev(doc.get("in_flight", {})))


WINDOW = "igg_bench.window"
#: the device line of the ops the core executes, and the line of async
#: transfers in flight between a ``*-start`` and its ``*-done`` (of which
#: only the collectives are kept: the exchange's in-flight time)
OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"


def from_xplane(path: str, hlo_text: str) -> Events:
    """Read one ``.xplane.pb``: the op lines of every TPU plane, and the host
    thread that holds the ``igg_bench.window`` annotation.  A TPU op event
    is named by its HLO instruction's text without metadata; its scope path
    (``op_name``) comes from the compiled program's text."""
    from jax.profiler import ProfileData

    index = hlo_index(hlo_text)
    pd = ProfileData.from_file(path)
    ops, in_flight, insts, host, window = {}, {}, {}, [], None
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name in (OP_LINE, ASYNC_LINE):
                dev = (ops if line.name == OP_LINE else in_flight).setdefault(
                    int(m.group(1)), [])
                for e in line.events:
                    im = _INST.match(e.name)
                    inst, rest = im.groups() if im else (e.name, "")
                    if inst not in insts:
                        info = _inst_info(rest)
                        known = index.get(inst, ("", "", ""))
                        insts[inst] = (info[0] or known[0], info[1] or known[1],
                                       known[2] or info[2])
                    if line.name == ASYNC_LINE and classify(insts[inst]) != "exchange":
                        continue
                    dev.append((inst, e.start_ns, e.start_ns + e.duration_ns))
            elif not m and plane.name.startswith("/host"):
                evs = list(line.events)
                win = [e for e in evs if e.name == WINDOW]
                if win:
                    window = (win[0].start_ns, win[0].start_ns + win[0].duration_ns)
                    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in evs if e.name != WINDOW]
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} annotation on any host thread")
    return Events(ops, insts, host, window, in_flight)


def find_xplane(logdir: str) -> str:
    hits = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise ValueError(f"{logdir}: the profiler wrote no .xplane.pb")
    return hits[-1]


# -- interval arithmetic ------------------------------------------------------


def union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(u) -> float:
    return float(sum(b - a for a, b in u))


def minus(u1, u2) -> float:
    """Length of union ``u1`` not covered by union ``u2``."""
    covered, j = 0.0, 0
    for a, b in u1:
        while j < len(u2) and u2[j][1] <= a:
            j += 1
        k = j
        while k < len(u2) and u2[k][0] < b:
            covered += min(b, u2[k][1]) - max(a, u2[k][0])
            k += 1
    return length(u1) - covered


@dataclasses.dataclass
class Reduced:
    """Per-layer totals of one traced window, seconds averaged over chips."""

    window_s: float
    busy_s: float
    idle_share_max: float
    kernel_s: float
    exchange_s: float
    exchange_exposed_s: float
    glue_s: float
    top_ops: list
    idle_gaps: list
    n_kernel_ops: int

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops, "idle_gaps": self.idle_gaps}


def reduce(ev: Events, ndev: int) -> Reduced:
    lo, hi = ev.window
    window_s = (hi - lo) * 1e-9
    devs = sorted(ev.ops)[:ndev] if ev.ops else []
    if not devs:
        raise ValueError("the trace holds no device ops")
    # The profiler may record transfers in flight for some chips only (on the
    # v5e 2x2: chip 0); the exchange is then read on those chips alone.
    ex_devs = [d for d in devs if ev.in_flight.get(d)] or devs
    sums = {"kernel": 0.0, "exchange": 0.0, "glue": 0.0}
    busy = exposed = 0.0
    idle_shares, by_name, gaps, n_kernel = [], {}, [], 0
    for d in devs:
        ops = [(n, max(a, lo), min(b, hi), classify(ev.insts[n]))
               for n, a, b in ev.ops[d] if b > lo and a < hi]
        leaf = [o for o in ops if o[3] != "container"]
        per = {c: [(a, b) for _, a, b, cc in leaf if cc == c] for c in sums}
        # the exchange: its ops on the core and its collectives in flight
        per["exchange"] += [(max(a, lo), min(b, hi)) for _, a, b in ev.in_flight.get(d, [])
                            if b > lo and a < hi]
        per = {c: union(iv) for c, iv in per.items()}
        for c in ("kernel", "glue"):
            sums[c] += length(per[c])
        if d in ex_devs:
            sums["exchange"] += length(per["exchange"])
            exposed += minus(per["exchange"], per["kernel"])
        n_kernel += sum(1 for o in leaf if o[3] == "kernel")
        u = union([(a, b) for _, a, b, _ in leaf])
        b_d = length(u)
        busy += b_d
        idle_shares.append(1.0 - b_d / (hi - lo))
        for n, a, b, c in leaf:
            key = _op_key(n, ev.insts[n], c)
            by_name[key] = by_name.get(key, 0.0) + (b - a)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _host_label(ev.host, (a + b) / 2)))
    n = len(devs)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gap_by_label = {}
    for dur, label in gaps:
        gap_by_label[label] = gap_by_label.get(label, 0.0) + dur
    top_gaps = sorted(gap_by_label.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(
        window_s=window_s,
        busy_s=busy / n * 1e-9,
        idle_share_max=max(idle_shares),
        kernel_s=sums["kernel"] / n * 1e-9,
        exchange_s=sums["exchange"] / len(ex_devs) * 1e-9,
        exchange_exposed_s=exposed / len(ex_devs) * 1e-9,
        glue_s=sums["glue"] / n * 1e-9,
        top_ops=[[k, v / n * 1e-9] for k, v in top],
        idle_gaps=[[k, v / n * 1e-9] for k, v in top_gaps],
        n_kernel_ops=n_kernel,
    )


def _op_key(inst, info, cls) -> str:
    """What a device op is, for the breakdown: its scope path (or, without
    one, its instruction name less the ``.N`` suffix), opcode and class, so
    the unrolled copies of one op add up."""
    what = info[2] or re.sub(r"\.\d+$", "", inst)
    return f"{what} ({info[0]}, {cls})"


def _host_label(host, t) -> str:
    """The innermost host annotation of the window's thread at time ``t``."""
    best = None
    for name, a, b in host:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return f"host: {best[0]}" if best else "host: between annotations"


def reduce_dir(logdir: str, ndev: int, hlo_text: str) -> Reduced:
    return reduce(from_xplane(find_xplane(logdir), hlo_text), ndev)


def save_events(ev: Events, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ev.to_json(), f)


def load_events(path: str) -> Events:
    with gzip.open(path, "rt") as f:
        return Events.from_json(json.load(f))
