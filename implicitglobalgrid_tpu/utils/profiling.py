"""Device-timeline profiling plane: windowed capture, per-scope attribution,
measured comm/compute overlap, cross-run drift diffing.

Three observability PRs built the HOST side of the story — telemetry
(PR 4), host spans + the merged cross-rank timeline (PR 9), the live plane
(PR 10) — but the repo's performance claims are about the DEVICE timeline:
ROADMAP item 1 wants the exchange provably concurrent with the interior
pass, and VERDICT r5 names a third of the diffusion headline lost between
the 976 GB/s kernel and the 659.5 GB/s cadence — "cadence glue" only
per-op device-time attribution can localize.  This module cashes in the
correlate-BY-NAME contract `utils.tracing` set up: host spans reuse the
compiled ``named_scope`` names (``igg_ring_pass``/``igg_interior_pass``/
``igg_halo_exchange``/``igg_slab_exchange_*``), so a parsed profiler
capture attributes device time to the same namespace the host timeline
already speaks (docs/observability.md "Device timeline").

* **Capture** — ``IGG_PROFILE=steps:A-B`` arms a `jax.profiler` capture
  around time-loop steps A..B of the next instrumented run
  (`ProfileCapture`, constructed by the step pipeline the way the live
  plane's server is: `maybe_arm` from `telemetry._StepLoop`).  Output is
  per-rank (``profile.p<rank>/`` under ``IGG_PROFILE_DIR`` /
  ``IGG_TELEMETRY_DIR``) with ``create_perfetto_trace=True`` so a
  parseable ``*.trace.json.gz`` lands next to the xplane protobuf.  The
  capture meta file ``profile.p<rank>.json`` (window, host perf anchors,
  trace path, attribution) is the discovery surface for the merge and the
  CLI.  Every failure mode — no profiler in the toolchain, no directory,
  a start/stop error, an unparseable trace — degrades to ONE structured
  ``profile.capture_failed`` event, never a crash; a window left open at
  scope exit (guard trip, injected crash) is stopped by
  `resilience.guarded_time_loop`'s exit path so the bytes already
  captured still land.
* **Attribution** — `attribute_trace` parses the Chrome/Perfetto JSON,
  keeps the DEVICE ops (XLA:CPU's events carrying ``args.hlo_op``; a TPU
  plane's ``XLA Ops`` line, whose events carry the instruction's text and
  its ``named_scope`` path as ``args.tf_op``), and attributes their time
  in the program's names: a Pallas launch to its kernel variant
  (``igg_diffusion_k4_zpatch``, `ops._fused_envelope.kernel_name`), any
  other op to the `SCOPE_NAMES` member on its scope path, else to the
  blessed `utils.hlo_analysis.classify_op_name` buckets: ``collectives``
  (fabric traffic), ``kernels`` (fusions / custom-calls), ``glue``
  (copies, slices, control flow — XLA-inserted copies carry no scope and
  land here).  The **measured overlap fraction** is wall-clock
  union-intersection per device track: |union(collective intervals) ∩
  union(kernel intervals)| / |union(collective intervals)| — the number
  ROADMAP item 1's acceptance needs, honest bounds in
  docs/observability.md.
* **Join** — `attach_device_tracks` adds per-rank device tracks to the
  PR-9 merged host timeline (``scripts/igg_trace.py merge --device``):
  device events ride the owning rank's pid on dedicated device tids,
  aligned on a program span that is both in the host ring and in the
  capture (every span is a profiler annotation too; the host
  ``start_trace`` instant is the fallback), and the output still passes
  `tracing.validate_chrome_trace`.
* **Feed out** — `publish_attribution` lands
  ``profile.scope_seconds.<name>`` / ``profile.overlap_fraction`` gauges;
  ``bench.py`` records ``extras.profile_attribution`` with the overlap
  fraction as a REPORTED perf-gate key (`analysis.perf`);
  ``scripts/igg_prof.py diff A B`` names the scope a cross-run regression
  ate its time in.

Layering: module scope imports only stdlib + `config`/`telemetry`/
`hlo_analysis`; jax is reached lazily inside start/stop so the parser and
diff tooling work in a jax-less (or broken-accelerator) environment.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import time
from typing import Any, Sequence

from . import config as _config
from . import hlo_analysis as _hlo
from . import telemetry as _telemetry

__all__ = [
    "SCOPE_NAMES",
    "parse_profile_window",
    "ProfileCapture",
    "maybe_arm",
    "active_capture",
    "close_open_capture",
    "profile_trace",
    "profile_meta_filename",
    "load_trace",
    "find_trace_files",
    "device_ops",
    "attribute_trace",
    "attribute_capture",
    "attribution_delta",
    "render_attribution_table",
    "render_delta_table",
    "publish_attribution",
    "attach_device_tracks",
    "find_capture_metas",
    "resolve_trace_path",
    "reset",
]

#: capture meta / attribution record schema version
PROFILE_SCHEMA = 1

#: the compiled named_scope namespace (docs/observability.md): device ops
#: whose qualified name carries one of these attribute to it directly —
#: the same names the host spans use, which is what lets the merged
#: timeline line both sides up.  Ordered begin/finish before the bare
#: exchange so the most specific name wins a substring match.
SCOPE_NAMES = (
    "igg_ring_pass",
    "igg_interior_pass",
    "igg_slab_exchange_begin",
    "igg_slab_exchange_finish",
    "igg_halo_exchange",
    "igg_zpatch_identity",
    "igg_zpatch_apply",
    "igg_zpatch_from_export",
)

#: a Pallas kernel variant's name (`ops._fused_envelope.kernel_name`): a
#: kernel op attributes to its variant, ahead of any enclosing scope
KERNEL_VARIANT = re.compile(r"igg_[a-z]+_k\d+_(?:plain|zpatch)\w*")

#: profiler lines of a TPU plane (Chrome-trace thread names): the ops the
#: core executes, and the async ops in flight from start to done (of which
#: only the collectives count: the exchange's transfers)
OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"

#: opcodes that only enclose other ops on a TPU op line
CONTAINER_OPCODES = ("while", "conditional", "call")

#: fallback buckets for device ops outside any named scope (the
#: `hlo_analysis.classify_op_name` vocabulary; "glue" is the unattributed
#: cadence overhead the attribution exists to localize)
FALLBACK_BUCKETS = ("collectives", "kernels", "glue")


def profile_meta_filename(rank: int) -> str:
    return f"profile.p{rank}.json"


# -- window spec --------------------------------------------------------------


def parse_profile_window(spec: str) -> tuple[int, int]:
    """``IGG_PROFILE`` grammar -> ``(start_step, stop_step)``, 1-based
    inclusive.

    ``steps:A-B`` captures time-loop steps A..B; ``steps:N`` is shorthand
    for ``steps:1-N``.  Error messages follow the config contract (name
    the variable, the accepted format and the obtained value).
    """
    err = ValueError(
        f"Environment variable IGG_PROFILE must be 'steps:A-B' or "
        f"'steps:N' (1-based inclusive time-loop steps, e.g. "
        f"'steps:20-40'), got {spec!r}."
    )
    head, sep, rng = spec.partition(":")
    if head != "steps" or not sep or not rng:
        raise err
    lo, dash, hi = rng.partition("-")
    try:
        a = int(lo)
        b = int(hi) if dash else a
        if not dash:
            a = 1
    except ValueError:
        raise err from None
    if a < 1 or b < a:
        raise err
    return a, b


# -- capture ------------------------------------------------------------------


class ProfileCapture:
    """One armed windowed device capture for this process's current run.

    Driven by the step pipeline (`telemetry._StepLoop`): `on_step(it)` is
    called after every completed step and starts/stops the profiler at the
    window edges.  All device interaction is guarded — any failure emits a
    structured ``profile.capture_failed`` event and disarms the capture;
    the run never pays more than the event.
    """

    def __init__(self, window: tuple[int, int], *, logdir: str | None = None,
                 rank: int | None = None):
        self.window = (int(window[0]), int(window[1]))
        self.rank = _telemetry._proc_index() if rank is None else rank
        if logdir is None:
            base = _config.profile_dir_env() or _config.telemetry_dir_env()
            logdir = (
                os.path.join(base, f"profile.p{self.rank}") if base else None
            )
        self.logdir = logdir
        self.started = False
        self.done = False
        self.started_at_step: int | None = None
        self.last_step: int | None = None
        self.t_start_perf: float | None = None
        self.wall_start: float | None = None
        self.meta_path: str | None = None

    # - lifecycle -

    def _fail(self, stage: str, error: Exception | str) -> None:
        self.done = True
        if self.started:
            # best-effort teardown so a later capture can start
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self.started = False
        _telemetry.event(
            "profile.capture_failed",
            stage=stage,
            window=list(self.window),
            logdir=self.logdir,
            error=str(error),
        )
        _telemetry.counter("profile.capture_failures").inc()

    def _start(self, step: int) -> None:
        if self.logdir is None:
            self._fail(
                "start",
                "no capture directory (set IGG_PROFILE_DIR or "
                "IGG_TELEMETRY_DIR)",
            )
            return
        try:
            import jax

            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(
                self.logdir, create_perfetto_trace=True
            )
        except Exception as e:
            self._fail("start", e)
            return
        # anchor AFTER start returns: the profiler is live from here, so
        # this perf sample is the instant the device track aligns to.
        self.t_start_perf = time.perf_counter()
        self.wall_start = time.time()
        self.started = True
        self.started_at_step = step
        _telemetry.event(
            "profile.start",
            step=step,
            window=list(self.window),
            logdir=self.logdir,
        )

    def _stop(self, step: int, reason: str) -> None:
        self.done = True
        if not self.started:
            return
        self.started = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            self._fail("stop", e)
            return
        t_stop_perf = time.perf_counter()
        meta: dict[str, Any] = {
            "schema": PROFILE_SCHEMA,
            "rank": self.rank,
            "pid": os.getpid(),
            "window": list(self.window),
            "started_at_step": self.started_at_step,
            "stopped_at_step": step,
            "reason": reason,
            "logdir": self.logdir,
            "t_start_perf": self.t_start_perf,
            "t_stop_perf": t_stop_perf,
            "wall_start": self.wall_start,
            "wall_stop": time.time(),
        }
        traces = find_trace_files(self.logdir)
        if not traces:
            meta["trace_path"] = None
            meta["attribution"] = {
                "error": "no *.trace.json.gz emitted (toolchain without "
                "the Chrome-trace exporter?)"
            }
            self._write_meta(meta)
            self._fail("locate", meta["attribution"]["error"])
            return
        meta["trace_path"] = traces[-1]
        try:
            attribution = attribute_trace(traces[-1])
        except (OSError, ValueError) as e:
            attribution = {"error": f"{type(e).__name__}: {e}"}
            _telemetry.event(
                "profile.capture_failed",
                stage="attribute",
                window=list(self.window),
                error=attribution["error"],
            )
            _telemetry.counter("profile.capture_failures").inc()
        meta["attribution"] = attribution
        if "error" not in attribution:
            publish_attribution(attribution)
        self._write_meta(meta)
        _telemetry.counter("profile.captures").inc()
        _telemetry.event(
            "profile.stop",
            step=step,
            window=list(self.window),
            reason=reason,
            trace=meta["trace_path"],
            meta=self.meta_path,
            overlap_fraction=(
                attribution.get("overlap", {}).get("fraction")
                if "error" not in attribution
                else None
            ),
        )

    def _write_meta(self, meta: dict) -> None:
        # The meta lands where `find_capture_metas` looks: the telemetry
        # dir, else the capture BASE dir (logdir's parent — logdir itself
        # is the per-rank profile.p<rank>/ subdir, where a non-recursive
        # glob would never see it).
        directory = _config.telemetry_dir_env() or (
            os.path.dirname(self.logdir) if self.logdir else None
        )
        if not directory:
            return
        try:
            self.meta_path = _telemetry.atomic_write_json(
                os.path.join(directory, profile_meta_filename(self.rank)),
                meta,
                indent=1,
            )
        except OSError:
            self.meta_path = None

    # - step pipeline hooks -

    def on_run_start(self, start_step: int) -> None:
        """Arm-time hook: a window already entered at resume (checkpointed
        runs) starts immediately — step ``start_step + 1`` is next."""
        a, b = self.window
        if not self.done and a <= start_step + 1 <= b:
            self._start(start_step + 1)

    def on_step(self, it: int) -> None:
        """Post-step hook from the instrumented loop (step ``it`` done)."""
        self.last_step = it
        if self.done:
            return
        a, b = self.window
        if self.started:
            if it >= b:
                self._stop(it, "window")
        elif it + 1 >= a and it + 1 <= b:
            self._start(it + 1)
        elif it + 1 > b:
            self.done = True  # window passed before the run reached it

    def close(self, reason: str) -> None:
        """Scope-exit stop (`resilience.guarded_time_loop`'s finally path
        and `_StepLoop.finish`): a window still open when the run ends —
        normally or through a guard trip — stops cleanly so the captured
        bytes land.  The recorded stop step is the LAST completed step the
        pipeline reported (falling back to the start step when the window
        opened and the run died before any step finished)."""
        if self.started and not self.done:
            step = (
                self.last_step
                if self.last_step is not None
                else (self.started_at_step or 0)
            )
            self._stop(step, reason)
        else:
            self.done = True

    def info(self) -> dict:
        """The in-flight description a flight-recorder bundle wants."""
        return {
            "window": list(self.window),
            "logdir": self.logdir,
            "started": self.started,
            "started_at_step": self.started_at_step,
            "done": self.done,
        }


_active: ProfileCapture | None = None


def maybe_arm(start_step: int = 0) -> ProfileCapture | None:
    """Arm a windowed capture for this run when ``IGG_PROFILE`` says so.

    Called from the step pipeline (`telemetry._StepLoop.__init__`, the
    live-plane `ensure_server` slot).  Returns None when the knob is unset
    or telemetry is off (the zero-overhead contract: the loop then pays
    one ``is not None`` check per step).  An invalid spec raises — the
    config-tier error contract, same as every other malformed ``IGG_*``.
    """
    global _active
    spec = _config.profile_env()
    if not spec or not _telemetry.enabled():
        return None
    if _active is not None:
        # Fire-once per process (the documented "next instrumented run"
        # contract): a process running several instrumented loops —
        # bench.py runs three models back to back — must not pay a
        # profiler session per run and overwrite the first capture's
        # artifacts with whichever run happened last.  `reset()` re-arms.
        return None
    window = parse_profile_window(spec)
    cap = ProfileCapture(window)
    _active = cap
    cap.on_run_start(start_step)
    return cap


def active_capture() -> dict | None:
    """The open capture window's description, or None — what
    `tracing.dump_flight_recorder` bundles so a crash mid-capture is
    explained (docs/observability.md)."""
    if _active is not None and _active.started and not _active.done:
        return _active.info()
    return None


def close_open_capture(reason: str = "scope_exit") -> None:
    """Stop any open window (the resilience scope-exit path).  Idempotent
    and never raises — it runs inside ``finally`` blocks."""
    global _active
    try:
        if _active is not None:
            _active.close(reason)
    except Exception:
        pass


def reset() -> None:
    """Drop the armed capture (test hook)."""
    global _active
    _active = None


@contextlib.contextmanager
def profile_trace(logdir, **kwargs):
    """Record a `jax.profiler` trace of the enclosed block (the ONE manual
    capture implementation; ``igg.profile_trace`` is a thin alias).

    ``create_perfetto_trace`` defaults to True so the capture always emits
    the parseable ``*.trace.json.gz`` the attribution pipeline reads::

        with igg.profile_trace("/tmp/igg-trace"):
            for _ in range(20):
                state = step(*state)
        rec = profiling.attribute_capture("/tmp/igg-trace")

    Prefer the windowed env-armed capture (``IGG_PROFILE=steps:A-B``) for
    instrumented runs — it needs no code changes and lands the per-rank
    meta file the merge/CLI tooling discovers.
    """
    import jax

    kwargs.setdefault("create_perfetto_trace", True)
    with jax.profiler.trace(str(logdir), **kwargs):
        yield


# -- trace parsing ------------------------------------------------------------


def load_trace(path: str | os.PathLike) -> dict:
    """One Chrome-trace JSON document from ``path`` (gzip by suffix).

    Raises ValueError on malformed/truncated input — callers turn that
    into a structured finding (`attribute_trace` callers, the CLI), never
    a traceback shown to an operator.
    """
    path = os.fspath(path)
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rt", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, EOFError) as e:
        # gzip truncation surfaces as EOFError/OSError mid-read
        raise ValueError(f"{path}: unreadable trace ({e})") from e
    except ValueError as e:
        raise ValueError(f"{path}: malformed trace JSON ({e})") from e
    if not isinstance(doc, dict) or not isinstance(
        doc.get("traceEvents"), list
    ):
        raise ValueError(
            f"{path}: not a Chrome trace (no traceEvents list)."
        )
    return doc


def find_trace_files(logdir: str | os.PathLike) -> list[str]:
    """The ``*.trace.json.gz`` files under a profiler log dir, oldest
    first (the exporter nests them under ``plugins/profile/<run>/``; the
    ``perfetto_trace.json.gz`` sibling is protobuf-oriented and skipped by
    the suffix match)."""
    logdir = os.fspath(logdir)
    hits = glob.glob(
        os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True
    )
    hits += glob.glob(os.path.join(logdir, "*.trace.json"))
    return sorted(set(hits), key=lambda p: (os.path.getmtime(p), p))


def _opcode(long_name: str) -> str:
    """Opcode of one HLO instruction's text (``%x = type opcode(...)``),
    skipping a result type that may be a tuple with nested parentheses."""
    rest = long_name.partition("=")[2]
    depth, i = 0, 0
    while i < len(rest):
        ch = rest[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0 and i > 0:
            break
        i += 1
    m = re.match(r"\s*([a-z][\w\-]*)\(", rest[i:])
    return m.group(1) if m else ""


def _thread_names(doc: dict) -> dict:
    return {
        (e.get("pid"), e.get("tid")): (e.get("args") or {}).get("name", "")
        for e in doc.get("traceEvents", [])
        if isinstance(e, dict) and e.get("ph") == "M"
        and e.get("name") == "thread_name"
    }


def device_ops(doc: dict) -> list[dict]:
    """The device-op events of a capture (µs): ``{name, hlo_op, op_name,
    kind, hlo_module, pid, tid, ts, dur}`` dicts.

    Two shapes of capture.  XLA:CPU's op events carry ``args.hlo_op``.  A
    TPU plane's events carry none: an op of the ``XLA Ops`` line is named
    by its instruction, with the instruction's text in ``args.long_name``
    (its opcode gives ``kind``) and its ``named_scope`` path in
    ``args.tf_op`` (the compiled op's ``op_name``; absent on ops XLA
    inserted).  Ops that only enclose others (``while``, ``call``) are
    left out; of the ``Async XLA Ops`` line only the collectives are kept,
    from start to done: the exchange's transfers in flight.
    Runtime/python/annotation events are no device ops, which is exactly
    the filter (host time is the span ring's job)."""
    threads = _thread_names(doc)
    out = []
    for e in doc.get("traceEvents", []):
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        hlo_op = args.get("hlo_op")
        line = threads.get((e.get("pid"), e.get("tid")))
        long_name = args.get("long_name")
        if line in (OP_LINE, ASYNC_LINE) and long_name:
            hlo_op = e.get("name") or hlo_op
            opcode = _opcode(str(long_name))
            if opcode in CONTAINER_OPCODES:
                continue
            kind = _hlo.classify_op_name(opcode)
            if line == ASYNC_LINE and kind != "collective":
                continue
        elif hlo_op:
            kind = _hlo.classify_op_name(hlo_op)
        else:
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if not isinstance(ts, (int, float)) or not isinstance(
            dur, (int, float)
        ):
            continue
        out.append(
            {
                "name": e.get("name", hlo_op),
                "hlo_op": hlo_op,
                "op_name": str(args.get("tf_op", "")).rstrip(":") or None,
                "kind": kind,
                "hlo_module": args.get("hlo_module"),
                "pid": e.get("pid", 0),
                "tid": e.get("tid", 0),
                "ts": float(ts),
                "dur": float(dur),
            }
        )
    return out


def scope_of(op: dict) -> str:
    """Attribution bucket of one device op, in the program's names: the
    kernel variant a Pallas launch is (`KERNEL_VARIANT`), else the
    `SCOPE_NAMES` member its scope path carries — the op's ``op_name``
    where the capture records it, else the event's name — else the
    op's kind as a bucket (``collectives`` / ``kernels`` / ``glue``)."""
    path = op.get("op_name") or op["name"]
    if op_kind(op) == "kernel":
        m = KERNEL_VARIANT.search(path) or KERNEL_VARIANT.search(op["name"])
        if m:
            return m.group(0)
    for scope in SCOPE_NAMES:
        if scope in path:
            return scope
    return {"collective": "collectives", "kernel": "kernels"}.get(
        op_kind(op), "glue"
    )


def op_kind(op: dict) -> str:
    """``collective`` | ``kernel`` | ``glue`` of one device op (the opcode
    a TPU capture records, else the blessed name vocabulary — scope
    membership does not change what the op IS; a collective inside
    ``igg_slab_exchange_begin`` still counts as comm time in the overlap
    measure)."""
    return op.get("kind") or _hlo.classify_op_name(op["hlo_op"] or op["name"])


# -- interval arithmetic (overlap measure) ------------------------------------


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for a, b in intervals[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _intersection_seconds(u1, u2) -> float:
    total, i, j = 0.0, 0, 0
    while i < len(u1) and j < len(u2):
        a = max(u1[i][0], u2[j][0])
        b = min(u1[i][1], u2[j][1])
        if b > a:
            total += b - a
        if u1[i][1] < u2[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_measure(ops: Sequence[dict]) -> dict:
    """The measured comm/compute overlap of one capture's device ops.

    Per device track (pid): union the collective-op intervals and the
    kernel-op intervals, intersect the two unions, sum across tracks.
    ``fraction = overlapped / comm`` — the share of fabric time hidden
    under compute, the number ROADMAP item 1's acceptance gates on.  None
    when the capture holds no collectives (single-device runs) — absence
    is meaningful, never 0.0 (docs/observability.md honesty bounds).
    """
    by_pid: dict[Any, dict[str, list]] = {}
    for op in ops:
        kind = op_kind(op)
        if kind == "glue":
            continue
        iv = (op["ts"], op["ts"] + op["dur"])
        by_pid.setdefault(op["pid"], {"collective": [], "kernel": []})[
            kind
        ].append(iv)
    comm = compute = overlapped = 0.0
    for tracks in by_pid.values():
        u_comm = _union(tracks["collective"])
        u_kern = _union(tracks["kernel"])
        comm += sum(b - a for a, b in u_comm)
        compute += sum(b - a for a, b in u_kern)
        overlapped += _intersection_seconds(u_comm, u_kern)
    return {
        "comm_seconds": comm * 1e-6,
        "compute_seconds": compute * 1e-6,
        "overlapped_seconds": overlapped * 1e-6,
        "fraction": round(overlapped / comm, 6) if comm > 0 else None,
    }


# -- attribution --------------------------------------------------------------


def attribute_ops(ops: Sequence[dict]) -> dict:
    """Per-scope device-time attribution over parsed device ops."""
    scope_s: dict[str, float] = {}
    for op in ops:
        scope = scope_of(op)
        scope_s[scope] = scope_s.get(scope, 0.0) + op["dur"]
    scope_seconds = {
        name: round(us * 1e-6, 9) for name, us in sorted(scope_s.items())
    }
    total = round(sum(op["dur"] for op in ops) * 1e-6, 9)
    return {
        "schema": PROFILE_SCHEMA,
        "n_device_ops": len(ops),
        "device_seconds": total,
        "scope_seconds": scope_seconds,
        "unattributed_seconds": scope_seconds.get("glue", 0.0),
        "overlap": overlap_measure(ops),
    }


def attribute_trace(trace: str | os.PathLike | dict) -> dict:
    """Full attribution record of one capture (path or loaded doc).

    Raises ValueError on malformed input (callers degrade to a structured
    finding); a VALID trace with zero device ops returns a record saying
    so (``n_device_ops: 0``) rather than failing — a host-only capture is
    an answer, not an error.
    """
    doc = trace if isinstance(trace, dict) else load_trace(trace)
    rec = attribute_ops(device_ops(doc))
    if not isinstance(trace, dict):
        rec["trace"] = os.fspath(trace)
    return rec


def attribute_capture(logdir: str | os.PathLike) -> dict:
    """Attribute the newest trace under a profiler log dir."""
    traces = find_trace_files(logdir)
    if not traces:
        raise ValueError(
            f"{os.fspath(logdir)}: no *.trace.json.gz capture found "
            f"(run with IGG_PROFILE / profile_trace first)."
        )
    return attribute_trace(traces[-1])


def publish_attribution(rec: dict) -> None:
    """Land an attribution record on the metrics registry:
    ``profile.scope_seconds.<scope>`` gauges plus
    ``profile.overlap_fraction`` (set only when measured — a gauge of
    None would fake a number)."""
    for scope, seconds in rec.get("scope_seconds", {}).items():
        _telemetry.gauge(f"profile.scope_seconds.{scope}").set(seconds)
    frac = rec.get("overlap", {}).get("fraction")
    if frac is not None:
        _telemetry.gauge("profile.overlap_fraction").set(frac)


# -- cross-run diffing --------------------------------------------------------


def attribution_delta(a: dict, b: dict) -> dict:
    """Attribute the drift between two attribution records (run A -> B).

    Per scope: seconds in each run and the delta (positive = B spends
    MORE); ``worst`` names the scope that grew the most — where a
    regression went.  The overlap fractions ride along so "the exchange
    stopped hiding" is visible next to "interior got slower".
    """
    scopes = sorted(
        set(a.get("scope_seconds", {})) | set(b.get("scope_seconds", {}))
    )
    table = {}
    worst, worst_delta = None, 0.0
    for s in scopes:
        sa = float(a.get("scope_seconds", {}).get(s, 0.0))
        sb = float(b.get("scope_seconds", {}).get(s, 0.0))
        delta = round(sb - sa, 9)
        table[s] = {"a_s": sa, "b_s": sb, "delta_s": delta}
        if delta > worst_delta:
            worst, worst_delta = s, delta
    return {
        "schema": PROFILE_SCHEMA,
        "scopes": table,
        "device_seconds": {
            "a": a.get("device_seconds"),
            "b": b.get("device_seconds"),
        },
        "overlap_fraction": {
            "a": a.get("overlap", {}).get("fraction"),
            "b": b.get("overlap", {}).get("fraction"),
        },
        "worst": worst,
        "worst_delta_s": round(worst_delta, 9),
    }


def render_attribution_table(rec: dict) -> str:
    """Fixed-width per-scope table (golden-pinned by
    tests/test_profiling.py: change the format deliberately and update the
    golden)."""
    head = f"{'scope':<28} {'device_ms':>12} {'share':>7}"
    lines = [head, "-" * len(head)]
    total = rec.get("device_seconds") or 0.0
    for name, sec in rec.get("scope_seconds", {}).items():
        share = (sec / total) if total else 0.0
        lines.append(f"{name:<28} {sec * 1e3:>12.3f} {share:>6.1%}")
    lines.append("-" * len(head))
    lines.append(
        f"{'total':<28} {total * 1e3:>12.3f} {'':>7} "
        f"({rec.get('n_device_ops', 0)} device op(s))"
    )
    ov = rec.get("overlap", {})
    frac = ov.get("fraction")
    lines.append(
        "overlap: comm "
        f"{(ov.get('comm_seconds') or 0.0) * 1e3:.3f} ms, compute "
        f"{(ov.get('compute_seconds') or 0.0) * 1e3:.3f} ms, overlapped "
        f"{(ov.get('overlapped_seconds') or 0.0) * 1e3:.3f} ms -> fraction "
        + (f"{frac:.4f}" if frac is not None else "n/a (no collectives)")
    )
    return "\n".join(lines)


def render_delta_table(delta: dict) -> str:
    """Fixed-width cross-run drift table (``igg_prof.py diff``)."""
    head = f"{'scope':<28} {'A_ms':>10} {'B_ms':>10} {'delta_ms':>10}"
    lines = [head, "-" * len(head)]
    for name, row in delta.get("scopes", {}).items():
        lines.append(
            f"{name:<28} {row['a_s'] * 1e3:>10.3f} "
            f"{row['b_s'] * 1e3:>10.3f} {row['delta_s'] * 1e3:>+10.3f}"
        )
    ov = delta.get("overlap_fraction", {})

    def _f(v):
        return f"{v:.4f}" if isinstance(v, (int, float)) else "n/a"

    lines.append(
        f"overlap fraction: A {_f(ov.get('a'))} -> B {_f(ov.get('b'))}"
    )
    if delta.get("worst"):
        lines.append(
            f"worst regression: {delta['worst']} "
            f"(+{delta['worst_delta_s'] * 1e3:.3f} ms)"
        )
    return "\n".join(lines)


# -- merged-timeline join (igg_trace.py merge --device) -----------------------


def find_capture_metas(directory: str | os.PathLike) -> list[str]:
    """The per-rank capture meta files (``profile.p<rank>.json``) in a
    telemetry/run directory."""
    return sorted(
        glob.glob(os.path.join(os.fspath(directory), "profile.p*.json"))
    )


def resolve_trace_path(meta: dict, meta_dir: str | None = None) -> str | None:
    """The capture's trace file, surviving archived/copied run dirs.

    The meta records ``trace_path``/``logdir`` as ABSOLUTE paths from
    capture time; a run directory copied off the original machine (the
    diff tool's cross-round use) still holds the trace under its own
    ``profile.p<rank>/`` — so resolution falls back from the recorded
    absolute path to the meta's own directory before giving up (None).
    """
    path = meta.get("trace_path")
    if path and os.path.isfile(path):
        return path
    if meta_dir is not None and meta.get("rank") is not None:
        traces = find_trace_files(
            os.path.join(os.fspath(meta_dir), f"profile.p{meta['rank']}")
        )
        if traces:
            return traces[-1]
    if meta.get("logdir"):
        traces = find_trace_files(meta["logdir"])
        if traces:
            return traces[-1]
    return None


#: tid base for attached device tracks (host spans sit on tid 0; a large
#: offset keeps original device-thread identity visible as tid - base)
DEVICE_TID_BASE = 10_000

#: max seconds a capture meta's wall_start may PREDATE the merged
#: timeline's per-rank clock-sync anchor before `attach_device_tracks`
#: refuses it as stale (same spirit as `tracing.BARRIER_WALL_TOL_S`: a
#: capture happens during the run, after the sync barrier — anything
#: earlier is a previous run's leftover in a reused telemetry dir).
STALE_META_TOL_S = 2.0


def _span_key(name: str, args: dict | None) -> tuple:
    """A program span's identity across the ring and a capture: its name
    and its scalar tag values as strings (a capture's annotation holds its
    arguments as strings — a step annotation's under ``step_num`` for its
    numbering tag, beside profiler-private ``_`` keys)."""
    vals = sorted(
        str(v) for k, v in (args or {}).items()
        if not str(k).startswith("_") and isinstance(v, (str, int, float))
    )
    return name, tuple(vals)


def _span_anchor(events: list, rank: int, trace_doc: dict,
                 guess_us: float) -> tuple[float, float, str] | None:
    """``(capture µs, merged-timeline µs, name)`` of the first program span
    (``igg.*``) of a capture that is also on ``rank``'s host track of the
    merged timeline, or None.  ``guess_us`` (capture µs -> merged µs by the
    ``start_trace`` anchor) picks among host spans of the same identity."""
    host: dict[tuple, list[float]] = {}
    for e in events:
        if e.get("ph") == "X" and e.get("pid") == rank and e.get("tid") == 0:
            host.setdefault(_span_key(e["name"], e.get("args")), []).append(
                e["ts"]
            )
    caps = sorted(
        (e for e in trace_doc.get("traceEvents", [])
         if isinstance(e, dict) and e.get("ph") == "X"
         and str(e.get("name", "")).startswith("igg.")
         and isinstance(e.get("ts"), (int, float))),
        key=lambda e: e["ts"],
    )
    for c in caps:
        cands = host.get(_span_key(c["name"], c.get("args")))
        if cands:
            guess = c["ts"] + guess_us
            return (float(c["ts"]), min(cands, key=lambda t: abs(t - guess)),
                    c["name"])
    return None


def attach_device_tracks(
    doc: dict, metas: Sequence[str | os.PathLike | dict]
) -> dict:
    """Add per-rank device tracks to a merged host timeline (in place).

    ``doc`` is `tracing.merge_trace_files` output; ``metas`` are capture
    meta files (or loaded dicts) from the same run's ranks.  Each rank's
    device ops land on ITS host track's pid (new ``DEVICE_TID_BASE + k``
    tids, one per original device thread), riding the host track's barrier
    offset.  Alignment: a program span present both on the rank's host
    track and in the capture's host plane (`_span_anchor`: every
    `tracing.trace_span` is a profiler annotation too) pins the capture's
    clock to the host track's — accurate to the microseconds between the
    annotation's entry and the span's ring timestamp.  A capture holding no
    such span falls back to anchoring its first device op at the host
    ``start_trace`` instant (the meta's ``t_start_perf``), accurate to the
    profiler's start latency (ms-scale).  Either way the capture's own
    mapping of device time to host time stays as recorded (device ops up
    to 0.7 ms early on a v5e capture).  Which anchor each rank got is
    recorded in ``otherData.device_alignment``.  The result still passes
    `tracing.validate_chrome_trace`.
    """
    alignment = doc.get("otherData", {}).get("clock_alignment")
    if alignment is None:
        raise ValueError(
            "attach_device_tracks needs merge_trace_files output "
            "(otherData.clock_alignment missing)."
        )
    base_us = float(alignment.get("ts_zero_offset_s", 0.0)) * 1e6
    dev_align: dict[str, Any] = {
        "note": (
            "each rank's capture is aligned on a program span present both "
            "on its host track and in the capture's host plane (anchor "
            "'span': microseconds between the annotation's entry and the "
            "ring's timestamp); without one, its first captured device op "
            "is anchored at the host start_trace instant (anchor "
            "'start_trace': the profiler start latency, ms-scale).  Neither "
            "corrects the capture's own device-to-host clock mapping (a v5e "
            "capture shows device ops up to 0.7 ms before the span that "
            "dispatched them), and both come ON TOP of the host "
            "clock_alignment uncertainty: cross-track ordering finer than "
            "about a millisecond is not trustworthy."
        ),
        "per_rank": {},
    }
    events = doc["traceEvents"]
    # Phase 1 — validate EVERY meta before touching the doc, so a raising
    # check (schema drift, the stale-file refusal) can never leave the
    # caller holding a partially mutated timeline.
    plans: list[tuple[dict, dict, str | None, list]] = []
    for meta_in in metas:
        if isinstance(meta_in, dict):
            meta, meta_dir = meta_in, None
        else:
            with open(os.fspath(meta_in), encoding="utf-8") as f:
                meta = json.load(f)
            meta_dir = os.path.dirname(os.path.abspath(os.fspath(meta_in)))
        if meta.get("schema") != PROFILE_SCHEMA:
            raise ValueError(
                f"capture meta {meta_in}: unsupported schema "
                f"{meta.get('schema')!r} (expected {PROFILE_SCHEMA})."
            )
        rank = meta["rank"]
        per = alignment.get("per_rank", {}).get(str(rank))
        if per is None:
            # A crashed rank can leave a capture meta with no host dump
            # (the meta publishes at window close, the trace.p<rank>.json
            # at dump_trace) — exactly the post-mortem this plane serves,
            # so degrade per rank instead of refusing the whole merge.
            dev_align["per_rank"][str(rank)] = {
                "trace": None,
                "n_ops": 0,
                "note": (
                    "no host track for this rank in the merged trace "
                    "(crashed before dump_trace?) — device ops omitted"
                ),
            }
            continue
        # Staleness guard — the device twin of merge_trace_files' same-
        # barrier refusal: a capture happens DURING the run, so its wall
        # clock cannot predate this rank's clock-sync anchor.  A
        # profile.p<rank>.json left in a reused telemetry dir by a
        # PREVIOUS run is exactly that shape, and joining it would anchor
        # dead-process perf samples onto the live timeline (then the
        # re-base below would silently shift every host span too).
        sync_wall = per.get("wall_at_sync_unix_s")
        wall_start = meta.get("wall_start")
        if (
            sync_wall is not None
            and wall_start is not None
            and wall_start < sync_wall - STALE_META_TOL_S
        ):
            raise ValueError(
                f"capture meta for rank {rank} predates the merged "
                f"timeline's clock sync by "
                f"{sync_wall - wall_start:.1f}s — a stale "
                f"profile.p{rank}.json from a previous run in a reused "
                f"telemetry dir looks exactly like this: delete it, or "
                f"re-run the capture alongside the current trace dumps."
            )
        trace_path = resolve_trace_path(meta, meta_dir)
        trace_doc = (
            load_trace(trace_path)
            if trace_path and meta.get("t_start_perf") is not None
            else {}  # load_trace raising here is still pre-mutation
        )
        plans.append((meta, per, trace_path, trace_doc))
    # Phase 2 — attach the validated ranks' device tracks.
    for meta, per, trace_path, trace_doc in plans:
        rank = meta["rank"]
        ops = device_ops(trace_doc)
        entry: dict[str, Any] = {"trace": trace_path, "n_ops": 0}
        dev_align["per_rank"][str(rank)] = entry
        if not trace_path or meta.get("t_start_perf") is None:
            entry["note"] = "no device trace captured"
            continue
        if not ops:
            entry["note"] = "capture holds no device ops"
            continue
        # host merged-timeline µs of device ts, first guess: the first
        # device op at the capture-start perf instant, through this rank's
        # host offset, minus the merge's zero re-base.
        t0 = min(op["ts"] for op in ops)
        anchor_us = (
            (meta["t_start_perf"] + per["offset_s"]) * 1e6 - base_us
        )
        pinned = _span_anchor(events, rank, trace_doc, anchor_us - t0)
        if pinned is None:
            entry["anchor"] = "start_trace"
        else:
            cap_us, host_us, name = pinned
            t0, anchor_us = cap_us, host_us
            entry["anchor"] = "span"
            entry["anchor_span"] = name
        tids = sorted({op["tid"] for op in ops})
        tid_map = {t: DEVICE_TID_BASE + i for i, t in enumerate(tids)}
        for t in tids:
            events.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": rank,
                    "tid": tid_map[t],
                    "args": {"name": f"device ops (capture tid {t})"},
                }
            )
        for op in ops:
            events.append(
                {
                    "ph": "X",
                    "name": op["name"],
                    "pid": rank,
                    "tid": tid_map[op["tid"]],
                    "ts": anchor_us + (op["ts"] - t0),
                    "dur": op["dur"],
                    "args": {
                        "hlo_op": op["hlo_op"],
                        "hlo_module": op["hlo_module"],
                        "igg_scope": scope_of(op),
                    },
                }
            )
        entry["n_ops"] = len(ops)
        entry["t_start_perf"] = meta["t_start_perf"]
        entry["window"] = meta.get("window")
    # Re-base: the validator refuses negative timestamps, and a device op
    # may align before the earliest host span.
    xs = [e["ts"] for e in events if e.get("ph") == "X"]
    if xs:
        shift = -min(min(xs), 0.0)
        if shift > 0:
            for e in events:
                if e.get("ph") == "X":
                    e["ts"] += shift
            alignment["ts_zero_offset_s"] = (
                float(alignment.get("ts_zero_offset_s", 0.0)) - shift / 1e6
            )
    events.sort(key=lambda e: (e["pid"], e.get("ts", -1.0)))
    doc["otherData"]["device_alignment"] = dev_align
    return doc
