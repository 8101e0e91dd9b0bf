"""Telemetry: process-local metrics registry + per-process JSONL event log.

The reference's entire instrumentation story is ``tic``/``toc``
(`/root/reference/src/tools.jl:230-236`), yet its headline claims are
*measurements* — weak-scaling efficiency and the effective memory throughput
``T_eff`` the ImplicitGlobalGrid/ParallelStencil papers report solver
performance in.  This module is the first-class observability layer behind
those numbers (docs/observability.md):

* **Metrics registry** — process-local counters, gauges and histograms
  (bounded reservoirs), keyed by dotted names (``halo.exchanges``,
  ``diffusion3d.t_eff_gbs``).  `snapshot()` returns the whole registry as
  plain data; `dump_metrics` writes it as JSON *and* Prometheus text
  exposition so any scrape/collect pipeline can ingest it.
* **Event log** — append-only JSONL, one file per process under
  ``IGG_TELEMETRY_DIR`` (``events.jsonl`` for process 0, ``events.pN.jsonl``
  for the rest).  Every line carries an absolute timestamp, the process
  rank, pid and (when a grid is up) the block coordinates — so a soak
  failover drill yields a machine-readable cross-process timeline of
  crashes, checkpoint fallbacks, elastic reshards and recoveries.  Lines
  are written with a single ``os.write`` on an ``O_APPEND`` descriptor:
  crash-safe (a hard ``os._exit`` right after an `event` call loses
  nothing) and interleaving-safe across processes.
* **Step-loop instrumentation** — `step_loop` hands the models'
  `guarded_time_loop` a per-step recorder: wall time, steps/s and the
  built-in ``T_eff`` (GB/s) from the solver's bytes-moved-per-step model
  (the reference perf convention: only arrays that *must* stream per step
  count, so ``T_eff = bytes_model / t_step`` is a lower bound on achieved
  HBM traffic), plus an optional rank-0 heartbeat line every
  ``IGG_HEARTBEAT_EVERY`` steps.
* **Compile counters** — one `jax.monitoring` listener, installed the
  first time telemetry is used, turns JAX's own compile events into
  ``compile.backend`` / ``compile.backend_seconds`` (every backend compile,
  persistent-cache reads included), ``compile.cache_hits`` /
  ``compile.cache_misses`` and one ``igg.compile`` span per backend
  compile, tagged with the compiled function's name.

Zero overhead when disabled: with ``IGG_TELEMETRY=0`` every accessor
returns a shared no-op singleton (`counter`/`gauge`/`histogram`) or ``None``
(`step_loop`), `event` returns before touching the filesystem, and the
instrumented hot paths guard on `enabled()` — no allocation, no locks, no
timestamps on the disabled branch (pinned by ``tests/test_telemetry.py``).

The registry is process-lifetime state (NOT reset by `finalize_global_grid`
— a run's metrics outlive its grid); `reset()` exists for tests.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Sequence

from . import config as _config

__all__ = [
    "enabled",
    "counter",
    "gauge",
    "histogram",
    "tenant_counter",
    "tenant_histogram",
    "frontdoor_tenant_counter",
    "event",
    "atomic_write_json",
    "snapshot",
    "telemetry_snapshot",
    "dump_metrics",
    "prometheus_text",
    "step_loop",
    "teff_bytes",
    "proc_rss_bytes",
    "process_count",
    "note_progress",
    "last_progress",
    "reset",
]


def enabled() -> bool:
    """The ``IGG_TELEMETRY`` master switch (read per call, like IGG_DONATE).
    The first call that finds it on in a process that has imported jax also
    installs the compile listener (`_install_compile_listener`)."""
    on = _config.telemetry_enabled_env()
    if on and not _compile_listener_on:
        _install_compile_listener()
    return on


# -- JAX's compile events -------------------------------------------------------

#: the monitoring event of every backend compile, a persistent-cache read
#: included: a time span tagged with the compiled function's ``fun_name``
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: persistent compile-cache events -> the counters they feed
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_compile_listener_on = False
_listener_lock = threading.Lock()


def _on_jax_event(event: str, **kwargs: Any) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        counter(name).inc()


def _on_jax_time_span(event: str, start_time: float, end_time: float,
                      **kwargs: Any) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    dur = end_time - start_time
    counter("compile.backend").inc()
    counter("compile.backend_seconds").inc(dur)
    from . import tracing as _tracing

    # the event's clock is time.time(); the span ring's is perf_counter
    t0 = time.perf_counter() - (time.time() - start_time)
    _tracing.record_span(
        "igg.compile", t0=t0, dur=dur, fun_name=str(kwargs.get("fun_name", ""))
    )


def _install_compile_listener() -> None:
    """Turn JAX's compile events into this registry's numbers, once per
    process (a `reset` keeps the listeners): ``compile.backend`` /
    ``compile.backend_seconds`` and one ``igg.compile`` ring span per
    backend compile, ``compile.cache_hits`` / ``compile.cache_misses`` per
    persistent-cache lookup.  Nothing happens before jax is imported — a
    jax-free process compiles nothing — and the listeners feed the
    registry only while telemetry is on."""
    global _compile_listener_on
    if "jax" not in sys.modules:
        return
    with _listener_lock:
        if _compile_listener_on:
            return
        _compile_listener_on = True
        try:
            from jax import monitoring
        except Exception:
            return
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_time_span_listener(_on_jax_time_span)


# -- Metric types -------------------------------------------------------------

#: reservoir size of every histogram — enough for stable p50/p90/p99 while
#: bounding a million-step run's memory to a few KiB per metric
RESERVOIR_SIZE = 512

#: rolling-SLO geometry (docs/observability.md live-plane section): every
#: histogram additionally keeps a ring of per-window sub-reservoirs so the
#: live plane can answer "p99 over the last few windows" instead of "p99
#: since process start".  `SLO_WINDOWS` windows of ``IGG_SLO_WINDOW_S``
#: seconds each (default `SLO_WINDOW_S_DEFAULT`), `WINDOW_RESERVOIR`
#: samples per window — bounded however long the run.
SLO_WINDOWS = 5
SLO_WINDOW_S_DEFAULT = 30.0
WINDOW_RESERVOIR = 256


def _slo_window_s() -> float:
    val = _config.slo_window_env()
    return SLO_WINDOW_S_DEFAULT if val is None else val


class Counter:
    """Monotonic counter.  ``inc`` is the only mutator (never decremented)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int | float = 1) -> None:
        self.value += n


class Gauge:
    """Last-write-wins scalar."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class _Window:
    """One rolling-SLO sub-window: a bounded sample list over a time slice."""

    __slots__ = ("t0", "count", "total", "samples")

    def __init__(self, t0: float):
        self.t0 = t0
        self.count = 0
        self.total = 0.0
        self.samples: list[float] = []

    def add(self, v: float, rng) -> None:
        self.count += 1
        self.total += v
        if len(self.samples) < WINDOW_RESERVOIR:
            self.samples.append(v)
        else:
            j = rng.randrange(self.count)
            if j < WINDOW_RESERVOIR:
                self.samples[j] = v


def _quantile_of(samples: list[float], q: float) -> float | None:
    if not samples:
        return None
    s = sorted(samples)
    idx = min(len(s) - 1, max(0, round(q * (len(s) - 1))))
    return s[idx]


class Histogram:
    """Streaming distribution: count/sum/min/max + a bounded reservoir.

    The reservoir is classic Vitter-R sampling with a per-histogram seeded
    PRNG — deterministic for a given record sequence (tests), uniform over
    the stream, and bounded at `RESERVOIR_SIZE` samples however many values
    are recorded.  Quantiles in `summary()` come from the reservoir.

    On top of the run-lifetime reservoir, every histogram keeps a ring of
    rolling sub-windows (`SLO_WINDOWS` windows of ``IGG_SLO_WINDOW_S``
    seconds, `WINDOW_RESERVOIR` samples each — allocated lazily on first
    record, so the disabled-mode zero-allocation contract is untouched):
    `window_summary()` yields live p50/p90/p99 over the last few windows —
    the ``slo.*`` gauge family and the ``/healthz`` live plane read it
    (docs/observability.md).  All mutators and readers hold the instance
    lock, so a scrape thread rendering `prometheus_text` mid-`record` sees
    a consistent snapshot (the concurrent-scrape pin in
    ``tests/test_telemetry.py``).
    """

    __slots__ = ("name", "count", "total", "min", "max", "_samples", "_rng",
                 "_hlock", "_win_cur", "_win_ring", "_win_len")

    def __init__(self, name: str):
        import random

        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._samples: list[float] = []
        self._rng = random.Random(0x1661)  # seeded: deterministic reservoirs
        self._hlock = threading.Lock()
        self._win_cur: _Window | None = None  # lazy: first record allocates
        self._win_ring: collections.deque | None = None
        self._win_len = 0.0

    def record(self, v: float, now: float | None = None) -> None:
        v = float(v)
        with self._hlock:
            self.count += 1
            self.total += v
            if self.min is None or v < self.min:
                self.min = v
            if self.max is None or v > self.max:
                self.max = v
            if len(self._samples) < RESERVOIR_SIZE:
                self._samples.append(v)
            else:
                j = self._rng.randrange(self.count)
                if j < RESERVOIR_SIZE:
                    self._samples[j] = v
            # rolling-SLO window ring (lazy; ``now`` injectable for tests)
            if now is None:
                now = time.monotonic()
            w = self._win_cur
            if w is None:
                self._win_len = _slo_window_s()
                self._win_ring = collections.deque(maxlen=SLO_WINDOWS - 1)
                w = self._win_cur = _Window(now)
            elif now - w.t0 >= self._win_len:
                self._win_ring.append(w)
                self._win_len = _slo_window_s()  # re-read per rollover
                w = self._win_cur = _Window(now)
            w.add(v, self._rng)

    def quantile(self, q: float) -> float | None:
        with self._hlock:
            return _quantile_of(self._samples, q)

    def window_summary(self, now: float | None = None) -> dict | None:
        """Live ``{window_s, windows, count, p50, p90, p99}`` over the last
        `SLO_WINDOWS` windows, or None before the first record.  Windows
        older than the rolling horizon (``SLO_WINDOWS * window_s`` behind
        ``now``) are excluded, so a long-idle histogram goes quiet instead
        of replaying stale quantiles forever."""
        with self._hlock:
            return self._window_summary_locked(now)

    def _window_summary_locked(self, now: float | None = None) -> dict | None:
        if self._win_cur is None:
            return None
        if now is None:
            now = time.monotonic()
        horizon = now - SLO_WINDOWS * self._win_len
        live = [
            w
            for w in (*self._win_ring, self._win_cur)
            if w.t0 >= horizon
        ]
        samples: list[float] = []
        count = 0
        total = 0.0
        for w in live:
            samples.extend(w.samples)
            count += w.count
            total += w.total
        if not count:
            return None
        return {
            "window_s": self._win_len,
            "windows": len(live),
            "count": count,
            "mean": total / count,
            "p50": _quantile_of(samples, 0.50),
            "p90": _quantile_of(samples, 0.90),
            "p99": _quantile_of(samples, 0.99),
        }

    def summary(self) -> dict:
        with self._hlock:
            out = {
                "count": self.count,
                "sum": self.total,
                "min": self.min,
                "max": self.max,
                "mean": (self.total / self.count) if self.count else None,
                "p50": _quantile_of(self._samples, 0.50),
                "p90": _quantile_of(self._samples, 0.90),
                "p99": _quantile_of(self._samples, 0.99),
            }
            win = self._window_summary_locked()
            if win is not None:
                out["window"] = win
            return out


class _Noop:
    """Shared do-nothing metric: the disabled-mode singleton every accessor
    returns — identity-stable so tests can pin the zero-allocation branch."""

    __slots__ = ()
    name = "<noop>"
    value = 0

    def inc(self, n: int | float = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def record(self, v: float, now: float | None = None) -> None:
        pass


NOOP = _Noop()


# -- Registry -----------------------------------------------------------------

_lock = threading.Lock()
_counters: dict[str, Counter] = {}
_gauges: dict[str, Gauge] = {}
_histograms: dict[str, Histogram] = {}
# (dir, filename) -> fd of the open event log
_event_fds: dict[tuple[str, str], int] = {}


def counter(name: str) -> Counter | _Noop:
    """The registry counter ``name`` (created on first use); `NOOP` when
    telemetry is disabled."""
    if not enabled():
        return NOOP
    with _lock:
        m = _counters.get(name)
        if m is None:
            m = _counters[name] = Counter(name)
        return m


def gauge(name: str) -> Gauge | _Noop:
    if not enabled():
        return NOOP
    with _lock:
        m = _gauges.get(name)
        if m is None:
            m = _gauges[name] = Gauge(name)
        return m


def gauge_value(name: str) -> float | None:
    """Current value of a gauge IF it exists (never creates one) — the
    read side the enriched heartbeat uses to attach skew/serving context
    only when something actually published it."""
    with _lock:
        g = _gauges.get(name)
        return g.value if g is not None else None


def histogram(name: str) -> Histogram | _Noop:
    if not enabled():
        return NOOP
    with _lock:
        m = _histograms.get(name)
        if m is None:
            m = _histograms[name] = Histogram(name)
        return m


#: default ``IGG_TELEMETRY_MAX_TENANTS`` (distinct per-tenant series)
MAX_TENANTS_DEFAULT = 64

#: the fold-over series once the tenant cap is hit
TENANT_OVERFLOW = "serving.tenant.__other__.steps"

_TENANT_PREFIX, _TENANT_SUFFIX = "serving.tenant.", ".steps"


def _capped_tenant_metric(registry: dict, factory, tenant: str,
                          prefix: str, suffix: str, overflow: str):
    """One cardinality-capped per-tenant series out of ``registry``.

    Tenant strings arrive from REQUESTS, so an uncapped per-tenant series
    is an unbounded-memory hole (every distinct string a metric, forever).
    At most ``IGG_TELEMETRY_MAX_TENANTS`` (default `MAX_TENANTS_DEFAULT`)
    distinct tenant series are created per (prefix, suffix) family; once
    the cap is reached, new tenants fold into the shared ``overflow``
    series (existing tenants keep their own).  Family totals stay exact
    either way — only per-tenant attribution degrades past the cap.
    Caller does NOT hold `_lock`.
    """
    name = f"{prefix}{tenant}{suffix}"
    with _lock:
        m = registry.get(name)
        if m is None:
            env = _config.telemetry_max_tenants_env()
            cap = MAX_TENANTS_DEFAULT if env is None else env
            distinct = sum(
                1
                for k in registry
                if k.startswith(prefix) and k.endswith(suffix)
                and k != overflow
            )
            if name != overflow and distinct >= cap:
                name = overflow
                m = registry.get(name)
            if m is None:
                m = registry[name] = factory(name)
        return m


def tenant_counter(tenant: str) -> Counter | _Noop:
    """The ``serving.tenant.<tenant>.steps`` counter, cardinality-capped
    (see `_capped_tenant_metric` for the fold-over contract)."""
    if not enabled():
        return NOOP
    return _capped_tenant_metric(
        _counters, Counter, tenant, _TENANT_PREFIX, _TENANT_SUFFIX,
        TENANT_OVERFLOW,
    )


#: the fold-over series of the front door's per-tenant latency family
FRONTDOOR_TENANT_OVERFLOW = "frontdoor.tenant.__other__.request_seconds"

_FD_TENANT_PREFIX, _FD_TENANT_SUFFIX = "frontdoor.tenant.", ".request_seconds"


def frontdoor_tenant_counter(tenant: str, kind: str) -> Counter | _Noop:
    """``frontdoor.tenant.<tenant>.<kind>`` counter (``kind`` in
    ``admitted``/``rejected`` — the per-tenant admission ledger the
    ``/healthz`` frontdoor section and `scripts/igg_top.py`'s reject-rate
    column read), cardinality-capped like `tenant_counter`."""
    if not enabled():
        return NOOP
    return _capped_tenant_metric(
        _counters, Counter, tenant, _FD_TENANT_PREFIX, f".{kind}",
        f"frontdoor.tenant.__other__.{kind}",
    )


def tenant_histogram(tenant: str) -> Histogram | _Noop:
    """The ``frontdoor.tenant.<tenant>.request_seconds`` histogram,
    cardinality-capped like `tenant_counter` (the per-tenant submit→result
    latency family of `serving.frontdoor`; its rolling window rides the
    ``slo.*`` gauge publication because the name ends in
    ``request_seconds``)."""
    if not enabled():
        return NOOP
    return _capped_tenant_metric(
        _histograms, Histogram, tenant, _FD_TENANT_PREFIX, _FD_TENANT_SUFFIX,
        FRONTDOOR_TENANT_OVERFLOW,
    )


def reset() -> None:
    """Drop every metric and close the event-log descriptors (test hook)."""
    global _rank_hint, _progress
    with _lock:
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        for fd in _event_fds.values():
            try:
                os.close(fd)
            except OSError:
                pass
        _event_fds.clear()
    _rank_hint = None
    _progress = None


# -- Run progress (the live plane's last-step-age source) ---------------------

# The newest completed unit of work of this process — ``{wall, kind, step,
# init, done}`` — written by the instrumented loops (one dict write per
# step) and read by `utils.liveplane`'s ``/healthz`` endpoint and its
# step-stall anomaly rule.  ``init=True`` marks the pre-first-step phase
# (bring-up + first compile: a stall alarm there would be noise);
# ``done=True`` marks a completed run (the server outlives the loop — age
# keeps growing, but nothing is stalled).
_progress: dict | None = None


def note_progress(kind: str, step: int, *, init: bool = False,
                  done: bool = False) -> None:
    """Record the newest completed work unit (see `_progress`)."""
    global _progress
    _progress = {
        "wall": time.time(),
        "kind": kind,
        "step": int(step),
        "init": init,
        "done": done,
    }


def last_progress() -> dict | None:
    """The newest progress record plus its ``age_s``, or None before any."""
    p = _progress
    if p is None:
        return None
    out = dict(p)
    out["age_s"] = time.time() - p["wall"]
    return out


def proc_rss_bytes() -> int | None:
    """This process's resident set size in bytes, or None when unknown.

    ``/proc/self/statm`` (Linux) is the primary source; the
    ``resource.getrusage`` peak-RSS fallback covers platforms without
    procfs (a PEAK, not current — good enough for the growth-rule and
    leak-triage consumers, and graceful absence beats a wrong number).
    """
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        import sys

        maxrss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        if not maxrss:
            return None
        # ru_maxrss is KILOBYTES on Linux/BSD but BYTES on macOS — the
        # platform this fallback exists for (no procfs there)
        return int(maxrss) if sys.platform == "darwin" else int(maxrss) * 1024
    except Exception:
        return None


# -- Identity tagging ---------------------------------------------------------

# Rank during bring-up, BEFORE the runtime can answer `jax.process_index()`:
# `parallel.distributed.init_distributed` stages its resolved process_id here
# so retry/fault events fired mid-bring-up land in the right per-rank file
# with the right tag (otherwise every process would claim rank 0 and write
# into rank 0's events.jsonl — exactly the events most worth attributing).
# Auto-detected pods without an explicit process_id cannot stage a hint; their
# bring-up events fall back to rank 0 (the pid field still disambiguates).
_rank_hint: int | None = None


def set_rank_hint(rank: int | None) -> None:
    """Stage the process rank for event tagging during runtime bring-up."""
    global _rank_hint
    _rank_hint = None if rank is None else int(rank)


def _proc_index() -> int:
    """Process rank without touching an absent runtime (hint/0 during
    bring-up — see `_rank_hint`)."""
    try:
        import jax

        from ..parallel import distributed as _dist

        if _dist.is_distributed_initialized():
            return jax.process_index()
    except Exception:
        pass
    return _rank_hint if _rank_hint is not None else 0


def process_count() -> int:
    """Process count without touching an absent runtime (1 then) — the ONE
    probe behind every "is this multi-process" gate (the SPMD-divergence
    guards in `resilience.RunGuard` and `serving.ServingLoop` key on it)."""
    try:
        import jax

        return jax.process_count()
    except Exception:
        return 1


def _grid_coords() -> list[int] | None:
    try:
        from ..parallel import grid as _grid

        if _grid.grid_is_initialized():
            return list(_grid.global_grid().coords)
    except Exception:
        pass
    return None


# -- Event log ----------------------------------------------------------------


def _event_filename(rank: int) -> str:
    return "events.jsonl" if rank == 0 else f"events.p{rank}.jsonl"


def event(etype: str, **payload: Any) -> None:
    """Append one rank/coords-tagged event line to this process's JSONL log.

    No-op unless telemetry is enabled AND ``IGG_TELEMETRY_DIR`` is set.
    The line is serialized first and written with one ``os.write`` on an
    ``O_APPEND`` descriptor — crash-safe (complete lines or nothing, even
    through an ``os._exit`` right after) and safe against cross-process
    interleaving in a shared directory.  Non-serializable payload values
    are stringified rather than dropped (an event log must never raise out
    of a hot path or a crash handler).
    """
    if not enabled():
        return
    directory = _config.telemetry_dir_env()
    if not directory:
        return
    rank = _proc_index()
    rec = {
        "ts": time.time(),
        "type": etype,
        "rank": rank,
        "pid": os.getpid(),
        "coords": _grid_coords(),
    }
    # Supervised runs thread the incarnation's generation token through
    # every event line (docs/robustness.md): a post-mortem timeline from a
    # shared directory attributes each event to its incarnation, and a
    # zombie's late writes are visibly stale.  Absent when unfenced.
    gen = _config.generation_env()
    if gen is not None:
        rec["gen"] = gen
    # An event emitted inside a request-scoped span inherits that request's
    # trace_id (lazy import: tracing imports telemetry at module scope, so
    # this edge must stay function-local), letting `igg_trace.py request`
    # line events up against a request's causal tree.  Absent outside any
    # request context or when the payload already names one.
    if "trace_id" not in payload:
        from . import tracing as _tracing

        ctx = _tracing.current_context()
        if ctx is not None and "trace_id" in ctx:
            rec["trace_id"] = ctx["trace_id"]
    rec.update(payload)
    try:
        line = json.dumps(rec, default=str) + "\n"
    except (TypeError, ValueError):
        line = json.dumps({k: str(v) for k, v in rec.items()}) + "\n"
    key = (directory, _event_filename(rank))
    try:
        with _lock:
            fd = _event_fds.get(key)
            if fd is None:
                os.makedirs(directory, exist_ok=True)
                fd = os.open(
                    os.path.join(*key),
                    os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                    0o644,
                )
                _event_fds[key] = fd
        os.write(fd, line.encode())
    except OSError:
        pass  # a full/unwritable disk must not take the run down


def atomic_write_json(path: str | os.PathLike, doc, *, fsync: bool = True,
                      indent: int | None = None) -> str:
    """Publish ``doc`` as JSON at ``path`` whole-or-not-at-all: write a
    ``.tmp`` sibling, flush + (by default) fsync, then ONE ``os.replace``.

    The shared crash-safety primitive behind every JSON artifact a consumer
    discovers by path (bench round records, the front door's endpoint file
    and ``resize.json``, the liveplane endpoint file) — round 5's bench
    record was lost to a mid-capture crash precisely because its only copy
    was a half-flushed stream.  ``fsync=False`` trades power-loss safety
    for speed where the artifact is advisory.
    """
    path = os.fspath(path)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=indent, default=str)
        f.flush()
        if fsync:
            os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def read_events(path: str | os.PathLike) -> list[dict]:
    """Parse one JSONL event file (helper for tests/tools); skips any
    torn trailing line."""
    out = []
    with open(os.fspath(path)) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


# -- Snapshot + exposition ----------------------------------------------------


def snapshot() -> dict:
    """The whole registry as plain data (JSON-serializable)."""
    with _lock:
        return {
            "enabled": enabled(),
            "rank": _proc_index(),
            "pid": os.getpid(),
            "coords": _grid_coords(),
            "ts": time.time(),
            "counters": {n: c.value for n, c in _counters.items()},
            "gauges": {n: g.value for n, g in _gauges.items()},
            "histograms": {n: h.summary() for n, h in _histograms.items()},
        }


#: public-API alias (exported as ``igg.telemetry_snapshot``)
telemetry_snapshot = snapshot


def _prom_name(name: str) -> str:
    """Prometheus metric name: ``igg_`` prefix, dots/dashes to underscores."""
    safe = "".join(c if (c.isalnum() or c == "_") else "_" for c in name)
    return f"igg_{safe}"


def prometheus_text(snap: dict | None = None) -> str:
    """Prometheus text exposition (format 0.0.4) of a registry snapshot.

    Counters as ``counter``, gauges as ``gauge``, histograms as ``summary``
    (reservoir quantiles + ``_sum``/``_count``).  Every line group carries
    its ``# TYPE`` header, so standard parsers/scrapers accept the output.
    """
    if snap is None:
        snap = snapshot()
    lines: list[str] = []
    for name, value in sorted(snap.get("counters", {}).items()):
        pn = _prom_name(name) + "_total"
        lines.append(f"# TYPE {pn} counter")
        lines.append(f"{pn} {value}")
    for name, value in sorted(snap.get("gauges", {}).items()):
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} gauge")
        lines.append(f"{pn} {value}")
    for name, s in sorted(snap.get("histograms", {}).items()):
        pn = _prom_name(name)
        lines.append(f"# TYPE {pn} summary")
        for q, key in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
            v = s.get(key)
            if v is not None:
                lines.append(f'{pn}{{quantile="{q}"}} {v}')
        lines.append(f"{pn}_sum {s.get('sum', 0.0)}")
        lines.append(f"{pn}_count {s.get('count', 0)}")
    return "\n".join(lines) + "\n"


def dump_metrics(path: str | os.PathLike) -> tuple[str, str]:
    """Write the registry snapshot as JSON and Prometheus text.

    ``path`` is the basename: ``<path>.json`` and ``<path>.prom`` are
    written (a ``path`` already ending in ``.json`` keeps that name and the
    exposition drops the suffix).  Returns ``(json_path, prom_path)``.
    Exported as ``igg.dump_metrics``.
    """
    path = os.fspath(path)
    base = path[: -len(".json")] if path.endswith(".json") else path
    json_path, prom_path = base + ".json", base + ".prom"
    snap = snapshot()
    d = os.path.dirname(base)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(snap, f, indent=1, default=str)
    with open(prom_path, "w") as f:
        f.write(prometheus_text(snap))
    return json_path, prom_path


# -- Step-loop instrumentation ------------------------------------------------


def teff_bytes(fields: Sequence) -> int:
    """The solver's bytes-moved-per-step model from its must-stream fields.

    Reference perf convention (ParallelStencil/IGG papers; bench.py's
    ``A_eff``): only arrays that MUST stream once in and once out per step
    count, i.e. ``2 * sum(nbytes)`` of the evolving state — reads of
    read-only parameter fields and the halo traffic are free on top, so
    ``T_eff = teff_bytes / t_step`` is a lower bound on achieved traffic.
    Per solver (docs/observability.md): diffusion counts T; acoustic counts
    P, Vx, Vy, Vz; porous convection counts T, Pf, qDx, qDy, qDz.  Sizes
    are the GLOBAL arrays' (aggregate throughput; divide by block count for
    a per-device figure).
    """
    total = 0
    for A in fields:
        nbytes = getattr(A, "nbytes", None)
        if nbytes is None:
            import numpy as np

            nbytes = int(np.prod(A.shape)) * np.dtype(A.dtype).itemsize
        total += int(nbytes)
    return 2 * total


class _StepLoop:
    """Per-step recorder handed to the models' time loops (see `step_loop`)."""

    def __init__(self, model: str, bytes_per_step: int | None,
                 start_step: int, total_steps: int, heartbeat_every: int):
        self.model = model
        self.bytes_per_step = bytes_per_step
        self.total_steps = total_steps
        self.heartbeat_every = heartbeat_every
        self._is_rank0 = _proc_index() == 0
        self._steps = counter(f"{model}.steps")
        self._step_s = histogram(f"{model}.step_seconds")
        self._sps = gauge(f"{model}.steps_per_s")
        self._teff = histogram(f"{model}.t_eff_gbs") if bytes_per_step else None
        self._teff_g = gauge(f"{model}.t_eff_gbs_last") if bytes_per_step else None
        self._t_last = time.perf_counter()
        # last-window accumulator for the all-ranks skew probe (one window
        # per heartbeat interval; docs/observability.md straggler section)
        self._win_sum = 0.0
        self._win_n = 0
        # Live plane (utils.liveplane): bring the per-rank scrape server up
        # (no-op unless IGG_METRICS_PORT is set) and mark the pre-first-step
        # phase so the step-stall rule ignores bring-up/compile time.
        note_progress(model, start_step, init=True)
        from . import liveplane as _liveplane

        _liveplane.ensure_server()
        # Device-timeline capture (utils.profiling, docs/observability.md):
        # armed per run from the step pipeline exactly like the live-plane
        # server above — None unless IGG_PROFILE names a step window.
        from . import profiling as _profiling

        self._profile = _profiling.maybe_arm(start_step)
        event("run.start", model=model, start_step=start_step,
              total_steps=total_steps, bytes_per_step=bytes_per_step)

    def on_step(self, it: int) -> None:
        """Record one completed step (wall time since the previous call)."""
        now = time.perf_counter()
        dt = now - self._t_last
        self._t_last = now
        self._steps.inc()
        self._step_s.record(dt)
        self._win_sum += dt
        self._win_n += 1
        if dt > 0:
            self._sps.set(1.0 / dt)
        gbs = None
        if self._teff is not None and dt > 0:
            gbs = self.bytes_per_step / dt / 1e9
            self._teff.record(gbs)
            self._teff_g.set(gbs)
        note_progress(self.model, it)
        if self._profile is not None:
            self._profile.on_step(it)
        if self.heartbeat_every and it % self.heartbeat_every == 0:
            # The skew probe is a COLLECTIVE: every rank must run it at the
            # same step (hence outside the rank-0 gate below; single-process
            # grids return None without touching any transport).
            skew = None
            if self._win_n:
                from . import tracing as _tracing

                skew = _tracing.skew_probe(self._win_sum / self._win_n)
            self._win_sum = 0.0
            self._win_n = 0
            # Live-plane heartbeat tick on EVERY rank (strictly local — no
            # collectives): publish the proc.rss_bytes gauge and the slo.*
            # windowed quantiles, then evaluate the anomaly rules
            # (docs/observability.md live-plane section).
            rss = proc_rss_bytes()
            if rss is not None:
                gauge("proc.rss_bytes").set(rss)
            from . import liveplane as _liveplane

            _liveplane.heartbeat_tick(model=self.model)
            if self._is_rank0:
                import sys

                teff_s = f" T_eff {gbs:.2f} GB/s" if gbs is not None else ""
                skew_s = (
                    f" skew {skew['ratio']:.2f} (slowest rank "
                    f"{skew['slowest_rank']})" if skew else ""
                )
                print(
                    f"[igg.telemetry] {self.model} step {it}/"
                    f"{self.total_steps} "
                    f"{dt * 1e3:.2f} ms/step {1.0 / dt if dt > 0 else 0.0:.1f} "
                    f"steps/s{teff_s}{skew_s}",
                    file=sys.stderr,
                    flush=True,
                )
                event("heartbeat", model=self.model, step=it,
                      step_seconds=dt, t_eff_gbs=gbs,
                      **_heartbeat_context(skew))

    def finish(self, it: int) -> None:
        if self._profile is not None:
            # a window still open past the last step (nt inside it) stops
            # here so the capture lands with the run's artifacts
            self._profile.close("run_complete")
        note_progress(self.model, it, done=True)
        event("run.complete", model=self.model, step=it)


def _heartbeat_context(skew: dict | None) -> dict:
    """The heartbeat event's extended context (docs/observability.md):
    the current skew gauges (fresh probe result preferred, else the last
    published gauges) and the serving pool occupancy — each attached only
    when something actually recorded it."""
    ctx: dict = {}
    if skew is not None:
        ctx["skew"] = {
            "step_seconds_max_over_min": skew["ratio"],
            "slowest_rank": skew["slowest_rank"],
        }
    else:
        ratio = gauge_value("skew.step_seconds_max_over_min")
        if ratio is not None:
            ctx["skew"] = {
                "step_seconds_max_over_min": ratio,
                "slowest_rank": gauge_value("skew.slowest_rank"),
            }
    active = gauge_value("serving.active_members")
    if active is not None:
        ctx["serving"] = {
            "active_members": active,
            "queue_depth": gauge_value("serving.queue_depth"),
        }
    # The live plane's scrape endpoint, when one is serving: the rank-0
    # heartbeat is the discovery channel for an ephemeral (port 0) bind.
    port = gauge_value("liveplane.port")
    if port is not None:
        ctx["liveplane"] = {"port": int(port)}
    return ctx


def step_loop(
    model: str,
    *,
    bytes_per_step: int | None = None,
    start_step: int = 0,
    total_steps: int = 0,
) -> _StepLoop | None:
    """A per-step recorder for a host-side time loop, or ``None`` disabled.

    The ``None`` return IS the zero-overhead contract: the caller's loop
    guards every telemetry touch behind ``if tele is not None`` and the
    disabled path allocates nothing per step (``tests/test_telemetry.py``
    pins this).  ``bytes_per_step`` (see `teff_bytes`) switches on the
    built-in ``T_eff``; heartbeat cadence comes from ``IGG_HEARTBEAT_EVERY``.
    """
    if not enabled():
        return None
    hb = _config.heartbeat_every_env() or 0
    return _StepLoop(model, bytes_per_step, start_step, total_steps, hb)
