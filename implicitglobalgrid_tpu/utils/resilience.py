"""Resilience layer: guarded bring-up, numerical guards, fault injection.

The reference's whole value proposition is that the 3-function API survives
scaling to thousands of processes (`/root/reference/README.md:12`); at that
scale the failures that dominate are not stencil bugs but *runtime* faults:
coordinator races during multi-host bring-up, a NaN born in one block
silently flooding the global grid through `update_halo`, and preempted
workers losing whole simulations.  This module is the robustness layer a
production stack ships first:

* **Guarded bring-up** — `retry_call` / `backoff_schedule` give
  `parallel.distributed.init_distributed` configurable retry with
  exponential backoff + seeded jitter and an overall deadline
  (``IGG_INIT_RETRIES`` / ``IGG_INIT_TIMEOUT_S`` / ``IGG_INIT_BACKOFF_S``);
  `watchdog` dumps all-thread stacks when a collective hangs (generalizing
  what ``tests/_distributed_worker.py`` hand-rolled).
* **Numerical guards** — `check_fields` runs ONE cheap jitted all-reduce
  isnan/isinf probe per guard point and reports the offending *block
  coordinates*; `RunGuard` applies the ``raise`` | ``warn`` | ``rollback``
  policy inside the models' time loops (``guard_every=N``).
* **Fault injection** — `FaultInjector` parses ``IGG_FAULT_INJECT``
  (``init_flake:N``, ``halo_corrupt:stepN[:blockB]``,
  ``worker_crash:stepN[:procP]``, ``stall:stepN[:procP]``,
  ``net_delay:stepN[:procP]``, ``ckpt_corrupt:stepN[:shardS]``,
  ``ckpt_truncate:stepN[:shardS]``,
  ``bit_flip:stepN[:field|transport|ckpt][:procP]``; several compose
  comma-separated via
  `FaultSet`, and ``chaos:seed=N:rate=R[:steps=M][:kinds=a+b]`` expands
  into a deterministic randomized storm over those kinds —
  `chaos_schedule`) so the 2-process `test_distributed.py` path and
  `scripts/soak.py` can prove crash→restart-from-checkpoint,
  corruption→guard-trip, damaged-generation fallback and the supervised
  multi-fault ``chaos`` drill end to end.

Checkpoint/restart itself lives in `utils.checkpoint`; `RunGuard` drives it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import faulthandler
import os
import random
import sys
import time
import warnings
from typing import Any, Callable, Sequence

import numpy as np

from ..parallel import grid as _grid
from ..parallel.topology import AXIS_NAMES, NDIMS
from . import config as _config
from . import telemetry as _telemetry
from . import tracing as _tracing

__all__ = [
    "GuardError",
    "FieldReport",
    "RunGuard",
    "FaultInjector",
    "FaultSet",
    "backoff_schedule",
    "retry_call",
    "watchdog",
    "check_fields",
    "get_fault_injector",
    "reset_fault_injector",
    "snapshot_state",
    "chaos_schedule",
    "expand_fault_spec",
    "fault_event_matches_spec",
]


# -- Guarded bring-up ---------------------------------------------------------

#: Built-in defaults of the init retry tier (kwarg > ``IGG_*`` env > these).
DEFAULT_INIT_RETRIES = 3
DEFAULT_INIT_TIMEOUT_S = 600.0
DEFAULT_INIT_BACKOFF_S = 1.0
_BACKOFF_CAP_S = 30.0


def backoff_schedule(
    retries: int,
    *,
    base_s: float = DEFAULT_INIT_BACKOFF_S,
    cap_s: float = _BACKOFF_CAP_S,
    jitter: float = 0.5,
    seed: int | None = None,
) -> list[float]:
    """Exponential backoff delays for ``retries`` re-attempts.

    Delay ``i`` is ``min(base * 2**i, cap)`` stretched by a uniform jitter in
    ``[1, 1 + jitter]`` — jitter de-synchronizes thousands of workers
    hammering a coordinator after a correlated failure (the thundering-herd
    fix), and seeding it makes schedules reproducible in tests.  ``seed``
    defaults to this process's index when the runtime is up; during bring-up
    `init_distributed` passes its ``process_id`` through instead (an
    auto-detected pod without one falls back to a shared seed — spread-out
    retries need the explicit id).
    """
    if retries < 0:
        raise ValueError(f"retries must be >= 0 (got {retries})")
    if base_s <= 0 or cap_s <= 0:
        raise ValueError(f"base_s and cap_s must be > 0 (got {base_s}, {cap_s})")
    if jitter < 0:
        raise ValueError(f"jitter must be >= 0 (got {jitter})")
    if seed is None:
        seed = _safe_process_index()
    rng = random.Random(seed)
    return [
        min(base_s * (2.0**i), cap_s) * (1.0 + rng.uniform(0.0, jitter))
        for i in range(retries)
    ]


def _safe_process_index() -> int:
    """Process index without touching the (possibly absent) runtime."""
    try:
        import jax

        from ..parallel import distributed as _dist

        if _dist.is_distributed_initialized():
            return jax.process_index()
    except Exception:
        pass
    return 0


def retry_call(
    fn: Callable[[], Any],
    *,
    retries: int = DEFAULT_INIT_RETRIES,
    timeout_s: float | None = DEFAULT_INIT_TIMEOUT_S,
    base_backoff_s: float = DEFAULT_INIT_BACKOFF_S,
    jitter: float = 0.5,
    seed: int | None = None,
    describe: str = "operation",
    on_retry: Callable[[int, BaseException, float], None] | None = None,
    sleep: Callable[[float], None] = time.sleep,
    clock: Callable[[], float] = time.monotonic,
) -> Any:
    """Call ``fn`` with up to ``retries`` re-attempts under a deadline.

    ``timeout_s`` is an *overall* deadline across attempts: a retry whose
    backoff would cross it is not taken (a hang inside one attempt cannot be
    interrupted from Python — arm `watchdog` for that).  ``on_retry(attempt,
    error, delay)`` observes each failure; the default logs to stderr.
    Raises the last error, annotated with the attempt count and deadline.
    """
    if timeout_s is not None and timeout_s <= 0:
        raise ValueError(f"timeout_s must be > 0 (got {timeout_s})")
    delays = backoff_schedule(
        retries, base_s=base_backoff_s, jitter=jitter, seed=seed
    )
    t0 = clock()
    last: BaseException | None = None
    for attempt in range(retries + 1):
        try:
            return fn()
        except (KeyboardInterrupt, SystemExit, GeneratorExit):
            # Deliberate shutdown is not a flaky bring-up: never retry it.
            raise
        except BaseException as e:
            last = e
            if attempt >= retries:
                break
            delay = delays[attempt]
            elapsed = clock() - t0
            if timeout_s is not None and elapsed + delay > timeout_s:
                raise RuntimeError(
                    f"{describe} failed after {attempt + 1} attempt(s) in "
                    f"{elapsed:.1f}s; the overall deadline "
                    f"(timeout_s={timeout_s}, IGG_INIT_TIMEOUT_S) leaves no "
                    f"room for another retry. Last error: {e!r}"
                ) from e
            # Machine-readable retry record (docs/observability.md): the
            # soak/ops timeline needs every bring-up retry, not just stderr.
            _telemetry.event(
                "retry",
                what=describe,
                attempt=attempt + 1,
                of=retries + 1,
                delay_s=delay,
                error=repr(e),
            )
            _telemetry.counter("resilience.retries").inc()
            if on_retry is not None:
                on_retry(attempt, e, delay)
            else:
                print(
                    f"[igg.resilience] {describe} attempt {attempt + 1}/"
                    f"{retries + 1} failed ({e!r}); retrying in {delay:.2f}s",
                    file=sys.stderr,
                    flush=True,
                )
            sleep(delay)
    raise RuntimeError(
        f"{describe} failed after {retries + 1} attempt(s) "
        f"(retries={retries}, IGG_INIT_RETRIES). Last error: {last!r}"
    ) from last


# faulthandler keeps ONE process-wide timer; this stack makes nested
# watchdogs well-behaved AND strictest-wins: arming/exiting re-arms with the
# SMALLEST timeout on the stack and the OR of the exit flags, so an inner
# watchdog with a laxer deadline (e.g. init_distributed's 600 s default
# inside a test worker's 270 s exit=True watchdog) can never weaken the
# enclosing one.  Each re-arm restarts the timer, so an outer deadline can
# extend by at most one inner scope's duration — bounded, and strictly
# tighter than the pre-stack behavior (inner exit silently DISARMED the
# outer watchdog entirely).
_watchdog_stack: list[tuple[float, bool, Any]] = []


def arm_watchdog(timeout_s: float, *, exit: bool = False, file=None) -> None:
    """Arm the stack-dump watchdog for the remaining process lifetime.

    For linear scripts (test workers) where a ``with`` block is awkward;
    pair with `disarm_watchdog` or let it ride until process exit.
    """
    _watchdog_stack.append((float(timeout_s), exit, file))
    _rearm()


def disarm_watchdog() -> None:
    _watchdog_stack.pop() if _watchdog_stack else None
    _rearm()


def _rearm() -> None:
    if not _watchdog_stack:
        faulthandler.cancel_dump_traceback_later()
        return
    # The entry whose deadline will actually fire supplies the dump stream.
    timeout_s, _, file = min(_watchdog_stack, key=lambda e: e[0])
    kwargs = {"exit": any(e for _, e, _ in _watchdog_stack)}
    if file is not None:
        kwargs["file"] = file
    faulthandler.dump_traceback_later(timeout_s, **kwargs)


@contextlib.contextmanager
def watchdog(timeout_s: float | None, *, exit: bool = False, file=None):
    """Dump all-thread stack traces if the enclosed block runs past ``timeout_s``.

    The collective-hang debugging tool: a deadlocked `psum`/`ppermute` (one
    process missing from a collective) blocks in C++ where Python sees
    nothing — `faulthandler.dump_traceback_later` fires from a watchdog
    thread and shows every thread's stack, and ``exit=True`` also kills the
    process so an orchestrator can restart it (generalizes the hand-rolled
    watchdog in ``tests/_distributed_worker.py``).  ``timeout_s=None``/0
    disarms (the ``IGG_WATCHDOG_S``-unset path).  faulthandler keeps one
    process-wide timer, so nesting is strictest-wins: the smallest timeout
    on the watchdog stack is armed and ``exit`` flags OR together — an
    inner watchdog can tighten but never weaken an enclosing one.
    """
    if not timeout_s:
        yield
        return
    arm_watchdog(timeout_s, exit=exit, file=file)
    t0 = time.monotonic()
    try:
        yield
    finally:
        disarm_watchdog()
        elapsed = time.monotonic() - t0
        if elapsed > timeout_s:
            # The block outlived this watchdog's deadline — the closest
            # observable proxy for "the dump fired" (faulthandler cannot
            # call back into Python).  NOT a guarantee: nested scopes
            # re-arm the one process-wide timer (`_rearm`), so the timer
            # may never have run `timeout_s` continuously; the stderr dump
            # is the ground truth, this event is the timeline marker.
            _telemetry.event(
                "watchdog.deadline_exceeded",
                timeout_s=timeout_s,
                elapsed_s=elapsed,
            )
            _telemetry.counter("resilience.watchdog_deadline_exceeded").inc()
            # Flight-recorder bundle (docs/observability.md): a blown
            # watchdog deadline is exactly the moment an operator needs
            # the span ring + metrics + config of this rank on disk.
            _tracing.dump_flight_recorder(
                "watchdog.deadline_exceeded",
                timeout_s=timeout_s,
                elapsed_s=elapsed,
            )


# -- Numerical guards ---------------------------------------------------------


class GuardError(RuntimeError):
    """A NaN/Inf guard tripped.  Carries the step and the offending blocks."""

    def __init__(self, message: str, *, step: int | None = None, report=None):
        super().__init__(message)
        self.step = step
        self.report = report


@dataclasses.dataclass(frozen=True)
class FieldReport:
    """Result of one `check_fields` probe.

    ``bad_blocks`` maps field name -> tuple of block ``coords`` (Cartesian
    mesh coordinates, the reference's ``coords``) holding at least one
    non-finite value.  Replicated across processes: every rank sees the
    same report and can take the same policy action.
    """

    names: tuple[str, ...]
    bad_blocks: dict[str, tuple[tuple[int, ...], ...]]

    @property
    def ok(self) -> bool:
        return not self.bad_blocks

    def summary(self) -> str:
        if self.ok:
            return f"all finite ({', '.join(self.names)})"
        parts = [
            f"{name}: block(s) {', '.join(str(c) for c in coords)}"
            for name, coords in self.bad_blocks.items()
        ]
        return "non-finite values in " + "; ".join(parts)


_probe_cache: dict = {}


def _clear_caches() -> None:
    _probe_cache.clear()


def _probe_fn(gg, shapes_dtypes):
    """Build (and cache) the jitted per-block finite probe.

    One program per (epoch, signature): each block reduces its fields to a
    per-field bad flag, scatters it into a ``dims``-shaped one-hot and
    `psum`s over all mesh axes — the result is a tiny REPLICATED
    ``(nfields, *dims)`` flag array every process can read without extra
    communication (the all-reduce rides the same compiled collectives as a
    step).  Cost: one elementwise isfinite pass + an all-reduce of
    ``nfields * prod(dims)`` int32s.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = (gg.epoch, shapes_dtypes)
    fn = _probe_cache.get(key)
    if fn is not None:
        return fn

    def block_flags(*fields):
        flags = []
        for A in fields:
            if jnp.issubdtype(A.dtype, jnp.inexact):
                bad = jnp.any(~jnp.isfinite(A)).astype(jnp.int32)
            else:
                bad = jnp.int32(0)  # integer fields cannot hold NaN/Inf
            flags.append(bad)
        return jnp.stack(flags)

    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(lambda *f: block_flags(*f).reshape((len(shapes_dtypes), 1, 1, 1)))
        _probe_cache[key] = fn
        return fn

    def per_block(*fields):
        flags = block_flags(*fields)  # (nfields,)
        onehot = jnp.zeros((len(shapes_dtypes), *gg.dims), jnp.int32)
        for i, (shp, _) in enumerate(shapes_dtypes):
            # Coordinates only over the FIELD's own dimensions: a lower-rank
            # field is replicated along the remaining mesh axes, and using
            # the device's full 3-D coords would report one phantom bad
            # block per replica (the replicas scatter at distinct cz).
            # Clamping those axes to 0 makes every replica-holding device
            # scatter at the same logical coords (the count psums up > 1,
            # which argwhere treats the same as 1).
            coords = tuple(
                lax.axis_index(AXIS_NAMES[d])
                if d < len(shp) and gg.dims[d] > 1
                else jnp.int32(0)
                for d in range(NDIMS)
            )
            onehot = lax.dynamic_update_slice(
                onehot,
                flags[i].reshape((1, 1, 1, 1)),
                (jnp.int32(i), *coords),
            )
        # psum over every mesh axis -> replicated on all devices/processes.
        return lax.psum(onehot, AXIS_NAMES)

    specs = tuple(P(*AXIS_NAMES[: len(s)]) for s, _ in shapes_dtypes)
    mapped = shard_map(
        per_block, mesh=gg.mesh, in_specs=specs, out_specs=P(), check_vma=False
    )
    fn = jax.jit(mapped)
    _probe_cache[key] = fn
    return fn


def check_fields(*fields, names: Sequence[str] | None = None) -> FieldReport:
    """Probe global-block field(s) for NaN/Inf; report offending blocks.

    The numerical-guard API (`igg.check_fields`): one cheap jitted
    all-reduce isnan/isinf pass over the given fields.  Returns a
    `FieldReport` whose ``bad_blocks`` names the Cartesian ``coords`` of
    every block holding a non-finite value — the information an operator
    needs to localize the fault on a pod (which host, which block), which a
    plain ``jnp.isnan(A).any()`` on the global array cannot give.

    Works on concrete global-block arrays (the models' time loops, any
    host-side loop).  Multi-host safe: the probe result is replicated, so
    every process sees the same report and the ``rollback`` policy cannot
    diverge across ranks.
    """
    from ..ops.halo import local_shape

    _grid.check_initialized()
    gg = _grid.global_grid()
    if not fields:
        raise ValueError("check_fields requires at least one field.")
    if names is None:
        names = tuple(f"field{i}" for i in range(len(fields)))
    else:
        names = tuple(names)
        if len(names) != len(fields):
            raise ValueError(
                f"names has {len(names)} entries for {len(fields)} fields."
            )
    sig = tuple((local_shape(A, gg), str(A.dtype)) for A in fields)
    flags = np.asarray(_probe_fn(gg, sig)(*fields))
    bad: dict[str, tuple[tuple[int, ...], ...]] = {}
    for i, name in enumerate(names):
        coords = tuple(tuple(int(c) for c in idx) for idx in np.argwhere(flags[i]))
        if coords:
            bad[name] = coords
    return FieldReport(names=names, bad_blocks=bad)


# -- Fault injection ----------------------------------------------------------

FAULT_KINDS = (
    "init_flake",
    "halo_corrupt",
    "worker_crash",
    "stall",
    "net_delay",
    "ckpt_corrupt",
    "ckpt_truncate",
    "bit_flip",
)

#: third spec component's prefix per fault kind (e.g. ``halo_corrupt:step3:block5``)
_TARGET_PREFIX = {
    "halo_corrupt": "block",
    "worker_crash": "proc",
    "stall": "proc",
    "net_delay": "proc",
    "ckpt_corrupt": "shard",
    "ckpt_truncate": "shard",
    "bit_flip": "proc",
}

#: ``bit_flip``'s reserved (non-field-name) placement components
BIT_FLIP_PLACEMENTS = ("transport", "ckpt")

#: kinds the seeded chaos schedule samples from by default (init_flake
#: excluded: it fires during bring-up, outside the per-step storm the
#: schedule models; bit_flip excluded from the DEFAULT draw because it is
#: guard-invisible — a storm that lands one in a run without the integrity
#: plane armed silently falsifies the result instead of exercising recovery.
#: ``kinds=…+bit_flip`` opts a storm in explicitly when ``IGG_INTEGRITY``
#: detectors are armed.)
CHAOS_KINDS = (
    "worker_crash",
    "stall",
    "net_delay",
    "ckpt_corrupt",
    "ckpt_truncate",
    "halo_corrupt",
)

#: chaos-mode defaults (spec grammar: ``chaos:seed=N:rate=R[:steps=M][:kinds=a+b]``)
CHAOS_STEPS_DEFAULT = 16


def chaos_schedule(
    seed: int,
    rate: float,
    *,
    steps: int = CHAOS_STEPS_DEFAULT,
    kinds: Sequence[str] = CHAOS_KINDS,
) -> list[str]:
    """The deterministic randomized fault storm of one chaos spec.

    Samples at most ONE fault per time-loop step (unambiguous
    ``(kind, step)`` identity — what lets a supervisor match ``fault.*``
    events back to the armed schedule and prune fired faults across
    relaunches): for each step ``1..steps``, with probability ``rate`` a
    kind is drawn uniformly from ``kinds``.  Pure function of its
    arguments (`random.Random(seed)`), so the supervisor, the soak driver
    and a test all derive the identical storm from the spec alone.
    Targets stay at each kind's default (crash/stall/delay: the last
    process; ckpt damage: shard 0; corruption: block 0).
    """
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"chaos rate must be in [0, 1] (got {rate})")
    if steps < 1:
        raise ValueError(f"chaos steps must be >= 1 (got {steps})")
    bad = [k for k in kinds if k not in FAULT_KINDS or k == "init_flake"]
    if bad:
        raise ValueError(
            f"chaos kinds {bad} not samplable; choose from {CHAOS_KINDS}"
        )
    rng = random.Random(seed)
    out = []
    for step in range(1, steps + 1):
        if rng.random() < rate:
            out.append(f"{rng.choice(list(kinds))}:step{step}")
    return out


def _parse_chaos_spec(spec: str) -> list[str]:
    """``chaos:seed=N:rate=R[:steps=M][:kinds=a+b]`` -> concrete specs."""
    fields: dict[str, str] = {}
    for part in spec.split(":")[1:]:
        key, sep, val = part.partition("=")
        if not sep or key not in ("seed", "rate", "steps", "kinds"):
            raise ValueError(
                f"IGG_FAULT_INJECT: {spec!r} — chaos takes "
                f"'chaos:seed=N:rate=R[:steps=M][:kinds=a+b]' "
                f"(got component {part!r})."
            )
        fields[key] = val
    try:
        seed = int(fields["seed"])
        rate = float(fields["rate"])
        steps = int(fields.get("steps", CHAOS_STEPS_DEFAULT))
    except (KeyError, ValueError):
        raise ValueError(
            f"IGG_FAULT_INJECT: {spec!r} — chaos needs integer seed=, "
            f"decimal rate= (and optional integer steps=)."
        )
    kinds = (
        tuple(fields["kinds"].split("+")) if "kinds" in fields else CHAOS_KINDS
    )
    return chaos_schedule(seed, rate, steps=steps, kinds=kinds)


def expand_fault_spec(spec: str | None) -> list[str]:
    """A comma-separated ``IGG_FAULT_INJECT`` value as CONCRETE per-fault
    specs, ``chaos:`` parts expanded through `chaos_schedule` — the form a
    supervisor arms, prunes (`fault_event_matches_spec`) and re-arms."""
    if not spec:
        return []
    out: list[str] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if part.startswith("chaos:") or part == "chaos":
            out.extend(_parse_chaos_spec(part))
        else:
            FaultInjector.from_spec(part)  # validate eagerly
            out.append(part)
    return out


def fault_event_matches_spec(events: Sequence[dict], spec: str) -> bool:
    """Did one of these ``fault.*`` event records fire THIS concrete spec?

    The supervisor's cross-incarnation fire-once hygiene: a fault whose
    event is on the timeline is pruned from the next incarnation's
    environment (a crash at step N must not re-crash the restart that
    resumes from the step-N checkpoint).  Identity is ``(kind, step)``
    (`chaos_schedule` guarantees uniqueness); ``init_flake`` matches on
    any firing.
    """
    inj = FaultInjector.from_spec(spec)
    etype = f"fault.{inj.kind}"
    for e in events:
        if e.get("type") != etype:
            continue
        if inj.kind == "init_flake":
            return True
        if e.get("step") == inj.step:
            return True
    return False


@dataclasses.dataclass
class FaultInjector:
    """Deterministic fault injection, armed via ``IGG_FAULT_INJECT``.

    Spec grammar (see docs/robustness.md):

    * ``init_flake:N`` — the first ``N`` `init_distributed` attempts raise
      (simulated coordinator race); attempt ``N+1`` proceeds.  Proves the
      retry/backoff path end to end.
    * ``halo_corrupt:stepN[:blockB]`` — after time-loop step ``N``, a NaN is
      written into an interior cell of block ``B`` (Cartesian rank, default
      0).  Every process executes the same scatter (the target index is
      derived from the block's coords, which all ranks can compute), so the
      injection stays SPMD-consistent on multi-host runs.  Proves
      corruption→guard-trip.
    * ``worker_crash:stepN[:procP]`` — after time-loop step ``N`` (and after
      that step's checkpoint), process ``P`` (default: the last process)
      exits hard with status 17.  Proves crash→restart-from-checkpoint.
    * ``stall:stepN[:procP]`` — after time-loop step ``N``, process ``P``
      (default: the last process) sleeps `STALL_S` seconds before
      continuing — a transient hang, NOT a crash.  On a communicating grid
      every rank's loop wedges with it (the neighbors block in the next
      collective), which is exactly the condition the live plane's
      scrape-time step-stall rule (`utils.liveplane.StepStallRule`) exists
      to see from outside the loop; the soak ``live_plane`` scenario
      drives this end to end.
    * ``net_delay:stepN[:procP]`` — after time-loop step ``N``, process
      ``P`` (default: the last process) arms `NET_DELAY_S` seconds of
      latency on its NEXT host control collective (the skew-probe /
      ``broadcast_control`` transport, `utils.tracing.
      arm_collective_delay`): the rank enters the collective late and its
      peers block with it — a transient network fault that recovers on
      its own (the chaos storm's benign kind).
    * ``ckpt_corrupt:stepN[:shardS]`` — right after the step-``N`` checkpoint
      publishes, a byte of shard file ``S`` (default 0) is flipped WITHOUT
      updating the manifest (process 0 applies it).  Proves the CRC
      verification + generation fallback of `utils.checkpoint`.
    * ``ckpt_truncate:stepN[:shardS]`` — same, but the shard file is
      truncated to half its size (a torn write).
    * ``bit_flip:stepN[:field|transport|ckpt][:procP]`` — silent data
      corruption: ONE mantissa LSB flips, producing a perfectly FINITE
      wrong value that `check_fields` can never see (``halo_corrupt`` is
      its guard-VISIBLE twin — same injection point, NaN payload).  The
      optional placement component picks the detector under test: a FIELD
      NAME (or omitted: field 0) flips an interior cell of the committed
      post-step state — caught only by the shadow-step audit
      (``IGG_INTEGRITY_EVERY``); ``transport`` arms a payload-word flip on
      rank ``P``'s next checksummed halo hop (`ops.halo.
      arm_transport_flip`) — caught by the RECEIVER's transport checksum,
      implicating the sender; ``ckpt`` flips one payload byte after the
      lineage digests are taken but before the shard writer runs — CRC
      verifies clean (the bytes on disk are intact), only the lineage
      chain convicts the generation as poisoned-at-save.

    Each fault fires once per injector (a rolled-back or restarted run does
    not re-trip), mirroring how real transient faults behave.  Several
    faults compose as a comma-separated spec (parsed by `FaultSet`), e.g.
    ``worker_crash:step4:proc1,ckpt_corrupt:step4`` — crash AND damaged
    newest generation in one run, the elastic-failover drill.
    """

    kind: str | None = None
    step: int | None = None
    target: int | None = None  # halo_corrupt: block rank; worker_crash: process
    count: int = 0  # init_flake: remaining flaky attempts
    fired: bool = False
    #: bit_flip placement: a field NAME, "transport", "ckpt", or None (field 0)
    field: str | None = None

    #: exit status of an injected worker crash (distinct from real crashes)
    CRASH_STATUS = 17

    #: injected-stall duration in seconds (class attr: tests shrink it)
    STALL_S = 6.0

    #: injected host-collective latency in seconds (class attr: tests shrink)
    NET_DELAY_S = 1.5

    def spec(self) -> str:
        """The canonical spec string this injector parses back from (the
        supervisor's arm/prune round-trip)."""
        if self.kind is None:
            return ""
        if self.kind == "init_flake":
            return f"init_flake:{self.count}"
        out = f"{self.kind}:step{self.step}"
        if self.kind == "bit_flip" and self.field is not None:
            out += f":{self.field}"
        if self.target is not None:
            out += f":{_TARGET_PREFIX[self.kind]}{self.target}"
        return out

    @classmethod
    def from_spec(cls, spec: str | None) -> "FaultInjector":
        if not spec:
            return cls()
        parts = spec.split(":")
        kind = parts[0]
        if kind not in FAULT_KINDS:
            raise ValueError(
                f"IGG_FAULT_INJECT: unknown fault kind {kind!r} in {spec!r}; "
                f"accepted kinds: {', '.join(FAULT_KINDS)} (format: "
                f"'init_flake:N' or 'halo_corrupt:stepN[:blockB]' or "
                f"'worker_crash:stepN[:procP]')."
            )
        if kind == "init_flake":
            if len(parts) != 2 or not parts[1].isdigit():
                raise ValueError(
                    f"IGG_FAULT_INJECT: {spec!r} — init_flake takes "
                    f"'init_flake:N' with N a non-negative integer count of "
                    f"attempts to fail."
                )
            return cls(kind=kind, count=int(parts[1]))
        tgt_prefix = _TARGET_PREFIX[kind]
        if kind == "bit_flip":
            if len(parts) not in (2, 3, 4) or not parts[1].startswith("step"):
                raise ValueError(
                    f"IGG_FAULT_INJECT: {spec!r} — bit_flip takes "
                    f"'bit_flip:stepN[:field|transport|ckpt][:procP]' with N "
                    f"the 1-based time-loop step."
                )
            try:
                step = int(parts[1][len("step"):])
            except ValueError:
                raise ValueError(
                    f"IGG_FAULT_INJECT: {spec!r} — step must be an integer, "
                    f"got {parts[1][len('step'):]!r}."
                )
            field = None
            target = None
            for comp in parts[2:]:
                if comp.startswith("proc") and comp[len("proc"):].isdigit():
                    if target is not None:
                        raise ValueError(
                            f"IGG_FAULT_INJECT: {spec!r} — bit_flip takes at "
                            f"most one 'procP' component."
                        )
                    target = int(comp[len("proc"):])
                elif comp.isdigit():
                    # A bare integer is ambiguous (field index? rank?) and
                    # would silently mis-read as a target on the other
                    # kinds' grammar — demand the explicit form.
                    raise ValueError(
                        f"IGG_FAULT_INJECT: {spec!r} — bare integer "
                        f"{comp!r} is not a bit_flip placement; name the "
                        f"FIELD (e.g. ':T'), a reserved placement "
                        f"(':transport' or ':ckpt'), or the target rank as "
                        f"':proc{comp}'."
                    )
                elif field is None:
                    field = comp
                else:
                    raise ValueError(
                        f"IGG_FAULT_INJECT: {spec!r} — bit_flip takes at "
                        f"most one placement component (field name, "
                        f"'transport' or 'ckpt'); got both {field!r} and "
                        f"{comp!r}."
                    )
            return cls(kind=kind, step=step, target=target, field=field)
        if len(parts) not in (2, 3) or not parts[1].startswith("step"):
            raise ValueError(
                f"IGG_FAULT_INJECT: {spec!r} — {kind} takes "
                f"'{kind}:stepN[:{tgt_prefix}P]' with N the 1-based "
                f"time-loop step."
            )
        try:
            step = int(parts[1][len("step"):])
        except ValueError:
            raise ValueError(
                f"IGG_FAULT_INJECT: {spec!r} — step must be an integer, "
                f"got {parts[1][len('step'):]!r}."
            )
        target = None
        if len(parts) == 3:
            if not parts[2].startswith(tgt_prefix):
                what = {
                    "block": "a block rank.",
                    "proc": "a process index.",
                    "shard": "a shard (writer process) index.",
                }[tgt_prefix]
                raise ValueError(
                    f"IGG_FAULT_INJECT: {spec!r} — the third component must "
                    f"be '{tgt_prefix}P' with P " + what
                )
            try:
                target = int(parts[2][len(tgt_prefix):])
            except ValueError:
                raise ValueError(
                    f"IGG_FAULT_INJECT: {spec!r} — {tgt_prefix} must be an "
                    f"integer, got {parts[2][len(tgt_prefix):]!r}."
                )
        return cls(kind=kind, step=step, target=target)

    @property
    def active(self) -> bool:
        return self.kind is not None

    # - init_flake -

    def maybe_flake_init(self) -> None:
        """Raise a simulated coordinator race while flaky attempts remain."""
        if self.kind == "init_flake" and self.count > 0:
            self.count -= 1
            _telemetry.event("fault.init_flake", remaining=self.count)
            raise RuntimeError(
                "IGG_FAULT_INJECT(init_flake): simulated coordinator race "
                f"({self.count} flaky attempt(s) remaining)"
            )

    # - halo_corrupt -

    def maybe_corrupt(self, state: tuple, step: int) -> tuple:
        """After step ``step``: NaN-poison one interior cell of the target block.

        Runs identically on EVERY process (same scatter, same global index),
        so multi-host programs stay SPMD-consistent; only the target block's
        owner actually holds the poisoned cell.
        """
        if self.kind != "halo_corrupt" or self.fired or step != self.step:
            return state
        self.fired = True
        A = self._poison_block(state[0], announce_step=step)
        return (A, *state[1:])

    def _poison_block(self, A, announce_step=None):
        import jax.numpy as jnp

        idx = _block_interior_index(A, self.target or 0)
        _telemetry.event(
            "fault.halo_corrupt",
            index=list(int(i) for i in idx),
            block=self.target or 0,
            step=announce_step if announce_step is not None else self.step,
        )
        if _safe_process_index() == 0:
            at = "" if announce_step is None else f" after step {announce_step}"
            print(
                f"[igg.resilience] IGG_FAULT_INJECT(halo_corrupt): writing "
                f"NaN into global index {tuple(idx)} (block "
                f"{self.target or 0}){at}",
                file=sys.stderr,
                flush=True,
            )
        return A.at[idx].set(jnp.nan)

    def corrupt_halo_hook(self, fields: tuple) -> tuple:
        """`ops.halo` post-exchange hook: poison direct `update_halo` output.

        Step-agnostic (direct halo calls carry no step): fires on the first
        exchange after arming.  Installed by the pytest ``fault_injection``
        fixture / `install_halo_fault_hook`.
        """
        if self.kind != "halo_corrupt" or self.fired:
            return fields
        self.fired = True
        return (self._poison_block(fields[0]), *fields[1:])

    # - worker_crash -

    def maybe_crash(self, step: int) -> None:
        """After step ``step``'s guard+checkpoint: hard-exit this process."""
        if self.kind != "worker_crash" or self.fired or step != self.step:
            return  # cheap short-circuit: this runs every step of every loop
        want = self.target if self.target is not None else _last_process_index()
        if _safe_process_index() != want:
            return
        self.fired = True
        # The event line is a single O_APPEND os.write — it survives the
        # os._exit below, which is exactly what the failover drill's
        # machine-readable timeline needs (the crash marker).
        _telemetry.event(
            "fault.worker_crash", step=step, status=self.CRASH_STATUS
        )
        # Same discipline for the flight bundle: one complete line on disk
        # BEFORE the hard exit (the soak drill verifies it exists).
        _tracing.dump_flight_recorder(
            "fault.worker_crash", step=step, status=self.CRASH_STATUS
        )
        print(
            f"[igg.resilience] IGG_FAULT_INJECT(worker_crash): exiting hard "
            f"after step {step} (status {self.CRASH_STATUS})",
            file=sys.stderr,
            flush=True,
        )
        sys.stderr.flush()
        sys.stdout.flush()
        os._exit(self.CRASH_STATUS)

    # - stall -

    def maybe_stall(self, step: int) -> None:
        """After step ``step``: the target process sleeps `STALL_S` seconds.

        The event line lands BEFORE the sleep (the timeline marker an
        operator correlates the live-plane ``alert.step_stall`` against).
        """
        if self.kind != "stall" or self.fired or step != self.step:
            return
        want = self.target if self.target is not None else _last_process_index()
        if _safe_process_index() != want:
            return
        self.fired = True
        _telemetry.event("fault.stall", step=step, sleep_s=self.STALL_S)
        print(
            f"[igg.resilience] IGG_FAULT_INJECT(stall): sleeping "
            f"{self.STALL_S}s after step {step}",
            file=sys.stderr,
            flush=True,
        )
        time.sleep(self.STALL_S)

    # - net_delay -

    def maybe_net_delay(self, step: int) -> None:
        """After step ``step``: arm `NET_DELAY_S` of latency on the target
        process's NEXT host control collective (`utils.tracing.
        arm_collective_delay` — the skew-probe / `broadcast_control`
        transport).  A transient network fault, not a hang: the delayed
        rank enters the collective late, its peers block with it, and the
        skew probe sees the straggle — nothing needs supervisor
        intervention, which is exactly what a chaos storm needs between
        the faults that do."""
        if self.kind != "net_delay" or self.fired or step != self.step:
            return
        want = self.target if self.target is not None else _last_process_index()
        if _safe_process_index() != want:
            return
        self.fired = True
        _telemetry.event("fault.net_delay", step=step, delay_s=self.NET_DELAY_S)
        print(
            f"[igg.resilience] IGG_FAULT_INJECT(net_delay): delaying the "
            f"next host control collective by {self.NET_DELAY_S}s "
            f"(after step {step})",
            file=sys.stderr,
            flush=True,
        )
        _tracing.arm_collective_delay(self.NET_DELAY_S)

    # - ckpt_corrupt / ckpt_truncate -

    def maybe_damage_checkpoint(self, step_dir: str, step: int) -> None:
        """After the step-``step`` checkpoint published: damage one shard.

        Called by `utils.checkpoint.save_checkpoint` on process 0 right
        after the atomic rename — the manifest already vouches for the
        intact bytes, so the damage is exactly what `verify_checkpoint`
        exists to catch.  ``ckpt_corrupt`` flips one byte mid-file (CRC
        mismatch); ``ckpt_truncate`` halves the file (size mismatch).
        """
        if (
            self.kind not in ("ckpt_corrupt", "ckpt_truncate")
            or self.fired
            or step != self.step
        ):
            return
        self.fired = True
        shard = os.path.join(step_dir, f"shards_p{self.target or 0}.npz")
        size = os.path.getsize(shard)
        if self.kind == "ckpt_truncate":
            os.truncate(shard, size // 2)
            what = f"truncated to {size // 2} of {size} bytes"
        else:
            with open(shard, "r+b") as f:
                f.seek(size // 2)
                byte = f.read(1)
                f.seek(size // 2)
                f.write(bytes([byte[0] ^ 0xFF]))
            what = f"flipped byte at offset {size // 2}"
        _telemetry.event(
            f"fault.{self.kind}", step=step, shard=self.target or 0, what=what
        )
        print(
            f"[igg.resilience] IGG_FAULT_INJECT({self.kind}): {what} in "
            f"{shard} after step {step}",
            file=sys.stderr,
            flush=True,
        )

    # - bit_flip -

    def _bit_flip_armed(self, step: int, placement: str | None) -> bool:
        """Does this injector's bit_flip fire at ``step`` for ``placement``
        (None = the state placement: any non-reserved ``field``)?"""
        if self.kind != "bit_flip" or self.fired or step != self.step:
            return False
        if placement is None:
            return self.field not in BIT_FLIP_PLACEMENTS
        return self.field == placement

    def maybe_bit_flip(self, state: tuple, step: int,
                       names: Sequence[str] | None = None) -> tuple:
        """State placement: after step ``step``, flip ONE mantissa LSB of an
        interior cell of the committed state — a finite wrong value, by
        construction invisible to the NaN/Inf guard (``halo_corrupt`` is the
        guard-visible twin).  Only the shadow-step audit can convict it.
        Runs identically on every process (same scatter, same global index),
        like `maybe_corrupt`.
        """
        if not self._bit_flip_armed(step, None):
            return state
        fidx = 0
        if self.field is not None:
            if names is None or self.field not in tuple(names):
                have = ", ".join(map(repr, names)) if names else "(unnamed)"
                raise ValueError(
                    f"IGG_FAULT_INJECT(bit_flip): field {self.field!r} does "
                    f"not exist in this run — the guarded state carries "
                    f"{have}. The spec is 'bit_flip:stepN[:field|transport|"
                    f"ckpt][:procP]'; a field component must name one of the "
                    f"run's fields."
                )
            fidx = tuple(names).index(self.field)
        self.fired = True
        A = self._flip_state_cell(state[fidx], step, fidx)
        return (*state[:fidx], A, *state[fidx + 1:])

    def _flip_state_cell(self, A, step: int, fidx: int):
        import jax.numpy as jnp
        from jax import lax

        from ..ops.gather import _word_dtype

        idx = _block_interior_index(A, self.target or 0)
        _telemetry.event(
            "fault.bit_flip", step=step, placement="state",
            field=self.field or f"field{fidx}",
            index=list(int(i) for i in idx), proc=self.target or 0,
        )
        if _safe_process_index() == 0:
            print(
                f"[igg.resilience] IGG_FAULT_INJECT(bit_flip): flipping one "
                f"mantissa bit at global index {tuple(idx)} "
                f"(field {self.field or fidx}, block {self.target or 0}) "
                f"after step {step}",
                file=sys.stderr,
                flush=True,
            )
        val = A[idx]
        if jnp.issubdtype(A.dtype, jnp.floating):
            word = lax.bitcast_convert_type(val, _word_dtype(A.dtype))
            new = lax.bitcast_convert_type(
                word ^ np.array(1, word.dtype), A.dtype
            )
        else:
            new = val ^ np.array(1, A.dtype)
        return A.at[idx].set(new)

    def maybe_bit_flip_transport(self, step: int) -> None:
        """Transport placement: arm a payload-word flip on rank ``P``'s next
        checksummed halo hop (`ops.halo.arm_transport_flip`) — the
        arm-on-step / fire-on-next-collective idiom of ``net_delay``.  Every
        process arms (the flip is rank-conditional INSIDE the traced
        program), so the SPMD build stays identical on all ranks."""
        if not self._bit_flip_armed(step, "transport"):
            return
        self.fired = True
        from ..ops import halo as _halo

        want = self.target if self.target is not None else 0
        _telemetry.event(
            "fault.bit_flip", step=step, placement="transport", proc=want
        )
        if _safe_process_index() == 0:
            print(
                f"[igg.resilience] IGG_FAULT_INJECT(bit_flip): arming an "
                f"in-flight payload-word flip on rank {want}'s next "
                f"checksummed halo transport (after step {step})",
                file=sys.stderr,
                flush=True,
            )
        _halo.arm_transport_flip(want)

    def maybe_bit_flip_ckpt(self, payload: dict, step: int) -> None:
        """Checkpoint placement: flip one payload byte AFTER the lineage
        digests were taken and BEFORE the shard writer runs (`utils.
        checkpoint._save_checkpoint` calls this between the two).  The CRC
        manifest then vouches for the flipped bytes — the file on disk is
        intact — and only the lineage chain convicts the generation as
        poisoned-at-save.  Mutates the payload dict's arrays in place; the
        writer process ``P`` (default 0) applies it."""
        if not self._bit_flip_armed(step, "ckpt"):
            return
        want = self.target if self.target is not None else 0
        if _safe_process_index() != want:
            return
        self.fired = True
        keys = sorted(k for k in payload if not k.endswith("_shape"))
        if not keys:
            return
        # copy=True: the payload entries are zero-copy views of the live
        # device buffers and arrive read-only
        arr = np.array(payload[keys[0]], copy=True)
        arr.view(np.uint8).reshape(-1)[0] ^= 1
        payload[keys[0]] = arr
        _telemetry.event(
            "fault.bit_flip", step=step, placement="ckpt", key=keys[0],
            proc=want,
        )
        print(
            f"[igg.resilience] IGG_FAULT_INJECT(bit_flip): flipped one "
            f"payload byte of {keys[0]} between digest and write of the "
            f"step-{step} checkpoint",
            file=sys.stderr,
            flush=True,
        )


@dataclasses.dataclass
class FaultSet:
    """Several `FaultInjector`s armed at once (comma-separated spec).

    The process-wide injector `get_fault_injector` returns: every hook
    point (`maybe_flake_init`, `maybe_corrupt`, `maybe_crash`,
    `maybe_damage_checkpoint`, `corrupt_halo_hook`) fans out to each armed
    fault, so e.g. ``worker_crash:step4:proc1,ckpt_corrupt:step4`` crashes
    a worker AND damages the newest checkpoint generation in one run — the
    supervised-failover drill `scripts/soak.py` and the 2-process elastic
    restart test run.
    """

    injectors: tuple = ()

    @classmethod
    def from_spec(cls, spec: str | None) -> "FaultSet":
        """Parse a comma-separated spec; ``chaos:seed=N:rate=R[...]`` parts
        expand into their deterministic storm (`chaos_schedule`) first."""
        return cls(
            tuple(
                FaultInjector.from_spec(part)
                for part in expand_fault_spec(spec)
            )
        )

    @property
    def active(self) -> bool:
        return any(i.active for i in self.injectors)

    def maybe_flake_init(self) -> None:
        for i in self.injectors:
            i.maybe_flake_init()

    def maybe_corrupt(self, state: tuple, step: int) -> tuple:
        for i in self.injectors:
            state = i.maybe_corrupt(state, step)
        return state

    def corrupt_halo_hook(self, fields: tuple) -> tuple:
        for i in self.injectors:
            fields = i.corrupt_halo_hook(fields)
        return fields

    def maybe_crash(self, step: int) -> None:
        for i in self.injectors:
            i.maybe_crash(step)

    def maybe_stall(self, step: int) -> None:
        for i in self.injectors:
            i.maybe_stall(step)

    def maybe_net_delay(self, step: int) -> None:
        for i in self.injectors:
            i.maybe_net_delay(step)

    def maybe_bit_flip(self, state: tuple, step: int,
                       names: Sequence[str] | None = None) -> tuple:
        for i in self.injectors:
            state = i.maybe_bit_flip(state, step, names)
        return state

    def maybe_bit_flip_transport(self, step: int) -> None:
        for i in self.injectors:
            i.maybe_bit_flip_transport(step)

    def maybe_bit_flip_ckpt(self, payload: dict, step: int) -> None:
        for i in self.injectors:
            i.maybe_bit_flip_ckpt(payload, step)

    def specs(self) -> list[str]:
        """Canonical per-fault spec strings (the supervisor round-trip)."""
        return [i.spec() for i in self.injectors if i.active]

    def maybe_damage_checkpoint(self, step_dir: str, step: int) -> None:
        for i in self.injectors:
            i.maybe_damage_checkpoint(step_dir, step)


def _last_process_index() -> int:
    return _telemetry.process_count() - 1


def _block_interior_index(A, block_rank: int) -> tuple:
    """A global index inside block ``block_rank`` of global-block field ``A``,
    one cell off the block edge (so frozen boundary rings don't mask it and
    the models' interior updates propagate it).  Derived purely from the grid
    topology — every process computes the same index."""
    from ..ops.halo import local_shape
    from ..parallel import topology

    gg = _grid.global_grid()
    if not 0 <= block_rank < gg.nprocs:
        raise ValueError(
            f"IGG_FAULT_INJECT(halo_corrupt): block {block_rank} is out of "
            f"range for this grid ({gg.nprocs} blocks, dims {gg.dims})."
        )
    coords = topology.coords_of_rank(block_rank, gg.dims)
    lsh = local_shape(A, gg)
    return tuple(
        c * n + min(1, n - 1) for c, n in zip(coords[: len(lsh)], lsh)
    )


_injector: FaultSet | None = None
_injector_spec: str | None = None


def get_fault_injector() -> FaultSet:
    """The process-wide injector for the current ``IGG_FAULT_INJECT`` value
    (a `FaultSet`: the spec may arm several comma-separated faults).

    Cached per spec string so fired/remaining state persists across calls;
    changing the env var re-arms automatically, `reset_fault_injector`
    re-arms explicitly (the pytest fixture path).
    """
    global _injector, _injector_spec
    spec = _config.fault_inject_env()
    if _injector is None or spec != _injector_spec:
        _injector = FaultSet.from_spec(spec)
        _injector_spec = spec
    return _injector


def reset_fault_injector() -> None:
    global _injector, _injector_spec
    _injector = None
    _injector_spec = None


def install_halo_fault_hook() -> None:
    """Wire the active injector into `ops.halo`'s post-exchange hook point."""
    from ..ops import halo as _halo

    inj = get_fault_injector()
    _halo.set_post_exchange_hook(inj.corrupt_halo_hook if inj.active else None)


# -- Run guard (the models' time-loop hook) -----------------------------------


_copy_jit = None

#: shared reusable null context for the untraced step pipeline
#: (`contextlib.nullcontext` instances are stateless and re-enterable)
_NULL_CM = contextlib.nullcontext()


def snapshot_state(state: tuple) -> tuple:
    """Device-side bit-exact copy of a state tuple (fresh buffers).

    A plain reference is not enough for rollback: the models' step functions
    donate their inputs, so the snapshot must own separate buffers.  `jnp.copy`
    under jit produces a genuine device copy with the input's sharding.
    """
    global _copy_jit
    import jax
    import jax.numpy as jnp

    if _copy_jit is None:
        _copy_jit = jax.jit(jnp.copy)
    return tuple(_copy_jit(A) for A in state)


def guarded_time_loop(step_fn, state: tuple, nt: int, *, guard: "RunGuard",
                      sync_every_step: bool = False,
                      model: str | None = None,
                      bytes_per_step: int | None = None) -> tuple:
    """The models' host-side time loop with the guard pipeline attached.

    Resumes from the guard's checkpoint dir when one exists, then advances
    to step ``nt``, running `RunGuard.on_step` after every step (fault
    injection → shadow-step audit at the ``integrity_every`` cadence →
    NaN/Inf guard → checkpoint → crash injection; rollback may rewind the
    loop variable).  Shared by the three models' ``run()`` so the guard
    semantics cannot drift between them.

    ``model`` switches on the per-step telemetry (docs/observability.md):
    wall time, steps/s and — with ``bytes_per_step`` (the solver's
    must-stream bytes model, `telemetry.teff_bytes`) — the built-in
    ``T_eff`` histogram, plus the rank-0 ``IGG_HEARTBEAT_EVERY`` heartbeat.
    Per-step wall time is the LOOP iteration's host time (dispatch + sync +
    guard pipeline), exact when each step synchronizes and amortized-only
    otherwise.  With ``IGG_TELEMETRY=0`` (or ``model=None``) the loop takes
    the zero-allocation branch: one ``is not None`` check per step.
    """
    state, it = guard.start(state)
    enabled = guard.enabled  # skip the per-step pipeline entirely when idle
    tele = (
        _telemetry.step_loop(
            model, bytes_per_step=bytes_per_step, start_step=it,
            total_steps=nt,
        )
        if model is not None
        else None
    )
    if it > nt:
        # A checkpoint past the requested horizon is almost always a stale
        # directory (e.g. a previous longer run) — returning it silently
        # would mislabel old physics as this run's result.
        warnings.warn(
            f"resumed checkpoint is at step {it}, past the requested "
            f"nt={nt}; returning the checkpointed state unchanged (stale "
            f"checkpoint_dir?)",
            RuntimeWarning,
            stacklevel=2,
        )
    # Live-plane escalation wiring (docs/observability.md): while this loop
    # runs, a CRITICAL anomaly alert (from the heartbeat tick or a scrape)
    # forces an out-of-cadence guard probe instead of scrolling past as a
    # log line.  Subscribed only for the loop's lifetime.
    _liveplane = None
    if tele is not None and enabled:
        from . import liveplane as _liveplane_mod

        _liveplane = _liveplane_mod
        _liveplane.subscribe(guard.on_alert)
    try:
        return _guarded_loop_body(
            step_fn, state, nt, it, guard, enabled, sync_every_step,
            model, tele,
        )
    finally:
        if _liveplane is not None:
            _liveplane.unsubscribe(guard.on_alert)
        if tele is not None:
            # Crash-safe capture stop (docs/observability.md device
            # timeline): a profiler window still open when the loop exits
            # through a guard trip / injected fault stops HERE, so the
            # bytes already captured land next to the flight bundle
            # instead of dying with the process state.  Never raises.
            from . import profiling as _profiling

            _profiling.close_open_capture("scope_exit")


def _guarded_loop_body(step_fn, state, nt, it, guard, enabled,
                       sync_every_step, model, tele) -> tuple:
    import jax

    while it < nt:
        # The ``igg.step`` host span (docs/observability.md): one span per
        # loop iteration — dispatch + sync + guard pipeline, the same wall
        # time the step_seconds histogram records — tagged so a merged
        # cross-rank trace aligns steps BY NUMBER, and in an on-device
        # capture's host plane through the span's own profiler annotation.
        # Untraced loops reuse the shared null manager (the
        # zero-allocation contract).
        if tele is None:
            span = _NULL_CM
        else:
            span = _tracing.trace_span("igg.step", model=model, step=it + 1)
        with span:
            # Shadow-audit retention (docs/robustness.md): off-cadence steps
            # pay one `is not None`-style check; on-cadence steps snapshot
            # the pre-step state the audit re-executes from.
            pre = guard.audit_snapshot(state, it) if enabled else None
            state = step_fn(*state)
            if sync_every_step:
                jax.block_until_ready(state)
            it += 1
            if enabled:
                state, it = guard.on_step(state, it, replay=(step_fn, pre))
        if tele is not None:
            tele.on_step(it)
    if tele is not None:
        tele.finish(it)
    return state


class RunGuard:
    """Guard + checkpoint + fault-injection driver for a host-side time loop.

    Used by the three models' ``run()`` loops::

        guard = RunGuard(guard_every=10, policy="rollback",
                         checkpoint_every=100, checkpoint_dir="/ckpt",
                         names=("T", "Cp"))
        state, it = guard.start(state)
        while it < nt:
            state = step(*state)
            it += 1
            state, it = guard.on_step(state, it)

    Per step, in order: (1) fault injection (``halo_corrupt``, ``bit_flip``),
    (2) the shadow-step audit every ``integrity_every`` steps
    (``IGG_INTEGRITY_EVERY``): the loop retained a pre-step snapshot
    (`audit_snapshot`), the just-committed step re-executes from it and the
    two results bit-compare (`integrity.audit_fields`) — a mismatch raises
    `integrity.IntegrityError` BEFORE any checkpoint can persist the corrupt
    state, with a ``reason=sdc`` flight bundle naming the implicated
    rank(s), (3) the NaN/Inf guard every ``guard_every`` steps with the
    ``raise`` | ``warn`` | ``rollback`` policy, (4) checkpoint every
    ``checkpoint_every`` steps (only ever of guard-passed state) followed by
    retention pruning when ``checkpoint_keep`` (``IGG_CHECKPOINT_KEEP``) is
    set — pruning never deletes the only integrity-verified generation,
    (5) fault injection (``worker_crash`` — after the checkpoint, so restart
    resumes exactly at the crash point — ``stall``, ``net_delay``, and the
    ``bit_flip`` transport arming).  Rollback restores the last good
    snapshot (in-memory; the disk checkpoint serves cross-process restart)
    and rewinds ``it``.  A pending CRITICAL live-plane alert (`on_alert`,
    subscribed by `guarded_time_loop`) forces the step-(3) probe out of
    cadence at the next step.

    All knobs resolve kwarg > ``IGG_*`` env > default (the reference's
    configuration tiers); ``IGG_INTEGRITY=0`` force-disables the audit
    cadence regardless of either tier (the pinned zero-overhead switch,
    like ``IGG_TELEMETRY=0``).
    """

    def __init__(
        self,
        *,
        guard_every: int | None = None,
        policy: str | None = None,
        checkpoint_every: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_keep: int | None = None,
        names: Sequence[str] | None = None,
        max_rollbacks: int = 3,
        injector: "FaultInjector | FaultSet | None" = None,
        integrity_every: int | None = None,
    ):
        env_ge = _config.guard_every_env()
        env_pol = _config.guard_policy_env()
        env_ce = _config.checkpoint_every_env()
        env_dir = _config.checkpoint_dir_env()
        env_keep = _config.checkpoint_keep_env()
        env_ie = _config.integrity_every_env()
        self.guard_every = int(
            guard_every if guard_every is not None else (env_ge or 0)
        )
        self.policy = policy if policy is not None else (env_pol or "raise")
        self.checkpoint_every = int(
            checkpoint_every if checkpoint_every is not None else (env_ce or 0)
        )
        self.checkpoint_dir = (
            checkpoint_dir if checkpoint_dir is not None else env_dir
        )
        # Retention: keep this many newest generations, pruning the rest
        # after every save (0 = unbounded).  Pruning never deletes the only
        # integrity-verified generation (`checkpoint.prune_checkpoints`).
        self.checkpoint_keep = int(
            checkpoint_keep if checkpoint_keep is not None else (env_keep or 0)
        )
        # Shadow-step audit cadence (docs/robustness.md).  ``IGG_INTEGRITY=0``
        # overrides BOTH tiers to 0: the master switch pins the whole
        # integrity plane to zero overhead, whatever a cadence knob says.
        self.integrity_every = int(
            integrity_every if integrity_every is not None else (env_ie or 0)
        )
        if _config.integrity_enabled_env() is False:
            self.integrity_every = 0
        if self.integrity_every < 0:
            raise ValueError(
                f"integrity_every must be >= 0 (got {self.integrity_every})"
            )
        if self.guard_every < 0:
            raise ValueError(f"guard_every must be >= 0 (got {self.guard_every})")
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0 (got {self.checkpoint_every})"
            )
        if self.checkpoint_keep < 0:
            raise ValueError(
                f"checkpoint_keep must be >= 0 (got {self.checkpoint_keep}; "
                f"0 keeps every generation)"
            )
        if self.policy not in _config.GUARD_POLICIES:
            raise ValueError(
                f"guard policy must be one of "
                f"{', '.join(map(repr, _config.GUARD_POLICIES))}, got {self.policy!r}."
            )
        if self.checkpoint_every and not self.checkpoint_dir:
            raise ValueError(
                "checkpoint_every > 0 requires a checkpoint_dir (kwarg or "
                "IGG_CHECKPOINT_DIR)."
            )
        self.names = tuple(names) if names is not None else None
        self.max_rollbacks = max_rollbacks
        self.rollbacks = 0
        self._last_good: tuple | None = None
        self._last_good_step = 0
        self._injector = injector if injector is not None else get_fault_injector()
        # Live-plane escalation (utils.liveplane): a pending critical alert
        # staged by `on_alert`; the next `on_step` consumes it as a forced
        # out-of-cadence field probe.
        self._alert: dict | None = None

    @property
    def enabled(self) -> bool:
        return bool(
            self.guard_every
            or self.checkpoint_every
            or self.integrity_every
            or self._injector.active
        )

    def start(self, state: tuple) -> tuple:
        """Resume from the latest checkpoint if one exists, else step 0.

        Returns ``(state, start_step)``.  The step-0 state is snapshotted as
        the initial rollback target when the policy needs one.
        """
        it = 0
        if self.checkpoint_dir:
            from . import checkpoint as _ckpt

            latest = _ckpt.latest_checkpoint(self.checkpoint_dir)
            if latest is not None:
                # latest_checkpoint already CRC-verified this generation:
                # don't pay a second full read+CRC pass over every shard.
                state, it, _ = _ckpt.restore_checkpoint(
                    latest, like=state, verify=False
                )
                _telemetry.event("run.resumed", step=it, path=latest)
                _telemetry.counter("resilience.resumes").inc()
                print(
                    f"[igg.resilience] resumed from checkpoint {latest} "
                    f"(step {it})",
                    file=sys.stderr,
                    flush=True,
                )
        if self.policy == "rollback" and self.guard_every:
            self._last_good = snapshot_state(state)
            self._last_good_step = it
        return state, it

    def on_alert(self, alert: dict) -> None:
        """Live-plane subscriber (`utils.liveplane.subscribe`): a CRITICAL
        alert escalates into the guard machinery — the next `on_step` runs
        the NaN/Inf field probe immediately, out of cadence, under the
        configured policy.  Warn-severity alerts stay observability-only.
        Thread-safe by construction: one reference assignment (the engine
        may call from the scrape thread)."""
        if alert.get("severity") == "critical":
            self._alert = alert

    def audit_snapshot(self, state: tuple, it: int) -> tuple | None:
        """Pre-step state retained for the shadow audit of step ``it + 1``,
        or None when that step is off-cadence.  Called by the loop BEFORE the
        step executes; the snapshot owns fresh buffers (`snapshot_state`), so
        donating step functions can consume it in the re-execution."""
        if not self.integrity_every or (it + 1) % self.integrity_every != 0:
            return None
        return snapshot_state(state)

    def on_step(self, state: tuple, it: int, replay=None) -> tuple:
        """Run the per-step guard pipeline; returns ``(state, it)``.

        ``replay``: ``(step_fn, pre_state_or_None)`` from the loop — when the
        retained `audit_snapshot` is present, step ``it`` re-executes from it
        and bit-compares against the committed ``state`` (the shadow-step
        audit).  Injection runs FIRST, so an armed state-placement
        ``bit_flip`` lands in the committed copy and the clean re-execution
        convicts it — the detection matrix's compute-placement leg."""
        state = self._injector.maybe_corrupt(state, it)
        state = self._injector.maybe_bit_flip(state, it, self.names)
        if replay is not None and replay[1] is not None:
            state = self._audit(state, it, replay)
        escalated, self._alert = self._alert, None
        if escalated is not None and _last_process_index() > 0:
            # Multi-process grid: `check_fields` is a COLLECTIVE, and an
            # alert is rank-LOCAL — a probe keyed on it would be exactly
            # the SPMD-divergence (deadlock) class the static analyzer
            # pins.  The alert event + health view carry the signal;
            # cross-rank escalation is an operator decision.
            escalated = None
        if escalated is not None:
            _telemetry.event(
                "guard.alert_probe", step=it, rule=escalated.get("rule"),
                severity=escalated.get("severity"),
            )
            _telemetry.counter("resilience.alert_probes").inc()
        do_guard = (
            (self.guard_every and it % self.guard_every == 0)
            or escalated is not None
        )
        do_ckpt = self.checkpoint_every and it % self.checkpoint_every == 0
        # Checkpoints must only ever hold guard-passed state: when guarding
        # is on, a checkpoint step that falls between probe points is probed
        # too (guard_every=3, checkpoint_every=2 must not persist a NaN born
        # at step 2 and first probed at step 3).
        if do_guard or (do_ckpt and self.guard_every):
            report = check_fields(*state, names=self.names)
            if not report.ok:
                state, it = self._trip(state, it, report)
                return state, it  # fresh state: skip checkpoint/crash this round
            if self.policy == "rollback":
                self._last_good = snapshot_state(state)
                self._last_good_step = it
        if do_ckpt:
            from . import checkpoint as _ckpt

            _ckpt.save_checkpoint(self.checkpoint_dir, state, it)
            if self.checkpoint_keep:
                _ckpt.prune_checkpoints(
                    self.checkpoint_dir, keep=self.checkpoint_keep
                )
        self._injector.maybe_crash(it)
        self._injector.maybe_stall(it)
        self._injector.maybe_net_delay(it)
        self._injector.maybe_bit_flip_transport(it)
        return state, it

    def _audit(self, state: tuple, it: int, replay) -> tuple:
        """The shadow-step audit of step ``it`` (docs/robustness.md).

        Re-executes the step from the retained pre-step snapshot and
        bit-compares against the committed result.  Healthy hardware is
        run-to-run deterministic under XLA, so ANY difference is silent data
        corruption; the verdict is replicated (`integrity.audit_fields`), so
        every rank raises together — no rank-local collective divergence.
        Raises BEFORE the checkpoint stage so corrupt state never persists.
        """
        from ..integrity import audit_fields
        from ..integrity.errors import IntegrityError

        step_fn, pre = replay
        redone = step_fn(*pre)
        report = audit_fields(tuple(state), tuple(redone), names=self.names)
        _telemetry.counter("integrity.audits").inc()
        if report.ok:
            return state
        _telemetry.counter("integrity.audit_mismatches").inc()
        _telemetry.event(
            "integrity.audit_mismatch", detector="shadow_audit", step=it,
            report=report.summary(),
            implicated_ranks=list(report.implicated_ranks),
        )
        implicated = (
            report.implicated_ranks[0] if report.implicated_ranks else -1
        )
        _tracing.dump_flight_recorder(
            "sdc", detector="shadow_audit", step=it,
            implicated_rank=implicated,
            implicated_ranks=list(report.implicated_ranks),
            report=report.summary(),
        )
        raise IntegrityError(
            f"shadow-step audit mismatch at step {it}: {report.summary()}. "
            f"The committed step and its re-execution from identical inputs "
            f"differ bitwise — silent data corruption on the implicated "
            f"rank(s); quarantine them (restart-in-place re-runs the lying "
            f"core).",
            detector="shadow_audit", implicated_rank=implicated, step=it,
        )

    def _trip(self, state: tuple, it: int, report: FieldReport) -> tuple:
        msg = f"NaN/Inf guard tripped at step {it}: {report.summary()}"
        _telemetry.event(
            "guard.trip", step=it, policy=self.policy, report=report.summary()
        )
        _telemetry.counter("resilience.guard_trips").inc()
        _tracing.dump_flight_recorder(
            "guard.trip", step=it, policy=self.policy,
            report=report.summary(),
        )
        if self.policy == "raise":
            raise GuardError(msg, step=it, report=report)
        if self.policy == "warn":
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            return state, it
        # rollback
        if self._last_good is None:
            raise GuardError(
                msg + " — policy='rollback' but no good state was ever "
                "recorded (is guard_every set?)",
                step=it,
                report=report,
            )
        self.rollbacks += 1
        if self.rollbacks > self.max_rollbacks:
            raise GuardError(
                msg + f" — giving up after {self.max_rollbacks} rollback(s): "
                "the fault re-occurs deterministically",
                step=it,
                report=report,
            )
        warnings.warn(
            msg + f" — rolling back to step {self._last_good_step}",
            RuntimeWarning,
            stacklevel=3,
        )
        _telemetry.event(
            "guard.rollback", step=it, to_step=self._last_good_step
        )
        _telemetry.counter("resilience.rollbacks").inc()
        return snapshot_state(self._last_good), self._last_good_step
