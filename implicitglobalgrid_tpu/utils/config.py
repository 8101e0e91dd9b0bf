"""Environment-variable configuration tier.

The reference reads ``IGG_*`` env vars once at `init_global_grid`
(`/root/reference/src/init_global_grid.jl:51-68`) as the deploy-time
configuration tier below the kwargs tier.  Its specific knobs
(``IGG_CUDAAWARE_MPI[_DIMX/Y/Z]``, ``IGG_ROCMAWARE_MPI*``,
``IGG_LOOPVECTORIZATION*``) toggle GPU-direct MPI transport and CPU
vectorization per dimension — both N/A on TPU, where `collective_permute`
always moves HBM→HBM over ICI and packing is compiled (SURVEY.md §2.3).

The *mechanism* carries over with the TPU-meaningful knobs:

========================  ====================================================
``IGG_DEVICE_TYPE``       default ``device_type`` (``auto|tpu|cpu|gpu``)
``IGG_QUIET``             nonzero suppresses the rank-0 banner
``IGG_REORDER``           default mesh reorder flag (ICI-torus alignment)
``IGG_OVERLAP``           default overlap in every dimension (reference
                          kwarg ``overlapx/y/z`` default 2)
``IGG_DONATE``            default for `update_halo`'s global-array buffer
                          donation (0 = off; see `ops.halo._default_donate`
                          — read per call, not at init)
``IGG_COALESCE``          multi-field halo-exchange message combining
                          (``ops.halo``): unset = auto — whenever >= 2
                          fields share a dimension's exchange, their send
                          slabs pack into one buffer per dtype byte width
                          and ride ONE collective-permute pair per
                          (dimension, width group); ``0`` restores per-field
                          collectives (debug/attribution); bit-identical
                          either way.  Read per call/trace, like
                          ``IGG_DONATE`` (`ops.halo._default_coalesce`)
``IGG_VMEM_MB``           per-core VMEM capacity the fused kernels plan
                          against (`ops._fused_envelope.vmem_budget` — read
                          per kernel build, not at init)
``IGG_INIT_RETRIES``      retry attempts for `init_distributed`'s runtime
                          bring-up (int >= 0, default 3; coordinator races
                          are the #1 multi-host bring-up failure) — read
                          per call by `parallel.distributed.init_distributed`
``IGG_INIT_TIMEOUT_S``    overall deadline in seconds across all bring-up
                          attempts (number > 0, default 600)
``IGG_INIT_BACKOFF_S``    base of the exponential retry backoff in seconds
                          (number > 0, default 1; doubles per attempt with
                          seeded jitter — `utils.resilience.backoff_schedule`)
``IGG_WATCHDOG_S``        collective-hang watchdog: dump all-thread stacks
                          after this many seconds during grid/runtime
                          bring-up.  Unset = off around the init barrier but
                          `init_distributed` defaults to its bring-up
                          deadline; 0 = off everywhere

``IGG_GUARD_EVERY``       default ``guard_every`` for the models' time loops
                          (int >= 0; 0 = guards off) — run the NaN/Inf
                          field probe every N steps (`igg.check_fields`)
``IGG_GUARD_POLICY``      what a tripped guard does: ``raise`` (default) |
                          ``warn`` | ``rollback`` (restore last good state)
``IGG_CHECKPOINT_EVERY``  default checkpoint cadence for the models' time
                          loops (int >= 0; 0 = off)
``IGG_CHECKPOINT_DIR``    default checkpoint directory (`utils.checkpoint`)
``IGG_CHECKPOINT_KEEP``   checkpoint retention for the models' time loops
                          (int >= 0; 0 = keep every generation): after each
                          save, prune to the newest N generations — pruning
                          never deletes the only integrity-verified one
``IGG_FAULT_INJECT``      fault-injection knob for the test/soak harness:
                          ``init_flake:N`` | ``halo_corrupt:stepN[:blockB]``
                          | ``worker_crash:stepN[:procP]``
                          | ``stall:stepN[:procP]``
                          | ``ckpt_corrupt:stepN[:shardS]``
                          | ``ckpt_truncate:stepN[:shardS]``
                          | ``bit_flip:stepN[:field][:procP]`` — flip ONE
                          mantissa bit (finite, NaN/Inf-guard-invisible;
                          the silent-data-corruption twin of
                          ``halo_corrupt``).  The optional third component
                          is a FIELD NAME or a reserved placement:
                          ``transport`` (flip a packed send-slab word in
                          flight) or ``ckpt`` (flip serialized shard bytes
                          after the lineage digest, before the write);
                          several faults compose comma-separated
                          (docs/robustness.md)
``IGG_INTEGRITY``         silent-data-corruption integrity plane master
                          switch (`implicitglobalgrid_tpu.integrity`,
                          docs/robustness.md): ``1`` arms transport
                          checksums on the host-entry coalesced halo
                          exchange; ``0`` force-disables EVERY detector
                          (checksums, shadow audit, env cadences) to a
                          pinned zero-overhead path like
                          ``IGG_TELEMETRY=0``; unset = checksums off but
                          ``IGG_INTEGRITY_EVERY`` still honored.  Resolved
                          host-side at the exchange entry / loop start
                          (the knob-binding contract) — never read from
                          traced code
``IGG_INTEGRITY_EVERY``   shadow-step audit cadence in steps for the
                          guarded time loops (int >= 0; 0/unset = off):
                          every N committed steps `guarded_time_loop`
                          re-executes the step from the retained pre-step
                          state and bit-compares against the committed
                          result; any difference raises
                          `integrity.IntegrityError` naming the implicated
                          rank.  Ignored when ``IGG_INTEGRITY=0``

``IGG_GATHER_BATCH``      blocks fetched per compiled dispatch in the
                          multi-host gather (int, clamped to >= 1, default
                          8; `ops.gather._gather_batch_size`)
``IGG_BATCH``             default slot-pool capacity B of the batched
                          serving loop (`serving.ServingLoop`; int >= 1,
                          default 4) — B ensemble members share one vmapped
                          SPMD step at ONE collective pair per exchanged
                          dimension (read per loop construction)
``IGG_BATCH_ROUND_STEPS`` default steps advanced per serving round (int >=
                          1, default 1; `serving.ServingLoop`) — the
                          admit/retire/guard granularity of the slot pool
``IGG_TELEMETRY``         telemetry master switch (``0`` disables the
                          metrics registry, the event log and every
                          instrumented hot path to their zero-allocation
                          no-op branch; unset/nonzero = on) — read per
                          call by `utils.telemetry` (docs/observability.md)
``IGG_TELEMETRY_DIR``     directory for the per-process JSONL event log
                          (``events.jsonl`` / ``events.pN.jsonl``); unset =
                          metrics-registry-only, no files written
``IGG_HEARTBEAT_EVERY``   rank-0 heartbeat cadence in steps for the models'
                          instrumented run loops (int >= 0; 0/unset = off):
                          every N steps print step time, steps/s and T_eff —
                          and, on multi-process grids, run the all-ranks
                          skew probe (`utils.tracing.skew_probe`)
``IGG_PROFILE``           windowed device-timeline capture (`utils.profiling`,
                          docs/observability.md): ``steps:A-B`` arms a
                          `jax.profiler` capture around time-loop steps A..B
                          (1-based, inclusive) of the next instrumented run;
                          ``steps:N`` = steps 1..N.  Per-rank output under
                          ``IGG_PROFILE_DIR`` / ``IGG_TELEMETRY_DIR``;
                          unset = no capture (the default).  Consumed ONCE
                          per process: the first instrumented run arms it
                          (`utils.profiling.maybe_arm`; several loops in
                          one process must not pay a profiler session
                          each or overwrite the first capture)
``IGG_PROFILE_DIR``       base directory for the per-rank profiler capture
                          dirs (``profile.p<rank>/``); unset = under
                          ``IGG_TELEMETRY_DIR`` (no directory at all
                          degrades to a structured ``profile.capture_failed``
                          event, never a crash)
``IGG_TRACE_RING``        capacity of the per-process host-span ring buffer
                          (`utils.tracing`; int >= 0, default 4096; 0
                          disables span recording entirely) — read per
                          span, like ``IGG_TELEMETRY``
``IGG_TRACE_SAMPLE``      head-based request-trace sampling rate at the
                          serving/fleet front doors (number in [0, 1],
                          default 1.0 = every request gets a trace
                          context minted); 0 disables minting entirely —
                          no context allocation, no header emission
                          beyond echoing an inbound ``traceparent``
``IGG_SKEW_WARN``         straggler threshold for the all-ranks skew probe
                          (number >= 0, default 2.0): a ``skew.straggler``
                          event fires when max/min per-rank step wall time
                          exceeds it; 0 disables the event (gauges still
                          publish)
``IGG_TELEMETRY_MAX_TENANTS``  cap on distinct ``serving.tenant.<t>.steps``
                          counter series (int >= 1, default 64); overflow
                          tenants fold into ``serving.tenant.__other__.steps``
``IGG_METRICS_PORT``      live-plane scrape port (`utils.liveplane`): unset =
                          no HTTP server (the default); ``0`` = bind an
                          ephemeral port (published via the
                          ``liveplane.port`` gauge, the rank-0 heartbeat
                          event and a ``liveplane.p<rank>.json`` endpoint
                          file under ``IGG_TELEMETRY_DIR``); N > 0 = bind
                          exactly N.  Never consulted when ``IGG_TELEMETRY=0``
                          (the server does not start)
``IGG_METRICS_HOST``      bind address of the live-plane server (default
                          ``127.0.0.1`` — loopback only; the endpoints are
                          unauthenticated read-only snapshots, widen the
                          bind deliberately)
``IGG_SLO_WINDOW_S``      length in seconds of one rolling SLO sub-window of
                          every `utils.telemetry.Histogram` (number > 0,
                          default 30; `telemetry.SLO_WINDOW_S_DEFAULT`) —
                          the ``window`` section of histogram summaries and
                          the ``slo.*`` gauges aggregate the last
                          `telemetry.SLO_WINDOWS` windows (read per window
                          rollover, like the other telemetry knobs)
``IGG_SERVE_PORT``        serving front-door port (`serving.frontdoor`,
                          docs/serving.md): 0 (the default when a
                          `FrontDoor` is constructed without an explicit
                          ``port``) binds an ephemeral port, published via
                          the ``frontdoor.port`` gauge and a
                          ``frontdoor.p<rank>.json`` endpoint file under
                          ``IGG_TELEMETRY_DIR``; N > 0 binds exactly N.
                          Rank 0 only — the front door is the cluster's
                          single network entry
``IGG_SERVE_HOST``        bind address of the front-door server (default
                          ``127.0.0.1`` — loopback only; the submit
                          endpoint is unauthenticated, widen deliberately)
``IGG_TENANT_QUOTA``      per-tenant token-bucket arrival limit for the
                          front door: ``RATE`` or ``RATE:BURST`` (requests
                          per second sustained, bucket depth BURST >= 1,
                          default burst = max(1, RATE)); unset = unlimited.
                          Exhaustion rejects with 429 reason ``quota``
``IGG_FRONTDOOR_QUEUE_MAX``  backpressure threshold (int >= 1): reject new
                          requests with 429 reason ``backpressure`` while
                          the ``serving.queue_depth`` gauge is at/above it
                          (unset = 4x the pool capacity)
``IGG_FRONTDOOR_SLO_P99_S``  SLO backpressure threshold (number > 0): reject
                          with 429 reason ``slo`` while the live
                          ``slo.serving.round_seconds.p99`` window exceeds
                          it (unset = only active CRITICAL anomaly alerts
                          flip the ``slo`` backpressure)
``IGG_AUTOSCALE_QUEUE_HIGH``  sustained-queue scale-up threshold for the
                          `serving.autoscale.Autoscaler` (int >= 1; unset =
                          the pool capacity): queue depth at/above it votes
                          ``up``
``IGG_AUTOSCALE_SUSTAIN`` consecutive autoscaler observations (int >= 1,
                          default 2) a non-``hold`` verdict must sustain
                          before a resize commits
``IGG_SERVE_MAX_BODY``    front-door request-body bound in bytes (int >= 1,
                          default 1 MiB = 1048576): a ``POST`` whose body
                          (declared or actual) exceeds it is refused with
                          a structured 413 before the handler buffers it —
                          the slow-loris/oversize hardening of
                          `serving.frontdoor` (docs/serving.md)
``IGG_RESULT_KEEP``       retired-result retention depth of the serving
                          loop (int >= 0; 0/unset = keep every result,
                          the pre-fleet behavior): after each round, prune
                          CONSUMED results (harvested by a front door)
                          beyond the newest N — `serving.ServingLoop`
                          (docs/serving.md, "bounded result retention")
``IGG_RESULT_TTL_S``      age bound in seconds on retired results (number
                          > 0; unset = no TTL): a consumed result older
                          than this is pruned at round end regardless of
                          ``IGG_RESULT_KEEP``.  A pruned result's fetch
                          returns a structured 410 (``results_expired``)
``IGG_FLEET_PORT``        fleet router public port (`fleet.router`,
                          docs/serving.md): 0/unset = bind an ephemeral
                          port (published via a ``fleet.json`` endpoint
                          file under ``IGG_TELEMETRY_DIR``); N > 0 binds
                          exactly N
``IGG_FLEET_POLL_S``      fleet controller liveness/health polling cadence
                          in seconds (number > 0, default 0.5;
                          `fleet.controller.FleetController`)
``IGG_FLEET_RESPAWN_LIMIT``  in-place pool respawns per continuous failure
                          streak before the fleet policy quarantines the
                          pool's device subset (int >= 0, default 2;
                          `fleet.policy.FleetPolicy`)
``IGG_FLEET_SCRAPE_RETRIES``  per-endpoint retry budget of the fleet/router
                          health scrapes and of ``scripts/igg_top.py``
                          (int >= 0, default 2): a scrape is retried with
                          exponential backoff before the endpoint is
                          marked ``UNREACHABLE``
``IGG_FLEET_SPILL_QUEUE`` hot-pool spill threshold (int >= 1; unset =
                          spill off): a pool whose scraped queue depth
                          sits at/above it makes the policy spawn a fresh
                          spill pool instead of resizing the live one
``IGG_FLEET_IDLE_RETIRE`` consecutive idle observations (queue 0, no
                          active members) before a spilled pool retires
                          (int >= 1; unset = pools never retire)
``IGG_FLEET_CANARY_STREAK``  consecutive healthy canary observations before
                          the candidate config auto-promotes fleet-wide
                          (int >= 1, default 3; `fleet.canary`)
``IGG_FLEET_CANARY_P99_S``  canary SLO breach threshold on the canary
                          pool's rolling ``slo.serving.round_seconds.p99``
                          window in seconds (number > 0; unset = only
                          active CRITICAL alerts breach the canary)
``IGG_GENERATION``        this incarnation's generation token (int >= 0;
                          unset = unfenced).  Set by the run supervisor
                          identically on every rank of one incarnation;
                          threaded through checkpoint meta, telemetry
                          event tags and front-door control broadcasts,
                          and checked against the authoritative fence file
                          at every durable publish
                          (`supervisor.generation`, docs/robustness.md)
``IGG_FENCE_DIR``         directory of the supervisor-published
                          authoritative ``generation.json`` fence file
                          (unset = no fence checks) — read per publish,
                          like the other resilience knobs
``IGG_SUPERVISE_MAX_RESTARTS``  in-place restarts per continuous failure
                          streak before the supervisor's policy engine
                          drops a topology rung (int >= 0, default 2;
                          `supervisor.policy.RecoveryPolicy`)
``IGG_SUPERVISE_BACKOFF_S``  base of the supervisor's exponential relaunch
                          backoff in seconds (number > 0, default 0.5;
                          `utils.resilience.backoff_schedule` semantics)
``IGG_SUPERVISE_POLL_S``  supervisor liveness/health polling cadence in
                          seconds (number > 0, default 0.5;
                          `supervisor.manager.RunSupervisor`)
``IGG_AUTOTUNE``          default for the models' ``make_multi_step``
                          ``autotune=`` kwarg (``implicitglobalgrid_tpu.
                          tuning``; nonzero = on, unset/0 = off): on first
                          use of a (backend, topology, model, size, dtype,
                          batch) point, search the schedule-kwarg space
                          (cost-model-pruned, short measured runs) and
                          apply the cached winner on every later call —
                          a pure substitution of existing kwargs, resolved
                          host-side before tracing (docs/performance.md)
``IGG_TUNE_CACHE``        primary directory of the autotuner's on-disk
                          winner table (unset = ``.igg_tune`` in the
                          checkout, `CHECKOUT_ROOT`); the committed
                          seed layer ``tuning/entries`` is always the
                          read-only fallback — read per resolve
``IGG_TUNE_TOPK``         total candidates measured per search (int >= 1,
                          default 4; `tuning.space.prune` — the default
                          config always counts among them, so ``1`` can
                          only ever confirm the default)
``IGG_TUNE_STEPS``        timed chunk calls per measured candidate (int >=
                          1, default 3; `tuning.search.measure_candidate`
                          — short by design, the bench harness owns
                          publication-grade timing)
========================  ====================================================

Explicit kwargs always win over env values; env values win over built-in
defaults — the reference's precedence.  The resilience knobs are read per
call (like ``IGG_DONATE``), not snapshotted at init.

JAX's own ``JAX_COMPILATION_CACHE_DIR`` places the persistent compile cache
that entry points turn on with `enable_compile_cache`.
"""

from __future__ import annotations

import os

#: The checkout this package runs from.  Every default path the program
#: writes lies under it (compile cache, tuner winners), never under
#: ``HOME`` or a temp dir.
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """Where `enable_compile_cache` keeps compiled programs:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed ``.jax_cache``
    in the checkout (a fixed path, so the next run in this checkout finds
    what this one compiled)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT_ROOT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called by entry points (``chip_smoke.py``, ``bench.py``, the examples),
    never at library import.  A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's
    own default for the directory and is left alone.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path

#: Valid values for ``IGG_GUARD_POLICY`` / the models' ``guard_policy``.
GUARD_POLICIES = ("raise", "warn", "rollback")


def _getenv(name: str) -> str | None:
    """``os.environ.get(name)`` without the KeyError round trip that
    `os.environ` takes for an unset variable: one dict lookup.  Hot paths
    read their switches on every call (``IGG_TELEMETRY`` and
    ``IGG_TRACE_RING`` per span), and right after a device dispatch that
    round trip measured several microseconds per read on the CPU."""
    data = getattr(os.environ, "_data", None)
    if data is None:
        return os.environ.get(name)
    raw = data.get(os.environ.encodekey(name))
    return None if raw is None else os.environ.decodevalue(raw)


def _int_env(name: str, *, minimum: int | None = None, maximum: int | None = None) -> int | None:
    """Read an integer env var; ``None`` when unset/empty.

    Error messages follow the reference's contract (name the variable and
    the obtained value) and state the accepted range/format.
    """
    val = _getenv(name)
    if val is None or val == "":
        return None
    try:
        parsed = int(val)
    except ValueError:
        raise ValueError(
            f"Environment variable {name} must be an integer"
            f"{_range_desc(minimum, maximum)} (format: a base-10 integer), "
            f"got {val!r}."
        )
    _check_range(name, parsed, minimum, maximum, val)
    return parsed


def _float_env(
    name: str,
    *,
    minimum: float | None = None,
    exclusive_minimum: float | None = None,
) -> float | None:
    """Read a float env var; ``None`` when unset/empty.  Same error contract
    as `_int_env` (variable name, accepted range, obtained value)."""
    val = os.environ.get(name)
    if val is None or val == "":
        return None
    try:
        parsed = float(val)
    except ValueError:
        raise ValueError(
            f"Environment variable {name} must be a number"
            f"{_range_desc(minimum, None, exclusive_minimum)} "
            f"(format: a decimal number of seconds, e.g. '2' or '0.5'), "
            f"got {val!r}."
        )
    _check_range(name, parsed, minimum, None, val, exclusive_minimum)
    return parsed


def _choice_env(name: str, choices: tuple[str, ...]) -> str | None:
    """Read an enumerated env var; ``None`` when unset/empty."""
    val = os.environ.get(name)
    if val is None or val == "":
        return None
    if val not in choices:
        raise ValueError(
            f"Environment variable {name} must be one of "
            f"{', '.join(repr(c) for c in choices)}, got {val!r}."
        )
    return val


def _range_desc(minimum, maximum, exclusive_minimum=None) -> str:
    if exclusive_minimum is not None:
        return f" > {exclusive_minimum}"
    if minimum is not None and maximum is not None:
        return f" in [{minimum}, {maximum}]"
    if minimum == 0:
        return " >= 0 (non-negative)"
    if minimum is not None:
        return f" >= {minimum}"
    if maximum is not None:
        return f" <= {maximum}"
    return ""


def _check_range(name, parsed, minimum, maximum, val, exclusive_minimum=None):
    bad = (
        (minimum is not None and parsed < minimum)
        or (maximum is not None and parsed > maximum)
        or (exclusive_minimum is not None and parsed <= exclusive_minimum)
    )
    if bad:
        kind = "an integer" if isinstance(parsed, int) else "a number"
        raise ValueError(
            f"Environment variable {name} must be {kind}"
            f"{_range_desc(minimum, maximum, exclusive_minimum)}, got {val!r}."
        )


def env_config() -> dict:
    """Read the ``IGG_*`` environment tier (once per init, like the reference)."""
    cfg: dict = {}
    device_type = os.environ.get("IGG_DEVICE_TYPE")
    if device_type:
        cfg["device_type"] = device_type
    quiet = _int_env("IGG_QUIET")
    if quiet is not None:
        cfg["quiet"] = quiet > 0
    reorder = _int_env("IGG_REORDER")
    if reorder is not None:
        cfg["reorder"] = reorder
    overlap = _int_env("IGG_OVERLAP")
    if overlap is not None:
        cfg["overlap"] = overlap
    return cfg


# -- Resilience knobs (read per call, like IGG_DONATE) ------------------------
#
# Each accessor validates the reference's error contract: negative retries,
# zero/negative timeouts and unknown policies are rejected with a message
# naming the variable, the accepted range and the obtained value.


def init_retries_env() -> int | None:
    """``IGG_INIT_RETRIES``: retry attempts after the first bring-up failure."""
    return _int_env("IGG_INIT_RETRIES", minimum=0)


def init_timeout_env() -> float | None:
    """``IGG_INIT_TIMEOUT_S``: overall bring-up deadline in seconds (> 0)."""
    return _float_env("IGG_INIT_TIMEOUT_S", exclusive_minimum=0)


def init_backoff_env() -> float | None:
    """``IGG_INIT_BACKOFF_S``: base retry backoff in seconds (> 0)."""
    return _float_env("IGG_INIT_BACKOFF_S", exclusive_minimum=0)


def watchdog_env() -> float | None:
    """``IGG_WATCHDOG_S``: collective-hang watchdog in seconds (>= 0).

    ``None`` = unset (caller picks its default), ``0.0`` = explicitly off —
    the distinction lets an explicit 0 disable a watchdog a caller would
    otherwise arm with its own fallback timeout.
    """
    return _float_env("IGG_WATCHDOG_S", minimum=0)


def guard_every_env() -> int | None:
    """``IGG_GUARD_EVERY``: NaN/Inf guard cadence in steps (>= 0; 0 = off)."""
    return _int_env("IGG_GUARD_EVERY", minimum=0)


def guard_policy_env() -> str | None:
    """``IGG_GUARD_POLICY``: ``raise`` | ``warn`` | ``rollback``."""
    return _choice_env("IGG_GUARD_POLICY", GUARD_POLICIES)


def checkpoint_every_env() -> int | None:
    """``IGG_CHECKPOINT_EVERY``: checkpoint cadence in steps (>= 0; 0 = off)."""
    return _int_env("IGG_CHECKPOINT_EVERY", minimum=0)


def checkpoint_dir_env() -> str | None:
    """``IGG_CHECKPOINT_DIR``: default checkpoint directory."""
    val = os.environ.get("IGG_CHECKPOINT_DIR")
    return val or None


def checkpoint_keep_env() -> int | None:
    """``IGG_CHECKPOINT_KEEP``: retention depth in generations (>= 0;
    0 = keep every generation)."""
    return _int_env("IGG_CHECKPOINT_KEEP", minimum=0)


def fault_inject_env() -> str | None:
    """``IGG_FAULT_INJECT``: raw fault spec (parsed by `utils.resilience`)."""
    val = os.environ.get("IGG_FAULT_INJECT")
    return val or None


def integrity_enabled_env() -> bool | None:
    """``IGG_INTEGRITY``: integrity-plane master switch (tri-state).

    ``None`` = unset (transport checksums off; ``IGG_INTEGRITY_EVERY``
    still honored), ``False`` = ``0`` (every detector force-disabled —
    the pinned zero-overhead path), ``True`` = armed.  Read host-side at
    the exchange entry / loop construction, never from traced code.
    """
    val = _int_env("IGG_INTEGRITY")
    return None if val is None else val > 0


def integrity_every_env() -> int | None:
    """``IGG_INTEGRITY_EVERY``: shadow-step audit cadence in steps
    (>= 0; 0 = off).  Ignored when ``IGG_INTEGRITY=0``."""
    return _int_env("IGG_INTEGRITY_EVERY", minimum=0)


def coalesce_env() -> bool | None:
    """``IGG_COALESCE``: multi-field halo-exchange message combining.

    ``None`` = unset (auto: coalesce whenever >= 2 fields share a
    dimension's exchange), ``False`` = per-field collectives, ``True`` =
    the auto behavior pinned explicitly.  Bit-identical either way — the
    knob exists for debugging/per-field attribution and A/B measurement.
    """
    val = _int_env("IGG_COALESCE")
    return None if val is None else val > 0


def gather_batch_env() -> int | None:
    """``IGG_GATHER_BATCH``: blocks per compiled gather dispatch.

    Clamped (not rejected) to >= 1 by the consumer, matching the original
    `ops.gather` behavior for 0/negative values.
    """
    return _int_env("IGG_GATHER_BATCH")


# -- Batched serving knobs (read per loop construction; docs/usage.md) --------


def batch_env() -> int | None:
    """``IGG_BATCH``: default serving slot-pool capacity B (>= 1)."""
    return _int_env("IGG_BATCH", minimum=1)


def batch_round_steps_env() -> int | None:
    """``IGG_BATCH_ROUND_STEPS``: default steps per serving round (>= 1)."""
    return _int_env("IGG_BATCH_ROUND_STEPS", minimum=1)


# -- Telemetry knobs (read per call; docs/observability.md) -------------------


def telemetry_enabled_env() -> bool:
    """``IGG_TELEMETRY``: master switch for `utils.telemetry` (default ON;
    ``0`` routes every instrumented hot path to its no-op branch)."""
    val = _int_env("IGG_TELEMETRY")
    return True if val is None else val > 0


def telemetry_dir_env() -> str | None:
    """``IGG_TELEMETRY_DIR``: event-log directory (unset = no files)."""
    val = os.environ.get("IGG_TELEMETRY_DIR")
    return val or None


def heartbeat_every_env() -> int | None:
    """``IGG_HEARTBEAT_EVERY``: rank-0 heartbeat cadence in steps (>= 0;
    0 = off)."""
    return _int_env("IGG_HEARTBEAT_EVERY", minimum=0)


def profile_env() -> str | None:
    """``IGG_PROFILE``: device-timeline capture window spec (``steps:A-B``
    or ``steps:N``); unset/empty = no capture.  Parsed and validated by
    `utils.profiling.parse_profile_window` (the error contract names the
    variable and the accepted grammar)."""
    val = os.environ.get("IGG_PROFILE")
    return val or None


def profile_dir_env() -> str | None:
    """``IGG_PROFILE_DIR``: base directory for per-rank profiler capture
    dirs (unset = derive from ``IGG_TELEMETRY_DIR``)."""
    val = os.environ.get("IGG_PROFILE_DIR")
    return val or None


def trace_ring_env() -> int | None:
    """``IGG_TRACE_RING``: per-process span ring-buffer capacity (>= 0;
    0 disables span recording; unset = the `utils.tracing` default)."""
    return _int_env("IGG_TRACE_RING", minimum=0)


def trace_sample_env() -> float | None:
    """``IGG_TRACE_SAMPLE``: head-based request-trace sampling rate at the
    front doors (in [0, 1]; 0 mints no contexts, unset/1 traces every
    request; inbound contexts are never re-sampled)."""
    return _float_env("IGG_TRACE_SAMPLE", minimum=0)


def skew_warn_env() -> float | None:
    """``IGG_SKEW_WARN``: straggler event threshold on max/min per-rank
    step wall time (>= 0; 0 disables the event, gauges still publish)."""
    return _float_env("IGG_SKEW_WARN", minimum=0)


def telemetry_max_tenants_env() -> int | None:
    """``IGG_TELEMETRY_MAX_TENANTS``: cap on distinct per-tenant counter
    series (>= 1); overflow folds into ``serving.tenant.__other__.steps``."""
    return _int_env("IGG_TELEMETRY_MAX_TENANTS", minimum=1)


# -- Live-plane knobs (read per call; docs/observability.md) ------------------


def metrics_port_env() -> int | None:
    """``IGG_METRICS_PORT``: live-plane scrape port (>= 0; 0 = ephemeral).
    ``None`` = unset — the per-rank HTTP server never starts."""
    return _int_env("IGG_METRICS_PORT", minimum=0)


def metrics_host_env() -> str | None:
    """``IGG_METRICS_HOST``: live-plane bind address (default loopback —
    the consumer falls back to ``127.0.0.1`` when unset)."""
    val = os.environ.get("IGG_METRICS_HOST")
    return val or None


def slo_window_env() -> float | None:
    """``IGG_SLO_WINDOW_S``: rolling SLO sub-window length in seconds
    (> 0; unset = the `utils.telemetry.SLO_WINDOW_S_DEFAULT` default)."""
    return _float_env("IGG_SLO_WINDOW_S", exclusive_minimum=0)


# -- Serving front-door knobs (read per construction; docs/serving.md) --------


def serve_port_env() -> int | None:
    """``IGG_SERVE_PORT``: front-door port (>= 0; 0 = ephemeral).  ``None``
    = unset — `serving.frontdoor.FrontDoor` falls back to 0 (ephemeral)."""
    return _int_env("IGG_SERVE_PORT", minimum=0)


def serve_host_env() -> str | None:
    """``IGG_SERVE_HOST``: front-door bind address (default loopback —
    the consumer falls back to ``127.0.0.1`` when unset)."""
    val = os.environ.get("IGG_SERVE_HOST")
    return val or None


def tenant_quota_env() -> tuple[float, float] | None:
    """``IGG_TENANT_QUOTA``: per-tenant token-bucket arrival limit as
    ``(rate_per_s, burst)``, or ``None`` when unset (= unlimited).

    Format ``RATE`` or ``RATE:BURST`` — sustained RATE requests/second per
    tenant with up to BURST (>= 1; default ``max(1, RATE)``) accumulated.
    """
    val = os.environ.get("IGG_TENANT_QUOTA")
    if val is None or val == "":
        return None
    parts = val.split(":")
    try:
        if len(parts) not in (1, 2):
            raise ValueError
        rate = float(parts[0])
        burst = float(parts[1]) if len(parts) == 2 else max(1.0, rate)
    except ValueError:
        raise ValueError(
            f"Environment variable IGG_TENANT_QUOTA must be 'RATE' or "
            f"'RATE:BURST' (decimal requests/second, e.g. '5' or '5:10'), "
            f"got {val!r}."
        )
    if rate <= 0 or burst < 1:
        raise ValueError(
            f"Environment variable IGG_TENANT_QUOTA needs RATE > 0 and "
            f"BURST >= 1, got {val!r}."
        )
    return rate, burst


def frontdoor_queue_max_env() -> int | None:
    """``IGG_FRONTDOOR_QUEUE_MAX``: queue-depth backpressure threshold
    (>= 1; unset = the front door's 4x-capacity default)."""
    return _int_env("IGG_FRONTDOOR_QUEUE_MAX", minimum=1)


def frontdoor_slo_p99_env() -> float | None:
    """``IGG_FRONTDOOR_SLO_P99_S``: round-latency p99 backpressure
    threshold in seconds (> 0; unset = alerts-only SLO backpressure)."""
    return _float_env("IGG_FRONTDOOR_SLO_P99_S", exclusive_minimum=0)


def autoscale_queue_high_env() -> int | None:
    """``IGG_AUTOSCALE_QUEUE_HIGH``: queue depth that votes for a scale-up
    (>= 1; unset = the pool capacity)."""
    return _int_env("IGG_AUTOSCALE_QUEUE_HIGH", minimum=1)


def autoscale_sustain_env() -> int | None:
    """``IGG_AUTOSCALE_SUSTAIN``: consecutive non-hold autoscaler verdicts
    before a resize commits (>= 1, default 2)."""
    return _int_env("IGG_AUTOSCALE_SUSTAIN", minimum=1)


def serve_max_body_env() -> int | None:
    """``IGG_SERVE_MAX_BODY``: front-door request-body bound in bytes
    (>= 1; unset = the 1 MiB default, `serving.frontdoor.MAX_BODY_DEFAULT`)."""
    return _int_env("IGG_SERVE_MAX_BODY", minimum=1)


def result_keep_env() -> int | None:
    """``IGG_RESULT_KEEP``: retired-result retention depth (>= 0;
    0/unset = keep every result — the pre-fleet behavior)."""
    return _int_env("IGG_RESULT_KEEP", minimum=0)


def result_ttl_env() -> float | None:
    """``IGG_RESULT_TTL_S``: age bound in seconds on consumed results
    (> 0; unset = no TTL)."""
    return _float_env("IGG_RESULT_TTL_S", exclusive_minimum=0)


# -- Fleet knobs (read per construction, host-side; docs/serving.md) ----------


def fleet_port_env() -> int | None:
    """``IGG_FLEET_PORT``: fleet router public port (>= 0; 0 = ephemeral).
    ``None`` = unset — `fleet.router.FleetRouter` falls back to 0."""
    return _int_env("IGG_FLEET_PORT", minimum=0)


def fleet_poll_env() -> float | None:
    """``IGG_FLEET_POLL_S``: fleet controller polling cadence in seconds
    (> 0, default 0.5)."""
    return _float_env("IGG_FLEET_POLL_S", exclusive_minimum=0)


def fleet_respawn_limit_env() -> int | None:
    """``IGG_FLEET_RESPAWN_LIMIT``: pool respawns per failure streak before
    the policy quarantines the pool's device subset (>= 0, default 2)."""
    return _int_env("IGG_FLEET_RESPAWN_LIMIT", minimum=0)


def fleet_scrape_retries_env() -> int | None:
    """``IGG_FLEET_SCRAPE_RETRIES``: per-endpoint health-scrape retry budget
    (>= 0, default 2) before the endpoint is marked ``UNREACHABLE``."""
    return _int_env("IGG_FLEET_SCRAPE_RETRIES", minimum=0)


def fleet_spill_queue_env() -> int | None:
    """``IGG_FLEET_SPILL_QUEUE``: hot-pool queue depth that makes the policy
    spawn a spill pool (>= 1; unset = spill off)."""
    return _int_env("IGG_FLEET_SPILL_QUEUE", minimum=1)


def fleet_idle_retire_env() -> int | None:
    """``IGG_FLEET_IDLE_RETIRE``: consecutive idle observations before a
    spilled pool retires (>= 1; unset = pools never retire)."""
    return _int_env("IGG_FLEET_IDLE_RETIRE", minimum=1)


def fleet_canary_streak_env() -> int | None:
    """``IGG_FLEET_CANARY_STREAK``: consecutive healthy canary observations
    before auto-promote (>= 1, default 3)."""
    return _int_env("IGG_FLEET_CANARY_STREAK", minimum=1)


def fleet_canary_p99_env() -> float | None:
    """``IGG_FLEET_CANARY_P99_S``: canary round-p99 breach threshold in
    seconds (> 0; unset = alerts-only breach detection)."""
    return _float_env("IGG_FLEET_CANARY_P99_S", exclusive_minimum=0)


# -- Supervisor / generation-fencing knobs (docs/robustness.md) ---------------


def generation_env() -> int | None:
    """``IGG_GENERATION``: this incarnation's generation token (>= 0;
    None = unfenced — the default outside a supervised run)."""
    return _int_env("IGG_GENERATION", minimum=0)


def fence_dir_env() -> str | None:
    """``IGG_FENCE_DIR``: directory of the authoritative ``generation.json``
    fence file (unset = fence checks off)."""
    val = os.environ.get("IGG_FENCE_DIR")
    return val or None


def supervise_max_restarts_env() -> int | None:
    """``IGG_SUPERVISE_MAX_RESTARTS``: in-place restarts per failure streak
    before the supervisor shrinks a rung (>= 0, default 2)."""
    return _int_env("IGG_SUPERVISE_MAX_RESTARTS", minimum=0)


def supervise_backoff_env() -> float | None:
    """``IGG_SUPERVISE_BACKOFF_S``: base relaunch backoff in seconds
    (> 0, default 0.5)."""
    return _float_env("IGG_SUPERVISE_BACKOFF_S", exclusive_minimum=0)


def supervise_poll_env() -> float | None:
    """``IGG_SUPERVISE_POLL_S``: supervisor liveness/health polling cadence
    in seconds (> 0, default 0.5)."""
    return _float_env("IGG_SUPERVISE_POLL_S", exclusive_minimum=0)


# -- Autotuning knobs (read per resolve, host-side; docs/performance.md) ------


def autotune_env() -> bool | None:
    """``IGG_AUTOTUNE``: default for ``make_multi_step(autotune=)``.

    ``None`` = unset (off unless the kwarg says otherwise); resolved
    host-side before any tracing, so the knob can never bind into a cached
    executable (the knob-binding contract).
    """
    val = _int_env("IGG_AUTOTUNE")
    return None if val is None else val > 0


def tune_cache_env() -> str | None:
    """``IGG_TUNE_CACHE``: primary winner-table directory (unset = the
    in-checkout default, `tuning.cache.default_cache_dir`)."""
    val = os.environ.get("IGG_TUNE_CACHE")
    return val or None


def tune_topk_env() -> int | None:
    """``IGG_TUNE_TOPK``: total candidates measured per search, the default
    config included (>= 1, default 4)."""
    return _int_env("IGG_TUNE_TOPK", minimum=1)


def tune_steps_env() -> int | None:
    """``IGG_TUNE_STEPS``: timed chunk calls per measured candidate (>= 1,
    default 3)."""
    return _int_env("IGG_TUNE_STEPS", minimum=1)
