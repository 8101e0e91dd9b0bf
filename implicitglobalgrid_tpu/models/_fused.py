"""Shared fused-kernel helpers (diffusion + acoustic + porous).

Fallback warning: the reference's precedent is runtime path selection by
threshold (`/root/reference/src/update_halo.jl:755-784`); here the selection
happens at trace time against the kernel envelope (`fused_support_error`),
warning once per (shape, k, reason) so production loops are not spammed.

Autodiff: `fused_with_xla_grad` makes ``jax.grad`` work through the fused
Pallas chunks (which have no VJP of their own) by differentiating the
equivalent XLA cadence in the backward pass.

Spans: `spanned_multi_step` gives every model's ``make_multi_step``
callable its ``igg.multi_step`` host span.

Counters: ``fused.fallback`` (and ``fused.fallback.<model>``) count every
fallback decision, one per trace of a kernel path the envelope rejects;
``make_multi_step`` registers ``fused.fallback`` at 0, so a telemetry
snapshot reads 0, not nothing, where no kernel path fell back.
"""

from __future__ import annotations

import functools

_warned: set = set()

#: the telemetry counter of fused-kernel fallback decisions
FALLBACK_COUNTER = "fused.fallback"


def spanned_multi_step(model: str):
    """Decorator of a model's ``make_multi_step(params, nsteps, ...)``: the
    callable it returns — plain jit, `igg.stencil` or batched alike — runs
    each call inside one ``igg.multi_step`` span (`tracing.spanned_calls`)
    tagged ``model``, ``nsteps`` and ``call``, the callable's own count of
    its calls.  Building one registers the ``fused.fallback`` counter (at 0
    until a kernel path falls back, `warn_fused_fallback`)."""
    from ..utils import telemetry, tracing

    def deco(make):
        @functools.wraps(make)
        def make_spanned(params, nsteps, *args, **kwargs):
            telemetry.counter(FALLBACK_COUNTER).inc(0)
            return tracing.spanned_calls(
                make(params, nsteps, *args, **kwargs), "igg.multi_step",
                model=model, nsteps=int(nsteps),
            )

        return make_spanned

    return deco


def run_group_schedule(chunks, body, carry, *, unroll_limit=8,
                       fori_excess_only=True):
    """Run ``carry = body(ki, carry)`` for each ``ki`` in ``chunks``.

    The one loop shape behind every fused cadence's group sequence: up to
    ``unroll_limit`` groups are Python-unrolled — one Pallas call per group
    is tiny HLO, and the unrolled form measured ~15-30% faster than a
    fori_loop over groups (XLA pipelines DMAs across group boundaries;
    probed on v5e: porous npt=12 fused6 788 -> 1017 GB/s/PT-iter, acoustic
    256^3 fused6 1117 -> 1564).  A leading run of equal chunks longer than
    the limit routes only its EXCESS through ONE `lax.fori_loop` (bounds
    compile size for long schedules) and still unrolls ``unroll_limit``
    groups in total — a 12-group production schedule keeps the pipelining
    win on 8 of them (advisor r4: the old shape sent such schedules
    entirely through the fori_loop).

    On that (kernel) shape the fori_loop runs TWO groups per iteration,
    ``body(k0, body(k0, c))``, over ``nloop // 2`` iterations; an odd
    excess runs its one leftover group unrolled just before the loop.  A
    while loop keeps each carry element in one buffer, and every fused
    kernel is out of place (a tile reads the halo rows its neighbour tile
    just wrote), so a one-kernel body's fresh output cannot take the
    parameter's buffer while the parameter is live: XLA copies the whole
    field back into the carry after every looped group (~204 us per 64 MiB
    field on v5e, PERF.md §6).  With two chained kernels the second output
    takes the parameter's buffer, dead once the first kernel has read it —
    no copy, the same groups in the same order, bit-identical values.
    Trace-time counters ``schedule.fori_paired_groups`` /
    ``schedule.fori_single_groups`` count the looped groups of each shape.

    ``fori_excess_only=False`` restores the all-or-nothing shape: a uniform
    run longer than the limit goes ENTIRELY through the fori_loop, one
    group per iteration (the ragged tail still unrolls).  The porous XLA
    cadence needs this — its group bodies are large unrolled XLA programs
    whose bit-identity across cadences relies on the fori boundary as a
    fusion barrier between groups (unrolling the last group, or pairing two
    groups in one body, lets XLA contract FMAs differently per surrounding
    context); the Pallas paths are immune (fusion cannot reach inside a
    kernel).
    """
    prefix = 0
    while prefix < len(chunks) and chunks[prefix] == chunks[0]:
        prefix += 1
    if fori_excess_only:
        keep = max(unroll_limit - (len(chunks) - prefix), 0)
    else:
        keep = prefix if prefix <= unroll_limit else 0
    if prefix > keep:
        from jax import lax

        from ..utils import telemetry

        k0 = chunks[0]
        nloop = prefix - keep
        chunks = chunks[nloop:]
        if fori_excess_only:
            if nloop % 2:
                carry = body(k0, carry)
            if nloop >= 2:
                carry = lax.fori_loop(
                    0, nloop // 2, lambda i, c: body(k0, body(k0, c)), carry
                )
                telemetry.counter("schedule.fori_paired_groups").inc(
                    nloop - nloop % 2
                )
        else:
            carry = lax.fori_loop(0, nloop, lambda i, c: body(k0, c), carry)
            telemetry.counter("schedule.fori_single_groups").inc(nloop)
    for ki in chunks:
        carry = body(ki, carry)
    return carry


def run_pipelined_group_schedule(chunks, boundary, interior, carry, *,
                                 unroll_limit=8, fori_excess_only=True):
    """Boundary-first pipelined sibling of `run_group_schedule`.

    Each group's kernel launch is split in two (the `@hide_communication`
    scheduling of the per-step path — `ops/overlap.py` — lifted to tile
    granularity inside the fused group schedule):

    * ``boundary(ki, carry) -> (b_out, pend)`` runs the RING tiles — the
      tiles owning every x/y slab-exchange send plane — and dispatches the
      group's exchange early (`ops.halo.begin_slab_exchange`): the
      `collective-permute`s depend only on thin slices of the ring outputs.
    * ``interior(ki, carry, b_out, pend) -> carry`` runs the MID tiles as
      an op independent of the in-flight collectives (the ring outputs ride
      an input/output alias into the interior launch, so XLA schedules the
      permutes across it), then applies the received slabs
      (`ops.halo.finish_slab_exchange`) and the group's z-patch carry.

    The split-launch carry threaded through each group keeps per-group
    results bit-identical to the serialized schedule: ring+mid partition
    the same tiles tile-for-tile, and the early exchange moves exactly the
    slabs the serialized exchange would (corner strips patched in,
    `ops.halo._patch_slab`).  The loop shaping (unrolled prefix, fori
    excess) is `run_group_schedule`'s.
    """

    from jax import named_scope

    def group(ki, c):
        # Named profiler scopes (docs/observability.md): the ring pass (and
        # the early slab-exchange dispatch it feeds) vs the interior pass
        # show up as distinctly named op groups in a `profile_trace`
        # capture — the runtime evidence that the collectives overlap the
        # interior launch, by name in Perfetto.
        with named_scope("igg_ring_pass"):
            b_out, pend = boundary(ki, c)
        with named_scope("igg_interior_pass"):
            return interior(ki, c, b_out, pend)

    return run_group_schedule(
        chunks, group, carry,
        unroll_limit=unroll_limit, fori_excess_only=fori_excess_only,
    )


def resolve_pipelined(pipelined, split_err, shape, k, model: str) -> bool:
    """Resolve a cadence's ``pipelined`` knob against split admissibility.

    ``pipelined`` is the user knob (None = auto); ``split_err`` is
    `ops.overlap.tile_split_error`'s verdict (None = admissible) for the
    traced local block.  Auto turns the pipelined schedule ON whenever the
    split is admissible (it is bit-identical to the serialized schedule,
    so the only reason to stay serialized is an inadmissible split);
    ``pipelined=True`` on an inadmissible config warns once and runs the
    serialized schedule — the same warn-once fallback contract as the
    kernel envelope (`warn_fused_fallback`).
    """
    if pipelined is False:
        return False
    if split_err is None:
        return True
    if pipelined:
        warn_pipelined_fallback(shape, k, split_err, model)
    return False


def split_selector(kernel_mod, shape, k, width, itemsize, bx, by, active01,
                   zpatch, stagger: int = 0, gg=None):
    """(selector suffix, admissibility error, resolved tile) for a cadence.

    THE one trace-time gate behind every model's pipelined path (and the
    benchmark-provenance wrappers): resolves a missing/half tile through
    the kernel's own ladder (mirroring the kernels' ``bx is None or by is
    None`` handling), derives the y-halo H for the resolved tile, and
    checks `ops.overlap.tile_split_error` with the per-field maximum
    overlaps (``stagger=1`` for the staggered models, whose face fields'
    shape-aware ``ol`` is one deeper than the grid overlap).  The
    RESOLVED tile is returned so ragged schedules can pin every chunk's
    launch to the geometry this gate actually validated (a shorter chunk
    re-resolving its own ladder default could otherwise launch an
    unvalidated — or subset-incapable — tile; the validated tile stays
    legal for any ``ki <= k``: smaller halo, same divisibility).
    """
    from ..ops._fused_envelope import aligned_halo
    from ..ops.overlap import tile_split_error, tile_split_sel
    from ..parallel.grid import global_grid

    if gg is None:
        gg = global_grid()
    shape = tuple(shape)
    if bx is None or by is None:
        t = kernel_mod.default_tile(shape, k, itemsize, zpatch=zpatch)
        if t is None:
            return None, "no valid kernel tile for this shape", None
        bx, by = t
    H = 0 if by == shape[1] else aligned_halo(k)
    err = tile_split_error(
        shape, k, width, bx, by, H, active01,
        ox=gg.overlaps[0] + stagger, oy=gg.overlaps[1] + stagger,
    )
    return tile_split_sel(active01), err, (bx, by)


def pipelined_support_error(kernel_mod, shape, k, itemsize: int = 4,
                            bx=None, by=None, gg=None,
                            stagger: int = 0) -> str | None:
    """Why the pipelined group schedule cannot split this config, or None.

    Mirrors the cadence builders' trace-time decision: on z-active grids
    the z-patch kernel variant must be admissible (the pipelined schedule
    routes z through the in-kernel patches; a z-DUS cadence stays
    serialized), then the split must clear `split_selector`.  One
    implementation for the three models (``kernel_mod`` = the model's
    Pallas kernel module); the per-model wrappers
    (`models.*.pipelined_support_error`) exist for benchmark provenance.
    A kernel module with ``Z_SELF_WRAP`` wraps a self-neighbour z inside its
    tiles, so such a z gates like an inactive one (the plain envelope).
    """
    from ..ops.halo import _partner_self, dim_has_halo_activity
    from ..parallel.grid import global_grid

    if gg is None:
        gg = global_grid()
    shape = tuple(shape)
    active = tuple(d for d in (0, 1) if dim_has_halo_activity(gg, d))
    z_active = dim_has_halo_activity(gg, 2) and not (
        getattr(kernel_mod, "Z_SELF_WRAP", False) and _partner_self(gg, 2)
    )
    zp = z_active and kernel_mod.fused_support_error(
        shape, k, itemsize, bx, by, zpatch=True
    ) is None
    if z_active and not zp:
        return "z-active grid without the z-patch kernel: serialized z-DUS cadence"
    if not zp:
        # The split only exists on a kernel path: a config the plain
        # envelope rejects runs the XLA cadence, and labeling it
        # "pipelined" would corrupt the A/B provenance.
        kerr = kernel_mod.fused_support_error(shape, k, itemsize, bx, by)
        if kerr is not None:
            return f"kernel envelope rejects this config ({kerr}): XLA cadence"
    _, err, _ = split_selector(
        kernel_mod, shape, k, k, itemsize, bx, by, active, zp, stagger, gg
    )
    return err


def warn_pipelined_fallback(shape, k, reason, model: str = "diffusion") -> None:
    """Warn once per (model, shape, k, reason) that pipelined=True fell back
    to the serialized group schedule."""
    import warnings

    key = ("pipelined", model, shape, k, reason)
    if key in _warned:
        return
    _warned.add(key)
    where = (
        f"{model}'s local block shape {shape}" if shape is not None
        else f"the {model} cadence"  # grid-level rejection, no shape to cite
    )
    warnings.warn(
        f"pipelined=True is not admissible for {where} at k={k} ({reason}); "
        "running the serialized group schedule.",
        RuntimeWarning,
        stacklevel=3,
    )


def fused_with_xla_grad(fused_body, xla_body):
    """Make a fused Pallas chunk differentiable via its XLA-cadence twin.

    The temporally-blocked Pallas kernels have no VJP; their XLA cadences
    (same steps, same slab exchanges, pure jnp/lax ops) match them to a few
    float ULPs — so the primal runs ``fused_body`` (full kernel speed) and
    the backward pass recomputes + differentiates ``xla_body`` via
    ``jax.vjp``.  Residuals are just the chunk inputs (rematerialization:
    one extra XLA-cadence forward per backward, nothing saved across the
    k-step loop).  Without this wrapper ``jax.grad`` over a fused multi-step
    dies inside `pallas_call` with no actionable message; with it the fused
    production path and the autodiff story (`tests/test_autodiff.py`)
    compose.  TPU-first capability — no reference analogue (the reference
    has no autodiff, SURVEY.md §0).

    Composes with `jax.vmap` (the ensemble batch axis, `models._batched`):
    custom_vjp has a batching rule, the Pallas chunk batches through the
    `pallas_call` rule (batch as an outer grid dimension), and the XLA twin
    vmaps like any jnp code — so `make_multi_step(batch=True)` keeps both
    the fused primal and the differentiable story at any B
    (`tests/test_batched_serving.py` pins the fused bit-identity per
    member).
    """
    import jax

    f = jax.custom_vjp(fused_body)

    def fwd(*args):
        return fused_body(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(xla_body, *args)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def warn_fused_fallback(shape, k, err, model: str = "diffusion") -> None:
    """Count a fallback of fused_k to XLA; warn once per (model, shape, k,
    reason).

    Every call counts (``fused.fallback`` and ``fused.fallback.<model>``),
    the warning only the first.  ``model`` keys the registry per kernel: the
    diffusion and leapfrog envelopes share reason strings, and one model's
    fallback must not silence another's first warning.
    """
    import warnings

    from ..utils import telemetry

    telemetry.counter(FALLBACK_COUNTER).inc()
    telemetry.counter(f"{FALLBACK_COUNTER}.{model}").inc()
    key = (model, shape, k, err)
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(
        f"fused_k={k} is unsupported for {model}'s local block shape {shape} "
        f"({err}); falling back to the XLA path at the same exchange cadence.",
        RuntimeWarning,
        stacklevel=3,
    )
