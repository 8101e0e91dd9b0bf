"""The fused cadence of the three solvers (diffusion + acoustic + porous).

Each solver declares its kernel family once (`Family`: the kernel module,
the layout its kernel runs on, how one group launches, its z-patch
operations, its XLA twin).  `fused_cadence` checks a ``fused_k`` request,
builds the group bodies from that declaration and picks one at trace time
with ONE gate (`pick_body`).  Adding a solver means a kernel module, a
`Family` and its physics.

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
``fused.z_self_wrap`` counts traced kernel groups that do a self-neighbour z
exchange inside the kernel's tiles.  ``make_multi_step`` registers both at
0, so a telemetry snapshot reads 0, not nothing, where no kernel path fell
back or wrapped.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

_warned: set = set()

#: the telemetry counter of fused-kernel fallback decisions
FALLBACK_COUNTER = "fused.fallback"
#: the telemetry counter of traced kernel groups that do the self-neighbour z
#: exchange inside the kernel's tiles (a family with ``z_wrap``)
Z_SELF_WRAP_COUNTER = "fused.z_self_wrap"


def spanned_multi_step(model: str):
    """Decorator of a model's ``make_multi_step(params, nsteps, ...)``: the
    callable it returns — plain jit, `igg.stencil` or batched alike — runs
    each call inside one ``igg.multi_step`` span (`tracing.spanned_calls`)
    tagged ``model``, ``nsteps`` and ``call``, the callable's own count of
    its calls.  Building one registers the ``fused.fallback`` and
    ``fused.z_self_wrap`` counters at 0.

    ``autotune`` (None = ``IGG_AUTOTUNE``, default off) is read here for the
    three models: the cached winner schedule of this point substitutes into
    the five tunable keywords (`tuning.search.maybe_autotune`; explicit
    keywords always win, results stay bit-identical)."""
    from ..utils import telemetry, tracing

    def deco(make):
        @functools.wraps(make)
        def make_spanned(params, nsteps, *, autotune=None, batch=False, **kw):
            from ..tuning.search import maybe_autotune

            telemetry.counter(FALLBACK_COUNTER).inc(0)
            telemetry.counter(Z_SELF_WRAP_COUNTER).inc(0)
            kw = maybe_autotune(model, params, nsteps, autotune, batch=batch, **kw)
            return tracing.spanned_calls(
                make(params, nsteps, batch=batch, **kw), "igg.multi_step",
                model=model, nsteps=int(nsteps),
            )

        return make_spanned

    return deco


def stencil_call(fn, nfields: int, donate_argnums=(), batch: bool = False):
    """`igg.stencil` of a per-block step, or with ``batch`` the ensemble step
    over ``nfields`` batched fields (`models._batched.batched_stencil`)."""
    if batch:
        from ._batched import batched_stencil

        return batched_stencil(fn, nfields, donate_argnums=donate_argnums)
    from ..ops.stencil import stencil

    return stencil(fn, donate_argnums=donate_argnums)


def group_exchange(fields, width: int, early, coalesce=None) -> tuple:
    """The XLA cadences' width-``width`` slab exchange of all ``fields``
    after a group: `update_halo`, or with ``early`` the early-dispatch shape
    ``pipelined=True`` asks for (`ops.halo.begin_slab_exchange` /
    `finish_slab_exchange`, bit-identical values)."""
    from .. import update_halo
    from ..ops.halo import begin_slab_exchange, finish_slab_exchange

    if early:
        pend = begin_slab_exchange(fields, (0, 1, 2), width=width, coalesce=coalesce)
        return finish_slab_exchange(fields, pend)
    out = update_halo(*fields, width=width, coalesce=coalesce)
    return out if len(fields) > 1 else (out,)


def check_xla_cadence(fam, w: int, *, nsteps=None, pipelined=None,
                      hide_comm: bool = False) -> None:
    """Argument checks of a model's XLA paths at ``exchange_every=w``
    (``nsteps``: where it must be a multiple of ``w``)."""
    from ..ops.halo import require_deep_halo

    if w < 1:
        raise ValueError(f"exchange_every must be >= 1 (got {w})")
    if w > 1:
        if hide_comm:
            raise ValueError(
                "exchange_every and hide_comm are mutually exclusive: overlap "
                f"scheduling hides the per-{fam.units[0]} exchange; a slab "
                "cadence replaces it."
            )
        if nsteps is not None and nsteps % w != 0:
            raise ValueError(
                f"nsteps={nsteps} must be a multiple of exchange_every={w}"
            )
        require_deep_halo(w)
    elif pipelined:
        raise ValueError(
            "pipelined applies to the group cadences (fused_k or "
            f"exchange_every > 1); the per-{fam.units[0]} path has no group "
            "schedule."
        )


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


@dataclasses.dataclass(frozen=True)
class ZPatch:
    """A layout's z-patch operations: the z-patch kernel variant applies a
    patch per tile and exports the next group's z send slabs, and these
    carry one group's exports to the next group's patch."""

    identity: Callable  # (s, width) -> the patch that rewrites fresh halos
    exchange: Callable  # (s, exports, width) -> (s, patch): serialized group
    finish: Callable  # (exports, s, width) -> patch: after finish_slab_exchange
    apply: Callable  # (s, patch, width) -> s: the chunk-end application
    fix: Callable | None = None  # (exports, s, width) -> exports, first


@dataclasses.dataclass(frozen=True)
class Family:
    """One solver's fused kernel family, declared once in its model module.

    Fixed facts (the module's ``FAMILY``): ``name`` labels warnings and
    counters; ``kernels`` is the kernel module (`ops/pallas_*.py`: its
    envelope ``fused_support_error``, tile ladder ``default_tile`` and
    kernel names); ``stagger=1`` where the face fields' ``ol`` is one deeper
    than the grid overlap; ``z_wrap``: the kernel can do a self-neighbour z
    exchange inside its tiles (``z_wrap=True, z_overlap=o``); ``units``:
    what one kernel step is, for the error messages; ``idle_steps``: on a
    grid with no halo activity the XLA twin is the one-step XLA path.

    Hooks, bound per ``make_multi_step`` call (`bind`), on the layout ``s``
    (a tuple of fields) the kernel runs on:

    * ``enter(*state) -> (s, ctx)`` / ``leave(s, ctx) -> state``: into and
      out of the layout (``ctx``: the read-only fields the kernel takes);
    * ``launch(ki, s, ctx, bx, by, **kw)``: one kernel group, returning the
      layout's fields and then any z exports (``kw``: ``z_patches`` with
      ``z_overlap``, or ``z_wrap`` with ``z_overlap``, and ``tile_sel`` /
      ``carry_in`` of a ring or interior pass);
    * ``exchange(s, width[, dims])``: the slab exchange on the layout;
    * ``logicals(s)``: the fields' real shapes for `begin_slab_exchange`;
    * ``zpatch(s) -> ZPatch``;
    * ``xla(w, early)``: the model's XLA cadence at ``w`` steps per
      exchange, the fused cadence's twin (``early``: early-dispatch shape).
    """

    name: str
    kernels: Any
    stagger: int = 0
    z_wrap: bool = False
    units: tuple = ("step", "steps")
    idle_steps: bool = False
    enter: Callable | None = None
    leave: Callable | None = None
    launch: Callable | None = None
    exchange: Callable | None = None
    logicals: Callable = lambda s: None
    zpatch: Callable | None = None
    xla: Callable | None = None

    def bind(self, **hooks) -> "Family":
        return dataclasses.replace(self, **hooks)


def pad_enter(C, *faces):
    """``enter`` hook of the padded face layout (`ops.pallas_leapfrog.
    pad_faces`: a cell field and three staggered face fields, +8 in x/y and
    +128 in z), the layout the leapfrog and PT kernels run on."""
    from ..ops.pallas_leapfrog import pad_faces

    return (C, *pad_faces(*faces)), None


def unpad_leave(s, ctx=None):
    """``leave`` hook of the padded face layout (see `pad_enter`)."""
    from ..ops.pallas_leapfrog import unpad_faces

    return (s[0], *unpad_faces(*s[1:]))


def padded_faces(coalesce=None) -> dict:
    """Exchange hooks of the padded face layout (see `pad_enter`), shared by
    the leapfrog and PT families: slab exchanges run on the padded fields
    with their real ``n+1`` shapes, and the z patches go through the
    padded-face set with its top-face fix-up."""
    from ..ops import halo

    def exchange(s, width, dims=None):
        return halo.update_halo_padded_faces(
            *s, width=width, dims=dims, coalesce=coalesce
        )

    def from_exports(exports, s, width):
        return halo.z_patches_from_exports(exports, tuple(s[0].shape), width=width)

    def z_exchange(s, exports, width):
        # x/y slabs exchange outside for the fields, then the z
        # communication runs on the packed exports alone
        s = exchange(s, width, dims=(0, 1))
        return s, from_exports(exports, s, width)

    zpatch = ZPatch(
        identity=lambda s, width: halo.identity_z_patches(*s, width=width),
        fix=lambda exports, s, width: halo.fix_topface_z_exports(
            exports, *s, width=width
        ),
        exchange=z_exchange,
        finish=from_exports,
        apply=lambda s, patch, width: halo.apply_z_patches(*s, patch, width=width),
    )
    return dict(
        exchange=exchange,
        logicals=lambda s: halo._padded_logicals(*s),
        zpatch=lambda s: zpatch,
    )


def pipelined_support_error(fam, shape, k, itemsize: int = 4, bx=None,
                            by=None, gg=None) -> str | None:
    """Why the pipelined group schedule cannot split this config, or None.

    Mirrors the cadence's trace-time decision (`pick_body`): on a grid that
    exchanges z with a partner (or whose family cannot wrap z in its tiles)
    the z-patch kernel variant must be admissible (the pipelined schedule
    routes z through the in-kernel patches; a z-DUS cadence stays
    serialized), then the split must clear `split_selector`.  A
    self-neighbour z that the family wraps inside its tiles gates like an
    inactive one (the plain envelope).  One implementation for the three
    models; the per-model wrappers (`models.*.pipelined_support_error`)
    exist for benchmark provenance.
    """
    from ..ops.halo import _partner_self, dim_has_halo_activity
    from ..parallel.grid import global_grid

    if gg is None:
        gg = global_grid()
    shape = tuple(shape)
    kernels = fam.kernels
    active = tuple(d for d in (0, 1) if dim_has_halo_activity(gg, d))
    z_active = dim_has_halo_activity(gg, 2) and not (
        fam.z_wrap and _partner_self(gg, 2)
    )
    zp = z_active and kernels.fused_support_error(
        shape, k, itemsize, bx, by, zpatch=True
    ) is None
    if z_active and not zp:
        return "z-active grid without the z-patch kernel: serialized z-DUS cadence"
    if not zp:
        # The split only exists on a kernel path: a config the plain
        # envelope rejects runs the XLA cadence, and labeling it
        # "pipelined" would corrupt the A/B provenance.
        kerr = kernels.fused_support_error(shape, k, itemsize, bx, by)
        if kerr is not None:
            return f"kernel envelope rejects this config ({kerr}): XLA cadence"
    _, err, _ = split_selector(
        kernels, shape, k, k, itemsize, bx, by, active, zp, fam.stagger, gg
    )
    return err


def pick_body(fam, *, active, z_active, z_self, plain_err, zpatch_err,
              schedule_err, split_err, pipelined, shape, k) -> str:
    """THE trace-time gate of every fused cadence: which body runs.

    ``active``: some dimension has halo activity; ``z_active`` / ``z_self``:
    z has, and every block is its own z partner; ``plain_err`` /
    ``zpatch_err``: the kernel envelope's verdicts (None = admitted);
    ``schedule_err``: why the schedule has no kernel group, or None;
    ``split_err(zpatch)``: the ring/interior split's verdict.

    1. z exchanged with a partner (or a self partner the family cannot wrap
       in its tiles) and the z-patch envelope admits the block: the z-patch
       body (``zpatch``, or ``zpatch_piped`` where the split is admitted).
    2. Else the plain envelope admits it: ``plain`` — with the z wrap on a
       self-neighbour z — or ``plain_piped`` where the grid communicates and
       the split is admitted.
    3. Else warn once and run the XLA twin (``xla``), with the
       early-dispatch exchange (``xla_early``) on ``pipelined=True`` where
       that twin has a group exchange to dispatch.
    """
    z_exchange = z_active and not (fam.z_wrap and z_self)
    if z_exchange and schedule_err is None and zpatch_err is None:
        piped = resolve_pipelined(pipelined, split_err(True), shape, k, fam.name)
        return "zpatch_piped" if piped else "zpatch"
    err = plain_err or schedule_err
    if err is None:
        piped = active and not z_exchange and resolve_pipelined(
            pipelined, split_err(False), shape, k, fam.name
        )
        return "plain_piped" if piped else "plain"
    warn_fused_fallback(shape, k, err, model=fam.name)
    return "xla_early" if pipelined and (active or not fam.idle_steps) else "xla"


def fused_cadence(fam, k, *, nsteps=None, chunks=None, schedule_err=None,
                  fused_tile=None, exchange_every=1, pipelined=None,
                  hide_comm=False, coalesce=None):
    """Check a ``fused_k=k`` request and build the fused cadence of ``fam``:
    returns ``(body, idle)``, ``body(*state) -> state`` picking its group
    body at trace time (`pick_body`), ``idle`` when no dimension has halo
    activity.

    ``nsteps``: steps per call, run as groups of ``k`` (a multiple of ``k``);
    or ``chunks``: the group sizes, with ``schedule_err`` why there is no
    kernel group where the list is empty.  The bodies (serialized and
    pipelined, plain and z-patch) run the group sequence through
    `run_group_schedule` / `run_pipelined_group_schedule`; every group
    after the first re-runs the kernel on the exchanged layout, ``k``
    kernel steps per width-``k`` slab exchange (the kernel's k-deep
    contaminated rind is exactly the slab the exchange refreshes, and the
    sent planes ``[ol-k, ol)`` are still exact).  Kernel bodies are wrapped
    with `fused_with_xla_grad`: ``jax.grad`` differentiates the XLA twin.
    """
    from ..ops.halo import (
        _partner_self,
        begin_slab_exchange,
        dim_has_halo_activity,
        finish_slab_exchange,
        ol,
        require_deep_halo,
    )
    from ..parallel.grid import global_grid
    from ..utils import telemetry

    gg = global_grid()
    one, many = fam.units
    if hide_comm:
        raise ValueError(
            "fused_k and hide_comm are mutually exclusive: the fused "
            f"kernel's slab exchange is already amortized over k {one}s; "
            f"overlap scheduling applies to the per-{one} XLA path."
        )
    if nsteps is not None and nsteps % k != 0:
        raise ValueError(f"nsteps={nsteps} must be a multiple of fused_k={k}")
    if exchange_every not in (1, k):
        raise ValueError(
            f"fused_k={k} already exchanges every fused_k {many}; "
            f"exchange_every={exchange_every} conflicts."
        )
    require_deep_halo(k, gg, what="fused_k")
    bx, by = fused_tile if fused_tile is not None else (None, None)
    if (bx is None) != (by is None):
        # A half-specified tile is a caller error, not a shape the kernel
        # cannot run: raise eagerly rather than warn and fall back.
        raise ValueError(f"fused_tile={fused_tile}: pass both bx and by, or neither")
    if chunks is None:
        chunks = [k] * (nsteps // k)
    active = [d for d in range(3) if dim_has_halo_activity(gg, d)]
    active01 = tuple(d for d in (0, 1) if d in active)
    z_self = _partner_self(gg, 2)
    # Every block its own z partner: a z_wrap kernel wraps z in its tiles.
    wrap = fam.z_wrap and 2 in active and z_self
    if not active and pipelined:
        warn_pipelined_fallback(
            None, k, "no halo activity: nothing to overlap", model=fam.name
        )
    if not active or (wrap and not active01):
        exchange = None  # the kernel's output already has fresh halos
    elif wrap:
        exchange = lambda s: fam.exchange(s, k, dims=(0, 1))  # noqa: E731
    else:
        exchange = lambda s: fam.exchange(s, k)  # noqa: E731

    def split(s, zpatch):
        """(ring/mid selector suffix, admissibility error, resolved tile):
        the pipelined bodies pin every group to the tile this validated."""
        A = s[0]
        return split_selector(
            fam.kernels, tuple(A.shape), k, k, A.dtype.itemsize, bx, by,
            active01, zpatch, fam.stagger, gg,
        )

    def z_kw(s):
        """The kernel arguments of the in-tile z wrap (or none)."""
        if not wrap:
            return {}
        return dict(z_wrap=True, z_overlap=ol(2, shape=tuple(s[0].shape), gg=gg))

    def plain(*state):
        s, ctx = fam.enter(*state)
        zkw = z_kw(s)

        def group(ki, s):
            if wrap:
                telemetry.counter(Z_SELF_WRAP_COUNTER).inc()
            s = fam.launch(ki, s, ctx, bx, by, **zkw)
            return s if exchange is None else exchange(s)

        return fam.leave(run_group_schedule(chunks, group, s), ctx)

    def plain_piped(*state):
        # Boundary-first split of `plain`: the ring pass feeds the x/y slab
        # exchange early, the interior pass runs across the in-flight
        # collectives, the received slabs land on the aliased output.
        s0, ctx = fam.enter(*state)
        sel, _, tile = split(s0, False)
        logicals = fam.logicals(s0)
        zkw = z_kw(s0)

        def boundary(ki, s):
            if wrap:
                telemetry.counter(Z_SELF_WRAP_COUNTER).inc()
            out_b = fam.launch(ki, s, ctx, *tile, tile_sel="ring" + sel, **zkw)
            return out_b, begin_slab_exchange(
                out_b, (0, 1), width=k, logicals=logicals, coalesce=coalesce
            )

        def interior(ki, s, out_b, pend):
            out = fam.launch(
                ki, s, ctx, *tile, tile_sel="mid" + sel, carry_in=out_b, **zkw
            )
            return finish_slab_exchange(out, pend, logicals=logicals)

        s = run_pipelined_group_schedule(chunks, boundary, interior, s0)
        return fam.leave(s, ctx)

    def zpatch(*state):
        # The kernel applies the z patch per tile in VMEM AND exports the
        # next group's send slabs (extraction outside the kernel paid
        # whole-array relayouts per group); x/y slabs exchange outside.
        s0, ctx = fam.enter(*state)
        zp = fam.zpatch(s0)
        o_z = ol(2, shape=tuple(s0[0].shape), gg=gg)
        n = len(s0)
        # Chunk entry has fresh halos: the first group's patch re-writes
        # the planes already in place.
        patch0 = zp.identity(s0, k)

        def group(ki, carry):
            s, patch = carry
            out = fam.launch(ki, s, ctx, bx, by, z_patches=patch, z_overlap=o_z)
            s, exports = out[:n], out[n:]
            if zp.fix is not None:
                exports = zp.fix(exports, s, k)
            return zp.exchange(s, exports, k)

        s, patch = run_group_schedule(chunks, group, (s0, patch0))
        # One whole-array application restores the chunk-boundary
        # fresh-halo invariant (amortized over the whole chunk).
        return fam.leave(zp.apply(s, patch, k), ctx)

    def zpatch_piped(*state):
        # Boundary-first split of `zpatch`: the fields' x/y slabs exchange
        # early off the ring pass; the z exports (which every tile feeds)
        # complete with the interior pass, and their thin communication
        # stays on the group's serialized tail.
        s0, ctx = fam.enter(*state)
        zp = fam.zpatch(s0)
        o_z = ol(2, shape=tuple(s0[0].shape), gg=gg)
        n = len(s0)
        patch0 = zp.identity(s0, k)
        sel, _, tile = split(s0, True)
        logicals = fam.logicals(s0)

        def boundary(ki, carry):
            s, patch = carry
            out_b = fam.launch(
                ki, s, ctx, *tile, z_patches=patch, z_overlap=o_z,
                tile_sel="ring" + sel,
            )
            return out_b, begin_slab_exchange(
                out_b[:n], (0, 1), width=k, logicals=logicals, coalesce=coalesce
            )

        def interior(ki, carry, out_b, pend):
            s, patch = carry
            out = fam.launch(
                ki, s, ctx, *tile, z_patches=patch, z_overlap=o_z,
                tile_sel="mid" + sel, carry_in=out_b,
            )
            s2, exports = out[:n], out[n:]
            if zp.fix is not None:  # reads the PRE-exchange outputs
                exports = zp.fix(exports, s2, k)
            s2 = finish_slab_exchange(s2, pend, logicals=logicals)
            return s2, zp.finish(exports, s2, k)

        s, patch = run_pipelined_group_schedule(
            chunks, boundary, interior, (s0, patch0)
        )
        return fam.leave(zp.apply(s, patch, k), ctx)

    bodies = dict(plain=plain, plain_piped=plain_piped, zpatch=zpatch,
                  zpatch_piped=zpatch_piped)
    twin_w = 1 if not active and fam.idle_steps else k

    def gated(*state):
        # Shapes are only known at trace time, so the kernel-vs-fallback
        # choice happens there: a local block the kernel's envelope
        # rejects warns once and runs the XLA twin at the same exchange
        # cadence (the deep halo is already validated above).
        A = state[0]
        shape, isz = tuple(A.shape), A.dtype.itemsize
        env = fam.kernels.fused_support_error
        pick = pick_body(
            fam, active=bool(active), z_active=2 in active, z_self=z_self,
            plain_err=env(shape, k, isz, bx, by),
            zpatch_err=env(shape, k, isz, bx, by, zpatch=True),
            schedule_err=schedule_err,
            split_err=lambda zpatch: split(state, zpatch)[1],
            pipelined=pipelined, shape=shape, k=k,
        )
        if pick == "xla_early":
            return fam.xla(k, True)(*state)
        twin = fam.xla(twin_w, False)
        if pick == "xla":
            return twin(*state)
        return fused_with_xla_grad(bodies[pick], twin)(*state)

    # A lambda: the jitted program is then named `jit__lambda`, the prefix
    # of every op name a device trace of the cadence reads.
    return (lambda *state: gated(*state)), not active


def fused_multi_step(fam, k, nsteps, *, nfields, donate_argnums=(),
                     batch=False, **knobs):
    """`fused_cadence` of ``nsteps`` steps per call, as one program: on a
    grid with no halo activity a plain `jax.jit` on the grid's one device
    (no collectives, so no shard_map), else `stencil_call`."""
    body, idle = fused_cadence(fam, k, nsteps=nsteps, **knobs)
    if not idle:
        return stencil_call(body, nfields, donate_argnums, batch)
    import jax

    if batch:
        body = jax.vmap(body)
    return jax.jit(body, donate_argnums=donate_argnums)


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
