"""3-D heat diffusion — the flagship model (reference `examples/diffusion3D_*.jl`).

The reference's headline application: heat diffusion with spatially variable
heat capacity and two Gaussian anomalies, solved with a conservative
finite-difference stencil on the implicit global grid
(`/root/reference/examples/diffusion3D_multigpu_CuArrays_novis.jl:11-50`).
The reference allocates explicit flux arrays (``qx, qy, qz, dTedt``) and runs
five broadcast kernels plus `update_halo!` per step; here the whole time step
is ONE fused XLA program per block — fluxes never hit HBM, and the halo
exchange (`collective_permute`) is scheduled by XLA inside the same program.
With ``hide_comm=True`` the boundary slabs are computed first so the exchange
overlaps the interior update (the `@hide_communication` capability,
reference `README.md:10`).

Physics (reference lines :41-46):

    q      = -lam * grad(T)              (Fourier's law, on the staggered flux grid)
    dT/dt  = -(1/Cp) * div(q)            (conservation of energy)
    T     += dt * dT/dt                  (explicit Euler, interior points only)

Usage::

    import implicitglobalgrid_tpu.models.diffusion3d as m
    state, params = m.setup(nx=128, ny=128, nz=128)
    step = m.make_step(params)
    for _ in range(nt):
        state = step(state)
    T = m.temperature(state)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

from .. import (
    coord_fields,
    finalize_global_grid,
    init_global_grid,
    nx_g,
    ny_g,
    nz_g,
    stencil,
    update_halo,
    zeros,
)
from ..ops import pallas_stencil
from ..ops.overlap import hide_communication
from . import _fused


@dataclasses.dataclass(frozen=True)
class Params:
    """Physics + numerics of the run (reference lines :13-23,:39)."""

    lam: float = 1.0  # thermal conductivity
    cp_min: float = 1.0  # minimal heat capacity
    lx: float = 10.0
    ly: float = 10.0
    lz: float = 10.0
    dx: float = 0.0
    dy: float = 0.0
    dz: float = 0.0
    dt: float = 0.0
    dtype: Any = None
    hide_comm: bool = False


def _inn(A):
    return A[1:-1, 1:-1, 1:-1]


def _gaussians(X, Y, Z, params: Params, jnp):
    """The reference's two pairs of Gaussian anomalies (lines :34-37)."""
    lx, ly, lz = params.lx, params.ly, params.lz
    cp = params.cp_min + (
        5 * jnp.exp(-((X - lx / 1.5) ** 2) - (Y - ly / 2) ** 2 - (Z - lz / 1.5) ** 2)
        + 5 * jnp.exp(-((X - lx / 3.0) ** 2) - (Y - ly / 2) ** 2 - (Z - lz / 1.5) ** 2)
    )
    t = 100 * jnp.exp(
        -(((X - lx / 2) / 2) ** 2) - ((Y - ly / 2) / 2) ** 2 - ((Z - lz / 3.0) / 2) ** 2
    ) + 50 * jnp.exp(
        -(((X - lx / 2) / 2) ** 2) - ((Y - ly / 2) / 2) ** 2 - ((Z - lz / 1.5) / 2) ** 2
    )
    return cp, t


def setup(
    nx: int = 128,
    ny: int = 128,
    nz: int = 128,
    *,
    lam: float = 1.0,
    cp_min: float = 1.0,
    lx: float = 10.0,
    ly: float = 10.0,
    lz: float = 10.0,
    dtype=None,
    hide_comm: bool = False,
    init_grid: bool = True,
    ic_scale: float = 1.0,
    **grid_kwargs,
):
    """Initialize the global grid (unless ``init_grid=False``) and the fields.

    Returns ``(state, params)`` where ``state = (T, Cp)`` are global-block
    fields with the reference's initial conditions (lines :34-37).
    ``ic_scale`` scales the initial temperature anomaly — the ensemble
    lever: `models._batched.batched_setup` gives each member a distinct
    scale so batched members are distinct problems on one grid.
    """
    import jax
    import jax.numpy as jnp

    if init_grid:
        init_global_grid(nx, ny, nz, **grid_kwargs)
    if dtype is None:
        dtype = jax.dtypes.canonicalize_dtype(float)
    dx = lx / (nx_g() - 1)  # reference line :21-23
    dy = ly / (ny_g() - 1)
    dz = lz / (nz_g() - 1)
    dt = min(dx * dx, dy * dy, dz * dz) * cp_min / lam / 8.1  # reference line :39
    params = Params(
        lam=lam, cp_min=cp_min, lx=lx, ly=ly, lz=lz,
        dx=dx, dy=dy, dz=dz, dt=dt, dtype=dtype, hide_comm=hide_comm,
    )
    T = zeros((nx, ny, nz), dtype)
    X, Y, Z = coord_fields(T, (dx, dy, dz), dtype=dtype)

    @stencil
    def init_ic(X, Y, Z):
        cp, t = _gaussians(X, Y, Z, params, jnp)
        return cp.astype(dtype), (ic_scale * t).astype(dtype)

    Cp, T = init_ic(X, Y, Z)
    return (T, Cp), params


def _diffusion_update(params: Params):
    """Per-block, pure T update (no exchange): the reference's five broadcast
    kernels (lines :41-45) fused into one expression.

    Formulation chosen by measurement on TPU: with scalar conductivity the
    flux divergence is the Laplacian, computed from interior slices and added
    back as ``T + pad(delta, 1)`` — ~1.5x faster than the literal
    flux-arrays + scatter-update translation (`.at[1:-1,...].set` lowers to an
    unaligned dynamic-update-slice against the (8,128)-tiled layout, and the
    intermediate flux arrays cost extra HBM passes).  The padded-delta form
    also freezes the outermost ring (width 1 = stencil radius), exactly the
    reference's boundary behavior.
    """
    import jax.numpy as jnp

    lam, dt = params.lam, params.dt
    dx, dy, dz = params.dx, params.dy, params.dz

    def update(T, Cp):
        lap = (
            (T[2:, 1:-1, 1:-1] - 2 * _inn(T) + T[:-2, 1:-1, 1:-1]) / (dx * dx)
            + (T[1:-1, 2:, 1:-1] - 2 * _inn(T) + T[1:-1, :-2, 1:-1]) / (dy * dy)
            + (T[1:-1, 1:-1, 2:] - 2 * _inn(T) + T[1:-1, 1:-1, :-2]) / (dz * dz)
        )
        delta = (dt * lam) / _inn(Cp) * lap
        return T + jnp.pad(delta, 1)

    return update


def make_step(params: Params, *, donate: bool = True, batch: bool = False):
    """Build the jitted SPMD time step: ``(T, Cp) -> (T, Cp)``.

    One call = one fused XLA program: stencil update + halo exchange
    (+ overlap scheduling when ``params.hide_comm``).

    ``batch=True``: the ensemble step over ``(B, nx, ny, nz)`` batched
    fields (`models._batched`) — `jax.vmap` of the same per-block step, so
    B members advance bit-identically to B independent calls while every
    exchanged dimension still issues ONE collective pair (the ppermute
    batching rule carries the ensemble axis inside the same hop).

    Unlike `make_multi_step`'s, this callable records no ``igg.multi_step``
    span: at one step per call, even the microseconds a span costs are a
    visible share of a step (a time loop's ``igg.step`` span covers it).
    """
    update = _diffusion_update(params)

    if params.hide_comm:
        overlapped = hide_communication(update, radius=1)

        def block_step(T, Cp):
            return overlapped(T, Cp), Cp

    else:

        def block_step(T, Cp):
            T = update(T, Cp)
            T = update_halo(T)
            return T, Cp

    return _fused.stencil_call(block_step, 2, (0,) if donate else (), batch)


def pipelined_support_error(shape, k, itemsize: int = 4, bx=None, by=None,
                            gg=None) -> str | None:
    """Why the pipelined group schedule cannot split this config, or None.

    The same decision the ``pipelined`` knob's auto mode makes at trace
    time (`models._fused.pipelined_support_error` over the diffusion
    kernel's envelope) — exported for benchmark provenance.
    """
    return _fused.pipelined_support_error(FAMILY, shape, k, itemsize, bx, by, gg)


def _tune_state(params: Params):
    """Synthetic ones-filled state for autotuner candidate measurement
    (`tuning.search`): the first steps are linear on ones (lap(1) = 0 — no
    NaN risk) and the fields are real global-block sharded arrays, so a
    measured candidate runs the production SPMD program."""
    from .. import ones
    from ..parallel.grid import global_grid

    shape = tuple(global_grid().nxyz)
    return ones(shape, params.dtype), ones(shape, params.dtype)


def _cell_zpatch(transposed: bool, coalesce=None):
    """The diffusion kernel's z-patch operations on its one cell field:
    full-y tiles take the transposed thin-plane patch layout
    (`ops.pallas_stencil.zpatch_transposed`, ~16x less patch/export window
    traffic), others the packed 128-lane one.  The x/y exchange of ``T``
    and of its export run in ONE coalesced pass (one permute pair per dim
    for the pair of fields; corner semantics preserved), then the z
    communication runs on the export alone."""
    from ..ops import halo

    if transposed:
        def exchange(s, exports, width):
            T, zex = halo.exchange_dims_multi(
                (s[0], *exports), (0, 1), width=width,
                logicals=(None, tuple(s[0].shape)), axes=(None, halo._T_AXES),
                coalesce=coalesce,
            )
            return (T,), halo.z_patch_from_export_t(zex, width=width)

        def finish(exports, s, width):
            zex = halo.exchange_dims_t(
                exports[0], width=width, shape=tuple(s[0].shape), coalesce=coalesce
            )
            return halo.z_patch_from_export_t(zex, width=width)

        return _fused.ZPatch(
            identity=lambda s, width: halo.identity_z_patch_t(s[0], width=width),
            exchange=exchange, finish=finish,
            apply=lambda s, p, width: (halo.apply_z_patch_t(s[0], p, width=width),),
        )

    def exchange(s, exports, width):
        T, zex = halo.exchange_dims_multi(
            (s[0], *exports), (0, 1), width=width, coalesce=coalesce
        )
        return (T,), halo.z_patch_from_export(zex, width=width)

    def finish(exports, s, width):
        zex = halo.exchange_dims(exports[0], (0, 1), width=width)
        return halo.z_patch_from_export(zex, width=width)

    return _fused.ZPatch(
        identity=lambda s, width: halo.identity_z_patch(s[0], width=width),
        exchange=exchange, finish=finish,
        apply=lambda s, p, width: (halo.apply_z_patch(s[0], p, width=width),),
    )


#: the diffusion kernel family (`ops/pallas_stencil.py`); its hooks are bound
#: per `make_multi_step` call
FAMILY = _fused.Family("diffusion", pallas_stencil, z_wrap=True, idle_steps=True)


@_fused.spanned_multi_step("diffusion3d")
def make_multi_step(
    params: Params,
    nsteps: int,
    *,
    donate: bool = True,
    fused_k: int | None = None,
    fused_tile: tuple[int, int] | None = None,
    exchange_every: int = 1,
    pipelined: bool | None = None,
    batch: bool = False,
    coalesce: bool | None = None,
):
    """Like `make_step` but advances ``nsteps`` steps per call via `lax.fori_loop`.

    TPU-first: the whole loop is one XLA program, so per-call dispatch
    overhead amortizes away and the compiler schedules across iterations —
    use this for production runs and benchmarks.

    ``exchange_every=w`` (XLA path): on a deep-halo grid (``overlap >= 2w``
    in every dimension with halo activity) run ``w`` stencil steps between
    halo exchanges and exchange a width-``w`` slab — one collective per
    ``w`` steps, results at group boundaries identical up to compiler
    fusion rounding (bitwise on the CPU mesh; few f32 ULPs on TPU, where
    differently-fused programs contract FMAs differently) — the w-deep
    stale rind each block accumulates is exactly the slab the exchange
    replaces with the neighbor's still-exact planes.  The latency-amortization half
    of the deep-halo story without the Pallas kernel; combine with
    ``fused_k=w`` to also amortize HBM traffic.

    ``fused_k``: advance ``fused_k`` steps per HBM pass with the
    temporally-blocked Pallas kernel (`ops/pallas_stencil.py`) — the analogue
    of the reference's custom-kernel-when-generic-is-slow move
    (`/root/reference/src/update_halo.jl:430`), here lifting T_eff past the
    streaming bound.  On a grid with no halo activity (single block,
    non-periodic) the kernel runs alone.  On a communicating grid the block
    needs a **deep halo**: every dimension with halo activity must have
    ``overlap >= 2*fused_k`` (``init_global_grid(..., overlapx=2*k, ...)``);
    the chunk then alternates ``fused_k`` kernel steps with ONE slab
    exchange (`update_halo(T, width=fused_k)`) — k steps per HBM pass *and*
    per collective, so both the memory and the latency cost amortize.
    Requires ``nsteps % fused_k == 0`` and TPU-compatible shapes (see
    `fused_diffusion_steps`); the cadence and its trace-time choice of
    kernel path are `models._fused.fused_cadence`'s.

    ``pipelined`` (default auto): run the fused groups on the
    boundary-first pipelined schedule
    (`models._fused.run_pipelined_group_schedule`) — each group's kernel
    launch splits into a ring pass that feeds the slab exchange early and
    an interior pass XLA schedules across the in-flight
    `collective-permute`s.  Bit-identical to the serialized schedule
    (`pipelined=False`); auto turns it on whenever the grid communicates
    in x/y and the tile split is admissible
    (`pipelined_support_error`).  ``pipelined=True`` also applies the
    early-dispatch exchange shape to the XLA cadences (the fused fallback
    and ``exchange_every``).

    ``batch``: vmap the whole cadence over a leading ensemble axis (see
    `make_step`).  Every path — fused Pallas chunks included (the
    `pallas_call` batching rule adds the ensemble as an outer grid
    dimension), slab exchanges, pipelined begin/finish — batches through
    the same vmap, and the per-(dimension, width group) collective budget
    is B-invariant (pinned by `analysis.budget.batched_budget_findings`).

    ``coalesce`` (None = the ``IGG_COALESCE`` env default, auto): the
    cadence's multi-field exchanges pass it through to `ops.halo`
    (bit-identical either way; the diffusion cadence exchanges a single
    field except on the z-patch path, so the knob mostly matters to the
    acoustic/porous siblings — it exists here so a tuned config is one
    vocabulary across the three models).

    ``autotune`` (None = ``IGG_AUTOTUNE`` env, default off): substitute the
    cached winner config of this (backend, topology, model, local size,
    dtype, batch) point into the kwargs above — searching (cost-model
    pruned, short measured runs) and persisting it on first use
    (`implicitglobalgrid_tpu.tuning`, docs/performance.md;
    `models._fused.spanned_multi_step` reads it).  A pure schedule
    substitution: results stay bit-identical to the default config.
    Explicitly-set kwargs always win — autotune only fills fields left at
    their defaults.
    """
    from jax import lax

    update = _diffusion_update(params)
    donate_argnums = (0,) if donate else ()

    def xla_path(w, early=False):
        """``w`` steps per exchange: ``w=1`` the per-step exchange (overlapped
        under ``hide_comm``), else a width-``w`` slab exchange after every
        ``w`` steps (``early``: its early-dispatch shape)."""
        if w == 1:
            if params.hide_comm:
                one = hide_communication(update, radius=1)
            else:

                def one(T, Cp):
                    return update_halo(update(T, Cp), coalesce=coalesce)

            def block_step(T, Cp):
                T = lax.fori_loop(0, nsteps, lambda i, T: one(T, Cp), T)
                return T, Cp

            return block_step

        def block_step(T, Cp):
            def group(i, T):
                T = lax.fori_loop(0, w, lambda j, T: update(T, Cp), T)
                return _fused.group_exchange((T,), w, early, coalesce)[0]

            return lax.fori_loop(0, nsteps // w, group, T), Cp

        return block_step

    if fused_k:
        from ..ops import halo
        from ..ops.pallas_stencil import fused_diffusion_steps, zpatch_transposed

        cx = params.dt * params.lam / (params.dx * params.dx)
        cy = params.dt * params.lam / (params.dy * params.dy)
        cz = params.dt * params.lam / (params.dz * params.dz)

        def launch(ki, s, Cp, bx, by, z_patches=None, **kw):
            if z_patches is not None:
                kw.update(z_patch=z_patches, z_export=True)
            out = fused_diffusion_steps(s[0], Cp, ki, cx, cy, cz, bx=bx, by=by, **kw)
            return out if isinstance(out, tuple) else (out,)

        def exchange(s, width, dims=None):
            # ``dims=(0, 1)``: the x/y slabs of a grid whose z the kernel
            # wraps in its tiles
            if dims is None:
                return (update_halo(s[0], width=width, coalesce=coalesce),)
            return (halo.exchange_dims(s[0], dims, width=width),)

        def zpatch(s):
            T, tile = s[0], fused_tile or (None, None)
            tr = zpatch_transposed(tuple(T.shape), fused_k, T.dtype.itemsize, *tile)
            return _cell_zpatch(tr, coalesce)

        fam = FAMILY.bind(
            enter=lambda T, Cp: ((T,), Cp),
            leave=lambda s, Cp: (s[0], Cp),
            launch=launch,
            exchange=exchange,
            zpatch=zpatch,
            xla=xla_path,
        )
        return _fused.fused_multi_step(
            fam, fused_k, nsteps, nfields=2, donate_argnums=donate_argnums,
            batch=batch, fused_tile=fused_tile, exchange_every=exchange_every,
            pipelined=pipelined, hide_comm=params.hide_comm, coalesce=coalesce,
        )

    _fused.check_xla_cadence(
        FAMILY, exchange_every, nsteps=nsteps, pipelined=pipelined,
        hide_comm=params.hide_comm,
    )
    return _fused.stencil_call(
        xla_path(exchange_every, bool(pipelined)), 2, donate_argnums, batch
    )


def run(
    nt: int,
    nx: int = 128,
    ny: int = 128,
    nz: int = 128,
    *,
    finalize: bool = True,
    guard_every: int | None = None,
    guard_policy: str | None = None,
    checkpoint_every: int | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_keep: int | None = None,
    integrity_every: int | None = None,
    **setup_kwargs,
):
    """End-to-end run (the reference's ``diffusion3D()`` without visualization).

    Returns the final global-block temperature field.

    Resilience hooks (kwarg > ``IGG_*`` env > off; docs/robustness.md):
    ``guard_every=N`` runs the `igg.check_fields` NaN/Inf probe every ``N``
    steps under ``guard_policy`` (``raise`` | ``warn`` | ``rollback``);
    ``checkpoint_every=N`` writes restartable checkpoints to
    ``checkpoint_dir`` — a rerun pointing at the same directory resumes
    from the latest VALID one, even on a different admissible topology
    (elastic restart: re-init with any ``dims``/local sizes implying the
    same global grid).  ``checkpoint_keep=N`` (``IGG_CHECKPOINT_KEEP``)
    prunes to the newest N generations after each save, never deleting the
    only integrity-verified one.  ``integrity_every=N``
    (``IGG_INTEGRITY_EVERY``) arms the shadow-step audit: every Nth step
    is re-executed from retained pre-step state and bit-compared — a
    finite silent corruption the NaN/Inf guard can never see raises
    `integrity.IntegrityError` naming the corrupting rank.
    """
    import jax

    from ..parallel.grid import global_grid, grid_is_initialized
    from ..utils.resilience import RunGuard, guarded_time_loop

    caller_owns_grid = grid_is_initialized()  # init_grid=False with a live grid
    try:
        from ..utils import tracing as _tracing

        # Live plane up BEFORE the long bring-up/compile phase (no-op
        # unless IGG_METRICS_PORT is set): an operator can scrape /healthz
        # while the program is still building (docs/observability.md).
        from ..utils import liveplane as _liveplane

        _liveplane.ensure_server()
        # Setup span: grid bring-up + field allocation, distinct from the
        # per-step `igg.step` spans the loop records (docs/observability.md).
        with _tracing.trace_span("igg.run.setup", model="diffusion3d"):
            state, params = setup(nx, ny, nz, **setup_kwargs)
            step = make_step(params)
        guard = RunGuard(
            guard_every=guard_every,
            policy=guard_policy,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            checkpoint_keep=checkpoint_keep,
            integrity_every=integrity_every,
            names=("T", "Cp"),
        )
        # On the virtual CPU mesh, XLA's in-process collectives deadlock if
        # too many asynchronously dispatched programs pile up; syncing each
        # step costs nothing there and is skipped on real accelerators.
        sync_every_step = global_grid().mesh.devices.flat[0].platform == "cpu"
        # Telemetry bytes model (docs/observability.md): the diffusion step
        # MUST stream T once in and once out; Cp is a read-only parameter
        # field and does not count (the reference T_eff convention).
        from ..utils.telemetry import teff_bytes

        state = guarded_time_loop(
            step, state, nt, guard=guard, sync_every_step=sync_every_step,
            model="diffusion3d", bytes_per_step=teff_bytes(state[:1]),
        )
        T = jax.block_until_ready(state[0])
    except BaseException:
        # A failed run must not poison the next init_global_grid in this
        # process (the singleton would report "already initialized") — but
        # never tear down a grid the caller set up themselves.
        if not caller_owns_grid and grid_is_initialized():
            finalize_global_grid()
        raise
    if finalize:
        finalize_global_grid()
    return T


def temperature(state):
    return state[0]
