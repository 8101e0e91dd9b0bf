"""3-D porous convection — the HydroMech3D weak-scaling analogue.

BASELINE config 4.  The reference's headline scaling result is a multi-physics
hydro-mechanical solver built on its grid (`/root/reference/README.md:6-8`);
the publicly documented miniapp of that family is pseudo-transient porous
convection (Darcy flow + temperature advection-diffusion, the
PorousConvection3D miniapp of the reference's ecosystem).  This module builds
it TPU-first:

* **Pseudo-transient pressure solve**: each time step runs ``npt`` relaxation
  iterations of the Darcy flux / fluid pressure pair inside `lax.fori_loop` —
  the whole inner solver is ONE XLA program with a halo exchange per
  iteration, the communication pattern that dominates the reference's
  weak-scaling benchmark.
* **Comm-lean exchange**: the per-iteration exchange is the PRESSURE (one
  cell field), not the three staggered fluxes — the fluxes at every interior
  face are recomputable from post-exchange ``Pf`` (plus their own local
  relaxation history, which never crosses the block edge), so one plane per
  side per dimension crosses the wire instead of three.  3x less
  communication volume per PT iteration than the flux-exchange formulation
  on the same grid; a single 3-field flux exchange at the end of the PT loop
  restores the all-duplicated-cells-agree invariant for the frozen face
  rings (gather/visualization contract).
* **Staggered fields**: Darcy fluxes live on cell faces (``n+1`` shapes).
* **Buoyancy** (Boussinesq): ``qD = -k/eta * (grad(Pf) - Ra_hat * T * e_z)``.
* **Temperature**: explicit upwind advection + diffusion, interior update +
  halo exchange; frozen boundary planes give Dirichlet walls in z (hot
  bottom / cold top) and fixed side walls.

State: ``(T, Pf, qDx, qDy, qDz)``, all global-block fields.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from .. import (
    coord_fields,
    finalize_global_grid,
    init_global_grid,
    stencil,
    update_halo,
    zeros,
)
from ..ops import pallas_pt
from ..ops.overlap import hide_communication
from . import _fused


@dataclasses.dataclass(frozen=True)
class Params:
    Ra: float = 1000.0  # Rayleigh number
    lx: float = 2.0
    ly: float = 1.0
    lz: float = 1.0
    dT: float = 1.0  # temperature contrast bottom-top
    phi: float = 0.1  # porosity
    lam_T: float = 1.0 / 1000.0  # effective thermal diffusivity (lam/rhoCp = 1/Ra)
    dx: float = 0.0
    dy: float = 0.0
    dz: float = 0.0
    dt: float = 0.0
    theta_q: float = 0.5  # PT relaxation for fluxes
    beta_p: float = 0.0  # PT relaxation for pressure (set in setup, see bound below)
    npt: int = 20  # PT iterations per time step
    dtype: Any = None
    hide_comm: bool = False


def _inn(A):
    return A[1:-1, 1:-1, 1:-1]


def setup(
    nx: int = 32,
    ny: int = 32,
    nz: int = 32,
    *,
    Ra: float = 1000.0,
    lx: float = 2.0,
    ly: float = 1.0,
    lz: float = 1.0,
    dT: float = 1.0,
    npt: int = 20,
    dtype=None,
    hide_comm: bool = False,
    init_grid: bool = True,
    ic_scale: float = 1.0,
    **grid_kwargs,
):
    """Grid + fields: linear conductive T profile with a central Gaussian
    perturbation (the standard porous-convection initial condition), zero
    pressure and fluxes.  Returns ``(state, params)``.  ``ic_scale`` scales
    the Gaussian perturbation (the ensemble lever,
    `models._batched.batched_setup`)."""
    import jax
    import jax.numpy as jnp

    from ..utils import tools

    if init_grid:
        init_global_grid(nx, ny, nz, **grid_kwargs)
    if dtype is None:
        dtype = jax.dtypes.canonicalize_dtype(float)
    dx = lx / (tools.nx_g() - 1)
    dy = ly / (tools.ny_g() - 1)
    dz = lz / (tools.nz_g() - 1)
    lam_T = 1.0 / Ra
    dmin = min(dx, dy, dz)
    # Fixed dt bounded by both explicit limits (miniapp simplification of the
    # adaptive dt used in the reference ecosystem): diffusive dmin^2/lam/8.1,
    # and advective phi*dmin/(3*q_scale) with the buoyancy-limited flux scale
    # q_scale = Ra*lam_T*dT.
    phi = 0.1
    q_scale = Ra * lam_T * dT
    dt = min(dmin**2 / lam_T / 8.1, phi * dmin / (3.0 * q_scale))
    # Pressure relaxation: von Neumann bound for the (theta, beta) PT pair is
    # beta*theta*k^2 <= 2 with the 3-D staggered-Laplacian spectral bound
    # k^2 <= 4*(1/dx^2 + 1/dy^2 + 1/dz^2).
    theta_q = 0.5
    k2_max = 4.0 * (1.0 / dx**2 + 1.0 / dy**2 + 1.0 / dz**2)
    beta_p = 0.9 * 2.0 / (theta_q * k2_max)
    params = Params(
        Ra=Ra, lx=lx, ly=ly, lz=lz, dT=dT, phi=phi, lam_T=lam_T,
        dx=dx, dy=dy, dz=dz, dt=dt, theta_q=theta_q, beta_p=beta_p,
        npt=int(npt), dtype=dtype, hide_comm=hide_comm,
    )

    T0 = zeros((nx, ny, nz), dtype)
    X, Y, Z = coord_fields(T0, (dx, dy, dz), dtype=dtype)

    @stencil
    def init_ic(X, Y, Z):
        # Conductive profile: +dT/2 at z=0 (hot bottom) to -dT/2 at z=lz.
        prof = dT / 2 - dT * Z / lz
        pert = (
            0.1
            * dT
            * jnp.exp(
                -(((X - lx / 2) / 0.1) ** 2)
                - ((Y - ly / 2) / 0.1) ** 2
                - ((Z - lz / 2) / 0.1) ** 2
            )
        )
        return (prof + ic_scale * pert).astype(dtype)

    T = init_ic(X, Y, Z)
    Pf = zeros((nx, ny, nz), dtype)
    qDx = zeros((nx + 1, ny, nz), dtype)
    qDy = zeros((nx, ny + 1, nz), dtype)
    qDz = zeros((nx, ny, nz + 1), dtype)
    return (T, Pf, qDx, qDy, qDz), params


def _flux_update(params: Params):
    """Pure per-block Darcy flux relaxation (no exchange): interior faces only
    (padded-delta form — boundary faces frozen, the no-flow walls)."""
    import jax.numpy as jnp

    th = params.theta_q
    dx, dy, dz = params.dx, params.dy, params.dz

    def av_z_to_face(T):
        # T averaged onto interior z-faces: (nx-2, ny-2, nz-1)
        return 0.5 * (T[1:-1, 1:-1, 1:] + T[1:-1, 1:-1, :-1])

    def update(T, Pf, qDx, qDy, qDz):
        # Darcy flux relaxation toward -grad(Pf) + Ra*T e_z (interior faces).
        fx = -jnp.diff(Pf[:, 1:-1, 1:-1], axis=0) / dx
        fy = -jnp.diff(Pf[1:-1, :, 1:-1], axis=1) / dy
        fz = -jnp.diff(Pf[1:-1, 1:-1, :], axis=2) / dz + params.Ra * params.lam_T * av_z_to_face(T)
        qDx = qDx + jnp.pad(th * (fx - _inn(qDx)), 1)
        qDy = qDy + jnp.pad(th * (fy - _inn(qDy)), 1)
        qDz = qDz + jnp.pad(th * (fz - _inn(qDz)), 1)
        return qDx, qDy, qDz

    return update


def _pressure_update(params: Params):
    """Pure per-block pressure relaxation: all cells, from fresh fluxes.

    At global walls the frozen boundary faces carry flux 0, so the outermost
    cells evolve under the physical no-flow condition; at block-internal
    edges the same expression writes garbage into the halo cells (stale
    frozen faces), which the Pf exchange overwrites with the neighbor's
    interior values — the standard recompute-then-exchange pattern."""
    import jax.numpy as jnp

    bp = params.beta_p
    dx, dy, dz = params.dx, params.dy, params.dz

    def update(Pf, qDx, qDy, qDz):
        div = (
            jnp.diff(qDx, axis=0) / dx
            + jnp.diff(qDy, axis=1) / dy
            + jnp.diff(qDz, axis=2) / dz
        )
        return Pf - bp * div

    return update


def _pt_iteration(params: Params):
    """One pseudo-transient Darcy relaxation: flux update (+buoyancy) on
    interior faces, pressure update at all cells, halo exchange of ``Pf``
    (ONE field — see the module docstring's comm-lean design note; the
    reference's analogue exchanges every relaxed field per iteration,
    `/root/reference/src/update_halo.jl:25-78` applied in its miniapp loops).
    The fluxes need no per-iteration exchange: their interior faces are
    recomputed each iteration from post-exchange ``Pf`` halos and their own
    (purely local) relaxation history.  With ``params.hide_comm`` the ``Pf``
    exchange overlaps the interior pressure update (`hide_communication`)."""
    flux_update = _flux_update(params)
    p_update = _pressure_update(params)

    if params.hide_comm:
        overlapped_p = hide_communication(p_update, radius=1)

        def pressure_exchanged(Pf, qDx, qDy, qDz):
            return overlapped_p(Pf, qDx, qDy, qDz)

    else:

        def pressure_exchanged(Pf, qDx, qDy, qDz):
            return update_halo(p_update(Pf, qDx, qDy, qDz))

    def iteration(T, Pf, qDx, qDy, qDz):
        qDx, qDy, qDz = flux_update(T, Pf, qDx, qDy, qDz)
        Pf = pressure_exchanged(Pf, qDx, qDy, qDz)
        return Pf, qDx, qDy, qDz

    return iteration


def _temperature_update(params: Params):
    """Explicit upwind advection + diffusion of T (interior), frozen walls."""
    import jax.numpy as jnp

    dx, dy, dz = params.dx, params.dy, params.dz
    lam = params.lam_T
    iphi = 1.0 / params.phi
    dt = params.dt

    def update(T, qDx, qDy, qDz):
        lap = (
            (T[2:, 1:-1, 1:-1] - 2 * _inn(T) + T[:-2, 1:-1, 1:-1]) / (dx * dx)
            + (T[1:-1, 2:, 1:-1] - 2 * _inn(T) + T[1:-1, :-2, 1:-1]) / (dy * dy)
            + (T[1:-1, 1:-1, 2:] - 2 * _inn(T) + T[1:-1, 1:-1, :-2]) / (dz * dz)
        )
        # Upwind advective derivatives at interior cells from face fluxes.
        qxm = qDx[1:-2, 1:-1, 1:-1]  # face below cell (x), interior cells
        qxp = qDx[2:-1, 1:-1, 1:-1]  # face above cell (x)
        qym = qDy[1:-1, 1:-2, 1:-1]
        qyp = qDy[1:-1, 2:-1, 1:-1]
        qzm = qDz[1:-1, 1:-1, 1:-2]
        qzp = qDz[1:-1, 1:-1, 2:-1]
        dTm_x = (_inn(T) - T[:-2, 1:-1, 1:-1]) / dx
        dTp_x = (T[2:, 1:-1, 1:-1] - _inn(T)) / dx
        dTm_y = (_inn(T) - T[1:-1, :-2, 1:-1]) / dy
        dTp_y = (T[1:-1, 2:, 1:-1] - _inn(T)) / dy
        dTm_z = (_inn(T) - T[1:-1, 1:-1, :-2]) / dz
        dTp_z = (T[1:-1, 1:-1, 2:] - _inn(T)) / dz
        adv = (
            jnp.maximum(qxm, 0.0) * dTm_x
            + jnp.minimum(qxp, 0.0) * dTp_x
            + jnp.maximum(qym, 0.0) * dTm_y
            + jnp.minimum(qyp, 0.0) * dTp_y
            + jnp.maximum(qzm, 0.0) * dTm_z
            + jnp.minimum(qzp, 0.0) * dTp_z
        )
        dTdt = lam * lap - iphi * adv
        return T + jnp.pad(dt * dTdt, 1)

    return update


def _build_block_step(params: Params, coalesce: bool | None = None):
    """One whole time step (per-iteration exchange cadence), shared verbatim
    by `make_step` and `make_multi_step(exchange_every=1)` so the physics can
    never diverge between the two entry points: ``npt`` PT iterations
    (fori_loop, per-iteration ``Pf`` exchange), the once-per-step 3-field
    flux exchange (refreshing only the frozen face rings — the interior
    faces are already exact — to restore the duplicated-cells-agree
    invariant for gather/visualization), then the T update + exchange.
    ``coalesce``: forwarded to the multi-field flux exchange
    (`make_multi_step`'s knob; None = the ``IGG_COALESCE`` default)."""
    from jax import lax

    pt_iter = _pt_iteration(params)
    t_update = _temperature_update(params)
    npt = params.npt

    def block_step(T, Pf, qDx, qDy, qDz):
        def body(i, s):
            Pf, qDx, qDy, qDz = s
            return pt_iter(T, Pf, qDx, qDy, qDz)

        Pf, qDx, qDy, qDz = lax.fori_loop(0, npt, body, (Pf, qDx, qDy, qDz))
        qDx, qDy, qDz = update_halo(qDx, qDy, qDz, coalesce=coalesce)
        T = t_update(T, qDx, qDy, qDz)
        T = update_halo(T)
        return T, Pf, qDx, qDy, qDz

    return block_step


def _tune_state(params: Params):
    """Synthetic ones-filled state for autotuner candidate measurement
    (`tuning.search`): finite on ones (linear relaxations), real
    global-block sharded fields — a measured candidate runs the production
    SPMD program.  ``npt`` is part of the cache KEY (it changes numerics),
    so the state carries no tuned physics."""
    from .. import ones
    from ..parallel.grid import global_grid

    nx, ny, nz = global_grid().nxyz
    dt = params.dtype
    return (
        ones((nx, ny, nz), dt), ones((nx, ny, nz), dt),
        ones((nx + 1, ny, nz), dt), ones((nx, ny + 1, nz), dt),
        ones((nx, ny, nz + 1), dt),
    )


def make_step(params: Params, *, donate: bool = True, batch: bool = False):
    """One time step: ``npt`` PT pressure iterations (fori_loop) + T update,
    compiled into one XLA program per block (see `_build_block_step`).

    ``batch=True``: the ensemble step over ``(B, ...)`` batched fields —
    `jax.vmap` of the same per-block step; bit-identical per member, one
    collective pair per exchanged dimension at any B (see
    `models.diffusion3d.make_step`).  No ``igg.multi_step`` span: at one
    step per call its microseconds would be a visible share of a step.
    """
    return _fused.stencil_call(
        _build_block_step(params), 5, tuple(range(5)) if donate else (), batch
    )


def _pt_schedule(npt: int, w: int, *, even: bool = True):
    """Chunk ``npt`` PT iterations into groups of at most ``w``: ``(lead,
    chunks)``.

    ``even=True`` (the fused cadence — Pallas kernels need even k): ``lead``
    (0 or 1) per-iteration-exchanged XLA iterations for odd ``npt``, then
    greedy even chunks; ``w < 2`` admits no kernel chunk at all (the caller
    falls back to the XLA cadence).  ``even=False`` (the pure-XLA
    ``exchange_every`` cadence, which has no parity constraint): plain
    greedy chunks, so ``npt % w == 0`` reproduces the uniform round-3
    schedule exactly.  Ragged schedules still exchange/patch at width
    ``w`` after every chunk (VERDICT r3 #5).
    """
    if even and w < 2:
        return npt, []
    lead = npt % 2 if even else 0
    rem = npt - lead
    chunks = []
    while rem > 0:
        ki = min(w, rem)
        if even and ki % 2:
            ki -= 1
        chunks.append(ki)
        rem -= ki
    return lead, chunks


def pipelined_support_error(shape, k, itemsize: int = 4, bx=None, by=None,
                            gg=None, npt=None) -> str | None:
    """Why the pipelined group schedule cannot split this config, or None
    (benchmark provenance; see `models._fused.pipelined_support_error`).

    ``npt``: when given, also require the PT schedule to admit a kernel
    chunk at all (``npt=1`` leaves none, and the cadence then runs the XLA
    path regardless of the split)."""
    if npt is not None and not _pt_schedule(int(npt), k)[1]:
        return f"npt={npt} leaves no even kernel chunk: XLA cadence"
    return _fused.pipelined_support_error(FAMILY, shape, k, itemsize, bx, by, gg)


#: the PT kernel family (`ops/pallas_pt.py`, padded face layout for the
#: fluxes; stagger=1: their ol is one deeper than the grid overlap); the
#: hooks are bound per `make_multi_step` call
FAMILY = _fused.Family(
    "porous", pallas_pt, stagger=1, units=("iteration", "PT iterations")
)


@_fused.spanned_multi_step("porous_convection3d")
def make_multi_step(
    params: Params,
    nsteps: int,
    *,
    donate: bool = True,
    exchange_every: int = 1,
    fused_k: int | None = None,
    fused_tile: tuple[int, int] | None = None,
    pipelined: bool | None = None,
    batch: bool = False,
    coalesce: bool | None = None,
):
    """Advance ``nsteps`` time steps per call in ONE XLA program
    (`lax.fori_loop` over whole time steps) — the production path: per-call
    dispatch amortizes over ``nsteps * npt`` PT iterations, the
    communication pattern of the reference's weak-scaling headline
    (`/root/reference/README.md:6-8`).

    ``exchange_every=w`` (deep-halo grids, ``overlap >= 2w``): the PT inner
    loop runs ``w`` relaxation iterations between exchanges and then
    slab-exchanges ALL FOUR PT fields (``Pf`` + fluxes, width ``w``) in one
    collective call — unlike the per-iteration path, the fluxes' relaxation
    history goes stale in the rind between exchanges (each unexchanged
    iteration contaminates one more ring of both ``Pf`` and ``q``), so the
    slab must replace the fluxes' stale rind too, exactly like the acoustic
    cadence exchanges its incrementally-updated ``P``.  One collective per
    ``w`` PT iterations; owned-cell results bitwise identical to the
    per-iteration path on the CPU mesh (few f32 ULPs on TPU, where
    differently-fused programs round differently).  Requires
    ``npt % w == 0``.

    ``fused_k=w``: run the ``w`` PT iterations between slab exchanges inside
    the temporally-blocked Pallas kernel (`ops/pallas_pt.py`) — one HBM pass
    per field per ``w`` iterations instead of ``w`` read/write sweeps, the
    porous sibling of the diffusion/acoustic ``fused_k`` levers.  Same
    cadence semantics as ``exchange_every=w`` (deep halo ``overlap >= 2w``,
    all-four-field width-``w`` slab exchange per group, ``npt % w == 0``);
    local blocks the kernel envelope rejects warn once and run the XLA
    cadence instead.  On grids with no halo activity the fluxes stay in the
    kernel's padded layout across the whole PT loop (pad/unpad once per time
    step); on communicating grids each group pays one pad/unpad of the three
    flux fields around the slab exchange.

    Loop structure chosen by measurement on v5e (160^3 f32, npt=10): the
    per-step PT loop stays a `lax.fori_loop`, the outer time-step loop is
    unrolled in Python INSIDE the one program — nesting it as a second
    `fori_loop` costs ~35% (225 vs 357 GB/s), while fully unrolling the PT
    loop also loses (~210 GB/s, fusion blow-up).  ``nsteps`` is a small
    production chunk, so the unroll is cheap to compile.

    ``pipelined`` (default auto): boundary-first pipelined group schedule
    for the fused PT groups — ring/interior split launches with the
    all-field slab exchange dispatched off the ring pass, exactly as on
    `models.diffusion3d.make_multi_step` (bit-identical to the serialized
    schedule; auto when admissible, see `pipelined_support_error`).
    ``pipelined=True`` also applies the early-dispatch exchange shape to
    the XLA cadences' group exchange.

    ``coalesce`` (None = ``IGG_COALESCE``, auto): passed through to every
    multi-field exchange of the cadence (`ops.halo`; bit-identical either
    way — the per-field-attribution/A/B knob, tunable per config).
    ``autotune`` (None = ``IGG_AUTOTUNE``, default off): substitute this
    point's cached winner schedule into the kwargs above
    (`implicitglobalgrid_tpu.tuning`; pure substitution — explicit kwargs
    always win, results bit-identical).  ``npt`` is part of the tuning KEY,
    never tuned: it changes the numerics, and tuning changes schedule only.
    """
    from ._fused import group_exchange, run_group_schedule

    t_update = _temperature_update(params)
    flux_update = _flux_update(params)
    p_update = _pressure_update(params)
    npt = params.npt

    def pt_iterate(T, s):
        Pf, qDx, qDy, qDz = s
        qDx, qDy, qDz = flux_update(T, Pf, qDx, qDy, qDz)
        Pf = p_update(Pf, qDx, qDy, qDz)
        return Pf, qDx, qDy, qDz

    def cadence_block_step(w, lead=0, chunks=None, early_exchange=False):
        """One time step at the w-iterations-per-slab-exchange cadence — the
        ONE definition behind both ``exchange_every=w`` and the ``fused_k``
        branch's XLA fallback, so the fallback's bit-identical-to-cadence
        contract can never drift.  The exchanges are no-ops on dimensions
        without halo activity, so the same body serves 1-device grids.

        ``lead``/``chunks``: the ragged schedule for ``npt % w != 0``
        (`_pt_schedule`) — ``lead`` per-iteration-exchanged XLA iterations,
        then Python-unrolled chunks of ``ki <= w`` iterations, each followed
        by a width-``w`` slab exchange (width ``w`` for EVERY chunk: it
        heals any chunk's stale rind and keeps the exchange geometry
        uniform; the sent planes sit ``o - w >= w >= ki`` from the edge, so
        they are exact).  ``early_exchange``: ``pipelined=True``'s
        early-dispatch exchange shape (bit-identical values)."""
        sched = ([w] * (npt // w)) if chunks is None else list(chunks)

        def block_step(T, Pf, qDx, qDy, qDz):
            s = (Pf, qDx, qDy, qDz)
            for _ in range(lead):
                s = update_halo(*pt_iterate(T, s), coalesce=coalesce)

            # The small ki-iteration body is unrolled inside each group (a
            # nested fori_loop is the measured-slow shape); the group
            # sequence runs through `run_group_schedule` with unroll_limit=1
            # and the all-or-nothing shape — unlike the one-Pallas-call
            # fused groups, each XLA group is a large unrolled body, so any
            # uniform run longer than one group stays a fori_loop to bound
            # HLO size AND to keep the fori fusion barrier that makes this
            # cadence bit-identical to the per-iteration path.
            def group(ki, s):
                for _ in range(ki):
                    s = pt_iterate(T, s)
                return group_exchange(s, w, early_exchange, coalesce)

            s = run_group_schedule(
                sched, group, s, unroll_limit=1, fori_excess_only=False
            )
            Pf, qDx, qDy, qDz = s
            T = t_update(T, qDx, qDy, qDz)
            T = update_halo(T)
            return T, Pf, qDx, qDy, qDz

        return block_step

    if fused_k:
        from ..ops.pallas_pt import fused_pt_iterations

        w = fused_k
        # Ragged schedule (VERDICT r3 #5: ``w | npt`` made the kernel benefit
        # depend on a numerics parameter — npt=10 admitted only w=2): chunk
        # npt into even kernel chunks of at most w iterations, preceded by
        # one per-iteration-exchanged XLA iteration when npt is odd.  Patch
        # application and export stay at width w for every chunk (heals the
        # previous chunk's w-deep rind).
        lead, chunks = _pt_schedule(npt, w)
        th = params.theta_q
        idx, idy, idz = 1.0 / params.dx, 1.0 / params.dy, 1.0 / params.dz
        ralam = params.Ra * params.lam_T
        bp = params.beta_p

        def launch(ki, s, T, bx, by, z_patches=None, **kw):
            if z_patches is not None:
                kw.update(z_patch_width=w, z_export=True, z_export_width=w)
            return fused_pt_iterations(
                T, *s, ki, th, idx, idy, idz, ralam, bp,
                bx=bx, by=by, z_patches=z_patches, **kw,
            )

        def enter(T, Pf, qDx, qDy, qDz):
            # The fluxes stay padded across the whole PT loop: one pad/unpad
            # per time step, not one per group.
            for _ in range(lead):
                Pf, qDx, qDy, qDz = update_halo(
                    *pt_iterate(T, (Pf, qDx, qDy, qDz)), coalesce=coalesce
                )
            return _fused.pad_enter(Pf, qDx, qDy, qDz)[0], T

        def leave(s, T):
            Pf, qDx, qDy, qDz = _fused.unpad_leave(s)
            T = t_update(T, qDx, qDy, qDz)
            return update_halo(T), Pf, qDx, qDy, qDz

        def xla(width, early):
            return cadence_block_step(width, lead, chunks, early)

        fam = FAMILY.bind(
            enter=enter, leave=leave, launch=launch, xla=xla,
            **_fused.padded_faces(coalesce),
        )
        block_step, _ = _fused.fused_cadence(
            fam, w, chunks=chunks,
            schedule_err=None if chunks else f"npt={npt} leaves no even kernel chunk",
            fused_tile=fused_tile, exchange_every=exchange_every,
            pipelined=pipelined, hide_comm=params.hide_comm, coalesce=coalesce,
        )
    else:
        _fused.check_xla_cadence(
            FAMILY, exchange_every, pipelined=pipelined, hide_comm=params.hide_comm
        )
        if exchange_every > 1:
            block_step = cadence_block_step(
                exchange_every, *_pt_schedule(npt, exchange_every, even=False),
                early_exchange=bool(pipelined),
            )
        else:
            block_step = _build_block_step(params, coalesce=coalesce)

    # The Python unroll is only cheap for production-sized chunks; past this
    # the trace/HLO grows linearly (each step carries npt PT iterations) and
    # compile time explodes long before any dispatch saving pays back.
    # Callers wanting more steps per sync should call the chunk repeatedly.
    if nsteps > 64:
        raise ValueError(
            f"nsteps={nsteps} would unroll {nsteps} whole time steps into one "
            "program (the outer loop is unrolled by measurement — a nested "
            "fori_loop costs ~35% on v5e); keep chunks <= 64 and call the "
            "step function repeatedly instead"
        )

    def multi(*s):
        for _ in range(nsteps):  # unrolled: see the loop-structure note above
            s = block_step(*s)
        return s

    # batch: vmap over the leading member axis — every path (PT fori_loop,
    # slab exchanges, fused PT kernels via the pallas_call batching rule)
    # batches with a B-invariant collective budget.
    return _fused.stencil_call(
        multi, 5, tuple(range(5)) if donate else (), batch
    )


def run(
    nt: int,
    nx: int = 32,
    ny: int = 32,
    nz: int = 32,
    *,
    finalize: bool = True,
    guard_every: int | None = None,
    guard_policy: str | None = None,
    checkpoint_every: int | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_keep: int | None = None,
    integrity_every: int | None = None,
    **kw,
):
    """End-to-end run; returns the final global-block temperature field.

    Resilience hooks as in `models.diffusion3d.run` (``guard_every`` /
    ``guard_policy`` / ``checkpoint_every`` / ``checkpoint_dir`` /
    ``checkpoint_keep`` / ``integrity_every``; resume is
    topology-elastic)."""
    import jax

    from ..parallel.grid import global_grid, grid_is_initialized
    from ..utils.resilience import RunGuard, guarded_time_loop

    caller_owns_grid = grid_is_initialized()  # init_grid=False with a live grid
    try:
        from ..utils import liveplane as _liveplane
        from ..utils import tracing as _tracing

        # Live plane up BEFORE the long bring-up/compile phase (no-op
        # unless IGG_METRICS_PORT is set; docs/observability.md).
        _liveplane.ensure_server()
        with _tracing.trace_span("igg.run.setup", model="porous_convection3d"):
            state, params = setup(nx, ny, nz, **kw)
            step = make_step(params)
        guard = RunGuard(
            guard_every=guard_every,
            policy=guard_policy,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            checkpoint_keep=checkpoint_keep,
            integrity_every=integrity_every,
            names=("T", "Pf", "qDx", "qDy", "qDz"),
        )
        sync_every_step = global_grid().mesh.devices.flat[0].platform == "cpu"
        # Telemetry bytes model: the whole evolving state (T, Pf, qDx, qDy,
        # qDz) streams per time step; the inner PT iterations move more on
        # top, so the recorded T_eff stays a lower bound (docs convention).
        from ..utils.telemetry import teff_bytes

        state = guarded_time_loop(
            step, state, nt, guard=guard, sync_every_step=sync_every_step,
            model="porous_convection3d", bytes_per_step=teff_bytes(state),
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


def _pt_residual_block(params: Params):
    """Per-block PT defect: ``max |div(qD)|`` over interior cells — the
    pressure equation's residual, the criterion the PT relaxation drives to
    zero.  Interior cells only: the outermost ring evolves under frozen
    boundary faces (physical walls / halo planes) and its defect is not
    driven by the local relaxation."""
    import jax.numpy as jnp

    dx, dy, dz = params.dx, params.dy, params.dz

    def residual(T, Pf, qDx, qDy, qDz):
        div = (
            jnp.diff(qDx, axis=0) / dx
            + jnp.diff(qDy, axis=1) / dy
            + jnp.diff(qDz, axis=2) / dz
        )
        return jnp.max(jnp.abs(_inn(div)))

    return residual


def make_batched_residual(params: Params):
    """Jitted per-member PT residual of a BATCHED state: ``(B,)`` array.

    The serving loop's convergence criterion (ISSUE 8): member ``b``'s
    residual is the global max of its block defects (`_pt_residual_block`
    + `lax.pmax` over the mesh), replicated on every process so all ranks
    mask the same members.  One cheap fused reduction — no collective
    beyond the final scalar pmax per member batch.
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.grid import global_grid
    from ..parallel.topology import AXIS_NAMES
    from jax import shard_map
    from ._batched import _batched_spec

    block = _pt_residual_block(params)
    gg = global_grid()
    if gg.nprocs == 1 and not gg.force_spmd:
        return jax.jit(lambda *s: jax.vmap(block)(*s))

    def body(*state):
        return lax.pmax(jax.vmap(block)(*state), AXIS_NAMES)

    mapped = shard_map(
        body,
        mesh=gg.mesh,
        in_specs=(_batched_spec(4),) * 5,
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)
