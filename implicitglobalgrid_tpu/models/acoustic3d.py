"""3-D acoustic wave on a staggered grid with comm/compute overlap.

BASELINE config 3: velocity–pressure acoustic FDTD, the canonical *staggered*
application of the reference's grid machinery (staggered fields of shape
``n+1`` on one topology are the reference's test-pinned feature,
`/root/reference/test/test_update_halo.jl:828-937`; the solver structure
follows the acoustic miniapp of the reference's sister package
ParallelStencil, referenced at `/root/reference/README.md:10`).

Grid layout (one cell = one pressure point):

* ``P``  at cell centers, local shape ``(nx,   ny,   nz)``
* ``Vx`` on x-faces,      local shape ``(nx+1, ny,   nz)``
* ``Vy`` on y-faces,      local shape ``(nx,   ny+1, nz)``
* ``Vz`` on z-faces,      local shape ``(nx,   ny,   nz+1)``

Update (explicit leapfrog):

    V  -= dt/rho * grad(P)      (interior face points)
    P  -= dt*K   * div(V)       (all cell centers)

On the per-step path only the velocity fields exchange halos: ``P`` is
recomputed everywhere from post-exchange velocities, so its boundary planes
are always fresh — one 3-field `update_halo` per step instead of four.  (The
``exchange_every`` slab cadence in `make_multi_step` is the exception: there
``P``'s rind goes stale between exchanges and all FOUR fields are
slab-exchanged.)  With ``hide_comm=True`` the exchange of the velocity slabs
overlaps the interior velocity update (`hide_communication`), the
reference's `@hide_communication` capability.
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
from ..ops import pallas_leapfrog
from ..ops.overlap import hide_communication
from . import _fused

#: the telemetry counter of traced kernel groups that do the self-neighbour z
#: exchange inside the leapfrog kernel's tiles (``z_wrap``)
Z_SELF_WRAP_COUNTER = _fused.Z_SELF_WRAP_COUNTER


@dataclasses.dataclass(frozen=True)
class Params:
    K: float = 1.0  # bulk modulus
    rho: float = 1.0  # density
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


def setup(
    nx: int = 64,
    ny: int = 64,
    nz: int = 64,
    *,
    K: float = 1.0,
    rho: float = 1.0,
    lx: float = 10.0,
    ly: float = 10.0,
    lz: float = 10.0,
    dtype=None,
    hide_comm: bool = False,
    init_grid: bool = True,
    ic_scale: float = 1.0,
    **grid_kwargs,
):
    """Initialize grid + fields; a Gaussian pressure pulse at the domain center.

    Returns ``(state, params)`` with ``state = (P, Vx, Vy, Vz)``.
    ``ic_scale`` scales the initial pressure pulse (the ensemble lever,
    `models._batched.batched_setup`).
    """
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
    c = (K / rho) ** 0.5
    dt = min(dx, dy, dz) / c / 2.0  # CFL (3-D bound is 1/sqrt(3); 1/2 for margin)
    params = Params(
        K=K, rho=rho, lx=lx, ly=ly, lz=lz, dx=dx, dy=dy, dz=dz, dt=dt,
        dtype=dtype, hide_comm=hide_comm,
    )

    P = zeros((nx, ny, nz), dtype)
    X, Y, Z = coord_fields(P, (dx, dy, dz), dtype=dtype)

    @stencil
    def init_ic(X, Y, Z):
        p0 = 100 * jnp.exp(
            -(((X - lx / 2) / 1.0) ** 2)
            - ((Y - ly / 2) / 1.0) ** 2
            - ((Z - lz / 2) / 1.0) ** 2
        )
        return (ic_scale * p0).astype(dtype)

    P = init_ic(X, Y, Z)
    Vx = zeros((nx + 1, ny, nz), dtype)
    Vy = zeros((nx, ny + 1, nz), dtype)
    Vz = zeros((nx, ny, nz + 1), dtype)
    return (P, Vx, Vy, Vz), params


def _velocity_update(params: Params):
    """Pure per-block velocity update (no exchange): interior face points only
    (padded-delta form — boundary faces frozen, the rigid-wall condition)."""
    import jax.numpy as jnp

    a = params.dt / params.rho

    def update(P, Vx, Vy, Vz):
        dVx = -(a / params.dx) * jnp.diff(P[:, 1:-1, 1:-1], axis=0)  # (nx-1,ny-2,nz-2)
        dVy = -(a / params.dy) * jnp.diff(P[1:-1, :, 1:-1], axis=1)
        dVz = -(a / params.dz) * jnp.diff(P[1:-1, 1:-1, :], axis=2)
        Vx = Vx + jnp.pad(dVx, 1)  # interior of (nx+1,ny,nz)
        Vy = Vy + jnp.pad(dVy, 1)
        Vz = Vz + jnp.pad(dVz, 1)
        return Vx, Vy, Vz

    return update


def _pressure_update(params: Params):
    """Pure per-block pressure update: all centers, from fresh velocities."""
    import jax.numpy as jnp

    b = params.dt * params.K

    def update(P, Vx, Vy, Vz):
        div = (
            jnp.diff(Vx, axis=0) / params.dx
            + jnp.diff(Vy, axis=1) / params.dy
            + jnp.diff(Vz, axis=2) / params.dz
        )
        return P - b * div

    return update


def make_step(params: Params, *, donate: bool = True, batch: bool = False):
    """One fused SPMD leapfrog step: ``(P, Vx, Vy, Vz) -> (P, Vx, Vy, Vz)``.

    ``batch=True``: the ensemble step over ``(B, ...)`` batched fields —
    `jax.vmap` of the same per-block step; bit-identical per member, one
    collective pair per exchanged dimension at any B (see
    `models.diffusion3d.make_step`).  No ``igg.multi_step`` span: at one
    step per call its microseconds would be a visible share of a step.
    """
    v_update = _velocity_update(params)
    p_update = _pressure_update(params)

    if params.hide_comm:
        overlapped = hide_communication(v_update, radius=1)

        def block_step(P, Vx, Vy, Vz):
            Vx, Vy, Vz = overlapped(P, Vx, Vy, Vz)
            P = p_update(P, Vx, Vy, Vz)
            return P, Vx, Vy, Vz

    else:

        def block_step(P, Vx, Vy, Vz):
            Vx, Vy, Vz = v_update(P, Vx, Vy, Vz)
            Vx, Vy, Vz = update_halo(Vx, Vy, Vz)
            P = p_update(P, Vx, Vy, Vz)
            return P, Vx, Vy, Vz

    return _fused.stencil_call(
        block_step, 4, tuple(range(4)) if donate else (), batch
    )


def pipelined_support_error(shape, k, itemsize: int = 4, bx=None, by=None,
                            gg=None) -> str | None:
    """Why the pipelined group schedule cannot split this config, or None
    (benchmark provenance; see `models._fused.pipelined_support_error`)."""
    return _fused.pipelined_support_error(FAMILY, shape, k, itemsize, bx, by, gg)


def _tune_state(params: Params):
    """Synthetic ones-filled state for autotuner candidate measurement
    (`tuning.search`): linear updates on ones stay finite, and the fields
    are real global-block sharded arrays (staggered ``n+1`` faces), so a
    measured candidate runs the production SPMD program."""
    from .. import ones
    from ..parallel.grid import global_grid

    nx, ny, nz = global_grid().nxyz
    dt = params.dtype
    return (
        ones((nx, ny, nz), dt), ones((nx + 1, ny, nz), dt),
        ones((nx, ny + 1, nz), dt), ones((nx, ny, nz + 1), dt),
    )


#: the leapfrog kernel family (`ops/pallas_leapfrog.py`, padded face layout;
#: stagger=1: the face fields' ol is one deeper than the grid overlap).  Its
#: kernel does a self-neighbour z exchange inside its tiles (``z_wrap``); the
#: hooks are bound per `make_multi_step` call.
FAMILY = _fused.Family(
    "acoustic", pallas_leapfrog, stagger=1, z_wrap=True, idle_steps=True
)


@_fused.spanned_multi_step("acoustic3d")
def make_multi_step(
    params: Params, nsteps: int, *, donate: bool = True, exchange_every: int = 1,
    fused_k: int | None = None, fused_tile: tuple[int, int] | None = None,
    pipelined: bool | None = None, batch: bool = False,
    coalesce: bool | None = None,
):
    """``nsteps`` leapfrog steps per call in one XLA program (`lax.fori_loop`).

    ``exchange_every=w``: on a deep-halo grid (``overlap >= 2w``) run ``w``
    leapfrog steps between exchanges and then exchange width-``w`` slabs of
    ALL four fields in one collective call — unlike the per-step path, the
    incrementally-updated ``P`` must be exchanged too (its stale rind is
    never recomputed from fresh velocities, so the slab replaces it with the
    neighbor's still-exact planes).  One collective per ``w`` steps; states
    at group boundaries identical up to compiler fusion rounding (bitwise on
    the CPU mesh; few f32 ULPs on TPU).

    ``fused_k``: advance ``fused_k`` leapfrog steps per HBM pass with the
    temporally-blocked staggered Pallas kernel (`ops/pallas_leapfrog.py`) —
    the staggered sibling of the diffusion model's ``fused_k``, made possible
    by the even-extent padded face layout (`pad_faces`).  On a grid with no
    halo activity the kernel runs alone (pad once per chunk).  On a
    communicating grid every dimension with halo activity needs
    ``overlap >= 2*fused_k``; the chunk then alternates ``fused_k`` kernel
    steps with ONE width-``fused_k`` slab exchange of all four fields (the
    same all-field slab as ``exchange_every`` — the kernel's k-deep
    contaminated rind is exactly the slab the exchange refreshes).  Local
    blocks the kernel envelope rejects warn once and run the XLA path at the
    same cadence (`fused_support_error` is the single source of truth; the
    cadence is `models._fused.fused_cadence`'s).  Requires
    ``nsteps % fused_k == 0``.

    ``pipelined`` (default auto): boundary-first pipelined group schedule —
    ring/interior split launches with the all-field slab exchange
    dispatched off the ring pass, exactly as on
    `models.diffusion3d.make_multi_step` (bit-identical to the serialized
    schedule; auto when admissible, see `pipelined_support_error`).

    ``batch``: vmap the whole cadence over a leading ensemble axis — every
    path batches through the same vmap with a B-invariant collective
    budget (see `models.diffusion3d.make_multi_step`).

    ``coalesce`` (None = ``IGG_COALESCE``, auto): passed through to the
    cadence's all-field exchanges (`ops.halo`; bit-identical either way —
    the A/B-measurement knob, tunable per config).  ``autotune`` (None =
    ``IGG_AUTOTUNE``, default off): substitute this point's cached winner
    schedule into the kwargs above (`implicitglobalgrid_tpu.tuning`; pure
    substitution — explicit kwargs always win, results bit-identical).

    On a periodic z that is not split across devices (every block its own z
    partner) the kernel does the z exchange inside each tile (``z_wrap`` of
    `fused_leapfrog_steps`): each traced group adds 1 to the telemetry
    counter ``fused.z_self_wrap``, which every call registers at 0.
    """
    from jax import lax

    v_update = _velocity_update(params)
    p_update = _pressure_update(params)
    donate_argnums = tuple(range(4)) if donate else ()

    def xla_path(w, early=False):
        """``w`` steps per exchange: ``w=1`` the per-step velocity exchange
        (overlapped under ``hide_comm``), else a width-``w`` slab exchange of
        all four fields after every ``w`` steps (``early``: its
        early-dispatch shape)."""
        if w > 1:
            v_step = v_update
        elif params.hide_comm:
            v_step = hide_communication(v_update, radius=1)
        else:

            def v_step(P, Vx, Vy, Vz):
                return update_halo(*v_update(P, Vx, Vy, Vz), coalesce=coalesce)

        def step(i, s):
            P, Vx, Vy, Vz = s
            Vx, Vy, Vz = v_step(P, Vx, Vy, Vz)
            P = p_update(P, Vx, Vy, Vz)
            return (P, Vx, Vy, Vz)

        def block_step(P, Vx, Vy, Vz):
            if w == 1:
                return lax.fori_loop(0, nsteps, step, (P, Vx, Vy, Vz))

            def group(i, s):
                s = lax.fori_loop(0, w, step, s)
                return _fused.group_exchange(s, w, early, coalesce)

            return lax.fori_loop(0, nsteps // w, group, (P, Vx, Vy, Vz))

        return block_step

    if fused_k:
        from ..ops.pallas_leapfrog import fused_leapfrog_steps

        cax = params.dt / params.rho / params.dx
        cay = params.dt / params.rho / params.dy
        caz = params.dt / params.rho / params.dz
        b = params.dt * params.K
        idx, idy, idz = 1.0 / params.dx, 1.0 / params.dy, 1.0 / params.dz

        def launch(ki, s, ctx, bx, by, z_patches=None, **kw):
            if z_patches is not None:
                kw.update(z_export=True)
            return fused_leapfrog_steps(
                *s, ki, cax, cay, caz, b, idx, idy, idz,
                bx=bx, by=by, z_patches=z_patches, **kw,
            )

        fam = FAMILY.bind(
            enter=_fused.pad_enter, leave=_fused.unpad_leave, launch=launch,
            xla=xla_path, **_fused.padded_faces(coalesce),
        )
        return _fused.fused_multi_step(
            fam, fused_k, nsteps, nfields=4, donate_argnums=donate_argnums,
            batch=batch, fused_tile=fused_tile, exchange_every=exchange_every,
            pipelined=pipelined, hide_comm=params.hide_comm, coalesce=coalesce,
        )

    _fused.check_xla_cadence(
        FAMILY, exchange_every, nsteps=nsteps, pipelined=pipelined,
        hide_comm=params.hide_comm,
    )
    return _fused.stencil_call(
        xla_path(exchange_every, bool(pipelined)), 4, donate_argnums, batch
    )


def run(
    nt: int,
    nx: int = 64,
    ny: int = 64,
    nz: int = 64,
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
    """End-to-end run; returns the final global-block pressure field.

    Resilience hooks as in `models.diffusion3d.run` (``guard_every`` /
    ``guard_policy`` / ``checkpoint_every`` / ``checkpoint_dir`` /
    ``checkpoint_keep`` / ``integrity_every``; resume is
    topology-elastic)."""
    import jax

    from ..parallel.grid import global_grid

    from ..parallel.grid import grid_is_initialized
    from ..utils.resilience import RunGuard, guarded_time_loop

    caller_owns_grid = grid_is_initialized()  # init_grid=False with a live grid
    try:
        from ..utils import liveplane as _liveplane
        from ..utils import tracing as _tracing

        # Live plane up BEFORE the long bring-up/compile phase (no-op
        # unless IGG_METRICS_PORT is set; docs/observability.md).
        _liveplane.ensure_server()
        with _tracing.trace_span("igg.run.setup", model="acoustic3d"):
            state, params = setup(nx, ny, nz, **kw)
            step = make_step(params)
        guard = RunGuard(
            guard_every=guard_every,
            policy=guard_policy,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
            checkpoint_keep=checkpoint_keep,
            integrity_every=integrity_every,
            names=("P", "Vx", "Vy", "Vz"),
        )
        sync_every_step = global_grid().mesh.devices.flat[0].platform == "cpu"
        # Telemetry bytes model: all four leapfrog fields (P, Vx, Vy, Vz)
        # evolve, so each must stream once in and once out per step.
        from ..utils.telemetry import teff_bytes

        state = guarded_time_loop(
            step, state, nt, guard=guard, sync_every_step=sync_every_step,
            model="acoustic3d", bytes_per_step=teff_bytes(state),
        )
        P = jax.block_until_ready(state[0])
    except BaseException:
        # A failed run must not poison the next init_global_grid in this
        # process (the singleton would report "already initialized") — but
        # never tear down a grid the caller set up themselves.
        if not caller_owns_grid and grid_is_initialized():
            finalize_global_grid()
        raise
    if finalize:
        finalize_global_grid()
    return P


def pressure(state):
    return state[0]
