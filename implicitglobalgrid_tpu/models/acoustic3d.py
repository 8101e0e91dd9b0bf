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
from ..ops.overlap import hide_communication
from ._fused import spanned_multi_step as _spanned_multi_step

#: the telemetry counter of traced kernel groups that do the self-neighbour z
#: exchange inside the leapfrog kernel's tiles (``z_wrap``)
Z_SELF_WRAP_COUNTER = "fused.z_self_wrap"


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

    donate_argnums = tuple(range(4)) if donate else ()
    if batch:
        from ._batched import batched_stencil

        return batched_stencil(block_step, 4, donate_argnums=donate_argnums)
    return stencil(block_step, donate_argnums=donate_argnums)


def pipelined_support_error(shape, k, itemsize: int = 4, bx=None, by=None,
                            gg=None) -> str | None:
    """Why the pipelined group schedule cannot split this config, or None
    (benchmark provenance; see `models._fused.pipelined_support_error`)."""
    from ..ops import pallas_leapfrog
    from ._fused import pipelined_support_error as _generic

    # stagger=1: the face fields' shape-aware ol is one deeper than the
    # grid overlap, and their send planes must fit the ring tiles too.
    return _generic(pallas_leapfrog, shape, k, itemsize, bx, by, gg, stagger=1)


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


@_spanned_multi_step("acoustic3d")
def make_multi_step(
    params: Params, nsteps: int, *, donate: bool = True, exchange_every: int = 1,
    fused_k: int | None = None, fused_tile: tuple[int, int] | None = None,
    pipelined: bool | None = None, batch: bool = False,
    coalesce: bool | None = None, autotune: bool | None = None,
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
    same cadence (`fused_support_error` is the single source of truth).
    Requires ``nsteps % fused_k == 0``.

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

    from ..tuning.search import maybe_autotune
    from ..utils import telemetry

    telemetry.counter(Z_SELF_WRAP_COUNTER).inc(0)

    fused_k, fused_tile, exchange_every, pipelined, coalesce = maybe_autotune(
        "acoustic3d", params, nsteps, autotune, batch=batch,
        fused_k=fused_k, fused_tile=fused_tile, exchange_every=exchange_every,
        pipelined=pipelined, coalesce=coalesce,
    )

    def _wrap(block_fn):
        dn = tuple(range(4)) if donate else ()
        if batch:
            from ._batched import batched_stencil

            return batched_stencil(block_fn, 4, donate_argnums=dn)
        return stencil(block_fn, donate_argnums=dn)

    v_update = _velocity_update(params)
    p_update = _pressure_update(params)

    if fused_k:
        import jax

        from ..ops.halo import _partner_self, dim_has_halo_activity, require_deep_halo
        from ..ops.pallas_leapfrog import (
            Z_SELF_WRAP,
            fused_leapfrog_steps,
            fused_support_error,
            pad_faces,
            unpad_faces,
        )
        from ..parallel.grid import global_grid
        from ._fused import warn_fused_fallback

        gg = global_grid()
        if params.hide_comm:
            raise ValueError(
                "fused_k and hide_comm are mutually exclusive: the fused "
                "kernel's slab exchange is already amortized over k steps; "
                "overlap scheduling applies to the per-step XLA path."
            )
        if nsteps % fused_k != 0:
            raise ValueError(f"nsteps={nsteps} must be a multiple of fused_k={fused_k}")
        if exchange_every not in (1, fused_k):
            raise ValueError(
                f"fused_k={fused_k} already exchanges every fused_k steps; "
                f"exchange_every={exchange_every} conflicts."
            )
        require_deep_halo(fused_k, gg, what="fused_k")
        active = [d for d in range(3) if dim_has_halo_activity(gg, d)]
        cax = params.dt / params.rho / params.dx
        cay = params.dt / params.rho / params.dy
        caz = params.dt / params.rho / params.dz
        b = params.dt * params.K
        idx, idy, idz = 1.0 / params.dx, 1.0 / params.dy, 1.0 / params.dz
        bx, by = fused_tile if fused_tile is not None else (None, None)
        if (bx is None) != (by is None):
            raise ValueError(f"fused_tile={fused_tile}: pass both bx and by, or neither")

        def kernel_steps(P, Vxp, Vyp, Vzp, z_patches=None, **zkw):
            return fused_leapfrog_steps(
                P, Vxp, Vyp, Vzp, fused_k, cax, cay, caz, b, idx, idy, idz,
                bx=bx, by=by, z_patches=z_patches, **zkw,
            )

        def xla_step(s):
            P, Vx, Vy, Vz = s
            Vx, Vy, Vz = v_update(P, Vx, Vy, Vz)
            return p_update(P, Vx, Vy, Vz), Vx, Vy, Vz

        z_active = dim_has_halo_activity(gg, 2)
        # Every block its own z partner: the kernel wraps z in its tiles.
        z_self = z_active and Z_SELF_WRAP and _partner_self(gg, 2)
        from ._fused import (
            fused_with_xla_grad,
            resolve_pipelined,
            run_group_schedule,
            split_selector,
        )

        groups = [fused_k] * (nsteps // fused_k)
        active01 = tuple(d for d in (0, 1) if d in active)

        def _split(shape, itemsize, zpatch):
            """(ring/mid selector suffix, admissibility error) for the
            resolved tile — the shared trace-time gate (`split_selector`;
            stagger=1: the face fields' ol is one deeper)."""
            from ..ops import pallas_leapfrog

            return split_selector(
                pallas_leapfrog, shape, fused_k, fused_k, itemsize, bx, by,
                active01, zpatch, stagger=1, gg=gg,
            )

        def fused_or_fallback(P, Vx, Vy, Vz, fused_body, xla_body,
                              zpatch_body=None, pipelined_bodies=None):
            # Kernel paths wrapped with `fused_with_xla_grad`: primal runs
            # the Pallas chunk, jax.grad differentiates the XLA cadence.
            shape = tuple(P.shape)
            pb = pipelined_bodies or {}
            if (
                zpatch_body is not None
                and z_active
                and not z_self
                and fused_support_error(
                    shape, fused_k, P.dtype.itemsize, bx, by, zpatch=True
                ) is None
            ):
                # The in-kernel z-slab application: avoids the whole-array
                # relayouts a z-dim DUS costs at the kernel boundary (the
                # exchanged-dimension anisotropy, docs/performance.md).
                body = zpatch_body
                if "zpatch" in pb and resolve_pipelined(
                    pipelined, _split(shape, P.dtype.itemsize, True)[1],
                    shape, fused_k, "acoustic",
                ):
                    body = pb["zpatch"]
                return fused_with_xla_grad(body, xla_body)(P, Vx, Vy, Vz)
            err = fused_support_error(shape, fused_k, P.dtype.itemsize, bx, by)
            if err is None:
                body = fused_body
                # z inactive, or wrapped inside the kernel: x/y only outside
                if "plain" in pb and (z_self or not z_active) and resolve_pipelined(
                    pipelined, _split(shape, P.dtype.itemsize, False)[1],
                    shape, fused_k, "acoustic",
                ):
                    body = pb["plain"]
                return fused_with_xla_grad(body, xla_body)(P, Vx, Vy, Vz)
            warn_fused_fallback(shape, fused_k, err, model="acoustic")
            if pipelined and "xla" in pb:
                return pb["xla"](P, Vx, Vy, Vz)
            return xla_body(P, Vx, Vy, Vz)

        if not active:
            if pipelined:
                from ._fused import warn_pipelined_fallback

                warn_pipelined_fallback(
                    None, fused_k,
                    "no halo activity: nothing to overlap", model="acoustic",
                )

            def fused_chunk(P, Vx, Vy, Vz):
                # Pad once per chunk; the kernel keeps the padded layout
                # across all groups (no exchange to serve).
                P, Vxp, Vyp, Vzp = run_group_schedule(
                    groups, lambda ki, s: kernel_steps(*s),
                    (P, *pad_faces(Vx, Vy, Vz)),
                )
                return (P, *unpad_faces(Vxp, Vyp, Vzp))

            def xla_chunk(P, Vx, Vy, Vz):
                return lax.fori_loop(
                    0, nsteps, lambda i, s: xla_step(s), (P, Vx, Vy, Vz)
                )

            # No halo activity = no collectives: plain jit on the grid's
            # single device (same rationale as the diffusion fused path).
            body = lambda *s: fused_or_fallback(*s, fused_chunk, xla_chunk)
            if batch:
                body = jax.vmap(body)
            return jax.jit(
                body, donate_argnums=tuple(range(4)) if donate else ()
            )

        def z_wrap_kwargs(shape):
            """The kernel arguments of the in-tile z wrap, on a z_self grid
            (or none)."""
            if not z_self:
                return {}
            from ..ops.halo import ol

            return dict(z_wrap=True, z_overlap=ol(2, shape=shape, gg=gg))

        def fused_block_step(P, Vx, Vy, Vz):
            from ..ops.halo import update_halo_padded_faces

            zkw = z_wrap_kwargs(tuple(P.shape))

            def group(ki, s):
                if z_self:
                    telemetry.counter(Z_SELF_WRAP_COUNTER).inc()
                s = kernel_steps(*s, **zkw)
                # One all-field slab exchange licenses the next fused_k
                # steps (see the exchange_every docstring for why P's slab
                # must ride along) — directly on the padded layout, so the
                # chunk pays ONE pad/unpad instead of one per group.  On a
                # z_self grid the kernel's output already has fresh z halos.
                if z_self and not active01:
                    return s
                return update_halo_padded_faces(
                    *s, width=fused_k, dims=(0, 1) if z_self else None,
                    coalesce=coalesce,
                )

            P, Vxp, Vyp, Vzp = run_group_schedule(
                groups, group, (P, *pad_faces(Vx, Vy, Vz))
            )
            return (P, *unpad_faces(Vxp, Vyp, Vzp))

        def fused_zpatch_step(P, Vx, Vy, Vz):
            from ..ops.halo import (
                apply_z_patches,
                fix_topface_z_exports,
                identity_z_patches,
                ol,
                update_halo_padded_faces,
                z_patches_from_exports,
            )

            s0 = (P, *pad_faces(Vx, Vy, Vz))
            o_z = ol(2, shape=tuple(P.shape), gg=gg)
            # Chunk entry has fresh halos, so the first group's z patches
            # re-write the planes already in place.
            patches0 = identity_z_patches(*s0, width=fused_k)

            def group(ki, carry):
                s, patches = carry
                # The kernel applies the z patches tile-by-tile in VMEM AND
                # exports the next group's send slabs (round 4: extraction
                # outside paid whole-array relayouts per group); x/y slabs
                # exchange outside for the fields and the packed exports
                # alike (sequential-dimension corner semantics), then the z
                # communication runs on the packed arrays alone.
                out = kernel_steps(
                    *s, z_patches=patches, z_export=True, z_overlap=o_z
                )
                s, exports = out[:4], out[4:]
                exports = fix_topface_z_exports(exports, *s, width=fused_k)
                s = update_halo_padded_faces(
                    *s, width=fused_k, dims=(0, 1), coalesce=coalesce
                )
                patches = z_patches_from_exports(
                    exports, tuple(s[0].shape), width=fused_k
                )
                return s, patches

            s, patches = run_group_schedule(groups, group, (s0, patches0))
            # One whole-array application restores the chunk-boundary
            # fresh-halo invariant (amortized over the whole chunk).
            P, Vxp, Vyp, Vzp = apply_z_patches(*s, patches, width=fused_k)
            return (P, *unpad_faces(Vxp, Vyp, Vzp))

        def fused_pipelined_block_step(P, Vx, Vy, Vz):
            # Boundary-first split of `fused_block_step` (z inactive, or
            # z_self): ring pass feeds the all-field x/y slab exchange
            # early, interior pass runs across the in-flight collectives.
            from ..ops.halo import (
                _padded_logicals,
                begin_slab_exchange,
                finish_slab_exchange,
            )
            from ._fused import run_pipelined_group_schedule

            sel, _, _ = _split(tuple(P.shape), P.dtype.itemsize, False)
            s0 = (P, *pad_faces(Vx, Vy, Vz))
            logicals = _padded_logicals(*s0)

            zkw = z_wrap_kwargs(tuple(P.shape))

            def boundary(ki, s):
                if z_self:
                    telemetry.counter(Z_SELF_WRAP_COUNTER).inc()
                out_b = kernel_steps(*s, tile_sel="ring" + sel, **zkw)
                pend = begin_slab_exchange(
                    out_b, (0, 1), width=fused_k, logicals=logicals,
                    coalesce=coalesce,
                )
                return out_b, pend

            def interior(ki, s, out_b, pend):
                out = kernel_steps(*s, tile_sel="mid" + sel, carry_in=out_b, **zkw)
                return finish_slab_exchange(out, pend, logicals=logicals)

            P, Vxp, Vyp, Vzp = run_pipelined_group_schedule(
                groups, boundary, interior, s0
            )
            return (P, *unpad_faces(Vxp, Vyp, Vzp))

        def fused_zpatch_pipelined_step(P, Vx, Vy, Vz):
            # Boundary-first split of `fused_zpatch_step`: the four fields'
            # x/y slabs exchange early off the ring pass; the packed z
            # exports (which every tile feeds) complete with the interior
            # pass, and their thin communication stays on the group's
            # serialized tail.
            from ..ops.halo import (
                _padded_logicals,
                apply_z_patches,
                begin_slab_exchange,
                finish_slab_exchange,
                fix_topface_z_exports,
                identity_z_patches,
                ol,
                z_patches_from_exports,
            )
            from ._fused import run_pipelined_group_schedule

            s0 = (P, *pad_faces(Vx, Vy, Vz))
            o_z = ol(2, shape=tuple(P.shape), gg=gg)
            patches0 = identity_z_patches(*s0, width=fused_k)
            sel, _, _ = _split(tuple(P.shape), P.dtype.itemsize, True)
            logicals = _padded_logicals(*s0)

            def boundary(ki, carry):
                s, patches = carry
                out_b = kernel_steps(
                    *s, z_patches=patches, z_export=True, z_overlap=o_z,
                    tile_sel="ring" + sel,
                )
                pend = begin_slab_exchange(
                    out_b[:4], (0, 1), width=fused_k, logicals=logicals,
                    coalesce=coalesce,
                )
                return out_b, pend

            def interior(ki, carry, out_b, pend):
                s, patches = carry
                out = kernel_steps(
                    *s, z_patches=patches, z_export=True, z_overlap=o_z,
                    tile_sel="mid" + sel, carry_in=out_b,
                )
                s2, exports = out[:4], out[4:]
                # Top-face fix-up reads the PRE-exchange outputs, exactly
                # like the serialized cadence's ordering.
                exports = fix_topface_z_exports(exports, *s2, width=fused_k)
                s2 = finish_slab_exchange(s2, pend, logicals=logicals)
                patches2 = z_patches_from_exports(
                    exports, tuple(s2[0].shape), width=fused_k
                )
                return s2, patches2

            s, patches = run_pipelined_group_schedule(
                groups, boundary, interior, (s0, patches0)
            )
            P, Vxp, Vyp, Vzp = apply_z_patches(*s, patches, width=fused_k)
            return (P, *unpad_faces(Vxp, Vyp, Vzp))

        def xla_cadence_step(P, Vx, Vy, Vz):
            def group(i, s):
                s = lax.fori_loop(0, fused_k, lambda j, s: xla_step(s), s)
                return update_halo(*s, width=fused_k, coalesce=coalesce)

            return lax.fori_loop(0, nsteps // fused_k, group, (P, Vx, Vy, Vz))

        def xla_pipelined_cadence_step(P, Vx, Vy, Vz):
            # The XLA fallback with the early-dispatch exchange shape
            # (begin/finish; bit-identical values) — only pipelined=True
            # selects it (no tile split to ride).
            from ..ops.halo import begin_slab_exchange, finish_slab_exchange

            def group(i, s):
                s = lax.fori_loop(0, fused_k, lambda j, s: xla_step(s), s)
                pend = begin_slab_exchange(
                    s, (0, 1, 2), width=fused_k, coalesce=coalesce
                )
                return finish_slab_exchange(s, pend)

            return lax.fori_loop(0, nsteps // fused_k, group, (P, Vx, Vy, Vz))

        return _wrap(
            lambda *s: fused_or_fallback(
                *s, fused_block_step, xla_cadence_step, fused_zpatch_step,
                pipelined_bodies={
                    "plain": fused_pipelined_block_step,
                    "zpatch": fused_zpatch_pipelined_step,
                    "xla": xla_pipelined_cadence_step,
                },
            )
        )

    if exchange_every < 1:
        raise ValueError(f"exchange_every must be >= 1 (got {exchange_every})")
    if exchange_every > 1:
        from ..ops.halo import require_deep_halo

        if params.hide_comm:
            raise ValueError(
                "exchange_every and hide_comm are mutually exclusive: overlap "
                "scheduling hides the per-step exchange; a slab cadence "
                "replaces it."
            )
        if nsteps % exchange_every != 0:
            raise ValueError(
                f"nsteps={nsteps} must be a multiple of exchange_every={exchange_every}"
            )
        require_deep_halo(exchange_every)
        w = exchange_every

        def block_step(P, Vx, Vy, Vz):
            def group(i, s):
                def body(j, s):
                    P, Vx, Vy, Vz = s
                    Vx, Vy, Vz = v_update(P, Vx, Vy, Vz)
                    P = p_update(P, Vx, Vy, Vz)
                    return (P, Vx, Vy, Vz)

                s = lax.fori_loop(0, w, body, s)
                if pipelined:
                    from ..ops.halo import (
                        begin_slab_exchange,
                        finish_slab_exchange,
                    )

                    pend = begin_slab_exchange(
                        s, (0, 1, 2), width=w, coalesce=coalesce
                    )
                    return finish_slab_exchange(s, pend)
                return update_halo(*s, width=w, coalesce=coalesce)

            return lax.fori_loop(0, nsteps // w, group, (P, Vx, Vy, Vz))

        return _wrap(block_step)

    if pipelined:
        raise ValueError(
            "pipelined applies to the group cadences (fused_k or "
            "exchange_every > 1); the per-step path has no group schedule."
        )

    if params.hide_comm:
        v_exchange = hide_communication(v_update, radius=1)
    else:

        def v_exchange(P, Vx, Vy, Vz):
            return update_halo(*v_update(P, Vx, Vy, Vz), coalesce=coalesce)

    def block_step(P, Vx, Vy, Vz):
        def body(i, s):
            P, Vx, Vy, Vz = s
            Vx, Vy, Vz = v_exchange(P, Vx, Vy, Vz)
            P = p_update(P, Vx, Vy, Vz)
            return (P, Vx, Vy, Vz)

        return lax.fori_loop(0, nsteps, body, (P, Vx, Vy, Vz))

    return _wrap(block_step)


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
