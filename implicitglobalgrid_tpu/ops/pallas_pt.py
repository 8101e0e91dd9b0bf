"""Pallas TPU kernel: temporally-blocked fused pseudo-transient iterations.

The porous-convection sibling of `ops/pallas_leapfrog.py`: advance ``w``
Darcy flux / fluid pressure relaxation iterations of the PT inner solver
(`models/porous_convection3d.py` — the HydroMech weak-scaling flagship,
BASELINE config 4) in ONE HBM round trip per field.  Structurally the PT
iteration IS a staggered leapfrog — flux update at interior faces (a
relaxation toward ``-grad(Pf)`` plus buoyancy on z-faces), pressure update
at ALL cells from the fresh fluxes — so the even-extent padded face layout
(`pad_faces`), the tile/window geometry, the frozen-top-face fix-up DMAs,
the trapezoid validity argument, and the envelope checks are all inherited
from the leapfrog kernel (see its module docstring; the kernel body is
deliberately mirrored rather than abstracted over — the compute formulas and
buffer sets differ, and the DMA scaffolding is the delicate, hardware-
validated part that benefits from staying literal).

Differences from the leapfrog kernel:

* One extra **read-only** cell-shaped input ``T`` (temperature, frozen
  across the whole PT loop): double-buffered input DMAs like the diffusion
  kernel's ``Cp``, no scratch, no output.  Its window values are exact
  everywhere (no shrinkage), so the buoyancy term reads them at any step.
* Flux update: ``q <- q + th*(f - q)`` with ``f = -dPf*id`` (plus
  ``RaLam * av_z(T)`` on z-faces) instead of the leapfrog increment.
* Pressure update: ``Pf <- Pf - bp*div(q)`` — same all-cells form.

Semantics match `models/porous_convection3d.py`'s `_flux_update` +
`_pressure_update` pair for update regions and frozen sets, to a few f32
ULPs (the kernel multiplies by precomputed ``1/dx`` where the XLA path
divides; same stencil, different rounding).

Multi-device: ``fused_k=w`` in `porous_convection3d.make_multi_step` is the
kernel-accelerated version of its ``exchange_every=w`` deep-halo cadence —
w kernel iterations per width-``w`` all-field slab exchange.
"""

from __future__ import annotations

import functools

from . import _fused_envelope as _envelope
from .halo import Z_CZ_BAND
from .pallas_leapfrog import (  # noqa: F401  (re-export)
    pad_faces,
    padded_face_shapes,
    unpad_faces,
    z_patch_shapes,
)

_TILE_CANDIDATES = ((32, 64), (32, 32), (16, 64), (16, 32), (8, 16))

#: See `ops.pallas_leapfrog._VMEM_BUDGET_BYTES` (Mosaic's scoped stack runs
#: ~18% past the buffer-byte estimate on the staggered sets — the diffusion
#: kernel's overshoot is far larger, hence its smaller budget).
_VMEM_BUDGET_BYTES = 85 * 1024 * 1024


def _tile_bytes(n1, n2, k, bx, by, itemsize, zsets: int = 0):
    """VMEM bytes: 4 ping-pong fields x (2 slots + scratch) + 2 T slots
    plus ``zsets`` four-field double-buffered 128-lane window sets (1 = the
    z-patch input windows, 2 = + the z-export staging slots)."""
    H = _envelope.aligned_halo(k)
    SX, SY = bx + 2 * k, by + 2 * H
    per_set = (
        SX * SY * n2            # Pf
        + (SX + 8) * SY * n2    # qDx
        + SX * (SY + 8) * n2    # qDy
        + SX * SY * (n2 + 128)  # qDz
    )
    total = 3 * per_set + 2 * SX * SY * n2
    # Three z-window arrays per set since round 5 (merged Pf+qDz bands).
    total += zsets * 2 * 128 * (
        SX * SY + (SX + 8) * SY + SX * (SY + 8)
    )
    return total * itemsize


_tile_error = _envelope.make_tile_error(
    _tile_bytes, _VMEM_BUDGET_BYTES, "14 haloed staggered tiles spanning z"
)
_tile_error_zpatch = _envelope.make_tile_error(
    lambda n1, n2, k, bx, by, itemsize: _tile_bytes(n1, n2, k, bx, by, itemsize, 1),
    _VMEM_BUDGET_BYTES,
    "14 haloed staggered tiles spanning z + 6 z-patch windows",
)
_tile_error_zexport = _envelope.make_tile_error(
    lambda n1, n2, k, bx, by, itemsize: _tile_bytes(n1, n2, k, bx, by, itemsize, 2),
    _VMEM_BUDGET_BYTES,
    "14 haloed staggered tiles spanning z + 6 z windows + 6 export stagings",
)


def default_tile(shape, k: int, itemsize: int = 4, zpatch: bool = False,
                 zexport: bool | None = None):
    """First tuned tile candidate valid for cell ``shape``, or None.

    ``zexport`` defaults to ``zpatch`` (the production z-slab cadence always
    exports); pass ``zexport=False`` for a patch-only call."""
    return _envelope.default_tile(
        shape, k, itemsize,
        tile_error=_envelope.pick_tile_error(
            _tile_error, _tile_error_zpatch, _tile_error_zexport,
            zpatch, zexport,
        ),
        candidates=_TILE_CANDIDATES,
    )


def fused_support_error(shape, k: int, itemsize: int = 4,
                        bx: int | None = None, by: int | None = None,
                        zpatch: bool = False,
                        zexport: bool | None = None) -> str | None:
    """Why the fused PT kernel cannot run this cell shape, or None.

    Shared control flow in `ops/_fused_envelope.py`; only `_tile_error`'s
    14-buffer VMEM accounting is specific.  ``zpatch`` accounts for the
    in-kernel z-exchange variant (PT fields only — ``T`` is frozen through
    the PT loop, its halos are refreshed at its own once-per-step exchange,
    so it needs no patches).
    """
    return _envelope.support_error(
        shape, k, itemsize, bx, by,
        tile_error=_envelope.pick_tile_error(
            _tile_error, _tile_error_zpatch, _tile_error_zexport,
            zpatch, zexport,
        ),
        candidates=_TILE_CANDIDATES,
    )


def fused_pt_iterations(T, Pf, qxp, qyp, qzp, k: int,
                        th: float, idx: float, idy: float, idz: float,
                        ralam: float, bp: float,
                        *, bx: int | None = None, by: int | None = None,
                        z_patches=None, z_patch_width: int | None = None,
                        z_export: bool = False, z_export_width: int | None = None,
                        z_overlap: int | None = None,
                        tile_sel: str = "all", carry_in=None):
    """Advance ``k`` (even) PT relaxation iterations in one HBM pass per field.

    ``T``/``Pf`` are cell-centered ``(n0, n1, n2)``; ``qxp/qyp/qzp`` are the
    `pad_faces` layouts of the staggered Darcy fluxes.  Coefficients:
    ``th`` = flux relaxation, ``idx = 1/dx`` (likewise y, z), ``ralam =
    Ra*lam_T`` (buoyancy), ``bp`` = pressure relaxation.  Returns
    ``(Pf, qxp, qyp, qzp)`` — ``T`` is read-only.

    ``z_patches``: packed z-exchange patches for the four PT fields
    (`ops.halo.z_slab_patches`, width ``k``), applied per tile in VMEM —
    see `ops.pallas_leapfrog.fused_leapfrog_steps`.

    ``z_export``/``z_overlap``: additionally return the three packed z-slab
    exports for the NEXT group's patches (Pf and qDz share the merged
    first array's lane bands) — same layout, top-face fix-up obligation,
    and rationale as the leapfrog kernel's ``z_export``
    (`ops.pallas_leapfrog.fused_leapfrog_steps`).

    ``z_patch_width``/``z_export_width`` (default ``k``): widths of the
    patch application and the exported slabs — the ragged-``npt`` cadence
    (`models.porous_convection3d`) keeps both at the schedule's maximum
    chunk ``w`` for every chunk, so a shorter chunk (``k < w``) still heals
    the previous chunk's ``w``-deep stale rind and exports ``w``-deep
    slabs.  Requires ``k <= width`` and ``o >= z_export_width + k`` (the
    exported planes must be exact after ``k`` steps).

    ``tile_sel``/``carry_in``: tile-subset launch for the pipelined group
    schedule, exactly as on `ops.pallas_leapfrog.fused_leapfrog_steps`
    (``T`` is a plain input, not part of the carry).
    """
    n0, n1, n2 = Pf.shape
    if T.shape != Pf.shape:
        raise ValueError(f"T{T.shape} and Pf{Pf.shape} must share the cell shape")
    if (qxp.shape, qyp.shape, qzp.shape) != padded_face_shapes(Pf.shape):
        raise ValueError(
            f"flux fields must be in pad_faces layout for Pf{Pf.shape}: got "
            f"{qxp.shape}, {qyp.shape}, {qzp.shape}"
        )
    if not (T.dtype == Pf.dtype == qxp.dtype == qyp.dtype == qzp.dtype):
        raise ValueError("T, Pf and flux fields must share a dtype")
    zp = z_patches is not None
    if zp:
        if tuple(a.shape for a in z_patches) != z_patch_shapes(Pf.shape):
            raise ValueError(
                f"z_patches must have shapes {z_patch_shapes(Pf.shape)}: got "
                f"{tuple(a.shape for a in z_patches)}"
            )
        if any(a.dtype != Pf.dtype for a in z_patches):
            raise ValueError("z_patches must share the fields' dtype")
    wp = k if z_patch_width is None else int(z_patch_width)
    we = k if z_export_width is None else int(z_export_width)
    if zp and not (k <= wp <= 32):
        # 2*wp lanes per merged-band half (see Z_CZ_BAND).
        raise ValueError(f"z_patch_width must satisfy k <= wp <= 32: got {wp}, k={k}")
    if z_export:
        if not zp:
            raise ValueError("z_export requires z_patches (the z-slab cadence)")
        if z_overlap is None or not (we + k <= z_overlap <= n2 // 2):
            raise ValueError(
                f"z_export needs the grid z-overlap with we+k <= o <= n2/2: "
                f"got o={z_overlap}, k={k}, we={we}, n2={n2}"
            )
        if 4 * we > 64:
            raise ValueError(
                f"z_export packs 4*we lanes per merged-band half; "
                f"z_export_width={we} > 16 unsupported"
            )
    err = fused_support_error(
        (n0, n1, n2), k, Pf.dtype.itemsize, bx, by, zpatch=zp, zexport=z_export
    )
    if err is not None:
        raise ValueError(err)
    if bx is None:
        bx, by = default_tile(
            (n0, n1, n2), k, Pf.dtype.itemsize, zpatch=zp, zexport=z_export
        )
    carry_in = _envelope.check_tile_subset(
        tile_sel, carry_in, (n0, n1), (bx, by), nouts=7 if z_export else 4
    )
    fn = _build(n0, n1, n2, str(Pf.dtype), int(k),
                float(th), float(idx), float(idy), float(idz),
                float(ralam), float(bp), int(bx), int(by), zp,
                bool(z_export), int(z_overlap) if z_export else 0,
                wp if zp else 0, we if z_export else 0,
                str(tile_sel), carry_in is not None)
    args = (T, Pf, qxp, qyp, qzp) + (tuple(z_patches) if zp else ())
    if carry_in is not None:
        args += tuple(carry_in)
    return fn(*args)


@functools.lru_cache(maxsize=64)
def _build(n0, n1, n2, dtype, k, th, idx, idy, idz, ralam, bp, bx, by,
           zp: bool = False, zx: bool = False, o: int = 0,
           wp: int = 0, we: int = 0,
           tile_sel: str = "all", carry: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .overlap import tile_subset_count, tile_subset_map

    H = _envelope.aligned_halo(k)
    SX, SY = bx + 2 * k, by + 2 * H
    SZ = n2
    ncx, ncy = n0 // bx, n1 // by
    ntiles = ncx * ncy
    # Tile-subset launch (see ops/pallas_stencil.py); fix-up DMAs belong to
    # the ring pass, like the leapfrog kernel.
    nrun = tile_subset_count(tile_sel, ncx, ncy)
    t_of = tile_subset_map(tile_sel, ncx, ncy)
    fixup = not tile_sel.startswith("mid")
    dt_ = jnp.dtype(dtype)

    def sx_of(ix):
        return jnp.clip(ix * bx - k, 0, n0 - SX)

    def sy_of(iy):
        return pl.multiple_of(jnp.clip(iy * by - H, 0, n1 - SY), 8)

    # Frozen-region copies: identical regions to the leapfrog kernel (the
    # flux update regions match the velocity ones; Pf updates all cells).
    def ring_qx(dst, s):
        dst[0:1] = s[0:1]
        dst[SX : SX + 8] = s[SX : SX + 8]
        dst[1:SX, 0:1] = s[1:SX, 0:1]
        dst[1:SX, SY - 1 : SY] = s[1:SX, SY - 1 : SY]
        dst[1:SX, 1 : SY - 1, 0:1] = s[1:SX, 1 : SY - 1, 0:1]
        dst[1:SX, 1 : SY - 1, SZ - 1 : SZ] = s[1:SX, 1 : SY - 1, SZ - 1 : SZ]

    def ring_qy(dst, s):
        dst[:, 0:1] = s[:, 0:1]
        dst[:, SY : SY + 8] = s[:, SY : SY + 8]
        dst[0:1, 1:SY] = s[0:1, 1:SY]
        dst[SX - 1 : SX, 1:SY] = s[SX - 1 : SX, 1:SY]
        dst[1 : SX - 1, 1:SY, 0:1] = s[1 : SX - 1, 1:SY, 0:1]
        dst[1 : SX - 1, 1:SY, SZ - 1 : SZ] = s[1 : SX - 1, 1:SY, SZ - 1 : SZ]

    def ring_qz(dst, s):
        dst[:, :, 0:1] = s[:, :, 0:1]
        dst[:, :, SZ : SZ + 128] = s[:, :, SZ : SZ + 128]
        dst[0:1, :, 1:SZ] = s[0:1, :, 1:SZ]
        dst[SX - 1 : SX, :, 1:SZ] = s[SX - 1 : SX, :, 1:SZ]
        dst[1 : SX - 1, 0:1, 1:SZ] = s[1 : SX - 1, 0:1, 1:SZ]
        dst[1 : SX - 1, SY - 1 : SY, 1:SZ] = s[1 : SX - 1, SY - 1 : SY, 1:SZ]

    def step_into(dp, dqx, dqy, dqz, sp, sqx, sqy, sqz, tv, ring: bool):
        """One PT iteration: (sp, sq*) buffers -> (dp, dq*) buffers.

        ``tv`` is the tile's (frozen) temperature value.  Fluxes first
        (relaxation toward -grad(Pf), buoyancy on z), then Pf at ALL cells
        from the fresh fluxes.
        """
        if ring:
            ring_qx(dqx, sqx)
            ring_qy(dqy, sqy)
            ring_qz(dqz, sqz)
        P = sp[:]
        fx = -idx * (P[1:SX, 1 : SY - 1, 1 : SZ - 1] - P[0 : SX - 1, 1 : SY - 1, 1 : SZ - 1])
        q = sqx[1:SX, 1 : SY - 1, 1 : SZ - 1]
        dqx[1:SX, 1 : SY - 1, 1 : SZ - 1] = q + th * (fx - q)
        fy = -idy * (P[1 : SX - 1, 1:SY, 1 : SZ - 1] - P[1 : SX - 1, 0 : SY - 1, 1 : SZ - 1])
        q = sqy[1 : SX - 1, 1:SY, 1 : SZ - 1]
        dqy[1 : SX - 1, 1:SY, 1 : SZ - 1] = q + th * (fy - q)
        # z-faces carry buoyancy: Ra*lam_T * (T averaged onto the face).
        tz = 0.5 * (tv[1 : SX - 1, 1 : SY - 1, 1:SZ] + tv[1 : SX - 1, 1 : SY - 1, 0 : SZ - 1])
        fz = (
            -idz * (P[1 : SX - 1, 1 : SY - 1, 1:SZ] - P[1 : SX - 1, 1 : SY - 1, 0 : SZ - 1])
            + ralam * tz
        )
        q = sqz[1 : SX - 1, 1 : SY - 1, 1:SZ]
        dqz[1 : SX - 1, 1 : SY - 1, 1:SZ] = q + th * (fz - q)
        nqx = dqx[0 : SX + 1]
        nqy = dqy[:, 0 : SY + 1]
        nqz = dqz[:, :, 0 : SZ + 1]
        div = (
            (nqx[1:] - nqx[:-1]) * idx
            + (nqy[:, 1:] - nqy[:, :-1]) * idy
            + (nqz[:, :, 1:] - nqz[:, :, :-1]) * idz
        )
        dp[:] = P - bp * div

    def kernel(*refs):
        ZXcz = ZXx = ZXy = None
        Tin, Pfin, Qxin, Qyin, Qzin = refs[:5]
        ZPcz, ZPx, ZPy = refs[5:8] if zp else (None, None, None)
        nin = 8 if zp else 5
        # A carry launch receives the ring pass's outputs as aliased inputs
        # between the real inputs and the outputs; never read here.
        outs = refs[nin + ((7 if zx else 4) if carry else 0):]
        if zx:
            Pfout, Qxout, Qyout, Qzout, ZXcz, ZXx, ZXy = outs
        else:
            Pfout, Qxout, Qyout, Qzout = outs

        def body(t, p, qx, qy, qz, sp, sqx, sqy, sqz,
                 t_is, p_is, qx_is, qy_is, qz_is,
                 p_os, qx_os, qy_os, qz_os, fix_s,
                 zpcz=None, zpx=None, zpy=None, zp_is=None,
                 zxcz=None, zxx=None, zxy=None, zx_os=None):
            def ixy(tt):
                return tt // ncy, tt % ncy

            def in_dmas(tt, slot):
                ix, iy = ixy(tt)
                sx, sy = sx_of(ix), sy_of(iy)
                return (
                    pltpu.make_async_copy(
                        Tin.at[pl.ds(sx, SX), pl.ds(sy, SY)], t.at[slot], t_is.at[slot]
                    ),
                    pltpu.make_async_copy(
                        Pfin.at[pl.ds(sx, SX), pl.ds(sy, SY)], p.at[slot], p_is.at[slot]
                    ),
                    pltpu.make_async_copy(
                        Qxin.at[pl.ds(sx, SX + 8), pl.ds(sy, SY)],
                        qx.at[slot], qx_is.at[slot],
                    ),
                    pltpu.make_async_copy(
                        Qyin.at[pl.ds(sx, SX), pl.ds(sy, SY + 8)],
                        qy.at[slot], qy_is.at[slot],
                    ),
                    pltpu.make_async_copy(
                        Qzin.at[pl.ds(sx, SX), pl.ds(sy, SY)],
                        qz.at[slot], qz_is.at[slot],
                    ),
                ) + ((
                    # Pf and qDz ride ONE merged window (lane bands).
                    pltpu.make_async_copy(
                        ZPcz.at[pl.ds(sx, SX), pl.ds(sy, SY)],
                        zpcz.at[slot], zp_is.at[0, slot],
                    ),
                    pltpu.make_async_copy(
                        ZPx.at[pl.ds(sx, SX + 8), pl.ds(sy, SY)],
                        zpx.at[slot], zp_is.at[1, slot],
                    ),
                    pltpu.make_async_copy(
                        ZPy.at[pl.ds(sx, SX), pl.ds(sy, SY + 8)],
                        zpy.at[slot], zp_is.at[2, slot],
                    ),
                ) if zp else ())

            def out_dmas(tt, slot):
                ix, iy = ixy(tt)
                ox = ix * bx - sx_of(ix)
                oy = pl.multiple_of(iy * by - sy_of(iy), 8)
                gx, gy = ix * bx, iy * by
                return (
                    pltpu.make_async_copy(
                        p.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Pfout.at[pl.ds(gx, bx), pl.ds(gy, by)], p_os.at[slot],
                    ),
                    pltpu.make_async_copy(
                        qx.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Qxout.at[pl.ds(gx, bx), pl.ds(gy, by)], qx_os.at[slot],
                    ),
                    pltpu.make_async_copy(
                        qy.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Qyout.at[pl.ds(gx, bx), pl.ds(gy, by)], qy_os.at[slot],
                    ),
                    pltpu.make_async_copy(
                        qz.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Qzout.at[pl.ds(gx, bx), pl.ds(gy, by)], qz_os.at[slot],
                    ),
                )

            def zex_dmas(tt, slot):
                ix, iy = ixy(tt)
                ox = ix * bx - sx_of(ix)
                oy = pl.multiple_of(iy * by - sy_of(iy), 8)
                gx, gy = ix * bx, iy * by
                return (
                    pltpu.make_async_copy(
                        zxcz.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        ZXcz.at[pl.ds(gx, bx), pl.ds(gy, by)], zx_os.at[0, slot],
                    ),
                    pltpu.make_async_copy(
                        zxx.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        ZXx.at[pl.ds(gx, bx), pl.ds(gy, by)], zx_os.at[1, slot],
                    ),
                    pltpu.make_async_copy(
                        zxy.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        ZXy.at[pl.ds(gx, bx), pl.ds(gy, by)], zx_os.at[2, slot],
                    ),
                )

            def start_in(tt, slot):
                for d in in_dmas(tt, slot):
                    d.start()

            def wait_in(tt, slot):
                for d in in_dmas(tt, slot):
                    d.wait()

            def start_out(tt, slot):
                for d in out_dmas(tt, slot):
                    d.start()
                if zx:
                    for d in zex_dmas(tt, slot):
                        d.start()

            def wait_out(tt, slot):
                for d in out_dmas(tt, slot):
                    d.wait()
                if zx:
                    for d in zex_dmas(tt, slot):
                        d.wait()

            # Frozen top-slab fix-up (see the leapfrog kernel): Qx row-n0 and
            # Qy col-n1 planes; Qz's top face rides the full-minor out-DMAs.
            fix_qx = pltpu.make_async_copy(
                Qxin.at[pl.ds(n0, 8)], Qxout.at[pl.ds(n0, 8)], fix_s.at[0]
            )
            fix_qy = pltpu.make_async_copy(
                Qyin.at[pl.ds(0, n0), pl.ds(n1, 8)],
                Qyout.at[pl.ds(0, n0), pl.ds(n1, 8)],
                fix_s.at[1],
            )
            if fixup:
                fix_qx.start()
                fix_qy.start()
            start_in(t_of(0), 0)

            def tile(i, _):
                tt = t_of(i)
                slot = jax.lax.rem(i, 2)
                nslot = 1 - slot

                @pl.when(i + 1 < nrun)
                def _():
                    @pl.when(i >= 1)
                    def _():
                        wait_out(t_of(i - 1), nslot)

                    start_in(t_of(i + 1), nslot)

                wait_in(tt, slot)
                if zp:
                    # Apply the z-exchange patches in VMEM (see the
                    # leapfrog kernel): lanes [0,wp) -> planes [0,wp),
                    # lanes [wp,2wp) -> the top wp planes of each field.
                    p[slot, :, :, 0:wp] = zpcz[slot, :, :, 0:wp]
                    p[slot, :, :, SZ - wp : SZ] = zpcz[slot, :, :, wp : 2 * wp]
                    qx[slot, :, :, 0:wp] = zpx[slot, :, :, 0:wp]
                    qx[slot, :, :, SZ - wp : SZ] = zpx[slot, :, :, wp : 2 * wp]
                    qy[slot, :, :, 0:wp] = zpy[slot, :, :, 0:wp]
                    qy[slot, :, :, SZ - wp : SZ] = zpy[slot, :, :, wp : 2 * wp]
                    qz[slot, :, :, 0:wp] = zpcz[slot, :, :, Z_CZ_BAND : Z_CZ_BAND + wp]
                    qz[slot, :, :, SZ + 1 - wp : SZ + 1] = zpcz[
                        slot, :, :, Z_CZ_BAND + wp : Z_CZ_BAND + 2 * wp
                    ]
                tv = t[slot]
                for j in range(k):
                    if j % 2 == 0:
                        step_into(
                            sp, sqx, sqy, sqz,
                            p.at[slot], qx.at[slot], qy.at[slot], qz.at[slot],
                            tv, ring=(j == 0),
                        )
                    else:
                        step_into(
                            p.at[slot], qx.at[slot], qy.at[slot], qz.at[slot],
                            sp, sqx, sqy, sqz,
                            tv, ring=False,
                        )
                if zx:
                    # z-slab export for the NEXT group's patches (VMEM
                    # extraction — see the leapfrog kernel).  Qz uses its
                    # logical n_f = SZ+1, o_f = o+1 (staggered z face).
                    zxcz[slot, :, :, 0:we] = p[slot, :, :, SZ - o : SZ - o + we]
                    zxcz[slot, :, :, we : 2 * we] = p[slot, :, :, o - we : o]
                    zxcz[slot, :, :, 2 * we : 3 * we] = p[slot, :, :, 0:we]
                    zxcz[slot, :, :, 3 * we : 4 * we] = p[slot, :, :, SZ - we : SZ]
                    zxx[slot, :, :, 0:we] = qx[slot, :, :, SZ - o : SZ - o + we]
                    zxx[slot, :, :, we : 2 * we] = qx[slot, :, :, o - we : o]
                    zxx[slot, :, :, 2 * we : 3 * we] = qx[slot, :, :, 0:we]
                    zxx[slot, :, :, 3 * we : 4 * we] = qx[slot, :, :, SZ - we : SZ]
                    zxy[slot, :, :, 0:we] = qy[slot, :, :, SZ - o : SZ - o + we]
                    zxy[slot, :, :, we : 2 * we] = qy[slot, :, :, o - we : o]
                    zxy[slot, :, :, 2 * we : 3 * we] = qy[slot, :, :, 0:we]
                    zxy[slot, :, :, 3 * we : 4 * we] = qy[slot, :, :, SZ - we : SZ]
                    zxcz[slot, :, :, Z_CZ_BAND : Z_CZ_BAND + we] = qz[slot, :, :, SZ - o : SZ - o + we]
                    zxcz[slot, :, :, Z_CZ_BAND + we : Z_CZ_BAND + 2 * we] = qz[
                        slot, :, :, o + 1 - we : o + 1
                    ]
                    zxcz[slot, :, :, Z_CZ_BAND + 2 * we : Z_CZ_BAND + 3 * we] = qz[
                        slot, :, :, 0:we
                    ]
                    zxcz[slot, :, :, Z_CZ_BAND + 3 * we : Z_CZ_BAND + 4 * we] = qz[
                        slot, :, :, SZ + 1 - we : SZ + 1
                    ]
                start_out(tt, slot)
                return 0

            jax.lax.fori_loop(0, nrun, tile, 0)
            wait_out(t_of(nrun - 2), (nrun - 2) % 2)
            wait_out(t_of(nrun - 1), (nrun - 1) % 2)
            if fixup:
                fix_qx.wait()
                fix_qy.wait()

        scopes = dict(
            t=pltpu.VMEM((2, SX, SY, SZ), dt_),
            p=pltpu.VMEM((2, SX, SY, SZ), dt_),
            qx=pltpu.VMEM((2, SX + 8, SY, SZ), dt_),
            qy=pltpu.VMEM((2, SX, SY + 8, SZ), dt_),
            qz=pltpu.VMEM((2, SX, SY, SZ + 128), dt_),
            sp=pltpu.VMEM((SX, SY, SZ), dt_),
            sqx=pltpu.VMEM((SX + 8, SY, SZ), dt_),
            sqy=pltpu.VMEM((SX, SY + 8, SZ), dt_),
            sqz=pltpu.VMEM((SX, SY, SZ + 128), dt_),
            t_is=pltpu.SemaphoreType.DMA((2,)),
            p_is=pltpu.SemaphoreType.DMA((2,)),
            qx_is=pltpu.SemaphoreType.DMA((2,)),
            qy_is=pltpu.SemaphoreType.DMA((2,)),
            qz_is=pltpu.SemaphoreType.DMA((2,)),
            p_os=pltpu.SemaphoreType.DMA((2,)),
            qx_os=pltpu.SemaphoreType.DMA((2,)),
            qy_os=pltpu.SemaphoreType.DMA((2,)),
            qz_os=pltpu.SemaphoreType.DMA((2,)),
            fix_s=pltpu.SemaphoreType.DMA((2,)),
        )
        if zp:
            scopes.update(
                zpcz=pltpu.VMEM((2, SX, SY, 128), dt_),
                zpx=pltpu.VMEM((2, SX + 8, SY, 128), dt_),
                zpy=pltpu.VMEM((2, SX, SY + 8, 128), dt_),
                zp_is=pltpu.SemaphoreType.DMA((3, 2)),
            )
        if zx:
            scopes.update(
                zxcz=pltpu.VMEM((2, SX, SY, 128), dt_),
                zxx=pltpu.VMEM((2, SX + 8, SY, 128), dt_),
                zxy=pltpu.VMEM((2, SX, SY + 8, 128), dt_),
                zx_os=pltpu.SemaphoreType.DMA((3, 2)),
            )
        pl.run_scoped(body, **scopes)

    vmem_bytes = _tile_bytes(n1, n2, k, bx, by, dt_.itemsize, (2 if zx else 1) if zp else 0)
    out_shape = [
        jax.ShapeDtypeStruct((n0, n1, n2), dt_),
        jax.ShapeDtypeStruct((n0 + 8, n1, n2), dt_),
        jax.ShapeDtypeStruct((n0, n1 + 8, n2), dt_),
        jax.ShapeDtypeStruct((n0, n1, n2 + 128), dt_),
    ]
    if zx:
        out_shape += [
            jax.ShapeDtypeStruct(s, dt_) for s in z_patch_shapes((n0, n1, n2))
        ]
    nbase = 8 if zp else 5
    nouts = len(out_shape)
    call = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
        * (nbase + (nouts if carry else 0)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * nouts,
        input_output_aliases=(
            {nbase + j: j for j in range(nouts)} if carry else {}
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_envelope.vmem_limit(vmem_bytes)
        ),
        name=_envelope.kernel_name("pt", k, zp, tile_sel),
    )
    return _envelope.per_member(jax.jit(call))
