"""Pallas TPU kernel: temporally-blocked fused staggered leapfrog steps.

The staggered sibling of `ops/pallas_stencil.py` (same custom-kernel lever as
the reference's pack kernels, `/root/reference/src/update_halo.jl:599-649`):
advance ``k`` velocity–pressure leapfrog steps of the acoustic model
(`models/acoustic3d.py`) in ONE HBM round trip per field.  The XLA acoustic
path is at its streaming roofline (12 real passes/step, see
`docs/performance.md`); temporal blocking cuts that to ~``(8.6·R + 4)/k``
passes/step (R = halo-recompute redundancy), the only remaining lever.

**Why this works where the naive staggered tile faulted.**  A face field of
shape ``n+1`` sliced directly gives DMA extents of odd size in the
second-minor or minor dimension — probed on hardware to crash the TPU worker
(odd-extent second-minor DMA).  The fix is an *even-extent padded layout*:
each velocity field is carried in an array padded to ``n+8`` along its own
staggered axis (``pad_faces``), holding all ``n+1`` real faces plus 7 junk
planes.  Every window fetch then has 8-aligned offsets and
multiple-of-8-extents in the second-minor dimension (x-axis padding is
unconstrained — it is the major dimension), and every minor-dimension copy
moves the full minor extent.  No odd-extent slice exists anywhere in the
kernel:

* ``P``  (cells)   window ``(SX,   SY,   n2)``    at ``(sx, sy)``
* ``Vx`` (x-faces) window ``(SX+8, SY,   n2)``    at ``(sx, sy)`` — local
  face ``j`` is global face ``sx+j``; the +8 rows cover the window's top
  face ``SX`` (real data or the global frozen face) plus junk.
* ``Vy`` (y-faces) window ``(SX,   SY+8, n2)``
* ``Vz`` (z-faces) window ``(SX,   SY,   n2+128)`` — full minor extent
  (z is the minor dimension, where Mosaic requires lane-tile-aligned
  extents, so the z pad is 128, not 8).

Output DMAs write only each tile's *owned* ``(bx, by)`` block of cells and
faces ``[i·b, i·b + b)`` — an exact partition of faces ``0..n-1``; the top
global face ``n`` is frozen (rigid wall / exchange-refreshed rind) and never
updated, so it needs no odd-extent store either: ``Vz``'s top face rides
every tile's full-minor out-DMA, and the ``Vx``/``Vy`` top slabs (the real
frozen face plane + 7 junk planes) are carried input→output by two small
aligned fix-up DMAs (major-dim slab for ``Vx``; 8-aligned second-minor slab
for ``Vy``).  The outputs are separate buffers (NOT aliased to the inputs:
a later tile's halo fetch overlaps earlier tiles' owned blocks, so in-place
writes would feed k-step-advanced values into neighbors' windows).

**Semantics** (matches `models/acoustic3d.py` update for update region and
frozen set, bit-near-exactly — same constant folding, different FMA
contraction):

* V update at global-interior faces with global-interior transverse index
  (the XLA model's ``jnp.pad(dV, 1)`` form); all other faces frozen.
* P update at ALL cells — including global boundary cells, whose divergence
  reads the frozen boundary faces (true values, present in the window).
  Tiles clamped to a global edge therefore compute the physical boundary
  exactly; for interior tiles validity shrinks one ring per step and owned
  cells sit ``>= k`` from the window edge (same trapezoid argument as the
  diffusion kernel).

Structure (flat tile `fori_loop`, double-buffered input DMAs, k-step
VMEM ping-pong, out-DMA fencing) is inherited from `ops/pallas_stencil.py`
— see its docstring for the scheduling rationale.

Multi-device: between halo exchanges only ``k=1`` is valid on standard
``overlap=2`` grids; ``fused_k=k`` in `models.acoustic3d.make_multi_step`
pairs k kernel steps with one width-``k`` slab exchange of all four fields
on a deep-halo (``overlap >= 2k``) grid.
"""

from __future__ import annotations

import functools

from . import _fused_envelope as _envelope
from .halo import Z_CZ_BAND

#: Tile candidates for auto-selection, fastest first (shared heuristics with
#: the diffusion kernel; the 4-field working set is ~2.4x larger, so the
#: VMEM check prunes earlier — the intermediate rungs matter here most:
#: 512^3 rejects (32,64) and round 3 degraded straight to (16,32) at 959
#: GB/s; the (32,32) rung measures 1409 there (vs (16,64) 1296), hence its
#: rank (VERDICT r3 #6).
_TILE_CANDIDATES = ((32, 64), (32, 32), (16, 64), (16, 32), (8, 16))

#: See `ops.pallas_stencil._VMEM_BUDGET_BYTES` (v5e-tuned estimate bound).
#: Each kernel's budget encodes ITS probed Mosaic scoped-stack overshoot
#: over the `_tile_bytes` estimate: ~18% for this 4-field set (probed:
#: (32,128) k=6 estimated 92 MiB, Mosaic wanted 109 MiB) vs ~85% for the
#: diffusion kernel's 5-buffer ping-pong — hence 85 MiB here against
#: diffusion's 59.5.  The envelope rejects configs before they reach a
#: Mosaic stack OOM.
_VMEM_BUDGET_BYTES = 85 * 1024 * 1024


def _tile_bytes(n1, n2, k, bx, by, itemsize, zsets: int = 0):
    """VMEM bytes for one full ping-pong set (4 fields x (2 slots + scratch)).

    ``n1`` is unused (this kernel has no full-y mode — envelope signature).
    ``zsets``: how many four-field double-buffered 128-lane window sets to
    add (1 = the z-patch input windows, 2 = + the z-export staging slots)."""
    H = _envelope.aligned_halo(k)
    SX, SY = bx + 2 * k, by + 2 * H
    per_set = (
        SX * SY * n2          # P
        + (SX + 8) * SY * n2  # Vx
        + SX * (SY + 8) * n2  # Vy
        + SX * SY * (n2 + 128)  # Vz (minor pad is a full lane tile)
    )
    total = 3 * per_set
    # Three z-window arrays per set since round 5: the cell and z-face
    # fields share one merged array (lane bands — see `Z_CZ_BAND`).
    total += zsets * 2 * 128 * (
        SX * SY + (SX + 8) * SY + SX * (SY + 8)
    )
    return total * itemsize


_tile_error = _envelope.make_tile_error(
    _tile_bytes, _VMEM_BUDGET_BYTES, "12 haloed staggered tiles spanning z"
)
_tile_error_zpatch = _envelope.make_tile_error(
    lambda n1, n2, k, bx, by, itemsize: _tile_bytes(n1, n2, k, bx, by, itemsize, 1),
    _VMEM_BUDGET_BYTES,
    "12 haloed staggered tiles spanning z + 6 z-patch windows",
)
_tile_error_zexport = _envelope.make_tile_error(
    lambda n1, n2, k, bx, by, itemsize: _tile_bytes(n1, n2, k, bx, by, itemsize, 2),
    _VMEM_BUDGET_BYTES,
    "12 haloed staggered tiles spanning z + 6 z windows + 6 export stagings",
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
    """Why the fused leapfrog kernel cannot run this cell shape, or None.

    Single source of truth for the kernel envelope — used eagerly by
    `fused_leapfrog_steps` (raise) and by `models.acoustic3d.make_multi_step`
    (warn once + fall back to the XLA cadence, the reference's
    runtime-path-selection precedent, `/root/reference/src/update_halo.jl:755-784`).
    Kernel-independent checks live in `ops/_fused_envelope.py`, shared with
    the diffusion kernel; only `_tile_error`'s 12-buffer VMEM accounting is
    specific.  ``zpatch`` accounts for the in-kernel z-exchange variant's
    extra patch windows.
    """
    return _envelope.support_error(
        shape, k, itemsize, bx, by,
        tile_error=_envelope.pick_tile_error(
            _tile_error, _tile_error_zpatch, _tile_error_zexport,
            zpatch, zexport,
        ),
        candidates=_TILE_CANDIDATES,
    )


#: Padded-axis extents of the `pad_faces` layout, relative to the CELL size:
#: a padded face array spans ``cell + PADS[axis]`` along its own staggered
#: axis (``n+1`` real faces + junk planes).  x/y need sublane alignment (8);
#: z is the minor axis, where Mosaic requires lane-tile alignment (128).
#: The single source of truth for every pad_faces shape check
#: (`fused_leapfrog_steps`, `ops.pallas_pt.fused_pt_iterations`,
#: `ops.halo.update_halo_padded_faces`).
PADS = (8, 8, 128)

#: x-rows per step of the in-tile z wrap's loop.  Each lane-band copy
#: unrolls into vector code per row, so the (32, 64) tile's copies written out
#: whole made the periodic-z program's cold compile 46 s longer on a v5e host
#: (121 against the walls program's 75 s); a loop over 16-row blocks compiles
#: in 75 s and runs as fast, 8-row blocks 1.8% slower per kernel step.
_WRAP_ROWS = 16


def padded_face_shapes(cell_shape):
    """The three `pad_faces` array shapes for a given cell shape."""
    n0, n1, n2 = cell_shape
    return (
        (n0 + PADS[0], n1, n2),
        (n0, n1 + PADS[1], n2),
        (n0, n1, n2 + PADS[2]),
    )


def pad_faces(Vx, Vy, Vz):
    """Face fields ``(n+1 staggered)`` -> even-extent padded kernel layout.

    Pads each field's own staggered axis with zeros: ``n+1 -> n+8`` for the
    x/y (major/second-minor) axes, ``n+1 -> n+128`` for z (the minor axis,
    where Mosaic requires lane-tile-aligned extents).  The extra planes are
    junk by contract — never read by the kernel's compute, never written
    back into the real faces.  One HBM pass per field; amortized over a
    whole fused chunk by the model wrapper.  Runs under
    ``jax.named_scope("igg_faces_pad")``, so a device trace names the
    layout's cost.
    """
    import jax.numpy as jnp
    from jax import named_scope

    with named_scope("igg_faces_pad"):
        return (
            jnp.pad(Vx, ((0, PADS[0] - 1), (0, 0), (0, 0))),
            jnp.pad(Vy, ((0, 0), (0, PADS[1] - 1), (0, 0))),
            jnp.pad(Vz, ((0, 0), (0, 0), (0, PADS[2] - 1))),
        )


def unpad_faces(Vxp, Vyp, Vzp):
    """Inverse of `pad_faces`: slice the ``n+1`` real faces back out
    (under ``jax.named_scope("igg_faces_unpad")``)."""
    from jax import named_scope

    with named_scope("igg_faces_unpad"):
        return (
            Vxp[: 1 - PADS[0]],
            Vyp[:, : 1 - PADS[1]],
            Vzp[:, :, : 1 - PADS[2]],
        )


def z_patch_shapes(cell_shape):
    """The three packed z-patch array shapes (`ops.halo.z_slab_patches`):
    merged cell+z-face (bands at lanes 0 / `ops.halo.Z_CZ_BAND`), x-face,
    y-face."""
    n0, n1, n2 = cell_shape
    return (
        (n0, n1, 128),
        (n0 + PADS[0], n1, 128),
        (n0, n1 + PADS[1], 128),
    )


def fused_leapfrog_steps(P, Vxp, Vyp, Vzp, k: int,
                         cax: float, cay: float, caz: float,
                         b: float, idx: float, idy: float, idz: float,
                         *, bx: int | None = None, by: int | None = None,
                         z_patches=None, z_export: bool = False,
                         z_wrap: bool = False, z_overlap: int | None = None,
                         tile_sel: str = "all", carry_in=None):
    """Advance ``k`` (even) leapfrog steps in one HBM pass per field.

    ``P`` is the cell-centered pressure ``(n0, n1, n2)``; ``Vxp/Vyp/Vzp`` are
    the `pad_faces` layouts of the three staggered velocity fields.
    Coefficients: ``cax = dt/(rho*dx)`` (likewise y, z); ``b = dt*K``;
    ``idx = 1/dx`` (likewise y, z) — the same folds as the XLA model so the
    two paths differ only by FMA contraction.

    ``z_patches``: packed z-exchange patches (`ops.halo.z_slab_patches`,
    width ``k``) applied to each tile in VMEM before stepping — the
    in-kernel z-slab application that avoids whole-array relayouts at the
    kernel boundary (see the exchanged-dimension anisotropy note in
    docs/performance.md).  Lanes ``[0, k)`` overwrite each field's z planes
    ``[0, k)``, lanes ``[k, 2k)`` its planes ``[n_z - k, n_z)``.

    ``z_export`` (requires ``z_patches`` + the grid z-overlap ``z_overlap``):
    additionally return the three packed z-slab exports (shapes
    `z_patch_shapes`; P and Vz share the merged first array's lane bands)
    for the NEXT group's patches — the extraction half of
    the z-anisotropy fix (see `ops.pallas_stencil.fused_diffusion_steps`).
    Lane layout per field f with logical z size ``n_f`` and overlap ``o_f``
    (``o_f = o+1`` for Vz, shape-aware): ``[0,k)`` = planes
    ``[n_f-o_f, n_f-o_f+k)``, ``[k,2k)`` = planes ``[o_f-k, o_f)``,
    ``[2k,4k)`` = current boundary planes.  The Vx row ``n0`` / Vy column
    ``n1`` (frozen top-face) slabs are NOT exported by the tiles (their
    owned-block partition excludes them) — the model cadence fixes them up
    from the output arrays (`ops.halo.fix_topface_z_exports`), and on
    x/y-active grids the exports' own x/y slab exchange refreshes them
    anyway.

    ``z_wrap`` (requires the grid z-overlap ``z_overlap``, and no
    ``z_patches``): the self-neighbour z exchange of a periodic z that is not
    split across devices, done inside each tile.  After the k steps every
    field's z-halo planes are overwritten from the tile's own planes --
    ``[0,k)`` <- ``[n_f-o_f, n_f-o_f+k)`` and ``[n_f-k, n_f)`` <-
    ``[o_f-k, o_f)``, the export lanes followed by the patch apply of the
    ``z_patches``/``z_export`` cadence -- so the output is the k steps plus
    the z halo update, with no patch input, no export output and the
    plain variant's VMEM set.  The frozen Vx row ``n0`` / Vy column ``n1``
    slabs get the same wrap: the x-edge / y-edge tiles write them out from
    VMEM in place of the fix-up copies.

    ``tile_sel``/``carry_in``: tile-subset launch for the pipelined group
    schedule, exactly as on `ops.pallas_stencil.fused_diffusion_steps` — a
    ``"mid*"`` launch aliases the matching ``"ring*"`` launch's outputs
    (``carry_in``, all 4 or 7 of them) so the combined result needs no
    copy.  The frozen top-face fix-up DMAs run in the ring pass only (the
    alias carries their planes through the mid pass).
    """
    n0, n1, n2 = P.shape
    if (Vxp.shape, Vyp.shape, Vzp.shape) != padded_face_shapes(P.shape):
        raise ValueError(
            f"V fields must be in pad_faces layout for P{P.shape}: got "
            f"{Vxp.shape}, {Vyp.shape}, {Vzp.shape}"
        )
    if not (P.dtype == Vxp.dtype == Vyp.dtype == Vzp.dtype):
        raise ValueError("P and V fields must share a dtype")
    zp = z_patches is not None
    if zp:
        if tuple(a.shape for a in z_patches) != z_patch_shapes(P.shape):
            raise ValueError(
                f"z_patches must have shapes {z_patch_shapes(P.shape)}: got "
                f"{tuple(a.shape for a in z_patches)}"
            )
        if any(a.dtype != P.dtype for a in z_patches):
            raise ValueError("z_patches must share the fields' dtype")
    if z_export and not zp:
        raise ValueError("z_export requires z_patches (the z-slab cadence)")
    if z_wrap and zp:
        raise ValueError("z_wrap replaces z_patches; pass one or the other")
    if z_export or z_wrap:
        if z_overlap is None or not (2 * k <= z_overlap <= n2 // 2):
            raise ValueError(
                f"z_export and z_wrap need the grid z-overlap with "
                f"2k <= o <= n2/2: got o={z_overlap}, k={k}, n2={n2}"
            )
    if z_export:
        if 4 * k > 128 - Z_CZ_BAND:
            # Each merged-band half holds 4k lanes (see `ops.halo.Z_CZ_BAND`).
            raise ValueError(
                f"z_export packs 4k lanes per merged-band half; k={k} > "
                f"{(128 - Z_CZ_BAND) // 4} unsupported"
            )
    err = fused_support_error(
        (n0, n1, n2), k, P.dtype.itemsize, bx, by, zpatch=zp, zexport=z_export
    )
    if err is not None:
        raise ValueError(err)
    if bx is None:
        bx, by = default_tile(
            (n0, n1, n2), k, P.dtype.itemsize, zpatch=zp, zexport=z_export
        )
    carry_in = _envelope.check_tile_subset(
        tile_sel, carry_in, (n0, n1), (bx, by), nouts=7 if z_export else 4
    )
    fn = _build(n0, n1, n2, str(P.dtype), int(k),
                float(cax), float(cay), float(caz),
                float(b), float(idx), float(idy), float(idz),
                int(bx), int(by), zp,
                bool(z_export), int(z_overlap) if z_export or z_wrap else 0,
                str(tile_sel), carry_in is not None, bool(z_wrap))
    args = (P, Vxp, Vyp, Vzp) + (tuple(z_patches) if zp else ())
    if carry_in is not None:
        args += tuple(carry_in)
    return fn(*args)


@functools.lru_cache(maxsize=64)
def _build(n0, n1, n2, dtype, k, cax, cay, caz, b, idx, idy, idz, bx, by,
           zp: bool = False, zx: bool = False, o: int = 0,
           tile_sel: str = "all", carry: bool = False, zw: bool = False):
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
    # Tile-subset launch (see ops/pallas_stencil.py): the loop runs over the
    # subset's index space; per-tile work is unchanged.  The frozen top-face
    # fix-up DMAs belong to the ring pass (the mid pass's aliased outputs
    # already carry those planes).  Under ``zw`` the edge tiles carry them
    # instead (`top_dmas`), wrapped with the rest of the tile.
    nrun = tile_subset_count(tile_sel, ncx, ncy)
    t_of = tile_subset_map(tile_sel, ncx, ncy)
    fixup = not tile_sel.startswith("mid") and not zw
    dt_ = jnp.dtype(dtype)

    def sx_of(ix):
        return jnp.clip(ix * bx - k, 0, n0 - SX)

    def sy_of(iy):
        # Always a multiple of 8 (by, H, n1-SY all are); assert it for Mosaic.
        return pl.multiple_of(jnp.clip(iy * by - H, 0, n1 - SY), 8)

    # Frozen-region (ring) copies: the complement of each field's update
    # region, copied once into the scratch buffer (the in-slot buffer holds
    # it from the DMA; frozen values never change across the k steps).
    def ring_vx(dst, s):
        # update region: [1:SX, 1:SY-1, 1:SZ-1]
        dst[0:1] = s[0:1]
        dst[SX : SX + 8] = s[SX : SX + 8]
        dst[1:SX, 0:1] = s[1:SX, 0:1]
        dst[1:SX, SY - 1 : SY] = s[1:SX, SY - 1 : SY]
        dst[1:SX, 1 : SY - 1, 0:1] = s[1:SX, 1 : SY - 1, 0:1]
        dst[1:SX, 1 : SY - 1, SZ - 1 : SZ] = s[1:SX, 1 : SY - 1, SZ - 1 : SZ]

    def ring_vy(dst, s):
        # update region: [1:SX-1, 1:SY, 1:SZ-1]
        dst[:, 0:1] = s[:, 0:1]
        dst[:, SY : SY + 8] = s[:, SY : SY + 8]
        dst[0:1, 1:SY] = s[0:1, 1:SY]
        dst[SX - 1 : SX, 1:SY] = s[SX - 1 : SX, 1:SY]
        dst[1 : SX - 1, 1:SY, 0:1] = s[1 : SX - 1, 1:SY, 0:1]
        dst[1 : SX - 1, 1:SY, SZ - 1 : SZ] = s[1 : SX - 1, 1:SY, SZ - 1 : SZ]

    def ring_vz(dst, s):
        # update region: [1:SX-1, 1:SY-1, 1:SZ]
        dst[:, :, 0:1] = s[:, :, 0:1]
        dst[:, :, SZ : SZ + 128] = s[:, :, SZ : SZ + 128]
        dst[0:1, :, 1:SZ] = s[0:1, :, 1:SZ]
        dst[SX - 1 : SX, :, 1:SZ] = s[SX - 1 : SX, :, 1:SZ]
        dst[1 : SX - 1, 0:1, 1:SZ] = s[1 : SX - 1, 0:1, 1:SZ]
        dst[1 : SX - 1, SY - 1 : SY, 1:SZ] = s[1 : SX - 1, SY - 1 : SY, 1:SZ]

    def step_into(dp, dvx, dvy, dvz, sp, svx, svy, svz, ring: bool):
        """One leapfrog step: (sp, sv*) buffer values -> (dp, dv*) buffers.

        V first (global-interior faces, from old P), then P at ALL window
        cells from the NEW V — the divergence reads the dst V buffers just
        written, plus their frozen rows (input values, present via DMA for
        the in-slot buffers and via the one-time ring copy for scratch).
        """
        if ring:
            ring_vx(dvx, svx)
            ring_vy(dvy, svy)
            ring_vz(dvz, svz)
        P = sp[:]
        dvx[1:SX, 1 : SY - 1, 1 : SZ - 1] = svx[1:SX, 1 : SY - 1, 1 : SZ - 1] - cax * (
            P[1:SX, 1 : SY - 1, 1 : SZ - 1] - P[0 : SX - 1, 1 : SY - 1, 1 : SZ - 1]
        )
        dvy[1 : SX - 1, 1:SY, 1 : SZ - 1] = svy[1 : SX - 1, 1:SY, 1 : SZ - 1] - cay * (
            P[1 : SX - 1, 1:SY, 1 : SZ - 1] - P[1 : SX - 1, 0 : SY - 1, 1 : SZ - 1]
        )
        dvz[1 : SX - 1, 1 : SY - 1, 1:SZ] = svz[1 : SX - 1, 1 : SY - 1, 1:SZ] - caz * (
            P[1 : SX - 1, 1 : SY - 1, 1:SZ] - P[1 : SX - 1, 1 : SY - 1, 0 : SZ - 1]
        )
        nvx = dvx[0 : SX + 1]
        nvy = dvy[:, 0 : SY + 1]
        nvz = dvz[:, :, 0 : SZ + 1]
        div = (
            (nvx[1:] - nvx[:-1]) * idx
            + (nvy[:, 1:] - nvy[:, :-1]) * idy
            + (nvz[:, :, 1:] - nvz[:, :, :-1]) * idz
        )
        dp[:] = P - b * div

    def kernel(*refs):
        ZXcz = ZXx = ZXy = None
        Pin, Vxin, Vyin, Vzin = refs[:4]
        ZPcz, ZPx, ZPy = refs[4:7] if zp else (None, None, None)
        nin = 7 if zp else 4
        # A carry launch receives the ring pass's outputs as aliased inputs
        # between the real inputs and the outputs; never read here.
        outs = refs[nin + ((7 if zx else 4) if carry else 0):]
        if zx:
            Pout, Vxout, Vyout, Vzout, ZXcz, ZXx, ZXy = outs
        else:
            Pout, Vxout, Vyout, Vzout = outs

        def body(p, vx, vy, vz, sp, svx, svy, svz,
                 p_is, vx_is, vy_is, vz_is, p_os, vx_os, vy_os, vz_os, fix_s,
                 zpcz=None, zpx=None, zpy=None, zp_is=None,
                 zxcz=None, zxx=None, zxy=None, zx_os=None, top_os=None):
            def ixy(t):
                return t // ncy, t % ncy

            def owned(t):
                """Tile t's owned block: window offsets (ox, oy) and global
                offsets (gx, gy)."""
                ix, iy = ixy(t)
                ox = ix * bx - sx_of(ix)
                oy = pl.multiple_of(iy * by - sy_of(iy), 8)
                return ox, oy, ix * bx, iy * by

            def in_dmas(t, slot):
                ix, iy = ixy(t)
                sx, sy = sx_of(ix), sy_of(iy)
                return (
                    pltpu.make_async_copy(
                        Pin.at[pl.ds(sx, SX), pl.ds(sy, SY)], p.at[slot], p_is.at[slot]
                    ),
                    pltpu.make_async_copy(
                        Vxin.at[pl.ds(sx, SX + 8), pl.ds(sy, SY)],
                        vx.at[slot], vx_is.at[slot],
                    ),
                    pltpu.make_async_copy(
                        Vyin.at[pl.ds(sx, SX), pl.ds(sy, SY + 8)],
                        vy.at[slot], vy_is.at[slot],
                    ),
                    pltpu.make_async_copy(
                        Vzin.at[pl.ds(sx, SX), pl.ds(sy, SY)],
                        vz.at[slot], vz_is.at[slot],
                    ),
                ) + ((
                    # z-patch windows (full-minor 128-lane fetch, the only
                    # lane-aligned way to move a thin z slab per tile);
                    # P and Vz ride ONE merged window (lane bands).
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

            def out_dmas(t, slot):
                ox, oy, gx, gy = owned(t)
                return (
                    pltpu.make_async_copy(
                        p.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Pout.at[pl.ds(gx, bx), pl.ds(gy, by)], p_os.at[slot],
                    ),
                    pltpu.make_async_copy(
                        vx.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Vxout.at[pl.ds(gx, bx), pl.ds(gy, by)], vx_os.at[slot],
                    ),
                    pltpu.make_async_copy(
                        vy.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Vyout.at[pl.ds(gx, bx), pl.ds(gy, by)], vy_os.at[slot],
                    ),
                    pltpu.make_async_copy(
                        vz.at[slot, pl.ds(ox, bx), pl.ds(oy, by)],
                        Vzout.at[pl.ds(gx, bx), pl.ds(gy, by)], vz_os.at[slot],
                    ),
                )

            def zex_dmas(t, slot):
                ox, oy, gx, gy = owned(t)
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

            def top_dmas(t, slot, op):
                # zw: the x-edge tiles hold the frozen Vx row-n0 slab (window
                # rows SX..SX+8) and the y-edge tiles the Vy column-n1 slab
                # (window columns SY..SY+8); each writes its owned part.
                ix, iy = ixy(t)
                ox, oy, gx, gy = owned(t)

                @pl.when(ix == ncx - 1)
                def _():
                    getattr(pltpu.make_async_copy(
                        vx.at[slot, pl.ds(SX, 8), pl.ds(oy, by)],
                        Vxout.at[pl.ds(n0, 8), pl.ds(gy, by)],
                        top_os.at[0, slot],
                    ), op)()

                @pl.when(iy == ncy - 1)
                def _():
                    getattr(pltpu.make_async_copy(
                        vy.at[slot, pl.ds(ox, bx), pl.ds(SY, 8)],
                        Vyout.at[pl.ds(gx, bx), pl.ds(n1, 8)],
                        top_os.at[1, slot],
                    ), op)()

            def start_in(t, slot):
                for d in in_dmas(t, slot):
                    d.start()

            def wait_in(t, slot):
                for d in in_dmas(t, slot):
                    d.wait()

            def start_out(t, slot):
                for d in out_dmas(t, slot):
                    d.start()
                if zx:
                    for d in zex_dmas(t, slot):
                        d.start()
                if zw:
                    top_dmas(t, slot, "start")

            def wait_out(t, slot):
                for d in out_dmas(t, slot):
                    d.wait()
                if zx:
                    for d in zex_dmas(t, slot):
                        d.wait()
                if zw:
                    top_dmas(t, slot, "wait")

            # Top-slab fix-up: the frozen Vx row-n0 / Vy col-n1 face planes
            # (plus their 7 junk planes) are outside every tile's owned
            # block — carry them input→output once (under zw the edge tiles
            # write them, `top_dmas`).  Vz's top face is covered by the
            # tiles' full-minor out-DMAs.
            fixes = (
                pltpu.make_async_copy(
                    Vxin.at[pl.ds(n0, 8)], Vxout.at[pl.ds(n0, 8)], fix_s.at[0]
                ),
                pltpu.make_async_copy(
                    Vyin.at[pl.ds(0, n0), pl.ds(n1, 8)],
                    Vyout.at[pl.ds(0, n0), pl.ds(n1, 8)],
                    fix_s.at[1],
                ),
            ) if fixup else ()
            for d in fixes:
                d.start()
            start_in(t_of(0), 0)

            def tile(i, _):
                t = t_of(i)
                slot = jax.lax.rem(i, 2)
                nslot = 1 - slot

                @pl.when(i + 1 < nrun)
                def _():
                    @pl.when(i >= 1)
                    def _():
                        # nslot still holds the previous tile's output;
                        # fence its out-DMAs before prefetching into it.
                        wait_out(t_of(i - 1), nslot)

                    start_in(t_of(i + 1), nslot)

                wait_in(t, slot)
                if zp:
                    # Apply the z-exchange patches to this tile in VMEM
                    # (minor-dim plane surgery is free here, unlike the
                    # whole-array relayout a z-DUS costs at the kernel
                    # boundary): lanes [0,k) -> planes [0,k), lanes [k,2k)
                    # -> the top k planes of each field's REAL z extent.
                    p[slot, :, :, 0:k] = zpcz[slot, :, :, 0:k]
                    p[slot, :, :, SZ - k : SZ] = zpcz[slot, :, :, k : 2 * k]
                    vx[slot, :, :, 0:k] = zpx[slot, :, :, 0:k]
                    vx[slot, :, :, SZ - k : SZ] = zpx[slot, :, :, k : 2 * k]
                    vy[slot, :, :, 0:k] = zpy[slot, :, :, 0:k]
                    vy[slot, :, :, SZ - k : SZ] = zpy[slot, :, :, k : 2 * k]
                    vz[slot, :, :, 0:k] = zpcz[slot, :, :, Z_CZ_BAND : Z_CZ_BAND + k]
                    vz[slot, :, :, SZ + 1 - k : SZ + 1] = zpcz[
                        slot, :, :, Z_CZ_BAND + k : Z_CZ_BAND + 2 * k
                    ]
                # k-step ping-pong between the in-slot set and the scratch
                # set; k even, so the final state lands back in the slot.
                for j in range(k):
                    if j % 2 == 0:
                        step_into(
                            sp, svx, svy, svz,
                            p.at[slot], vx.at[slot], vy.at[slot], vz.at[slot],
                            ring=(j == 0),
                        )
                    else:
                        step_into(
                            p.at[slot], vx.at[slot], vy.at[slot], vz.at[slot],
                            sp, svx, svy, svz,
                            ring=False,
                        )
                if zx:
                    # z-slab export for the NEXT group's patches (VMEM
                    # extraction — see the diffusion kernel).  Vz uses its
                    # logical n_f = SZ+1, o_f = o+1 (staggered z face).
                    zxcz[slot, :, :, 0:k] = p[slot, :, :, SZ - o : SZ - o + k]
                    zxcz[slot, :, :, k : 2 * k] = p[slot, :, :, o - k : o]
                    zxcz[slot, :, :, 2 * k : 3 * k] = p[slot, :, :, 0:k]
                    zxcz[slot, :, :, 3 * k : 4 * k] = p[slot, :, :, SZ - k : SZ]
                    zxx[slot, :, :, 0:k] = vx[slot, :, :, SZ - o : SZ - o + k]
                    zxx[slot, :, :, k : 2 * k] = vx[slot, :, :, o - k : o]
                    zxx[slot, :, :, 2 * k : 3 * k] = vx[slot, :, :, 0:k]
                    zxx[slot, :, :, 3 * k : 4 * k] = vx[slot, :, :, SZ - k : SZ]
                    zxy[slot, :, :, 0:k] = vy[slot, :, :, SZ - o : SZ - o + k]
                    zxy[slot, :, :, k : 2 * k] = vy[slot, :, :, o - k : o]
                    zxy[slot, :, :, 2 * k : 3 * k] = vy[slot, :, :, 0:k]
                    zxy[slot, :, :, 3 * k : 4 * k] = vy[slot, :, :, SZ - k : SZ]
                    zxcz[slot, :, :, Z_CZ_BAND : Z_CZ_BAND + k] = vz[slot, :, :, SZ - o : SZ - o + k]
                    zxcz[slot, :, :, Z_CZ_BAND + k : Z_CZ_BAND + 2 * k] = vz[
                        slot, :, :, o + 1 - k : o + 1
                    ]
                    zxcz[slot, :, :, Z_CZ_BAND + 2 * k : Z_CZ_BAND + 3 * k] = vz[
                        slot, :, :, 0:k
                    ]
                    zxcz[slot, :, :, Z_CZ_BAND + 3 * k : Z_CZ_BAND + 4 * k] = vz[
                        slot, :, :, SZ + 1 - k : SZ + 1
                    ]
                if zw:
                    # The self-neighbour z exchange on the owned block (plus
                    # the 8 top-face rows / columns the edge tiles write out):
                    # the export lanes above followed by the patch apply.
                    # Vz's logical z size is SZ+1 and its overlap o+1.  A
                    # loop over blocks of _WRAP_ROWS x-rows (see there).
                    ox, oy, _, _ = owned(t)
                    ys = pl.ds(oy, by)
                    fields = ((p, ys, SZ), (vx, ys, SZ),
                              (vy, pl.ds(oy, by + 8), SZ), (vz, ys, SZ + 1))

                    def wrap(fields, x0, nx):
                        xr = pl.ds(x0, nx)
                        for ref, yr, nf in fields:
                            of = o + nf - SZ
                            ref[slot, xr, yr, 0:k] = ref[slot, xr, yr, nf - of : nf - of + k]
                            ref[slot, xr, yr, nf - k : nf] = ref[slot, xr, yr, of - k : of]

                    def rows(i, c):
                        wrap(fields, ox + i * _WRAP_ROWS, _WRAP_ROWS)
                        return c

                    jax.lax.fori_loop(0, bx // _WRAP_ROWS, rows, 0)
                    if bx % _WRAP_ROWS:
                        wrap(fields, ox + bx - bx % _WRAP_ROWS, bx % _WRAP_ROWS)
                    wrap(fields[1:2], ox + bx, 8)  # Vx's top-face rows
                start_out(t, slot)
                return 0

            jax.lax.fori_loop(0, nrun, tile, 0)
            # Drain the two in-flight out-DMA sets (every launch runs >= 2
            # tiles by validation; distinct slots).
            wait_out(t_of(nrun - 2), (nrun - 2) % 2)
            wait_out(t_of(nrun - 1), (nrun - 1) % 2)
            for d in fixes:
                d.wait()

        scopes = dict(
            p=pltpu.VMEM((2, SX, SY, SZ), dt_),
            vx=pltpu.VMEM((2, SX + 8, SY, SZ), dt_),
            vy=pltpu.VMEM((2, SX, SY + 8, SZ), dt_),
            vz=pltpu.VMEM((2, SX, SY, SZ + 128), dt_),
            sp=pltpu.VMEM((SX, SY, SZ), dt_),
            svx=pltpu.VMEM((SX + 8, SY, SZ), dt_),
            svy=pltpu.VMEM((SX, SY + 8, SZ), dt_),
            svz=pltpu.VMEM((SX, SY, SZ + 128), dt_),
            p_is=pltpu.SemaphoreType.DMA((2,)),
            vx_is=pltpu.SemaphoreType.DMA((2,)),
            vy_is=pltpu.SemaphoreType.DMA((2,)),
            vz_is=pltpu.SemaphoreType.DMA((2,)),
            p_os=pltpu.SemaphoreType.DMA((2,)),
            vx_os=pltpu.SemaphoreType.DMA((2,)),
            vy_os=pltpu.SemaphoreType.DMA((2,)),
            vz_os=pltpu.SemaphoreType.DMA((2,)),
            fix_s=pltpu.SemaphoreType.DMA((2,)),
        )
        if zp:
            scopes.update(
                zpcz=pltpu.VMEM((2, SX, SY, 128), dt_),
                zpx=pltpu.VMEM((2, SX + 8, SY, 128), dt_),
                zpy=pltpu.VMEM((2, SX, SY + 8, 128), dt_),
                zp_is=pltpu.SemaphoreType.DMA((3, 2)),
            )
        if zw:
            scopes.update(top_os=pltpu.SemaphoreType.DMA((2, 2)))
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
    nbase = 7 if zp else 4
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
        name=_envelope.kernel_name("leapfrog", k, zp or zw, tile_sel),
    )
    return _envelope.per_member(jax.jit(call))
