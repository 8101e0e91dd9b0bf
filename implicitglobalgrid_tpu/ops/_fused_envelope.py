"""Shared envelope control flow for the temporally-blocked Pallas kernels.

`ops/pallas_stencil.py` (cell-centered diffusion) and `ops/pallas_leapfrog.py`
(staggered leapfrog) share every hardware-probed constraint except the VMEM
accounting of their working sets: k even in [2, 8] (k=8 since round 5, with
the H=16 y-halo margin — see `aligned_halo`), minor dim <= 1024
(validated ceiling) and a multiple of 128 (Mosaic requires lane-tile-aligned
minor extents on HBM memref slices — probed at n2=192, round 3), y-size a
multiple of 8 (sublane-aligned second-minor DMA windows), tuned-candidate
auto-selection.  Keeping the control flow here means a newly probed
constraint lands in ONE place — the round-3 lane-alignment find had to be
retrofitted into the diffusion envelope precisely because each kernel
carried its own copy.

Each kernel supplies its own ``tile_error(n0, n1, n2, k, bx, by, itemsize)``
(VMEM budget + divisibility for its specific buffer set) and its candidate
list; this module owns everything kernel-independent.
"""

from __future__ import annotations

import math
import os


def aligned_halo(k: int) -> int:
    """y-halo: sublane-aligned with at least one spare ring beyond ``k`` —
    ``H = 8*ceil((k+1)/8)`` (8 for k <= 6, 16 for k = 8).

    The margin is load-bearing: k=8 with H=8 (halo exactly k, no spare
    ring) corrupted tile-corner cells on this toolchain (probed round 3);
    H=16 at k=8 is hardware-validated BITWISE against 8 XLA steps
    (round 5 probe, acoustic 256^3 (32,64))."""
    return 8 * math.ceil((k + 1) / 8)


def pad8(x: int) -> int:
    """Round up to sublane alignment (the transposed-layout plane pad)."""
    return -(-x // 8) * 8


def pad128(x: int) -> int:
    """Round up to lane-tile alignment (minor-dim extents of HBM arrays)."""
    return -(-x // 128) * 128


#: Per-core VMEM the tuned defaults were probed against (v5e/v5p: 128 MiB).
_TUNED_VMEM_MB = 128


def vmem_budget(default_bytes: int) -> int:
    """The VMEM budget a kernel plans against (VERDICT r3 #6).

    ``IGG_VMEM_MB`` declares the per-core VMEM capacity (MiB; the tuned
    defaults assume v5e's 128).  Each kernel's budget scales
    proportionally, so the per-kernel headroom ratios stay intact (each
    budget encodes that kernel's probed Mosaic scoped-stack overshoot over
    the buffer-byte estimate — ~85% for the diffusion kernel, ~18% for the
    staggered ones; a flat override would erase those margins).  jax's
    public API exposes no
    per-generation VMEM size, so another generation tunes via env instead
    of editing source.  Read per envelope check, not at import, so tests
    and long-running processes can flip it.
    """
    v = os.environ.get("IGG_VMEM_MB")
    if v:
        try:
            cap = int(v)
        except ValueError:
            raise ValueError(f"IGG_VMEM_MB must be an integer (MiB), got {v!r}")
        if cap <= 0:
            raise ValueError(f"IGG_VMEM_MB must be positive, got {v!r}")
        return default_bytes * cap // _TUNED_VMEM_MB
    return default_bytes


def vmem_limit(need_bytes: int) -> int:
    """``CompilerParams.vmem_limit_bytes`` for a kernel needing ``need_bytes``:
    the need plus Mosaic's working margin, capped at the capacity-scaled
    per-core ceiling (110 MiB of the tuned 128 MiB generation)."""
    return min(vmem_budget(110 * 1024 * 1024), need_bytes + 16 * 1024 * 1024)


def pick_tile_error(base, patch, export, zpatch, zexport=None):
    """Select a kernel's ``tile_error`` for the requested z-window mode.

    ``zexport=None`` defaults to ``zpatch`` — the production z-slab cadence
    always exports, so callers that only say "zpatch" budget for the full
    variant; pass ``zexport=False`` for a patch-only kernel call.  One
    definition for all three kernels (this module's contract: shared
    envelope control flow lands in ONE place).
    """
    if zexport is None:
        zexport = zpatch
    if zpatch and zexport:
        return export
    return patch if zpatch else base


def make_tile_error(tile_bytes, budget, desc, full_y_ok=False):
    """Build a kernel's ``tile_error`` from its VMEM accounting.

    ``tile_bytes(n1, n2, k, bx, by, itemsize)`` is the kernel-specific
    working set (``n1`` matters only to the full-y window modes);
    ``budget`` its tuned default budget (env-overridable, see
    `vmem_budget`); ``desc`` names it in the rejection message.  Everything
    else (divisibility, sublane alignment, haloed-tile fit) is
    kernel-independent and lives here once.

    ``full_y_ok``: admit ``by == n1`` full-y tiles (window spans all of y
    with NO y halo — the window edge is the block edge, where the frozen
    ring reproduces the XLA cadence's own frozen boundary, so no recompute
    halo is needed).  Only for kernels whose window math implements the
    mode (round 5: the diffusion kernel); others keep rejecting oversized
    windows.
    """

    def tile_error(n0, n1, n2, k, bx, by, itemsize):
        H = 0 if (full_y_ok and by == n1) else aligned_halo(k)
        vmem_need = tile_bytes(n1, n2, k, bx, by, itemsize)
        live_budget = vmem_budget(budget)
        if vmem_need > live_budget:
            # Name the env knob accurately: "scaled by" only when an override
            # is actually active (advisor r4).
            how = (
                "scaled by IGG_VMEM_MB"
                if os.environ.get("IGG_VMEM_MB")
                else "tunable via IGG_VMEM_MB"
            )
            return (
                f"tile ({bx},{by}) with k={k} needs ~{vmem_need >> 20} MiB of "
                f"VMEM ({desc}; budget {live_budget >> 20} MiB, {how}); "
                "shrink the tile or k"
            )
        if n0 % bx != 0 or n1 % by != 0:
            return f"tile ({bx},{by}) does not divide volume ({n0},{n1})"
        if by % 8 != 0 or n1 % 8 != 0:
            return "by and the y-size must be multiples of 8 (DMA alignment)"
        if bx + 2 * k > n0 or by + 2 * H > n1:
            return f"haloed tile ({bx + 2 * k},{by + 2 * H}) exceeds volume; lower k"
        return None

    return tile_error


def check_tile_subset(tile_sel, carry_in, n01, tile, nouts: int):
    """Validate a tile-subset launch request (shared by the three kernels).

    ``tile_sel``/``carry_in`` as documented on each kernel's public entry;
    ``n01`` = (n0, n1), ``tile`` = (bx, by), ``nouts`` = the launch's output
    count (what a ``mid*`` carry must alias).  Returns ``carry_in``
    normalized to a tuple, or None for non-aliasing launches.
    """
    if tile_sel == "all":
        if carry_in is not None:
            raise ValueError("carry_in is only for 'mid*' tile-subset launches")
        return None
    from .overlap import TILE_SELS, tile_subset_count

    if tile_sel not in TILE_SELS:
        raise ValueError(f"tile_sel {tile_sel!r} must be one of {TILE_SELS}")
    ncx, ncy = n01[0] // tile[0], n01[1] // tile[1]
    n = tile_subset_count(tile_sel, ncx, ncy)
    if n < 2:
        # The kernels' double-buffered DMA drain assumes >= 2 tiles; the
        # models gate admissibility through `ops.overlap.tile_split_error`,
        # so reaching this is a caller bug, not a fall-back condition.
        raise ValueError(
            f"tile subset {tile_sel!r} has {n} tiles on the ({ncx},{ncy}) "
            "tile grid; a subset launch needs >= 2"
        )
    if tile_sel.startswith("mid"):
        if carry_in is None:
            raise ValueError(
                "a 'mid*' launch needs carry_in: the matching 'ring*' "
                "launch's output array(s) to alias the combined result into"
            )
        carry = tuple(carry_in) if isinstance(carry_in, (tuple, list)) else (carry_in,)
        if len(carry) != nouts:
            raise ValueError(
                f"carry_in must hold the ring launch's {nouts} output(s); "
                f"got {len(carry)}"
            )
        return carry
    if carry_in is not None:
        raise ValueError("carry_in is only for 'mid*' tile-subset launches")
    return None


def default_tile(shape, k, itemsize, *, tile_error, candidates):
    """First candidate ``tile_error`` accepts for ``shape``, or None."""
    n0, n1, n2 = shape
    for bx, by in candidates:
        if tile_error(n0, n1, n2, k, bx, by, itemsize) is None:
            return (bx, by)
    return None


def support_error(shape, k, itemsize, bx, by, *, tile_error, candidates):
    """The kernel-independent envelope checks + tile-selection flow.

    Returns the reason the config cannot run, or None if it can — the
    single source of truth behind each kernel's ``fused_support_error``.
    """
    n0, n1, n2 = shape
    if itemsize > 4:
        # TPU hardware has no 8-byte element type: XLA emulates f64 in
        # software but Mosaic kernels cannot — without this check an
        # x64/complex field reaches a Mosaic compile error instead of the
        # warn-once XLA fallback.
        return (
            f"itemsize {itemsize} (f64/complex) is not supported by TPU "
            "Pallas kernels; fall back to the XLA path (XLA emulates x64)"
        )
    if k < 2 or k % 2 != 0 or k > 8:
        return (
            f"k must be even in [2, 8] (got {k}); use the XLA path for k=1. "
            "(k=8 runs with the H=16 y-halo margin — `aligned_halo`; deeper "
            "blocking is unvalidated)"
        )
    if n2 > 1024:
        # Bit-level agreement with the XLA path is validated on hardware up
        # to n2=1024 (an earlier toolchain miscompiled >2-lane-tile tiled
        # DMAs; the current one is clean, with `pl.multiple_of` alignment
        # hints on the dynamic offsets).
        return (
            f"minor dimension {n2} > 1024 not validated on this toolchain; "
            "fall back to the XLA path"
        )
    if n2 % 128 != 0:
        # Mosaic requires HBM memref slices to have lane-tile-aligned minor
        # extents ("Slice shape along dimension 2 must be aligned to tiling
        # (128)") — probed on hardware at n2=192 (round 3); every validated
        # size (256/512/1024) is a multiple of 128.
        return (
            f"minor dimension {n2} is not a multiple of 128 (lane-tile "
            "alignment for HBM slices); fall back to the XLA path"
        )
    if bx is None and by is None:
        picked = default_tile(
            (n0, n1, n2), k, itemsize, tile_error=tile_error, candidates=candidates
        )
        if picked is None:
            if n1 % 8 != 0:
                return (
                    f"y-size {n1} is not a multiple of 8 (DMA sublane "
                    "alignment); no tile can fit — use the XLA path"
                )
            return (
                f"no tuned tile candidate {candidates} fits volume "
                f"({n0},{n1},{n2}) with k={k}; pass bx/by explicitly"
            )
        return None
    if bx is None or by is None:
        return "pass both bx and by, or neither"
    return tile_error(n0, n1, n2, k, bx, by, itemsize)


def kernel_name(family: str, k: int, zpatch: bool, tile_sel: str) -> str:
    """The ``name=`` of one kernel variant's `pallas_call`:
    ``igg_<family>_k<k>_<plain|zpatch>``, plus ``_<tile_sel>`` for a
    pipelined subset launch (``ring0``, ``mid01``, ...).  It names the
    compiled custom call and sits in its ``op_name`` scope path, so a
    device trace says which variant ran."""
    name = f"igg_{family}_k{k}_{'zpatch' if zpatch else 'plain'}"
    return name if tile_sel == "all" else f"{name}_{tile_sel}"


def per_member(launch):
    """A kernel launch that `jax.vmap` runs once per ensemble member.

    The kernels address HBM through manual DMAs on ``memory_space=ANY``
    refs.  The `pallas_call` batching rule hands such a kernel the batched
    buffer while its DMA indices stay per-member, so a vmapped launch would
    read the wrong member or out of bounds.  Under vmap this wrapper runs
    the launch for each member in a `lax.map` instead: every member
    advances exactly as its own call (`models._batched`).  Unbatched calls
    run ``launch`` unchanged.
    """
    import jax
    import jax.numpy as jnp
    from jax import custom_batching, lax

    f = custom_batching.custom_vmap(launch)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = tuple(
            a if b else jnp.broadcast_to(a, (axis_size, *jnp.shape(a)))
            for a, b in zip(args, in_batched)
        )
        out = lax.map(lambda xs: f(*xs), args)
        return out, jax.tree.map(lambda _: True, out)

    return f
