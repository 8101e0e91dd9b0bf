"""Halo exchange — the hot path, re-designed TPU-first.

The reference implements `update_halo!` as ~670 LoC of explicit buffer
management, pack/unpack kernels, pinned host staging and `MPI_Isend/Irecv`
(`/root/reference/src/update_halo.jl`).  On TPU all of that collapses into a
single compiled XLA program: per dimension, the boundary planes are sliced,
moved HBM→HBM over ICI by `lax.ppermute` (XLA `collective-permute`), and
written into the opposite halo planes.  XLA owns scheduling, so the
reference's streams/tasks/waits have no equivalent — dependencies alone
enforce the required ordering.

Semantics ported exactly (with 0-based indices):

* One plane per side per dimension is exchanged: send plane ``ol-1`` goes to
  the lower neighbor's plane ``n-1``; send plane ``n-ol`` goes to the upper
  neighbor's plane ``0`` (reference ``sendranges``/``recvranges``,
  `/root/reference/src/update_halo.jl:544-563`).  ``update_halo(...,
  width=w)`` generalizes the plane to a ``w``-plane slab on deep-halo grids
  (``overlap >= 2w``) — the TPU-first extension that lets ``w`` fused
  stencil steps ride on one collective (temporal blocking; see
  `ops/pallas_stencil.py` and `models/diffusion3d.py:make_multi_step`).
* Dimensions are processed sequentially — the dim-``k`` exchange must see the
  dim-``k-1``-updated halos for corner correctness
  (`/root/reference/src/update_halo.jl:40`).  Here the sequencing is carried
  by data dependencies inside the one XLA program.
* Per-field overlap is shape-aware: ``ol(d, A) = overlaps[d] + (size(A,d) -
  nxyz[d])`` (`/root/reference/src/shared.jl:94`), which makes staggered
  fields (e.g. ``nx+1``) exchange the right planes.  A dimension with
  ``ol < 2`` has no halo and is skipped
  (`/root/reference/src/update_halo.jl:369`).
* Non-periodic edge blocks keep their boundary planes untouched (the
  reference's ``PROC_NULL`` neighbors): `ppermute` delivers zeros where a
  block has no source, so the received plane is masked against the old one
  with the block's mesh coordinate.
* Periodic with a single block in a dimension is a pure local copy — the
  reference's self-neighbor fast path
  (`/root/reference/src/update_halo.jl:57-63`).

`update_halo` works in two calling contexts:

1. **Global arrays** (outside any `shard_map`): the fields are global-block
   `jax.Array`s; a cached ``jit(shard_map(...))`` wrapper with donated inputs
   performs the exchange "in place".
2. **Inside `shard_map`/`stencil`** (fields are tracers of local blocks): the
   exchange is inlined into the caller's program so it fuses with the
   surrounding stencil computation — the analogue of the reference's advice to
   group halo updates for pipelining
   (`/root/reference/src/update_halo.jl:13-14`).

Batched/ensemble contract (ISSUE 8, `models._batched`): every traced-context
path in this module — `exchange_dims_multi`, `update_halo_padded_faces`,
`begin_slab_exchange`/`finish_slab_exchange`, the z-patch family — batches
under `jax.vmap` over a leading ensemble axis with the SAME collective count
at any B: the `lax.ppermute` batching rule carries the batch dimension
inside the one hop (payload ×B, never B hops), and the coalesced packer's
flatten/concat act on the per-member view so the width-group packing simply
grows a batch axis.  This is pinned as a tier-1 lint
(`analysis.budget.batched_budget_findings` — per-dimension ppermute counts
at B=1 vs B=4 must be equal) and in the compiled-HLO cost baseline
(`exchange/porous[coalesce=True,batch=4]`).  Code here must stay
vmap-transparent: any new transport that branches on concrete batch state
or issues per-member collectives breaks the B-for-the-price-of-1 invariant
and the lint will fail it.
"""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np

from ..parallel import grid as _grid
from ..parallel.topology import AXIS_NAMES, NDIMS

_jit_cache: dict = {}

#: Integrity-enabled exchange programs — (fn, TransportCollector) per key.
#: Separate from `_jit_cache` on purpose: the plain path's cache keys (and
#: the ``IGG_INTEGRITY=0`` zero-overhead pin) stay byte-for-byte unchanged.
_integrity_jit_cache: dict = {}

#: Armed ``bit_flip:…:transport`` target rank, consumed by the next
#: integrity-enabled global exchange (`utils.resilience` arms it with the
#: same arm-on-step / fire-on-next-collective idiom as ``net_delay``).
_transport_flip: int | None = None


def arm_transport_flip(proc: int) -> None:
    """Arm a one-shot in-flight payload-word flip on rank ``proc``'s next
    checksummed transport (the ``bit_flip`` chaos kind's transport
    placement).  No-op unless ``IGG_INTEGRITY=1`` routes the next global
    exchange through the checksummed build — the flip is baked into that
    program's wire buffers, after the checksum fold."""
    global _transport_flip
    _transport_flip = int(proc)


def _take_transport_flip() -> int | None:
    global _transport_flip
    proc, _transport_flip = _transport_flip, None
    return proc

# Guard/fault hook point: called on the OUTPUT tuple of every global-array
# `update_halo` (the host-side boundary where concrete fields exist — traced
# contexts inline into the caller's program and cannot run host hooks).  Two
# users: the fault-injection harness corrupts exchanged fields here
# (`utils.resilience.install_halo_fault_hook`), and debugging sessions can
# install a `check_fields` probe to localize which exchange first saw a NaN.
_post_exchange_hook = None


def set_post_exchange_hook(fn):
    """Install ``fn(fields_tuple) -> fields_tuple`` (or None to remove).
    Returns the previously installed hook."""
    global _post_exchange_hook
    prev = _post_exchange_hook
    _post_exchange_hook = fn
    return prev


def _clear_caches() -> None:
    _jit_cache.clear()
    _integrity_jit_cache.clear()


def _is_tracer(x) -> bool:
    import jax

    return isinstance(x, jax.core.Tracer)


def local_shape(A, gg=None) -> tuple[int, ...]:
    """Per-block (local) shape of a field.

    Tracers inside `shard_map` already have local shapes; concrete global-block
    arrays are ``dims``-times larger per sharded dimension.
    """
    if gg is None:
        gg = _grid.global_grid()
    if _is_tracer(A):
        return tuple(A.shape)
    shp = []
    for d in range(A.ndim):
        s, m = divmod(A.shape[d], gg.dims[d])
        if m != 0:
            raise ValueError(
                f"Field with global shape {tuple(A.shape)} is not divisible into "
                f"{gg.dims} blocks along dimension {d}; global-block fields must "
                f"have shape dims*local_shape (create them with the igg field "
                f"constructors)."
            )
        shp.append(s)
    return tuple(shp)


def ol(dim: int, A=None, shape: Sequence[int] | None = None, gg=None) -> int:
    """Shape-aware overlap of a field in ``dim`` (reference: src/shared.jl:93-94)."""
    if gg is None:
        gg = _grid.global_grid()
    if shape is None:
        shape = local_shape(A, gg)
    size_d = shape[dim] if dim < len(shape) else 1
    return gg.overlaps[dim] + (size_d - gg.nxyz[dim])


def halosize(dim: int, A, gg=None) -> tuple[int, ...]:
    """Shape of one halo plane of ``A`` in ``dim`` (reference: src/update_halo.jl:84)."""
    shp = local_shape(A, gg)
    if len(shp) > 1:
        return tuple(s for i, s in enumerate(shp) if i != dim)
    return (1,)


def _validate_fields(fields, gg) -> None:
    """Input validation ported from `/root/reference/src/update_halo.jl:804-834`.

    The reference's third check (identical concrete types) exists only because
    its communication buffers are reinterpreted across element types; there
    are no buffers here, so mixed-dtype calls are valid and the check is
    intentionally not ported.
    """
    shapes = [local_shape(A, gg) for A in fields]
    no_halo = [
        i
        for i, (A, shp) in enumerate(zip(fields, shapes))
        if all(ol(d, shape=shp, gg=gg) < 2 for d in range(len(shp)))
    ]
    if len(no_halo) > 1:
        pos = ", ".join(str(i + 1) for i in no_halo[:-1]) + f" and {no_halo[-1] + 1}"
        raise ValueError(f"The fields at positions {pos} have no halo; remove them from the call.")
    elif no_halo:
        raise ValueError(
            f"The field at position {no_halo[0] + 1} has no halo; remove it from the call."
        )
    dup = [
        (i, j)
        for i in range(len(fields))
        for j in range(i + 1, len(fields))
        if fields[i] is fields[j]
    ]
    if dup:
        i, j = dup[0]
        raise ValueError(
            f"The field at position {j + 1} is a duplicate of the one at the "
            f"position {i + 1}; remove the duplicate from the call."
        )


def dim_has_halo_activity(gg, d: int) -> bool:
    """Whether dimension ``d`` exchanges anything at all on this grid.

    Periodic dimensions always have partners (possibly self via the
    Cart_shift wrap); non-periodic ones only when the distance-``disp``
    shift stays on the grid for some block — ``abs(disp) >= dims[d]`` makes
    every partner PROC_NULL (and ``disp == 0`` a self-partner).
    """
    if gg.periods[d]:
        return True
    return abs(int(gg.disp)) < gg.dims[d]


def require_deep_halo(w: int, gg=None, *, what: str = "exchange_every") -> None:
    """Validate that every dimension with halo activity has ``overlap >= 2w``.

    Shared precondition of the temporal-blocking cadences
    (`update_halo(width=w)` once per ``w`` steps — the fused-kernel and
    XLA-only variants in the models): the sent slab planes must lie at
    distance >= ``w`` from the block edge, where ``w`` stencil steps are
    still exact.  Raises ``ValueError`` naming the shallow dimensions.

    This is a *grid-level* precheck against ``gg.overlaps`` for an early,
    caller-facing error at build time; the authoritative per-field check is
    the shape-aware ``ol`` validation inside `_exchange_dim`, which a field
    whose own ``ol`` is below the grid overlap (e.g. an ``n-1``-sized axis)
    still hits at trace time.
    """
    if gg is None:
        gg = _grid.global_grid()
    shallow = [
        d
        for d in range(NDIMS)
        if dim_has_halo_activity(gg, d) and gg.overlaps[d] < 2 * w
    ]
    if shallow:
        raise ValueError(
            f"{what}={w} on a communicating grid needs a deep halo: overlap >= "
            f"{2 * w} in every dimension with halo activity, but dims {shallow} "
            f"have overlaps {[gg.overlaps[d] for d in shallow]} (grid dims="
            f"{gg.dims}, periods={gg.periods}). Re-init with overlap"
            f"{'/'.join('xyz'[d] for d in shallow)}={2 * w}, or use the "
            "per-step exchange."
        )


def _set_plane(A, plane, index: int, dim: int):
    import jax.numpy as jnp
    from jax import lax

    return lax.dynamic_update_slice_in_dim(A, plane.astype(A.dtype), index, axis=dim)


def _get_plane(A, index: int, dim: int, width: int = 1):
    from jax import lax

    return lax.slice_in_dim(A, index, index + width, axis=dim)


def _exchange_dim(A, d: int, gg, width: int = 1, logical=None, axis=None) -> "jax.Array":
    """Exchange the two halo slabs (``width`` planes each) of block ``A``
    along dimension ``d``.

    ``width=1`` is the reference's exchange.  ``width=w>1`` is the deep-halo
    generalization for temporal blocking: my planes ``[o-w, o)`` refresh the
    lower neighbor's ``[n-w, n)`` and ``[n-o, n-o+w)`` refresh the upper
    neighbor's ``[0, w)`` — one collective per ``w`` steps instead of ``w``
    collectives, so the latency of a `collective_permute` hop amortizes over
    ``w`` fused steps.  Valid iff ``ol >= 2*width`` (the sent planes must lie
    at distance >= width from my own edge, where a width-deep stencil sweep
    still has exact values).

    ``logical``: the field's REAL local shape when ``A`` carries it in a
    larger padded layout (`ops.pallas_leapfrog.pad_faces`) — slab indices
    and the shape-aware ``ol`` are computed from it, and since every real
    plane index is within the padded array the slicing needs no change.
    The pad tail is junk by the layout's contract, so exchanging junk
    planes along *other* dimensions (full-extent slabs include the tail)
    is harmless.

    ``axis``: the ARRAY axis holding grid dimension ``d``'s data when the
    two differ (transposed patch layouts store y on axis 2); slab indices
    still come from ``logical[d]``, the field's real size in grid dim ``d``.
    """
    vals = _slab_recv_values(A, d, gg, width, logical, axis=axis)
    if vals is None:
        return A
    return _apply_recv(A, d, vals, width, logical=logical, axis=axis)


def _apply_recv(A, d: int, vals, width: int, logical=None, axis=None):
    """Write a dim-``d`` exchange's received ``(lo_vals, hi_vals)`` slabs
    into ``A``'s halo planes — the write half of `_exchange_dim`, shared
    with the multi-field exchange paths."""
    lo_vals, hi_vals = vals
    shp = logical if logical is not None else tuple(A.shape)
    ax = d if axis is None else axis
    A = _set_plane(A, hi_vals, shp[d] - width, ax)
    A = _set_plane(A, lo_vals, 0, ax)
    return A


def _patch_slab(slab, d: int, start: int, width: int, received, shp):
    """Overwrite a dim-``d`` slab's earlier-dim halo strips with received
    values — the sequential-dimension corner carry-over
    (`/root/reference/src/update_halo.jl:40`) applied at slab granularity.

    ``slab`` was sliced from the field at plane range ``[start,
    start+width)`` along ``d``; ``received`` maps each already-exchanged
    dim ``d2 < d`` to its ``(lo, hi)`` receive slabs (full field extent
    along ``d``); ``shp`` is the field's logical shape (the hi-strip
    offset, like `_set_plane`'s in `_exchange_dim`).  This makes
    `begin_slab_exchange`'s sends bit-identical to slicing the
    sequentially-updated array.
    """
    from jax import lax

    for d2, (lo2, hi2) in received.items():
        if d2 >= slab.ndim:
            continue
        w2 = lo2.shape[d2]
        strip = lax.slice_in_dim(lo2, start, start + width, axis=d)
        off = [0] * slab.ndim
        slab = lax.dynamic_update_slice(slab, strip.astype(slab.dtype), off)
        strip = lax.slice_in_dim(hi2, start, start + width, axis=d)
        off[d2] = shp[d2] - w2
        slab = lax.dynamic_update_slice(slab, strip.astype(slab.dtype), off)
    return slab


def _slab_parts(A, d: int, gg, width: int = 1, logical=None, axis=None,
                received=None):
    """The slabs a ``d``-exchange of ``A`` involves, without communicating.

    Returns ``None`` when the dimension exchanges nothing for this field,
    ``("self", lo_vals, hi_vals)`` on the self-partner fast path (a pure
    local copy needs no transport), or ``("permute", send_lo, send_hi,
    keep_lo, keep_hi)`` — the two eager send slabs plus the PROC_NULL
    keep-old slabs as thunks (built only when a non-periodic edge needs
    masking).  The communication half lives in `_permute_slabs`
    (per-field) and `_coalesced_permute` (packed multi-field).
    """
    shp = logical if logical is not None else tuple(A.shape)  # local block shape
    ax = d if axis is None else axis  # array axis carrying grid dim d's data
    if d >= len(shp):
        # A dimension beyond the field's rank can only ever be exchanged with a
        # self/absent neighbor (grid validation forces dims[d]==1, period 0).
        return None
    o = ol(d, shape=shp, gg=gg)
    if o < 2:
        return None  # no halo in this dimension (reference: update_halo.jl:369)
    n = shp[d]
    if not dim_has_halo_activity(gg, d):
        # No partners at all: dims==1 non-periodic, or every distance-disp
        # shift falls off the grid (all partners PROC_NULL).
        return None
    if o < 2 * width:
        # Only dimensions that actually exchange need the deep halo.
        raise ValueError(
            f"update_halo(width={width}) needs overlap >= {2 * width} in "
            f"dimension {d}; this field has ol={o}. Re-init the grid with "
            f"overlap{'xyz'[d]}={2 * width} (deep halo) or use width=1."
        )
    # Exchange partners sit at Cartesian distance ``disp`` — the semantics of
    # the reference's ``MPI_Cart_shift(dim, disp)`` neighbor table
    # (`/root/reference/src/init_global_grid.jl:89-92`), which its
    # `update_halo!` sends to (`/root/reference/src/update_halo.jl:713-735`).
    # The ppermute pairs (see `_permute_slabs`) realize exactly
    # `GlobalGrid.neighbors` (`parallel/topology.py:neighbors_table`):
    # send_lo goes to ``neighbors[0, d]`` (coordinate - disp), send_hi to
    # ``neighbors[1, d]``.
    def slab(start):
        s = _get_plane(A, start, ax, width)
        if received:
            s = _patch_slab(s, ax, start, width, received, shp)
        return s

    if _partner_self(gg, d):
        # Every block is its own partner (periodic wrap disp%nd==0, the
        # reference's self-neighbor fast path generalized, or disp==0):
        # pure local copy (reference: update_halo.jl:57-63).
        return (
            "self",
            slab(n - o),      # -> planes [0, width)
            slab(o - width),  # -> planes [n-width, n)
        )

    # Slabs go to the lower partner's top ``width`` planes / the upper
    # partner's bottom ``width`` planes (reference sendranges/recvranges,
    # generalized from one plane to a slab).
    return (
        "permute",
        slab(o - width),
        slab(n - o),
        lambda: slab(0),
        lambda: slab(n - width),
    )


def _slab_recv_values(A, d: int, gg, width: int = 1, logical=None, axis=None,
                      received=None):
    """The two slabs a ``d``-exchange of ``A`` would write, without writing.

    Returns ``(lo_vals, hi_vals)`` — the values destined for planes
    ``[0, width)`` and ``[n-width, n)`` (``n`` from ``logical`` when given)
    — or ``None`` when the dimension exchanges nothing for this field.
    `_exchange_dim` is get-values + two `_set_plane`s; the fused kernels'
    z-patch path (`z_slab_patches`) uses the values directly, applying them
    in VMEM where the minor-dim plane surgery is free (see
    docs/performance.md's exchanged-dimension anisotropy note).

    ``received`` (the `begin_slab_exchange` path): earlier dims' receive
    slabs, patched into this dim's send/keep slabs via `_patch_slab` so the
    sends equal those sliced from a sequentially-updated array.
    """
    p = _slab_parts(A, d, gg, width, logical, axis, received)
    if p is None:
        return None
    if p[0] == "self":
        return p[1], p[2]
    _, send_lo, send_hi, keep_lo, keep_hi = p
    return _permute_slabs(
        gg, d, send_lo=send_lo, send_hi=send_hi, keep_lo=keep_lo,
        keep_hi=keep_hi,
    )


def _partner_self(gg, d: int) -> bool:
    """Every block its own distance-``disp`` partner along ``d``?"""
    nd = gg.dims[d]
    disp = int(gg.disp)
    return (disp % nd == 0) if bool(gg.periods[d]) else (disp == 0)


def _permute_slabs(gg, d: int, *, send_lo, send_hi, keep_lo, keep_hi):
    """ppermute two send slabs to the distance-``disp`` partners along ``d``.

    The ONE implementation of the neighbor communication used by both the
    full-field exchange (`_slab_recv_values`) and the packed z-export path
    (`z_patch_from_export`) — partner permutation, periodic wrap, and
    PROC_NULL keep-old masking must never drift between the two.  Returns
    ``(lo_vals, hi_vals)`` destined for planes ``[0,w)`` / ``[n-w,n)``;
    ``keep_lo``/``keep_hi`` are thunks producing the current boundary slabs
    for blocks whose shift falls off a non-periodic grid (the reference's
    PROC_NULL neighbors do nothing).  Self-partner configs never reach
    here (both callers take their own fast path).
    """
    import jax.numpy as jnp
    from jax import lax

    nd = gg.dims[d]
    periodic = bool(gg.periods[d])
    disp = int(gg.disp)
    axis = AXIS_NAMES[d]
    if periodic:
        perm_down = [(i, (i - disp) % nd) for i in range(nd)]
        perm_up = [(i, (i + disp) % nd) for i in range(nd)]
    else:
        perm_down = [(i, i - disp) for i in range(nd) if 0 <= i - disp < nd]
        perm_up = [(i, i + disp) for i in range(nd) if 0 <= i + disp < nd]
    try:
        recv_hi = lax.ppermute(send_lo, axis, perm_down)  # from my upper partner
        recv_lo = lax.ppermute(send_hi, axis, perm_up)  # from my lower partner
    except NameError as e:
        raise RuntimeError(
            "update_halo was called on traced (non-concrete) fields outside of an "
            "igg.stencil/shard_map context over the global grid's mesh. Either call "
            "it on global-block arrays, or inside a function wrapped with "
            "igg.stencil (or jax.shard_map over igg's mesh axes 'x','y','z')."
        ) from e
    if periodic:
        return recv_lo, recv_hi
    # ppermute delivered zeros to blocks with no source partner; keep the
    # old boundary slab there.
    idx = lax.axis_index(axis)
    has_upper = (idx + disp >= 0) & (idx + disp < nd)
    has_lower = (idx - disp >= 0) & (idx - disp < nd)
    return (
        jnp.where(has_lower, recv_lo, keep_lo()),
        jnp.where(has_upper, recv_hi, keep_hi()),
    )


# --- Coalesced multi-field transport (message combining) ---------------------
#
# One `collective-permute` pair per (dimension, dtype byte width) instead of
# one per field: every participating field's send slab is flattened to its
# same-width unsigned-int words (the chunked gather's byte-exact transport,
# `ops.gather._block_fetch_fn` — f32/bf16/-0.0/NaN payloads survive because
# bitcasting is arithmetic-free), the flat words concatenate into one buffer
# per byte width, the packed buffers ride `_permute_slabs` (same partner
# permutation, same PROC_NULL whole-word masking), and the received buffer
# splits/bitcasts back into per-field slabs.  Fewer, fatter hops: the
# per-hop latency of a collective amortizes over every field of the step —
# the reference's own pipelining advice taken one level further
# (`/root/reference/src/update_halo.jl:13-14`).


def _word_width(dtype) -> int:
    """Transport word size in bytes (complex splits into two float words)."""
    dt = np.dtype(dtype)
    return dt.itemsize // 2 if dt.kind == "c" else dt.itemsize


def _flat_words(x):
    """Flatten ``x`` to its same-width unsigned-int words, byte-exactly.

    bool cannot `bitcast_convert_type`; its {0,1} values convert to uint8
    exactly (and back), which is just as byte-faithful for a transport.
    """
    import jax.numpy as jnp
    from jax import lax

    from .gather import _word_dtype

    if x.dtype == jnp.bool_.dtype:
        return x.reshape(-1).astype(jnp.uint8)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = jnp.stack((x.real, x.imag), axis=-1)
    return lax.bitcast_convert_type(x, _word_dtype(x.dtype)).reshape(-1)


def _from_words(buf, shape, dtype):
    """Invert `_flat_words`: words back to an array of ``shape``/``dtype``."""
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    if dt == jnp.bool_.dtype:
        return buf.reshape(tuple(shape)).astype(dt)
    if jnp.issubdtype(dt, jnp.complexfloating):
        ft = jnp.finfo(dt).dtype
        comp = lax.bitcast_convert_type(buf.reshape(tuple(shape) + (2,)), ft)
        return lax.complex(comp[..., 0], comp[..., 1])
    return lax.bitcast_convert_type(buf.reshape(tuple(shape)), dt)


def _coalesced_permute(gg, d: int, parts):
    """`_permute_slabs` for several fields at once: one ppermute pair per
    dtype byte-width group instead of one per field.

    ``parts``: per-field ``(send_lo, send_hi, keep_lo, keep_hi)`` tuples
    (keeps as thunks, `_slab_parts`).  Returns per-field ``(lo_vals,
    hi_vals)`` BIT-identical to the per-field path: the packed buffer moves
    the same words, the PROC_NULL mask picks whole words with the same
    per-dim predicate, and the bitcast round trip is arithmetic-free.  A
    width group with a single member skips the packing (nothing to combine
    — same collectives either way, no relayout paid).

    Autodiff: `lax.bitcast_convert_type` has no tangent, so the packed
    transport carries a custom VJP that differentiates the PER-FIELD
    transport instead (`_packed_transport` — the `fused_with_xla_grad`
    pattern): both move the identical values field-for-field, so the
    per-field path's exact ppermute/where transpose IS the packed path's
    transpose.  Without it, `jax.grad` through a coalesced exchange would
    silently drop every cotangent that crosses a block boundary.
    """
    periodic = bool(gg.periods[d])
    sends_lo = tuple(p[0] for p in parts)
    sends_hi = tuple(p[1] for p in parts)
    if periodic:
        # Keep slabs are only ever read by the PROC_NULL mask of
        # non-periodic dims; do not materialize them elsewhere.
        keeps_lo = keeps_hi = ()
    else:
        keeps_lo = tuple(p[2]() for p in parts)
        keeps_hi = tuple(p[3]() for p in parts)
    los, his = _packed_transport(gg, d)(sends_lo, sends_hi, keeps_lo, keeps_hi)
    return [(lo, hi) for lo, hi in zip(los, his)]


def _keep_thunks(keeps_lo, keeps_hi, j: int):
    """keep_lo/keep_hi thunk kwargs for field ``j`` (dummies on periodic
    dims, where `_permute_slabs` never invokes them)."""
    if not keeps_lo:
        return dict(keep_lo=lambda: None, keep_hi=lambda: None)
    return dict(keep_lo=lambda: keeps_lo[j], keep_hi=lambda: keeps_hi[j])


def _flip_wire_word(buf, proc: int, gg):
    """XOR bit 0 of payload word 0 of rank ``proc``'s wire buffer — the
    armed ``bit_flip:…:transport`` injection.  Applied AFTER the checksum
    fold (in-flight corruption: the sender's fold covered the clean words,
    so the receiver's recompute over the landed payload must disagree)."""
    import jax.numpy as jnp
    from jax import lax

    # row-major linear rank from the mesh coords (topology.rank_of_coords)
    rank = lax.axis_index(AXIS_NAMES[0])
    for dd in range(1, NDIMS):
        rank = rank * gg.dims[dd] + lax.axis_index(AXIS_NAMES[dd])
    flipped = buf.at[0].set(buf[0] ^ jnp.array(1, buf.dtype))
    return jnp.where(rank == proc, flipped, buf)


def _packed_transport(gg, d: int):
    """The width-group packed transport as a differentiable function of the
    per-field send/keep slabs.  Primal: bitcast-pack per byte width, one
    `_permute_slabs` pair per group.  VJP: `jax.vjp` of the per-field
    transport over the same operands (value-identical by the coalescing
    contract, and built from primitives with exact transpose rules).

    With a `integrity.transport.TransportCollector` active (the
    integrity-enabled global exchange, ``IGG_INTEGRITY=1``), every group —
    singletons included — packs to the word view and the wire buffer grows
    ONE checksum word (XOR fold of the payload words, `append_checksum`);
    the receive side recomputes the fold over the landed payload
    (`split_and_verify`) and the traced mismatch flags register on the
    collector.  Same hops, payload +1 word per (group, direction); PROC_NULL
    keep buffers carry their own self-consistent fold, so masked edges can
    never false-trip.  The checksummed build returns the RAW function (no
    `custom_vjp` envelope): the host integrity path never differentiates,
    and flags escaping through a custom-VJP primal would leak tracers.
    """
    import jax
    import jax.numpy as jnp

    from ..integrity import transport as _itransport
    from ..utils import telemetry as _telemetry

    col = _itransport.active_collector()

    def packed(sends_lo, sends_hi, keeps_lo, keeps_hi):
        groups: dict[int, list[int]] = {}
        for j, s in enumerate(sends_lo):
            groups.setdefault(_word_width(s.dtype), []).append(j)
        los: list = [None] * len(sends_lo)
        his: list = [None] * len(sends_lo)
        for wbytes, idxs in sorted(groups.items()):
            if len(idxs) == 1 and col is None:
                (j,) = idxs
                los[j], his[j] = _permute_slabs(
                    gg, d, send_lo=sends_lo[j], send_hi=sends_hi[j],
                    **_keep_thunks(keeps_lo, keeps_hi, j),
                )
                continue
            flats_lo = [_flat_words(sends_lo[j]) for j in idxs]
            flats_hi = [_flat_words(sends_hi[j]) for j in idxs]
            sizes = [int(f.shape[0]) for f in flats_lo]
            buf_lo = jnp.concatenate(flats_lo)
            buf_hi = jnp.concatenate(flats_hi)
            if len(idxs) > 1:
                # Trace-time counters (like `halo.begin_slab_traces`):
                # coalesced exchanges are built into compiled programs, so
                # these count traced collectives and their per-hop payload
                # bytes (docs/observability.md).
                _telemetry.counter("halo.coalesced_collectives").inc(2)
                _telemetry.counter("halo.coalesced_bytes").inc(
                    2 * int(buf_lo.shape[0]) * wbytes
                )
            if col is not None:
                wire_lo = _itransport.append_checksum(buf_lo)
                wire_hi = _itransport.append_checksum(buf_hi)
                flip = col.take_flip()
                if flip is not None:
                    wire_lo = _flip_wire_word(wire_lo, flip, gg)
                    wire_hi = _flip_wire_word(wire_hi, flip, gg)
                recv_lo, recv_hi = _permute_slabs(
                    gg, d,
                    send_lo=wire_lo,
                    send_hi=wire_hi,
                    keep_lo=lambda: _itransport.append_checksum(
                        jnp.concatenate([_flat_words(keeps_lo[j]) for j in idxs])
                    ),
                    keep_hi=lambda: _itransport.append_checksum(
                        jnp.concatenate([_flat_words(keeps_hi[j]) for j in idxs])
                    ),
                )
                recv_lo, bad_lo = _itransport.split_and_verify(recv_lo)
                recv_hi, bad_hi = _itransport.split_and_verify(recv_hi)
                col.record(
                    dim=d, width=wbytes, fields=idxs, bad_lo=bad_lo,
                    bad_hi=bad_hi,
                )
            else:
                recv_lo, recv_hi = _permute_slabs(
                    gg, d,
                    send_lo=buf_lo,
                    send_hi=buf_hi,
                    keep_lo=lambda: jnp.concatenate(
                        [_flat_words(keeps_lo[j]) for j in idxs]
                    ),
                    keep_hi=lambda: jnp.concatenate(
                        [_flat_words(keeps_hi[j]) for j in idxs]
                    ),
                )
            off = 0
            for j, size in zip(idxs, sizes):
                shape, dtype = sends_lo[j].shape, sends_lo[j].dtype
                los[j] = _from_words(recv_lo[off : off + size], shape, dtype)
                his[j] = _from_words(recv_hi[off : off + size], shape, dtype)
                off += size
        return tuple(los), tuple(his)

    def per_field(sends_lo, sends_hi, keeps_lo, keeps_hi):
        outs = [
            _permute_slabs(
                gg, d, send_lo=sends_lo[j], send_hi=sends_hi[j],
                **_keep_thunks(keeps_lo, keeps_hi, j),
            )
            for j in range(len(sends_lo))
        ]
        return tuple(o[0] for o in outs), tuple(o[1] for o in outs)

    if col is not None:
        # Checksummed build: raw function, no custom_vjp (docstring).
        return packed

    f = jax.custom_vjp(packed)

    def fwd(*ops):
        return packed(*ops), ops

    def bwd(ops, g):
        _, vjp = jax.vjp(per_field, *ops)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _multi_slab_recv_values(fields, d: int, gg, width: int = 1, logicals=None,
                            axes=None, receiveds=None, coalesce: bool = True):
    """Per-field ``(lo_vals, hi_vals)`` of a dim-``d`` exchange of a field
    LIST — `_slab_recv_values` over several fields, with the collectives
    coalesced across fields (`_coalesced_permute`) when ``coalesce`` is on
    and at least two fields actually permute.  Entries are ``None`` where a
    field skips the dimension; ``axes[i]``/``logicals[i]``/``receiveds[i]``
    as in `_slab_recv_values`.

    With a `TransportCollector` active (the integrity-enabled global
    exchange), EVERY permuting field routes through `_coalesced_permute`
    regardless of count or the ``coalesce`` flag — the checksum word rides
    the packed wire form, so per-field hops must pack too (still one
    ppermute pair per width group; the collective census is unchanged)."""
    from ..integrity.transport import active_collector

    n = len(fields)
    logicals = (None,) * n if logicals is None else tuple(logicals)
    axes = (None,) * n if axes is None else tuple(axes)
    receiveds = (None,) * n if receiveds is None else tuple(receiveds)
    out: list = [None] * n
    permuting: list = []
    for i, A in enumerate(fields):
        p = _slab_parts(A, d, gg, width, logicals[i], axes[i], receiveds[i])
        if p is None:
            continue
        if p[0] == "self":
            out[i] = (p[1], p[2])
        else:
            permuting.append((i, p[1:]))
    if permuting and (
        active_collector() is not None or (coalesce and len(permuting) >= 2)
    ):
        vals = _coalesced_permute(gg, d, [p for _, p in permuting])
        for (i, _), v in zip(permuting, vals):
            out[i] = v
    else:
        for i, (send_lo, send_hi, keep_lo, keep_hi) in permuting:
            out[i] = _permute_slabs(
                gg, d, send_lo=send_lo, send_hi=send_hi, keep_lo=keep_lo,
                keep_hi=keep_hi,
            )
    return out


def _default_coalesce() -> bool:
    """``IGG_COALESCE`` env default for the multi-field exchange paths.

    Unset = auto (coalesce whenever >= 2 fields share a dimension's
    exchange — it is bit-identical, so the only reason to stay per-field
    is debugging/attribution); ``0`` restores per-field collectives;
    nonzero forces the auto behavior explicitly.  Read per call/trace,
    like ``IGG_DONATE``.
    """
    from ..utils.config import coalesce_env

    val = coalesce_env()
    return True if val is None else val


def _update_halo_local(fields: tuple, gg, width: int = 1,
                       coalesce: bool | None = None) -> tuple:
    """Per-block exchange of all fields, dimensions strictly in order x→y→z.

    ``coalesce`` (None = `IGG_COALESCE` env, default auto): pack every
    field's send slab into one buffer per dtype byte width and issue ONE
    collective-permute pair per (dimension, width group) instead of one per
    field (`_coalesced_permute`) — bit-identical, fewer/fatter hops.
    """
    from jax import named_scope

    if coalesce is None:
        coalesce = _default_coalesce()
    out = list(fields)
    with named_scope("igg_halo_exchange"):
        for d in range(NDIMS):
            vals = _multi_slab_recv_values(out, d, gg, width, coalesce=coalesce)
            for i, v in enumerate(vals):
                if v is not None:
                    out[i] = _apply_recv(out[i], d, v, width)
    return tuple(out)


def _padded_logicals(C, Axp, Ayp, Azp):
    from .pallas_leapfrog import padded_face_shapes

    n0, n1, n2 = C.shape
    if (Axp.shape, Ayp.shape, Azp.shape) != padded_face_shapes(C.shape):
        raise ValueError(
            f"fields must be in pad_faces layout for cell shape {tuple(C.shape)}: "
            f"got {Axp.shape}, {Ayp.shape}, {Azp.shape}"
        )
    return (None, (n0 + 1, n1, n2), (n0, n1 + 1, n2), (n0, n1, n2 + 1))


def _zpatch_scope(what: str):
    """Run a z-patch helper under ``jax.named_scope("igg_zpatch_<what>")``,
    so a device trace names the ops of the self-neighbour z exchange.  The
    scope changes no op's class in a trace reduction: it never contains
    ``igg_halo_exchange`` or ``igg_slab_exchange``, and it adds to an op's
    scope path without taking one away."""

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            from jax import named_scope

            with named_scope(f"igg_zpatch_{what}"):
                return fn(*args, **kwargs)

        return scoped

    return deco


def _pack_z_patch(lo, hi, width: int):
    """Pack a field's two z slabs into one 128-lane array: lanes ``[0, w)``
    = values for planes ``[0, w)``, lanes ``[w, 2w)`` = values for planes
    ``[n-w, n)``, junk beyond — the layout the fused kernels' z-patch DMA
    windows require (full-minor 128-lane fetches are the only lane-aligned
    way to move a thin z slab; see the exchanged-dimension anisotropy note
    in docs/performance.md)."""
    import jax.numpy as jnp

    packed = jnp.concatenate([lo, hi], axis=2)
    return jnp.pad(packed, ((0, 0), (0, 0), (0, 128 - 2 * width)))


def z_slab_patch(A, *, width: int = 1):
    """Single-field version of `z_slab_patches` (the diffusion kernel's T).

    Returns the packed 128-lane patch for a plain cell field, or None when
    the z dimension exchanges nothing."""
    gg = _grid.global_grid()
    vals = _slab_recv_values(A, 2, gg, width)
    if vals is None:
        return None
    return _pack_z_patch(*vals, width)


@_zpatch_scope("identity")
def identity_z_patch(A, *, width: int = 1):
    """Single-field `identity_z_patches` (re-writes the current z planes)."""
    n = A.shape[2]
    return _pack_z_patch(
        _get_plane(A, 0, 2, width), _get_plane(A, n - width, 2, width), width
    )


@_zpatch_scope("apply")
def apply_z_patch(A, patch, *, width: int = 1):
    """Single-field `apply_z_patches` (the chunk-end restoration)."""
    n = A.shape[2]
    A = _set_plane(A, patch[:, :, :width], 0, 2)
    return _set_plane(A, patch[:, :, width : 2 * width], n - width, 2)


# --- Transposed thin-patch layout (round 5) ---------------------------------
#
# The packed 128-lane z-patch layout moves 128 lanes per window for 2k-4k
# real planes — at n2=256 the patch/export windows cost the fused z-split
# cadence ~30% extra HBM traffic (VERDICT r4 missing #3).  The transposed
# layout stores the thin dimension in SUBLANES instead: a patch is
# ``(n0, pad8(planes), n1p)`` with plane p of the field's y-row at
# ``[:, p, :]`` — sublanes are 8-dense, so windows move pad8(2k) planes
# instead of 128 lanes (~16x less patch traffic), and the export write
# shrinks the same way.  The kernel needs FULL-Y tiles (``by == n1``) for
# this layout: the transposed export's out-DMA then has no minor-dim window
# offsets at all (minor-dim slicing would need 128-aligned offsets the
# owned-block geometry cannot provide).  Plane layout along axis 1 is
# identical to the packed layout's lanes: patches [0,w) = values for planes
# [0,w), [w,2w) = the top w planes; exports [0,w) send-hi, [w,2w) send-lo,
# [2w,3w)/[3w,4w) keep-old.  ``n1p`` pads the minor (y) extent to a 128
# multiple (Mosaic lane-tile alignment).

from ._fused_envelope import pad8 as _pad8, pad128 as _pad128


def _pack_z_patch_t(lo, hi, width: int):
    """Pack two z slabs (each ``(n0, n1, width)``) into the transposed patch
    ``(n0, pad8(2w), pad128(n1))``."""
    import jax.numpy as jnp

    packed = jnp.concatenate([lo, hi], axis=2).transpose(0, 2, 1)
    n0, p, n1 = packed.shape
    return jnp.pad(packed, ((0, 0), (0, _pad8(p) - p), (0, _pad128(n1) - n1)))


@_zpatch_scope("identity")
def identity_z_patch_t(A, *, width: int = 1):
    """Transposed-layout `identity_z_patch` (re-writes the current z planes)."""
    n = A.shape[2]
    return _pack_z_patch_t(
        _get_plane(A, 0, 2, width), _get_plane(A, n - width, 2, width), width
    )


@_zpatch_scope("apply")
def apply_z_patch_t(A, patch_t, *, width: int = 1):
    """Transposed-layout `apply_z_patch` (the chunk-end restoration)."""
    n0, n1, n = A.shape
    lo = patch_t[:, 0:width, :n1].transpose(0, 2, 1)
    hi = patch_t[:, width : 2 * width, :n1].transpose(0, 2, 1)
    A = _set_plane(A, lo, 0, 2)
    return _set_plane(A, hi, n - width, 2)


#: Array-axis map of the transposed z-patch/export layout: grid dim 0's
#: slabs live on array axis 0 (as usual), grid dim 1's on array axis 2
#: (the ``axes`` override of `exchange_dims_multi`).
_T_AXES = {0: 0, 1: 2}


def exchange_dims_t(E, *, width: int, shape, coalesce=None):
    """x/y-exchange a TRANSPOSED z-patch/export array ``(n0, P, n1p)``.

    Grid dim 0's slabs live on array axis 0 (as usual); grid dim 1's live on
    array axis 2, with slab indices from the field's REAL shape ``shape`` —
    the ``axis`` override of `_exchange_dim`.  Dimension order (x before y)
    carries the sequential-dimension corner semantics exactly like the
    packed layout's `exchange_dims`.
    """
    (E,) = exchange_dims_multi(
        (E,), (0, 1), width=width, logicals=(shape,), axes=(_T_AXES,),
        coalesce=coalesce,
    )
    return E


@_zpatch_scope("from_export")
def z_patch_from_export_t(export_t, *, width: int):
    """Transposed-layout `z_patch_from_export`: the z communication on the
    ``(n0, PE, n1p)`` export's axis-1 plane slabs.  Must run AFTER the x/y
    exchange of the export (`exchange_dims_t`)."""
    import jax.numpy as jnp

    gg = _grid.global_grid()
    w = width
    if _partner_self(gg, 2):
        # Planes [0,2w) are already the patch (send-hi -> planes [0,w),
        # send-lo -> the top w planes); the pad8 tail planes are junk either
        # way, so hand the export straight back when the pads agree.
        if _pad8(2 * w) == export_t.shape[1]:
            return export_t
        return export_t[:, 0 : _pad8(2 * w), :]
    recv_lo, recv_hi = _permute_slabs(
        gg, 2,
        send_lo=export_t[:, w : 2 * w, :],
        send_hi=export_t[:, 0:w, :],
        keep_lo=lambda: export_t[:, 2 * w : 3 * w, :],
        keep_hi=lambda: export_t[:, 3 * w : 4 * w, :],
    )
    packed = jnp.concatenate([recv_lo, recv_hi], axis=1)
    pad = _pad8(2 * w) - 2 * w
    return jnp.pad(packed, ((0, 0), (0, pad), (0, 0)))


def exchange_dims(A, dims, *, width: int = 1, logical=None):
    """Exchange a single field along the given dimensions only (traced
    context; the z-patch cadences exchange x/y here and route z through
    the kernel).  ``logical`` as in `_exchange_dim` (packed z-slab exports
    exchange with their field's REAL x/y slab indices)."""
    gg = _grid.global_grid()
    for d in dims:
        A = _exchange_dim(A, d, gg, width, logical=logical)
    return A


def exchange_dims_multi(fields, dims, *, width: int = 1, logicals=None,
                        axes=None, coalesce: bool | None = None):
    """Exchange SEVERAL fields along the given dimensions in one pass — the
    multi-field `exchange_dims`, with each dimension's collectives coalesced
    across fields (one `collective-permute` pair per (dimension, dtype byte
    width); ``coalesce`` None = the ``IGG_COALESCE`` env default, auto-on).

    ``logicals[i]``: field ``i``'s REAL shape for padded layouts; ``axes[i]``:
    an optional ``{grid dim: array axis}`` map for transposed layouts
    (`exchange_dims_t`'s y-on-axis-2).  Dimensions run strictly in the given
    order, each seeing the previous dims' updated halos — the sequential-
    dimension corner semantics, unchanged.  Traced-context only, like
    `exchange_dims`.
    """
    gg = _grid.global_grid()
    if coalesce is None:
        coalesce = _default_coalesce()
    n = len(fields)
    logicals = (None,) * n if logicals is None else tuple(logicals)
    axes = (None,) * n if axes is None else tuple(axes)
    out = list(fields)
    for d in dims:
        axs = [None if a is None else a.get(d) for a in axes]
        vals = _multi_slab_recv_values(
            out, d, gg, width, logicals, axs, coalesce=coalesce
        )
        for i, v in enumerate(vals):
            if v is not None:
                out[i] = _apply_recv(out[i], d, v, width, logicals[i], axs[i])
    return tuple(out)


# --- Early-dispatch slab exchange (pipelined group schedule) ----------------


def begin_slab_exchange(fields, dims, *, width: int, logicals=None,
                        coalesce=None):
    """Start the slab exchange of ``fields`` along ``dims`` WITHOUT writing
    the received planes back.

    The pipelined group schedule's early-exchange entry: called on the
    boundary pass's outputs — which own every send plane — so the
    `collective-permute`s dispatch with only thin slab slices as
    dependencies and fly while the interior pass computes.  Sequential-
    dimension corner semantics are preserved at slab granularity: each
    dim-``d`` send (and PROC_NULL keep) slab is patched with the dims
    ``< d`` receive strips from THIS call (`_patch_slab`), exactly the
    values a serialized per-dim exchange would have sliced.  Returns one
    ``pend`` list per field — ``[(d, lo_vals, hi_vals), ...]`` over the
    dims that actually exchange — for `finish_slab_exchange`.

    ``finish_slab_exchange(fields', pends)`` on arrays holding the same
    owned values is bit-identical to the serialized exchange
    (`exchange_dims` / `update_halo_padded_faces`) over the same dims.
    ``logicals``: per-field REAL shapes for padded layouts (as in
    `_exchange_dim`).  ``coalesce`` (None = ``IGG_COALESCE``): pack each
    dimension's send slabs across fields into one collective-permute pair
    per dtype byte width — each field's sends depend only on its OWN
    earlier-dim receive strips, so the dim-major packing moves exactly the
    per-field values.  Traced-context only, like `exchange_dims`.
    """
    from ..utils import telemetry as _telemetry
    from ..utils import tracing as _tracing
    from jax import named_scope

    gg = _grid.global_grid()
    if logicals is None:
        logicals = (None,) * len(fields)
    if coalesce is None:
        coalesce = _default_coalesce()
    # Trace-time counter: begin/finish calls run while BUILDING a program
    # (the early-dispatch exchange shape), so this counts traced schedules,
    # not runtime executions (docs/observability.md).  The host span below
    # is trace-time too (tagged so a timeline reader cannot mistake it for
    # a runtime exchange).
    _telemetry.counter("halo.begin_slab_traces").inc()
    receiveds: list[dict] = [{} for _ in fields]
    pends: list[list] = [[] for _ in fields]
    with _tracing.trace_span(
        "igg_slab_exchange_begin", phase="trace", fields=len(fields)
    ), named_scope("igg_slab_exchange_begin"):
        for d in dims:
            vals = _multi_slab_recv_values(
                fields, d, gg, width, logicals, receiveds=receiveds,
                coalesce=coalesce,
            )
            for i, v in enumerate(vals):
                if v is None:
                    continue
                receiveds[i][d] = v
                pends[i].append((d, v[0], v[1]))
    return pends


def finish_slab_exchange(fields, pends, *, logicals=None):
    """Apply `begin_slab_exchange`'s received slabs to ``fields``.

    ``fields`` may be later arrays than the ones `begin_slab_exchange` saw
    (the pipelined schedule finishes on the combined boundary+interior
    output) as long as they hold the same owned values.  Returns the
    updated tuple.
    """
    from ..utils import telemetry as _telemetry
    from ..utils import tracing as _tracing
    from jax import named_scope

    if logicals is None:
        logicals = (None,) * len(fields)
    _telemetry.counter("halo.finish_slab_traces").inc()
    out = []
    with _tracing.trace_span(
        "igg_slab_exchange_finish", phase="trace", fields=len(fields)
    ), named_scope("igg_slab_exchange_finish"):
        for A, pend, logical in zip(fields, pends, logicals):
            shp = logical if logical is not None else tuple(A.shape)
            for d, lo, hi in pend:
                w = lo.shape[d]
                A = _set_plane(A, hi, shp[d] - w, d)
                A = _set_plane(A, lo, 0, d)
            out.append(A)
    return tuple(out)


@_zpatch_scope("from_export")
def z_patch_from_export(export, *, width: int):
    """The next group's packed z patch from a fused kernel's z-slab export.

    Export lane layout (see `ops.pallas_stencil.fused_diffusion_steps`
    ``z_export``): ``[0,w)`` = send-hi planes ``[n-o, n-o+w)``, ``[w,2w)``
    = send-lo planes ``[o-w, o)``, ``[2w,3w)``/``[3w,4w)`` = the current
    boundary planes (PROC_NULL keep-old values).  This is the z-dimension
    communication of `_slab_recv_values` performed on the packed 128-lane
    array instead of the full field — the kernel already did the
    extraction in VMEM, so no whole-array minor-dim relayout is paid.
    Must run AFTER the x/y exchanges of the export (sequential-dimension
    corner semantics ride the packed array).
    """
    gg = _grid.global_grid()
    w = width
    if _partner_self(gg, 2):
        # Lanes [0,2w) are already the patch (send-hi -> planes [0,w),
        # send-lo -> the top w planes) — the self-neighbor fast path.
        return export
    recv_lo, recv_hi = _permute_slabs(gg, 2, **_z_export_slabs(export, w))
    return _pack_recv_patch(recv_lo, recv_hi, w)


def _z_export_slabs(export, w: int) -> dict:
    """The send/keep slab kwargs of one packed z export's z communication
    (export lane layout: see `z_patch_from_export`)."""
    return dict(
        send_lo=export[:, :, w : 2 * w],
        send_hi=export[:, :, 0:w],
        keep_lo=lambda: export[:, :, 2 * w : 3 * w],
        keep_hi=lambda: export[:, :, 3 * w : 4 * w],
    )


def _pack_recv_patch(recv_lo, recv_hi, w: int):
    """Received z slabs -> the next group's 128-lane patch layout."""
    import jax.numpy as jnp

    packed = jnp.concatenate([recv_lo, recv_hi], axis=2)
    return jnp.pad(packed, ((0, 0), (0, 0), (0, 128 - 2 * w)))


#: Lane offset of the z-face band in the merged cell+z-face patch/export —
#: THE owner of the value (the kernels import it from here).  The cell
#: field (C/P/Pf) and its z-staggered face field (Az/Vz/qDz) share x/y
#: extents AND x/y slab indices (they stagger only in z), so one packed
#: array serves both at lane bands [0, 4w) and [Z_CZ_BAND, Z_CZ_BAND+4w):
#: one kernel window fetch and one export write instead of two (round 5).
Z_CZ_BAND = 64


def _pack_cz(cell_band, z_band):
    """Merge the cell and z-face 128-lane packed arrays into one: the cell
    lanes stay at [0, ...), the z-face lanes move to [Z_CZ_BAND, ...)."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [cell_band[:, :, :Z_CZ_BAND], z_band[:, :, : 128 - Z_CZ_BAND]], axis=2
    )


def fix_topface_z_exports(exports, C, Axp, Ayp, Azp, *, width: int):
    """Fill the frozen top-face slabs of the staggered kernels' z exports.

    The Vx row ``n0`` and Vy column ``n1`` (each field's real top face)
    sit outside every tile's owned block, so the kernels never write their
    export rows — fill them here from the output arrays (a one-row minor
    slice: ~n1*n2 elements, negligible next to the whole-array relayouts
    the export replaces).  Must run BEFORE the exports' x/y exchange: on
    x/y-active grids the exchange then overwrites the rows that belong to
    neighbors, exactly as it does for the fields themselves.
    """
    import jax.numpy as jnp
    from jax import lax

    gg = _grid.global_grid()
    n0, n1, n2 = C.shape
    w = width
    o = ol(2, shape=(n0, n1, n2), gg=gg)
    exp_cz, exp_x, exp_y = exports

    def packed_lanes(row):
        return jnp.concatenate(
            [
                row[..., n2 - o : n2 - o + w],
                row[..., o - w : o],
                row[..., 0:w],
                row[..., n2 - w : n2],
            ],
            axis=2,
        )

    exp_x = lax.dynamic_update_slice(
        exp_x, packed_lanes(Axp[n0 : n0 + 1]), (n0, 0, 0)
    )
    exp_y = lax.dynamic_update_slice(
        exp_y, packed_lanes(Ayp[:, n1 : n1 + 1]), (0, n1, 0)
    )
    return exp_cz, exp_x, exp_y


@_zpatch_scope("from_export")
def z_patches_from_exports(exports, C_shape, *, width: int, coalesce=None):
    """x/y-exchange the three packed z exports (real-shape slab indices via
    ``logical``) and turn each into the next group's patch — the multi-field
    z communication of the staggered z-slab cadence, all on packed arrays.

    The merged cell+z-face export's x/y slab indices are the CELL's (the
    z-face field staggers only in z); its z communication runs per lane
    band in the non-self case, and the self-partner fast path hands the
    whole merged array back untouched.

    Coalesced by default (``IGG_COALESCE``): the three exports' x/y hops
    combine into one permute pair per dimension, and the non-self z hops
    of all four lane bands (cell, z-face, x-face, y-face) pack into ONE
    pair — 2 collectives for the whole staggered family's z exchange
    instead of 8 (the residual VERDICT r5 names behind the porous
    periodic-z gap).
    """
    n0, n1, _ = C_shape
    w = width
    gg = _grid.global_grid()
    if coalesce is None:
        coalesce = _default_coalesce()

    exp_cz, exp_x, exp_y = exchange_dims_multi(
        exports, (0, 1), width=w,
        logicals=(None, (n0 + 1, n1, 128), (n0, n1 + 1, 128)),
        coalesce=coalesce,
    )
    if _partner_self(gg, 2):
        # Bands [L, L+2w) are already the patches (`z_patch_from_export`'s
        # self-partner fast path, applied to all three).
        return exp_cz, exp_x, exp_y
    bands = (
        exp_cz[:, :, :Z_CZ_BAND],
        exp_cz[:, :, Z_CZ_BAND : Z_CZ_BAND + 4 * w],
        exp_x,
        exp_y,
    )
    slabs = [_z_export_slabs(b, w) for b in bands]
    if coalesce:
        vals = _coalesced_permute(
            gg, 2,
            [(s["send_lo"], s["send_hi"], s["keep_lo"], s["keep_hi"])
             for s in slabs],
        )
    else:
        vals = [_permute_slabs(gg, 2, **s) for s in slabs]
    cell, zf, patch_x, patch_y = (
        _pack_recv_patch(lo, hi, w) for lo, hi in vals
    )
    return _pack_cz(cell, zf), patch_x, patch_y


def z_slab_patches(C, Axp, Ayp, Azp, *, width: int = 1, coalesce=None):
    """The z-dimension exchange of the four fields, as packed patch arrays.

    Returns ``(patch_CAz, patch_Ax, patch_Ay)`` (`_pack_z_patch` layout;
    the cell and z-face fields share the first array's lane bands, see
    `Z_CZ_BAND`; extents match each PADDED array's x/y extents so kernel
    tile windows slice them with the same aligned offsets), or ``None``
    when the z dimension exchanges nothing.  Must be called AFTER the x/y
    exchanges (sequential-dimension corner semantics).  The patches are
    consumed by the fused kernels, which apply them to their VMEM tiles
    where minor-dim plane surgery is free — instead of the whole-array
    relayouts a z-`dynamic-update-slice` costs at a kernel boundary.
    """
    gg = _grid.global_grid()
    logicals = _padded_logicals(C, Axp, Ayp, Azp)
    vals = _multi_slab_recv_values(
        (C, Axp, Ayp, Azp), 2, gg, width, logicals,
        coalesce=_default_coalesce() if coalesce is None else coalesce,
    )
    if any(v is None for v in vals):
        return None  # all-or-nothing: z activity is per-grid, not per-field
    packed = [_pack_z_patch(*v, width) for v in vals]
    return (_pack_cz(packed[0], packed[3]), packed[1], packed[2])


@_zpatch_scope("identity")
def identity_z_patches(C, Axp, Ayp, Azp, *, width: int = 1):
    """Patches that re-write the CURRENT z-halo planes (a no-op application).

    The chunk-entry state has fresh halos (the models' chunk-boundary
    invariant), so the first fused group's patches are the planes already
    in place."""
    logicals = _padded_logicals(C, Axp, Ayp, Azp)
    packed = []
    for A, logical in zip((C, Axp, Ayp, Azp), logicals):
        n = (logical or tuple(A.shape))[2]
        lo = _get_plane(A, 0, 2, width)
        hi = _get_plane(A, n - width, 2, width)
        packed.append(_pack_z_patch(lo, hi, width))
    return (_pack_cz(packed[0], packed[3]), packed[1], packed[2])


@_zpatch_scope("apply")
def apply_z_patches(C, Axp, Ayp, Azp, patches, *, width: int = 1):
    """Write packed z patches into the arrays (the chunk-end restoration).

    One whole-array `dynamic-update-slice` pass per field — paid once per
    CHUNK (the in-kernel application covers every group in between), so the
    relayout cost amortizes over ``nsteps``."""
    w = width
    patch_cz, patch_x, patch_y = patches
    per_field = (
        patch_cz,
        patch_x,
        patch_y,
        patch_cz[:, :, Z_CZ_BAND : Z_CZ_BAND + 2 * w],
    )
    logicals = _padded_logicals(C, Axp, Ayp, Azp)
    out = []
    for A, logical, patch in zip((C, Axp, Ayp, Azp), logicals, per_field):
        n = (logical or tuple(A.shape))[2]
        A = _set_plane(A, patch[:, :, :w], 0, 2)
        A = _set_plane(A, patch[:, :, w : 2 * w], n - w, 2)
        out.append(A)
    return tuple(out)


def update_halo_padded_faces(C, Axp, Ayp, Azp, *, width: int = 1, dims=None,
                             coalesce=None):
    """Slab-exchange a cell field + three `pad_faces`-layout staggered fields.

    The models' fused deep-halo cadences keep the staggered fields in the
    kernel's padded layout across a whole chunk; exchanging them directly
    (with slab indices computed from the REAL ``n+1`` shapes via the
    ``logical`` override of `_exchange_dim`) removes the two HBM passes per
    field per group an unpad/re-pad pair would cost.  Owned results are
    bitwise identical to unpad→`update_halo`→pad: the same real planes
    move; only the junk tail differs (it receives exchanged junk instead of
    zeros, and the layout's contract already forbids reading it).

    ``dims``: restrict the exchange to these dimensions (default all) — the
    z-patch cadence exchanges x/y here and routes z through `z_slab_patches`
    into the kernel.  ``coalesce``: the four fields' collectives combine
    into one permute pair per (dimension, dtype width) by default
    (`exchange_dims_multi`; ``IGG_COALESCE=0`` restores per-field hops).

    Tracer-context only (inside `stencil`/shard_map — where the fused block
    steps live); the public `update_halo` remains the global-array entry.
    """
    logicals = _padded_logicals(C, Axp, Ayp, Azp)
    return exchange_dims_multi(
        (C, Axp, Ayp, Azp),
        tuple(range(NDIMS)) if dims is None else dims,
        width=width,
        logicals=logicals,
        coalesce=coalesce,
    )


def _exchange_slab_bytes(fields, gg, width: int) -> int:
    """Per-call slab traffic of a global-array exchange, in bytes.

    For every field and every dimension that actually exchanges, two
    ``width``-deep slabs (one per side) are written into the halo planes —
    ``2 * width * plane_bytes`` per field per active dim.  Host-side shape
    math only (no device work); self-copies count (they move the same
    bytes), PROC_NULL keep-old planes of edge blocks are included (the
    per-block census is not knowable host-side without extra collectives),
    so this is the upper-bound slab payload the program was built to move.
    """
    total = 0
    for A in fields:
        shp = local_shape(A, gg)
        itemsize = np.dtype(A.dtype).itemsize
        n = int(np.prod(shp))
        for d in range(min(len(shp), NDIMS)):
            if not dim_has_halo_activity(gg, d):
                continue
            if ol(d, shape=shp, gg=gg) < 2:
                continue
            total += 2 * width * (n // shp[d]) * itemsize
    return total


def _default_donate() -> bool:
    """``IGG_DONATE`` env default for `update_halo`'s global-array entry.

    Donation makes the exchange buffer-in-place like the reference's mutating
    API (no extra allocation) and is the right default on production
    runtimes; a runtime that pays a runtime-side penalty for donated
    buffers can turn it off globally with ``IGG_DONATE=0``; the per-call
    ``donate=`` kwarg overrides both.
    """
    from ..utils.config import _int_env

    val = _int_env("IGG_DONATE")
    return True if val is None else val > 0


def _global_update_fn(gg, shapes_dtypes, width: int = 1, donate: bool = True,
                      coalesce: bool = True):
    """Build (and cache) the jitted shard_map wrapper for one field signature."""
    import jax
    from jax.sharding import PartitionSpec as P

    key = (gg.epoch, shapes_dtypes, width, donate, coalesce)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    ndims_per_field = tuple(len(s) for s, _ in shapes_dtypes)
    dn = tuple(range(len(ndims_per_field))) if donate else ()

    def exchange(*fields):
        return _update_halo_local(fields, gg, width, coalesce)

    if gg.nprocs == 1 and not gg.force_spmd:
        # 1-device grid: only self-neighbor local copies remain (no ppermute,
        # no axis environment) — plain jit avoids the SPMD execution path.
        fn = jax.jit(exchange, donate_argnums=dn)
        _jit_cache[key] = fn
        return fn

    from jax import shard_map

    specs = tuple(P(*AXIS_NAMES[:nd]) for nd in ndims_per_field)
    mapped = shard_map(
        exchange, mesh=gg.mesh, in_specs=specs, out_specs=specs, check_vma=False
    )
    fn = jax.jit(mapped, donate_argnums=dn)
    _jit_cache[key] = fn
    return fn


def _integrity_update_fn(gg, shapes_dtypes, width: int, donate: bool,
                         coalesce: bool, flip: int | None):
    """The checksummed twin of `_global_update_fn` (``IGG_INTEGRITY=1``).

    The exchange builds under an active `TransportCollector`, so every hop's
    wire buffer carries an XOR-fold checksum word (`_packed_transport`) and
    the per-hop mismatch flags escape as one extra per-block ``(1, 1, 1,
    nhops, 2)`` int32 output, out-spec sharded over the mesh — the host
    entry reads its OWN blocks' verdicts from addressable shards, no extra
    collective.  Cached per (epoch, signature, width, donate, coalesce,
    flip): an armed transport flip bakes a DIFFERENT program, so a chaos
    injection never poisons the clean entry.  Returns ``(fn, collector)``;
    the collector's trace-order records label the flag rows.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from ..integrity import transport as _itransport
    from jax import shard_map

    key = (gg.epoch, shapes_dtypes, width, donate, coalesce, flip)
    hit = _integrity_jit_cache.get(key)
    if hit is not None:
        return hit
    ndims_per_field = tuple(len(s) for s, _ in shapes_dtypes)
    dn = tuple(range(len(ndims_per_field))) if donate else ()
    col = _itransport.TransportCollector()

    def exchange(*fields):
        # A retrace rebuilds the records/flags and re-arms the baked flip
        # (trace-time collector state must match the program every time).
        col.records.clear()
        col.flags.clear()
        col.flip_proc = flip
        with _itransport.use_collector(col):
            out = _update_halo_local(fields, gg, width, coalesce)
        return tuple(out) + (col.stacked_flags()[None, None, None],)

    specs = tuple(P(*AXIS_NAMES[:nd]) for nd in ndims_per_field)
    mapped = shard_map(
        exchange, mesh=gg.mesh, in_specs=specs,
        out_specs=specs + (P(*AXIS_NAMES, None, None),), check_vma=False,
    )
    fn = jax.jit(mapped, donate_argnums=dn)
    _integrity_jit_cache[key] = (fn, col)
    return fn, col


def _check_transport_flags(gg, col, flags) -> None:
    """Rank-local verdict of one checksummed exchange.

    Scans the flag blocks THIS process hosts; any nonzero entry names a hop
    whose landed payload contradicts its checksum word.  Escalation is a
    LOCAL raise plus the out-of-band ``reason=sdc`` flight bundle
    implicating the SENDER (the wire buffer is the sender's slab until it
    lands, so a mismatch at the receiver indicts the sending rank/link) —
    never a collective: a rank-local integrity verdict driving a collective
    is the SPMD-divergence class `analysis.collectives` exists to catch.
    """
    from ..integrity.errors import IntegrityError
    from ..parallel import topology
    from ..utils import telemetry as _telemetry
    from ..utils import tracing as _tracing

    for shard in flags.addressable_shards:
        arr = np.asarray(shard.data)
        if not arr.size or not arr.any():
            continue
        coords = tuple(
            int(sl.start or 0) for sl in tuple(shard.index)[:NDIMS]
        )
        nbrs = topology.neighbors_table(
            coords, gg.dims, gg.periods, int(gg.disp)
        )
        hop, side = (
            int(i) for i in np.argwhere(arr.reshape(arr.shape[-2:]))[0]
        )
        rec = col.records[hop] if hop < len(col.records) else {}
        dim = int(rec.get("dim", -1))
        # flag column 0 = the lo receive (sent by my LOWER partner), column
        # 1 = the hi receive (sent by my upper partner) — `_permute_slabs`
        direction = "lo" if side == 0 else "hi"
        sender = int(nbrs[side, dim]) if dim >= 0 else -1
        fields = tuple(rec.get("fields", ()))
        _telemetry.counter("integrity.transport_mismatches").inc()
        _telemetry.event(
            "integrity.transport_mismatch", detector="transport_checksum",
            dim=dim, direction=direction, fields=list(fields),
            block=list(coords), implicated_rank=sender,
        )
        _tracing.dump_flight_recorder(
            "sdc", detector="transport_checksum", implicated_rank=sender,
            dim=dim, direction=direction, fields=list(fields),
            block=list(coords),
        )
        raise IntegrityError(
            f"halo transport checksum mismatch: dim {dim} ({direction} "
            f"receive) at block {coords} — the landed payload contradicts "
            f"its checksum word; implicating sender rank {sender}. A finite "
            f"bit flip in flight passes every NaN guard; quarantine the "
            f"implicated device (docs/robustness.md), do not restart in "
            f"place.",
            detector="transport_checksum", implicated_rank=sender,
            dim=dim, direction=direction, fields=fields,
        )


def update_halo(*fields, width: int = 1, donate: bool | None = None,
                coalesce: bool | None = None):
    """Update the halo planes of the given field(s).

    TPU-native counterpart of `update_halo!` (`/root/reference/src/update_halo.jl:25-78`).
    Functional: returns the updated field(s) — a single array for one argument,
    a tuple for several.  Pass all fields of a time step in one call so XLA
    compiles one fused program (the reference's pipelining advice,
    `/root/reference/src/update_halo.jl:13-14`) — and so their collectives
    COALESCE: by default every field's send slab packs into one flat buffer
    per dtype byte width and each exchanged dimension issues ONE
    `collective-permute` pair per width group instead of one per field
    (message combining; bit-identical — the transport bitcasts to same-width
    unsigned ints, like the chunked gather).  ``coalesce=False`` (or
    ``IGG_COALESCE=0``) restores per-field collectives; ``coalesce=None``
    takes the env default (auto: combine whenever >= 2 fields share a
    dimension's exchange).

    ``width``: halo planes refreshed per side (default 1 = the reference's
    exchange).  ``width=w`` on a deep-halo grid (``overlap >= 2w``) refreshes
    ``w`` planes in one collective, licensing ``w`` stencil steps between
    exchanges (temporal blocking, `make_multi_step(fused_k=w)`): the
    per-hop latency of the exchange amortizes over ``w`` steps.

    ``donate`` (global-array calls only): donate the inputs so the update is
    buffer-in-place like the reference's mutating API.  Default from the
    ``IGG_DONATE`` env var, else True; pass ``donate=False`` (or set
    ``IGG_DONATE=0``) on runtimes where donation is slow, or when the
    caller reuses the passed-in arrays.  Inside a traced context the
    flag is ignored: buffer lifetime belongs to the enclosing program.
    """
    import jax

    _grid.check_initialized()
    gg = _grid.global_grid()
    if not fields:
        raise ValueError("update_halo requires at least one field.")
    if width < 1:
        raise ValueError(f"width must be >= 1 (got {width})")
    _validate_fields(fields, gg)
    if any(_is_tracer(A) for A in fields):
        if not all(_is_tracer(A) for A in fields):
            # A concrete global-block array mixed into a traced (local-view)
            # call would be exchanged at global indices — always a bug.
            raise ValueError(
                "update_halo inside a stencil/shard_map context requires all "
                "fields to be local-block tracers; pass captured global-block "
                "fields as arguments of the stencil function instead."
            )
        out = _update_halo_local(tuple(fields), gg, width, coalesce)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        arrs = []
        for A in fields:
            if not isinstance(A, jax.Array):
                spec = P(*AXIS_NAMES[: np.ndim(A)])
                A = jax.device_put(np.asarray(A), NamedSharding(gg.mesh, spec))
            arrs.append(A)
        sig = tuple((local_shape(A, gg), str(A.dtype)) for A in arrs)
        if donate is None:
            donate = _default_donate()
        if coalesce is None:
            coalesce = _default_coalesce()
        from ..utils import config as _config
        from ..utils import telemetry as _telemetry
        from ..utils import tracing as _tracing

        # Transport checksums (docs/robustness.md): host-side resolution,
        # like IGG_DONATE/IGG_COALESCE — the traced paths never read the
        # env (knob-binding lint).  Only communicating grids have a wire
        # to checksum.
        integrity = (
            _config.integrity_enabled_env() is True
            and (gg.nprocs > 1 or gg.force_spmd)
        )
        flip = _take_transport_flip() if integrity else None

        if _telemetry.enabled():
            # Runtime counters (the global-array entry runs host-side per
            # call, unlike the traced paths — docs/observability.md).
            nbytes = _exchange_slab_bytes(arrs, gg, width)
            _telemetry.counter("halo.exchanges").inc()
            _telemetry.counter("halo.fields").inc(len(arrs))
            _telemetry.counter("halo.bytes").inc(nbytes)
            _telemetry.histogram("halo.slab_bytes").record(nbytes)
        # Host span named like the device-side annotation
        # (`named_scope("igg_halo_exchange")` inside the compiled program),
        # so the merged trace and a profiler capture correlate by name.
        with _tracing.trace_span(
            "igg_halo_exchange", fields=len(arrs), width=width
        ):
            if integrity:
                fn, col = _integrity_update_fn(
                    gg, sig, width, bool(donate), bool(coalesce), flip
                )
                *out, flags = fn(*arrs)
                _check_transport_flags(gg, col, flags)
            else:
                out = _global_update_fn(
                    gg, sig, width, bool(donate), bool(coalesce)
                )(*arrs)
        if _post_exchange_hook is not None:
            out = tuple(_post_exchange_hook(tuple(out)))
    return out[0] if len(fields) == 1 else tuple(out)
