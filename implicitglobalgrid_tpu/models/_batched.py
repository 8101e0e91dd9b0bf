"""Batched (ensemble) execution helpers — B simulations for the price of 1.

The north star is millions of users each running a small-to-medium
*independent* simulation; per-chip throughput for that workload comes from
batching B members into one program.  The mechanism is deliberately thin:
every model's per-block step is a pure function of local-block fields, so a
leading ensemble axis is just ``jax.vmap`` over it — and the collective
structure is *provably* invariant in B, because the batching rule of
`lax.ppermute` carries the batch dimension inside the SAME collective (one
fatter hop, not B hops).  The coalesced multi-field packer (`ops.halo`)
composes with this for free: under vmap its flatten/concatenate operate on
the per-member view, so the packed buffer simply grows a batch axis and the
one-permute-pair-per-(dimension, width group) budget holds at any B.  The
``collective-budget`` analyzer pins this as a static invariant
(`analysis.budget.batched_budget_findings`), and the compiled-HLO census
cross-checks it (``bench.py batch``).

Layout: a batched field is ``(B, *local_block)`` per device — global shape
``(B, dims[0]*nx, dims[1]*ny, dims[2]*nz)`` sharded ``P(None, 'x', 'y',
'z')`` (the ensemble axis is replicated-rank: every device holds all B
members of ITS block).  Members are independent problems on the SAME grid
topology; per-member physics parameters stay per-member fields (each member
carries its own Cp/state), scalar `Params` are shared.

Bit-exactness contract: a batched step is bit-identical, member for member,
to B independent unbatched steps (vmap of pure array code plus the batched
collectives moves exactly the per-member values; pinned across the oracle
matrix in ``tests/test_batched_serving.py`` and across a real 2-process
boundary in ``tests/_distributed_worker.py``).
"""

from __future__ import annotations

import numpy as np

from ..parallel import grid as _grid
from ..parallel.topology import AXIS_NAMES

_jit_cache: dict = {}


def _clear_caches() -> None:
    _jit_cache.clear()


def batch_size(state) -> int:
    """The ensemble size B of a batched state tuple (leading-axis extent)."""
    leaf = state[0] if isinstance(state, (tuple, list)) else state
    return int(np.shape(leaf)[0])


def _batched_spec(ndim: int):
    """PartitionSpec of one batched field: replicated ensemble axis, block-
    sharded grid axes (``P(None, 'x', 'y', 'z')`` for the usual 1+3 rank)."""
    from jax.sharding import PartitionSpec as P

    return P(None, *AXIS_NAMES[: ndim - 1])


def batched_stencil(block_step, nfields: int, *, donate_argnums=()):
    """`igg.stencil` for a vmapped per-block step over ``nfields`` batched
    fields.

    The single-member ``block_step`` is vmapped over the leading ensemble
    axis and wrapped with EXPLICIT specs (`_batched_spec`): the stencil
    heuristic maps array axis ``d`` to grid axis ``d`` and would shard the
    ensemble axis over ``'x'``.  Donation semantics match the unbatched
    wrapper.
    """
    import jax

    from ..ops.stencil import stencil

    specs = (_batched_spec(4),) * nfields
    return stencil(
        jax.vmap(block_step),
        in_specs=specs,
        out_specs=specs,
        donate_argnums=donate_argnums,
    )


def _stack_fn(gg, ndims: tuple[int, ...]):
    """Jitted shard_map stacking per-member global-block fields into one
    batched field — local per-device stacking, no host transfer (multi-host
    safe: each process stacks only its own shards)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("stack", gg.epoch, tuple(ndims))
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(lambda *fs: jnp.stack(fs))
        _jit_cache[key] = fn
        return fn
    nd = ndims[0]
    mapped = shard_map(
        lambda *fs: jnp.stack(fs),
        mesh=gg.mesh,
        in_specs=(P(*AXIS_NAMES[:nd]),) * len(ndims),
        out_specs=_batched_spec(nd + 1),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _jit_cache[key] = fn
    return fn


def stack_fields(*fields):
    """Stack B same-shaped global-block fields into one batched field
    ``(B, ...)`` (device-side; the inverse of `member_field`)."""
    _grid.check_initialized()
    gg = _grid.global_grid()
    if not fields:
        raise ValueError("stack_fields requires at least one field.")
    ndims = tuple(np.ndim(f) for f in fields)
    if len(set(ndims)) != 1:
        raise ValueError(f"stack_fields: mixed ranks {ndims}")
    return _stack_fn(gg, ndims)(*fields)


def stack_states(states):
    """Stack B state tuples (one per member) into one batched state tuple."""
    states = [tuple(s) for s in states]
    nf = len(states[0])
    if any(len(s) != nf for s in states):
        raise ValueError("stack_states: members have different field counts")
    return tuple(
        stack_fields(*(s[i] for s in states)) for i in range(nf)
    )


def _member_fn(gg, ndim: int):
    """Jitted shard_map slicing member ``k`` out of a batched field.  ``k``
    is a traced operand, so every member shares one executable."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("member", gg.epoch, ndim)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn

    def take(A, k):
        return lax.dynamic_index_in_dim(A, k, 0, keepdims=False)

    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(take)
        _jit_cache[key] = fn
        return fn
    mapped = shard_map(
        take,
        mesh=gg.mesh,
        in_specs=(_batched_spec(ndim), P()),
        out_specs=P(*AXIS_NAMES[: ndim - 1]),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _jit_cache[key] = fn
    return fn


def member_field(A, k: int):
    """Member ``k``'s global-block field out of a batched field — a device
    slice, never materializing the other members anywhere new."""
    import jax.numpy as jnp

    _grid.check_initialized()
    gg = _grid.global_grid()
    if np.ndim(A) < 2:
        raise ValueError(f"member_field needs a batched field, got rank {np.ndim(A)}")
    return _member_fn(gg, np.ndim(A))(A, jnp.int32(k))


def member_state(state, k: int):
    """Member ``k``'s state tuple out of a batched state tuple."""
    return tuple(member_field(A, k) for A in state)


def _set_member_fn(gg, ndim: int):
    """Jitted shard_map writing one member's fields into a batched field at
    slot ``k`` (the serving loop's admit/rollback primitive)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("set_member", gg.epoch, ndim)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn

    def put(B, A, k):
        return lax.dynamic_update_index_in_dim(B, A.astype(B.dtype), k, 0)

    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(put, donate_argnums=(0,))
        _jit_cache[key] = fn
        return fn
    mapped = shard_map(
        put,
        mesh=gg.mesh,
        in_specs=(_batched_spec(ndim), P(*AXIS_NAMES[: ndim - 1]), P()),
        out_specs=_batched_spec(ndim),
        check_vma=False,
    )
    fn = jax.jit(mapped, donate_argnums=(0,))
    _jit_cache[key] = fn
    return fn


def set_member_state(batched, state, k: int):
    """Write single-member ``state`` into slot ``k`` of ``batched`` (donating
    the old batched buffers — the slot pool's in-place admit)."""
    import jax.numpy as jnp

    _grid.check_initialized()
    gg = _grid.global_grid()
    kk = jnp.int32(k)
    return tuple(
        _set_member_fn(gg, np.ndim(B))(B, A, kk)
        for B, A in zip(batched, state)
    )


def _member_finite_fn(gg, sig):
    """Jitted per-member finite probe over a batched state: one ``(B,)``
    int32 flag vector, 1 where the member holds any non-finite value in any
    field — replicated across devices/processes (psum over the mesh), so
    every rank takes the same serving decision for member k and only member
    k (the batched sibling of `utils.resilience.check_fields`)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("finite", gg.epoch, sig)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn

    def flags(*fields):
        bad = None
        for A in fields:
            if jnp.issubdtype(A.dtype, jnp.inexact):
                f = jnp.any(
                    ~jnp.isfinite(A), axis=tuple(range(1, A.ndim))
                ).astype(jnp.int32)
            else:
                f = jnp.zeros((A.shape[0],), jnp.int32)
            bad = f if bad is None else jnp.maximum(bad, f)
        return bad

    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(flags)
        _jit_cache[key] = fn
        return fn

    def per_block(*fields):
        return lax.psum(flags(*fields), AXIS_NAMES)

    mapped = shard_map(
        per_block,
        mesh=gg.mesh,
        in_specs=tuple(_batched_spec(len(s) + 1) for s, _ in sig),
        out_specs=P(),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _jit_cache[key] = fn
    return fn


def _batched_local_shape(A, gg) -> tuple[int, ...]:
    """Per-block shape of a batched field's GRID axes (the leading ensemble
    axis is replicated, never divided by the mesh; `ops.halo.local_shape`
    only knows grid-rank fields)."""
    shp = np.shape(A)
    out = []
    for d, s in enumerate(shp[1:]):
        nd = gg.dims[d] if d < len(gg.dims) else 1
        q, m = divmod(s, nd)
        if m != 0:
            raise ValueError(
                f"batched field with global shape {tuple(shp)} is not "
                f"divisible into {gg.dims} blocks along grid dimension {d}."
            )
        out.append(q)
    return tuple(out)


def check_members_finite(state) -> np.ndarray:
    """Per-member NaN/Inf probe of a batched state: boolean ``(B,)`` array,
    True where the member is bad.  One compiled all-reduce — member k's
    fault never taints the verdict on member j."""
    _grid.check_initialized()
    gg = _grid.global_grid()
    sig = tuple(
        (_batched_local_shape(A, gg), str(A.dtype)) for A in state
    )
    flags = np.asarray(_member_finite_fn(gg, sig)(*state))
    return flags > 0


def _select_fn(gg, sig):
    """Jitted per-member select: ``where(mask[b], new[b], old[b])`` per
    field — the serving loop's convergence/idle masking (a masked member's
    state is BIT-frozen, not merely numerically close).  Donates both state
    tuples (the loop keeps only the result)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("select", gg.epoch, sig)
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    n = len(sig)

    def sel(mask, *fields):
        news, olds = fields[:n], fields[n:]
        return tuple(
            jnp.where(mask.reshape((-1,) + (1,) * (N.ndim - 1)), N, O)
            for N, O in zip(news, olds)
        )

    dn = tuple(range(1, 2 * n + 1))
    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(sel, donate_argnums=dn)
        _jit_cache[key] = fn
        return fn
    specs = tuple(_batched_spec(len(s) + 1) for s, _ in sig)
    mapped = shard_map(
        sel,
        mesh=gg.mesh,
        in_specs=(P(),) + specs + specs,
        out_specs=specs,
        check_vma=False,
    )
    fn = jax.jit(mapped, donate_argnums=dn)
    _jit_cache[key] = fn
    return fn


def select_members(mask, new_state, old_state):
    """Per-member select over batched state tuples: member ``b`` takes
    ``new_state`` where ``mask[b]`` is True, else keeps ``old_state``
    bit-for-bit.  ``mask`` is a length-B boolean array (host or device).
    Donates both inputs."""
    import jax.numpy as jnp

    _grid.check_initialized()
    gg = _grid.global_grid()
    sig = tuple(
        (_batched_local_shape(A, gg), str(A.dtype)) for A in new_state
    )
    m = jnp.asarray(np.asarray(mask), jnp.bool_)
    return _select_fn(gg, sig)(m, *new_state, *old_state)


def batched_setup(model, nx: int, ny: int, nz: int, *, batch: int,
                  ic_scales=None, init_grid: bool = True, **kw):
    """Grid + B-member batched initial state for one model module.

    ``model`` is one of `models.diffusion3d` / `acoustic3d` /
    `porous_convection3d` (any module with ``setup(..., ic_scale=...)``).
    Member ``b`` gets the model's standard initial condition with its
    perturbation scaled by ``ic_scales[b]`` (default ``1 + b/(8*batch)`` —
    distinct members, same smooth physics), so a batched run is directly
    comparable to B independent runs of ``setup(..., ic_scale=s_b)``.
    Returns ``(batched_state, params)``; `Params` are shared (same grid,
    same dt) by construction.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1 (got {batch})")
    if ic_scales is None:
        ic_scales = [1.0 + b / (8.0 * batch) for b in range(batch)]
    if len(ic_scales) != batch:
        raise ValueError(
            f"ic_scales has {len(ic_scales)} entries for batch={batch}"
        )
    states = []
    params = None
    for b, scale in enumerate(ic_scales):
        state, params = model.setup(
            nx, ny, nz,
            ic_scale=float(scale),
            init_grid=(init_grid and b == 0),
            **kw,
        )
        states.append(state)
    return stack_states(states), params
