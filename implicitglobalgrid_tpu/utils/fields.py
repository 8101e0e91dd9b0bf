"""Field constructors and block-wise helpers for global-block arrays.

The reference's users allocate plain per-process arrays (`zeros(nx,ny,nz)`);
here a field is one `jax.Array` whose global shape is ``dims * local_shape``
with one block per device (`NamedSharding` over the grid mesh).  These
constructors are the supported way to create fields — they guarantee the
sharding that `update_halo`/`gather` expect.

`coord_fields` replaces the reference's per-element comprehension idiom for
initial conditions (`/root/reference/examples/diffusion3D_multigpu_CuArrays_novis.jl:34-37`):
it returns global-block coordinate arrays (computed per block on device with
`lax.axis_index`, never materializing the global grid on host) so ICs are
plain vectorized jnp expressions.
"""

from __future__ import annotations

import numpy as np

from ..parallel import grid as _grid
from ..parallel.topology import AXIS_NAMES


def _sharding(ndim: int, gg):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(gg.mesh, P(*AXIS_NAMES[:ndim]))


def _global_shape(local_shape, gg) -> tuple[int, ...]:
    return tuple(gg.dims[d] * int(s) for d, s in enumerate(local_shape))


def zeros(local_shape, dtype=None):
    """A zero field with per-block shape ``local_shape`` (1-, 2- or 3-D).

    Defaults to the floating dtype (like ``jnp.zeros``), not the int dtype
    ``jnp.full(shape, 0)`` would infer.
    """
    import jax

    if dtype is None:
        dtype = jax.dtypes.canonicalize_dtype(float)
    return full(local_shape, 0, dtype)


def ones(local_shape, dtype=None):
    import jax

    if dtype is None:
        dtype = jax.dtypes.canonicalize_dtype(float)
    return full(local_shape, 1, dtype)


def full(local_shape, fill_value, dtype=None):
    import jax
    import jax.numpy as jnp

    _grid.check_initialized()
    gg = _grid.global_grid()
    local_shape = (local_shape,) if np.ndim(local_shape) == 0 else tuple(local_shape)
    shape = _global_shape(local_shape, gg)
    if gg.nprocs == 1 and not gg.force_spmd:
        # Degenerate 1-device grid: a mesh sharding is semantically inert but
        # routes later computations through the SPMD executable path (slower
        # on some runtimes) — commit to the grid's device without it
        # (measured equal to plain placement, and it honors a non-default
        # ``devices=[...]`` choice).
        from jax.sharding import SingleDeviceSharding

        return jax.jit(
            lambda: jnp.full(shape, fill_value, dtype=dtype),
            out_shardings=SingleDeviceSharding(gg.mesh.devices.flat[0]),
        )()
    sharding = _sharding(len(shape), gg)
    return jax.jit(
        lambda: jnp.full(shape, fill_value, dtype=dtype), out_shardings=sharding
    )()


def from_block_fn(fn, local_shape, dtype=None):
    """Build a field by evaluating ``fn(coords) -> block`` on every device.

    ``fn`` receives the block's Cartesian coordinates ``(cx, cy, cz)`` as
    traced scalars and must return an array of shape ``local_shape``.  This is
    the device-side analogue of the reference's "fill the local array from
    global coordinates" initialization pattern.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    _grid.check_initialized()
    gg = _grid.global_grid()
    local_shape = tuple(local_shape)
    nd = len(local_shape)

    def per_block():
        coords = tuple(
            lax.axis_index(AXIS_NAMES[d]) if gg.dims[d] > 1 else jnp.int32(0)
            for d in range(3)
        )
        out = jnp.asarray(fn(coords), dtype=dtype)
        if out.shape != local_shape:
            raise ValueError(
                f"from_block_fn: fn returned shape {out.shape}, expected {local_shape}."
            )
        return out

    if gg.nprocs == 1 and not gg.force_spmd:
        # All dims are 1, so no axis_index is ever taken: no shard_map, but
        # still commit to the grid's device (see full()).
        from jax.sharding import SingleDeviceSharding

        return jax.jit(
            per_block, out_shardings=SingleDeviceSharding(gg.mesh.devices.flat[0])
        )()

    from jax import shard_map

    mapped = shard_map(
        per_block,
        mesh=gg.mesh,
        in_specs=(),
        out_specs=P(*AXIS_NAMES[:nd]),
        check_vma=False,
    )
    return jax.jit(mapped)()


def coord_fields(A, spacings, dtype=None):
    """Global-coordinate arrays matching field ``A``'s shape.

    Returns one array per dimension of ``A`` — e.g. ``XG, YG, ZG =
    coord_fields(T, (dx, dy, dz))`` with each of ``XG[i,j,k] = x_g(i, dx, T)``
    etc., broadcast to ``A``'s global-block shape.  Staggering offsets and
    periodic wrap-around follow `x_g` exactly.
    """
    import jax.numpy as jnp

    from ..ops.halo import local_shape as _lshape
    from . import tools

    _grid.check_initialized()
    gg = _grid.global_grid()
    shp = _lshape(A, gg)
    nd = len(shp)
    spacings = (spacings,) * nd if np.ndim(spacings) == 0 else tuple(spacings)
    coord_g = (tools.x_g, tools.y_g, tools.z_g)

    outs = []
    for dim in range(nd):
        def make(dim=dim):
            def fn(coords):
                vec = coord_g[dim](
                    jnp.arange(shp[dim]), spacings[dim], A, coords=coords
                )
                bshape = [1] * nd
                bshape[dim] = shp[dim]
                return jnp.broadcast_to(vec.reshape(bshape), shp)

            return fn

        outs.append(from_block_fn(make(), shp, dtype=dtype))
    return tuple(outs)


def block_slice(A, slices):
    """Slice every local block of ``A`` (not the global array) with ``slices``.

    ``block_slice(T, (slice(1,-1),)*3)`` returns the per-block interior as a
    new global-block field — the idiom the reference uses before `gather!`
    (`/root/reference/examples/diffusion3D_multigpu_CuArrays.jl:53-54`, where
    the halo is stripped locally before gathering).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    _grid.check_initialized()
    gg = _grid.global_grid()
    from ..ops.halo import local_shape as _lshape

    _lshape(A, gg)  # validates divisibility
    nd = A.ndim
    slices = (slices,) if isinstance(slices, slice) else tuple(slices)

    def per_block(a):
        out = a[slices]
        if out.ndim != nd:
            raise ValueError("block_slice: slices must preserve the number of dimensions.")
        return out

    if gg.nprocs == 1 and not gg.force_spmd:
        from jax.sharding import SingleDeviceSharding

        return jax.jit(
            per_block, out_shardings=SingleDeviceSharding(gg.mesh.devices.flat[0])
        )(A)

    from jax import shard_map

    spec = P(*AXIS_NAMES[:nd])
    mapped = shard_map(
        per_block, mesh=gg.mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )
    return jax.jit(mapped)(A)
