"""`stencil` — run single-device solver code on every block of the grid.

This is the TPU carrier of the reference's core promise: a solver written for
one local array becomes a distributed one.  In the reference that works
because each MPI process executes the same Julia code on its local array; here
the same effect is `jax.shard_map` over the grid mesh — the decorated function
is traced once with *local block* arguments and compiled SPMD across the
slice, and `update_halo` calls inside it inline into the same XLA program
(fusing communication with compute).

Field arguments (arrays whose per-dimension sizes are divisible by the mesh
``dims``) are sharded one block per device; anything else is replicated.
Override with explicit ``in_specs``/``out_specs`` when the heuristic is wrong
(e.g. a parameter vector whose length happens to be divisible by ``dims[0]``).
"""

from __future__ import annotations

import weakref
from typing import Any

import numpy as np

from ..parallel import grid as _grid
from ..parallel.topology import AXIS_NAMES

# Live stencil objects, so finalize_global_grid can evict their compiled
# executables (each pins the old mesh and program memory).
_instances: "weakref.WeakSet[_Stencil]" = weakref.WeakSet()


def _clear_caches() -> None:
    for s in list(_instances):
        s._cache.clear()


def _infer_spec(leaf, gg):
    from jax.sharding import PartitionSpec as P

    ndim = np.ndim(leaf)
    if ndim == 0:
        return P()
    shape = np.shape(leaf)
    if all(shape[d] % gg.dims[d] == 0 and shape[d] > 0 for d in range(min(ndim, 3))):
        return P(*AXIS_NAMES[:ndim])
    return P()


def stencil(fn=None, *, in_specs=None, out_specs=None, donate_argnums=()):
    """Decorate a per-block step function; returns a jit-compiled SPMD callable.

    Example::

        @igg.stencil
        def step(T, Cp):          # T, Cp are the LOCAL (nx,ny,nz) blocks here
            ...
            T = igg.update_halo(T)
            return T

        T = step(T, Cp)           # called with global-block fields
    """
    if fn is None:
        return lambda f: stencil(
            f, in_specs=in_specs, out_specs=out_specs, donate_argnums=donate_argnums
        )
    return _Stencil(fn, in_specs, out_specs, donate_argnums)


class _Stencil:
    def __init__(self, fn, in_specs, out_specs, donate_argnums):
        self._fn = fn
        self._in_specs = in_specs
        self._out_specs = out_specs
        self._donate = tuple(donate_argnums) if donate_argnums else ()
        self._cache: dict[Any, Any] = {}
        self.__wrapped__ = fn
        self.__doc__ = fn.__doc__
        _instances.add(self)

    def __call__(self, *args):
        return self._jitted(args)(*args)

    def lower(self, *args):
        """Ahead-of-time lowering for ``args`` (arrays or shape structs), as
        `jax.jit(...).lower`: the program a call with these arguments runs.
        ``.compile()`` on the result also fills the persistent compile cache
        that such a call reads."""
        return self._jitted(args).lower(*args)

    def _jitted(self, args):
        import jax

        _grid.check_initialized()
        gg = _grid.global_grid()
        leaves, treedef = jax.tree.flatten(args)
        sig = (
            gg.epoch,
            treedef,
            tuple((np.shape(l), getattr(l, "dtype", type(l))) for l in leaves),
        )
        compiled = self._cache.get(sig)
        if compiled is None:
            compiled = self._build(gg, args, treedef)
            self._cache[sig] = compiled
        return compiled

    def _build(self, gg, args, treedef):
        import jax

        if gg.nprocs == 1 and not gg.force_spmd:
            # Degenerate 1-device grid: shard_map adds nothing semantically
            # (every mesh axis has size 1) but routes execution through the
            # SPMD path, which measurably caps throughput on some runtimes.
            # Plain jit — unless the function really uses mesh axis names
            # (e.g. a custom psum), detected with a cheap abstract trace.
            try:
                jax.eval_shape(self._fn, *args)
            except Exception:
                pass  # needs the axis environment: fall through to shard_map
            else:
                return jax.jit(self._fn, donate_argnums=self._donate)

        if self._in_specs is not None:
            in_specs = self._in_specs
        else:
            in_specs = jax.tree.map(lambda l: _infer_spec(l, gg), args)

        if self._out_specs is not None:
            out_specs = self._out_specs
        else:
            out_specs = self._infer_out_specs(gg, in_specs, args)

        from jax import shard_map

        mapped = shard_map(
            self._fn,
            mesh=gg.mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=self._donate)

    def _infer_out_specs(self, gg, in_specs, args):
        """Output specs, symmetric with the input heuristic: per-block
        (device-varying) outputs are sharded one block per device; outputs
        the function made replicated (e.g. a `psum` over the mesh axes) KEEP
        their local shape instead of being concatenated into dims-many
        copies.

        Mechanics: a rank-probe (out_specs=P(), never executed) recovers the
        output tree with the axis environment in place; then ONE
        `check_vma=True` trace of the shard_map exposes each output's
        varying-manual-axes set on the inner jaxpr's outvars — an empty set
        is statically-proven replication.  If that introspection is
        unavailable (jax version drift), per-output shape-probes test
        whether `P()` is provable instead (shard_map raises a clear
        ValueError exactly in the varying case); functions whose bodies do
        not trace under vma checking at all fall back to rank-based sharding
        for every output — the pre-round-3 behavior.
        """
        import jax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        probe = shard_map(
            self._fn,
            mesh=gg.mesh,
            in_specs=tuple(in_specs),
            out_specs=P(),
            check_vma=False,
        )
        out_shape = jax.eval_shape(probe, *args)
        shape_leaves, treedef = jax.tree.flatten(out_shape)
        rank_specs = [_infer_spec_from_ndim(len(l.shape)) for l in shape_leaves]

        def vma_mapped(specs):
            return shard_map(
                self._fn,
                mesh=gg.mesh,
                in_specs=tuple(in_specs),
                out_specs=treedef.unflatten(specs),
                check_vma=True,
            )

        try:
            jaxpr = jax.make_jaxpr(vma_mapped(rank_specs))(*args)
        except Exception:
            return treedef.unflatten(rank_specs)  # not vma-traceable: status quo
        try:
            (sm_eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
            inner = sm_eqn.params["jaxpr"]
            producer = {id(ov): e for e in inner.eqns for ov in e.outvars}

            def effective_vma(v):
                # shard_map widens a replicated value to the rank-based
                # out_spec with a `pvary` cast; the pre-cast vma is the
                # function's own — unwrap it.
                for _ in range(8):
                    e = producer.get(id(v))
                    if e is None or e.primitive.name != "pvary":
                        break
                    v = e.invars[0]
                return getattr(v.aval, "vma", None)

            vmas = [effective_vma(v) for v in inner.outvars]
            if len(vmas) == len(shape_leaves) and all(
                isinstance(v, frozenset) for v in vmas
            ):
                return treedef.unflatten(
                    [P() if not v else r for v, r in zip(vmas, rank_specs)]
                )
        except Exception:
            pass
        # Introspection shape changed: N per-output probes (slower, same result).
        specs = list(rank_specs)
        for i, leaf in enumerate(shape_leaves):
            if len(leaf.shape) == 0:
                continue  # scalars are already P()
            try:
                jax.eval_shape(
                    vma_mapped(rank_specs[:i] + [P()] + rank_specs[i + 1:]), *args
                )
            except Exception:
                # Device-varying (shard_map's replication ValueError) — or any
                # drifted-jax failure mode: keep the per-block sharding, the
                # safe pre-round-3 behavior.
                continue
            specs[i] = P()
        return treedef.unflatten(specs)


def _infer_spec_from_ndim(ndim: int):
    from jax.sharding import PartitionSpec as P

    return P(*AXIS_NAMES[:ndim])
