"""Shadow-step audit: bit-compare a re-executed step against its commit.

The mercurial-core detector (wrong *compute*, not wrong transport): at the
``IGG_INTEGRITY_EVERY`` cadence, `utils.resilience.guarded_time_loop`
retains a pre-step snapshot, re-executes the just-committed step from it
and bit-compares the two results here.  XLA programs are run-to-run
deterministic on healthy hardware (same executable, same inputs, same
partitioning), so ANY difference — one flipped mantissa bit included — is
a finding; the interpret-mode matrix in ``tests/test_integrity.py`` pins
that healthy re-execution is bit-identical across all three models.

The comparison follows the `utils.resilience._probe_fn` discipline: each
block reduces its field pairs to per-field mismatch flags over the
*bitcast word view* (NaN-proof — NaN != NaN would hide a corrupted NaN
under a float compare), scatters them into a ``dims``-shaped one-hot and
`psum`s over every mesh axis.  The verdict is therefore REPLICATED: every
rank sees the same report, raises (or not) together, and the rank-uniform
cadence + replicated verdict are exactly what
`analysis.collectives.integrity_plan_censuses` pins — a rank-local audit
verdict driving a collective would be the SPMD-divergence class the
analyzer exists to catch.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

__all__ = ["AuditReport", "audit_fields"]


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """Outcome of one shadow-step bit-compare."""

    names: tuple[str, ...]
    #: field name -> block coords whose re-execution differed bitwise
    bad_blocks: dict
    #: ranks owning a differing block (the quarantine targets)
    implicated_ranks: tuple[int, ...]

    @property
    def ok(self) -> bool:
        return not self.bad_blocks

    def summary(self) -> str:
        if self.ok:
            return f"bit-identical re-execution ({', '.join(self.names)})"
        parts = [
            f"{name}: block(s) {', '.join(str(c) for c in coords)}"
            for name, coords in self.bad_blocks.items()
        ]
        return (
            "re-execution differs bitwise in " + "; ".join(parts)
            + f" (implicated rank(s) {list(self.implicated_ranks)})"
        )


_compare_cache: dict = {}


def _clear_caches() -> None:
    _compare_cache.clear()


def _compare_fn(gg, shapes_dtypes):
    """Build (and cache) the jitted bitwise-difference probe.

    One program per (epoch, signature), shaped exactly like
    `utils.resilience._probe_fn`: per-block word-view inequality reduced
    to per-field flags, one-hot scattered at the block's coords, `psum`med
    over all mesh axes into a replicated ``(nfields, *dims)`` int32 array.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..ops.halo import _flat_words
    from ..parallel.topology import AXIS_NAMES, NDIMS
    from jax import shard_map

    key = (gg.epoch, shapes_dtypes)
    fn = _compare_cache.get(key)
    if fn is not None:
        return fn

    nfields = len(shapes_dtypes)

    def block_flags(args):
        committed, redone = args[:nfields], args[nfields:]
        flags = []
        for a, b in zip(committed, redone):
            # word-view compare: bit-exact, NaN bit patterns included
            flags.append(
                jnp.any(_flat_words(a) != _flat_words(b)).astype(jnp.int32)
            )
        return jnp.stack(flags)

    if gg.nprocs == 1 and not gg.force_spmd:
        fn = jax.jit(
            lambda *f: block_flags(f).reshape((nfields, 1, 1, 1))
        )
        _compare_cache[key] = fn
        return fn

    def per_block(*args):
        flags = block_flags(args)  # (nfields,)
        onehot = jnp.zeros((nfields, *gg.dims), jnp.int32)
        for i, (shp, _) in enumerate(shapes_dtypes):
            # replicated axes clamp to 0 (the `_probe_fn` discipline: a
            # lower-rank field's replicas must scatter at one coord)
            coords = tuple(
                lax.axis_index(AXIS_NAMES[d])
                if d < len(shp) and gg.dims[d] > 1
                else jnp.int32(0)
                for d in range(NDIMS)
            )
            onehot = lax.dynamic_update_slice(
                onehot, flags[i].reshape((1, 1, 1, 1)), (jnp.int32(i), *coords)
            )
        return lax.psum(onehot, AXIS_NAMES)

    specs = tuple(P(*AXIS_NAMES[: len(s)]) for s, _ in shapes_dtypes)
    mapped = shard_map(
        per_block, mesh=gg.mesh, in_specs=specs + specs, out_specs=P(),
        check_vma=False,
    )
    fn = jax.jit(mapped)
    _compare_cache[key] = fn
    return fn


def audit_fields(committed: tuple, redone: tuple,
                 names: Sequence[str] | None = None) -> AuditReport:
    """Bit-compare a committed state tuple against its re-execution.

    Returns an `AuditReport` naming every field and block whose bits
    differ plus the owning ranks.  Replicated verdict (module docstring):
    multi-host callers all see the same report.
    """
    from ..ops.halo import local_shape
    from ..parallel import grid as _grid
    from ..parallel import topology

    _grid.check_initialized()
    gg = _grid.global_grid()
    if len(committed) != len(redone):
        raise ValueError(
            f"audit_fields: committed has {len(committed)} fields, the "
            f"re-execution {len(redone)}."
        )
    if names is None:
        names = tuple(f"field{i}" for i in range(len(committed)))
    else:
        names = tuple(names)
        if len(names) != len(committed):
            raise ValueError(
                f"names has {len(names)} entries for {len(committed)} fields."
            )
    sig = tuple(
        (local_shape(A, gg), str(A.dtype)) for A in committed
    )
    flags = np.asarray(_compare_fn(gg, sig)(*committed, *redone))
    bad: dict = {}
    ranks: set[int] = set()
    for i, name in enumerate(names):
        coords = tuple(
            tuple(int(c) for c in idx) for idx in np.argwhere(flags[i])
        )
        if coords:
            bad[name] = coords
            for c in coords:
                ranks.add(topology.rank_of_coords(c, gg.dims))
    return AuditReport(
        names=names, bad_blocks=bad, implicated_ranks=tuple(sorted(ranks))
    )
