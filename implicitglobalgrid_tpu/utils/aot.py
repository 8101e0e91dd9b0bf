"""Synthetic-GlobalGrid AOT scaffolding.

Multi-chip TPU hardware is not attached in the build environment, but the
runtime CAN compile for detached topologies
(`jax.experimental.topologies.get_topology_desc`) — the basis of every
multi-chip structural check (`scripts/verify_tpu.py` checks 6/9/10/11, the
`benchmarks/run.py::aot_weak_proxy` north-star record).  They all need the
same scaffold: resolve a topology description, build a ``dims`` mesh over
its devices, and install a synthetic `GlobalGrid` carrying that mesh so the
per-block program builders (models, halo ops) trace against the multi-chip
topology.  One implementation here, so a change to the swap/restore
protocol (or a new `GlobalGrid` field) cannot drift between the four users.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math

#: Topology-name candidates per chip count, tried in order (the leading
#: ``{kind}`` entries resolve on the attached generation; the literal ones
#: are fallbacks for runtimes whose device_kind string differs).
_TOPOLOGY_NAMES = {
    8: ("{kind}:2x2x2", "{kind}:2x4", "v5e:2x4"),
    16: ("{kind}:4x4", "v5e:4x4", "v5litepod-16"),
    256: ("{kind}:16x16", "v5e:16x16", "v5litepod-256"),
}


def _derived_topology_names(nchips: int) -> tuple[str, ...]:
    """``{kind}:AxB`` candidates derived for chip counts not in the table.

    Square-ish 2-D factorization, largest divisor ``a <= sqrt(nchips)``
    first — the same shapes the curated `_TOPOLOGY_NAMES` entries use
    (8 -> 2x4, 16 -> 4x4, 256 -> 16x16), so an uncurated count (e.g. 64)
    still gets a plausible slice name instead of an immediate failure.
    """
    a = max(d for d in range(1, int(math.isqrt(nchips)) + 1) if nchips % d == 0)
    b = nchips // a
    names = [f"{{kind}}:{a}x{b}"]
    if a != b:
        names.append(f"{{kind}}:{b}x{a}")
    return tuple(names)


def topology_mesh(dims, topo=None):
    """An ``("x","y","z")`` `Mesh` of ``prod(dims)`` detached-topology devices.

    ``topo``: an already described topology to take the devices from;
    otherwise the candidate names below are tried.  Raises
    ``RuntimeError`` when no topology description resolves — the one
    legitimate skip reason for AOT checks.  The error carries every
    candidate's own failure (ADVICE r5 low #2): a misconfigured runtime
    used to surface as a bare "no topology available" with the per-name
    exceptions swallowed.
    """
    import numpy as np

    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    nchips = math.prod(dims)
    if topo is not None:
        devs = np.asarray(topo.devices)[:nchips].reshape(dims)
        return Mesh(devs, ("x", "y", "z"))
    kind = jax.devices()[0].device_kind
    names = _TOPOLOGY_NAMES.get(nchips) or _derived_topology_names(nchips)
    topo = None
    failures: list[str] = []
    for name in names:
        resolved = name.format(kind=kind)
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name=resolved
            )
            break
        except Exception as e:
            failures.append(f"{resolved}: {type(e).__name__}: {e}")
            continue
    if topo is None:
        detail = "; ".join(failures) if failures else "no candidates tried"
        raise RuntimeError(
            f"no AOT topology description available for {nchips} chips "
            f"(dims={tuple(dims)}); candidates failed with: {detail}"
        )
    devs = np.asarray(topo.devices)[:nchips].reshape(dims)
    return Mesh(devs, ("x", "y", "z"))


@contextlib.contextmanager
def synthetic_topology_grid(dims, nloc, overlaps=(2, 2, 2), topo=None,
                            periods=(0, 0, 0)):
    """Install a synthetic multi-chip `GlobalGrid` for AOT lowering.

    Initializes a real 1-device grid with local shape ``nloc``,
    ``overlaps`` and ``periods`` (so every derived quantity — implicit
    global size, halo widths, timing functions — is built by the public
    path), then swaps in a copy carrying the detached-topology ``dims``
    mesh.  Yields ``(gg, mesh)``; the grid is restored and finalized on
    exit.  Refuses to run with a live caller grid rather than silently
    destroying it.  ``topo`` is passed to `topology_mesh`.
    """
    import jax

    from ..parallel import grid as _grid

    if _grid.grid_is_initialized():
        raise RuntimeError(
            "synthetic_topology_grid needs a clean slate: finalize the "
            "current global grid first."
        )
    mesh = topology_mesh(dims, topo)  # before init: a topology failure must skip cleanly
    nx, ny, nz = nloc
    ox, oy, oz = overlaps
    px, py, pz = periods
    _grid.init_global_grid(
        nx, ny, nz, overlapx=ox, overlapy=oy, overlapz=oz, periodx=px,
        periody=py, periodz=pz, quiet=True, devices=list(jax.devices())[:1],
    )
    gg0 = _grid.get_global_grid()
    gg = dataclasses.replace(
        gg0, mesh=mesh, dims=tuple(dims), nprocs=math.prod(dims), coords=(0, 0, 0)
    )
    _grid.set_global_grid(gg)
    try:
        yield gg, mesh
    finally:
        _grid.set_global_grid(gg0)
        _grid.finalize_global_grid()
