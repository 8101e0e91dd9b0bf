"""Global-grid state: the TPU-native `GlobalGrid` and its singleton.

Re-designs the reference's mutable singleton (`/root/reference/src/shared.jl:46-81`)
as a frozen dataclass holding a `jax.sharding.Mesh`.  The grid is still a
module-level singleton guarded by ``check_initialized`` with the reference's
exact error contract, because the whole point of the library is the
three-function promise (`init_global_grid` / `update_halo` /
`finalize_global_grid`) with no grid object threaded through user code.

The implicit global grid: ``nxyz_g = dims*(nxyz-overlaps) + overlaps*(periods==0)``
(`/root/reference/src/init_global_grid.jl:93`).  Arrays are represented as
*global-block* `jax.Array`s: the array holding per-device local blocks of
shape ``(nx, ny, nz)`` has global shape ``(dims[0]*nx, dims[1]*ny, dims[2]*nz)``
sharded one block per device on the mesh — overlapping cells are stored
redundantly, exactly like the reference's per-process local arrays, and the
de-duplicated global grid is never materialized (except by `gather`).
"""

from __future__ import annotations

import contextlib as _contextlib
import dataclasses
import os
import time
from typing import Any

import numpy as np

from . import topology
from .topology import AXIS_NAMES, NDIMS, PROC_NULL

DEVICE_TYPE_AUTO = "auto"
DEVICE_TYPE_TPU = "tpu"
DEVICE_TYPE_CPU = "cpu"
DEVICE_TYPE_GPU = "gpu"
_DEVICE_TYPES = (DEVICE_TYPE_AUTO, DEVICE_TYPE_TPU, DEVICE_TYPE_CPU, DEVICE_TYPE_GPU)


@dataclasses.dataclass(frozen=True)
class GlobalGrid:
    """Immutable snapshot of the grid topology (reference: src/shared.jl:46-65).

    ``nprocs`` counts *blocks* (= devices), the analogue of MPI ranks; ``me``
    and ``coords`` are the process-level view (the first local device's block)
    used by host-side helpers like `x_g` and as `gather`'s root identity.
    """

    nxyz_g: tuple[int, int, int]
    nxyz: tuple[int, int, int]
    dims: tuple[int, int, int]
    overlaps: tuple[int, int, int]
    nprocs: int
    me: int
    coords: tuple[int, int, int]
    neighbors: Any  # np.ndarray (2, 3), PROC_NULL where absent
    periods: tuple[int, int, int]
    disp: int
    reorder: int
    mesh: Any  # jax.sharding.Mesh with axis names ("x", "y", "z")
    device_type: str
    quiet: bool
    # monotonically increasing across init/finalize cycles; keys jit caches
    epoch: int = 0
    # Snapshot at init time of whether this library brought up the
    # distributed runtime (see `distributed.owns_runtime`, which is the
    # live, module-level flag `finalize_global_grid` actually consults —
    # ownership survives `finalize_distributed=False` re-init cycles).
    owns_distributed: bool = False
    # Route even degenerate 1-device grids through shard_map/NamedSharding
    # (used by the weak-scaling benchmark so t(1) and t(N) measure the same
    # execution path; see docs/performance.md on the SPMD-path cost).
    force_spmd: bool = False

    def replace(self, **kw) -> "GlobalGrid":
        return dataclasses.replace(self, **kw)

    def checkpoint_meta(self) -> dict:
        """Topology metadata a checkpoint must match to be restorable here
        (`utils.checkpoint`): the implicit-global-grid identity — local
        sizes, dims, overlaps, periods — without runtime objects (mesh,
        devices) that legitimately differ across restarts."""
        return {
            "dims": list(self.dims),
            "nxyz": list(self.nxyz),
            "nxyz_g": list(self.nxyz_g),
            "overlaps": list(self.overlaps),
            "periods": list(self.periods),
            "disp": int(self.disp),
            "nprocs": int(self.nprocs),
            "device_type": self.device_type,
        }


def elastic_topology_error(saved: dict, current: dict) -> str | None:
    """Why ``current`` cannot elastically restore a checkpoint written under
    ``saved``, or None when it can.

    Both arguments are `GlobalGrid.checkpoint_meta` dicts.  The implicit
    global grid makes topology a *derived* quantity — any ``(nxyz, dims,
    overlaps, periods)`` implying the same de-duplicated global size
    (`topology.implied_global_shape`) describes the same physical grid, so a
    checkpoint written at ``dims=(2,2,2)`` is restorable on a surviving
    ``(2,2,1)`` or replacement ``(4,1,2)`` slice.  Periodicity must match:
    it is part of the physical problem (and changes the de-dup identity of
    the boundary overlap), not of the decomposition.
    """
    mismatches = []
    if tuple(saved.get("periods", ())) != tuple(current.get("periods", ())):
        mismatches.append(
            f"periods: checkpoint {list(saved.get('periods', []))} vs "
            f"current {list(current.get('periods', []))} (periodicity is "
            f"part of the physical problem, not of the decomposition)"
        )
    saved_g = topology.implied_global_shape(
        saved["nxyz"], saved["dims"], saved["overlaps"], saved["periods"]
    )
    cur_g = topology.implied_global_shape(
        current["nxyz"], current["dims"], current["overlaps"], current["periods"]
    )
    if saved_g != cur_g:
        mismatches.append(
            f"implied global size nxyz_g = dims*(nxyz-overlaps) + "
            f"overlaps*(periods==0): checkpoint "
            f"{list(saved.get('nxyz_g', saved_g))} (from nxyz="
            f"{list(saved['nxyz'])}, dims={list(saved['dims'])}, overlaps="
            f"{list(saved['overlaps'])}) vs current {list(cur_g)} (from "
            f"nxyz={list(current['nxyz'])}, dims={list(current['dims'])}, "
            f"overlaps={list(current['overlaps'])}) — adjust the local "
            f"sizes so the target topology spans the same global grid"
        )
    if mismatches:
        return "; ".join(mismatches)
    return None


_global_grid: GlobalGrid | None = None
_epoch = 0


def grid_is_initialized() -> bool:
    return _global_grid is not None


def check_initialized() -> None:
    # Error message contract from /root/reference/src/shared.jl:77.
    if not grid_is_initialized():
        raise RuntimeError(
            "No function of the module can be called before init_global_grid() "
            "or after finalize_global_grid()."
        )


def global_grid() -> GlobalGrid:
    check_initialized()
    return _global_grid


def set_global_grid(gg: GlobalGrid | None) -> None:
    global _global_grid
    _global_grid = gg


def get_global_grid() -> GlobalGrid:
    """Return the (immutable) current grid (reference: src/shared.jl:80)."""
    check_initialized()
    return _global_grid


def init_global_grid(
    nx: int,
    ny: int = 1,
    nz: int = 1,
    *,
    dimx: int = 0,
    dimy: int = 0,
    dimz: int = 0,
    periodx: int = 0,
    periody: int = 0,
    periodz: int = 0,
    overlapx: int | None = None,
    overlapy: int | None = None,
    overlapz: int | None = None,
    disp: int = 1,
    reorder: int | None = None,
    devices=None,
    device_type: str | None = None,
    init_distributed: bool = False,
    distributed_kwargs: dict | None = None,
    select_device: bool = True,
    quiet: bool | None = None,
    force_spmd: bool = False,
):
    """Initialize the Cartesian device topology, implicitly defining a global grid.

    TPU-native counterpart of `/root/reference/src/init_global_grid.jl:40-99`.
    ``nx, ny, nz`` are the *local* (per-device-block) grid sizes.  The device
    count is factored into ``dims`` (fixed entries honored, zeros filled
    balanced — `dims_create`), a 3-D `Mesh` is created over the TPU slice
    (``reorder=1`` aligns mesh axes with the ICI torus), and the implicit
    global size is derived as ``dims*(nxyz-overlaps) + overlaps*(periods==0)``.

    Configuration tiers (reference: src/init_global_grid.jl:40,51-68):
    explicit kwargs > ``IGG_*`` env vars (`utils.config.env_config`) >
    defaults.  ``init_distributed=True`` (the reference's ``init_MPI``) brings
    up the JAX multi-host runtime first; ``devices`` (the reference's
    ``comm``) restricts the grid to a device subset.

    Returns ``(me, dims, nprocs, coords, mesh)`` — the mesh takes the place of
    the reference's Cartesian communicator in the return tuple.
    """
    global _epoch
    import jax

    from ..utils.config import env_config

    if grid_is_initialized():
        raise RuntimeError("The global grid has already been initialized.")
    # Env tier (reference: src/init_global_grid.jl:51-68): kwargs > env > defaults.
    env = env_config()
    env_overlap = env.get("overlap", 2)
    overlapx = env_overlap if overlapx is None else overlapx
    overlapy = env_overlap if overlapy is None else overlapy
    overlapz = env_overlap if overlapz is None else overlapz
    reorder = env.get("reorder", 1) if reorder is None else reorder
    device_type = env.get("device_type", DEVICE_TYPE_AUTO) if device_type is None else device_type
    quiet = env.get("quiet", False) if quiet is None else quiet
    owns_distributed = False
    if init_distributed:
        # The reference's `init_MPI=true` analogue: bring up the multi-host
        # runtime before touching devices (src/init_global_grid.jl:78-83).
        # ``distributed_kwargs`` (coordinator_address, num_processes,
        # process_id, ...) pass through for manual cluster setups; on Cloud
        # TPU pods they auto-detect.
        from . import distributed as _distributed

        _distributed.init_distributed(**(distributed_kwargs or {}))
        owns_distributed = _distributed.owns_runtime()
    nxyz = [int(nx), int(ny), int(nz)]
    dims = [int(dimx), int(dimy), int(dimz)]
    periods = [int(periodx), int(periody), int(periodz)]
    overlaps = [int(overlapx), int(overlapy), int(overlapz)]

    if device_type not in _DEVICE_TYPES:
        raise ValueError(
            f"Argument `device_type`: invalid value obtained ({device_type}). "
            f"Valid values are: {', '.join(_DEVICE_TYPES)}"
        )
    # Argument validation ported from src/init_global_grid.jl:73-77.
    if nxyz[0] == 1:
        raise ValueError("Invalid arguments: nx can never be 1.")
    if nxyz[1] == 1 and nxyz[2] > 1:
        raise ValueError("Invalid arguments: ny cannot be 1 if nz is greater than 1.")
    if any(n == 1 and d > 1 for n, d in zip(nxyz, dims)):
        raise ValueError(
            "Incoherent arguments: if nx, ny, or nz is 1, then the corresponding "
            "dimx, dimy or dimz must not be set (or set 0 or 1)."
        )
    if any(n < 2 * o - 1 and p > 0 for n, o, p in zip(nxyz, overlaps, periods)):
        raise ValueError(
            "Incoherent arguments: if nx, ny, or nz is smaller than 2*overlapx-1, "
            "2*overlapy-1 or 2*overlapz-1, respectively, then the corresponding "
            "periodx, periody or periodz must not be set (or set 0)."
        )
    for d in range(NDIMS):
        if nxyz[d] == 1 and dims[d] == 0:
            dims[d] = 1  # src/init_global_grid.jl:77

    if devices is None:
        if device_type == DEVICE_TYPE_AUTO:
            devices = jax.devices()
        else:
            devices = jax.devices(device_type)
    nprocs = len(devices)
    dims = topology.dims_create(nprocs, tuple(dims))
    mesh = topology.create_mesh(dims, devices=devices, reorder=reorder)

    # This process's block identity = the mesh position of its first local
    # device (create_mesh with reorder=1 may permute devices for ICI locality,
    # so positions cannot be inferred from rank arithmetic).
    first_local = jax.local_devices()[0]
    pos = np.argwhere(mesh.devices == first_local)
    coords = tuple(int(c) for c in pos[0]) if len(pos) else (0, 0, 0)
    me = topology.rank_of_coords(coords, dims)
    neighbors = topology.neighbors_table(coords, dims, periods, disp)
    nxyz_g = topology.implied_global_shape(
        nxyz, dims, overlaps, periods
    )  # src/init_global_grid.jl:93

    _epoch += 1
    gg = GlobalGrid(
        nxyz_g=nxyz_g,
        nxyz=tuple(nxyz),
        dims=dims,
        overlaps=tuple(overlaps),
        nprocs=nprocs,
        me=me,
        coords=coords,
        neighbors=neighbors,
        periods=tuple(periods),
        disp=int(disp),
        reorder=int(reorder),
        mesh=mesh,
        device_type=device_type,
        quiet=bool(quiet),
        epoch=_epoch,
        owns_distributed=owns_distributed,
        force_spmd=bool(force_spmd),
    )
    set_global_grid(gg)
    if not quiet and jax.process_index() == 0:
        print(
            f"Global grid: {nxyz_g[0]}x{nxyz_g[1]}x{nxyz_g[2]} "
            f"(nprocs: {nprocs}, dims: {dims[0]}x{dims[1]}x{dims[2]})"
        )
    if select_device:
        _select_device()
    # The first barrier is the first collective every process must enter: a
    # straggler or mis-set coordinator hangs exactly here, in C++ where
    # Python tracebacks see nothing — the IGG_WATCHDOG_S watchdog dumps
    # all-thread stacks (and the env tier keeps it out of the hot loop).
    from ..utils import config as _cfg
    from ..utils import tracing as _tracing
    from ..utils.resilience import watchdog as _watchdog

    with _watchdog(_cfg.watchdog_env()):
        init_timing_functions()
        # Cross-rank clock sync (docs/observability.md): one more barrier,
        # with every rank's wall/perf clocks sampled right at its exit —
        # the shared instant `igg.dump_trace` merges per-rank timelines on.
        # The recorded uncertainty is the measured barrier duration (the
        # honest bound on cross-rank alignment).  Single process: no
        # barrier needed, the one local clock aligns with itself.
        _tracing.record_clock_sync(
            _barrier if jax.process_count() > 1 else None, epoch=_epoch
        )
    return me, dims, nprocs, coords, mesh


def finalize_global_grid(*, finalize_distributed: bool = True) -> None:
    """Tear down the grid singleton (reference: src/finalize_global_grid.jl:15-27).

    There are no MPI handles, pinned host buffers or persistent streams to
    free on TPU — communication state lives inside compiled XLA executables —
    so finalization drops the singleton and the grid-keyed jit caches.

    If `init_global_grid(init_distributed=True)` brought up the multi-host
    runtime, it is shut down here too — the reference's guarded
    ``MPI.Finalize`` (`/root/reference/src/finalize_global_grid.jl:19-23`).
    Pass ``finalize_distributed=False`` (the reference's ``finalize_MPI=false``)
    to keep the runtime alive, e.g. to re-init another grid in this process.
    """
    global _barrier_fn
    check_initialized()
    from ..models import _batched as _batched_mod
    from ..ops import gather as _gather
    from ..ops import halo as _halo
    from ..ops import stencil as _stencil
    from ..serving import frontdoor as _frontdoor
    from ..utils import resilience as _resilience
    from ..utils import tracing as _tracing

    _halo._clear_caches()
    _stencil._clear_caches()
    _gather._clear_caches()
    _resilience._clear_caches()
    _batched_mod._clear_caches()
    _tracing._clear_caches()
    _frontdoor._clear_caches()
    _barrier_fn = None
    set_global_grid(None)
    if finalize_distributed:
        from . import distributed as _distributed

        if _distributed.owns_runtime():
            _distributed.shutdown_distributed()


def select_device():
    """Bind this process to its accelerator and return the device.

    Parity shim for `/root/reference/src/select_device.jl:15-38`: under JAX's
    multi-controller runtime each process already owns its local devices
    (the work `MPI.Comm_split_type(COMM_TYPE_SHARED)` + `CUDA.device!` does in
    the reference happens implicitly at runtime init), so this validates the
    binding and returns the first local device.
    """
    import jax

    check_initialized()
    gg = global_grid()
    if gg.device_type != DEVICE_TYPE_AUTO:
        platforms = {d.platform for d in jax.local_devices()}
        if gg.device_type not in platforms:
            raise RuntimeError(
                f"Cannot select a device of type {gg.device_type!r}: local devices "
                f"are of platform(s) {sorted(platforms)}."
            )
    return jax.local_devices()[0]


def _select_device():
    return select_device()


# -- Timing tools (reference: src/tools.jl:230-236) --------------------------

# None = no user tic() yet: toc() must raise instead of measuring from an
# arbitrary epoch (init_timing_functions primes the barrier but resets this).
_t0: list[float | None] = [None]
_barrier_fn = None


def _barrier() -> None:
    """Synchronize all devices (the reference's `MPI.Barrier(comm())`).

    A tiny jitted all-device `psum` is dispatched and blocked on; on a
    multi-host runtime this synchronizes every process through ICI/DCN.
    """
    global _barrier_fn
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    gg = global_grid()
    if _barrier_fn is None or _barrier_fn[0] is not gg.mesh:
        mesh = gg.mesh
        mapped = shard_map(
            lambda: jnp.zeros((), jnp.int32),
            mesh=mesh,
            in_specs=(),
            out_specs=P(),
            check_vma=False,
        )
        _barrier_fn = (mesh, jax.jit(mapped, out_shardings=NamedSharding(mesh, P())))
    jax.block_until_ready(_barrier_fn[1]())


def tic() -> None:
    """Start the chronometer once all devices have reached this point.

    Monotonic (`time.perf_counter`): a wall-clock (`time.time`) chronometer
    jumps with NTP slews/steps, which at multi-minute production timings is
    a real error source — and the reference's own contract is pure elapsed
    time, not timestamps.
    """
    check_initialized()
    _barrier()
    _t0[0] = time.perf_counter()


def toc() -> float:
    """Elapsed seconds since `tic` once all devices have reached this point."""
    check_initialized()
    if _t0[0] is None:
        raise RuntimeError(
            "toc() called before tic(): the chronometer was never started "
            "(call igg.tic() at the start of the timed section)."
        )
    _barrier()
    return time.perf_counter() - _t0[0]


def init_timing_functions() -> None:
    # Pre-compile the barrier so the first user tic()/toc() is fast
    # (reference: src/init_global_grid.jl:97,102-105) — then reset the
    # chronometer: the priming tic must not masquerade as a user tic (a
    # user's toc()-without-tic() would silently time since init).
    tic()
    toc()
    _t0[0] = None


@_contextlib.contextmanager
def profile_trace(logdir, **kwargs):
    """Profiler hook: record a `jax.profiler` trace of the enclosed block.

    Thin alias of `utils.profiling.profile_trace` — the ONE capture
    implementation of the device-timeline plane (docs/observability.md):
    ``create_perfetto_trace`` now defaults True so the capture always
    emits the parseable ``*.trace.json.gz`` that
    ``scripts/igg_prof.py attribute`` and ``igg_trace.py merge --device``
    consume.  Kept at its historical home for API stability; new code
    should prefer the env-armed windowed capture (``IGG_PROFILE=
    steps:A-B``), which needs no code changes and writes the per-rank
    capture meta the tooling discovers::

        with igg.profile_trace("/tmp/igg-trace"):
            for _ in range(100):
                state = step(*state)
        # then: python scripts/igg_prof.py attribute /tmp/igg-trace
    """
    from ..utils import profiling as _profiling

    with _profiling.profile_trace(logdir, **kwargs):
        yield
