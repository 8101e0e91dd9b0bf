"""Worker process for the multi-process `jax.distributed` tests (not pytest).

Spawned by `tests/test_distributed.py` as ``(pid, nprocs, port, out_path[,
mesh])``: ``nprocs`` coordinator-connected processes share one virtual CPU
mesh of shape ``mesh`` (``"DXxDYxDZ"``, default ``2x2x2`` = the suite's
8-device mesh; each process hosts ``prod(mesh)/nprocs`` virtual devices)
with real process boundaries through it — the TPU translation of the
reference running its suite under ``mpiexec -n N``
(`/root/reference/test/runtests.jl:8-31`).

The default 2-process shape runs the full battery below.  A non-default
``mesh`` (e.g. the 4-process ``2x2x1``: one device per process, TWO
simultaneous process boundaries) runs the compact scenario: fused-cadence
exchange + fill-in-place gather with corner carry-over across both
boundaries, plus coalesced-vs-per-field exchange bit-identity on real gloo
hops (ISSUE 5).

Covers the paths no single-process test can reach:
`parallel/distributed.py` (init via `init_global_grid(init_distributed=True)`),
multi-host ``me``/``coords`` derivation (`parallel/grid.py`), `gather`'s
`process_allgather` branch with a non-default root
(`/root/reference/test/test_gather.jl:126-137` analogue), and the
finalize-shuts-down-the-runtime lifecycle
(`/root/reference/src/finalize_global_grid.jl:19-23` analogue).
"""

import math
import sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]
out_path = sys.argv[4]
mesh_arg = sys.argv[5] if len(sys.argv) > 5 else ""
MESH_DIMS = (
    tuple(int(x) for x in mesh_arg.split("x")) if mesh_arg else (2, 2, 2)
)
assert math.prod(MESH_DIMS) % nproc == 0, (MESH_DIMS, nproc)
LOCAL_DEVICES = math.prod(MESH_DIMS) // nproc

import faulthandler
import os

# Pre-import watchdog: jax import / backend plugin probing can itself stall;
# arm the raw timer BEFORE any heavy import (the library watchdog below
# replaces this timer once the package is importable).
faulthandler.dump_traceback_later(270, exit=True)

# Telemetry armed for the whole worker run (docs/observability.md): both
# ranks log into one shared directory; the parent test asserts per-rank
# event files with consistent rank/coords tags.
os.environ["IGG_TELEMETRY"] = "1"
os.environ["IGG_TELEMETRY_DIR"] = out_path + ".telemetry"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", LOCAL_DEVICES)
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import diffusion3d
from implicitglobalgrid_tpu.parallel import distributed as dist
from implicitglobalgrid_tpu.utils.resilience import arm_watchdog

# Watchdog below BOTH the parent's 480 s kill AND the JAX coordination
# service's 5-minute shutdown barrier: a straggler that misses that barrier
# is killed by the coordination service with NO stacks, so the watchdog
# must fire first — a deadlock or stall then dumps both workers' stacks
# into the logs the parent shows on failure, instead of dying silently.
# Replaces (and restarts) the raw pre-import timer armed at the top.
arm_watchdog(270, exit=True)

NX = 8
NSTEPS = 3
ROOT = 1  # non-default root: reference test_gather.jl:126-137

me, dims, nprocs, coords, mesh = igg.init_global_grid(
    NX,
    NX,
    NX,
    quiet=(pid != 0),
    init_distributed=True,
    distributed_kwargs=dict(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    ),
    **(
        dict(dimx=MESH_DIMS[0], dimy=MESH_DIMS[1], dimz=MESH_DIMS[2])
        if mesh_arg
        else {}
    ),
)
assert dist.is_distributed_initialized()
assert jax.process_count() == nproc, jax.process_count()
assert nprocs == math.prod(MESH_DIMS), nprocs
assert tuple(dims) == MESH_DIMS, (dims, MESH_DIMS)
assert igg.get_global_grid().owns_distributed

# me/coords = the block of this process's FIRST local device; distinct
# processes must land on distinct blocks.
assert 0 <= me < nprocs
assert coords == tuple(
    int(c) for c in np.argwhere(mesh.devices == jax.local_devices()[0])[0]
)

if mesh_arg:
    # ------------------------------------------------------------------
    # Compact multi-boundary scenario (ISSUE 5 satellite): run on the
    # requested mesh (e.g. 4 processes x 1 device = a 2x2 process grid in
    # x/y) and exercise exactly the paths where TWO simultaneous process
    # boundaries matter: the fused production cadence's slab exchange with
    # sequential-dimension corner carry-over, the fill-in-place chunked
    # gather, and the coalesced exchange's bit-identity on real gloo hops.
    # ------------------------------------------------------------------
    import warnings

    import jax.numpy as jnp

    from implicitglobalgrid_tpu.ops import gather as gather_mod

    # Deep-halo grid for the fused cadence (keep the runtime up, like the
    # reference's finalize_MPI=false re-init cycle).
    igg.finalize_global_grid(finalize_distributed=False)
    igg.init_global_grid(
        NX, NX, NX,
        dimx=MESH_DIMS[0], dimy=MESH_DIMS[1], dimz=MESH_DIMS[2],
        overlapx=4, overlapy=4, overlapz=4, quiet=True,
    )

    # (1) Corner carry-over + coalesced bit-identity across both process
    # boundaries: on a coordinate-derived field set, duplicated cells are
    # consistent by construction, so a CORRECT width-2 multi-field slab
    # exchange is a bitwise no-op — any wrong plane, offset, partner or
    # corner strip breaks it.  Run it coalesced AND per-field: both must be
    # no-ops, hence bit-identical to each other over the real gloo hops.
    state, params = diffusion3d.setup(NX, NX, NX, init_grid=False)
    T0, Cp0 = state[0], state[1]
    fields = (T0, Cp0, T0.astype(jnp.float32), Cp0.astype(jnp.float32))
    maxdiff = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
    for coalesce in (True, False):
        outs = igg.update_halo(
            *[f + 0 for f in fields], width=2, coalesce=coalesce
        )
        for f, o in zip(fields, outs):
            d = float(maxdiff(o, f))
            assert d == 0.0, (
                f"width-2 slab exchange (coalesce={coalesce}) not a no-op "
                f"on a consistent field across the 2x2 process grid: {d}"
            )

    # (2) The fused production cadence (f64 grid: the documented warn-once
    # XLA fallback at the kernel path's exact exchange schedule) across
    # both boundaries; the parent compares against a single-process run of
    # the same global problem with the same decomposition.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        stepc = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=2)
        state = jax.block_until_ready(stepc(*state))
    Tf = igg.gather(diffusion3d.temperature(state), root=0)
    stats = gather_mod.last_gather_stats
    assert stats["path"] == "chunked", stats
    assert stats["blocks"] == nprocs, stats
    if jax.process_index() == 0:
        np.save(out_path, Tf)
    else:
        assert stats["host_bytes"] == 0, stats

    # (3) Fill-in-place gather rounds across the 2x2 block grid (the gloo
    # cross-match tripwire, here with FOUR processes contending).
    for round_ in range(2):
        buf = np.zeros_like(Tf) if jax.process_index() == 0 else None
        assert igg.gather(diffusion3d.temperature(state), buf, root=0) is None
        if jax.process_index() == 0 and not np.array_equal(buf, Tf):
            # supervisor-visible escalation (docs/robustness.md): the
            # tripwire leaves a classified flight bundle, not a generic
            # crash (`supervisor.classify` maps reason=gather_tripwire)
            igg.tracing.dump_flight_recorder(
                "gather_tripwire", round=round_, mesh=list(MESH_DIMS),
                nproc=nproc,
            )
            raise AssertionError(
                f"fill-in-place gather round {round_} mixed blocks on the "
                f"{nproc}-process mesh"
            )

    igg.finalize_global_grid()
    assert not igg.grid_is_initialized()
    assert not dist.is_distributed_initialized()
    print(f"WORKER {pid} OK", flush=True)
    sys.exit(0)

state, params = diffusion3d.setup(NX, NX, NX, init_grid=False)
step = diffusion3d.make_step(params)
for _ in range(NSTEPS):
    state = jax.block_until_ready(step(*state))

T = diffusion3d.temperature(state)
assert not T.is_fully_addressable  # the chunked multi-host branch, gather.py

from implicitglobalgrid_tpu.ops import gather as gather_mod

got = igg.gather(T, root=ROOT)
# Memory-scalable root-only assembly (reference gather.jl:33-46 bound): the
# multi-host path fetches block by block; non-roots must fetch NOTHING to
# host — they never hold (any part of) the assembled array.
stats = gather_mod.last_gather_stats
assert stats["path"] == "chunked", stats
assert stats["blocks"] == 8, stats
# Batched fetches (ADVICE r5 low #1): 8 blocks arrive in ceil(8/batch)
# collectives; the root-only memory bound is per BATCH now, the total host
# bytes still exactly one copy of every block.
assert stats["fetches"] == -(-stats["blocks"] // stats["batch"]), stats
if jax.process_index() == ROOT:
    assert stats["host_bytes"] == stats["blocks"] * stats["block_bytes"], stats
    assert got is not None
    np.save(out_path, got)
else:
    assert stats["host_bytes"] == 0, stats
    assert got is None

# Also exercise the fill-in-place signature.  gather is a collective: every
# process must make the call (root passes the output buffer, others None).
# THREE consecutive rounds: the jax-0.4.37 gloo transport used to cross-match
# in-flight per-block collectives (~50% of runs) when non-roots left fetches
# pending — the fix completes every fetch on every process
# (`_gather_chunked`); repeat rounds make any recurrence trip DETERMINISTICALLY
# in-worker instead of intermittently across suite runs (ROADMAP open item).
for round_ in range(3):
    buf = np.zeros_like(got) if jax.process_index() == ROOT else None
    assert igg.gather(T, buf, root=ROOT) is None
    if jax.process_index() == ROOT and not np.array_equal(buf, got):
        # The ROADMAP watch-item's supervisor-visible escalation path: a
        # tripped gather tripwire records a flight bundle whose
        # reason=gather_tripwire classifies as a TRANSPORT fault
        # (`igg.supervisor.classify`) instead of vanishing into a generic
        # worker crash — suspect the jax-0.4.37 gloo transport itself.
        igg.tracing.dump_flight_recorder(
            "gather_tripwire", round=round_, nproc=nproc,
        )
        raise AssertionError(
            f"fill-in-place gather round {round_} mixed blocks (gloo "
            f"transport cross-match recurrence? see ROADMAP open items)"
        )

# De-duplicated gather across the real process boundary: the owner-wise
# assembly (`gather(dedup=True)`, shared with the elastic checkpoint restore)
# must equal the concatenated result with overlaps stripped by ownership.
ddup = igg.gather(T, root=ROOT, dedup=True)
if jax.process_index() == ROOT:
    from implicitglobalgrid_tpu.ops.gather import dedup_shape

    assert ddup.shape == dedup_shape(T), (ddup.shape, dedup_shape(T))
    # dims (2,2,2) non-periodic, overlap 2: interior of the de-dup array
    # must match the concatenated blocks' owner regions
    assert np.array_equal(ddup[:7, :7, :7], got[:7, :7, :7])
    assert np.array_equal(ddup[-7:, -7:, -7:], got[-7:, -7:, -7:])
else:
    assert ddup is None

# Deep-halo slab exchange across the real process boundary: re-init with
# overlap=4 (keeping the runtime up — the reference's finalize_MPI=false
# cycle), then check width-2 exchange idempotence on a coordinate-derived
# field: duplicated cells are consistent by construction, so a correct slab
# exchange is a bitwise no-op, and any wrong plane/offset would break it.
igg.finalize_global_grid(finalize_distributed=False)
assert dist.is_distributed_initialized()
igg.init_global_grid(
    NX, NX, NX, overlapx=4, overlapy=4, overlapz=4, quiet=True
)
state2, params2 = diffusion3d.setup(NX, NX, NX, init_grid=False)
T2 = state2[0]
import jax.numpy as jnp

out2 = igg.update_halo(T2 + 0, width=2)  # +0: update_halo donates its input
d = float(jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))(out2, T2))
assert d == 0.0, f"width-2 slab exchange not idempotent on consistent field: {d}"

# --- Fused production cadence across the real process boundary (VERDICT r4
# #3).  The Pallas kernel itself CANNOT run in interpret mode across a
# process boundary: the TPU interpreter synchronizes every *global* device
# of the computation through one `threading.Barrier(num_devices)`
# (jax/_src/pallas/mosaic/interpret/interpret_pallas_call.py), but only the
# process-local devices run interpreter threads — any cross-process
# interpret-mode kernel deadlocks by construction (probed here; worker hung
# in `_barrier`).  What a process boundary actually changes is the cadence's
# COMMUNICATION, and that is fully exercised below:
# `make_multi_step(fused_k=2)` on this f64 grid takes the documented
# warn-once fallback to the XLA cadence at the SAME exchange schedule as
# the kernel path (one width-2 deep-halo slab exchange per 2 steps,
# sequential-dim corner carry-over) — the production exchange pattern on
# real gloo hops.  The kernel-vs-XLA-cadence arithmetic equivalence is
# pinned single-process (test_models_diffusion.py::
# test_fused_deep_halo_matches_xla_multiblock); transport cannot change
# per-block arithmetic.
import warnings

with warnings.catch_warnings():
    warnings.simplefilter("ignore", RuntimeWarning)
    stepc = diffusion3d.make_multi_step(params2, 4, donate=False, fused_k=2)
    state2 = jax.block_until_ready(stepc(*state2))
Tf = igg.gather(diffusion3d.temperature(state2), root=ROOT)
stats = gather_mod.last_gather_stats
assert stats["path"] == "chunked", stats
if jax.process_index() == ROOT:
    np.save(out_path + ".fused.npy", Tf)
else:
    assert stats["host_bytes"] == 0, stats

# --- Pipelined XLA-fallback cadence over the same real gloo hops (ISSUE 2):
# pipelined=True on this f64 grid runs the XLA cadence with the
# early-dispatch exchange shape (`begin_slab_exchange`/`finish`), whose
# ppermutes ride the gloo transport; by contract it is bit-identical to the
# serialized cadence — asserted here across a real process boundary.
state3p, params3p = diffusion3d.setup(NX, NX, NX, init_grid=False)
with warnings.catch_warnings():
    warnings.simplefilter("ignore", RuntimeWarning)
    stepp = diffusion3d.make_multi_step(
        params3p, 4, donate=False, fused_k=2, pipelined=True
    )
    state3p = jax.block_until_ready(stepp(*state3p))
Tfp = igg.gather(diffusion3d.temperature(state3p), root=ROOT)
if jax.process_index() == ROOT:
    assert np.array_equal(Tf, Tfp), (
        "pipelined XLA-fallback cadence diverged from the serialized "
        "cadence over gloo hops"
    )

# --- Batched ensemble across the real process boundary (ISSUE 8): the
# B=2 vmapped step's collectives ride the same gloo hops as everything
# above, and each member must advance bit-identically to its own B=1 run
# — the cross-process half of the B-for-the-price-of-1 contract (the
# collective-count invariance itself is pinned single-process by the
# budget census; transport cannot change per-member arithmetic, and THIS
# proves the batched transport delivers per-member bytes intact).
from implicitglobalgrid_tpu.models import _batched
from implicitglobalgrid_tpu.serving import Request, ServingLoop
from implicitglobalgrid_tpu.utils.resilience import arm_watchdog as _rearm_wd

_rearm_wd(240, exit=True)  # restart the one-shot deadline for this leg
sA, _pA = diffusion3d.setup(NX, NX, NX, init_grid=False, ic_scale=1.0)
sB, _pB = diffusion3d.setup(NX, NX, NX, init_grid=False, ic_scale=1.25)
bstate = _batched.stack_states([sA, sB])
stepb = diffusion3d.make_step(params2, donate=False, batch=True)
step1b = diffusion3d.make_step(params2, donate=False)
for _ in range(2):
    bstate = jax.block_until_ready(stepb(*bstate))
    sA = jax.block_until_ready(step1b(*sA))
    sB = jax.block_until_ready(step1b(*sB))
for b, oracle in ((0, sA), (1, sB)):
    got_b = igg.gather(bstate[0], member=b, root=ROOT)
    want_b = igg.gather(oracle[0], root=ROOT)
    if jax.process_index() == ROOT:
        assert np.array_equal(got_b, want_b), (
            f"batched member {b} diverged from its B=1 run across the "
            f"process boundary"
        )

# Mid-flight serving on the 2-process grid: 1 slot, 2 requests — the
# second member must be admitted into the slot the first one freed, with
# every rank taking the identical admit/retire decisions (the per-member
# finite probe is replicated by construction).  Both requests carry a
# request-scoped trace context (ISSUE 19): every rank's round spans must
# tag the active member's trace_id, so one causal tree reconstructs from
# EITHER rank's dump even though the request entered at a single door.
from implicitglobalgrid_tpu.utils import tracing as _trc

_tid0, _tid1 = "ab" * 16, "cd" * 16
_loop = ServingLoop(diffusion3d, params2, capacity=1, steps_per_round=1)
_m0 = _loop.submit(Request(state=sA, max_steps=1, tenant="r0",
                           trace={"trace_id": _tid0, "span_id": "0a" * 8}))
_m1 = _loop.submit(Request(state=sB, max_steps=1, tenant="r1",
                           trace={"trace_id": _tid1, "span_id": "0b" * 8}))
_res = _loop.run(max_rounds=6)
assert sorted(_res) == [_m0, _m1], _res
assert all(r.status == "completed" and r.steps == 1 for r in _res.values())
assert _loop.rounds == 2, _loop.rounds  # slot reuse = one round per member
_round_tids = set()
for _s in _trc.span_records():
    if _s["name"] == "igg.serving.round":
        for _t in (_tid0, _tid1):
            if _trc._trace_match(_s.get("args"), _t)[0]:
                _round_tids.add(_t)
assert _round_tids == {_tid0, _tid1}, (
    f"rank {pid} round spans lost request trace contexts: {_round_tids}"
)

# --- Autotuned config over the broadcast host transport (ISSUE 13): rank 0
# holds a seeded winner cache, rank 1 an EMPTY one — the deliberately
# rank-divergent disk state whose naive (rank-keyed) lookup is exactly the
# deadlock class the collective-consistency analyzer pins.  The resolve
# must let rank 0 alone decide and broadcast, so BOTH ranks build the
# identical tuned cadence; the tuned run must then be bit-identical to the
# default-config run over the same real gloo hops (tuning changes
# schedule, never results).
from implicitglobalgrid_tpu import tuning as _tuning

_tdir = out_path + f".tune.p{pid}"
_gg_now = igg.get_global_grid()
_tkey = _tuning.make_key("diffusion3d", _gg_now.nxyz, params2.dtype,
                         gg=_gg_now, nsteps=4)
if pid == 0:
    _tuning.TuneCache(primary=_tdir, fallbacks=()).store(
        _tkey, _tuning.new_entry(_tkey, {"exchange_every": 2},
                                 source="worker-seed"),
    )
os.environ["IGG_TUNE_CACHE"] = _tdir
try:
    from implicitglobalgrid_tpu.utils import telemetry as _tele

    sdef, _ = diffusion3d.setup(NX, NX, NX, init_grid=False)
    stun, _ = diffusion3d.setup(NX, NX, NX, init_grid=False)
    step_def = diffusion3d.make_multi_step(params2, 4, donate=False)
    _hits0 = _tele.snapshot()["counters"].get("tune.cache_hit", 0)
    step_tun = diffusion3d.make_multi_step(params2, 4, donate=False,
                                           autotune=True)
    _snap_t = _tele.snapshot()["counters"]
    # the broadcast decision was rank 0's HIT on every rank — rank 1's
    # empty disk must not have triggered a search (no candidate measured)
    assert _snap_t.get("tune.cache_hit", 0) - _hits0 == 1, _snap_t
    assert _snap_t.get("tune.candidates_measured", 0) == 0, _snap_t
    sdef = jax.block_until_ready(step_def(*sdef))
    stun = jax.block_until_ready(step_tun(*stun))
    Tdef = igg.gather(diffusion3d.temperature(sdef), root=ROOT)
    Ttun = igg.gather(diffusion3d.temperature(stun), root=ROOT)
    if jax.process_index() == ROOT:
        assert np.array_equal(Tdef, Ttun), (
            "broadcast-tuned cadence diverged from the default-config run "
            "across the process boundary"
        )
finally:
    del os.environ["IGG_TUNE_CACHE"]

# --- hide_communication across the real process boundary (VERDICT r4 #3):
# the overlap-scheduled exchange's ppermutes ride the same gloo hops.
igg.finalize_global_grid(finalize_distributed=False)
state4, params4 = diffusion3d.setup(NX, NX, NX, hide_comm=True, quiet=True)
step4 = diffusion3d.make_step(params4, donate=False)
for _ in range(NSTEPS):
    state4 = jax.block_until_ready(step4(*state4))
Th = igg.gather(diffusion3d.temperature(state4), root=ROOT)
if jax.process_index() == ROOT:
    np.save(out_path + ".hc.npy", Th)

# --- Telemetry across the real process boundary (docs/observability.md):
# every rank writes its OWN event file, tagged with its runtime rank and
# grid coords; the registry folded the gathers/exchanges above.
from implicitglobalgrid_tpu.utils import telemetry as tele

assert jax.process_index() == pid  # rank tag source below
tele.event("worker.check", nsteps=NSTEPS)
snap = tele.snapshot()
assert snap["rank"] == pid, snap
assert snap["counters"].get("gather.calls", 0) >= 5, snap["counters"]
assert snap["counters"].get("gather.calls.chunked", 0) >= 5, snap["counters"]
assert snap["counters"].get("halo.exchanges", 0) >= 1, snap["counters"]
_ev_file = os.path.join(
    os.environ["IGG_TELEMETRY_DIR"],
    "events.jsonl" if pid == 0 else f"events.p{pid}.jsonl",
)
_mine = [e for e in tele.read_events(_ev_file) if e["type"] == "worker.check"]
assert len(_mine) == 1 and _mine[0]["rank"] == pid, _mine
assert _mine[0]["coords"] == list(igg.get_global_grid().coords), _mine

# --- Cross-rank observability plane (ISSUE 10): run a short instrumented
# loop at heartbeat cadence so the all-ranks SKEW PROBE rides the real gloo
# transport (both ranks enter the replicated share at steps 2 and 4 — a
# cadence mismatch would deadlock right here, which is the point), then
# dump this rank's span file for the parent's merged-Chrome-trace check.
# ISSUE 15 rides the same loop: BOTH ranks arm a windowed device capture
# (IGG_PROFILE=steps:2-3) so the parent can join each rank's device track
# into the device-merged timeline (`igg_trace.py merge --device`).
from implicitglobalgrid_tpu.utils import tracing as _tracing
from implicitglobalgrid_tpu.utils.resilience import RunGuard, guarded_time_loop
from implicitglobalgrid_tpu.utils.telemetry import teff_bytes

assert _tracing.clock_sync()["barrier"], (
    "multi-process init_global_grid must record a barrier-anchored "
    "clock sync"
)
os.environ["IGG_HEARTBEAT_EVERY"] = "2"
os.environ["IGG_PROFILE"] = "steps:2-3"
try:
    state5, params5 = diffusion3d.setup(NX, NX, NX, init_grid=False)
    state5 = guarded_time_loop(
        diffusion3d.make_step(params5), state5, 4, guard=RunGuard(),
        sync_every_step=True, model="diffusion3d",
        bytes_per_step=teff_bytes(state5[:1]),
    )
finally:
    del os.environ["IGG_HEARTBEAT_EVERY"]
    del os.environ["IGG_PROFILE"]

# The windowed capture landed: per-rank meta with a parseable attribution
# over the real multi-process step program.
from implicitglobalgrid_tpu.utils import profiling as _profiling

_meta_path = os.path.join(
    os.environ["IGG_TELEMETRY_DIR"], _profiling.profile_meta_filename(pid)
)
assert os.path.isfile(_meta_path), f"no capture meta at {_meta_path}"
import json as _json

with open(_meta_path) as _f:
    _meta = _json.load(_f)
assert _meta["rank"] == pid and _meta["window"] == [2, 3], _meta
assert _meta["trace_path"] and os.path.isfile(_meta["trace_path"]), _meta
assert "error" not in _meta["attribution"], _meta["attribution"]
assert _meta["attribution"]["n_device_ops"] > 0, _meta["attribution"]
_snap = tele.snapshot()
assert _snap["gauges"].get("skew.step_seconds_max_over_min", 0.0) >= 1.0, (
    "skew probe did not publish its gauges over the gloo transport",
    _snap["gauges"],
)
assert _snap["gauges"].get("skew.slowest_rank") in (0.0, 1.0), _snap["gauges"]
_trace_path = igg.dump_trace(os.environ["IGG_TELEMETRY_DIR"])
assert _trace_path is not None and os.path.isfile(_trace_path), _trace_path
assert _trace_path.endswith(f"trace.p{pid}.json"), _trace_path

# --- Transport-checksum integrity plane over real gloo hops (ISSUE 18):
# with IGG_INTEGRITY=1 the coalesced packed exchange carries per-hop
# XOR-fold checksum words on the same ppermute payload.  Arm an in-flight
# payload-word flip on block rank 0 (process 0's x=0 corner block): its
# upper-x send lands on block 4, which lives on PROCESS 1 — so the
# RECEIVER (this worker's pid 1) must trip with an IntegrityError that
# implicates the SENDER (rank 0), and its reason=sdc flight bundle must
# carry that attribution for `supervisor.classify`.  Process 0 sends the
# lie and must see nothing locally.  The flip is consumed by one exchange:
# the next checksummed exchange must be clean again (no poisoned cache).
from implicitglobalgrid_tpu.integrity import IntegrityError
from implicitglobalgrid_tpu.ops import halo as _halo

os.environ["IGG_INTEGRITY"] = "1"
try:
    sI, _pI = diffusion3d.setup(NX, NX, NX, init_grid=False)
    TI, CpI = sI[0], sI[1]
    # clean checksummed exchange: zero false positives, and still the
    # bitwise no-op a consistent field demands
    oT, oCp = igg.update_halo(TI + 0, CpI + 0)
    _dmax = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
    assert float(_dmax(oT, TI)) == 0.0, "checksummed exchange not a no-op"
    assert float(_dmax(oCp, CpI)) == 0.0

    _halo.arm_transport_flip(0)
    _trip = None
    try:
        igg.update_halo(TI + 0, CpI + 0)
    except IntegrityError as e:
        _trip = e
    if pid == 1:
        assert _trip is not None, (
            "receiver did not trip on the flipped transport payload"
        )
        assert _trip.implicated_rank == 0, vars(_trip)
        assert _trip.detector == "transport_checksum", vars(_trip)
        _fl = os.path.join(
            os.environ["IGG_TELEMETRY_DIR"], f"flight_{pid}.json"
        )
        assert os.path.isfile(_fl), "no sdc flight bundle on the receiver"
        _sdc = [
            r for r in map(_json.loads, open(_fl))
            if r.get("reason") == "sdc"
        ]
        assert _sdc and _sdc[-1]["info"].get("implicated_rank") == 0, _sdc
        assert tele.snapshot()["counters"].get(
            "integrity.transport_mismatches", 0
        ) >= 1
    else:
        assert _trip is None, (
            f"sender tripped on its own clean receives: {_trip}"
        )

    # flip consumed: the clean cached program serves the next exchange
    oT2, _ = igg.update_halo(TI + 0, CpI + 0)
    assert float(_dmax(oT2, TI)) == 0.0, "post-flip exchange not clean"
finally:
    del os.environ["IGG_INTEGRITY"]

igg.finalize_global_grid()
assert not igg.grid_is_initialized()
assert not dist.is_distributed_initialized()  # finalize tore the runtime down
print(f"WORKER {pid} OK", flush=True)
