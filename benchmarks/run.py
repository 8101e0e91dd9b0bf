"""Benchmark harness: T_eff (GB/s/chip) and weak-scaling efficiency.

The reference publishes only narrative numbers (`/root/reference/README.md:159-164`)
— no benchmark code.  This harness ships the measurements as code so every
number in `BASELINE.md` is reproducible.  One JSON line per config on stdout.

Configs (BASELINE.json):

    diffusion        3-D heat diffusion (configs 1, 2, 5 via --n/--dtype/mesh)
    acoustic         3-D acoustic staggered FDTD, overlap on/off (config 3)
    porous           porous convection PT solver (config 4, HydroMech analogue)
    weak             weak-scaling efficiency over sub-meshes of the available
                     devices (same local size per device, t(1)/t(N))

T_eff convention (ParallelStencil/IGG papers): only arrays that *must* stream
once per iteration count — temperature in+out for diffusion (2 passes);
P,V in+out for acoustic (8); fluxes+pressure in+out per PT iteration for
porous (8) — times local cells per chip, divided by measured time.

Usage:
    python benchmarks/run.py [diffusion|acoustic|porous|weak|all]
        [--n 256] [--steps 100] [--chunk 25] [--dtype float32] [--hide-comm]
"""

from __future__ import annotations

import argparse
import json
import os
import time


def _sync(state):
    import jax

    jax.block_until_ready(state)
    leaf = state[0] if isinstance(state, (tuple, list)) else state
    # Fetch ONE element of the process-local shard: on the earlier remote
    # runtime `block_until_ready` (plain or via a dependent scalar) returned
    # before the compute chain finished, and a fetch was the only honest
    # sync.  Its round trip is cancelled by `_time_steps`' two-point timing.
    shard = leaf.addressable_shards[0].data
    float(shard[(0,) * shard.ndim])


def _time_steps(step, state, chunk: int, reps: int):
    """Per-step time by two-point window timing, median over ``reps``.

    Each rep times a window of K chained ``step`` calls (K*chunk fused steps)
    and a window of 2K calls, both ending in the same `_sync`; their
    difference is K*chunk steps' worth of real device work — including those
    calls' own (pipelined) dispatch, which a production loop pays too — with
    the constant per-window sync round trip cancelled.

    Window sizing was the load-bearing detail on the earlier remote
    runtime: the sync round trip there was large and drifted (~0.05-0.3 s
    observed), and queued work executed *under* it, so windows must be sized
    by device work, not wall time of a synced call.  K targets ~1.5 s of
    estimated pure work per base window, making the residual RTT drift a
    few-percent effect on the difference.  The per-rep differences are
    combined by median (robust to a drift spike in either window of one rep)
    and clamped into the physically possible band derived from the fastest
    2K window (see the comment at the clamp).
    """
    import jax

    state = step(*state)  # compile + warmup
    _sync(state)
    # Virtual-CPU meshes (weak-scaling code-path validation) share one core:
    # a window of unsynced dispatches starves the device threads past the
    # XLA-CPU collective rendezvous timeout.  Sync every call there — CPU
    # timings are code-path checks, not performance numbers.
    leaf = state[0] if isinstance(state, (tuple, list)) else state
    sync_each = leaf.devices().pop().platform == "cpu"

    def run_window(state, ncalls):
        for _ in range(ncalls):
            state = step(*state)
            if sync_each:
                jax.block_until_ready(state)
        _sync(state)
        return state

    # Sync-only round trip: state is already materialized, so this times the
    # fetch RTT alone.  Min over a few samples — a single sample can catch a
    # drift spike and (over-subtracted below) inflate K enormously.
    rtt_est = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(state)
        rtt_est = min(rtt_est, time.perf_counter() - t0)
    # Work-only estimate from one ~20-call window (single sync at the end);
    # subtracting the measured RTT keeps K honest on fast configs, where one
    # RTT can otherwise inflate the estimate severalfold and shrink the
    # window below the work target.  The subtraction is capped at half the
    # elapsed time so a spiky RTT sample can never zero the estimate out.
    ncal = 20
    t0 = time.perf_counter()
    state = run_window(state, ncal)
    elapsed = time.perf_counter() - t0
    t_call_est = (elapsed - min(rtt_est, 0.5 * elapsed)) / ncal
    K = max(4, int(round(1.5 / t_call_est)))
    diffs = []
    b2_min = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        state = run_window(state, K)
        b1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = run_window(state, 2 * K)
        b2 = time.perf_counter() - t0
        b2_min = min(b2_min, b2)
        diffs.append((b2 - b1) / (K * chunk))
    diffs.sort()
    t_it = diffs[len(diffs) // 2]
    # Physical bounds from the fastest 2K window: it ran 2K*chunk steps plus
    # a sync RTT, so per-step time cannot exceed b2_min/(2K*chunk) — and
    # cannot be below (b2_min - rtt_bound)/(2K*chunk) either, which guards
    # against a drift pattern (slow K-windows, fast 2K-windows) driving the
    # median difference toward zero and inflating the reported speed without
    # bound.  rtt_bound is deliberately loose (>3x the worst observed RTT) so
    # the lower clamp only fires on pathological drift, not on honest
    # measurements; with ~3 s 2K windows it caps artifact inflation at ~1.5x.
    rtt_bound = 1.0
    lo = max((b2_min - rtt_bound) / (2 * K * chunk), 1e-9)
    t_it = min(max(t_it, lo), b2_min / (2 * K * chunk))
    # Per-rep spread (VERDICT r3 #7): the raw per-rep differences, pre-clamp,
    # so cross-round drift on the time-shared chip is interpretable from the
    # artifact alone (a tight spread + a >5% cross-round shift = real change;
    # a wide spread = tenancy noise).
    spread = {
        "reps": reps,
        "t_it_ms_min": round(diffs[0] * 1e3, 4),
        "t_it_ms_med": round(diffs[len(diffs) // 2] * 1e3, 4),
        "t_it_ms_max": round(diffs[-1] * 1e3, 4),
    }
    return t_it, state, spread


def _pipelined_provenance(pipelined, fused_k, model_mod, local_shape, itemsize,
                          fused_tile, support_kwargs=None):
    """(metric suffix, extra record) for a ``pipelined`` request.

    Same deterministic-provenance contract as `_fused_provenance`: the
    admissibility check is the model's own (`pipelined_support_error`), so
    a config whose split fell back to the serialized schedule is recorded
    as such instead of labeling a serialized number "pipelined" — and the
    AUTO default's decision is recorded too (``auto-on``/``auto-off``),
    since auto engages the pipelined schedule whenever admissible and an
    unmarked metric would make cross-round drift uninterpretable.  The
    metric-name suffix changes only for an explicit ``pipelined=True``
    (auto keeps prior rounds' names comparable)."""
    if not fused_k:
        return "", None
    bx, by = fused_tile if fused_tile is not None else (None, None)
    err = model_mod.pipelined_support_error(
        tuple(local_shape), fused_k, itemsize, bx, by, **(support_kwargs or {})
    )
    if pipelined is None:
        return "", {"pipelined": "auto-on" if err is None else f"auto-off: {err}"}
    if not pipelined:
        return "", {"pipelined": "off"}
    if err is None:
        return "_piped", {"pipelined": "on"}
    return "", {"pipelined": f"fallback: {err}"}


def _fused_provenance(fused_k, support_error, local_shape, itemsize, fused_tile,
                      z_active=False):
    """Metric suffix + path record for a ``fused_k`` request.

    Deterministic provenance (same envelope checks the model's fallback
    uses): a config the kernel envelope rejects ran the warn-once XLA
    cadence, and the emitted metric name must say so — otherwise an XLA
    number gets recorded under a fused-kernel label.  ``z_active`` mirrors
    the model's path selection: on z-communicating grids the z-patch
    envelope is consulted first (it admits full-y tiles the plain envelope
    does not, and vice versa at large volumes).
    """
    if not fused_k:
        return "", None
    bx, by = fused_tile if fused_tile is not None else (None, None)
    shape = tuple(local_shape)
    ok = support_error(shape, fused_k, itemsize, bx, by) is None
    if z_active and not ok:
        ok = support_error(shape, fused_k, itemsize, bx, by, zpatch=True) is None
    if ok:
        return f"_fused{fused_k}", "pallas-fused"
    return f"_fused{fused_k}fb", "xla-fallback"


def _grid_kwargs(overlap, period):
    """Shared setup kwargs for overlap/period CLI knobs (one definition for
    all three benchmarks).  Validates the period axis letters eagerly — a
    typo'd axis would otherwise surface as an opaque setup() TypeError."""
    kw = {} if overlap is None else dict(
        overlapx=overlap, overlapy=overlap, overlapz=overlap
    )
    for ax in period or "":
        if ax not in "xyz":
            raise ValueError(f"--period axes must be from 'xyz', got {period!r}")
        kw[f"period{ax}"] = 1
    return kw


def _emit(name, teff, t_it, extra=None, emit=True):
    rec = {
        "metric": name,
        "value": round(teff, 2),
        "unit": "GB/s/chip",
        "t_it_ms": round(t_it * 1e3, 4),
    }
    if extra:
        rec.update(extra)
    # Fold every emitted measurement into the process telemetry registry
    # (docs/observability.md): the driver's final snapshot then carries the
    # same numbers the JSON lines do — one source of truth for collectors.
    from implicitglobalgrid_tpu.utils import telemetry as _telemetry

    _telemetry.gauge(f"bench.{name}.teff_gbs").set(teff)
    _telemetry.histogram("bench.teff_gbs").record(teff)
    _telemetry.histogram("bench.t_it_s").record(t_it)
    if emit:
        print(json.dumps(rec), flush=True)
    return rec


def bench_diffusion(n=256, chunk=25, reps=4, dtype="float32", hide_comm=False,
                    devices=None, emit=True, fused_k=None, fused_tile=None,
                    exchange_every=1, overlap=None, force_spmd=False, period=None,
                    pipelined=None):
    """Benchmarks run with ``donate=False``: buffer donation cost ~3x on the
    earlier remote runtime of the round-1..5 measurements (375 -> 119 GB/s
    at 256^3 f32; identical HLO, runtime-side penalty; not re-measured on
    the attached chip), and T_eff measures streaming, not allocation.

    ``fused_k``: use the temporally-blocked Pallas kernel (k steps per HBM
    pass) — the lever that takes T_eff past the raw streaming bound.
    """
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    okw = _grid_kwargs(overlap, period)
    state, params = diffusion3d.setup(
        n, n, n, dtype=jax.numpy.dtype(dtype), hide_comm=hide_comm, quiet=True,
        devices=devices, force_spmd=force_spmd, **okw,
    )
    step = diffusion3d.make_multi_step(
        params, chunk, donate=False, fused_k=fused_k, fused_tile=fused_tile,
        exchange_every=exchange_every, pipelined=pipelined,
    )
    from implicitglobalgrid_tpu.ops.pallas_stencil import fused_support_error

    from implicitglobalgrid_tpu.ops.halo import dim_has_halo_activity

    fsuf, fpath = _fused_provenance(
        fused_k, fused_support_error, igg.local_shape(state[0]),
        jax.numpy.dtype(dtype).itemsize, fused_tile,
        z_active=dim_has_halo_activity(igg.get_global_grid(), 2),
    )
    psuf, prec = _pipelined_provenance(
        pipelined, fused_k, diffusion3d, igg.local_shape(state[0]),
        jax.numpy.dtype(dtype).itemsize, fused_tile,
    )
    t_it, state, spread = _time_steps(step, state, chunk, reps)
    gg = igg.get_global_grid()
    igg.finalize_global_grid()
    nbytes = 2 * n**3 * jax.numpy.dtype(dtype).itemsize
    extra = {"dims": list(gg.dims), "nprocs": gg.nprocs, "spread": spread}
    if fpath:
        extra["path"] = fpath
    if prec:
        extra.update(prec)
    return _emit(
        f"diffusion3d_{n}_{dtype}"
        + (f"_period{period}" if period else "")
        + ("_overlap" if hide_comm else "")
        + fsuf
        + psuf
        + (f"_xch{exchange_every}" if exchange_every > 1 else ""),
        nbytes / t_it / 1e9,
        t_it,
        extra,
        emit=emit,
    )


def bench_acoustic(n=192, chunk=25, reps=4, dtype="float32", hide_comm=False, devices=None,
                   emit=True, exchange_every=1, overlap=None, fused_k=None,
                   fused_tile=None, period=None, pipelined=None):
    """``fused_k``: the temporally-blocked staggered Pallas kernel
    (`ops/pallas_leapfrog.py`, k leapfrog steps per HBM pass) — needs
    ``n % 128 == 0`` in the minor dimension (use ``--n 256``)."""
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import acoustic3d

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    okw = _grid_kwargs(overlap, period)
    state, params = acoustic3d.setup(
        n, n, n, dtype=jax.numpy.dtype(dtype), hide_comm=hide_comm, quiet=True,
        devices=devices, **okw,
    )
    step = acoustic3d.make_multi_step(
        params, chunk, donate=False, exchange_every=exchange_every,
        fused_k=fused_k, fused_tile=fused_tile, pipelined=pipelined,
    )
    from implicitglobalgrid_tpu.ops.pallas_leapfrog import fused_support_error

    from implicitglobalgrid_tpu.ops.halo import dim_has_halo_activity

    fsuf, fpath = _fused_provenance(
        fused_k, fused_support_error, igg.local_shape(state[0]),
        jax.numpy.dtype(dtype).itemsize, fused_tile,
        z_active=dim_has_halo_activity(igg.get_global_grid(), 2),
    )
    psuf, prec = _pipelined_provenance(
        pipelined, fused_k, acoustic3d, igg.local_shape(state[0]),
        jax.numpy.dtype(dtype).itemsize, fused_tile,
    )
    t_it, state, spread = _time_steps(step, state, chunk, reps)
    gg = igg.get_global_grid()
    igg.finalize_global_grid()
    nbytes = 8 * n**3 * jax.numpy.dtype(dtype).itemsize  # P,Vx,Vy,Vz in+out
    extra = {"dims": list(gg.dims), "nprocs": gg.nprocs, "spread": spread}
    if fpath:
        extra["path"] = fpath
    if prec:
        extra.update(prec)
    return _emit(
        f"acoustic3d_{n}_{dtype}"
        + (f"_period{period}" if period else "")
        + ("_overlap" if hide_comm else "")
        + fsuf
        + psuf
        + (f"_xch{exchange_every}" if exchange_every > 1 else ""),
        nbytes / t_it / 1e9,
        t_it,
        extra,
        emit=emit,
    )


def bench_porous(n=128, chunk=4, reps=3, npt=10, dtype="float32", devices=None,
                 emit=True, exchange_every=1, overlap=None, fused_k=None,
                 fused_tile=None, period=None, pipelined=None):
    """``chunk`` whole time steps (= ``chunk*npt`` PT iterations) per call via
    `porous_convection3d.make_multi_step` — one XLA program, like the other
    models' production paths.  ``fused_k``: the temporally-blocked PT kernel
    (`ops/pallas_pt.py`; needs ``n % 128 == 0`` — use ``--n 256``)."""
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import porous_convection3d as pc

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    okw = _grid_kwargs(overlap, period)
    state, params = pc.setup(
        n, n, n, dtype=jax.numpy.dtype(dtype), npt=npt, quiet=True, devices=devices,
        **okw,
    )
    step = pc.make_multi_step(
        params, chunk, donate=False, exchange_every=exchange_every,
        fused_k=fused_k, fused_tile=fused_tile, pipelined=pipelined,
    )
    from implicitglobalgrid_tpu.ops.pallas_pt import fused_support_error

    from implicitglobalgrid_tpu.ops.halo import dim_has_halo_activity

    fsuf, fpath = _fused_provenance(
        fused_k, fused_support_error, igg.local_shape(state[0]),
        jax.numpy.dtype(dtype).itemsize, fused_tile,
        z_active=dim_has_halo_activity(igg.get_global_grid(), 2),
    )
    psuf, prec = _pipelined_provenance(
        pipelined, fused_k, pc, igg.local_shape(state[0]),
        jax.numpy.dtype(dtype).itemsize, fused_tile,
        support_kwargs={"npt": npt},
    )
    t_step, state, spread = _time_steps(step, state, chunk, reps)
    gg = igg.get_global_grid()
    igg.finalize_global_grid()
    # Per PT iteration: qDx,qDy,qDz,Pf in+out = 8 array passes.
    t_pt = t_step / npt
    nbytes = 8 * n**3 * jax.numpy.dtype(dtype).itemsize
    extra = {"dims": list(gg.dims), "nprocs": gg.nprocs,
             "t_pt_ms": round(t_pt * 1e3, 4), "spread": spread}
    if fpath:
        extra["path"] = fpath
    if prec:
        extra.update(prec)
    return _emit(
        f"porous_convection3d_{n}_{dtype}_npt{npt}"
        + (f"_period{period}" if period else "")
        + fsuf
        + psuf
        + (f"_xch{exchange_every}" if exchange_every > 1 else ""),
        nbytes / t_pt / 1e9,
        t_step,
        extra,
        emit=emit,
    )


def bench_tuned_vs_default(model="diffusion", n=256, chunk=24, reps=3,
                           dtype="float32", npt=12, overlap=None, period=None,
                           emit=True):
    """ISSUE 13: the autotuner's closed loop — time the DEFAULT-config
    production chunk and the ``autotune=True`` chunk at the same point and
    record the ratio.  ``tuned_speedup = t_default / t_tuned`` is a gated
    perf key (`analysis.perf.GATED_KEYS`): a tuner that starts picking
    slower-than-default configs (or a regression erasing a tuned win)
    drops the ratio past the band and fails `scripts/check_perf.py`.

    Both runs share one grid and start from fresh `setup` states; the
    tuned build resolves through the winner cache (`IGG_TUNE_CACHE` — a
    prior `igg_tune.py seed`/sweep makes this a pure cache hit, a cold
    cache pays one short search, and the record says which happened).
    """
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import (
        acoustic3d,
        diffusion3d,
        porous_convection3d,
    )
    from implicitglobalgrid_tpu.utils import telemetry as _tele

    mod, model_name, setup_kw = {
        "diffusion": (diffusion3d, "diffusion3d", {}),
        "acoustic": (acoustic3d, "acoustic3d", {}),
        "porous": (porous_convection3d, "porous_convection3d",
                   {"npt": npt}),
    }[model]
    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    okw = _grid_kwargs(overlap, period)
    state, params = mod.setup(
        n, n, n, dtype=jax.numpy.dtype(dtype), quiet=True, **setup_kw, **okw
    )

    def _counters():
        snap = _tele.snapshot()
        return {k: v for k, v in snap.get("counters", {}).items()
                if k.startswith("tune.")}

    before = _counters()
    step_default = mod.make_multi_step(params, chunk, donate=False)
    t_def, _, spread_def = _time_steps(step_default, state, chunk, reps)
    step_tuned = mod.make_multi_step(params, chunk, donate=False,
                                     autotune=True)
    after = _counters()
    state2, _ = mod.setup(n, n, n, dtype=jax.numpy.dtype(dtype),
                          init_grid=False, **setup_kw)
    t_tun, _, spread_tun = _time_steps(step_tuned, state2, chunk, reps)

    from implicitglobalgrid_tpu import tuning

    gg = igg.get_global_grid()
    key = tuning.make_key(
        model_name, gg.nxyz, jax.numpy.dtype(dtype), gg=gg,
        extra={"npt": int(npt)} if model == "porous" else None,
        nsteps=chunk,
    )
    entry = tuning.TuneCache().lookup(key)
    igg.finalize_global_grid()
    hits = after.get("tune.cache_hit", 0) - before.get("tune.cache_hit", 0)
    rec = {
        "model": model_name,
        "n": n,
        "tuned_speedup": round(t_def / t_tun, 4),
        "t_default_ms": round(t_def * 1e3, 4),
        "t_tuned_ms": round(t_tun * 1e3, 4),
        "config": entry["config"] if entry else {},
        "source": entry["source"] if entry else None,
        "cache": "hit" if hits else "miss",
        "spread": {"default": spread_def, "tuned": spread_tun},
    }
    if emit:
        print(json.dumps({"metric": f"{model_name}_{n}_{dtype}_tuned_vs_default",
                          "value": rec["tuned_speedup"], "unit": "x", **rec}),
              flush=True)
    return rec


#: Standard member job length (steps) the members/s/chip figure normalizes
#: to: members_per_s = B / (t_step * BATCH_JOB_STEPS) / nchips — a
#: completed-standard-jobs-per-second rate, so the sweep is comparable
#: across rounds whatever chunk the timing used.
BATCH_JOB_STEPS = 100


def bench_batch(n=128, chunk=16, reps=3, dtype="float32", B_list=(1, 2, 4, 8),
                emit=True, fused_k=None, fused_tile=None, exchange_every=1,
                overlap=None, period=None):
    """Batched ensemble serving throughput (ISSUE 8): members/s/chip over a
    B sweep of the vmapped diffusion cadence (`make_multi_step(batch=True)`,
    the `serving.ServingLoop` round step).

    The claim under test: batching is a near-free throughput multiplier —
    B members cost ONE collective pair per exchanged dimension (see the
    ``batch_hlo`` A/B for the structural proof), so members/s/chip should
    scale ~×B until the batch saturates HBM.  ``extras.sweep`` records one
    row per B (each row's ``members_per_s`` is a gated perf metric,
    `analysis.perf.GATED_KEYS`); the headline value is the best B's rate,
    with ``throughput_multiplier`` = best/B1.
    """
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import _batched, diffusion3d
    from implicitglobalgrid_tpu.utils import telemetry as _telemetry

    okw = _grid_kwargs(overlap, period)
    sweep = {}
    nprocs = 1
    for B in B_list:
        if igg.grid_is_initialized():
            igg.finalize_global_grid()
        bstate, params = _batched.batched_setup(
            diffusion3d, n, n, n, batch=B,
            dtype=jax.numpy.dtype(dtype), quiet=True, **okw,
        )
        step = diffusion3d.make_multi_step(
            params, chunk, donate=False, batch=True, fused_k=fused_k,
            fused_tile=fused_tile, exchange_every=exchange_every,
        )
        t_it, _state, spread = _time_steps(step, bstate, chunk, reps)
        gg = igg.get_global_grid()
        nprocs = gg.nprocs
        igg.finalize_global_grid()
        members_per_s = B / (t_it * BATCH_JOB_STEPS) / nprocs
        sweep[f"B{B}"] = {
            "members_per_s": round(members_per_s, 4),
            "member_steps_per_s": round(B / t_it / nprocs, 2),
            "t_step_ms": round(t_it * 1e3, 4),
            "spread": spread,
        }
        _telemetry.gauge(f"bench.batch.B{B}.members_per_s").set(
            members_per_s
        )
    b1 = sweep.get("B1", {}).get("members_per_s") or None
    best_key = max(sweep, key=lambda k: sweep[k]["members_per_s"])
    best = sweep[best_key]["members_per_s"]
    rec = {
        "metric": f"diffusion3d_batch_{n}_{dtype}",
        "value": best,
        "unit": "members/s/chip",
        "members_per_s": best,
        "best_B": int(best_key[1:]),
        "job_steps": BATCH_JOB_STEPS,
        "nprocs": nprocs,
        "sweep": sweep,
        "throughput_multiplier": round(best / b1, 3) if b1 else None,
    }
    if emit:
        print(json.dumps(rec), flush=True)
    return rec


def batch_hlo_ab(B=8, emit=True):
    """The batched exchange's compiled-HLO collective A/B (ISSUE 8
    acceptance): the B-member coalesced exchange must emit EXACTLY the
    unbatched program's collective-permute count, with payload bytes ×B.
    Structural (XLA:CPU 8-device mesh) — run it from any backend via the
    subprocess driver (`bench.py`'s `_cpu_mesh_json`)."""
    from implicitglobalgrid_tpu.analysis import ir
    from implicitglobalgrid_tpu.analysis.costmodel import text_census

    c1 = text_census(ir.compile_program(ir.EXCHANGE_HLO_PROGRAM).text)
    cB = text_census(ir._compile_batched_exchange_program(B=B).text)
    rec = {
        "metric": "batch_hlo_collectives_ab",
        "B": B,
        "b1_collective_permutes": c1["collective_permutes"],
        "bB_collective_permutes": cB["collective_permutes"],
        "collectives_equal": (
            c1["collective_permutes"] == cB["collective_permutes"]
        ),
        "b1_payload_bytes": c1["collective_payload_bytes"],
        "bB_payload_bytes": cB["collective_payload_bytes"],
        "payload_ratio": round(
            cB["collective_payload_bytes"]
            / max(c1["collective_payload_bytes"], 1),
            3,
        ),
    }
    if emit:
        print(json.dumps(rec), flush=True)
    return rec


def bench_halo_coalesce(n=32, width=2, reps=3, emit=True):
    """Coalesced-vs-per-field exchange A/B (ISSUE 5) on the porous 5-field
    shape set, with collective counts and per-hop payload bytes read from
    the OPTIMIZED HLO of each variant's exchange program.

    Runs on whatever mesh the backend offers (dims (2,2,2) + periodic z on
    the suite's 8-device layout — every dimension exchanges).  On a 1-chip
    backend all partners are self-copies and NO collectives exist either
    way, so `bench.py` drives this on the virtual 8-device CPU mesh in a
    subprocess (a CODE-PATH/structure record there — CPU wall times are
    not performance numbers; the structural counts are the point).
    """
    import jax
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.ops import halo as H
    from implicitglobalgrid_tpu.utils.hlo_analysis import collective_payloads

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    ndev = len(jax.devices())
    dims = dict(dimx=2, dimy=2, dimz=2) if ndev >= 8 else {}
    igg.init_global_grid(n, n, n, periodz=1, quiet=True,
                         overlapx=2 * width, overlapy=2 * width,
                         overlapz=2 * width, **dims)
    gg = igg.get_global_grid()
    rng = np.random.default_rng(0)
    shapes = [(n, n, n)] + [
        tuple(n + (1 if d == ax else 0) for d in range(3)) for ax in range(3)
    ] + [(n, n, n)]
    from jax.sharding import NamedSharding, PartitionSpec as P

    fields = tuple(
        jax.device_put(
            rng.random(tuple(gg.dims[d] * s[d] for d in range(3)))
            .astype(np.float32),
            NamedSharding(gg.mesh, P(*igg.AXIS_NAMES[:3])),
        )
        for s in shapes
    )
    sig = tuple((H.local_shape(A, gg), str(A.dtype)) for A in fields)
    rec = {"metric": f"halo_coalesce_ab_5field_{n}cube_w{width}",
           "nfields": len(fields), "dims": list(gg.dims)}
    for name, coalesce in (("per_field", False), ("coalesced", True)):
        fn = H._global_update_fn(gg, sig, width, False, coalesce)
        hlo = fn.lower(*fields).compile().as_text()
        hops = collective_payloads(hlo)
        t_call, _, spread = _time_steps(
            lambda *fs: fn(*fs), fields, 1, reps
        )
        rec[name] = {
            "n_collective_permutes": len(hops),
            "payload_bytes_total": sum(h["bytes"] for h in hops),
            "t_call_ms": round(t_call * 1e3, 4),
            "spread": spread,
        }
    igg.finalize_global_grid()
    rec["collectives_ratio"] = round(
        rec["per_field"]["n_collective_permutes"]
        / max(rec["coalesced"]["n_collective_permutes"], 1), 2
    )
    from implicitglobalgrid_tpu.utils import telemetry as _telemetry

    _telemetry.gauge("bench.halo_coalesce_ab.collectives_ratio").set(
        rec["collectives_ratio"]
    )
    if emit:
        print(json.dumps(rec), flush=True)
    return rec


def bench_diffusion_grad(n=256, chunk=8, reps=3, dtype="float32", fused_k=4,
                         overlap=None, period=None, emit=True):
    """Gradient-path throughput record (`fused_with_xla_grad`): time
    ``jax.grad`` through the fused cadence against the forward step, so an
    adjoint user can predict step cost (docs/performance.md's gradient-path
    row).  The backward pass recomputes + differentiates the XLA-cadence
    twin (rematerialization), so the expected cost is roughly one fused
    forward + two XLA-cadence-scale passes.
    """
    import jax
    import jax.numpy as jnp

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    okw = _grid_kwargs(overlap, period)
    state, params = diffusion3d.setup(
        n, n, n, dtype=jax.numpy.dtype(dtype), quiet=True, **okw
    )
    step = diffusion3d.make_multi_step(
        params, chunk, donate=False, fused_k=fused_k
    )
    t_fwd, state, spread_f = _time_steps(step, state, chunk, reps)

    gfn = jax.jit(jax.grad(lambda T, Cp: jnp.sum(step(T, Cp)[0])))

    def gstep(T, Cp):
        # the gradient wrt T feeds back as the next "T": diffusion's VJP is
        # value-independent (linear model), so this is a pure timing loop
        return gfn(T, Cp), Cp

    t_grad, _, spread_g = _time_steps(gstep, state, chunk, reps)
    igg.finalize_global_grid()
    nbytes = 2 * n**3 * jax.numpy.dtype(dtype).itemsize
    rec = _emit(
        f"diffusion3d_grad_{n}_{dtype}_fused{fused_k}"
        + (f"_period{period}" if period else ""),
        nbytes / t_grad / 1e9,  # A_eff convention applied to the grad step
        t_grad,
        {
            "t_fwd_ms": round(t_fwd * 1e3, 4),
            "grad_over_fwd": round(t_grad / t_fwd, 3),
            "spread": spread_g,
            "fwd_spread": spread_f,
            "note": (
                "value = A_eff/t of ONE grad step (forward fused chunk + "
                "rematerialized XLA-cadence forward + backward)"
            ),
        },
        emit=emit,
    )
    return rec


def aot_weak_proxy(dims=(4, 4, 16), nloc=512, k=4, emit=True, pipelined=None):
    """North-star-topology AOT compile proxy (VERDICT r4 missing #2).

    Compile the production fused z-patch cadence for a 256-chip
    ``dims``-mesh at BASELINE config 5's per-chip volume (``nloc``^3 f32)
    and report the program's collective-permute hops with per-hop payload
    BYTES from the optimized HLO.  This is a STRUCTURAL record, not a
    measurement — multi-chip hardware is unavailable here; what it
    establishes is that (a) the north-star program compiles, (b) the z
    exchange moves packed thin slabs (not full arrays), and (c) the hop
    payloads feed the written efficiency budget in docs/performance.md.
    Uses the shared synthetic-GlobalGrid AOT scaffold
    (`implicitglobalgrid_tpu.utils.aot`), like scripts/verify_tpu.py's
    checks 9-11.

    ``pipelined``: forward the cadence knob and additionally report
    `pipelined_overlap_evidence` — the count of (collective, kernel
    launch) pairs the optimized HLO leaves mutually independent, i.e. the
    interior passes XLA may schedule across the in-flight
    `collective-permute`s.  A serialized compile of the same config is the
    differential control (`bench.py` records both).
    """
    import math as _math

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from implicitglobalgrid_tpu.utils.aot import synthetic_topology_grid
    from implicitglobalgrid_tpu.utils.hlo_analysis import (
        collective_payloads,
        pipelined_overlap_evidence,
    )

    nchips = _math.prod(dims)
    o = 2 * k
    with synthetic_topology_grid(dims, (nloc,) * 3, (o,) * 3) as (gg, mesh):
        from implicitglobalgrid_tpu.models import diffusion3d

        params = diffusion3d.Params(
            dx=0.1, dy=0.1, dz=0.1, dt=0.1 * 0.1 / 8.1,
            dtype=jax.numpy.float32,
        )
        step = diffusion3d.make_multi_step(
            params, k, donate=False, fused_k=k, pipelined=pipelined
        )
        shapes = tuple(
            jax.ShapeDtypeStruct(
                tuple(dims[d] * nloc for d in range(3)),
                jax.numpy.float32,
                sharding=NamedSharding(mesh, P("x", "y", "z")),
            )
            for _ in range(2)
        )
        txt = step.lower(*shapes).compile().as_text()
        psel = diffusion3d.pipelined_support_error((nloc,) * 3, k, 4, gg=gg)

    hops = collective_payloads(txt)
    by_shape: dict = {}
    for h in hops:
        r = by_shape.setdefault(h["shape"], {"count": 0, "bytes_per_hop": h["bytes"]})
        r["count"] += 1
    total = sum(h["bytes"] for h in hops)
    rec = {
        "metric": f"aot_weak_proxy_{nchips}chip_{nloc}cube"
        + ("_piped" if pipelined and psel is None else ""),
        "dims": list(dims),
        "n_collective_permutes": len(hops),
        "per_hop": by_shape,
        "total_exchange_bytes_per_chunk": total,
        "note": (
            "structural AOT compile record at the north-star topology — "
            "NOT a timing; see docs/performance.md's weak-scaling budget"
        ),
    }
    if pipelined is not None:
        rec["pipelined"] = (
            "on" if (pipelined and psel is None)
            else ("off" if not pipelined else f"fallback: {psel}")
        )
        rec["overlap_evidence"] = pipelined_overlap_evidence(txt)
    if emit:
        print(json.dumps(rec), flush=True)
    return rec


def bench_profile_attribution(n=16, steps=6, emit=True):
    """ISSUE 15: the measured device-timeline record — a windowed profiler
    capture around a short diffusion run on THIS backend's communicating
    mesh, parsed into per-scope device-time attribution and the measured
    comm/compute overlap fraction (`utils.profiling`, docs/observability.md
    "Device timeline").  On the virtual CPU mesh the numbers are code-path
    records (one core timeshares the devices), but the overlap fraction is
    still the real union-intersection of the capture's collective vs kernel
    intervals — the measured twin of `hlo_analysis.
    pipelined_overlap_evidence`'s structural count, and the number ROADMAP
    item 5(c) wants next to ``efficiency...achieved_fraction``.
    """
    import tempfile

    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.utils import profiling

    import shutil

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    igg.init_global_grid(n, n, n, quiet=True)
    logdir = tempfile.mkdtemp(prefix="igg_profile_attr_")
    try:
        state, params = diffusion3d.setup(n, n, n, init_grid=False)
        step = diffusion3d.make_step(params, donate=False)
        state = jax.block_until_ready(step(*state))  # compile OUTSIDE the window
        with profiling.profile_trace(logdir):
            for _ in range(steps):
                state = jax.block_until_ready(step(*state))
        rec = profiling.attribute_capture(logdir)
        profiling.publish_attribution(rec)
    finally:
        igg.finalize_global_grid()
        shutil.rmtree(logdir, ignore_errors=True)  # captures are MBs per run
    out = {
        "metric": "profile_attribution",
        "value": rec["overlap"]["fraction"],
        "unit": "overlap_fraction",
        "n": n,
        "steps": steps,
        # flat twin of overlap.fraction: the REPORTED perf-gate key
        # (analysis.perf.REPORTED_KEYS walks extras for this exact name)
        "overlap_fraction": rec["overlap"]["fraction"],
        "scope_seconds": rec["scope_seconds"],
        "overlap": rec["overlap"],
        "n_device_ops": rec["n_device_ops"],
        "device_seconds": rec["device_seconds"],
    }
    if emit:
        print(json.dumps(out), flush=True)
    return out


def bench_weak_scaling(n=128, chunk=25, reps=4, dtype="float32", hide_comm=False,
                       model="diffusion", npt=10):
    """Weak scaling: same local n^3 per device on growing sub-meshes.

    Parallel efficiency = t(1 device) / t(N devices); ~1.0 means the halo
    exchange is fully hidden or negligible.  All counts run ``force_spmd``
    so the 1-device baseline goes through the same shard_map/SPMD execution
    path as the multi-device runs — otherwise the 1-device fast path (see
    docs/performance.md) would make the ratio conflate SPMD dispatch
    overhead with communication cost.

    ``model="porous"`` runs the HydroMech analogue instead — BASELINE
    config 4 is *porous* weak scaling (npt PT iterations per step, the
    communication-heaviest pattern); the porous model has no force_spmd
    lever, so its 1-device point keeps the plain-jit fast path and the
    reported efficiency is conservative on 1-core virtual meshes.
    """
    import jax

    devs = jax.devices()
    counts = []
    c = 1
    while c <= len(devs):
        counts.append(c)
        c *= 2
    if counts[-1] != len(devs):  # non-power-of-two: still measure the full mesh
        counts.append(len(devs))
    results = {}
    for c in counts:
        if model == "porous":
            rec = bench_porous(
                n=n, chunk=max(chunk // npt, 1), reps=reps, npt=npt,
                dtype=dtype, devices=devs[:c],
            )
        else:
            rec = bench_diffusion(
                n=n, chunk=chunk, reps=reps, dtype=dtype, hide_comm=hide_comm,
                devices=devs[:c], force_spmd=True,
            )
        results[c] = rec["t_it_ms"]
    base = results[1]
    effs = {c: round(base / t, 4) for c, t in results.items()}
    print(
        json.dumps(
            {
                "metric": f"weak_scaling_{model}3d_{n}_{dtype}"
                + ("_overlap" if hide_comm else ""),
                "value": effs[counts[-1]],
                "unit": "parallel_efficiency",
                "per_count": effs,
            }
        ),
        flush=True,
    )


def main():
    p = argparse.ArgumentParser()
    p.add_argument("what", nargs="?", default="all",
                   choices=["diffusion", "acoustic", "porous", "weak",
                            "coalesce", "grad", "batch", "batch_hlo",
                            "reconcile", "tuned", "profile", "all"])
    p.add_argument("--model", default="diffusion",
                   choices=["diffusion", "acoustic", "porous"],
                   help="model for the tuned mode (tuned-vs-default A/B)")
    p.add_argument("--batch-sizes", default="1,2,4,8",
                   help="comma-separated B sweep for the batch mode")
    p.add_argument("--n", type=int, default=None)
    # None sentinel: per-mode defaults below (25 everywhere; 24 for the
    # tuned A/B, whose cadence candidates need a ladder-divisible chunk)
    p.add_argument("--chunk", type=int, default=None)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument("--dtype", default="float32")
    p.add_argument("--hide-comm", action="store_true")
    p.add_argument("--npt", type=int, default=10)
    p.add_argument("--fused-k", type=int, default=None,
                   help="temporally-blocked Pallas kernel: k steps per HBM pass")
    p.add_argument("--exchange-every", type=int, default=1,
                   help="XLA slab cadence: w steps per width-w halo exchange "
                        "(needs a deep-halo grid: --overlap >= 2w)")
    p.add_argument("--overlap", type=int, default=None,
                   help="grid overlap in every dimension (deep halos for "
                        "--fused-k/--exchange-every on communicating grids)")
    p.add_argument("--period", default=None,
                   help="periodic dimensions, e.g. 'z' or 'xz' (the 1-chip "
                        "self-neighbor configs that exercise real exchanges)")
    p.add_argument("--pipelined", action="store_true", default=None,
                   help="boundary-first pipelined group schedule (fused_k "
                        "cadences); omit for the models' auto default")
    p.add_argument("--serialized", dest="pipelined", action="store_false",
                   help="force the serialized group schedule")
    p.add_argument("--weak-model", default="diffusion",
                   choices=["diffusion", "porous"],
                   help="model for the weak-scaling config (BASELINE config 4 "
                        "is porous weak scaling)")
    a = p.parse_args()
    tuned_chunk = 24 if a.chunk is None else a.chunk
    if a.chunk is None:
        a.chunk = 25  # the historical default of every other mode
    kw = dict(chunk=a.chunk, reps=a.reps, dtype=a.dtype)
    if a.what in ("diffusion", "all"):
        bench_diffusion(n=a.n or 256, hide_comm=a.hide_comm, fused_k=a.fused_k,
                        exchange_every=a.exchange_every, overlap=a.overlap,
                        period=a.period, pipelined=a.pipelined, **kw)
    if a.what in ("acoustic", "all"):
        bench_acoustic(n=a.n or (256 if a.fused_k else 192), hide_comm=a.hide_comm,
                       fused_k=a.fused_k, exchange_every=a.exchange_every,
                       overlap=a.overlap, period=a.period, pipelined=a.pipelined,
                       **kw)
    if a.what in ("porous", "all"):
        # porous steps contain npt inner iterations, so the outer chunk stays
        # small unless the user asked for porous explicitly
        porous_chunk = a.chunk if a.what == "porous" else 4
        # npt need not divide fused_k anymore: the ragged PT schedule
        # (round 4) chunks any npt into even kernel chunks.
        npt = a.npt
        bench_porous(n=a.n or (256 if a.fused_k else 128), chunk=porous_chunk,
                     reps=a.reps, npt=npt, dtype=a.dtype, fused_k=a.fused_k,
                     exchange_every=a.exchange_every, overlap=a.overlap,
                     period=a.period, pipelined=a.pipelined)
    if a.what in ("weak", "all"):
        bench_weak_scaling(n=a.n or 128, chunk=a.chunk, reps=a.reps,
                           dtype=a.dtype, hide_comm=a.hide_comm,
                           model=a.weak_model, npt=a.npt)
    if a.what == "coalesce":
        bench_halo_coalesce(n=a.n or 32, reps=a.reps)
    if a.what == "batch":
        bench_batch(
            n=a.n or 128, chunk=a.chunk, reps=a.reps, dtype=a.dtype,
            B_list=tuple(int(b) for b in a.batch_sizes.split(",")),
            fused_k=a.fused_k, exchange_every=a.exchange_every,
            overlap=a.overlap, period=a.period,
        )
    if a.what == "batch_hlo":
        batch_hlo_ab()
    if a.what == "profile":
        # Device-timeline attribution (ISSUE 15): windowed capture ->
        # per-scope device seconds + measured overlap fraction, one JSON
        # line (bench.py runs this on the virtual CPU mesh as
        # extras.profile_attribution).
        bench_profile_attribution(n=a.n or 16)
    if a.what == "reconcile":
        # Cost-model reconciliation (ISSUE 10): fresh XLA:CPU compiles of
        # the cadence matrix -> achieved_fraction per model, one JSON line
        # (bench.py runs this mode on the virtual CPU mesh and joins the
        # result with its measured teffs as extras.efficiency).
        from implicitglobalgrid_tpu.analysis.reconcile import reconcile_report

        print(json.dumps(reconcile_report(source="compiled")), flush=True)
    if a.what == "grad":
        bench_diffusion_grad(n=a.n or 256, chunk=a.chunk, reps=a.reps,
                             dtype=a.dtype, fused_k=a.fused_k or 4,
                             overlap=a.overlap, period=a.period)
    if a.what == "tuned":
        # the other modes' default chunk (25) divides NO fused_k rung — the
        # tuned A/B defaults to a cadence-friendly 24; an EXPLICIT --chunk
        # (25 included) is always honored
        bench_tuned_vs_default(
            model=a.model, n=a.n or 256, chunk=tuned_chunk, reps=a.reps,
            dtype=a.dtype, npt=a.npt, overlap=a.overlap, period=a.period,
        )


if __name__ == "__main__":
    # Direct invocation (`python benchmarks/run.py ...`) puts benchmarks/ on
    # sys.path, not the repo root where the package lives.
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    main()
