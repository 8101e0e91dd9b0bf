"""Chip smoke: drive the solver's main path once on the attached TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the decomposed grid only

One process owns the chip and starts no JAX children.  There is no CPU
fallback: a run that finds no TPU exits non-zero and names the platform it
found.  Each phase prints one JSON line with its name, its set-up seconds,
the max error it measured and the check it passed; the last line is the
device record ``{"ok": true, "device": {...}}``, printed only when every
check passed.  Nothing printed here is a speed.

Phases (local sizes are the reference's per-GPU 256^3 headline and
BASELINE config 5's 512^3 per chip, f32):

* ``three_function`` -- the README's path: `init_global_grid` with a
  periodic z, an `igg.stencil` diffusion step with `update_halo`, `gather`,
  `finalize_global_grid`.  Checks the self-neighbor planes bit for bit and
  the field against a plain `jnp` periodic diffusion written here.
* ``*_fused`` -- each model's Pallas cadence (`make_multi_step(fused_k=k)`)
  against the same model's XLA cadence from the same initial state.
  The fallback warnings are errors here, and the compiled program must
  hold a Mosaic kernel (``tpu_custom_call``).
* ``ensemble_fused`` -- `make_multi_step(..., fused_k=k, batch=True)` for
  B=2 members against two independent runs, bit for bit.

``--four-chips`` decomposes one grid over four chips (auto dims (2,2,1))
and compares with the same global grid on one chip of the same process:
the XLA cadence bit for bit, the pipelined fused cadence within tolerance.

Compilation: every program is lowered first and compiled in parallel
threads into the persistent compile cache (`enable_compile_cache`); the
phases then run through the public entry points, which read the cache.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

#: Fused-vs-XLA and script-reference tolerance: max |a - b| over
#: max |reference|, per field.  The cadences differ by a few f32 ULPs per
#: step (the kernels fold constants differently and XLA contracts FMAs per
#: program); 1e-5 is ~80 ULPs of headroom over a few dozen steps.
REL_TOL = 1e-5


class SmokeFailure(Exception):
    pass


def _fail(msg: str):
    raise SmokeFailure(msg)


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        _fail(f"shape {got.shape} != reference shape {ref.shape}")
    if not np.isfinite(got).all():
        _fail("non-finite values in the result")
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(got - ref))) / scale


def _max_rel_err(gots, refs) -> float:
    return max(_rel_err(g, r) for g, r in zip(gots, refs))


def _host(state):
    import numpy as np

    import implicitglobalgrid_tpu as igg

    return [np.asarray(igg.gather(A)) for A in state]


def _copy(state):
    import jax.numpy as jnp

    return tuple(jnp.array(A, copy=True) for A in state)


def _advance(step, state, nchunks):
    for _ in range(nchunks):
        state = step(*state)
    return state


def _require_kernel(compiled, name):
    if "tpu_custom_call" not in compiled.as_text():
        _fail(f"{name}: no Mosaic kernel (tpu_custom_call) in the compiled program")


# -- phases ------------------------------------------------------------------
#
# Each phase is ``fn(lower_only, **sizes)``.  With ``lower_only`` it builds
# its grid, state and programs and returns ``[(label, Lowered, kernel?)]``;
# otherwise it runs them and returns ``(max_err, check)``.  The caller
# finalizes the grid after either.


def _one_chip(devices):
    import jax

    return list(devices) if devices is not None else jax.devices()[:1]


def three_function(lower_only, shape=(256, 256, 256), nt=6, devices=None):
    """README path with a periodic z (the self-neighbor exchange)."""
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg

    lam, cp_min, lx, ly, lz = 1.0, 1.0, 10.0, 10.0, 10.0
    nz = shape[2]
    igg.init_global_grid(*shape, periodz=1, quiet=True,
                         devices=_one_chip(devices))
    dx = lx / (igg.nx_g() - 1)
    dy = ly / (igg.ny_g() - 1)
    dz = lz / (igg.nz_g() - 1)
    dt = min(dx * dx, dy * dy, dz * dz) * cp_min / lam / 8.1
    T = igg.zeros(shape, jnp.float32)
    X, Y, Z = igg.coord_fields(T, (dx, dy, dz), dtype=jnp.float32)

    @igg.stencil
    def init_ic(X, Y, Z):
        Cp = cp_min + 5 * jnp.exp(-(X - lx / 1.5) ** 2 - (Y - ly / 2) ** 2
                                  - (Z - lz / 1.5) ** 2)
        T = 100 * jnp.exp(-((X - lx / 2) / 2) ** 2 - ((Y - ly / 2) / 2) ** 2
                          - ((Z - lz / 3) / 2) ** 2)
        return Cp.astype(jnp.float32), T.astype(jnp.float32)

    def inn(A):
        return A[1:-1, 1:-1, 1:-1]

    @igg.stencil(donate_argnums=(0,))
    def step(T, Cp):
        lap = ((T[2:, 1:-1, 1:-1] - 2 * inn(T) + T[:-2, 1:-1, 1:-1]) / (dx * dx)
               + (T[1:-1, 2:, 1:-1] - 2 * inn(T) + T[1:-1, :-2, 1:-1]) / (dy * dy)
               + (T[1:-1, 1:-1, 2:] - 2 * inn(T) + T[1:-1, 1:-1, :-2]) / (dz * dz))
        T = T + jnp.pad(dt * lam / inn(Cp) * lap, 1)
        return igg.update_halo(T), Cp

    Cp, T = init_ic(X, Y, Z)
    if lower_only:
        return [("three_function.step", step.lower(T, Cp), False)]
    T0, C = np.asarray(igg.gather(T)), np.asarray(igg.gather(Cp))
    for _ in range(nt):
        T, Cp = step(T, Cp)
    got = np.asarray(igg.gather(T))
    o = igg.ol(2, T)
    if not (np.array_equal(got[:, :, -1], got[:, :, o - 1])
            and np.array_equal(got[:, :, 0], got[:, :, nz - o])):
        _fail("periodic self-neighbor planes differ (plane -1 vs 1, 0 vs n-ol)")
    # Plain jnp reference on the n-ol unique z planes, periodic by roll.
    U, C = jnp.asarray(T0[:, :, 1:nz - 1]), jnp.asarray(C[:, :, 1:nz - 1])
    for _ in range(nt):
        lap = ((U[2:, 1:-1] - 2 * U[1:-1, 1:-1] + U[:-2, 1:-1]) / (dx * dx)
               + (U[1:-1, 2:] - 2 * U[1:-1, 1:-1] + U[1:-1, :-2]) / (dy * dy)
               + (jnp.roll(U, -1, 2) - 2 * U + jnp.roll(U, 1, 2))[1:-1, 1:-1]
               / (dz * dz))
        U = U.at[1:-1, 1:-1].add(dt * lam / C[1:-1, 1:-1] * lap)
    err = _rel_err(got[:, :, 1:nz - 1], U)
    if err > REL_TOL:
        _fail(f"three_function vs jnp periodic diffusion: rel err {err:.3e}")
    return err, (f"self-neighbor planes bitwise; vs jnp reference rel err "
                 f"<= {REL_TOL:g}")


def _fused_vs_xla(lower_only, name, model, setup_kw, nsteps, fused_kw,
                  nchunks, shape, devices):
    import jax.numpy as jnp

    state, params = model.setup(*shape, dtype=jnp.float32, quiet=True,
                                devices=_one_chip(devices), **setup_kw)
    ref = model.make_multi_step(params, nsteps)
    fused = model.make_multi_step(params, nsteps, **fused_kw)
    if lower_only:
        return [(f"{name}.xla", ref.lower(*state), False),
                (f"{name}.fused", fused.lower(*state), True)]
    want = _host(_advance(ref, _copy(state), nchunks))
    got = _host(_advance(fused, state, nchunks))
    err = _max_rel_err(got, want)
    if err > REL_TOL:
        _fail(f"{name}: fused vs XLA cadence rel err {err:.3e}")
    return err, f"fused vs XLA cadence rel err <= {REL_TOL:g} on every field"


def diffusion_fused(lower_only, shape=(256, 256, 256), k=4, nchunks=3,
                    devices=None):
    from implicitglobalgrid_tpu.models import diffusion3d

    return _fused_vs_xla(lower_only, "diffusion_fused", diffusion3d, {},
                         2 * k, dict(fused_k=k), nchunks, shape, devices)


def diffusion512_fused(lower_only, shape=(512, 512, 512), k=4,
                       tile=(32, 128), nchunks=2, devices=None):
    from implicitglobalgrid_tpu.models import diffusion3d

    return _fused_vs_xla(lower_only, "diffusion512_fused", diffusion3d, {},
                         2 * k, dict(fused_k=k, fused_tile=tile), nchunks,
                         shape, devices)


def acoustic_fused(lower_only, shape=(256, 256, 256), k=6, nchunks=2,
                   devices=None):
    from implicitglobalgrid_tpu.models import acoustic3d

    return _fused_vs_xla(lower_only, "acoustic_fused", acoustic3d, {},
                         2 * k, dict(fused_k=k), nchunks, shape, devices)


def porous_fused(lower_only, shape=(256, 256, 256), k=6, npt=10, nchunks=2,
                 devices=None):
    from implicitglobalgrid_tpu.models import porous_convection3d

    return _fused_vs_xla(lower_only, "porous_fused", porous_convection3d,
                         dict(npt=npt), 1, dict(fused_k=k), nchunks, shape,
                         devices)


def zpatch_fused(lower_only, shape=(256, 256, 256), k=4, nchunks=3,
                 devices=None):
    """Periodic z with a deep halo: the in-kernel z-patch cadence."""
    from implicitglobalgrid_tpu.models import diffusion3d

    o = 2 * k
    kw = dict(periodz=1, overlapx=o, overlapy=o, overlapz=o)
    return _fused_vs_xla(lower_only, "zpatch_fused", diffusion3d, kw,
                         2 * k, dict(fused_k=k), nchunks, shape, devices)


def ensemble_fused(lower_only, shape=(128, 128, 128), k=4, batch=2,
                   nchunks=2, devices=None):
    """B members in one batched fused program vs B independent runs."""
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import _batched, diffusion3d

    scales = [1.0 + b / (8.0 * batch) for b in range(batch)]
    bstate, params = _batched.batched_setup(
        diffusion3d, *shape, batch=batch, ic_scales=scales,
        dtype=jnp.float32, quiet=True, devices=_one_chip(devices),
    )
    bstep = diffusion3d.make_multi_step(params, 2 * k, fused_k=k, batch=True)
    one = diffusion3d.make_multi_step(params, 2 * k, fused_k=k)
    members = [
        diffusion3d.setup(*shape, ic_scale=s, init_grid=False,
                          dtype=jnp.float32)[0]
        for s in scales
    ]
    if lower_only:
        return [("ensemble_fused.batched", bstep.lower(*bstate), True),
                ("ensemble_fused.member", one.lower(*members[0]), True)]
    bstate = _advance(bstep, bstate, nchunks)
    for b, state in enumerate(members):
        want = _host(_advance(one, state, nchunks))
        got = [np.asarray(igg.gather(A, member=b)) for A in bstate]
        if not all(np.array_equal(g, w) for g, w in zip(got, want)):
            _fail(f"ensemble member {b} differs from its own run "
                  f"(rel err {_max_rel_err(got, want):.3e})")
    return 0.0, f"each of B={batch} members bitwise equal to its own run"


ONE_CHIP = (three_function, diffusion_fused, diffusion512_fused,
            acoustic_fused, porous_fused, zpatch_fused, ensemble_fused)


def four_chip(lower_only, shape=(256, 256, 256), k=4, tile=(32, 64),
              nchunks=3, devices=None):
    """One grid over four chips vs the same global grid on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    devs = list(devices if devices is not None else jax.devices())[:4]
    o = 2 * k
    out, results = [], {}
    # (label, local grid kwargs, step kwargs): the XLA cadence at the
    # default overlap, the pipelined fused cadence on an x/y deep halo.
    legs = (
        ("xla", {}, {}),
        ("fused", dict(overlapx=o, overlapy=o),
         dict(fused_k=k, fused_tile=tile, pipelined=True)),
    )
    for label, gkw, skw in legs:
        state, params = diffusion3d.setup(*shape, dtype=jnp.float32,
                                          quiet=True, devices=devs, **gkw)
        gg = igg.get_global_grid()
        dims, nxyz_g = tuple(gg.dims), (igg.nx_g(), igg.ny_g(), igg.nz_g())
        step = diffusion3d.make_multi_step(params, 2 * k, **skw)
        if lower_only:
            out.append((f"four_chip.{label}", step.lower(*state), bool(skw)))
        else:
            owners = {s.device for s in state[0].addressable_shards}
            if len(owners) != len(devs):
                _fail(f"four_chip.{label}: shards on {len(owners)} devices")
            state = _advance(step, state, nchunks)
            results[label] = np.asarray(igg.gather(state[0], dedup=True))
        igg.finalize_global_grid()
        # The same global problem on one chip, XLA cadence.
        ref_state, ref_params = diffusion3d.setup(
            *nxyz_g, dtype=jnp.float32, quiet=True, devices=devs[:1],
        )
        ref = diffusion3d.make_multi_step(ref_params, 2 * k)
        if lower_only:
            out.append((f"four_chip.{label}_ref1", ref.lower(*ref_state), False))
        else:
            want = np.asarray(igg.gather(_advance(ref, ref_state, nchunks)[0]))
            results[label + "_ref"] = want
            results[label + "_dims"] = dims
        igg.finalize_global_grid()
    if lower_only:
        return out
    if results["xla_dims"] != (2, 2, 1):
        _fail(f"four_chip: auto dims {results['xla_dims']}, expected (2, 2, 1)")
    if not np.array_equal(results["xla"], results["xla_ref"]):
        err = _rel_err(results["xla"], results["xla_ref"])
        _fail(f"four_chip: 4-chip XLA differs from 1-chip (rel err {err:.3e})")
    err = _rel_err(results["fused"], results["fused_ref"])
    if err > REL_TOL:
        _fail(f"four_chip: 4-chip fused vs 1-chip XLA rel err {err:.3e}")
    return err, (f"dims {results['xla_dims']}, shards on 4 devices; XLA "
                 f"{tuple(results['xla'].shape)} bitwise = 1 chip; pipelined "
                 f"fused {tuple(results['fused'].shape)} rel err <= {REL_TOL:g}")


# -- driver ------------------------------------------------------------------


def _finalize():
    import implicitglobalgrid_tpu as igg

    if igg.grid_is_initialized():
        igg.finalize_global_grid()


def run_phases(phases, *, sizes=None, emit=print):
    """Lower every phase, compile all programs in parallel threads, then run
    each phase through its entry points.  ``sizes`` maps a phase name to
    keyword overrides (the CPU rehearsal's small shapes).  Fallback
    warnings are errors throughout.  Raises `SmokeFailure` on a failed
    check."""
    sizes = sizes or {}
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        t0 = time.perf_counter()
        lowered = []
        for ph in phases:
            try:
                lowered += [(ph.__name__, *x)
                            for x in ph(True, **sizes.get(ph.__name__, {}))]
            finally:
                _finalize()
        t_lower = time.perf_counter() - t0

        def compile_one(item):
            t = time.perf_counter()
            return item[1], item[2].compile(), time.perf_counter() - t

        workers = max(1, min(len(lowered), os.cpu_count() or 1))
        with ThreadPoolExecutor(workers) as pool:
            compiled = list(pool.map(compile_one, lowered))
        compile_s = {}
        for (phase, _, _, kernel), (label, exe, secs) in zip(lowered, compiled):
            if kernel:
                _require_kernel(exe, label)
            compile_s[phase] = compile_s.get(phase, 0.0) + secs
        emit(json.dumps({
            "phase": "compile", "programs": len(lowered),
            "lower_s": round(t_lower, 3),
            "wall_s": round(time.perf_counter() - t0, 3),
            "check": "every program compiled; every kernel program holds "
                     "tpu_custom_call",
        }))
        for ph in phases:
            t = time.perf_counter()
            try:
                err, check = ph(False, **sizes.get(ph.__name__, {}))
            finally:
                _finalize()
            emit(json.dumps({
                "phase": ph.__name__,
                "setup_s": round(compile_s.get(ph.__name__, 0.0), 3),
                "run_s": round(time.perf_counter() - t, 3),
                "max_err": err, "check": check, "ok": True,
            }))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip decomposed-grid phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX's default platform is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    cache = enable_compile_cache()
    print(json.dumps({"phase": "start", "device_kind": dev.device_kind,
                      "devices": len(devices), "compile_cache": cache}),
          flush=True)
    try:
        run_phases((four_chip,) if args.four_chips else ONE_CHIP,
                   emit=lambda line: print(line, flush=True))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
