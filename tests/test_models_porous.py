"""Porous-convection model tests (pseudo-transient Darcy + temperature)."""

import numpy as np
import pytest

import jax

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import porous_convection3d as pc

from tests.test_models_diffusion import dedup_global


def _run(nt, nx, devices=None, npt=8, hide_comm=False):
    state, params = pc.setup(nx, nx, nx, devices=devices, npt=npt, hide_comm=hide_comm)
    gg = igg.get_global_grid()
    dims = gg.dims
    step = pc.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    out = {}
    for name, A in zip(("T", "Pf", "qDx", "qDy", "qDz"), state):
        shp = igg.local_shape(A)
        ol = tuple(igg.ol(d, A) for d in range(3))
        g = np.asarray(igg.gather(A))
        out[name] = dedup_global(g, dims, shp, ol) if max(dims) > 1 else g
    igg.finalize_global_grid()
    return out


def test_multi_matches_single():
    nt, nx = 4, 10
    multi = _run(nt, nx)  # 2x2x2, global 18^3
    single = _run(nt, 18, devices=[jax.devices()[0]])
    for k in multi:
        np.testing.assert_allclose(multi[k], single[k], rtol=1e-11, atol=1e-12, err_msg=k)


def _pt_relax(params, n, state):
    """Run ``n`` PT Darcy iterations at frozen T; return (Pf, qDx, qDy, qDz)."""
    from jax import lax

    it = pc._pt_iteration(params)
    T, Pf, qDx, qDy, qDz = state
    f = jax.jit(
        lambda T, Pf, qx, qy, qz: lax.fori_loop(
            0, n, lambda i, s: it(T, *s), (Pf, qx, qy, qz)
        )
    )
    return f(T, Pf, qDx, qDy, qDz)


def _div_residual(params, pt_state):
    Pf, qDx, qDy, qDz = pt_state
    div = (
        np.diff(np.asarray(qDx), axis=0) / params.dx
        + np.diff(np.asarray(qDy), axis=1) / params.dy
        + np.diff(np.asarray(qDz), axis=2) / params.dz
    )
    return float(np.max(np.abs(div)))


def test_hide_comm_matches_plain():
    # Overlapped flux exchange (the acoustic pattern applied to the PT inner
    # loop) must be bit-equivalent to the plain per-iteration exchange.
    plain = _run(3, 10)
    hide = _run(3, 10, hide_comm=True)
    for k in plain:
        np.testing.assert_allclose(hide[k], plain[k], rtol=1e-12, atol=1e-12)


def test_pt_solver_converges_and_bound_is_sharp():
    """The hand-derived PT relaxation bounds must be pinned by convergence.

    The Darcy continuity residual max|div(qD)| must contract by a pinned
    factor over the PT iterations (beta_p's von Neumann bound,
    `porous_convection3d.setup`), and violating the bound (beta_p scaled 3x,
    so beta*theta*k^2 > 2) must blow the residual up — a wrong bound cannot
    slip through as "just slow convergence".
    """
    import dataclasses

    state, params = pc.setup(16, 16, 16, devices=[jax.devices()[0]], quiet=True)
    try:
        r_early = _div_residual(params, _pt_relax(params, 2, state))
        r_late = _div_residual(params, _pt_relax(params, 160, state))
        assert r_early > 1.0  # buoyancy drives a nontrivial residual first
        # measured 6.1e-3 vs 4.45 => contraction ~730x; pin with margin
        assert r_late < 0.02
        assert r_late < r_early / 100.0
        bad = dataclasses.replace(params, beta_p=params.beta_p * 3.0)
        r_bad = _div_residual(bad, _pt_relax(bad, 40, state))
        assert not np.isfinite(r_bad) or r_bad > 1e6  # diverges, not "slow"
    finally:
        igg.finalize_global_grid()


def test_multi_step_matches_single_steps():
    """The production chunk path (nsteps per XLA program) must reproduce the
    per-step path exactly."""
    nx, nt = 10, 3
    state, params = pc.setup(nx, nx, nx, npt=6)
    step = pc.make_step(params, donate=False)
    multi = pc.make_multi_step(params, nt, donate=False)
    s1 = state
    for _ in range(nt):
        s1 = jax.block_until_ready(step(*s1))
    s3 = jax.block_until_ready(multi(*state))
    for a, b, name in zip(s1, s3, ("T", "Pf", "qDx", "qDy", "qDz")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-12, atol=1e-13, err_msg=name
        )
    igg.finalize_global_grid()


def test_pt_cadence_matches_per_iteration():
    """Deep-halo PT cadence: w relaxation iterations + one width-w 4-field
    slab exchange must be bit-identical to the per-iteration Pf exchange at
    group boundaries (owned cells)."""
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True, npt=6)
    nx, nt = 12, 2

    def _run_cadence(exchange_every):
        state, params = pc.setup(nx, nx, nx, **kw)
        gg = igg.get_global_grid()
        dims, o = gg.dims, gg.overlaps
        step = pc.make_multi_step(params, nt, donate=False, exchange_every=exchange_every)
        state = jax.block_until_ready(step(*state))
        out = {}
        for name, A in zip(("T", "Pf", "qDx", "qDy", "qDz"), state):
            shp = igg.local_shape(A)
            ol = tuple(igg.ol(d, A) for d in range(3))
            g = np.asarray(igg.gather(A))
            out[name] = dedup_global(g, dims, shp, ol) if max(dims) > 1 else g
        igg.finalize_global_grid()
        return out

    ref = _run_cadence(1)
    cad = _run_cadence(2)
    for k in ref:
        np.testing.assert_array_equal(cad[k], ref[k], err_msg=k)


def test_pt_cadence_validation():
    state, params = pc.setup(10, 10, 10, npt=6, quiet=True)  # overlap 2
    with pytest.raises(ValueError, match="deep halo"):
        pc.make_multi_step(params, 2, exchange_every=2)
    igg.finalize_global_grid()


def test_pt_schedule():
    """The ragged-npt chunking (round 4, VERDICT r3 #5: ``w | npt`` made the
    kernel benefit depend on a numerics parameter)."""
    from implicitglobalgrid_tpu.models.porous_convection3d import _pt_schedule

    assert _pt_schedule(12, 6) == (0, [6, 6])
    assert _pt_schedule(10, 6) == (0, [6, 4])
    assert _pt_schedule(8, 6) == (0, [6, 2])
    assert _pt_schedule(9, 6) == (1, [6, 2])
    assert _pt_schedule(10, 2) == (0, [2] * 5)
    assert _pt_schedule(1, 2) == (1, [])
    assert _pt_schedule(5, 4) == (1, [4])
    # w=1 admits no even kernel chunk — everything leads (regression: this
    # case used to loop forever).
    assert _pt_schedule(10, 1) == (10, [])
    # The pure-XLA exchange_every cadence has no parity constraint: odd w
    # keeps the user's requested group size (regression: even-rounding was
    # wrongly applied, inflating the collective count by ~50%).
    assert _pt_schedule(6, 3, even=False) == (0, [3, 3])
    assert _pt_schedule(7, 3, even=False) == (0, [3, 3, 1])


def test_ragged_cadence_matches_per_iteration():
    """exchange_every with npt % w != 0 (ragged schedule) must still match
    the per-iteration path at time-step boundaries."""
    kw = dict(overlapx=8, overlapy=8, overlapz=8, npt=5, quiet=True)
    state, params = pc.setup(18, 18, 18, **kw)
    step = pc.make_multi_step(params, 2, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(18, 18, 18, **kw)
    step = pc.make_multi_step(params, 2, donate=False, exchange_every=4)
    cad = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()
    # Not bitwise: the lead iteration changes fusion boundaries, so the
    # compiler contracts FMAs differently (f64 ULPs).
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), cad, ref):
        np.testing.assert_allclose(g, r, rtol=1e-13, atol=1e-13, err_msg=name)


def test_convection_starts_and_is_bounded():
    state, params = pc.setup(12, 12, 12, npt=8)
    step = pc.make_step(params)
    for _ in range(12):
        state = jax.block_until_ready(step(*state))
    T = np.asarray(igg.gather(pc.temperature(state)))
    qDz = np.asarray(igg.gather(state[4]))
    igg.finalize_global_grid()
    assert np.isfinite(T).all() and np.isfinite(qDz).all()
    # Dirichlet walls intact (frozen boundary planes):
    assert abs(T[:, :, 0].mean() - 0.5) < 0.1
    assert abs(T[:, :, -1].mean() + 0.5) < 0.1
    # buoyancy must have driven an upward Darcy flux somewhere
    assert qDz.max() > 1e-8
    # temperature stays within the physical contrast (+ perturbation margin)
    assert T.max() <= 0.65 and T.min() >= -0.65


def test_fused_single_device_matches_xla():
    """fused_k on a no-halo-activity grid: the fluxes stay in the kernel's
    padded layout across the whole PT loop; results must match the plain
    multi-step path to few (scale-relative) f32 ULPs."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 2
    # dtype pinned: f64 is outside the kernel envelope (see the acoustic
    # fused tests); without it this exercises the fallback, not the kernel.
    kw = dict(devices=jax.devices()[:1], npt=4, quiet=True,
              dtype=jax.numpy.float32)
    state, params = pc.setup(16, 32, 128, **kw)
    step = pc.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(A) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = pc.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(A) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("npt,fused_k", [(10, 4), (5, 2)])
def test_fused_ragged_npt_matches_xla(npt, fused_k):
    """npt % fused_k != 0 (round 4, VERDICT r3 #5): the ragged schedule —
    odd lead iteration + even kernel chunks, all exchanges at width w —
    must match the per-iteration path.  (10, 4) -> chunks [4, 4, 2];
    (5, 2) -> lead 1 + chunks [2, 2]."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 2
    kw = dict(
        devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        overlapx=2 * fused_k, npt=npt, quiet=True, dtype=jax.numpy.float32,
    )
    state, params = pc.setup(16, 32, 128, **kw)
    step = pc.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = pc.make_multi_step(
            params, nt, donate=False, fused_k=fused_k, fused_tile=(8, 16)
        )
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("npt", [10, 9])
def test_fused_ragged_zpatch_periodic_z_matches_xla(npt):
    """Ragged schedule through the in-kernel z-slab cadence (periodic
    self-neighbor z): patch application and export both at width w for
    every chunk, shorter chunks included."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 1
    kw = dict(
        devices=jax.devices()[:1], periodz=1, overlapz=8, npt=npt,
        quiet=True, dtype=jax.numpy.float32,
    )
    state, params = pc.setup(16, 32, 128, **kw)
    step = pc.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(A) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = pc.make_multi_step(
            params, nt, donate=False, fused_k=4, fused_tile=(8, 16)
        )
        got = [np.asarray(A) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_deep_halo_matches_xla_multiblock():
    """k fused PT iterations + one width-k all-field slab exchange vs the
    per-iteration comm-lean path (interpret-mode kernel; 2 devices — the
    interpret-mode Pallas + shard_map deadlock constraint)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 2
    kw = dict(
        devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1, overlapx=4,
        npt=4, quiet=True, dtype=jax.numpy.float32,  # f64: outside envelope
    )
    state, params = pc.setup(16, 32, 128, **kw)
    step = pc.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = pc.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_fallback_warns_and_matches_cadence():
    """A local block the kernel envelope rejects must warn once and run the
    XLA path at the same slab cadence — bit-identical to exchange_every=w."""
    # dtype pinned so the fallback fires for the documented y%8 shape
    # rejection, not the x64-itemsize check (the suite runs x64).
    kw = dict(overlapx=4, overlapy=4, overlapz=4, npt=4, quiet=True,
              dtype=jax.numpy.float32)
    state, params = pc.setup(10, 10, 10, **kw)
    step = pc.make_multi_step(params, 2, donate=False, exchange_every=2)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(10, 10, 10, **kw)
    with pytest.warns(RuntimeWarning, match="falling back to the XLA path"):
        stepf = pc.make_multi_step(params, 2, donate=False, fused_k=2)
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_array_equal(g, r, err_msg=name)


def test_fused_validation():
    state, params = pc.setup(
        16, 32, 128, devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        npt=4, quiet=True,
    )
    with pytest.raises(ValueError, match="deep halo"):
        pc.make_multi_step(params, 2, fused_k=2)
    igg.finalize_global_grid()
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True)
    # npt=5 with fused_k=2 is no longer rejected: the ragged schedule (one
    # leading XLA iteration + [2, 2]) runs it — equivalence covered by
    # test_fused_ragged_npt_matches_xla.
    state, params = pc.setup(10, 10, 10, npt=4, **kw)
    with pytest.raises(ValueError, match="conflicts"):
        pc.make_multi_step(params, 2, fused_k=2, exchange_every=4)
    with pytest.raises(ValueError, match="pass both bx and by"):
        pc.make_multi_step(params, 2, fused_k=2, fused_tile=(8, None))
    igg.finalize_global_grid()
    state, params = pc.setup(10, 10, 10, npt=4, hide_comm=True, **kw)
    with pytest.raises(ValueError, match="mutually exclusive"):
        pc.make_multi_step(params, 2, fused_k=2)
    igg.finalize_global_grid()


def test_fused_zpatch_deep_halo_z_split_matches_xla():
    """The in-kernel z-slab PT cadence (z-dim decomposition) vs the
    per-iteration comm-lean path (interpret-mode kernel, 2 devices on z)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 2
    kw = dict(
        devices=jax.devices()[:2], dimx=1, dimy=1, dimz=2, overlapz=4,
        npt=4, quiet=True, dtype=jax.numpy.float32,
    )
    state, params = pc.setup(16, 32, 128, **kw)
    step = pc.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = pc.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_zpatch_periodic_z_matches_xla():
    """Same cadence on the periodic self-neighbor z config (1 device)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 2
    kw = dict(
        devices=jax.devices()[:1], periodz=1, overlapz=4, npt=4, quiet=True,
        dtype=jax.numpy.float32,
    )
    state, params = pc.setup(16, 32, 128, **kw)
    step = pc.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(A) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = pc.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = pc.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(A) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)
