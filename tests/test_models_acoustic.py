"""Acoustic staggered-grid FDTD model tests.

Oracle: decomposition invariance — the 8-device 2x2x2 run must match the
single-device run of the same global problem, including the staggered
(``n+1``-sized) velocity fields.
"""

import numpy as np
import pytest

import jax

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import acoustic3d
from implicitglobalgrid_tpu.utils import telemetry

from tests.test_models_diffusion import dedup_global


def _z_self_wraps():
    """The program's count of traced groups that wrapped z in the kernel."""
    return telemetry.snapshot()["counters"].get(acoustic3d.Z_SELF_WRAP_COUNTER, 0)


def _run(nt, nx, devices=None, hide_comm=False):
    state, params = acoustic3d.setup(
        nx, nx, nx, devices=devices, hide_comm=hide_comm
    )
    gg = igg.get_global_grid()
    dims, o = gg.dims, gg.overlaps
    step = acoustic3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    out = {}
    names = ("P", "Vx", "Vy", "Vz")
    for name, A in zip(names, state):
        shp = igg.local_shape(A)
        ol = tuple(igg.ol(d, A) for d in range(3))
        g = np.asarray(igg.gather(A))
        out[name] = dedup_global(g, dims, shp, ol) if max(dims) > 1 else g
    igg.finalize_global_grid()
    return out


def test_staggered_multi_matches_single():
    nt, nx = 12, 10
    multi = _run(nt, nx)  # 2x2x2, global 18^3 (+1 staggered)
    single = _run(nt, 18, devices=[jax.devices()[0]])
    assert multi["P"].shape == (18, 18, 18)
    assert multi["Vx"].shape == (19, 18, 18)
    for k in multi:
        np.testing.assert_allclose(multi[k], single[k], rtol=1e-12, atol=1e-13, err_msg=k)


def test_hide_comm_matches_plain():
    nt, nx = 8, 10
    plain = _run(nt, nx)
    hidden = _run(nt, nx, hide_comm=True)
    for k in plain:
        np.testing.assert_allclose(hidden[k], plain[k], rtol=1e-12, atol=1e-13, err_msg=k)


def test_multi_step_matches_single_steps():
    nx = 10
    state, params = acoustic3d.setup(nx, nx, nx)
    step = acoustic3d.make_step(params, donate=False)
    multi = acoustic3d.make_multi_step(params, 6, donate=False)
    s1 = state
    for _ in range(6):
        s1 = jax.block_until_ready(step(*s1))
    s6 = jax.block_until_ready(multi(*state))
    for a, b in zip(s1, s6):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12, atol=1e-13)
    igg.finalize_global_grid()


def test_wave_propagates_and_stays_bounded():
    state, params = acoustic3d.setup(12, 12, 12)
    P0 = np.asarray(igg.gather(acoustic3d.pressure(state)))
    step = acoustic3d.make_step(params)
    for _ in range(30):
        state = jax.block_until_ready(step(*state))
    P1 = np.asarray(igg.gather(acoustic3d.pressure(state)))
    igg.finalize_global_grid()
    assert P1.max() < P0.max()  # pulse spreads
    assert np.abs(P1).max() > 1e-6  # but is not lost
    assert np.isfinite(P1).all()


def test_exchange_cadence_matches_per_step():
    """w leapfrog steps + one width-w slab exchange of ALL fields (incl. the
    incrementally-updated P) must be bit-identical to the per-step path."""
    import numpy as np

    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True)
    state, params = acoustic3d.setup(10, 10, 10, **kw)
    step = acoustic3d.make_multi_step(params, 4, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = acoustic3d.setup(10, 10, 10, **kw)
    step2 = acoustic3d.make_multi_step(params, 4, donate=False, exchange_every=2)
    cad = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step2(*state))]
    igg.finalize_global_grid()
    for r, c in zip(ref, cad):
        np.testing.assert_array_equal(c, r)


@pytest.mark.parametrize("seed", range(3))
def test_random_overlap_staggered_invariance(seed):
    """Random overlaps with staggered fields: the multi-block run must match
    the single-device run of the same global problem exactly, with each
    field's shape-aware overlap (``ol = o + 1`` on its staggered axis)
    honored by the dedup."""
    rng = np.random.default_rng(8100 + seed)
    o = int(rng.integers(2, 5))
    nx = int(rng.integers(2 * o + 2, 2 * o + 5))
    nt = int(rng.integers(3, 7))
    okw = dict(overlapx=o, overlapy=o, overlapz=o)

    state, params = acoustic3d.setup(nx, nx, nx, quiet=True, **okw)
    gg = igg.get_global_grid()
    dims = gg.dims
    step = acoustic3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    multi = {}
    for name, A in zip(("P", "Vx", "Vy", "Vz"), state):
        shp = igg.local_shape(A)
        ol = tuple(igg.ol(d, A) for d in range(3))
        multi[name] = dedup_global(np.asarray(igg.gather(A)), dims, shp, ol)
    igg.finalize_global_grid()

    nxg = tuple(dims[d] * (nx - o) + o for d in range(3))
    state, params = acoustic3d.setup(
        *nxg, devices=[jax.devices()[0]], quiet=True
    )
    step = acoustic3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    for name, A in zip(("P", "Vx", "Vy", "Vz"), state):
        np.testing.assert_allclose(
            multi[name], np.asarray(igg.gather(A)), rtol=1e-12, atol=1e-13,
            err_msg=f"{name} o={o} nx={nx} nt={nt}",
        )
    igg.finalize_global_grid()


def test_fused_single_device_matches_xla():
    """fused_k on a no-halo-activity grid (1 device): the padded-layout
    staggered kernel chunk must match the per-step XLA path to few f32 ULPs
    (interpret-mode kernel)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    # dtype pinned: the suite runs x64, and f64 is outside the kernel
    # envelope (TPU Pallas has no 8-byte types) — without it this test
    # would silently exercise the XLA fallback instead of the kernel.
    kw = dict(devices=jax.devices()[:1], quiet=True, dtype=jax.numpy.float32)
    state, params = acoustic3d.setup(16, 32, 128, **kw)
    step = acoustic3d.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(A) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = acoustic3d.setup(16, 32, 128, **kw)
    wraps = _z_self_wraps()
    with force_tpu_interpret_mode():
        stepf = acoustic3d.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(A) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    # walls: no z exchange, so no in-kernel z wrap (the counter is registered)
    assert acoustic3d.Z_SELF_WRAP_COUNTER in telemetry.snapshot()["counters"]
    assert _z_self_wraps() == wraps
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)


def test_fused_deep_halo_matches_xla_multiblock():
    """Temporal blocking on a communicating STAGGERED grid: k fused kernel
    steps + one width-k all-field slab exchange vs the per-step path
    (interpret-mode kernel; deep halo overlapx=4 licenses fused_k=2).

    2 devices deliberately — the interpret-mode Pallas + shard_map deadlock
    constraint probed for the diffusion kernel applies here too."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1, overlapx=4, quiet=True,
        dtype=jax.numpy.float32,  # pinned: f64 is outside the kernel envelope
    )
    state, params = acoustic3d.setup(16, 32, 128, **kw)
    step = acoustic3d.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = acoustic3d.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = acoustic3d.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)


def test_fused_fallback_warns_and_matches_xla():
    """A local block the kernel envelope rejects (y-size not a multiple of 8)
    must warn once and run the XLA path at the same all-field slab cadence —
    bit-identical to the per-step path at group boundaries."""
    # dtype pinned so the fallback fires for the documented y%8 shape
    # rejection, not the x64-itemsize check (the suite runs x64).
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True,
              dtype=jax.numpy.float32)
    state, params = acoustic3d.setup(10, 10, 10, **kw)
    step = acoustic3d.make_multi_step(params, 4, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = acoustic3d.setup(10, 10, 10, **kw)
    with pytest.warns(RuntimeWarning, match="falling back to the XLA path"):
        stepf = acoustic3d.make_multi_step(params, 4, donate=False, fused_k=2)
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)


def test_fused_validation():
    state, params = acoustic3d.setup(
        16, 32, 128, devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1, quiet=True
    )
    with pytest.raises(ValueError, match="deep halo"):
        acoustic3d.make_multi_step(params, 4, fused_k=2)
    igg.finalize_global_grid()
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True)
    state, params = acoustic3d.setup(10, 10, 10, **kw)
    with pytest.raises(ValueError, match="multiple of fused_k"):
        acoustic3d.make_multi_step(params, 5, fused_k=2)
    with pytest.raises(ValueError, match="pass both bx and by"):
        acoustic3d.make_multi_step(params, 4, fused_k=2, fused_tile=(8, None))
    with pytest.raises(ValueError, match="conflicts"):
        acoustic3d.make_multi_step(params, 4, fused_k=2, exchange_every=4)
    igg.finalize_global_grid()
    state, params = acoustic3d.setup(10, 10, 10, hide_comm=True, **kw)
    with pytest.raises(ValueError, match="mutually exclusive"):
        acoustic3d.make_multi_step(params, 4, fused_k=2)
    igg.finalize_global_grid()


def test_fused_zpatch_deep_halo_z_split_matches_xla():
    """The in-kernel z-slab cadence (z-dim decomposition): k fused kernel
    steps with VMEM-applied z patches + outside x/y exchange vs the
    per-step path (interpret-mode kernel, 2 devices split along z)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:2], dimx=1, dimy=1, dimz=2, overlapz=4, quiet=True,
        dtype=jax.numpy.float32,
    )
    state, params = acoustic3d.setup(16, 32, 128, **kw)
    step = acoustic3d.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = acoustic3d.setup(16, 32, 128, **kw)
    wraps = _z_self_wraps()
    with force_tpu_interpret_mode():
        stepf = acoustic3d.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16)
        )
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    # a real z partner: the patch/export cadence, no in-kernel wrap
    assert _z_self_wraps() == wraps
    for name, g, r in zip(("P", "Vx", "Vy", "Vz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize(
    "dims,nloc,pipelined",
    [
        # 1 device, z-activity via the wrap: the one-chip benchmark's config
        ((1, 1, 1), (16, 32, 128), False),
        # x split, each device its own z partner: x slabs exchange outside
        # the wrapping kernel, serialized or under the ring/interior split
        ((2, 1, 1), (24, 32, 128), False),
        ((2, 1, 1), (24, 32, 128), True),
        # x split and a real z partner: the patch/export kernel, its packed
        # exports riding the x/y exchange (the `zpatch` and `zpatch_piped`
        # bodies of `models._fused.fused_cadence`), no in-kernel wrap
        ((2, 1, 2), (24, 32, 128), False),
        ((2, 1, 2), (24, 32, 128), True),
    ],
    ids=["self", "x2-self", "x2-self-piped", "x2z2", "x2z2-piped"],
)
def test_fused_zpatch_periodic_z_matches_xla(dims, nloc, pipelined):
    """The fused cadence on a periodic z against the per-step path
    (interpret-mode kernel).  Where every block is its own z partner the
    kernel does the z exchange inside its tiles (``fused.z_self_wrap``); a
    z split across devices keeps the patch/export cadence."""
    import warnings

    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[: int(np.prod(dims))], dimx=dims[0],
        dimy=dims[1], dimz=dims[2], periodz=1, overlapx=4, overlapz=4,
        quiet=True, dtype=jax.numpy.float32,
    )
    state, params = acoustic3d.setup(*nloc, **kw)
    step = acoustic3d.make_multi_step(params, nt, donate=False)
    ref = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(step(*state))]
    igg.finalize_global_grid()

    state, params = acoustic3d.setup(*nloc, **kw)
    if pipelined:
        # the split is admissible: the pipelined body, not a fallback, runs
        assert acoustic3d.pipelined_support_error(nloc, 2, bx=8, by=16) is None
    wraps = _z_self_wraps()
    with force_tpu_interpret_mode(), warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*not admissible")
        stepf = acoustic3d.make_multi_step(
            params, nt, donate=False, fused_k=2, fused_tile=(8, 16),
            pipelined=pipelined,
        )
        got = [np.asarray(igg.gather(A)) for A in jax.block_until_ready(stepf(*state))]
    igg.finalize_global_grid()
    if dims[2] == 1:
        assert _z_self_wraps() >= wraps + 1
    else:
        assert _z_self_wraps() == wraps
    for name, g, r in zip(("P", "Vx", "Vy", "Vz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)
