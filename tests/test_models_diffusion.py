"""Model tests: 3-D heat diffusion (reference examples/diffusion3D_*.jl).

Correctness oracle: a multi-block run on the 8-device mesh must reproduce a
single-device run of the same *global* problem exactly (the implicit global
grid is an implementation detail — physics can't see the decomposition).
"""

import itertools

import jax
import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import diffusion3d


def dedup_global(gathered, dims, n, o):
    """Assemble the de-duplicated global array from side-by-side blocks.

    Block c's local cell i sits at global index c*(n-o)+i; overlapping cells
    are written repeatedly (they must agree after update_halo).
    """
    nd = len(n)
    out_shape = tuple(dims[d] * (n[d] - o[d]) + o[d] for d in range(nd))
    out = np.zeros(out_shape, gathered.dtype)
    for c in itertools.product(*(range(d) for d in dims)):
        src = tuple(slice(c[d] * n[d], (c[d] + 1) * n[d]) for d in range(nd))
        dst = tuple(
            slice(c[d] * (n[d] - o[d]), c[d] * (n[d] - o[d]) + n[d]) for d in range(nd)
        )
        out[dst] = gathered[src]
    return out


def run_multi(nt, nx, hide_comm=False):
    state, params = diffusion3d.setup(nx, nx, nx, hide_comm=hide_comm)
    gg = igg.get_global_grid()
    dims, o = gg.dims, gg.overlaps
    step = diffusion3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    T = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()
    return dedup_global(T, dims, (nx,) * 3, o)


def run_single(nt, nxg):
    state, params = diffusion3d.setup(
        nxg, nxg, nxg, devices=[jax.devices()[0]]
    )
    step = diffusion3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    T = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()
    return T


def test_multi_block_matches_single_device():
    nx = 10  # 2x2x2 blocks of 10^3, global deduped 18^3
    nt = 20
    T_multi = run_multi(nt, nx)
    assert T_multi.shape == (18, 18, 18)
    T_single = run_single(nt, 18)
    np.testing.assert_allclose(T_multi, T_single, rtol=1e-12, atol=1e-12)


def test_hide_comm_matches_plain():
    nx = 10
    nt = 10
    T_plain = run_multi(nt, nx)
    T_hide = run_multi(nt, nx, hide_comm=True)
    np.testing.assert_allclose(T_hide, T_plain, rtol=1e-12, atol=1e-12)


def test_run_end_to_end():
    T = diffusion3d.run(5, 8, 8, 8)
    assert not igg.grid_is_initialized()  # finalized
    assert np.isfinite(np.asarray(jax.device_get(T))).all()


def test_initial_conditions_decomposition_invariant():
    # ICs are computed from global coordinates: independent of the block layout.
    (T8, Cp8), _ = diffusion3d.setup(10, 10, 10)
    gg = igg.get_global_grid()
    dims, o = gg.dims, gg.overlaps
    T8 = dedup_global(np.asarray(igg.gather(T8)), dims, (10,) * 3, o)
    Cp8 = dedup_global(np.asarray(igg.gather(Cp8)), dims, (10,) * 3, o)
    igg.finalize_global_grid()

    (T1, Cp1), _ = diffusion3d.setup(18, 18, 18, devices=[jax.devices()[0]])
    T1 = np.asarray(igg.gather(T1))
    Cp1 = np.asarray(igg.gather(Cp1))
    igg.finalize_global_grid()

    np.testing.assert_allclose(T8, T1, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(Cp8, Cp1, rtol=1e-12, atol=1e-12)


def test_anomaly_diffuses():
    # The peak must decay and heat must spread (sanity physics check).
    state, params = diffusion3d.setup(10, 10, 10)
    T0 = np.asarray(igg.gather(diffusion3d.temperature(state)))
    step = diffusion3d.make_step(params)
    for _ in range(50):
        state = jax.block_until_ready(step(*state))
    T1 = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()
    assert T1.max() < T0.max()
    assert T1.min() >= -1e-9


def test_multi_step_matches_single_steps():
    nx = 10
    state, params = diffusion3d.setup(nx, nx, nx)
    step = diffusion3d.make_step(params, donate=False)
    multi = diffusion3d.make_multi_step(params, 6, donate=False)
    s1 = state
    for _ in range(6):
        s1 = jax.block_until_ready(step(*s1))
    s6 = jax.block_until_ready(multi(*state))
    np.testing.assert_allclose(
        np.asarray(s1[0]), np.asarray(s6[0]), rtol=1e-12, atol=1e-13
    )
    igg.finalize_global_grid()


def test_fused_deep_halo_matches_xla_multiblock():
    """Temporal blocking on a communicating grid: k fused kernel steps + one
    width-k slab exchange must match the per-step XLA path on the same mesh
    (interpret-mode kernel; deep halo overlapx=4 licenses fused_k=2).

    2 devices deliberately: >2 concurrent interpret-mode Pallas kernels
    under shard_map deadlock inside the interpreter (no collective
    rendezvous involved — probed at 4 and 8 virtual devices; the compiled
    kernel + slab path is validated on hardware and the slab exchange alone
    on 8 devices in test_update_halo)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1, overlapx=4, quiet=True,
        dtype=jax.numpy.float32,  # pinned: f64 is outside the kernel envelope
    )
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, nt, donate=False)
    state = jax.block_until_ready(step(*state))
    T_xla = np.asarray(igg.gather(state[0]))
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = diffusion3d.make_multi_step(params, nt, donate=False, fused_k=2)
        state = jax.block_until_ready(stepf(*state))
    T_fused = np.asarray(igg.gather(state[0]))
    igg.finalize_global_grid()
    np.testing.assert_allclose(T_fused, T_xla, rtol=1e-5, atol=1e-5)


def test_fused_fallback_warns_and_matches_xla():
    """A local block the kernel envelope rejects (y-size not a multiple of 8)
    must warn once and run the XLA path at the same exchange cadence —
    bit-identical to the per-step path at group boundaries."""
    # dtype pinned so the fallback fires for the documented y%8 shape
    # rejection, not the x64-itemsize check (the suite runs x64).
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True,
              dtype=jax.numpy.float32)
    state, params = diffusion3d.setup(10, 10, 10, **kw)
    step = diffusion3d.make_multi_step(params, 4, donate=False)
    T_ref = np.asarray(igg.gather(jax.block_until_ready(step(*state))[0]))
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(10, 10, 10, **kw)
    with pytest.warns(RuntimeWarning, match="falling back to the XLA path"):
        stepf = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=2)
        state = jax.block_until_ready(stepf(*state))
    T_fb = np.asarray(igg.gather(state[0]))
    igg.finalize_global_grid()
    np.testing.assert_array_equal(T_fb, T_ref)


def test_fused_complex_falls_back_and_matches():
    """complex64 (itemsize 8) is outside the Mosaic envelope: fused_k must
    warn once and run the XLA cadence, bit-identical to the per-step path
    (the reference's dtype matrix includes complex; here the kernel lever
    simply declines them instead of miscompiling)."""
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True,
              dtype=jax.numpy.complex64)
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, 4, donate=False)
    T_ref = np.asarray(igg.gather(jax.block_until_ready(step(*state))[0]))
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    with pytest.warns(RuntimeWarning, match="f64/complex"):
        stepf = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=2)
        state = jax.block_until_ready(stepf(*state))
    T_fb = np.asarray(igg.gather(state[0]))
    igg.finalize_global_grid()
    np.testing.assert_array_equal(T_fb, T_ref)


def test_fused_requires_deep_halo():
    state, params = diffusion3d.setup(
        16, 32, 128, devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1, quiet=True
    )
    with pytest.raises(ValueError, match="deep halo"):
        diffusion3d.make_multi_step(params, 4, fused_k=2)
    igg.finalize_global_grid()


def test_exchange_cadence_matches_per_step():
    """Deep-halo cadence on the XLA path: w steps + one width-w slab exchange
    must be bit-identical to per-step exchange at group boundaries."""
    kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True)
    state, params = diffusion3d.setup(10, 10, 10, **kw)
    step = diffusion3d.make_multi_step(params, 4, donate=False)
    T_ref = np.asarray(igg.gather(jax.block_until_ready(step(*state))[0]))
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(10, 10, 10, **kw)
    step2 = diffusion3d.make_multi_step(params, 4, donate=False, exchange_every=2)
    T_cad = np.asarray(igg.gather(jax.block_until_ready(step2(*state))[0]))
    igg.finalize_global_grid()
    np.testing.assert_array_equal(T_cad, T_ref)


def test_exchange_cadence_validation():
    state, params = diffusion3d.setup(10, 10, 10, quiet=True)  # overlap 2
    with pytest.raises(ValueError, match="deep halo"):
        diffusion3d.make_multi_step(params, 4, exchange_every=2)
    with pytest.raises(ValueError, match="multiple of exchange_every"):
        diffusion3d.make_multi_step(params, 5, exchange_every=2)
    igg.finalize_global_grid()


@pytest.mark.parametrize("seed", range(4))
def test_random_topology_decomposition_invariance(seed):
    """End-to-end oracle across random topologies: a multi-block run must
    reproduce the single-device run of the same global problem exactly,
    whatever dims/overlap are drawn.  Non-periodic only — on periodic dims
    the implicit global size drops the +overlap term and the duplicated
    cells wrap, so the single-device problem is not the simple dedup; the
    halo-level sweeps in test_update_halo carry the periodic coverage."""
    rng = np.random.default_rng(7000 + seed)
    o = int(rng.integers(2, 5))
    nx = int(rng.integers(2 * o + 2, 2 * o + 6))
    overlaps = {f"overlap{ax}": o for ax in "xyz"}
    nt = int(rng.integers(3, 8))

    state, params = diffusion3d.setup(nx, nx, nx, quiet=True, **overlaps)
    gg = igg.get_global_grid()
    dims = gg.dims
    step = diffusion3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    T_multi = dedup_global(
        np.asarray(igg.gather(state[0])), dims, (nx,) * 3, (o,) * 3
    )
    igg.finalize_global_grid()

    nxg = tuple(dims[d] * (nx - o) + o for d in range(3))
    state, params = diffusion3d.setup(
        *nxg, devices=[jax.devices()[0]], quiet=True
    )
    step = diffusion3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    T_single = np.asarray(igg.gather(state[0]))
    igg.finalize_global_grid()
    np.testing.assert_allclose(T_multi, T_single, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("seed", range(3))
def test_fused_zpatch_random_topology_invariance(seed):
    """Decomposition-invariance oracle for the fused z-patch cadence
    (VERDICT r3 #1): a z-split fused_k run must reproduce the single-device
    per-step run of the same global problem.  The decomposition is fixed at
    dims=(1,1,2) — interpret-mode Pallas under shard_map deadlocks with >2
    concurrent kernel instances (see __graft_entry__.dryrun_multichip) —
    and the random draws cover local shape, tile, and step count instead;
    dims_z=2 keeps the in-kernel z-slab machinery on the exercised path in
    every draw."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    rng = np.random.default_rng(7100 + seed)
    dims = (1, 1, 2)
    k = 2
    o = 2 * k
    nt = int(rng.integers(1, 3)) * k
    n0 = int(rng.choice([16, 24, 32]))
    n1 = int(rng.choice([32, 64]))
    nloc = (n0, n1, 128)
    # (16,32) tiles need bx|n0 with the haloed window inside the block
    # (n0 >= 20) and by|n1 with SY=48 <= n1 — only the (32,64) draw.
    # A by=n1 draw exercises the TRANSPOSED full-y patch layout (round 5);
    # the others pin the packed 128-lane layout.
    big_ok = n0 == 32 and n1 == 64
    choice = int(rng.integers(3))
    if choice == 0:
        tile = (8, n1)  # full-y -> transposed layout
    elif big_ok and choice == 1:
        tile = (16, 32)
    else:
        tile = (8, 16)

    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        fused_support_error,
        zpatch_transposed,
    )

    # The oracle is only meaningful if the z-patch kernel path is actually
    # selected (f32: the envelope rejects f64) — guard against a silent
    # fall-back to the XLA cadence.
    assert fused_support_error(nloc, k, 4, *tile, zpatch=True) is None
    assert zpatch_transposed(nloc, k, 4, *tile) == (tile[1] == n1)

    kw = dict(
        devices=jax.devices()[: dims[0] * dims[1] * dims[2]],
        dimx=dims[0], dimy=dims[1], dimz=dims[2],
        overlapx=o, overlapy=o, overlapz=o, quiet=True,
        dtype=jax.numpy.float32,
    )
    state, params = diffusion3d.setup(*nloc, **kw)
    with force_tpu_interpret_mode():
        step = diffusion3d.make_multi_step(
            params, nt, donate=False, fused_k=k, fused_tile=tile
        )
        state = jax.block_until_ready(step(*state))
    T_multi = dedup_global(
        np.asarray(igg.gather(state[0])), dims, nloc, (o,) * 3
    )
    igg.finalize_global_grid()

    nxg = tuple(dims[d] * (nloc[d] - o) + o for d in range(3))
    state, params = diffusion3d.setup(
        *nxg, devices=[jax.devices()[0]], quiet=True, dtype=jax.numpy.float32
    )
    step = diffusion3d.make_step(params)
    for _ in range(nt):
        state = jax.block_until_ready(step(*state))
    T_single = np.asarray(igg.gather(state[0]))
    igg.finalize_global_grid()
    np.testing.assert_allclose(T_multi, T_single, rtol=2e-5, atol=2e-5)


def test_fused_zpatch_deep_halo_z_split_matches_xla():
    """The in-kernel z-slab diffusion cadence (z-dim decomposition) vs the
    per-step path (interpret-mode kernel, 2 devices split along z)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:2], dimx=1, dimy=1, dimz=2, overlapz=4, quiet=True,
        dtype=jax.numpy.float32,
    )
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, nt, donate=False)
    T_ref = np.asarray(igg.gather(jax.block_until_ready(step(*state))[0]))
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = diffusion3d.make_multi_step(params, nt, donate=False, fused_k=2)
        T_got = np.asarray(igg.gather(jax.block_until_ready(stepf(*state))[0]))
    igg.finalize_global_grid()
    np.testing.assert_allclose(T_got, T_ref, rtol=1e-5, atol=1e-5)


def test_fused_zpatch_periodic_z_multiblock_matches_xla():
    """Periodic z with dims_z=2: the packed exports communicate via the
    wrap ppermute (neither the self-neighbor fast path nor the PROC_NULL
    masking — the third topology of `z_patch_from_export`)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:2], dimx=1, dimy=1, dimz=2, periodz=1,
        overlapz=4, quiet=True, dtype=jax.numpy.float32,
    )
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, nt, donate=False)
    T_ref = np.asarray(igg.gather(jax.block_until_ready(step(*state))[0]))
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = diffusion3d.make_multi_step(params, nt, donate=False, fused_k=2)
        T_got = np.asarray(igg.gather(jax.block_until_ready(stepf(*state))[0]))
    igg.finalize_global_grid()
    np.testing.assert_allclose(T_got, T_ref, rtol=1e-5, atol=1e-5)


def test_fused_zpatch_periodic_z_bfloat16():
    """The z-patch/export cadence at bf16 (itemsize 2): packing, patch
    application, and export must be dtype-clean — compared against the XLA
    bf16 path at bf16 accuracy.  nt=4 = two fused groups, so the second
    group applies a REAL export-derived patch in-kernel (one group would
    only ever apply the trivial identity patch)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:1], periodz=1, overlapz=4, quiet=True,
        dtype=jax.numpy.bfloat16,
    )
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, nt, donate=False)
    T_ref = np.asarray(
        jax.block_until_ready(step(*state))[0].astype(jax.numpy.float32)
    )
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = diffusion3d.make_multi_step(params, nt, donate=False, fused_k=2)
        T_got = np.asarray(
            jax.block_until_ready(stepf(*state))[0].astype(jax.numpy.float32)
        )
    igg.finalize_global_grid()
    # bf16 has ~3 decimal digits; values are O(100).
    np.testing.assert_allclose(T_got, T_ref, rtol=0.05, atol=0.5)


def test_fused_zpatch_periodic_z_matches_xla():
    """Same cadence on the periodic self-neighbor z config (1 device)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    nt = 4
    kw = dict(
        devices=jax.devices()[:1], periodz=1, overlapz=4, quiet=True,
        dtype=jax.numpy.float32,
    )
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, nt, donate=False)
    T_ref = np.asarray(jax.block_until_ready(step(*state))[0])
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    with force_tpu_interpret_mode():
        stepf = diffusion3d.make_multi_step(params, nt, donate=False, fused_k=2)
        T_got = np.asarray(jax.block_until_ready(stepf(*state))[0])
    igg.finalize_global_grid()
    np.testing.assert_allclose(T_got, T_ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("periodz", [1, 0], ids=["periodz", "walls"])
def test_fused_self_z_wraps_in_kernel(periodz):
    """On one device a periodic z is its own z partner: the kernel wraps z
    inside its tiles (``fused.z_self_wrap`` counts the groups), and the
    program has no z-patch op left (no ``igg_zpatch_*`` scope).  With walls
    nothing wraps.  Either way the result is the XLA cadence's."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    from implicitglobalgrid_tpu.models._fused import Z_SELF_WRAP_COUNTER
    from implicitglobalgrid_tpu.utils import telemetry

    def wraps():
        return telemetry.snapshot()["counters"].get(Z_SELF_WRAP_COUNTER, 0)

    nt = 4
    kw = dict(
        devices=jax.devices()[:1], periodz=periodz, overlapz=4, quiet=True,
        dtype=jax.numpy.float32,
    )
    state, params = diffusion3d.setup(16, 32, 128, **kw)
    step = diffusion3d.make_multi_step(params, nt, donate=False)
    T_ref = np.asarray(jax.block_until_ready(step(*state))[0])
    igg.finalize_global_grid()

    state, params = diffusion3d.setup(16, 32, 128, **kw)
    before = wraps()
    with force_tpu_interpret_mode():
        stepf = diffusion3d.make_multi_step(params, nt, donate=False, fused_k=2)
        text = stepf.lower(*state).as_text(debug_info=True)
        T_got = np.asarray(jax.block_until_ready(stepf(*state))[0])
    igg.finalize_global_grid()
    assert (wraps() > before) == bool(periodz)
    assert "igg_zpatch_" not in text
    variant = "zpatch" if periodz else "plain"
    assert f"igg_diffusion_k2_{variant}" in text
    np.testing.assert_allclose(T_got, T_ref, rtol=1e-5, atol=1e-5)
