"""Tests for the temporally-blocked staggered Pallas leapfrog kernel.

Same harness as `tests/test_pallas_stencil.py`: interpret-mode kernel on the
CPU suite (the interpreter implements the DMA/semaphore semantics the
double-buffering + padded-layout logic needs validated); compiled-mode
equivalence and numbers come from `bench.py` / `scripts/verify_tpu.py` on the
real chip.

Oracle: ``fused_leapfrog_steps(..., k)`` vs ``k`` applications of the
acoustic model's `_velocity_update` + `_pressure_update` — few-ULP interior
agreement (same constant folds, different FMA contraction), bit-exact frozen
velocity boundary faces, and P evolving at ALL cells including the global
boundary (the staggered model's boundary semantics, unlike the diffusion
kernel's frozen-cell ring).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from implicitglobalgrid_tpu.models.acoustic3d import (
    Params,
    _pressure_update,
    _velocity_update,
)
from implicitglobalgrid_tpu.ops.pallas_leapfrog import (
    default_tile,
    fused_leapfrog_steps,
    fused_support_error,
    pad_faces,
    unpad_faces,
)


def _setup(shape, seed=0, spacing=(0.1, 0.1, 0.1), K=1.0, rho=1.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    n0, n1, n2 = shape
    P = jnp.asarray(rng.standard_normal(shape), dtype)
    Vx = jnp.asarray(0.1 * rng.standard_normal((n0 + 1, n1, n2)), dtype)
    Vy = jnp.asarray(0.1 * rng.standard_normal((n0, n1 + 1, n2)), dtype)
    Vz = jnp.asarray(0.1 * rng.standard_normal((n0, n1, n2 + 1)), dtype)
    dx, dy, dz = spacing
    dt = min(spacing) / (K / rho) ** 0.5 / 2.0
    params = Params(K=K, rho=rho, dx=dx, dy=dy, dz=dz, dt=dt, dtype=dtype)
    return (P, Vx, Vy, Vz), params


def _xla_steps(state, params, k):
    vu = _velocity_update(params)
    pu = _pressure_update(params)

    @jax.jit
    def step(P, Vx, Vy, Vz):
        Vx, Vy, Vz = vu(P, Vx, Vy, Vz)
        return pu(P, Vx, Vy, Vz), Vx, Vy, Vz

    for _ in range(k):
        state = step(*state)
    return state


def _fused_interpret(state, params, k, **kw):
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    P, Vx, Vy, Vz = state
    cax = params.dt / params.rho / params.dx
    cay = params.dt / params.rho / params.dy
    caz = params.dt / params.rho / params.dz
    b = params.dt * params.K
    Vxp, Vyp, Vzp = pad_faces(Vx, Vy, Vz)
    with force_tpu_interpret_mode():
        Pg, Vxp, Vyp, Vzp = fused_leapfrog_steps(
            P, Vxp, Vyp, Vzp, k, cax, cay, caz, b,
            1.0 / params.dx, 1.0 / params.dy, 1.0 / params.dz, **kw,
        )
    return (Pg, *unpad_faces(Vxp, Vyp, Vzp))


@pytest.mark.parametrize(
    "k,shape,tile",
    [
        (2, (16, 32, 128), dict(bx=8, by=16)),
        (4, (16, 32, 128), dict(bx=8, by=16)),
        (6, (32, 32, 128), dict(bx=8, by=16)),
        # k=8: in the envelope since round 5 (H=16 y-halo margin)
        (8, (32, 64, 128), dict(bx=8, by=16)),
    ],
)
def test_fused_matches_k_single_steps(k, shape, tile):
    state, params = _setup(shape, spacing=(0.1, 0.15, 0.2), K=1.3, rho=0.8)
    ref = _xla_steps(state, params, k)
    got = _fused_interpret(state, params, k, **tile)
    names = ("P", "Vx", "Vy", "Vz")
    for name, g, r in zip(names, got, ref):
        g, r = np.asarray(g), np.asarray(r)
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5, err_msg=name)
    # Frozen velocity boundary faces: bit-exact (never touched by either
    # path).
    for d, (g0, v0) in enumerate(zip(got[1:], state[1:])):
        g0, v0 = np.asarray(g0), np.asarray(v0)
        for ax in range(3):
            assert np.array_equal(np.take(g0, 0, axis=ax), np.take(v0, 0, axis=ax))
            last = g0.shape[ax] - 1
            assert np.array_equal(
                np.take(g0, last, axis=ax), np.take(v0, last, axis=ax)
            )
    # P must EVOLVE at the global boundary (all-cells update — the staggered
    # semantics the diffusion kernel's frozen ring does not have).
    P0, Pk = np.asarray(state[0]), np.asarray(got[0])
    for ax in range(3):
        assert not np.array_equal(np.take(Pk, 0, axis=ax), np.take(P0, 0, axis=ax))


def test_default_tile_shape():
    # The production default (32, 64) on a volume that admits it.
    state, params = _setup((64, 128, 128))
    assert default_tile((64, 128, 128), 2) == (32, 64)
    ref = _xla_steps(state, params, 2)
    got = _fused_interpret(state, params, 2)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-5, atol=2e-5)


def test_pad_unpad_roundtrip():
    state, _ = _setup((16, 32, 128), seed=3)
    _, Vx, Vy, Vz = state
    back = unpad_faces(*pad_faces(Vx, Vy, Vz))
    for a, b in zip(back, (Vx, Vy, Vz)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bfloat16_structure():
    # Structural correctness at bf16 accuracy + bit-exact frozen faces.
    state, params = _setup((16, 32, 128), seed=5, dtype=jnp.bfloat16)
    ref = _xla_steps(state, params, 2)
    got = _fused_interpret(state, params, 2, bx=8, by=16)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(
            np.asarray(g.astype(jnp.float32)),
            np.asarray(r.astype(jnp.float32)),
            atol=0.05, rtol=0.05,
        )
    Vx0, Vxk = np.asarray(state[1].astype(jnp.float32)), np.asarray(
        got[1].astype(jnp.float32)
    )
    assert np.array_equal(Vxk[0], Vx0[0])
    assert np.array_equal(Vxk[-1], Vx0[-1])


def test_envelope_validation():
    state, params = _setup((16, 32, 128))
    P, Vx, Vy, Vz = state
    Vxp, Vyp, Vzp = pad_faces(Vx, Vy, Vz)
    args = (0.1, 0.1, 0.1, 0.1, 10.0, 10.0, 10.0)
    with pytest.raises(ValueError, match="k must be even"):
        fused_leapfrog_steps(P, Vxp, Vyp, Vzp, 3, *args)
    with pytest.raises(ValueError, match="does not divide"):
        fused_leapfrog_steps(P, Vxp, Vyp, Vzp, 2, *args, bx=7, by=16)
    with pytest.raises(ValueError, match="pad_faces layout"):
        fused_leapfrog_steps(P, Vx, Vy, Vz, 2, *args)
    # Minor-dim lane alignment (Mosaic HBM-slice requirement, probed on
    # hardware at n2=192 — also enforced for the diffusion kernel now).
    assert "multiple of 128" in fused_support_error((16, 32, 192), 2)
    assert "multiple of 128" in fused_support_error((64, 128, 192), 2)
    assert fused_support_error((16, 32, 2048), 2) is not None
    assert fused_support_error((16, 32, 128), 2, 4, 8, None) is not None
    # VMEM budget rejects oversize tiles before Mosaic stack OOM (probed:
    # (32,128) k=6 at n2=256).
    assert "VMEM" in fused_support_error((256, 256, 256), 6, 4, 32, 128)


def test_diffusion_envelope_minor_alignment():
    # The same probe closed a latent diffusion-kernel envelope gap.
    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        fused_support_error as diff_err,
    )

    assert "multiple of 128" in diff_err((64, 128, 192), 2)
    assert diff_err((64, 128, 256), 2) is None


@pytest.mark.parametrize("seed", range(4))
def test_random_envelope_config_matches_xla(seed):
    """Property sweep: a random envelope-valid (shape, k, tile) config must
    match k XLA leapfrog steps (same oracle as the pinned cases)."""
    rng = np.random.default_rng(100 + seed)
    k = int(rng.choice([2, 4, 6]))
    bx = int(rng.choice([8, 16]))
    by = int(rng.choice([8, 16]))
    H = 8 * ((k + 7) // 8)
    n0 = bx * int(rng.integers((2 * k) // bx + 2, 5))
    n1 = by * max(int(rng.integers(2, 5)), (by + 2 * H) // by + 1)
    shape = (n0, n1, 128)
    err = fused_support_error(shape, k, 4, bx, by)
    if err is not None:
        pytest.skip(f"random config rejected by envelope: {err}")
    state, params = _setup(shape, seed=seed, spacing=(0.11, 0.13, 0.17), K=1.4, rho=0.7)
    ref = _xla_steps(state, params, k)
    got = _fused_interpret(state, params, k, bx=bx, by=by)
    for name, g, r in zip(("P", "Vx", "Vy", "Vz"), got, ref):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=3e-5, atol=3e-5, err_msg=name
        )


def test_envelope_rejects_x64():
    # f64/complex reaches the XLA fallback, not a Mosaic compile error
    # (TPU Pallas has no 8-byte element type) — all three kernels share
    # the check via ops/_fused_envelope.py.
    from implicitglobalgrid_tpu.ops.pallas_pt import fused_support_error as pt_err
    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        fused_support_error as diff_err,
    )

    for err_fn in (fused_support_error, pt_err, diff_err):
        assert "not supported by TPU" in err_fn((64, 128, 128), 2, 8)
        assert err_fn((64, 128, 128), 2, 4) is None


@pytest.mark.parametrize(
    "k,shape,o,bx",
    [
        (2, (16, 32, 128), 4, 8),
        (2, (16, 32, 128), 6, 8),  # o > 2k: the sources sit deeper than the halo
        (6, (32, 32, 128), 12, 8),
        # 24 rows a tile: one step of the wrap's row-block loop and a rest
        (2, (48, 32, 128), 4, 24),
    ],
)
def test_z_wrap_matches_patch_export_cadence(k, shape, o, bx):
    """The in-tile self-neighbour z wrap equals the patch/export kernel
    followed by the top-face fix-up and the chunk-end patch apply, bit for
    bit, over two groups from a state whose z halos are not fresh — the four
    fields' real faces, the frozen Vx row n0 / Vy column n1 among them."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.ops.halo import (
        apply_z_patches,
        fix_topface_z_exports,
        identity_z_patches,
    )

    state, params = _setup(shape, seed=7, spacing=(0.1, 0.15, 0.2), K=1.3, rho=0.8)
    coef = (
        params.dt / params.rho / params.dx, params.dt / params.rho / params.dy,
        params.dt / params.rho / params.dz, params.dt * params.K,
        1.0 / params.dx, 1.0 / params.dy, 1.0 / params.dz,
    )
    tile = dict(bx=bx, by=16)
    # one device, periodic z: every block is its own z partner
    igg.init_global_grid(*shape, periodz=1, overlapz=o, quiet=True,
                         devices=jax.devices()[:1])
    s0 = (state[0], *pad_faces(*state[1:]))
    with force_tpu_interpret_mode():
        s, patches = s0, identity_z_patches(*s0, width=k)
        for _ in range(2):
            out = fused_leapfrog_steps(
                *s, k, *coef, **tile, z_patches=patches, z_export=True,
                z_overlap=o,
            )
            s = out[:4]
            # the self partner hands the exports back as the next patches
            patches = fix_topface_z_exports(out[4:], *s, width=k)
        want = apply_z_patches(*s, patches, width=k)
        got = s0
        for _ in range(2):
            got = fused_leapfrog_steps(*got, k, *coef, **tile, z_wrap=True,
                                       z_overlap=o)
    want = (want[0], *unpad_faces(*want[1:]))
    got = (got[0], *unpad_faces(*got[1:]))
    for name, g, w, a in zip(("P", "Vx", "Vy", "Vz"), got, want, state):
        assert not np.array_equal(np.asarray(g), np.asarray(a)), name
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
