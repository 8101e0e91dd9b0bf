"""Tests for the temporally-blocked fused PT-iteration Pallas kernel.

Same harness as `tests/test_pallas_leapfrog.py` (interpret-mode kernel on
the CPU suite; compiled equivalence + numbers from `bench.py` /
`scripts/verify_tpu.py` on the real chip).

Oracle: ``fused_pt_iterations(..., k)`` vs ``k`` applications of the porous
model's `_flux_update` + `_pressure_update` pair — scale-relative few-ULP
agreement (the kernel multiplies by precomputed ``1/dx`` where the XLA path
divides; flux magnitudes scale as ``|grad Pf|/dx``, so comparisons are
normalized by each field's scale), bit-exact frozen flux boundary faces,
Pf evolving at all cells, and T read-only.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from implicitglobalgrid_tpu.models.porous_convection3d import (
    Params,
    _flux_update,
    _pressure_update,
)
from implicitglobalgrid_tpu.ops.pallas_pt import (
    default_tile,
    fused_pt_iterations,
    fused_support_error,
    pad_faces,
    unpad_faces,
)


def _setup(shape, seed=0, spacing=(0.1, 0.15, 0.2), dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    n0, n1, n2 = shape
    T = jnp.asarray(rng.standard_normal(shape), dtype)
    Pf = jnp.asarray(rng.standard_normal(shape), dtype)
    qDx = jnp.asarray(0.1 * rng.standard_normal((n0 + 1, n1, n2)), dtype)
    qDy = jnp.asarray(0.1 * rng.standard_normal((n0, n1 + 1, n2)), dtype)
    qDz = jnp.asarray(0.1 * rng.standard_normal((n0, n1, n2 + 1)), dtype)
    dx, dy, dz = spacing
    params = Params(
        Ra=100.0, lam_T=0.01, dx=dx, dy=dy, dz=dz,
        theta_q=0.5, beta_p=3e-4, dtype=dtype,
    )
    return (T, Pf, qDx, qDy, qDz), params


def _xla_iters(state, params, k):
    fu = _flux_update(params)
    pu = _pressure_update(params)
    T = state[0]

    @jax.jit
    def it(Pf, qDx, qDy, qDz):
        qDx, qDy, qDz = fu(T, Pf, qDx, qDy, qDz)
        return pu(Pf, qDx, qDy, qDz), qDx, qDy, qDz

    s = state[1:]
    for _ in range(k):
        s = it(*s)
    return s


def _fused_interpret(state, params, k, **kw):
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    T, Pf, qDx, qDy, qDz = state
    qxp, qyp, qzp = pad_faces(qDx, qDy, qDz)
    with force_tpu_interpret_mode():
        Pf, qxp, qyp, qzp = fused_pt_iterations(
            T, Pf, qxp, qyp, qzp, k,
            params.theta_q,
            1.0 / params.dx, 1.0 / params.dy, 1.0 / params.dz,
            params.Ra * params.lam_T, params.beta_p, **kw,
        )
    return (Pf, *unpad_faces(qxp, qyp, qzp))


def _assert_scale_close(got, ref, names, tol=2e-5):
    for name, g, r in zip(names, got, ref):
        g, r = np.asarray(g), np.asarray(r)
        scale = max(float(np.abs(r).max()), 1.0)
        assert float(np.abs(g - r).max()) / scale < tol, name


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
def test_fused_matches_k_single_iterations(k, shape, tile):
    state, params = _setup(shape)
    ref = _xla_iters(state, params, k)
    got = _fused_interpret(state, params, k, **tile)
    _assert_scale_close(got, ref, ("Pf", "qDx", "qDy", "qDz"))
    # Frozen flux boundary faces: bit-exact.
    for g0, q0 in zip(got[1:], state[2:]):
        g0, q0 = np.asarray(g0), np.asarray(q0)
        for ax in range(3):
            assert np.array_equal(np.take(g0, 0, axis=ax), np.take(q0, 0, axis=ax))
            last = g0.shape[ax] - 1
            assert np.array_equal(
                np.take(g0, last, axis=ax), np.take(q0, last, axis=ax)
            )
    # Pf evolves at the global boundary (all-cells update).
    Pf0, Pfk = np.asarray(state[1]), np.asarray(got[0])
    for ax in range(3):
        assert not np.array_equal(np.take(Pfk, 0, axis=ax), np.take(Pf0, 0, axis=ax))


def test_buoyancy_reaches_z_faces_only():
    # With grad(Pf) = 0 and q = 0, one iteration must produce flux ONLY on
    # interior z-faces (th * Ra*lam_T * av_z(T)) — the x/y fluxes stay zero.
    shape = (16, 32, 128)
    rng = np.random.default_rng(7)
    T = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    Pf = jnp.zeros(shape, jnp.float32)
    z = [
        jnp.zeros((17, 32, 128), jnp.float32),
        jnp.zeros((16, 33, 128), jnp.float32),
        jnp.zeros((16, 32, 129), jnp.float32),
    ]
    state = (T, Pf, *z)
    _, params = _setup(shape)
    got = _fused_interpret(state, params, 2, bx=8, by=16)
    ref = _xla_iters(state, params, 2)
    _assert_scale_close(got, ref, ("Pf", "qDx", "qDy", "qDz"))
    assert float(np.abs(np.asarray(got[3])).max()) > 0.0  # qDz moved
    # qDx/qDy only react through the induced pressure gradient, never at the
    # first iteration; check iteration count 2 left them matching XLA above.


def test_t_input_buffer_unmodified():
    # T has no output alias; the kernel must not write through the input
    # buffer either (a donation/aliasing bug would).  Snapshot the device
    # buffer before and compare after.
    state, params = _setup((16, 32, 128), seed=9)
    t_before = np.asarray(state[0]).copy()
    got = _fused_interpret(state, params, 2, bx=8, by=16)
    assert not np.array_equal(np.asarray(got[0]), np.asarray(state[1]))  # Pf moved
    np.testing.assert_array_equal(np.asarray(state[0]), t_before)


def test_bfloat16_structure():
    # Structural correctness at bf16 accuracy + bit-exact frozen flux faces
    # (same coverage bar as the diffusion and leapfrog kernels).
    state, params = _setup((16, 32, 128), seed=11, dtype=jnp.bfloat16)
    ref = _xla_iters(state, params, 2)
    got = _fused_interpret(state, params, 2, bx=8, by=16)
    for name, g, r in zip(("Pf", "qDx", "qDy", "qDz"), got, ref):
        g = np.asarray(g.astype(jnp.float32))
        r = np.asarray(r.astype(jnp.float32))
        scale = max(float(np.abs(r).max()), 1.0)
        assert float(np.abs(g - r).max()) / scale < 0.05, name
    q0, qk = np.asarray(state[2].astype(jnp.float32)), np.asarray(
        got[1].astype(jnp.float32)
    )
    assert np.array_equal(qk[0], q0[0])
    assert np.array_equal(qk[-1], q0[-1])


def test_envelope_validation():
    state, params = _setup((16, 32, 128))
    T, Pf, qDx, qDy, qDz = state
    qxp, qyp, qzp = pad_faces(qDx, qDy, qDz)
    args = (0.5, 10.0, 10.0, 10.0, 1.0, 1e-3)
    with pytest.raises(ValueError, match="k must be even"):
        fused_pt_iterations(T, Pf, qxp, qyp, qzp, 3, *args)
    with pytest.raises(ValueError, match="pad_faces layout"):
        fused_pt_iterations(T, Pf, qDx, qDy, qDz, 2, *args)
    with pytest.raises(ValueError, match="cell shape"):
        fused_pt_iterations(T[:-1], Pf, qxp, qyp, qzp, 2, *args)
    assert "multiple of 128" in fused_support_error((16, 32, 192), 2)
    assert default_tile((64, 128, 128), 2) == (32, 64)
    # The 14-buffer VMEM accounting prunes earlier than the leapfrog's 12.
    assert "VMEM" in fused_support_error((256, 256, 512), 6, 4, 32, 64)
