"""Differentiability through the SPMD machinery — a TPU-first capability.

The reference's halo exchange is imperative MPI with mutable buffers
(`/root/reference/src/update_halo.jl`) and cannot be differentiated; here
`update_halo` is a pure function of its inputs (`lax.ppermute` has a
transpose rule, the PROC_NULL masking is a `where`), so `jax.grad` flows
through the full multi-device step — adjoint/sensitivity solvers and
ML-hybrid pipelines get the exchange's VJP for free.

Oracle: central finite differences in float64 on the 8-device CPU mesh.
The loss is O(1e7) (Gaussian ICs squared over all cells), so the FD quotient
itself carries absolute error ~|loss|*2^-52/eps ≈ 1e-4 — the tolerances are
the FD's honest resolution, not the (exact) analytic gradient's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import acoustic3d, diffusion3d


def _fd_check(loss, args, wrt, points, eps=1e-5, rtol=1e-3, atol=1e-3):
    g = jax.block_until_ready(jax.grad(loss, argnums=wrt)(*args))
    A = args[wrt]
    for idx in points:
        ap = [*args]
        ap[wrt] = A.at[idx].add(eps)
        am = [*args]
        am[wrt] = A.at[idx].add(-eps)
        fd = (loss(*ap) - loss(*am)) / (2 * eps)
        np.testing.assert_allclose(
            float(g[idx]), float(fd), rtol=rtol, atol=atol, err_msg=str(idx)
        )


def test_grad_through_diffusion_step():
    """grad through stencil + ppermute exchange, checked by FD at interior,
    block-edge, and halo-plane points of the global-block array."""
    state, params = diffusion3d.setup(8, 8, 8, quiet=True, dtype=jnp.float64)
    T, Cp = state
    step = diffusion3d.make_step(params, donate=False)

    def loss(T, Cp):
        T2, _ = step(T, Cp)
        return jnp.sum(T2**2)

    _fd_check(loss, (T, Cp), 0, [(5, 5, 5), (0, 3, 3), (8, 8, 8), (15, 2, 2)])
    # Sensitivity to the coefficient field flows through too.
    _fd_check(loss, (T, Cp), 1, [(5, 5, 5), (9, 9, 9)])
    igg.finalize_global_grid()


def test_grad_through_update_halo_periodic():
    """The self-neighbor (periodic) local-copy path is linear; its VJP must
    route cotangents from the halo planes back to the interior source planes.

    Differentiation happens through a `stencil`-wrapped function (the
    production pattern): calling `update_halo` directly on global arrays
    under `jax.grad` is unsupported — the grad tracer makes it take the
    inline (inside-shard_map) path with no mesh context, and `ppermute`
    has no eval rule outside one."""
    state, params = diffusion3d.setup(
        8, 8, 8, periodx=1, quiet=True, dtype=jnp.float64
    )
    T, _ = state
    exchange = igg.stencil(lambda T: igg.update_halo(T))

    def loss(T):
        return jnp.sum(exchange(T) ** 2)

    _fd_check(loss, (T,), 0, [(1, 4, 4), (14, 4, 4), (7, 7, 7)])
    igg.finalize_global_grid()


def test_grad_through_staggered_multi_step():
    """grad of the acoustic leapfrog chunk (fori_loop of V+P updates with a
    3-field exchange per step) w.r.t. the initial pressure."""
    state, params = acoustic3d.setup(8, 8, 8, quiet=True, dtype=jnp.float64)
    P, Vx, Vy, Vz = state
    multi = acoustic3d.make_multi_step(params, 3, donate=False)

    def loss(P):
        out = multi(P, Vx, Vy, Vz)
        return jnp.sum(out[0] ** 2)

    _fd_check(loss, (P,), 0, [(4, 4, 4), (8, 8, 8), (0, 5, 5)])
    igg.finalize_global_grid()


def test_grad_through_fused_diffusion_multi_step():
    """jax.grad through `make_multi_step(fused_k=...)` (VERDICT r3 #8): the
    Pallas chunk has no VJP, so `fused_with_xla_grad` runs the kernel in the
    primal and differentiates the XLA-cadence twin in the backward pass —
    the gradient must match the XLA cadence's gradient to float rounding."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode
    from implicitglobalgrid_tpu.ops.pallas_stencil import fused_support_error

    nloc = (16, 32, 128)
    # f32: the kernel envelope rejects f64, which would silently test the
    # fallback path instead of the custom-VJP wrapper.
    assert fused_support_error(nloc, 2, 4, 8, 16, zpatch=True) is None
    kw = dict(
        devices=jax.devices()[:1], periodz=1, overlapz=4, quiet=True,
        dtype=jnp.float32,
    )
    state, params = diffusion3d.setup(*nloc, **kw)
    T, Cp = state

    with force_tpu_interpret_mode():
        fused = diffusion3d.make_multi_step(
            params, 2, donate=False, fused_k=2, fused_tile=(8, 16)
        )

        def loss_fused(T, Cp):
            T2, _ = fused(T, Cp)
            return jnp.sum(T2**2) * 1e-6

        g_fused = jax.block_until_ready(jax.grad(loss_fused, argnums=(0, 1))(T, Cp))

    cadence = diffusion3d.make_multi_step(params, 2, donate=False, exchange_every=2)

    def loss_cad(T, Cp):
        T2, _ = cadence(T, Cp)
        return jnp.sum(T2**2) * 1e-6

    g_cad = jax.block_until_ready(jax.grad(loss_cad, argnums=(0, 1))(T, Cp))
    igg.finalize_global_grid()
    for name, gf, gc in zip(("dT", "dCp"), g_fused, g_cad):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gc), rtol=1e-4, atol=1e-4, err_msg=name
        )


def test_grad_through_fused_staggered_multi_step():
    """Same custom-VJP story for a staggered fused chunk (acoustic)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode
    from implicitglobalgrid_tpu.ops.pallas_leapfrog import fused_support_error

    nloc = (16, 32, 128)
    assert fused_support_error(nloc, 2, 4, 8, 16, zpatch=True) is None
    kw = dict(
        devices=jax.devices()[:1], periodz=1, overlapz=4, quiet=True,
        dtype=jnp.float32,
    )
    state, params = acoustic3d.setup(*nloc, **kw)
    P, Vx, Vy, Vz = state

    with force_tpu_interpret_mode():
        fused = acoustic3d.make_multi_step(
            params, 2, donate=False, fused_k=2, fused_tile=(8, 16)
        )

        def loss_fused(P):
            out = fused(P, Vx, Vy, Vz)
            return jnp.sum(out[0] ** 2)

        g_fused = jax.block_until_ready(jax.grad(loss_fused)(P))

    cadence = acoustic3d.make_multi_step(params, 2, donate=False, exchange_every=2)

    def loss_cad(P):
        out = cadence(P, Vx, Vy, Vz)
        return jnp.sum(out[0] ** 2)

    g_cad = jax.block_until_ready(jax.grad(loss_cad)(P))
    igg.finalize_global_grid()
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_cad), rtol=1e-4, atol=1e-4
    )
