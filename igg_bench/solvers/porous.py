"""3-D porous convection with pseudo-transient (PT) pressure iterations.

Each time step runs ``npt`` PT iterations, then one temperature update:

    qD   += theta * (-grad(Pf) + Ra * lam_T * avg_z(T) e_z - qD)   interior faces
    Pf   -= beta * div(qD)                                         all cells
    T    += dt * (lam_T * laplacian(T) - upwind(qD . grad T) / phi) interior cells

with fixed boundary faces and planes, and the time step and relaxation
constants the program's module documents (`models/porous_convection3d.py`:
dt bounded by the diffusive and advective limits, beta at 0.9 of the von
Neumann bound).  The reference below is written from these equations in
plain `jax.numpy` and imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from igg_bench import fields

FIELDS = ("T", "Pf", "qDx", "qDy", "qDz")
RA, DT_CONTRAST, PHI, THETA_Q, LENGTHS = 1000.0, 1.0, 0.1, 0.5, (2.0, 1.0, 1.0)

#: arrays a PT iteration must stream by the reference's T_eff convention
#: (`benchmarks/run.py::bench_porous`): Pf, qDx, qDy, qDz read and written
A_EFF_ARRAYS = 8


def setup(cfg, grid, devices):
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.models import porous_convection3d

    return porous_convection3d.setup(
        *grid.local_shape, dtype=jnp.dtype(cfg["dtype"]), npt=cfg["npt"],
        quiet=True, devices=devices, **grid.init_kwargs(),
    )


def make_step(params, cfg, traffic):
    from implicitglobalgrid_tpu.models import porous_convection3d

    tile = cfg.get("fused_tile")
    return porous_convection3d.make_multi_step(
        params, traffic["steps_per_call"], fused_k=cfg["fused_k"],
        fused_tile=tuple(tile) if tile else None,
        pipelined=cfg.get("pipelined"),
    )


def units_per_call(cfg, traffic) -> int:
    """PT iterations per call: the unit `teff_gbs` counts."""
    return traffic["steps_per_call"] * cfg["npt"]


def a_eff_bytes(cfg, grid) -> int:
    return A_EFF_ARRAYS * int(np.prod(grid.local_shape)) * grid.itemsize


def pt_groups(npt: int, w: int) -> list[int]:
    """Kernel groups of one time step: even chunks of at most ``w`` PT
    iterations (an odd ``npt`` runs its first iteration outside a kernel)."""
    rem, out = npt - npt % 2, []
    while rem > 0:
        k = min(w, rem)
        k -= k % 2
        out.append(k)
        rem -= k
    return out


def groups_per_call(cfg, traffic) -> int:
    return traffic["steps_per_call"] * len(pt_groups(cfg["npt"], cfg["fused_k"]))


def least_bytes_per_group(cfg, grid) -> int:
    """Least HBM bytes one group must move: T, Pf, qDx, qDy, qDz read once
    and Pf, qDx, qDy, qDz written once, at their logical (unpadded) sizes."""
    n0, n1, n2 = grid.local_shape
    cells = n0 * n1 * n2
    faces = (n0 + 1) * n1 * n2 + n0 * (n1 + 1) * n2 + n0 * n1 * (n2 + 1)
    return (2 * cells + faces + cells + faces) * grid.itemsize


def draw(seed: int) -> np.ndarray:
    """Initial temperature parameters: 3 bumps on the conductive profile,
    amplitudes up to a tenth of the contrast, and one noise salt."""
    rng = np.random.default_rng(seed)
    t = fields.draw_bumps(rng, 3, (1.0, 1.0, 1.0), (-0.1, 0.1))
    salt = rng.integers(0, 2**24, size=1)  # exact in float32
    return np.concatenate([t.ravel(), salt]).astype(np.float32)


def initial(name, g, extents, periodic, p):
    import jax.numpy as jnp

    if name != "T":
        return jnp.zeros(g[0].shape, jnp.float32)
    scale = jnp.asarray(list(extents) + [min(extents), 1.0], jnp.float32)
    t = p[:15].reshape(3, 5).astype(jnp.float32) * scale
    z = g[2].astype(jnp.float32) / (extents[2] - 1)
    return (DT_CONTRAST / 2 - DT_CONTRAST * z + fields.bumps(g, extents, periodic, t)
            + 0.01 * DT_CONTRAST * fields.hash_uniform(g, p[15].astype(jnp.uint32)))


def constants(grid):
    h = [LENGTHS[d] / (grid.extents[d] - 1) for d in range(3)]
    lam_t = 1.0 / RA
    hmin = min(h)
    dt = min(hmin**2 / lam_t / 8.1, PHI * hmin / (3.0 * RA * lam_t * DT_CONTRAST))
    k2 = 4.0 * sum(1.0 / x**2 for x in h)
    beta = 0.9 * 2.0 / (THETA_Q * k2)
    return h, dt, lam_t, beta


def reference(state, grid, cfg, traffic, dtype):
    """``steps_per_call`` time steps of ``npt`` PT iterations from
    ``state = (T, Pf, qDx, qDy, qDz)`` on the global grid (non-periodic),
    every operation in ``dtype``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if any(grid.periodic):
        raise ValueError("the porous reference has no periodic boundaries")
    h, dt, lam_t, beta = constants(grid)
    npt = cfg["npt"]
    c = lambda x: jnp.asarray(x, dtype)
    th, ralam, iphi = c(THETA_Q), c(RA * lam_t), c(1.0 / PHI)
    ih = [c(1.0 / x) for x in h]
    ih2 = [c(1.0 / (x * x)) for x in h]
    I = (slice(1, -1),) * 3

    def pt(_, s):
        T, Pf, qx, qy, qz = s
        fx = -(Pf[1:, 1:-1, 1:-1] - Pf[:-1, 1:-1, 1:-1]) * ih[0]
        fy = -(Pf[1:-1, 1:, 1:-1] - Pf[1:-1, :-1, 1:-1]) * ih[1]
        fz = (-(Pf[1:-1, 1:-1, 1:] - Pf[1:-1, 1:-1, :-1]) * ih[2]
              + ralam * c(0.5) * (T[1:-1, 1:-1, 1:] + T[1:-1, 1:-1, :-1]))
        qx = qx.at[I].add(th * (fx - qx[I]))
        qy = qy.at[I].add(th * (fy - qy[I]))
        qz = qz.at[I].add(th * (fz - qz[I]))
        div = ((qx[1:] - qx[:-1]) * ih[0] + (qy[:, 1:] - qy[:, :-1]) * ih[1]
               + (qz[:, :, 1:] - qz[:, :, :-1]) * ih[2])
        return T, Pf - c(beta) * div, qx, qy, qz

    def temperature(T, qx, qy, qz):
        Ti = T[I]
        lap = ((T[2:, 1:-1, 1:-1] - 2 * Ti + T[:-2, 1:-1, 1:-1]) * ih2[0]
               + (T[1:-1, 2:, 1:-1] - 2 * Ti + T[1:-1, :-2, 1:-1]) * ih2[1]
               + (T[1:-1, 1:-1, 2:] - 2 * Ti + T[1:-1, 1:-1, :-2]) * ih2[2])
        zero = c(0.0)
        adv = (jnp.maximum(qx[1:-2, 1:-1, 1:-1], zero) * (Ti - T[:-2, 1:-1, 1:-1]) * ih[0]
               + jnp.minimum(qx[2:-1, 1:-1, 1:-1], zero) * (T[2:, 1:-1, 1:-1] - Ti) * ih[0]
               + jnp.maximum(qy[1:-1, 1:-2, 1:-1], zero) * (Ti - T[1:-1, :-2, 1:-1]) * ih[1]
               + jnp.minimum(qy[1:-1, 2:-1, 1:-1], zero) * (T[1:-1, 2:, 1:-1] - Ti) * ih[1]
               + jnp.maximum(qz[1:-1, 1:-1, 1:-2], zero) * (Ti - T[1:-1, 1:-1, :-2]) * ih[2]
               + jnp.minimum(qz[1:-1, 1:-1, 2:-1], zero) * (T[1:-1, 1:-1, 2:] - Ti) * ih[2])
        return T.at[I].add(c(dt) * (c(lam_t) * lap - iphi * adv))

    def time_step(_, s):
        T, Pf, qx, qy, qz = lax.fori_loop(0, npt, pt, s)
        return temperature(T, qx, qy, qz), Pf, qx, qy, qz

    run = jax.jit(lambda s: lax.fori_loop(0, traffic["steps_per_call"], time_step, s))
    return run(tuple(jnp.asarray(a, dtype) for a in state))
