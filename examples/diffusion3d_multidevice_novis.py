"""3-D heat diffusion on the implicit global grid — no visualization.

Port of `/root/reference/examples/diffusion3D_multicpu_novis.jl` (and its GPU
twin `diffusion3D_multigpu_CuArrays_novis.jl` — on TPU the device distinction
is just `device_type`).  This is the three-function promise in action: a
single-device stencil solver plus `init_global_grid` / `update_halo` /
`finalize_global_grid` runs on every device of the slice.

Run:
    python examples/diffusion3d_multidevice_novis.py [--nx 128] [--nt 1000]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg


def diffusion3d(nx=128, ny=None, nz=None, nt=1000, device_type="auto", dtype=None):
    # Physics (reference lines :14-16)
    lam = 1.0          # thermal conductivity
    cp_min = 1.0       # minimal heat capacity
    lx, ly, lz = 10.0, 10.0, 10.0

    # Numerics
    ny = nx if ny is None else ny
    nz = nx if nz is None else nz
    me, dims, nprocs, coords, mesh = igg.init_global_grid(
        nx, ny, nz, device_type=device_type
    )
    dx = lx / (igg.nx_g() - 1)  # global grid spacing (reference :21-23)
    dy = ly / (igg.ny_g() - 1)
    dz = lz / (igg.nz_g() - 1)
    if dtype is None:
        dtype = jax.dtypes.canonicalize_dtype(float)

    # Initial conditions: heat capacity and temperature with two Gaussian
    # anomalies each, from *global* coordinates (reference :33-37).
    T = igg.zeros((nx, ny, nz), dtype)
    X, Y, Z = igg.coord_fields(T, (dx, dy, dz), dtype=dtype)

    @igg.stencil
    def init_ic(X, Y, Z):
        Cp = cp_min + (
            5 * jnp.exp(-((X - lx / 1.5) ** 2) - (Y - ly / 2) ** 2 - (Z - lz / 1.5) ** 2)
            + 5 * jnp.exp(-((X - lx / 3.0) ** 2) - (Y - ly / 2) ** 2 - (Z - lz / 1.5) ** 2)
        )
        T = 100 * jnp.exp(
            -(((X - lx / 2) / 2) ** 2) - ((Y - ly / 2) / 2) ** 2 - ((Z - lz / 3.0) / 2) ** 2
        ) + 50 * jnp.exp(
            -(((X - lx / 2) / 2) ** 2) - ((Y - ly / 2) / 2) ** 2 - ((Z - lz / 1.5) / 2) ** 2
        )
        return Cp.astype(dtype), T.astype(dtype)

    Cp, T = init_ic(X, Y, Z)

    # Time step for 3-D heat diffusion (reference :39)
    dt = min(dx * dx, dy * dy, dz * dz) * cp_min / lam / 8.1

    def inn(A):
        return A[1:-1, 1:-1, 1:-1]

    @igg.stencil(donate_argnums=(0,))
    def step(T, Cp):
        # Fourier's law + conservation of energy, fused (reference :41-45);
        # with scalar lam the flux divergence is the Laplacian.
        lap = (
            (T[2:, 1:-1, 1:-1] - 2 * inn(T) + T[:-2, 1:-1, 1:-1]) / (dx * dx)
            + (T[1:-1, 2:, 1:-1] - 2 * inn(T) + T[1:-1, :-2, 1:-1]) / (dy * dy)
            + (T[1:-1, 1:-1, 2:] - 2 * inn(T) + T[1:-1, 1:-1, :-2]) / (dz * dz)
        )
        T = T + jnp.pad(dt * lam / inn(Cp) * lap, 1)
        T = igg.update_halo(T)  # reference :46
        return T, Cp

    sync = mesh.devices.flat[0].platform == "cpu"  # virtual-mesh dispatch guard
    igg.tic()
    for it in range(nt):
        T, Cp = step(T, Cp)
        if sync:
            jax.block_until_ready(T)
    wtime = igg.toc()
    if me == 0:
        print(f"nt={nt} steps, global {igg.nx_g()}x{igg.ny_g()}x{igg.nz_g()}, "
              f"{nprocs} device(s), time {wtime:.3f} s ({wtime / nt * 1e3:.3f} ms/step)")

    igg.finalize_global_grid()
    return T


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=128)
    p.add_argument("--nt", type=int, default=1000)
    p.add_argument("--device-type", default="auto")
    a = p.parse_args()
    diffusion3d(nx=a.nx, nt=a.nt, device_type=a.device_type)
