"""3-D heat diffusion with in-situ visualization on process 0.

Port of `/root/reference/examples/diffusion3D_multicpu.jl` (vis variant; the
GPU twin is `diffusion3D_multigpu_CuArrays.jl`).  Every ``nvis`` steps the
halo-stripped temperature blocks are gathered to the root process and a
mid-plane heatmap frame is written; at the end the frames become a GIF —
the reference's `gather!` → `heatmap` → `gif` pipeline
(`/root/reference/examples/diffusion3D_multicpu.jl:44-56,66-68`).

Run:
    python examples/diffusion3d_multidevice.py [--nx 64] [--nt 2000] [--nvis 500]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import implicitglobalgrid_tpu as igg


def diffusion3d_vis(nx=64, nt=2000, nvis=500, device_type="auto", outdir="."):
    import jax.numpy as jnp

    lam, cp_min = 1.0, 1.0
    lx, ly, lz = 10.0, 10.0, 10.0
    ny = nz = nx
    me, dims, nprocs, coords, mesh = igg.init_global_grid(
        nx, ny, nz, device_type=device_type
    )
    dx = lx / (igg.nx_g() - 1)
    dy = ly / (igg.ny_g() - 1)
    dz = lz / (igg.nz_g() - 1)
    dtype = jax.dtypes.canonicalize_dtype(float)

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
    dt = min(dx * dx, dy * dy, dz * dz) * cp_min / lam / 8.1

    def inn(A):
        return A[1:-1, 1:-1, 1:-1]

    @igg.stencil(donate_argnums=(0,))
    def step(T, Cp):
        lap = (
            (T[2:, 1:-1, 1:-1] - 2 * inn(T) + T[:-2, 1:-1, 1:-1]) / (dx * dx)
            + (T[1:-1, 2:, 1:-1] - 2 * inn(T) + T[1:-1, :-2, 1:-1]) / (dy * dy)
            + (T[1:-1, 1:-1, 2:] - 2 * inn(T) + T[1:-1, 1:-1, :-2]) / (dz * dz)
        )
        T = T + jnp.pad(dt * lam / inn(Cp) * lap, 1)
        return igg.update_halo(T), Cp

    # Preparation of visualization (reference :42-48): the gathered array is
    # the halo-stripped blocks side by side — (nx-2)*dims per dimension.
    frames = []
    ny_v = (ny - 2) * dims[1]
    sync = mesh.devices.flat[0].platform == "cpu"

    for it in range(nt):
        if it % nvis == 0:  # reference :52 (visualize every nvis-th step)
            T_nohalo = igg.block_slice(T, (slice(1, -1),) * 3)  # strip halo (:53)
            T_v = igg.gather(T_nohalo)  # gather on process 0 (:54)
            if me == 0:
                frames.append(np.array(T_v[:, ny_v // 2, :]).T)  # mid-plane (:55)
        T, Cp = step(T, Cp)
        if sync:
            jax.block_until_ready(T)

    if me == 0 and frames:
        _write_frames(frames, outdir)
    igg.finalize_global_grid()
    return frames


def _write_frames(frames, outdir):
    """Write heatmap frames; make a GIF when matplotlib is available
    (the reference's `gif(anim, ...)`, else dump raw .npy frames)."""
    try:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        from matplotlib import animation

        fig, ax = plt.subplots()
        im = ax.imshow(frames[0], origin="lower", aspect="equal", cmap="inferno")
        fig.colorbar(im, ax=ax)

        def update(i):
            im.set_data(frames[i])
            im.autoscale()
            ax.set_title(f"frame {i}")
            return (im,)

        ani = animation.FuncAnimation(fig, update, frames=len(frames))
        path = os.path.join(outdir, "diffusion3d.gif")
        ani.save(path, writer=animation.PillowWriter(fps=15))
        print(f"wrote {path} ({len(frames)} frames)")
    except Exception as e:  # matplotlib optional in this environment
        path = os.path.join(outdir, "diffusion3d_frames.npy")
        np.save(path, np.stack(frames))
        print(f"matplotlib unavailable ({e!r}); wrote {path}")


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=64)
    p.add_argument("--nt", type=int, default=2000)
    p.add_argument("--nvis", type=int, default=500)
    p.add_argument("--device-type", default="auto")
    p.add_argument("--outdir", default=".")
    a = p.parse_args()
    diffusion3d_vis(a.nx, a.nt, a.nvis, a.device_type, a.outdir)
