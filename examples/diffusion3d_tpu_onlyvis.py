"""Visualization pattern only — the solver lines are elided.

Port of `/root/reference/examples/diffusion3D_multigpu_CuArrays_onlyvis.jl`,
which documents just the in-situ visualization recipe: every ``nvis`` steps,
strip the halo locally, gather the blocks to process 0, and render the
mid-plane.  The solver (physics, numerics, stencil update) is deliberately
elided — see `diffusion3d_multidevice.py` for the complete program — but the
recipe itself is runnable: one field stands in for the solver state so the
strip/gather/frame path executes end to end.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import implicitglobalgrid_tpu as igg


def diffusion3d(nx=8, ny=8, nz=8, nt=3, nvis=1, **grid_kwargs):
    # Physics
    # (...)

    # Numerics
    # (...)
    me, dims, nprocs, coords, mesh = igg.init_global_grid(nx, ny, nz, **grid_kwargs)

    # Array initializations + initial conditions (solver arrays elided — one
    # field suffices to demonstrate the visualization recipe)
    T = igg.zeros((nx, ny, nz))

    # Preparation of visualization: the gathered array is the halo-stripped
    # blocks side by side — (n-2)*dims cells per dimension.
    frames = []
    ny_v = (ny - 2) * dims[1]

    # Time loop
    for it in range(nt):
        if it % nvis == 0:  # visualize every nvis-th step
            T_nohalo = igg.block_slice(T, (slice(1, -1),) * 3)  # strip halo locally
            T_v = igg.gather(T_nohalo)  # gather on process 0
            if me == 0:
                frames.append(np.array(T_v[:, ny_v // 2, :]).T)  # mid-plane heatmap frame
        # (... stencil update + update_halo ...)

    # Postprocessing: write frames to GIF/MP4 on process 0.
    # (...)
    igg.finalize_global_grid()
    return frames


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    n = len(diffusion3d())
    print(f"onlyvis recipe produced {n} frame(s)")
