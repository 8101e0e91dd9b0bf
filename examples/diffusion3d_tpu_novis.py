"""3-D heat diffusion pinned to TPU devices — no visualization.

Port of `/root/reference/examples/diffusion3D_multigpu_CuArrays_novis.jl`: the
reference's GPU variant differs from the CPU one only in allocating `CuArray`s
and binding each rank to a GPU (`select_device`); here the same is
``device_type="tpu"`` — fields live in TPU HBM and each host process binds its
local chips automatically.

Run:
    python examples/diffusion3d_tpu_novis.py [--nx 256] [--nt 1000]
"""

import argparse
import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "diffusion3d_multidevice_novis", os.path.join(_here, "diffusion3d_multidevice_novis.py")
)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=256)
    p.add_argument("--nt", type=int, default=1000)
    a = p.parse_args()
    import jax

    _mod.diffusion3d(nx=a.nx, nt=a.nt, device_type="tpu", dtype=jax.numpy.float32)
