"""3-D heat diffusion on TPU with in-situ visualization on process 0.

Port of `/root/reference/examples/diffusion3D_multigpu_CuArrays.jl`: the full
solver with the `gather` → heatmap → GIF pipeline, with fields in TPU HBM
(``device_type="tpu"``).

Run:
    python examples/diffusion3d_tpu.py [--nx 128] [--nt 2000] [--nvis 500]
"""

import argparse
import importlib.util
import os

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "diffusion3d_multidevice", os.path.join(_here, "diffusion3d_multidevice.py")
)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=128)
    p.add_argument("--nt", type=int, default=2000)
    p.add_argument("--nvis", type=int, default=500)
    p.add_argument("--outdir", default=".")
    a = p.parse_args()
    _mod.diffusion3d_vis(a.nx, a.nt, a.nvis, "tpu", a.outdir)
