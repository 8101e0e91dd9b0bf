"""Share (%) of the HBM roofline of the diffusion kernel
(`ops/pallas_stencil.py`): see `igg_bench/roofline.py`."""

from igg_bench.roofline import read  # noqa: F401
