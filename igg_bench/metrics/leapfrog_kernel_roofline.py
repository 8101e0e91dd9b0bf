"""Share (%) of the HBM roofline of the staggered leapfrog kernel
(`ops/pallas_leapfrog.py`), with the least bytes of the acoustic solver's
`least_bytes_per_group`: see `igg_bench/roofline.py`."""

from igg_bench.roofline import read  # noqa: F401
