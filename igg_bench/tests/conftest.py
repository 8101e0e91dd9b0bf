"""CPU rehearsal of the benchmark: 8 virtual CPU devices, 32-bit types
(the kernels lower no 64-bit index), Pallas kernels in the TPU interpreter.

    JAX_PLATFORMS=cpu python -m pytest igg_bench/tests -q

Run on its own, not together with the library's `tests/` (whose conftest
turns 64-bit types on).
"""

import json
import os
import shutil
import sys

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", False)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: per config: the smallest blocks the kernel envelopes admit
TINY_CONFIGS = {
    "diffusion3d_256": dict(local_shape=[16, 32, 128]),
    "diffusion3d_256_x4": dict(local_shape=[32, 48, 128], fused_k=2,
                               fused_tile=[8, 16], overlap=[4, 4, 2]),
}
TINY_STEPS = {"walls.1000": 8, "periodz.1000": 8}

#: the porous solver has no cell yet (its deployment is unsourced, PERF.md
#: section 7); the rehearsal adds one from data files alone, as a later PR would
POROUS_CELL = {
    "config": {"name": "porous_tiny", "solver": "porous", "local_shape": [16, 32, 128],
               "dtype": "float32", "dims": [1, 1, 1], "overlap": [2, 2, 2], "npt": 10,
               "fused_k": 6, "fused_tile": None, "pipelined": None},
    "traffic": {"name": "walls.4", "periodic": "", "steps_per_call": 4},
    "limits": {"rel_err": 2e-2},
    "workload": {"name": "porous.tiny", "config": "porous_tiny", "traffic": "walls.4",
                 "chips": 1, "why": "rehearsal only"},
}


def _rewrite(path, **over):
    with open(path) as f:
        doc = json.load(f)
    doc.update(over)
    with open(path, "w") as f:
        json.dump(doc, f)


def make_tiny_root(dest):
    """A copy of the benchmark whose configurations and traffic are cut to
    tiny sizes (the same files, a few numbers changed), with a peak entry
    for the CPU so the roofline arithmetic runs."""
    shutil.copytree(os.path.join(ROOT, "igg_bench"), os.path.join(dest, "igg_bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    for name, over in TINY_CONFIGS.items():
        _rewrite(os.path.join(dest, "igg_bench", "configs", f"{name}.json"), **over)
    for name, steps in TINY_STEPS.items():
        _rewrite(os.path.join(dest, "igg_bench", "traffic", f"{name}.json"),
                 steps_per_call=steps)
    peaks = os.path.join(dest, "igg_bench", "peaks.json")
    with open(peaks) as f:
        doc = json.load(f)
    doc["devices"]["cpu"] = {"hbm_bytes_per_s": 1e11}
    with open(peaks, "w") as f:
        json.dump(doc, f)
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b, cell = os.path.join(dest, "igg_bench"), POROUS_CELL
    for kind, name, doc in (("configs", "porous_tiny", cell["config"]),
                            ("traffic", "walls.4", cell["traffic"]),
                            ("limits", "porous.tiny", cell["limits"])):
        with open(os.path.join(b, kind, f"{name}.json"), "w") as f:
            json.dump(doc, f)
    spec["configs"].append({"name": "porous_tiny", "source": "rehearsal",
                            "file": "igg_bench/configs/porous_tiny.json",
                            "reduced": [], "why": "rehearsal only"})
    spec["workloads"].append(cell["workload"])
    return spec


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp("tiny"))
    return dest, make_tiny_root(dest)


@pytest.fixture(autouse=True)
def _finalize_grid():
    yield
    import implicitglobalgrid_tpu as igg

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
