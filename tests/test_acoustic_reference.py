"""The staggered acoustic solver against an independent plain reference.

The benchmark's plain leapfrog (`igg_bench/solvers/acoustic.py::reference`,
written from the model's equations in `jax.numpy`, loaded by path as the
benchmark harness loads it) advances the same seeded fields as the
program's `acoustic3d.make_multi_step`: the fused k=6 leapfrog kernel (in
the Pallas TPU interpreter) and the XLA path, on walls and on a periodic z
with overlap 12.  78 steps are 13 kernel groups: 8 unrolled and an odd
excess of 5, one group unrolled before two paired `fori_loop` iterations.
The block is the smallest the k=6 tile ladder admits (tile (8, 16)).
"""

import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from igg_bench import harness
from implicitglobalgrid_tpu.models import acoustic3d
from implicitglobalgrid_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (24, 32, 128)
NSTEPS, K = 78, 6
TRAFFIC = {
    "walls": {"periodic": "", "steps_per_call": NSTEPS},
    "periodz": {"periodic": "z", "overlap": {"z": 12}, "steps_per_call": NSTEPS},
}
#: f32 rounding only: the kernel, the XLA path and the reference fold the
#: same constants but order and contract the operations differently; over
#: 78 steps that reads ~1e-6 of the field's largest value (the benchmark's
#: cells allow 1e-3 over 1002 steps; a skipped step or a stale halo plane
#: reads 1e-2 or more).
REL_TOL = 1e-5


solver = harness.load_module(ROOT, "solvers", "acoustic")


def _counter(name):
    return telemetry.snapshot()["counters"].get(name, 0)


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
@pytest.mark.parametrize("path", ["fused", "xla"])
def test_program_matches_plain_reference(path, traffic):
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    cfg = {"local_shape": list(SHAPE), "dtype": "float32", "dims": [1, 1, 1],
           "overlap": [2, 2, 2], "fused_k": K}
    grid = harness.make_grid(cfg, TRAFFIC[traffic])
    cell = SimpleNamespace(grid=grid, solver=solver)
    state, params = solver.setup(cfg, grid, jax.devices()[:1])
    state = harness.seeded_state(cell, state, 2**31 + 11)
    before = [np.asarray(igg.gather(a, dedup=True)) for a in state]

    fallbacks, paired = _counter("fused.fallback"), _counter("schedule.fori_paired_groups")
    with force_tpu_interpret_mode():
        step = acoustic3d.make_multi_step(
            params, NSTEPS, donate=False, fused_k=K if path == "fused" else None)
        out = jax.block_until_ready(step(*state))
    after = [np.asarray(igg.gather(a, dedup=True)) for a in out]
    igg.finalize_global_grid()

    assert "fused.fallback" in telemetry.snapshot()["counters"]
    if path == "fused":
        assert _counter("fused.fallback") == fallbacks, "the kernel path fell back"
        # the odd excess: one group before the loop, two paired iterations
        assert _counter("schedule.fori_paired_groups") == paired + 4
    want = solver.reference(before, grid, cfg, TRAFFIC[traffic], jnp.float32)
    for name, got, w in zip(solver.FIELDS, after, want):
        w = np.asarray(w)
        assert got.shape == w.shape, name
        assert harness.rel_err(got, w) <= REL_TOL, (name, harness.rel_err(got, w))
    # the fields moved: a program that returned its input would fail above
    assert harness.rel_err(before[0], np.asarray(want[0])) > 1e-2
