"""The diffusion solver against an independent plain reference.

The benchmark's plain diffusion step (`igg_bench/solvers/diffusion.py::
reference`, written from the model's equations in `jax.numpy`, loaded by
path as the benchmark harness loads it) advances the same seeded fields as
the program's `diffusion3d.make_multi_step`: the fused k=4 kernel (in the
Pallas TPU interpreter) on walls and on a periodic z with overlap 8, the
benchmark's two one-chip traffic mixes.  48 steps are 12 kernel groups: 8
unrolled and 4 in two paired `fori_loop` iterations.
"""

import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from igg_bench import harness
from implicitglobalgrid_tpu.models import diffusion3d
from implicitglobalgrid_tpu.models._fused import Z_SELF_WRAP_COUNTER
from implicitglobalgrid_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (16, 32, 128)
NSTEPS, K = 48, 4
TRAFFIC = {
    "walls": {"periodic": "", "steps_per_call": NSTEPS},
    "periodz": {"periodic": "z", "overlap": {"z": 8}, "steps_per_call": NSTEPS},
}
#: f32 rounding only: the kernel folds the constants as ``lap*c`` times a
#: precomputed ``1/Cp``, the reference as ``dt*lam/Cp`` times ``lap/h^2``;
#: over 48 steps that reads ~1e-7 of the field's largest value (the
#: benchmark's cells allow 1e-3 over 1000 steps).
REL_TOL = 1e-5


solver = harness.load_module(ROOT, "solvers", "diffusion")


def _counter(name):
    return telemetry.snapshot()["counters"].get(name, 0)


def _advance(traffic):
    """The seeded fields before and after one call of the fused program,
    and the plain reference's fields after the same steps."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    cfg = {"local_shape": list(SHAPE), "dtype": "float32", "dims": [1, 1, 1],
           "overlap": [2, 2, 2], "fused_k": K}
    grid = harness.make_grid(cfg, TRAFFIC[traffic])
    cell = SimpleNamespace(grid=grid, solver=solver)
    state, params = solver.setup(cfg, grid, jax.devices()[:1])
    state = harness.seeded_state(cell, state, 2**31 + 23)
    before = [np.asarray(igg.gather(a, dedup=True)) for a in state]

    with force_tpu_interpret_mode():
        step = diffusion3d.make_multi_step(params, NSTEPS, donate=False, fused_k=K)
        out = jax.block_until_ready(step(*state))
    after = [np.asarray(igg.gather(a, dedup=True)) for a in out]
    igg.finalize_global_grid()
    want = solver.reference(before, grid, cfg, TRAFFIC[traffic], jnp.float32)
    return before, after, [np.asarray(w) for w in want]


def _worst(after, want):
    return max(harness.rel_err(g, w) for g, w in zip(after, want))


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_fused_program_matches_plain_reference(traffic):
    fallbacks, wraps = _counter("fused.fallback"), _counter(Z_SELF_WRAP_COUNTER)
    before, after, want = _advance(traffic)

    assert _counter("fused.fallback") == fallbacks, "the kernel path fell back"
    wrapped = _counter(Z_SELF_WRAP_COUNTER) > wraps
    assert wrapped == (traffic == "periodz")
    for name, got, w in zip(solver.FIELDS, after, want):
        assert got.shape == w.shape, name
        assert harness.rel_err(got, w) <= REL_TOL, (name, harness.rel_err(got, w))
    # the fields moved: a program that returned its input would fail above
    assert harness.rel_err(before[0], want[0]) > REL_TOL


def test_periodic_z_wrap_left_out_is_caught(monkeypatch):
    """The kernel run without its in-tile z wrap, so the self-neighbour z
    exchange of the periodic-z cadence is left out: the comparison with the
    plain reference fails by far more than the benchmark cell's limit."""
    from implicitglobalgrid_tpu.ops import pallas_stencil

    kernel = pallas_stencil.fused_diffusion_steps

    def no_wrap(*a, z_wrap=False, z_overlap=None, **kw):
        return kernel(*a, **kw)

    monkeypatch.setattr(pallas_stencil, "fused_diffusion_steps", no_wrap)
    wraps = _counter(Z_SELF_WRAP_COUNTER)
    _, after, want = _advance("periodz")
    assert _counter(Z_SELF_WRAP_COUNTER) > wraps, "the wrap path did not run"
    limit = harness.load_json(
        ROOT, os.path.join("igg_bench", "limits", "diffusion256.fused.periodz.json")
    )["rel_err"]
    assert _worst(after, want) > 10 * limit, _worst(after, want)
