"""The control: the plain reference put in the program's place through
`harness.run` (`control.place`).  In bfloat16, one precision below the
configurations' float32, the harness's own check finds it not correct; in
float32 it is correct, which also shows the layout maps hold."""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from igg_bench import control, harness

CELLS = ("diffusion256.fused", "diffusion256x4.piped", "diffusion256.fused.periodz",
         "porous.tiny")


def _run(tiny, cell, dtype, seed):
    root, spec = tiny
    c = harness.load_cell(root, spec, cell)
    with force_tpu_interpret_mode():
        return harness.run(root, spec, cell, seed, 0.2, False, jax.devices(),
                           t0=time.perf_counter(), wrap_step=control.place(c, dtype))


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_reference_is_not_correct(tiny, cell):
    for seed in (3, 2**31 + 99):
        line = _run(tiny, cell, jnp.bfloat16, seed)
        assert line["correct"] is False, line["checks"]
        assert line["failed"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_float32_reference_in_place_is_correct(tiny, cell):
    line = _run(tiny, cell, jnp.float32, 5)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["rel_err"]["value"] < 1e-5
