"""The acoustic cells through the whole harness on the CPU at tiny sizes:
both run and are correct against the plain staggered reference; each fault
a cell can have turns ``correct`` false; the new per-layer readers read
what they should and nothing where there is nothing."""

import json
import os
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from igg_bench import control, harness

CELLS = ("acoustic256.fused", "acoustic256.fused.periodz")
#: the smallest block the k=6 leapfrog tile ladder admits (tile (8, 16))
TINY_SHAPE = [24, 32, 128]
#: two kernel groups of 6 per call: the interpreted kernel is slow
TINY_STEPS = {"walls.1002": 12, "periodz12.1002": 12}


@pytest.fixture(scope="module")
def acoustic_tiny(tiny, tmp_path_factory):
    """The conftest's tiny root, copied, with the acoustic configuration and
    traffic cut to tiny sizes (the same files, a few numbers changed)."""
    root, spec = tiny
    dest = str(tmp_path_factory.mktemp("acoustic_tiny"))
    shutil.copytree(os.path.join(root, "igg_bench"), os.path.join(dest, "igg_bench"))
    b = os.path.join(dest, "igg_bench")

    def rewrite(rel, **over):
        path = os.path.join(b, rel)
        with open(path) as f:
            doc = json.load(f)
        doc.update(over)
        with open(path, "w") as f:
            json.dump(doc, f)

    rewrite(os.path.join("configs", "acoustic3d_256.json"), local_shape=TINY_SHAPE)
    for name, steps in TINY_STEPS.items():
        rewrite(os.path.join("traffic", f"{name}.json"), steps_per_call=steps)
    return dest, spec


def _run(root_spec, cell, *, seed=2**31 + 29, wrap_step=None):
    root, spec = root_spec
    with force_tpu_interpret_mode():
        return harness.run(root, spec, cell, seed, 0.2, False, jax.devices(),
                           t0=time.perf_counter(), wrap_step=wrap_step)


@pytest.mark.parametrize("cell", CELLS)
def test_acoustic_cell_runs_and_is_correct(acoustic_tiny, cell):
    line = _run(acoustic_tiny, cell)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"teff_gbs", "setup_s"}
    assert line["device"]["count"] == 1
    assert line["checks"]["rel_err"]["value"] < 1e-5


def _unchanged(step):
    return jax.jit(lambda *s: tuple(a + 0 for a in s))


def _altered(step):
    def body(*s):
        out = step(*s)
        P = out[0]
        bump = 0.1 * jnp.max(jnp.abs(P))
        return (P.at[tuple(n // 4 + 1 for n in P.shape)].add(bump),) + tuple(out[1:])

    return jax.jit(body)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _altered], ids=["unchanged", "altered"])
def test_acoustic_fault_is_caught(acoustic_tiny, cell, fault):
    line = _run(acoustic_tiny, cell, wrap_step=fault)
    assert line["correct"] is False
    assert line["failed"] == 1


def test_acoustic_periodic_z_exchange_left_out(acoustic_tiny, monkeypatch):
    """The four-field z patches replaced by zeros: the self-neighbour z
    exchange of the staggered cadence is left out."""
    from implicitglobalgrid_tpu.ops import halo

    monkeypatch.setattr(
        halo, "z_patches_from_exports",
        lambda exports, shape, *, width, coalesce=None: tuple(
            jnp.zeros_like(e) for e in exports))
    assert _run(acoustic_tiny, "acoustic256.fused.periodz")["correct"] is False


def test_kernel_fallback_is_refused(acoustic_tiny, monkeypatch):
    """A run whose leapfrog kernel path falls back to the XLA cadence is
    refused, not measured as the kernel's cell."""
    from implicitglobalgrid_tpu.ops import pallas_leapfrog

    monkeypatch.setattr(pallas_leapfrog, "fused_support_error",
                        lambda *a, **k: "refused by the test")
    with pytest.raises((harness.BenchError, RuntimeWarning), match="fell back|fused_k="):
        _run(acoustic_tiny, "acoustic256.fused")


def _control(root_spec, cell, dtype, seed):
    root, spec = root_spec
    c = harness.load_cell(root, spec, cell)
    return _run(root_spec, cell, seed=seed, wrap_step=control.place(c, dtype))


@pytest.mark.parametrize("cell", CELLS)
def test_acoustic_bfloat16_reference_is_not_correct(acoustic_tiny, cell):
    for seed in (3, 2**31 + 99):
        line = _control(acoustic_tiny, cell, jnp.bfloat16, seed)
        assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_acoustic_float32_reference_in_place_is_correct(acoustic_tiny, cell):
    """The reference in the program's place, through the staggered fields'
    layout maps (a periodic Vz has one face fewer globally than locally)."""
    line = _control(acoustic_tiny, cell, jnp.float32, 5)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["rel_err"]["value"] < 1e-5


def _load_metric(name):
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return harness.load_module(root, "metrics", name)


def _trace(top_ops):
    return SimpleNamespace(top_ops=top_ops, kernel_s=0.5, n_kernel_ops=3)


def test_leapfrog_kernel_ms_reads_named_kernels_only():
    read = _load_metric("leapfrog_kernel_ms_per_step").read
    ctx = SimpleNamespace(calls=2, units_per_call=1000, trace=_trace([
        ["jit_f/while/body/igg_leapfrog_k6_plain/pallas_call (custom-call, kernel)", 0.3],
        ["jit_f/igg_leapfrog_k6_zpatch/pallas_call (custom-call, kernel)", 0.1],
        ["jit_f/igg_diffusion_k4_plain/pallas_call (custom-call, kernel)", 0.05],
        ["jit_f/igg_faces_pad/pad (fusion, glue)", 0.01],
    ]))
    assert read(ctx) == pytest.approx(0.4 * 1e3 / 2000)
    ctx.trace = _trace([["jit_f/igg_diffusion_k4_plain/pallas_call (custom-call, kernel)",
                         0.2]])
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_fused_fallbacks_reads_the_program_counter(monkeypatch):
    from implicitglobalgrid_tpu.utils import telemetry

    read = _load_metric("fused_fallbacks").read
    snap = {"counters": {}}
    monkeypatch.setattr(telemetry, "snapshot", lambda: snap)
    assert read(None) is None  # a program that keeps no such counter
    snap["counters"]["fused.fallback"] = 0
    assert read(None) == 0.0
    snap["counters"]["fused.fallback"] = 2
    assert read(None) == 2.0
