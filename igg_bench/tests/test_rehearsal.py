"""The whole harness on the CPU at tiny sizes: every cell runs and is
correct; each fault a cell can have turns ``correct`` false; a new
configuration, traffic mix and metric are found by name from new files."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from igg_bench import harness

#: the benchmark's cells, and the porous solver's rehearsal cell (conftest)
CELLS = ("diffusion256.fused", "diffusion256x4.piped", "diffusion256.fused.periodz",
         "porous.tiny")


def _run(tiny, cell, *, seed=2**31 + 17, trace=False, wrap_step=None):
    root, spec = tiny
    with force_tpu_interpret_mode():
        return harness.run(root, spec, cell, seed, 0.2, trace, jax.devices(),
                           t0=time.perf_counter(), wrap_step=wrap_step)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny, cell):
    line = _run(tiny, cell)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"teff_gbs", "setup_s"}
    assert line["metrics"]["teff_gbs"]["value"] > 0
    assert line["device"]["count"] == (4 if "x4" in cell else 1)
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= harness.sampled_call(2**31 + 17) + 1


def _unchanged(step):
    return jax.jit(lambda *s: tuple(a + 0 for a in s))


def _altered(step):
    def body(*s):
        out = step(*s)
        T = out[0]
        bump = 0.1 * jnp.max(jnp.abs(T))
        return (T.at[tuple(n // 4 + 1 for n in T.shape)].add(bump),) + tuple(out[1:])

    return jax.jit(body)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _altered], ids=["unchanged", "altered"])
def test_fault_is_caught(tiny, cell, fault):
    line = _run(tiny, cell, wrap_step=fault)
    assert line["correct"] is False
    assert line["failed"] == 1


def test_exchange_between_chips_left_out(tiny, monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
    assert _run(tiny, "diffusion256x4.piped")["correct"] is False


def test_periodic_z_exchange_left_out(tiny, monkeypatch):
    from implicitglobalgrid_tpu.ops import halo

    for name in ("z_patch_from_export", "z_patch_from_export_t"):
        monkeypatch.setattr(halo, name, lambda e, *, width: jnp.zeros_like(e))
    assert _run(tiny, "diffusion256.fused.periodz")["correct"] is False


def test_new_files_are_found_by_name(tiny, tmp_path):
    """A configuration, a traffic mix, a limits file and a per-layer metric
    added as new files (and entries in BENCHMARK.json) run with no edit to
    any existing file of the harness."""
    import shutil

    root, spec = tiny
    new = str(tmp_path)
    shutil.copytree(os.path.join(root, "igg_bench"), os.path.join(new, "igg_bench"))
    b = os.path.join(new, "igg_bench")
    cfg = json.load(open(os.path.join(b, "configs", "diffusion3d_256.json")))
    cfg.update(name="diffusion3d_tiny_k2", fused_k=2)
    json.dump(cfg, open(os.path.join(b, "configs", "diffusion3d_tiny_k2.json"), "w"))
    json.dump({"name": "walls.6", "periodic": "", "steps_per_call": 6},
              open(os.path.join(b, "traffic", "walls.6.json"), "w"))
    json.dump({"rel_err": 1e-4}, open(os.path.join(b, "limits", "tiny.k2.json"), "w"))
    with open(os.path.join(b, "metrics", "calls_made.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.calls)\n")
    spec = json.loads(json.dumps(spec))
    spec["configs"].append({"name": "diffusion3d_tiny_k2", "source": "test",
                            "file": "igg_bench/configs/diffusion3d_tiny_k2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.k2", "config": "diffusion3d_tiny_k2",
                              "traffic": "walls.6", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "calls_made", "unit": "calls", "better": "higher",
                               "bound": 0.25, "source": "host_clock",
                               "workloads": ["tiny.k2"]})
    with force_tpu_interpret_mode():
        line = harness.run(new, spec, "tiny.k2", 5, 0.2, False, jax.devices(),
                           t0=time.perf_counter())
    assert line["correct"] is True
    assert line["metrics"]["calls_made"]["value"] == line["attempted"]


def test_refuses_without_a_tpu():
    """No CPU fallback: exit non-zero, name the platform, print no result."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    p = subprocess.run(
        [sys.executable, "igg_bench/run.py", "--workload", "diffusion256.fused",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_in_a_bare_checkout(tmp_path):
    """A directory with only BENCHMARK.json and igg_bench/ (no program):
    non-zero exit and no result."""
    import shutil

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    shutil.copytree(os.path.join(root, "igg_bench"), tmp_path / "igg_bench")
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "igg_bench/run.py", "--workload", "diffusion256.fused",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
