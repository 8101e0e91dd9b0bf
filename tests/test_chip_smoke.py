"""CPU rehearsal of ``chip_smoke.py``: every phase at a small shape, with
the kernels in the TPU interpreter, so a wrong path, argument or check is
found here rather than on the chip.  The compiled-kernel check
(``tpu_custom_call``) only exists on the chip and is stubbed out here; the
real-size Mosaic compiles live in ``tests/test_tpu_compile.py``."""

import importlib.util
import json
import os

import pytest

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMALL = (16, 32, 128)  # the smallest block every kernel envelope admits

ONE_CHIP_SIZES = {
    "three_function": dict(shape=(8, 8, 8), nt=3),
    "diffusion_fused": dict(shape=SMALL, nchunks=2),
    "diffusion512_fused": dict(shape=SMALL, tile=(8, 16), nchunks=1),
    "acoustic_fused": dict(shape=SMALL, k=4, nchunks=1),
    "porous_fused": dict(shape=SMALL, k=4, npt=6, nchunks=1),
    "zpatch_fused": dict(shape=SMALL, nchunks=1),
    "ensemble_fused": dict(shape=SMALL, nchunks=1),
}


def _run(smoke, monkeypatch, phases, sizes):
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    monkeypatch.setattr(smoke, "_require_kernel", lambda exe, name: None)
    lines = []
    with force_tpu_interpret_mode():
        smoke.run_phases(phases, sizes=sizes, emit=lines.append)
    return [json.loads(line) for line in lines]


@pytest.mark.parametrize("phase", sorted(ONE_CHIP_SIZES))
def test_one_chip_phase_rehearsal(smoke, monkeypatch, phase):
    fn = getattr(smoke, phase)
    assert fn in smoke.ONE_CHIP
    sizes = {phase: dict(ONE_CHIP_SIZES[phase], devices=jax.devices()[:1])}
    recs = _run(smoke, monkeypatch, (fn,), sizes)
    assert recs[0]["phase"] == "compile" and recs[0]["programs"] >= 1
    assert recs[1]["phase"] == phase and recs[1]["ok"] is True
    assert recs[1]["max_err"] <= smoke.REL_TOL


def test_four_chip_rehearsal(smoke, monkeypatch):
    """The decomposed grid on 4 virtual devices: dims (2,2,1), XLA bitwise
    equal to one device, pipelined fused within tolerance."""
    sizes = {"four_chip": dict(shape=(32, 48, 128), k=2, tile=(8, 16),
                               nchunks=1, devices=jax.devices()[:4])}
    recs = _run(smoke, monkeypatch, (smoke.four_chip,), sizes)
    assert recs[1]["phase"] == "four_chip" and recs[1]["ok"] is True
    assert "(2, 2, 1)" in recs[1]["check"]


def test_refuses_without_a_tpu(smoke, capsys):
    """No CPU fallback: on this CPU-only backend the script exits non-zero,
    names the platform it found and prints no result line."""
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert '"ok"' not in out.out
