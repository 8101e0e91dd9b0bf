"""Smoke tests for the example scripts (the reference ships 5 runnable
`examples/diffusion3D_*` variants; these are their ports — they must stay
importable and runnable, not just exist)."""

import importlib.util
import os
import sys

import numpy as np
import pytest

_examples = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")


def _load(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(_examples, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_multidevice_novis_runs():
    import implicitglobalgrid_tpu as igg

    mod = _load("diffusion3d_multidevice_novis")
    T = mod.diffusion3d(nx=8, nt=3)
    T = np.asarray(T)
    assert T.shape == (16, 16, 16)  # 2x2x2 blocks of 8^3
    assert np.isfinite(T).all()
    assert T.max() > 0  # the Gaussian anomaly diffused, not zeroed
    assert not igg.grid_is_initialized()  # example finalizes after itself


def test_multidevice_vis_runs(tmp_path):
    import implicitglobalgrid_tpu as igg

    mod = _load("diffusion3d_multidevice")
    mod.diffusion3d_vis(nx=8, nt=4, nvis=2, outdir=str(tmp_path))
    # frames (npy fallback) or a gif must have been produced on process 0
    produced = list(tmp_path.iterdir())
    assert produced, "visualization example produced no output"
    assert not igg.grid_is_initialized()


def test_tpu_onlyvis_importable():
    # The single-device variants guard real work behind __main__/functions;
    # importing them must not initialize a grid or crash.
    import implicitglobalgrid_tpu as igg

    for name in ("diffusion3d_tpu", "diffusion3d_tpu_novis", "diffusion3d_tpu_onlyvis"):
        _load(name)
    assert not igg.grid_is_initialized()


def test_tpu_onlyvis_recipe_runs():
    # The onlyvis visualization recipe (strip halo -> gather -> mid-plane
    # frame) must execute end to end on a tiny grid, like the reference's
    # examples/diffusion3D_multigpu_CuArrays_onlyvis.jl recipe.
    import implicitglobalgrid_tpu as igg

    mod = _load("diffusion3d_tpu_onlyvis")
    frames = mod.diffusion3d(nx=8, nt=4, nvis=2, quiet=True)
    assert len(frames) == 2  # it = 0 and 2
    gg_dims = 2  # 8 devices -> 2x2x2
    for f in frames:
        assert f.shape == ((8 - 2) * gg_dims, (8 - 2) * gg_dims)
        assert np.isfinite(f).all()
    assert not igg.grid_is_initialized()


def test_tpu_fused_runs():
    # The deep-halo temporal-blocking example on the virtual mesh (interpret-
    # mode kernel; overlap=2k licenses fused_k=k on the communicating grid).
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    import implicitglobalgrid_tpu as igg

    import jax

    mod = _load("diffusion3d_tpu_fused")
    with force_tpu_interpret_mode():
        T = mod.diffusion3d_fused(
            nx=32, nt=4, k=2, quiet=True,
            devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        )
    T = np.asarray(T)
    gshape = T.shape
    assert np.isfinite(T).all() and T.max() > 0
    assert not igg.grid_is_initialized()


def test_tpu_zsplit_fused_runs():
    # The round-4 z-split production example: 2 devices are forced onto
    # dimz=2, so the in-kernel z-slab apply + export cadence is the
    # exercised path (interpret-mode kernel).
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    import jax

    import implicitglobalgrid_tpu as igg

    from implicitglobalgrid_tpu.ops.pallas_stencil import fused_support_error

    # Local blocks (16, 32, 128): inside the kernel envelope, so the example
    # runs the real z-patch cadence, not the warn-once XLA fallback.
    assert fused_support_error((16, 32, 128), 2, 4, zpatch=True) is None
    mod = _load("diffusion3d_tpu_zsplit_fused")
    with force_tpu_interpret_mode():
        T = mod.diffusion3d_zsplit(
            nx=16, ny=32, nz=128, nt=4, k=2, quiet=True,
            devices=jax.devices()[:2],
        )
    T = np.asarray(T)
    assert np.isfinite(T).all() and T.max() > 0
    assert not igg.grid_is_initialized()


def test_acoustic_fused_runs():
    # The staggered fused example on the virtual mesh (interpret-mode
    # kernel; per-block (16, 32, 128) fits the (8, 16) tile envelope at
    # k=2 — the nx=256 k=6 production default is a hardware config).
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    import jax
    import numpy as np

    import implicitglobalgrid_tpu as igg

    mod = _load("acoustic3d_tpu_fused")
    with force_tpu_interpret_mode():
        P = mod.acoustic3d_fused(
            nx=16, ny=32, nz=128, nt=4, k=2, fused_tile=(8, 16), quiet=True,
            devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        )
    assert np.isfinite(np.asarray(P)).all()
    assert not igg.grid_is_initialized()


def test_porous_fused_runs():
    # The flagship's fused production example on the virtual mesh
    # (interpret-mode kernel; per-block (16, 32, 128) fits (8, 16) at w=2).
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    import jax
    import numpy as np

    import implicitglobalgrid_tpu as igg

    mod = _load("porous_convection3d_tpu_fused")
    with force_tpu_interpret_mode():
        T = mod.porous_convection3d_fused(
            nx=16, ny=32, nz=128, nt=2, w=2, npt=4, fused_tile=(8, 16),
            quiet=True, devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        )
    assert np.isfinite(np.asarray(T)).all()
    assert not igg.grid_is_initialized()
