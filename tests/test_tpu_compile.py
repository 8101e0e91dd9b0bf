"""The main path's kernel programs compiled at real size for a described
TPU v5e (no chip attached): the installed Mosaic must accept every kernel
the chip smoke runs, and the four-chip cadence must keep its collectives.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU compiler); everything built from it is built
in the fixture too.  The programs compile in parallel threads with the
persistent compile cache off.  A compile here is not a chip run: it says
nothing about results or times.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# name: (model, local n (cube), dims, overlaps, nsteps, make_multi_step
#        kwargs, extra setup kwargs)
# Longest compile first (Mosaic time grows with the tile window: ~180, 165,
# 145, 90, 80, 40, 25, 16 and 16 s here), so three threads finish close together.
PROGRAMS = {
    "diffusion_k4_512_tile32x128": ("diffusion3d", 512, (1, 1, 1), (2, 2, 2),
                                    8, dict(fused_k=4, fused_tile=(32, 128)),
                                    {}),
    # at the cell's 256^3: at 128^3 memory-space assignment keeps the
    # padded x/y face fields (8.9 MB) in VMEM and evicts them to HBM in
    # every loop iteration, a path the cell's 66 MiB fields never take
    "acoustic_k6_256_periodz": ("acoustic3d", 256, (1, 1, 1), (2, 2, 12), 78,
                                dict(fused_k=6), {}),
    "diffusion_k4_256": ("diffusion3d", 256, (1, 1, 1), (2, 2, 2), 8,
                         dict(fused_k=4), {}),
    "porous_k6_npt10_256": ("porous_convection3d", 256, (1, 1, 1), (2, 2, 2),
                            1, dict(fused_k=6), dict(npt=10)),
    "acoustic_k6_256": ("acoustic3d", 256, (1, 1, 1), (2, 2, 2), 12,
                        dict(fused_k=6), {}),
    "four_chip_diffusion_pipelined": (
        "diffusion3d", 256, (2, 2, 1), (8, 8, 2), 8,
        dict(fused_k=4, fused_tile=(32, 64), pipelined=True),
        dict(overlapx=8, overlapy=8)),
    "diffusion_k4_128_periodz": ("diffusion3d", 128, (1, 1, 1), (2, 2, 8), 48,
                                 dict(fused_k=4), {}),
    "diffusion_k4_128_walls": ("diffusion3d", 128, (1, 1, 1), (2, 2, 2), 48,
                               dict(fused_k=4), {}),
    "acoustic_k6_128_walls": ("acoustic3d", 128, (1, 1, 1), (2, 2, 2), 78,
                              dict(fused_k=6), {}),
    # the four-chip split with a periodic z each chip owns: the kernel wraps z
    # inside its y-windowed tiles, under the ring/interior split
    "four_chip_diffusion_pipelined_periodz": (
        "diffusion3d", 256, (2, 2, 1), (8, 8, 8), 8,
        dict(fused_k=4, fused_tile=(32, 64), pipelined=True),
        dict(overlapx=8, overlapy=8)),
}
#: programs on a grid with periodic dimensions (x, y, z); others have none
PERIODS = {"diffusion_k4_128_periodz": (0, 0, 1), "acoustic_k6_256_periodz": (0, 0, 1),
           "four_chip_diffusion_pipelined_periodz": (0, 0, 1)}
#: programs with a fori excess: diffusion 12 groups (8 unrolled, 4 looped),
#: the loop shape of every 1000-step diffusion cell; acoustic 13 groups (8
#: unrolled, an odd excess of 5: one more unrolled, two paired iterations;
#: XLA unrolls a loop of one iteration), the shape of the 1002-step acoustic
#: cells (167 groups, excess 159)
LOOPED = ("diffusion_k4_128_periodz", "diffusion_k4_128_walls",
          "acoustic_k6_128_walls", "acoustic_k6_256_periodz")

#: what each program's text must name: the kernel variants (the `name=` of
#: each `pallas_call`) and the self-neighbour z exchange's scopes, as a
#: device trace of the program names them
NAMES = {
    "diffusion_k4_512_tile32x128": ["igg_diffusion_k4_plain"],
    "diffusion_k4_256": ["igg_diffusion_k4_plain"],
    "porous_k6_npt10_256": ["igg_pt_k6_plain"],
    "acoustic_k6_256": ["igg_leapfrog_k6_plain"],
    "four_chip_diffusion_pipelined": ["igg_diffusion_k4_plain_ring01",
                                      "igg_diffusion_k4_plain_mid01"],
    "four_chip_diffusion_pipelined_periodz": [
        "igg_diffusion_k4_zpatch_ring01", "igg_diffusion_k4_zpatch_mid01"],
    # one chip: the kernel wraps z inside its tiles (its own z partner)
    "diffusion_k4_128_periodz": ["igg_diffusion_k4_zpatch"],
    "diffusion_k4_128_walls": ["igg_diffusion_k4_plain"],
    "acoustic_k6_128_walls": ["igg_leapfrog_k6_plain", "igg_faces_pad",
                              "igg_faces_unpad"],
    # one chip: the kernel wraps z inside its tiles (its own z partner)
    "acoustic_k6_256_periodz": ["igg_leapfrog_k6_zpatch", "igg_faces_pad",
                                "igg_faces_unpad"],
}
#: what a program's text must not name: the one-chip periodic-z programs
#: have no patch to make at chunk entry, none to take from an export and none
#: to apply at their end
ABSENT = {
    "acoustic_k6_256_periodz": ["igg_zpatch_identity", "igg_zpatch_apply"],
    "diffusion_k4_128_periodz": ["igg_zpatch_identity", "igg_zpatch_apply",
                                 "igg_zpatch_from_export"],
    "four_chip_diffusion_pipelined_periodz": [
        "igg_zpatch_identity", "igg_zpatch_apply", "igg_zpatch_from_export"],
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    import importlib
    from concurrent.futures import ThreadPoolExecutor

    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import NamedSharding, PartitionSpec as P

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.utils.aot import synthetic_topology_grid

    if igg.grid_is_initialized():
        igg.finalize_global_grid()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # The suite runs with 64-bit types on; Mosaic lowers no int64 index, and
    # the chip smoke (like any f32 deployment) runs with them off.
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        lowered = {}
        for name, (model, n, dims, ovl, nsteps, kw, setup_kw) in (
                PROGRAMS.items()):
            mod = importlib.import_module(
                f"implicitglobalgrid_tpu.models.{model}")
            # Params and field ranks from a small grid: a compile needs
            # shapes, not values (staggered faces keep their +1).
            state, params = mod.setup(16, 16, 16, dtype=jnp.float32,
                                      quiet=True, devices=jax.devices()[:1],
                                      **setup_kw)
            local = [tuple(s + n - 16 for s in A.shape) for A in state]
            igg.finalize_global_grid()
            with synthetic_topology_grid(
                    dims, (n,) * 3, ovl, topo,
                    periods=PERIODS.get(name, (0, 0, 0))) as (_, mesh):
                sh = NamedSharding(mesh, P("x", "y", "z"))
                shapes = tuple(
                    jax.ShapeDtypeStruct(
                        tuple(d * e for d, e in zip(dims, shp)),
                        jnp.float32, sharding=sh)
                    for shp in local
                )
                step = mod.make_multi_step(params, nsteps, **kw)
                lowered[name] = step.lower(*shapes)
        # three threads: the suite's other workers share this machine
        with ThreadPoolExecutor(3) as pool:
            texts = pool.map(lambda lo: lo.compile().as_text(),
                             lowered.values())
            return dict(zip(lowered, texts))
    finally:
        jax.config.update("jax_enable_x64", x64)
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_kernel_program_compiles_for_v5e(compiled, name):
    assert "tpu_custom_call" in compiled[name], (
        f"{name}: no Mosaic kernel in the compiled program (an XLA or "
        "interpret path)"
    )


def test_four_chip_program_keeps_its_collectives(compiled):
    txt = compiled["four_chip_diffusion_pipelined"]
    # two groups x (x, y) slab exchanges, each a send/recv pair per side
    assert txt.count("collective-permute-start") >= 8


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_kernel_variants_are_named(compiled, name):
    txt = compiled[name]
    for want in NAMES[name]:
        assert want in txt, f"{name}: {want} not in the compiled program"
    for gone in ABSENT.get(name, ()):
        assert gone not in txt, f"{name}: {gone} in the compiled program"
    kernels = [ln for ln in txt.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(
        re.match(r"\s*(ROOT\s+)?%igg_\w+_k\d+_(plain|zpatch)", ln)
        for ln in kernels), f"{name}: an unnamed Mosaic kernel"


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_no_field_copy_in_a_while_body(compiled, name):
    """The fused group schedule's fori excess runs two kernel groups per
    iteration, so no looped group pays a whole-field copy back into the
    loop carry (`models._fused.run_group_schedule`)."""
    from implicitglobalgrid_tpu.utils.hlo_analysis import while_body_copies

    txt = compiled[name]
    if name in LOOPED:
        assert " while(" in txt, f"{name}: the group excess no longer loops"
    n = PROGRAMS[name][1]
    assert while_body_copies(txt, n ** 3) == [], (
        f"{name}: a field-sized copy in a while body"
    )


@pytest.mark.parametrize("name", ["acoustic_k6_128_walls", "acoustic_k6_256_periodz"])
def test_no_carried_array_copy_in_acoustic_while_body(compiled, name):
    """The looped acoustic schedule carries P and the three face fields in
    the padded layout (n+8 on x and y, n+128 on z); on a one-chip periodic z
    the kernel wraps z in its tiles, so no z patches are carried: no while
    body copies any of them, larger or smaller than n^3."""
    from implicitglobalgrid_tpu.ops import pallas_leapfrog
    from implicitglobalgrid_tpu.utils.hlo_analysis import while_body_copies

    n = PROGRAMS[name][1]
    cell = (n, n, n)
    shapes = [cell, *pallas_leapfrog.padded_face_shapes(cell)]
    carried = {int(np.prod(s)) for s in shapes}
    assert len(carried) > 2  # the padded sizes differ from n^3
    txt = compiled[name]
    assert " while(" in txt, f"{name}: the group excess no longer loops"
    assert while_body_copies(txt, carried) == [], (
        f"{name}: a copy of a carried array in a while body"
    )
