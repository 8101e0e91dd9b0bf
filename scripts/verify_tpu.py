"""One-command hardware validation on a real TPU chip.

Runs the end-to-end drives that the CPU test suite cannot: compiled (not
interpreted) kernels and exchanges on the attached chip, via the public API
only.  Complements `python -m pytest tests/` (virtual 8-device CPU mesh) and
`python bench.py` (performance).

    python scripts/verify_tpu.py

Checks:
 1. periodic self-neighbor halo restoration on the chip,
 2. fused Pallas kernel vs the XLA path (few-ULP, ring bit-exact),
 3. deep-halo temporal blocking (fused + width-k slab exchange) vs the
    per-step XLA path on a communicating (periodic) grid,
 4. the XLA-only slab cadence (`exchange_every`) matching per-step to
    few f32 ULPs (per-program FMA contraction),
 5. example `diffusion3d_tpu_fused` end-to-end,
 6. the hide_communication overlap schedule in the TPU backend's compiled
    multi-chip program: async collective-permute-start/-done pairs present,
    and no exchange waiting on the interior fusion (AOT topology compile;
    skipped with a pointer to the CPU-mesh dataflow test when the runtime
    cannot compile for a multi-chip topology),
 7. the staggered fused leapfrog kernel (even-extent padded layout) vs the
    XLA acoustic path — compiled, the config the round-2 infeasibility note
    said could not run (reversed in round 3, see docs/performance.md),
 8. the fused PT-iteration kernel vs the per-iteration XLA porous path —
    compiled, scale-relative tolerance (flux magnitudes scale as
    |grad Pf|/dx, so absolute ULP size scales with them),
 9. the multi-chip staggered fused program AOT-compiled for an 8-chip TPU
    topology: acoustic fused_k chunk (Mosaic kernel + width-k all-field
    slab exchange) lowered over a 2x2x2 mesh — the Pallas custom call and
    the collective-permute exchanges coexist in one compiled program,
10. the round-4 z-patch export cadence AOT-compiled for the same 8-chip
    topology with a REAL z split: one fused group (in-kernel patch apply +
    z-slab export) + x/y exchanges of field and packed export + the packed
    z communication (`z_patch_from_export`) in one program,
11. the same production cadence scaled to a 16-chip (4,2,2) topology with
    TWO pipelined kernel groups — the weak-scaling compile proxy.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sync(x):
    """Completion sync: fetch one element (the benchmark harness's sync,
    `benchmarks/run.py::_sync`)."""
    shard = x.addressable_shards[0].data
    float(shard[(0,) * shard.ndim])
    return x


def check_self_neighbor():
    import jax
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    state, params = diffusion3d.setup(
        32, 32, 32, periodx=1, quiet=True, dtype=jax.numpy.float32
    )
    step = diffusion3d.make_step(params)
    for _ in range(3):
        state = step(*state)
    T = np.asarray(sync(state[0]))
    o = igg.get_global_grid().overlaps[0]
    assert np.array_equal(T[-1], T[o - 1]), "self-neighbor hi plane"
    assert np.array_equal(T[0], T[-o]), "self-neighbor lo plane"
    igg.finalize_global_grid()
    print("1. periodic self-neighbor halo: OK")


def check_fused_vs_xla():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    state, params = diffusion3d.setup(64, 128, 256, quiet=True, dtype=jnp.float32)
    xla = diffusion3d.make_multi_step(params, 4, donate=False)
    fused = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=4)
    ref = np.asarray(sync(xla(*state)[0]))
    got = np.asarray(sync(fused(*state)[0]))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    T0 = np.asarray(state[0])
    for ax in range(3):
        assert np.array_equal(np.take(got, 0, axis=ax), np.take(T0, 0, axis=ax))
    igg.finalize_global_grid()
    print(f"2. fused kernel vs XLA (compiled): OK, max|d|={np.max(np.abs(got - ref)):.2e}")


def check_deep_halo_slab():
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    kw = dict(periodz=1, overlapz=4, quiet=True, dtype=jnp.float32)
    state, params = diffusion3d.setup(64, 64, 256, **kw)
    sx = diffusion3d.make_multi_step(params, 4, donate=False)
    sf = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=2)
    ref = np.asarray(sync(sx(*state)[0]))
    got = np.asarray(sync(sf(*state)[0]))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    igg.finalize_global_grid()
    print(
        "3. deep-halo temporal blocking (fused + width-2 slab exchange): OK, "
        f"max|d|={np.max(np.abs(got - ref)):.2e}"
    )


def check_cadence():
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    kw = dict(periodz=1, overlapz=4, quiet=True, dtype=jnp.float32)
    state, params = diffusion3d.setup(64, 64, 256, **kw)
    sx = diffusion3d.make_multi_step(params, 4, donate=False)
    sc = diffusion3d.make_multi_step(params, 4, donate=False, exchange_every=2)
    ref = np.asarray(sync(sx(*state)[0]))
    got = np.asarray(sync(sc(*state)[0]))
    # Few-ULP, not bitwise: the two programs fuse differently and XLA's FMA
    # contraction rounds differently per program on TPU (measured ~5e-7 on
    # O(100) values; the CPU-mesh test is bitwise because codegen matches).
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    igg.finalize_global_grid()
    print(
        "4. XLA slab cadence (exchange_every=2) matches per-step: OK, "
        f"max|d|={np.max(np.abs(got - ref)):.2e}"
    )


def check_example():
    import importlib.util

    import numpy as np

    ex = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples",
        "diffusion3d_tpu_fused.py",
    )
    spec = importlib.util.spec_from_file_location("dtf", ex)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    T = mod.diffusion3d_fused(nx=128, nt=40, k=2, quiet=True)
    assert np.isfinite(np.asarray(T)).all()
    print("5. fused example end-to-end: OK")


def _aot_hide_comm_hlo():
    """Compile the hide_comm step for an 8-chip TPU topology AOT (no second
    chip needed); returns the optimized HLO text, or raises when the runtime
    cannot compile for a multi-chip topology (the only legitimate skip)."""
    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.ops.overlap import hide_communication
    from implicitglobalgrid_tpu.utils.aot import synthetic_topology_grid

    # Build the per-block program against the AOT mesh via the shared
    # synthetic-GlobalGrid scaffold (the public init path binds to the
    # attached client's devices, which is exactly what AOT avoids).
    with synthetic_topology_grid((2, 2, 2), (16, 16, 16)) as (gg, mesh):
        params = diffusion3d.Params(
            dx=0.1, dy=0.1, dz=0.1, dt=1e-4, dtype=np.float32, hide_comm=True
        )
        update = diffusion3d._diffusion_update(params)
        overlapped = hide_communication(update, radius=1)

        def block_step(T, Cp):
            return overlapped(T, Cp), Cp

        mapped = jax.jit(
            jax.shard_map(
                block_step, mesh=mesh,
                in_specs=(P("x", "y", "z"),) * 2,
                out_specs=(P("x", "y", "z"),) * 2,
                check_vma=False,
            )
        )
        aval = jax.ShapeDtypeStruct(
            (32, 32, 32), np.float32, sharding=NamedSharding(mesh, P("x", "y", "z"))
        )
        return mapped.lower(aval, aval).compile().as_text()


def check_overlap_schedule():
    """Pin the overlap claim on the real backend's compiled program: async
    collective-permute-start/-done pairs + no exchange waiting on the
    interior fusion.  Only the AOT compile itself may skip; a failed
    ASSERTION on the obtained program fails the whole script."""
    from implicitglobalgrid_tpu.utils.hlo_analysis import collective_waits

    try:
        txt = _aot_hide_comm_hlo()
    except Exception as e:  # noqa: BLE001 — report and point at the CPU pin
        print(
            f"6. overlap schedule: SKIPPED ({type(e).__name__}: {e}) — the "
            "dataflow property is pinned by tests/test_stencil_overlap.py::"
            "test_hide_comm_collectives_do_not_wait_on_interior on the "
            "8-device CPU mesh"
        )
        return
    n_cp, waits, n_async = collective_waits(txt, 16 * 16 * 16)
    assert n_cp >= 6, f"expected >= 6 exchanges in the AOT program, got {n_cp}"
    assert n_async > 0, "TPU program has no async collective-permute-start"
    assert "collective-permute-done" in txt
    assert not any(waits), f"exchange waits on the interior fusion: {waits}"
    print(
        f"6. overlap schedule (AOT 2x2x2): OK — {n_async} async "
        "collective-permute-start/-done pairs, none waiting on the interior"
    )


def check_staggered_fused():
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import acoustic3d

    state, params = acoustic3d.setup(64, 128, 256, quiet=True, dtype=jnp.float32)
    xla = acoustic3d.make_multi_step(params, 6, donate=False)
    fused = acoustic3d.make_multi_step(params, 6, donate=False, fused_k=6)
    ref = [np.asarray(A) for A in xla(*state)]
    sync(state[0])
    got = fused(*state)
    sync(got[0])
    got = [np.asarray(A) for A in got]
    for name, g, r in zip(("P", "Vx", "Vy", "Vz"), got, ref):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5, err_msg=name)
    # Frozen velocity boundary faces stay bit-exact; P's boundary evolves.
    Vx0 = np.asarray(state[1])
    assert np.array_equal(got[1][0], Vx0[0]) and np.array_equal(got[1][-1], Vx0[-1])
    assert not np.array_equal(got[0][0], np.asarray(state[0])[0])
    igg.finalize_global_grid()
    print(
        "7. staggered fused leapfrog kernel vs XLA (compiled): OK, "
        f"max|dP|={np.max(np.abs(got[0] - ref[0])):.2e}"
    )


def _aot_staggered_fused_hlo():
    """AOT-compile the acoustic fused_k chunk for an 8-chip topology.

    Same synthetic-GlobalGrid technique as `_aot_hide_comm_hlo`; the mesh is
    2x2x2 with deep halos in every dimension, local blocks (16, 32, 128)
    with the (8, 16) tile, so the kernel envelope accepts the block and the
    program contains BOTH the Mosaic kernel custom-call and the width-2
    slab exchanges."""
    import numpy as np

    import jax

    from implicitglobalgrid_tpu.utils.aot import synthetic_topology_grid

    with synthetic_topology_grid(
        (2, 2, 2), (16, 32, 128), (4, 4, 4)
    ) as (gg, mesh):
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from implicitglobalgrid_tpu.ops.pallas_leapfrog import (
            fused_leapfrog_steps,
            pad_faces,
            unpad_faces,
        )

        # The fused chunk body of acoustic3d.make_multi_step's deep-halo
        # branch, shard_mapped by hand (the `stencil` wrapper builds from
        # concrete args, which AOT avals cannot provide).
        c = 1e-3 / 0.1

        from implicitglobalgrid_tpu.ops.halo import update_halo_padded_faces

        def block_step(Pf, Vx, Vy, Vz):
            def group(i, s):
                s = fused_leapfrog_steps(
                    *s, 2, c, c, c, 1e-3, 10.0, 10.0, 10.0, bx=8, by=16
                )
                return update_halo_padded_faces(*s, width=2)

            Pf, Vxp, Vyp, Vzp = lax.fori_loop(
                0, 2, group, (Pf, *pad_faces(Vx, Vy, Vz))
            )
            return (Pf, *unpad_faces(Vxp, Vyp, Vzp))

        mapped = jax.jit(
            jax.shard_map(
                block_step, mesh=mesh,
                in_specs=(P("x", "y", "z"),) * 4,
                out_specs=(P("x", "y", "z"),) * 4,
                check_vma=False,
            )
        )
        spec = NamedSharding(mesh, P("x", "y", "z"))
        avals = tuple(
            jax.ShapeDtypeStruct(s, np.float32, sharding=spec)
            for s in ((32, 64, 256), (34, 64, 256), (32, 66, 256), (32, 64, 258))
        )
        return mapped.lower(*avals).compile().as_text()


def check_multichip_fused_aot():
    """Pin the multi-chip staggered fused path on the real backend's AOT
    compiler: kernel custom-call + collective-permute exchanges in one
    program.  Only the AOT compile itself may skip (same rule as check 6)."""
    try:
        txt = _aot_staggered_fused_hlo()
    except Exception as e:  # noqa: BLE001 — report and point at the CPU pin
        print(
            f"9. multi-chip staggered fused AOT: SKIPPED ({type(e).__name__}: "
            f"{e}) — the path is pinned by tests/test_models_acoustic.py::"
            "test_fused_deep_halo_matches_xla_multiblock on the CPU mesh"
        )
        return
    assert "tpu_custom_call" in txt, "no Mosaic kernel custom-call in the AOT program"
    n_cp = txt.count("collective-permute-start(") + txt.count("collective-permute(")
    assert n_cp >= 6, f"expected >= 6 slab exchanges in the AOT program, got {n_cp}"
    print(
        f"9. multi-chip staggered fused AOT (2x2x2): OK — Mosaic kernel + "
        f"{n_cp} collective-permute exchanges in one program"
    )


def _aot_zpatch_fused_hlo(dims=(2, 2, 2), k=2, groups=1):
    """AOT-compile ``groups`` diffusion z-patch-export group(s) over a mesh.

    Same synthetic-GlobalGrid technique as `_aot_staggered_fused_hlo`, but
    the mesh has a real z split, so the compiled program must contain the
    Mosaic kernel (with its z-export output), the x/y collective-permute
    slab exchanges of BOTH the field and the packed export, and the packed
    z communication of `z_patch_from_export`.  ``dims=(4,2,2)`` with
    ``groups=2`` is the 16-chip production-shape variant (check 11)."""
    import numpy as np

    import jax

    from implicitglobalgrid_tpu.utils.aot import synthetic_topology_grid

    o = 2 * k
    with synthetic_topology_grid(dims, (16, 32, 128), (o, o, o)) as (gg, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from implicitglobalgrid_tpu.ops.halo import (
            apply_z_patch,
            exchange_dims,
            identity_z_patch,
            z_patch_from_export,
        )
        from implicitglobalgrid_tpu.ops.pallas_stencil import fused_diffusion_steps

        c = 1e-3 / 0.01

        def block_step(T, Cp):
            patch = identity_z_patch(T, width=k)
            for _ in range(groups):
                T, zex = fused_diffusion_steps(
                    T, Cp, k, c, c, c, bx=8, by=16, z_patch=patch,
                    z_export=True, z_overlap=o,
                )
                T = exchange_dims(T, (0, 1), width=k)
                zex = exchange_dims(zex, (0, 1), width=k)
                patch = z_patch_from_export(zex, width=k)
            return apply_z_patch(T, patch, width=k)

        mapped = jax.jit(
            jax.shard_map(
                block_step, mesh=mesh,
                in_specs=(P("x", "y", "z"),) * 2,
                out_specs=P("x", "y", "z"),
                check_vma=False,
            )
        )
        spec = NamedSharding(mesh, P("x", "y", "z"))
        gshape = (16 * dims[0], 32 * dims[1], 128 * dims[2])
        avals = tuple(
            jax.ShapeDtypeStruct(gshape, np.float32, sharding=spec)
            for _ in range(2)
        )
        return mapped.lower(*avals).compile().as_text()


def check_zpatch_export_aot():
    """Pin the round-4 z-split production cadence on the TPU AOT compiler."""
    try:
        txt = _aot_zpatch_fused_hlo()
    except Exception as e:  # noqa: BLE001 — report and point at the CPU pin
        print(
            f"10. z-patch export cadence AOT: SKIPPED ({type(e).__name__}: "
            f"{e}) — the path is pinned by tests/test_models_diffusion.py::"
            "test_fused_zpatch_random_topology_invariance on the CPU mesh"
        )
        return
    assert "tpu_custom_call" in txt, "no Mosaic kernel custom-call in the AOT program"
    n_cp = txt.count("collective-permute-start(") + txt.count("collective-permute(")
    # x/y exchanges of T (4) + of the packed export (4) + the packed z
    # communication's two ppermutes = >= 10 collective-permutes.
    assert n_cp >= 10, f"expected >= 10 collective-permutes, got {n_cp}"
    # The z hop must move packed (n0, n1, k) slabs, NOT full arrays — the
    # point of the export design.  Local block (16,32,128), k=2: count the
    # thin-slab permute OPS (start/sync forms only — an async op's matching
    # -done line would double-count the same hop).
    thin = sum(
        1
        for line in txt.splitlines()
        if ("collective-permute-start(" in line or "collective-permute(" in line)
        and "f32[16,32,2]" in line
    )
    assert thin >= 2, (
        f"expected >= 2 packed (16,32,2) z-slab collective-permutes, got {thin}"
    )
    print(
        f"10. z-patch export cadence AOT (2x2x2, z split): OK — Mosaic kernel "
        f"+ {n_cp} collective-permutes ({thin} packed (16,32,2) z hops; no "
        "full-array z exchange) in one program"
    )


def check_zpatch_export_aot_16chip():
    """Scale the production cadence compile to 16 chips, two groups — the
    weak-scaling compile proxy at (4,2,2): the program must pipeline two
    kernel groups with packed z hops between them."""
    try:
        txt = _aot_zpatch_fused_hlo(dims=(4, 2, 2), k=4, groups=2)
    except Exception as e:  # noqa: BLE001
        print(
            f"11. 16-chip production cadence AOT: SKIPPED ({type(e).__name__}: {e})"
        )
        return
    assert "tpu_custom_call" in txt, "no Mosaic kernel custom-call"
    n_cp = txt.count("collective-permute-start(") + txt.count("collective-permute(")
    thin = sum(
        1
        for line in txt.splitlines()
        if ("collective-permute-start(" in line or "collective-permute(" in line)
        and "f32[16,32,4]" in line
    )
    assert n_cp >= 20, f"expected >= 20 collective-permutes (2 groups), got {n_cp}"
    assert thin >= 4, f"expected >= 4 packed z hops (2 groups x 2), got {thin}"
    print(
        f"11. 16-chip (4,2,2) production cadence AOT: OK — 2 pipelined kernel "
        f"groups, {n_cp} collective-permutes, {thin} packed (16,32,4) z hops"
    )


def check_pt_fused():
    import jax.numpy as jnp
    import numpy as np

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import porous_convection3d as pc

    state, params = pc.setup(64, 128, 256, npt=4, quiet=True, dtype=jnp.float32)
    xla = pc.make_multi_step(params, 2, donate=False)
    fused = pc.make_multi_step(params, 2, donate=False, fused_k=2)
    ref = [np.asarray(A) for A in xla(*state)]
    sync(state[0])
    got = fused(*state)
    sync(got[0])
    got = [np.asarray(A) for A in got]
    worst = 0.0
    for name, g, r in zip(("T", "Pf", "qDx", "qDy", "qDz"), got, ref):
        scale = max(float(np.abs(r).max()), 1.0)
        rel = float(np.abs(g - r).max()) / scale
        worst = max(worst, rel)
        assert rel < 1e-5, (name, rel)
    igg.finalize_global_grid()
    print(f"8. fused PT-iteration kernel vs XLA (compiled): OK, worst rel={worst:.2e}")




def check_transposed_zpatch_aot():
    """Round 5: AOT-pin the TRANSPOSED z-patch cadence's hop structure on a
    2x2x2 topology — the full-y tile (by == n1) routes the diffusion cadence
    through the transposed thin-patch machinery (`ops.halo.*_t`), and the
    compiled program's collective-permutes must all move slab-sized
    payloads (never a full block).  The transposed routing itself is pinned
    structurally: the export's y exchange slices axis 2 (an
    `exchange_dims_t`-only shape), so its (n0, PE, w) hop can only exist if
    the cadence really built transposed patches."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from implicitglobalgrid_tpu.utils.aot import synthetic_topology_grid
    from implicitglobalgrid_tpu.utils.hlo_analysis import collective_payloads

    k = 2
    try:
        ctx = synthetic_topology_grid((2, 2, 2), (16, 32, 128), (4, 4, 4))
        ctx.__enter__()
    except Exception as e:  # noqa: BLE001 — AOT topology is the only skip
        print(
            f"12. transposed z-patch cadence AOT: SKIPPED ({type(e).__name__}: "
            f"{e}) — the layout equivalence is pinned by tests/test_update_halo"
            ".py::test_transposed_z_patch_communication_matches_packed on the "
            "CPU mesh"
        )
        return
    try:
        gg = None
        from implicitglobalgrid_tpu.parallel.grid import global_grid

        gg = global_grid()
        mesh = gg.mesh
        from implicitglobalgrid_tpu.models import diffusion3d

        params = diffusion3d.Params(
            dx=0.1, dy=0.1, dz=0.1, dt=1e-4, dtype=jax.numpy.float32
        )
        step = diffusion3d.make_multi_step(
            params, 2 * k, donate=False, fused_k=k, fused_tile=(8, 32)
        )
        shapes = tuple(
            jax.ShapeDtypeStruct(
                (32, 64, 256), jax.numpy.float32,
                sharding=NamedSharding(mesh, P("x", "y", "z")),
            )
            for _ in range(2)
        )
        txt = step.lower(*shapes).compile().as_text()
    finally:
        ctx.__exit__(None, None, None)
    assert "tpu_custom_call" in txt, "no Mosaic kernel custom-call in the AOT program"
    hops = collective_payloads(txt)
    assert len(hops) >= 10, f"expected >= 10 hops, got {len(hops)}"
    block_bytes = 16 * 32 * 128 * 4
    biggest = max(h["bytes"] for h in hops)
    assert biggest < block_bytes // 4, (
        f"a collective moves {biggest} bytes — slab exchanges should be far "
        f"below the {block_bytes}-byte block (full-array z exchange regression?)"
    )
    # The transposed-routing signature: the export's axis-2 y-slab hop,
    # shape (n0, pad8(4k), w) = (16, 8, 2).
    assert any(h["shape"] == "f32[16,8,2]" for h in hops), (
        "no (16,8,2) export y-slab hop — the cadence did not route through "
        f"the transposed patch machinery (hops: {[h['shape'] for h in hops]})"
    )
    print(
        f"12. transposed z-patch cadence AOT (2x2x2, full-y tile): OK — "
        f"{len(hops)} slab hops incl. the (16,8,2) transposed-export y hop, "
        f"largest {biggest} B << {block_bytes} B block"
    )


if __name__ == "__main__":
    import jax

    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()

    print("device:", jax.devices()[0].device_kind)
    check_self_neighbor()
    check_fused_vs_xla()
    check_deep_halo_slab()
    check_cadence()
    check_example()
    check_overlap_schedule()
    check_staggered_fused()
    check_pt_fused()
    check_multichip_fused_aot()
    check_zpatch_export_aot()
    check_zpatch_export_aot_16chip()
    check_transposed_zpatch_aot()
    print("ALL TPU CHECKS PASSED")
