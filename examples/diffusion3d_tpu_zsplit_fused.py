"""3-D heat diffusion, fused deep-halo cadence on a z-split decomposition.

The production path for topologies that split the MINOR (z) dimension —
where a naive slab exchange is the most expensive (minor-dim plane surgery
at lane-unaligned offsets forces whole-array relayouts at the Pallas kernel
boundary; docs/performance.md's exchanged-dimension anisotropy section).
`make_multi_step(fused_k=k)` detects z halo activity and routes the z
exchange through small patch arrays: the kernel applies the incoming patch
tile-by-tile in VMEM AND exports the next group's send slabs
(`ops/pallas_stencil.py` ``z_export``), so the z communication runs
entirely on thin arrays — on a mesh the z `collective_permute` moves
(nx, ny, k)-sized slabs instead of full fields.  Since round 5 the
diffusion cadence auto-selects full-y tiles where VMEM allows and then uses
the TRANSPOSED thin-patch layout (`ops/halo.py::z_patch_from_export_t`,
~16x less patch window traffic than the packed 128-lane form it falls back
to on y-windowed tiles).

Measured on one v5e chip (periodic-z self-neighbor degenerate config, the
same exchange work a z-split mesh pays per hop): 256^3 f32 k=4 at ~520
GB/s/chip effective (round 4 packed: 409; round-2 non-kernel cadence:
~210); the acoustic analogue reaches ~855 GB/s (vs 557 receive-side-only).

The reference has no counterpart: its z exchange always copies full halo
planes through staged buffers (`/root/reference/src/update_halo.jl:544-563`).

Run (1 device exercises the self-neighbor wrap; N devices split z):
    python examples/diffusion3d_tpu_zsplit_fused.py [--nx 256] [--nt 200] [--k 4]
"""

import argparse
import time


def diffusion3d_zsplit(nx=256, nt=200, k=4, ny=None, nz=None, **setup_kwargs):
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    ny = nx if ny is None else ny
    nz = nx if nz is None else nz
    n_dev = len(setup_kwargs.get("devices") or jax.devices())
    if n_dev > 1:
        # Force the decomposition onto z — the config this cadence exists
        # for (default dims_create splits x first).
        setup_kwargs.update(dimx=1, dimy=1, dimz=n_dev)
    else:
        # One device: periodic z makes the block its own z-neighbor, so the
        # full z-patch pipeline (pack -> communicate -> in-kernel apply +
        # export) runs and is verifiable — the reference's self-neighbor
        # trick (/root/reference/test/test_update_halo.jl:1-3).
        setup_kwargs.setdefault("periodz", 1)
    state, params = diffusion3d.setup(
        nx, ny, nz,
        overlapx=2 * k, overlapy=2 * k, overlapz=2 * k,
        dtype=jax.numpy.float32,
        **setup_kwargs,
    )
    chunk = max(k * max(min(nt, 100) // k, 1), k)
    step = diffusion3d.make_multi_step(params, chunk, fused_k=k)
    state = step(*state)  # compile + warmup chunk
    float(state[0].addressable_shards[0].data[0, 0, 0])
    igg.tic()
    for _ in range(max(nt // chunk, 1)):
        state = step(*state)
    T = diffusion3d.temperature(state)
    float(T.addressable_shards[0].data[0, 0, 0])
    t = igg.toc()
    gg = igg.get_global_grid()
    me, dims = gg.me, gg.dims
    igg.finalize_global_grid()
    if me == 0:
        steps = max(nt // chunk, 1) * chunk + chunk
        teff = 2 * nx * ny * nz * 4 / (t / (max(nt // chunk, 1) * chunk)) / 1e9
        print(
            f"z-split fused diffusion: dims={dims}, ({nx},{ny},{nz})/block, k={k}, "
            f"{steps} steps, T_eff ~ {teff:.0f} GB/s/chip (single-sync wall "
            "clock; benchmarks/run.py --period z times two windows)"
        )
    return T


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=256)
    p.add_argument("--nt", type=int, default=200)
    p.add_argument("--k", type=int, default=4)
    a = p.parse_args()
    diffusion3d_zsplit(a.nx, a.nt, a.k)
