"""3-D porous convection with the fused PT-iteration kernel — the fast path.

The flagship (HydroMech weak-scaling analogue, BASELINE config 4) on its
production configuration: ``overlap = 2w`` deep halos license ``w``
pseudo-transient relaxation iterations per HBM pass *and* per all-field slab
exchange — `porous_convection3d.make_multi_step(fused_k=w)` wires both over
the padded face layout (`ops/pallas_pt.py`).  On one v5e chip at 256^3 f32
the PT loop sustains ~1050 GB/s/chip effective (8-pass convention, w=6) vs
~225 GB/s for the XLA path at the same size; the full time step (including
the temperature update) lands at ~700-770 GB/s/PT-iter.

``w`` must divide ``npt`` (the PT iterations per time step — a caller error
otherwise); shapes outside the kernel envelope (e.g. a minor dimension that
is not a multiple of 128) warn once and fall back to the XLA cadence.

Run (any number of devices; overlap=12 enables the tuned w=6):
    python examples/porous_convection3d_tpu_fused.py [--nx 256] [--nt 24] [--w 6] [--npt 12]
"""

import argparse


def porous_convection3d_fused(nx=256, nt=24, w=6, npt=12, ny=None, nz=None,
                              fused_tile=None, **setup_kwargs):
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import porous_convection3d as pc

    state, params = pc.setup(
        nx,
        ny if ny is not None else nx,
        nz if nz is not None else nx,
        npt=npt,
        overlapx=2 * w,
        overlapy=2 * w,
        overlapz=2 * w,
        dtype=jax.numpy.float32,
        **setup_kwargs,
    )
    # Whole time steps chunk into one program (each carries npt PT
    # iterations).
    chunk = max(min(nt, 8), 1)
    step = pc.make_multi_step(
        params, chunk, fused_k=w, fused_tile=fused_tile
    )
    state = step(*state)  # compile + warmup chunk
    float(state[0].addressable_shards[0].data[0, 0, 0])  # honest completion sync
    igg.tic()
    for _ in range(max(nt // chunk, 1)):
        state = step(*state)
    T = pc.temperature(state)
    float(T.addressable_shards[0].data[0, 0, 0])
    t = igg.toc()
    me = igg.get_global_grid().me
    igg.finalize_global_grid()
    if me == 0:
        steps = max(nt // chunk, 1) * chunk
        print(
            f"{steps} steps x {npt} PT iterations in {t:.3f} s = "
            f"{t / (steps * npt) * 1e3:.3f} ms/PT-iteration"
        )
    return T


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=256)
    p.add_argument("--nt", type=int, default=24)
    p.add_argument("--w", type=int, default=6)
    p.add_argument("--npt", type=int, default=12)
    a = p.parse_args()
    porous_convection3d_fused(nx=a.nx, nt=a.nt, w=a.w, npt=a.npt)
