"""3-D staggered acoustic FDTD with the fused leapfrog kernel — the fast path.

The staggered sibling of `diffusion3d_tpu_fused.py`: ``overlap = 2k`` deep
halos license ``k`` temporally-blocked leapfrog steps per HBM pass *and* per
all-field slab exchange — `acoustic3d.make_multi_step(fused_k=k)` wires both
over the even-extent padded face layout (`ops/pallas_leapfrog.py`).  On one
v5e chip at 256^3 f32 this sustains ~1050-1130 GB/s/chip effective (8-pass
convention) vs ~400 GB/s for the best per-step XLA config — the kernel that
the round-2 analysis said could not exist for ``n+1`` staggered fields (see
`docs/performance.md`).

The reference has no counterpart: its staggered test fields
(`/root/reference/test/test_update_halo.jl:828-937`) always exchange one
plane per step.

Run (any number of devices; overlap=12 enables the tuned k=6; the minor
dimension must be a multiple of 128 or the model falls back to XLA):
    python examples/acoustic3d_tpu_fused.py [--nx 256] [--nt 600] [--k 6]
"""

import argparse


def acoustic3d_fused(nx=256, nt=600, k=6, ny=None, nz=None, fused_tile=None,
                     **setup_kwargs):
    import jax

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import acoustic3d

    state, params = acoustic3d.setup(
        nx,
        ny if ny is not None else nx,
        nz if nz is not None else nx,
        overlapx=2 * k,
        overlapy=2 * k,
        overlapz=2 * k,
        dtype=jax.numpy.float32,
        **setup_kwargs,
    )
    # Large chunks amortize per-call dispatch latency; `fused_k` must divide
    # the chunk.
    chunk = max(k * max(min(nt, 96) // k, 1), k)
    step = acoustic3d.make_multi_step(
        params, chunk, fused_k=k, fused_tile=fused_tile
    )
    state = step(*state)  # compile + warmup chunk
    float(state[0].addressable_shards[0].data[0, 0, 0])  # honest completion sync
    igg.tic()
    for _ in range(max(nt // chunk, 1)):
        state = step(*state)
    P = acoustic3d.pressure(state)
    float(P.addressable_shards[0].data[0, 0, 0])
    t = igg.toc()
    me = igg.get_global_grid().me
    igg.finalize_global_grid()
    if me == 0:
        steps = max(nt // chunk, 1) * chunk
        print(f"{steps} steps in {t:.3f} s = {t / steps * 1e3:.3f} ms/step")
    return P


if __name__ == "__main__":
    from implicitglobalgrid_tpu.utils.config import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--nx", type=int, default=256)
    p.add_argument("--nt", type=int, default=600)
    p.add_argument("--k", type=int, default=6)
    a = p.parse_args()
    acoustic3d_fused(nx=a.nx, nt=a.nt, k=a.k)
