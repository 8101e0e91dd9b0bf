"""Boundary-first pipelined group schedule (ISSUE 2).

Oracles: the pipelined schedule must be BIT-identical to the serialized
cadence on the CPU mesh in every admissible config — ring+mid launches
partition the same tiles tile-for-tile, and the early-dispatch exchange
(`ops.halo.begin_slab_exchange`/`finish_slab_exchange`) moves exactly the
serialized slabs (corner strips patched in).  Inadmissible configs must
fall back to the serialized schedule (still bit-identical, warn-once under
``pipelined=True``).  Kernels run through the TPU Pallas interpreter
(`pltpu.force_tpu_interpret_mode`).
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.ops import halo as halo_mod
from implicitglobalgrid_tpu.ops.overlap import (
    tile_split_error,
    tile_subset_count,
    tile_subset_map,
)
from implicitglobalgrid_tpu.analysis.ir import is_kernel_eqn
from implicitglobalgrid_tpu.utils import telemetry
from jax.experimental.pallas.tpu import force_tpu_interpret_mode


# --- tile-subset decomposition ---------------------------------------------


@pytest.mark.parametrize("ncx,ncy", [(3, 1), (3, 3), (4, 3), (5, 4), (8, 1)])
def test_ring_mid_partition_all_tiles(ncx, ncy):
    """Every admissible ring/mid pair partitions the flat tile set exactly,
    and the traced index map agrees with the Python-int one."""
    allt = set(range(ncx * ncy))
    for dims, ring, mid in (("0", "ring0", "mid0"), ("1", "ring1", "mid1"),
                            ("01", "ring01", "mid01")):
        if "0" in dims and ncx < 3:
            continue
        if "1" in dims and ncy < 3:
            continue
        r = [tile_subset_map(ring, ncx, ncy)(i)
             for i in range(tile_subset_count(ring, ncx, ncy))]
        m = [tile_subset_map(mid, ncx, ncy)(i)
             for i in range(tile_subset_count(mid, ncx, ncy))]
        assert len(set(r)) == len(r) and len(set(m)) == len(m)
        assert set(r) | set(m) == allt and not (set(r) & set(m))
        for t in m:  # interior tiles never touch a split-dim edge
            ix, iy = t // ncy, t % ncy
            if "0" in dims:
                assert 0 < ix < ncx - 1
            if "1" in dims:
                assert 0 < iy < ncy - 1
        traced = [int(tile_subset_map(ring, ncx, ncy)(jnp.int32(i)))
                  for i in range(len(r))]
        assert traced == r


def test_tile_split_error_conditions():
    # admissible reference config
    assert tile_split_error(
        (256, 256, 256), 4, 4, 32, 64, 8, (0, 1), ox=8, oy=8) is None
    # nothing active -> nothing to overlap
    assert "no x/y halo activity" in tile_split_error(
        (256, 256, 256), 4, 4, 32, 64, 8, (), ox=8, oy=8)
    # too few tiles along the split dim
    assert "3 x-tiles" in tile_split_error(
        (64, 256, 256), 4, 4, 32, 64, 8, (0,), ox=8, oy=8)
    # interior windows would read refreshed planes
    assert "refreshed x planes" in tile_split_error(
        (64, 256, 256), 6, 6, 8, 64, 8, (0,), ox=8, oy=8)
    assert "refreshed y planes" in tile_split_error(
        (256, 64, 256), 6, 6, 32, 8, 8, (1,), ox=8, oy=8)
    # deeper-than-bx overlap: the send/keep planes would lie outside the
    # ring tiles' owned rows -> must be rejected, not silently admitted
    assert "past the ring tiles" in tile_split_error(
        (256, 256, 256), 2, 2, 8, 64, 8, (0,), ox=12, oy=4)
    assert "past the ring tiles" in tile_split_error(
        (256, 256, 256), 2, 2, 32, 8, 8, (1,), ox=4, oy=12)


def test_pipelined_deep_overlap_falls_back_serialized():
    """A valid deeper-than-minimum overlap (overlapx=12 with fused_k=2,
    tile bx=8) puts the x send planes [10,12) outside the ring tiles'
    owned rows: the split must be inadmissible — and the cadence must
    still be bitwise-correct (serialized fallback) under every knob."""
    from implicitglobalgrid_tpu.models import diffusion3d

    def run(pipelined):
        kw = dict(devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
                  overlapx=12, overlapy=4, overlapz=4, quiet=True,
                  dtype=jnp.float32)
        state, params = diffusion3d.setup(24, 32, 128, **kw)
        err = diffusion3d.pipelined_support_error((24, 32, 128), 2, 4, 8, 16)
        assert err is not None and "past the ring tiles" in err, err
        with force_tpu_interpret_mode():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                step = diffusion3d.make_multi_step(
                    params, 4, donate=False, fused_k=2, fused_tile=(8, 16),
                    pipelined=pipelined,
                )
                out = np.asarray(jax.block_until_ready(step(*state))[0])
        igg.finalize_global_grid()
        return out

    np.testing.assert_array_equal(run(False), run(True))
    np.testing.assert_array_equal(run(False), run(None))


def test_pipelined_support_error_half_tile_no_crash():
    """A half-specified tile must resolve through the kernel ladder (the
    same contract as `zpatch_transposed`), not crash on `n1 // None`."""
    from implicitglobalgrid_tpu.models import diffusion3d

    igg.init_global_grid(256, 256, 256, dimx=2, dimy=2, dimz=2,
                         overlapx=8, overlapy=8, overlapz=8, quiet=True)
    full = diffusion3d.pipelined_support_error((256, 256, 256), 4, 4)
    assert diffusion3d.pipelined_support_error((256, 256, 256), 4, 4, bx=32) \
        in (full, None) or isinstance(
            diffusion3d.pipelined_support_error((256, 256, 256), 4, 4, bx=32),
            str,
        )
    # by-only likewise returns a verdict, never raises
    r = diffusion3d.pipelined_support_error((256, 256, 256), 4, 4, by=64)
    assert r is None or isinstance(r, str)
    igg.finalize_global_grid()


def test_zpatch_transposed_half_tile_matches_kernel_default():
    """ADVICE r5 low #4 regression: a ``by=None``-only call must resolve
    the default ladder like the kernel, not trust the lone parameter — the
    helper and `fused_diffusion_steps` must agree on the patch layout."""
    from implicitglobalgrid_tpu.ops.pallas_stencil import (
        default_tile,
        zpatch_transposed,
    )

    shape = (64, 64, 128)
    full = zpatch_transposed(shape, 4, 4)  # both None: ladder default
    tb = default_tile(shape, 4, 4, zpatch=True)
    assert full == (tb[1] == shape[1])
    # by=None only (bx given): same ladder resolution as the kernel
    assert zpatch_transposed(shape, 4, 4, bx=32) == full
    # bx=None only: likewise
    assert zpatch_transposed(shape, 4, 4, by=16) == full
    # fully-specified tiles still decide by the GIVEN by
    assert zpatch_transposed(shape, 4, 4, bx=8, by=shape[1]) is True
    assert zpatch_transposed(shape, 4, 4, bx=8, by=16) is False


# --- begin/finish slab exchange vs the serialized exchange ------------------


def test_begin_finish_matches_serialized_exchange():
    """`begin_slab_exchange` + `finish_slab_exchange` over (0,1,2) must be
    bitwise the serialized sequential-dim exchange, periodic and
    PROC_NULL dims alike (corner strips patched into the sends)."""
    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=2, periodx=1,
                         overlapx=4, overlapy=4, overlapz=4, quiet=True)
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.random((32, 32, 32)))

    @igg.stencil
    def serial(A):
        return halo_mod.exchange_dims(A, (0, 1, 2), width=2)

    @igg.stencil
    def piped(A):
        pend = halo_mod.begin_slab_exchange([A], (0, 1, 2), width=2)
        (out,) = halo_mod.finish_slab_exchange([A], pend)
        return out

    np.testing.assert_array_equal(np.asarray(serial(A)), np.asarray(piped(A)))


def test_begin_finish_padded_faces_matches_serialized():
    """Same bit-identity on the staggered `pad_faces` layout with per-field
    logical shapes (the fused cadences' exchange geometry)."""
    from implicitglobalgrid_tpu.ops.pallas_leapfrog import pad_faces

    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=2, periody=1,
                         overlapx=4, overlapy=4, overlapz=4, quiet=True)
    rng = np.random.default_rng(1)
    C = jnp.asarray(rng.random((32, 32, 32)))
    Vx = jnp.asarray(rng.random((34, 32, 32)))
    Vy = jnp.asarray(rng.random((32, 34, 32)))
    Vz = jnp.asarray(rng.random((32, 32, 34)))

    @igg.stencil
    def serial(C, Vx, Vy, Vz):
        Vxp, Vyp, Vzp = pad_faces(Vx, Vy, Vz)
        return halo_mod.update_halo_padded_faces(
            C, Vxp, Vyp, Vzp, width=2, dims=(0, 1)
        )

    @igg.stencil
    def piped(C, Vx, Vy, Vz):
        Vxp, Vyp, Vzp = pad_faces(Vx, Vy, Vz)
        fields = (C, Vxp, Vyp, Vzp)
        logicals = halo_mod._padded_logicals(*fields)
        pends = halo_mod.begin_slab_exchange(
            fields, (0, 1), width=2, logicals=logicals
        )
        return halo_mod.finish_slab_exchange(fields, pends, logicals=logicals)

    for r, g in zip(serial(C, Vx, Vy, Vz), piped(C, Vx, Vy, Vz)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


# --- pipelined cadence oracles (bitwise vs serialized) ----------------------


def _diffusion_states(nloc, dims, periods, k, nt, pipelined, tile):
    from implicitglobalgrid_tpu.models import diffusion3d

    kw = dict(devices=jax.devices()[: dims[0] * dims[1] * dims[2]],
              dimx=dims[0], dimy=dims[1], dimz=dims[2],
              overlapx=2 * k, overlapy=2 * k, overlapz=2 * k, quiet=True,
              dtype=jnp.float32, **periods)
    state, params = diffusion3d.setup(*nloc, **kw)
    with force_tpu_interpret_mode():
        step = diffusion3d.make_multi_step(
            params, nt, donate=False, fused_k=k, fused_tile=tile,
            pipelined=pipelined,
        )
        out = np.asarray(jax.block_until_ready(step(*state))[0])
    igg.finalize_global_grid()
    return out


@pytest.mark.parametrize(
    "dims,periods,nloc,tile",
    [
        # x-split, z-inactive: the non-zpatch ring0/mid0 split
        ((2, 1, 1), {}, (24, 32, 128), (8, 16)),
        # x-split + periodic z: the z-patch cadence under the split
        ((2, 1, 1), {"periodz": 1}, (24, 32, 128), (8, 16)),
        # y-split (ring1/mid1), z-inactive
        ((1, 2, 1), {}, (16, 48, 128), (8, 16)),
        # x periodic self-neighbor on 2 z-split devices: both the split
        # AND real z communication in one config
        ((1, 1, 2), {"periodx": 1}, (24, 32, 128), (8, 16)),
    ],
)
def test_pipelined_matches_serialized_bitwise(dims, periods, nloc, tile):
    k, nt = 2, 4
    ser = _diffusion_states(nloc, dims, periods, k, nt, False, tile)
    pip = _diffusion_states(nloc, dims, periods, k, nt, True, tile)
    auto = _diffusion_states(nloc, dims, periods, k, nt, None, tile)
    np.testing.assert_array_equal(ser, pip)
    np.testing.assert_array_equal(ser, auto)


def test_pipelined_inadmissible_falls_back_warn_once():
    """z-split-only grids have no x/y activity: pipelined=True warns once
    and runs the serialized schedule, bit-identically."""
    ser = _diffusion_states((16, 32, 128), (1, 1, 2), {}, 2, 4, False, (8, 16))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        pip = _diffusion_states((16, 32, 128), (1, 1, 2), {}, 2, 4, True, (8, 16))
    assert any("pipelined=True is not admissible" in str(x.message) for x in w)
    np.testing.assert_array_equal(ser, pip)


@pytest.mark.parametrize(
    "dims",
    [
        # x split, each device its own z partner: the in-kernel z wrap
        (2, 1, 1),
        # x split and a real z partner: the patch/export kernel, its packed
        # exports riding the x/y exchange
        (2, 1, 2),
    ],
    ids=["x2-self", "x2z2"],
)
def test_pipelined_acoustic_matches_serialized_bitwise(dims):
    from implicitglobalgrid_tpu.models import acoustic3d

    def run(pipelined):
        kw = dict(devices=jax.devices()[: dims[0] * dims[1] * dims[2]],
                  dimx=dims[0], dimy=dims[1], dimz=dims[2],
                  overlapx=4, overlapy=4, overlapz=4, periodz=1, quiet=True,
                  dtype=jnp.float32)
        state, params = acoustic3d.setup(24, 32, 128, **kw)
        # the split is admissible: pipelined=True runs the pipelined body
        assert acoustic3d.pipelined_support_error(
            (24, 32, 128), 2, bx=8, by=16) is None
        wraps = counter()
        with force_tpu_interpret_mode():
            step = acoustic3d.make_multi_step(
                params, 4, donate=False, fused_k=2, fused_tile=(8, 16),
                pipelined=pipelined,
            )
            out = [np.asarray(x) for x in jax.block_until_ready(step(*state))]
        igg.finalize_global_grid()
        assert (counter() > wraps) == (dims[2] == 1)
        return out

    def counter():
        return telemetry.snapshot()["counters"].get(
            acoustic3d.Z_SELF_WRAP_COUNTER, 0)

    for r, g in zip(run(False), run(True)):
        np.testing.assert_array_equal(r, g)


def test_pipelined_porous_ragged_matches_serialized_bitwise():
    """npt=5 -> lead 1 + chunks [2, 2]: the ragged PT schedule under the
    pipelined shape (patch/export widths stay w for every chunk)."""
    from implicitglobalgrid_tpu.models import porous_convection3d as pc

    def run(pipelined):
        kw = dict(devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
                  overlapx=4, overlapy=4, overlapz=4, periodz=1, quiet=True,
                  dtype=jnp.float32, npt=5)
        state, params = pc.setup(24, 32, 128, **kw)
        with force_tpu_interpret_mode():
            step = pc.make_multi_step(
                params, 2, donate=False, fused_k=2, fused_tile=(8, 16),
                pipelined=pipelined,
            )
            out = [np.asarray(x) for x in jax.block_until_ready(step(*state))]
        igg.finalize_global_grid()
        return out

    for r, g in zip(run(False), run(True)):
        np.testing.assert_array_equal(r, g)


def test_pipelined_xla_fallback_cadence_matches_serialized():
    """f64 keeps the kernels out (itemsize envelope): pipelined=True then
    runs the XLA cadence with the early-dispatch exchange — bit-identical
    to the serialized XLA cadence."""
    from implicitglobalgrid_tpu.models import diffusion3d

    def run(pipelined):
        kw = dict(devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
                  overlapx=4, overlapy=4, overlapz=4, quiet=True,
                  dtype=jnp.float64)
        state, params = diffusion3d.setup(24, 32, 128, **kw)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            step = diffusion3d.make_multi_step(
                params, 4, donate=False, fused_k=2, pipelined=pipelined
            )
            out = np.asarray(jax.block_until_ready(step(*state))[0])
        igg.finalize_global_grid()
        return out

    np.testing.assert_array_equal(run(False), run(True))


def test_pipelined_rejected_on_per_step_path():
    from implicitglobalgrid_tpu.models import diffusion3d

    igg.init_global_grid(16, 32, 128, quiet=True)
    state, params = diffusion3d.setup(16, 32, 128, init_grid=False)
    with pytest.raises(ValueError, match="group cadences"):
        diffusion3d.make_multi_step(params, 4, pipelined=True)
    igg.finalize_global_grid()


# --- run_pipelined_group_schedule loop shape --------------------------------


def test_run_pipelined_group_schedule_phases():
    """boundary runs before interior within each group; the loop shaping
    (unrolled prefix + fori excess) is inherited from run_group_schedule."""
    from implicitglobalgrid_tpu.models._fused import (
        run_pipelined_group_schedule,
    )

    calls = []

    def boundary(ki, c):
        calls.append(("b", ki))
        return c * 2.0, "pend"

    def interior(ki, c, b_out, pend):
        assert pend == "pend"
        calls.append(("i", ki))
        return c + ki

    out = jax.jit(
        lambda c: run_pipelined_group_schedule(
            [2] * 3, boundary, interior, c
        )
    )(jnp.float32(0))
    assert float(out) == 6.0
    assert calls == [("b", 2), ("i", 2)] * 3

    calls.clear()
    out = jax.jit(
        lambda c: run_pipelined_group_schedule(
            [2] * 12, boundary, interior, c
        )
    )(jnp.float32(0))
    assert float(out) == 24.0
    # 8 unrolled groups + the paired fori body's trace(s), two groups each
    assert 10 <= len(calls) // 2 <= 12


# --- structural overlap evidence (jaxpr level) ------------------------------


def _kernel_permute_independent_pairs(pipelined):
    """Count (pallas_call, ppermute) pairs with no dependency either way in
    the traced program — the dataflow freedom the pipelined schedule exists
    to create, asserted below the compiler (toolchain-independent)."""
    from implicitglobalgrid_tpu.models import diffusion3d
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    k = 2
    kw = dict(devices=jax.devices()[:4], dimx=4, dimy=1, dimz=1,
              overlapx=4, overlapy=4, overlapz=4, quiet=True,
              dtype=jnp.float32)
    state, params = diffusion3d.setup(40, 32, 128, **kw)  # ncx=5 at bx=8
    with force_tpu_interpret_mode():
        step = diffusion3d.make_multi_step(
            params, 2 * k, donate=False, fused_k=k, fused_tile=(8, 16),
            pipelined=pipelined,
        )
        gg = igg.get_global_grid()
        mapped = shard_map(
            step.__wrapped__, mesh=gg.mesh,
            in_specs=(P("x", "y", "z"),) * 2, out_specs=(P("x", "y", "z"),) * 2,
            check_vma=False,
        )
        jaxpr = jax.make_jaxpr(mapped)(*state)
    igg.finalize_global_grid()
    (sm,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    inner = sm.params["jaxpr"]
    # The kernel-vs-fallback wrapper (`fused_with_xla_grad`) nests the whole
    # cadence under one custom_vjp eqn: unwrap to its primal jaxpr.
    while len(inner.eqns) == 1 and "custom_vjp" in inner.eqns[0].primitive.name:
        inner = inner.eqns[0].params["call_jaxpr"].jaxpr
    producer = {}
    for e in inner.eqns:
        for ov in e.outvars:
            producer[id(ov)] = e

    def closure(eqn):
        seen, stack = set(), [eqn]
        while stack:
            for v in stack.pop().invars:
                p = producer.get(id(v))
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    stack.append(p)
        return seen

    kernels = [e for e in inner.eqns if is_kernel_eqn(e)]
    perms = [e for e in inner.eqns if e.primitive.name == "ppermute"]
    assert kernels and perms, (len(kernels), len(perms))
    kc = {id(e): closure(e) for e in kernels}
    pairs = 0
    for p in perms:
        pc = closure(p)
        for c in kernels:
            if id(c) not in pc and id(p) not in kc[id(c)]:
                pairs += 1
    return pairs, len(kernels), len(perms)


def test_interior_kernel_independent_of_group_permutes():
    """Serialized: every kernel launch transitively orders against every
    group-boundary ppermute (the barrier the ISSUE names).  Pipelined: each
    group's interior launch and its in-flight permutes are mutually
    independent — the compiler is licensed to overlap them."""
    pairs_ser, nk_ser, np_ser = _kernel_permute_independent_pairs(False)
    assert nk_ser == 2 and np_ser >= 4  # 2 groups x (>=2 x-permutes)
    assert pairs_ser == 0, f"serialized schedule has {pairs_ser} free pairs"
    pairs_pip, nk_pip, np_pip = _kernel_permute_independent_pairs(True)
    assert nk_pip == 4  # ring + interior per group
    # each group's >= 2 x-permutes are independent of ITS interior launch
    assert pairs_pip >= 4, f"pipelined schedule has only {pairs_pip} free pairs"


# --- HLO analysis helpers ---------------------------------------------------

_SYNTH_HLO = """
HloModule m

ENTRY %main (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8] parameter(0)
  %cc1 = f32[4,8] custom-call(%p0), custom_call_target="tpu_custom_call"
  %slice = f32[1,8] slice(%cc1), slice={[0:1], [0:8]}
  %cps = (f32[1,8], f32[1,8], u32[], u32[]) collective-permute-start(%slice), source_target_pairs={{0,1},{1,0}}
  %cc2 = f32[4,8] custom-call(%p0), custom_call_target="tpu_custom_call"
  %cpd = f32[1,8] collective-permute-done(%cps)
  %dus = f32[4,8] dynamic-update-slice(%cc2, %cpd)
  ROOT %out = f32[4,8] custom-call(%dus), custom_call_target="tpu_custom_call"
}
"""


def test_collective_payloads_async_start_result_half():
    """ADVICE r5 low #3: the async-start payload comes from explicit
    operand/result tuple parsing (matching halves), not a blind //2."""
    from implicitglobalgrid_tpu.utils.hlo_analysis import collective_payloads

    (rec,) = collective_payloads(_SYNTH_HLO)
    assert rec["bytes"] == 1 * 8 * 4
    assert rec["shape"] == "f32[1,8]"
    assert "payload_fallback" not in rec
    # a start op whose tuple does NOT split into matching halves is flagged
    odd = _SYNTH_HLO.replace(
        "(f32[1,8], f32[1,8], u32[], u32[])", "(f32[1,8], f32[2,8], u32[])"
    )
    (rec2,) = collective_payloads(odd)
    assert rec2["payload_fallback"] == "raw-sum"
    assert rec2["bytes"] == (8 + 16) * 4


def test_pipelined_overlap_evidence_synthetic():
    """cc2 neither feeds nor consumes the permute -> one independent pair;
    cc1 feeds it and the root consumes it -> dependent."""
    from implicitglobalgrid_tpu.utils.hlo_analysis import (
        pipelined_overlap_evidence,
    )

    ev = pipelined_overlap_evidence(_SYNTH_HLO)
    assert ev["n_collectives"] == 1
    assert ev["n_custom_calls"] == 3
    assert ev["independent_pairs"] == 1
    assert ev["overlappable_collectives"] == 1
