"""Coalesced multi-field halo exchange (ISSUE 5).

Contract: for each exchanged dimension, every field's send slab packs into
one flat buffer per dtype byte width (bitcast to same-width unsigned ints —
the chunked gather's byte-exact transport) and rides ONE
`collective-permute` pair per (dimension, width group) instead of one per
field — BIT-identical to the per-field path across the full config matrix
(mixed dtypes incl. bf16/f64/complex, staggered ``n+1`` shapes,
``width>1``, ``disp != 1``, periodic self-neighbor, PROC_NULL edges), with
unchanged total payload bytes.  `IGG_COALESCE=0` / ``coalesce=False``
restores today's per-field collectives.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.ops import halo as H
from implicitglobalgrid_tpu.utils.hlo_analysis import collective_payloads

from test_update_halo import put, simulate_update_halo, unique_field


# ---------------------------------------------------------------- bit identity


def _check_ab(config, fields_lshapes, dtypes, width=1, **initkw):
    """Coalesced vs per-field `update_halo`: both bitwise equal to the numpy
    simulator (and hence to each other), across the whole field set."""
    nx, ny, nz = config
    igg.init_global_grid(nx, ny, nz, quiet=True, **initkw)
    gg = igg.get_global_grid()
    fields = []
    for ls, dt in zip(fields_lshapes, dtypes):
        f = unique_field(ls, gg, np.float64)
        if np.dtype(dt) in (np.dtype(np.float16), jnp.bfloat16.dtype):
            f = np.mod(f, 512)  # low-precision dtypes can't hold unique ints
        if np.dtype(dt).kind == "c":
            fields.append((f + 1j * (f + 0.5)).astype(dt))
        else:
            fields.append(np.asarray(f, dtype=dt))
    for coalesce in (True, False):
        outs = igg.update_halo(
            *[put(f) for f in fields], width=width, coalesce=coalesce
        )
        if len(fields) == 1:
            outs = (outs,)
        for f, o in zip(fields, outs):
            exp = simulate_update_halo(f, gg, width)
            got = np.asarray(o)
            if got.dtype == jnp.bfloat16.dtype:
                got, exp = got.astype(np.float64), exp.astype(np.float64)
            np.testing.assert_array_equal(got, exp)
    igg.finalize_global_grid()


def test_mixed_dtypes_all_width_groups():
    # u16 (bf16 + f16), u32 (f32 + i32), u64 (f64), complex64 riding the u32
    # group, complex128 riding u64 — every transport group in one call.
    _check_ab(
        (6, 6, 6),
        [(6, 6, 6)] * 6,
        ["bfloat16", "float16", "float32", "int32", "float64", "complex64"],
        periodx=1,
    )


def test_complex128_and_staggered():
    _check_ab(
        (5, 5, 5),
        [(5, 5, 5), (6, 5, 5), (5, 6, 5), (5, 5, 6)],
        ["complex128", "float64", "float64", "float64"],
    )


def test_staggered_deep_halo_width2():
    _check_ab(
        (8, 8, 8),
        [(8, 8, 8), (9, 8, 8), (8, 9, 8)],
        ["float64"] * 3,
        width=2, overlapx=4, overlapy=4, overlapz=4, periodz=1,
    )


def test_disp2_mixed_partners():
    # dims=(4,2,1) disp=2: x has distance-2 partners, y all-PROC_NULL, z no
    # neighbors — the coalesced pack must honor the same partner table.
    _check_ab(
        (6, 6, 6), [(6, 6, 6), (6, 6, 6)], ["float64", "float32"],
        disp=2, dimx=4, dimy=2, dimz=1,
    )


def test_disp2_periodic_wrap_self_partner():
    # y's wrap (c±2) mod 2 == c makes every block its own partner: the
    # self-partner fast path must stay per-field local copies (no packing).
    _check_ab(
        (6, 6, 6), [(6, 6, 6), (6, 6, 6)], ["float64", "float64"],
        disp=2, dimx=4, dimy=2, dimz=1, periodx=1, periody=1,
    )


def test_rank_mismatch_fields():
    # A 2-D field in the 3-D grid skips z; the 3-D partner still exchanges
    # it — per-dim participation is per FIELD, not per call.
    _check_ab((6, 6, 6), [(6, 6, 6), (6, 6)], ["float64", "float64"])


def test_bool_fields_coalesce():
    """bool cannot `bitcast_convert_type` — the transport converts {0,1} to
    uint8 instead (regression: two bool masks crashed the coalesced default
    while coalesce=False worked)."""
    igg.init_global_grid(6, 6, 6, periodx=1, quiet=True)
    gg = igg.get_global_grid()
    base = unique_field((6, 6, 6), gg)
    a = (np.mod(base, 2) == 0)
    b = (np.mod(base, 3) == 0)
    for coalesce in (True, False):
        oa, ob = igg.update_halo(put(a), put(b), coalesce=coalesce,
                                 donate=False)
        np.testing.assert_array_equal(np.asarray(oa), simulate_update_halo(a, gg))
        np.testing.assert_array_equal(np.asarray(ob), simulate_update_halo(b, gg))
    igg.finalize_global_grid()


def test_negative_zero_and_nan_payloads_survive_bytewise():
    """-0.0 and NaN payload bits must survive the packed transport exactly
    (the bitcast transport's whole point: a float path would lose them)."""
    igg.init_global_grid(6, 6, 6, periodx=1, quiet=True)
    gg = igg.get_global_grid()
    base = unique_field((6, 6, 6), gg, np.float32)
    a = -np.zeros_like(base)
    a[::3] = np.float32(np.nan)
    b = base.copy()
    b[1::3] = -0.0
    outs = {}
    for coalesce in (True, False):
        oa, ob = igg.update_halo(
            put(a), put(b), coalesce=coalesce, donate=False
        )
        outs[coalesce] = (np.asarray(oa), np.asarray(ob))
    for x, y in zip(outs[True], outs[False]):
        assert x.tobytes() == y.tobytes()  # bytewise, incl. NaN payloads/-0.0
    igg.finalize_global_grid()


# ------------------------------------------------------- collective structure


def _exchange_hlo(gg, fields, width=1, coalesce=True, donate=False):
    sig = tuple((H.local_shape(A, gg), str(A.dtype)) for A in fields)
    fn = H._global_update_fn(gg, sig, width, donate, coalesce)
    return fn.lower(*fields).compile().as_text()


def _n_collectives(hlo: str) -> int:
    return hlo.count(" collective-permute(") + hlo.count(
        " collective-permute-start("
    )


def test_five_field_exchange_two_permutes_per_dim_and_width_group():
    """The acceptance pin: a 5-field exchange emits <= 2 collective-permutes
    per exchanged (dim, width group) — here 3 groups (u32 x3 fields, u16,
    u64) over 3 exchanged dims = 18, vs 30 per-field — with IGG_COALESCE=0
    restoring the per-field count, and total payload bytes unchanged."""
    igg.init_global_grid(6, 6, 6, periodz=1, quiet=True)
    gg = igg.get_global_grid()
    exchanged = sum(1 for d in range(3) if gg.dims[d] > 1 or gg.periods[d])
    base = unique_field((6, 6, 6), gg)
    fields = [
        put(np.asarray(base * (i + 1), dtype=dt))
        for i, dt in enumerate(
            ["float32", "float32", "float32", "bfloat16", "float64"]
        )
    ]
    hlo_c = _exchange_hlo(gg, fields, coalesce=True)
    hlo_p = _exchange_hlo(gg, fields, coalesce=False)
    n_groups, n_fields = 3, 5
    assert _n_collectives(hlo_c) == 2 * exchanged * n_groups
    assert _n_collectives(hlo_p) == 2 * exchanged * n_fields
    # unchanged total payload: the packed buffers move exactly the per-field
    # slab bytes (2-byte, 4-byte and 8-byte groups included)
    bytes_c = sum(r["bytes"] for r in collective_payloads(hlo_c))
    bytes_p = sum(r["bytes"] for r in collective_payloads(hlo_p))
    assert bytes_c == bytes_p > 0
    igg.finalize_global_grid()


def test_coalesce_env_default_and_cache_key(monkeypatch):
    """IGG_COALESCE wiring: 0 -> per-field, unset/1 -> coalesced; the kwarg
    wins; the resolved flag lands in the jit-cache key (so env flips cannot
    serve a stale program)."""
    from implicitglobalgrid_tpu.utils.config import coalesce_env

    monkeypatch.setenv("IGG_COALESCE", "0")
    assert H._default_coalesce() is False and coalesce_env() is False
    monkeypatch.setenv("IGG_COALESCE", "1")
    assert H._default_coalesce() is True and coalesce_env() is True
    monkeypatch.delenv("IGG_COALESCE")
    assert H._default_coalesce() is True and coalesce_env() is None
    monkeypatch.setenv("IGG_COALESCE", "x")
    with pytest.raises(ValueError, match="IGG_COALESCE"):
        H._default_coalesce()
    monkeypatch.delenv("IGG_COALESCE")

    igg.init_global_grid(6, 6, 6, periodz=1, quiet=True)
    gg = igg.get_global_grid()
    f = unique_field((6, 6, 6), gg)
    H._clear_caches()
    monkeypatch.setenv("IGG_COALESCE", "0")
    igg.update_halo(put(f), put(f * 2), donate=False)
    assert {k[-1] for k in H._jit_cache} == {False}
    monkeypatch.delenv("IGG_COALESCE")
    igg.update_halo(put(f), put(f * 2), donate=False)
    assert {k[-1] for k in H._jit_cache} == {False, True}
    igg.finalize_global_grid()


def _traced_ppermutes(build, args):
    """Count ppermute eqns in the traced (jaxpr-level) program of ``build``
    shard_mapped over the grid's mesh — toolchain-independent, like
    test_pipelined_schedule's structural checks.  The recursive census is
    the budget lint's own (`scripts/check_collectives.py`) so the two
    counters cannot drift."""
    import importlib.util

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    _here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "igg_check_collectives_for_tests",
        os.path.join(os.path.dirname(_here), "scripts", "check_collectives.py"),
    )
    cc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cc)

    gg = igg.get_global_grid()
    specs = tuple(P(*igg.AXIS_NAMES[: a.ndim]) for a in args)
    mapped = shard_map(
        build, mesh=gg.mesh, in_specs=specs, out_specs=specs, check_vma=False
    )
    return cc._count_ppermutes(jax.make_jaxpr(mapped)(*args).jaxpr)


def test_begin_finish_coalesced_counts_and_bit_identity():
    """The pipelined schedule's early-dispatch exchange coalesces too: one
    permute pair per (dim, width group) at the jaxpr level, values bitwise
    the serialized per-field exchange (corner strips included)."""
    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=2, periodx=1,
                         overlapx=4, overlapy=4, overlapz=4, quiet=True)
    rng = np.random.default_rng(7)
    A = jnp.asarray(rng.random((32, 32, 32)))
    B = jnp.asarray(rng.random((32, 32, 32)))

    def piped(coalesce):
        @igg.stencil
        def fn(A, B):
            pend = H.begin_slab_exchange(
                (A, B), (0, 1, 2), width=2, coalesce=coalesce
            )
            return H.finish_slab_exchange((A, B), pend)

        return fn

    def build(co):
        def f(a, b):
            pend = H.begin_slab_exchange((a, b), (0, 1, 2), width=2,
                                         coalesce=co)
            return H.finish_slab_exchange((a, b), pend)

        return f

    shapes = (jax.ShapeDtypeStruct((32, 32, 32), jnp.float64),) * 2
    assert _traced_ppermutes(build(True), shapes) == 2 * 3      # 1 pair/dim
    assert _traced_ppermutes(build(False), shapes) == 2 * 3 * 2  # per field

    @igg.stencil
    def serial(A, B):
        return H._update_halo_local((A, B), igg.get_global_grid(), 2, False)

    ref = serial(A, B)
    for coalesce in (True, False):
        got = piped(coalesce)(A, B)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    igg.finalize_global_grid()


def test_padded_faces_coalesced_matches_per_field():
    """`update_halo_padded_faces` (the staggered fused cadences' exchange
    geometry, per-field logical shapes): coalesced == per-field, bitwise."""
    from implicitglobalgrid_tpu.ops.pallas_leapfrog import pad_faces

    igg.init_global_grid(16, 16, 16, dimx=2, dimy=2, dimz=2, periody=1,
                         overlapx=4, overlapy=4, overlapz=4, quiet=True)
    rng = np.random.default_rng(3)
    C = jnp.asarray(rng.random((32, 32, 32)))
    Vx = jnp.asarray(rng.random((34, 32, 32)))
    Vy = jnp.asarray(rng.random((32, 34, 32)))
    Vz = jnp.asarray(rng.random((32, 32, 34)))

    def run(coalesce):
        @igg.stencil
        def fn(C, Vx, Vy, Vz):
            return H.update_halo_padded_faces(
                C, *pad_faces(Vx, Vy, Vz), width=2, coalesce=coalesce
            )

        return [np.asarray(x) for x in fn(C, Vx, Vy, Vz)]

    for r, g in zip(run(False), run(True)):
        np.testing.assert_array_equal(r, g)
    igg.finalize_global_grid()


def test_transposed_export_coalesces_with_cell_field():
    """The diffusion transposed-layout pair (T + z export, y on array axis
    2): `exchange_dims_multi` with the `_T_AXES` map must equal the two
    separate single-field exchanges, bitwise."""
    from implicitglobalgrid_tpu.ops.halo import _T_AXES, _pad8, _pad128

    w = 2
    n0, n1, n2 = 8, 8, 128
    igg.init_global_grid(n0, n1, n2, dimx=2, dimy=2, dimz=2, periodx=1,
                         overlapx=2 * w, overlapy=2 * w, overlapz=2 * w,
                         quiet=True)
    gg = igg.get_global_grid()
    PB = _pad8(4 * w)
    n1p = _pad128(n1)

    def block_vals(c):
        cx, cy, cz = c
        key = jax.random.PRNGKey((cx * 5 + cy) * 13 + cz)
        return jax.random.normal(key, (n0, n1, 4 * w), jnp.float32)

    T = igg.from_block_fn(
        lambda c: jax.random.normal(
            jax.random.PRNGKey(c[0] * 100 + c[1] * 10 + c[2]),
            (n0, n1, n2), jnp.float32),
        (n0, n1, n2),
    )
    E = igg.from_block_fn(
        lambda c: jnp.pad(
            block_vals(c).transpose(0, 2, 1),
            ((0, 0), (0, PB - 4 * w), (0, n1p - n1)),
        ),
        (n0, PB, n1p),
    )

    @igg.stencil
    def separate(T, E):
        T = H.exchange_dims(T, (0, 1), width=w)
        E = H.exchange_dims_t(E, width=w, shape=(n0, n1, n2), coalesce=False)
        return T, E

    @igg.stencil
    def combined(T, E):
        return H.exchange_dims_multi(
            (T, E), (0, 1), width=w,
            logicals=(None, (n0, n1, n2)), axes=(None, _T_AXES),
            coalesce=True,
        )

    for r, g in zip(separate(T, E), combined(T, E)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    igg.finalize_global_grid()


def test_z_patches_from_exports_coalesced_matches_per_field():
    """The staggered z-slab family's packed-export communication: coalesced
    x/y hops AND the packed one-pair z hop must reproduce the per-field
    path's patches exactly (all four lane bands)."""
    w = 2
    n0, n1 = 8, 8
    igg.init_global_grid(n0, n1, 128, dimx=2, dimy=2, dimz=2, periodz=1,
                         overlapx=2 * w, overlapy=2 * w, overlapz=2 * w,
                         quiet=True)

    def mk(shape, salt):
        def f(c):
            key = jax.random.PRNGKey(salt)
            for comp in c:
                key = jax.random.fold_in(key, comp)
            return jax.random.normal(key, shape, jnp.float32)

        return igg.from_block_fn(f, shape)

    exp_cz = mk((n0, n1, 128), 1)
    exp_x = mk((n0 + 1, n1, 128), 2)
    exp_y = mk((n0, n1 + 1, 128), 3)

    def run(coalesce):
        @igg.stencil
        def fn(a, b, c):
            return H.z_patches_from_exports(
                (a, b, c), (n0, n1, 128), width=w, coalesce=coalesce
            )

        return [np.asarray(x) for x in fn(exp_cz, exp_x, exp_y)]

    ref, got = run(False), run(True)
    for name, r, g in zip(("cz", "x", "y"), ref, got):
        # the pad128 junk tail beyond the patch bands is layout junk either
        # way; compare the real lane bands only
        np.testing.assert_array_equal(
            r[:, :, : 2 * w], g[:, :, : 2 * w], err_msg=name
        )
        if name == "cz":
            Z = H.Z_CZ_BAND
            np.testing.assert_array_equal(
                r[:, :, Z : Z + 2 * w], g[:, :, Z : Z + 2 * w]
            )
    igg.finalize_global_grid()


def test_grad_through_coalesced_exchange_matches_per_field():
    """`jax.grad` through a coalesced multi-field exchange must equal the
    per-field path's gradient EXACTLY (regression: the bitcast transport
    has no tangent, so without `_packed_transport`'s custom VJP every
    cotangent crossing a block boundary was silently dropped)."""
    igg.init_global_grid(8, 8, 8, periodx=1, quiet=True)
    gg = igg.get_global_grid()
    a = jnp.asarray(unique_field((8, 8, 8), gg))
    b = jnp.asarray(unique_field((8, 8, 8), gg) * 0.5)

    def loss(coalesce):
        ex = igg.stencil(
            lambda x, y: igg.update_halo(x, y, coalesce=coalesce)
        )

        def f(x, y):
            ox, oy = ex(x, y)
            return jnp.sum(ox**2) + jnp.sum(ox * oy)

        return f

    ga_c, gb_c = jax.grad(loss(True), argnums=(0, 1))(a, b)
    ga_p, gb_p = jax.grad(loss(False), argnums=(0, 1))(a, b)
    np.testing.assert_array_equal(np.asarray(ga_c), np.asarray(ga_p))
    np.testing.assert_array_equal(np.asarray(gb_c), np.asarray(gb_p))
    # the exchange's VJP routes cotangents ACROSS blocks: interior send
    # planes must carry non-trivial gradient, not just the local identity
    assert float(jnp.sum(jnp.abs(ga_c))) > 0
    # finite-difference spot check at a halo-plane point (cross-boundary)
    eps = 1e-6
    f = loss(True)
    for idx in [(0, 4, 4), (15, 4, 4), (7, 7, 7)]:
        fd = (f(a.at[idx].add(eps), b) - f(a.at[idx].add(-eps), b)) / (2 * eps)
        np.testing.assert_allclose(
            float(ga_c[idx]), float(fd), rtol=1e-4, atol=1e-3, err_msg=str(idx)
        )
    igg.finalize_global_grid()


def test_coalesced_telemetry_counters():
    """Trace-time counters (docs/observability.md): a coalesced trace
    records its packed collectives and per-hop payload bytes."""
    from implicitglobalgrid_tpu.utils import telemetry as tele

    igg.init_global_grid(6, 6, 6, periodz=1, quiet=True)
    gg = igg.get_global_grid()
    tele.reset()
    H._clear_caches()
    f = unique_field((6, 6, 6), gg)
    igg.update_halo(put(f), put(f * 2), donate=False, coalesce=True)
    snap = tele.snapshot()
    exchanged = sum(1 for d in range(3) if gg.dims[d] > 1 or gg.periods[d])
    assert snap["counters"]["halo.coalesced_collectives"] == 2 * exchanged
    # per (dim, group): 2 hops x (2 fields x width-1 slab plane of 6^3 f64)
    plane = {0: 36, 1: 36, 2: 36}
    expect = sum(2 * 2 * plane[d] * 8 for d in range(3))
    assert snap["counters"]["halo.coalesced_bytes"] == expect
    igg.finalize_global_grid()
