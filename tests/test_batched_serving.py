"""Batched ensemble execution + serving loop (ISSUE 8).

The two contracts under test:

* **bit-exactness** — a B-stacked batched step is bit-identical, member
  for member, to B independent unbatched runs, across the oracle matrix:
  all three models, coalesce on/off, periodic + PROC_NULL transports in
  one grid (dims (2,2,2), periodz=1), the deep-halo slab cadence and the
  fused Pallas path (the 2-process gloo leg lives in
  ``tests/_distributed_worker.py``);
* **B for the price of 1** — the traced collective count of the batched
  exchange equals the unbatched one per dimension (the full census is
  tier-1 via `analysis.budget`; here the model-level step programs are
  pinned too), and the serving loop's admit/retire/guard machinery acts
  per member.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import (
    _batched,
    acoustic3d,
    diffusion3d,
    porous_convection3d,
)

MODELS = {
    "diffusion": (diffusion3d, 2, {}),
    "acoustic": (acoustic3d, 4, {}),
    "porous": (porous_convection3d, 5, {"npt": 3}),
}


def _members(model, n, B, extra):
    """B single-member states with the batched_setup scales."""
    return [
        model.setup(n, n, n, init_grid=False,
                    ic_scale=1.0 + b / (8.0 * B), **extra)[0]
        for b in range(B)
    ]


def _assert_members_equal(bstate, singles, nf):
    for b, s in enumerate(singles):
        mem = _batched.member_state(bstate, b)
        for i in range(nf):
            np.testing.assert_array_equal(
                np.asarray(mem[i]), np.asarray(s[i]),
                err_msg=f"member {b} field {i} diverged from its "
                        f"independent run",
            )


@pytest.mark.parametrize("name", list(MODELS))
@pytest.mark.parametrize("coalesce", ["1", "0"])
def test_batched_step_matches_independent_runs(name, coalesce, monkeypatch):
    """B-stacked `make_step(batch=True)` ≡ B independent B=1 runs, on a
    grid with BOTH periodic and PROC_NULL transports, coalesce on/off."""
    monkeypatch.setenv("IGG_COALESCE", coalesce)
    model, nf, extra = MODELS[name]
    n, B = 8, 3
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    _, params = model.setup(n, n, n, init_grid=False, **extra)
    singles = _members(model, n, B, extra)
    bstate = _batched.stack_states(singles)

    step1 = model.make_step(params, donate=False)
    stepB = model.make_step(params, donate=False, batch=True)
    for _ in range(2):
        bstate = stepB(*bstate)
        singles = [step1(*s) for s in singles]
    _assert_members_equal(bstate, singles, nf)


def test_batched_slab_cadence_matches_independent(monkeypatch):
    """The deep-halo ``exchange_every`` cadence, batched vs independent —
    the serving loop's production XLA step shape."""
    n, B = 8, 2
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         overlapx=4, overlapy=4, overlapz=4, quiet=True)
    _, params = diffusion3d.setup(n, n, n, init_grid=False)
    singles = _members(diffusion3d, n, B, {})
    bstate = _batched.stack_states(singles)
    step1 = diffusion3d.make_multi_step(params, 4, donate=False,
                                        exchange_every=2)
    stepB = diffusion3d.make_multi_step(params, 4, donate=False,
                                        exchange_every=2, batch=True)
    bstate = stepB(*bstate)
    singles = [step1(*s) for s in singles]
    _assert_members_equal(bstate, singles, 2)


@pytest.mark.parametrize("name", list(MODELS))
def test_batched_fused_cadence_matches_independent(name):
    """The fused Pallas chunks under vmap (interpret mode): the
    pallas_call batching rule must advance every member exactly as its
    own call — all three kernel families (stencil, leapfrog, PT)."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    model, nf, extra = MODELS[name]
    if name == "porous":
        extra = {"npt": 4}
    n0, n1, n2, k = 16, 32, 128, 2
    igg.init_global_grid(n0, n1, n2, devices=jax.devices()[:1], quiet=True)
    _, params = model.setup(n0, n1, n2, init_grid=False,
                            dtype=jnp.float32, **extra)
    singles = [
        model.setup(n0, n1, n2, init_grid=False, dtype=jnp.float32,
                    ic_scale=s, **extra)[0]
        for s in (1.0, 1.25)
    ]
    bstate = _batched.stack_states(singles)
    with force_tpu_interpret_mode(), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        step1 = model.make_multi_step(params, k, donate=False, fused_k=k)
        stepB = model.make_multi_step(params, k, donate=False, fused_k=k,
                                      batch=True)
        bstate = jax.block_until_ready(stepB(*bstate))
        singles = [jax.block_until_ready(step1(*s)) for s in singles]
    _assert_members_equal(bstate, singles, nf)


def test_batched_step_collective_count_is_b_invariant():
    """The traced per-step model program emits the SAME ppermute count
    batched and unbatched (the model-level twin of the budget census)."""
    from implicitglobalgrid_tpu.analysis.budget import _count_ppermutes
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = 8
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    state, params = acoustic3d.setup(n, n, n, init_grid=False)
    gg = igg.get_global_grid()

    def count(step, nf, batched, B=4):
        spec = (
            P(None, *igg.AXIS_NAMES) if batched else P(*igg.AXIS_NAMES)
        )
        mapped = shard_map(
            step.__wrapped__, mesh=gg.mesh, in_specs=(spec,) * nf,
            out_specs=(spec,) * nf, check_vma=False,
        )
        args = [
            jax.ShapeDtypeStruct(
                ((B,) + A.shape) if batched else A.shape, A.dtype
            )
            for A in state
        ]
        return _count_ppermutes(jax.make_jaxpr(mapped)(*args).jaxpr)

    c1 = count(acoustic3d.make_step(params, donate=False), 4, False)
    cB = count(acoustic3d.make_step(params, donate=False, batch=True), 4,
               True)
    assert c1 > 0, "census saw no collectives at all"
    assert cB == c1, (
        f"batched step emits {cB} ppermutes vs {c1} unbatched — batching "
        f"must ride the same collectives"
    )


def _rand_field(seed, n=8):
    """A random global-block field (distinct values per block)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    gg = igg.get_global_grid()
    g = np.random.default_rng(seed).normal(size=(2 * n, 2 * n, 2 * n))
    return jax.device_put(g, NamedSharding(gg.mesh, P("x", "y", "z")))


def test_stack_member_set_roundtrip_and_select():
    n = 8
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, quiet=True)
    fields = [_rand_field(i) for i in range(3)]
    B = _batched.stack_fields(*fields)
    assert B.shape == (3,) + fields[0].shape
    for b in range(3):
        np.testing.assert_array_equal(
            np.asarray(_batched.member_field(B, b)), np.asarray(fields[b])
        )
    # set_member writes slot 1 only
    (B2,) = _batched.set_member_state((B + 0,), (fields[0],), 1)
    np.testing.assert_array_equal(np.asarray(B2[1]), np.asarray(fields[0]))
    np.testing.assert_array_equal(np.asarray(B2[0]), np.asarray(fields[0]))
    np.testing.assert_array_equal(np.asarray(B2[2]), np.asarray(fields[2]))
    # select freezes masked members bit-for-bit
    (sel,) = _batched.select_members(
        np.array([True, False, True]), (B + 1.0,), (B + 0,)
    )
    np.testing.assert_array_equal(np.asarray(sel[1]), np.asarray(B[1]))
    np.testing.assert_array_equal(
        np.asarray(sel[0]), np.asarray(B[0]) + 1.0
    )


def test_check_members_finite_flags_only_the_bad_member():
    n = 8
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, quiet=True)
    good = igg.ones((n, n, n), "float64")
    bad = np.ones((2 * n, 2 * n, 2 * n))
    bad[3, 3, 3] = np.inf
    from jax.sharding import NamedSharding, PartitionSpec as P

    gg = igg.get_global_grid()
    badf = jax.device_put(bad, NamedSharding(gg.mesh, P("x", "y", "z")))
    B = _batched.stack_fields(good, badf, good)
    flags = _batched.check_members_finite((B,))
    assert flags.tolist() == [False, True, False]


# -- serving loop -------------------------------------------------------------


def _mk_loop(**kw):
    from implicitglobalgrid_tpu.serving import ServingLoop

    _, params = diffusion3d.setup(8, 8, 8, init_grid=False)
    return ServingLoop(diffusion3d, params, **kw), params


def _req(scale, max_steps, tenant="t"):
    from implicitglobalgrid_tpu.serving import Request

    s, _ = diffusion3d.setup(8, 8, 8, init_grid=False, ic_scale=scale)
    return Request(state=s, max_steps=max_steps, tenant=tenant)


def test_serving_mid_flight_admit_and_bit_exact_results():
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    loop, params = _mk_loop(capacity=2, steps_per_round=2)
    mids = [loop.submit(_req(1.0 + i * 0.1, 4, tenant=f"t{i}"))
            for i in range(4)]
    res = loop.run(max_rounds=20)
    assert sorted(res) == sorted(mids)
    assert all(r.status == "completed" and r.steps == 4
               for r in res.values())
    # queue (4) > capacity (2): members 2/3 were admitted mid-flight
    assert loop.rounds > 2
    # bit-exact vs a standalone run of member 2
    s, _ = diffusion3d.setup(8, 8, 8, init_grid=False, ic_scale=1.2)
    step = diffusion3d.make_step(params, donate=False)
    for _ in range(4):
        s = step(*s)
    np.testing.assert_array_equal(
        np.asarray(res[mids[2]].state[0]), np.asarray(s[0])
    )


def test_serving_evicts_only_the_nan_member():
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    loop, _ = _mk_loop(capacity=2, steps_per_round=1)
    good = _req(1.1, 2)
    bad = _req(1.0, 5)
    T = np.asarray(bad.state[0]).copy()
    T[2, 2, 2] = np.nan
    from jax.sharding import NamedSharding, PartitionSpec as P

    gg = igg.get_global_grid()
    bad.state = (
        jax.device_put(T, NamedSharding(gg.mesh, P("x", "y", "z"))),
        bad.state[1],
    )
    m_bad = loop.submit(bad)
    m_good = loop.submit(good)
    res = loop.run(max_rounds=10)
    assert res[m_bad].status == "evicted" and res[m_bad].state is None
    assert res[m_good].status == "completed"
    assert np.isfinite(np.asarray(res[m_good].state[0])).all()


def test_serving_rollback_restores_member_then_gives_up():
    from implicitglobalgrid_tpu.serving import Request

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    loop, _ = _mk_loop(capacity=1, steps_per_round=1,
                       guard_policy="rollback", max_rollbacks=2)
    m = loop.submit(_req(1.0, 3))
    loop.run_round()
    assert loop.slots[0].steps == 1
    # poison the live slot: rollback must rewind to the last good snapshot
    T = np.asarray(_batched.member_field(loop._state[0], 0)).copy()
    T[1, 1, 1] = np.nan
    from jax.sharding import NamedSharding, PartitionSpec as P

    gg = igg.get_global_grid()
    badf = jax.device_put(T, NamedSharding(gg.mesh, P("x", "y", "z")))
    loop._state = _batched.set_member_state(
        loop._state, (badf, _batched.member_field(loop._state[1], 0)), 0
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        loop._guard(loop._mask())
    assert loop.slots[0].rollbacks == 1
    assert not _batched.check_members_finite(loop._state).any()
    res = loop.run(max_rounds=10)
    assert res[m].status == "completed"


def test_serving_porous_convergence_mask():
    from implicitglobalgrid_tpu.serving import Request, ServingLoop

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    _, params = porous_convection3d.setup(8, 8, 8, init_grid=False, npt=3)
    loop = ServingLoop(porous_convection3d, params, capacity=2,
                       steps_per_round=1)

    def member(scale):
        return porous_convection3d.setup(
            8, 8, 8, init_grid=False, npt=3, ic_scale=scale
        )[0]

    m_c = loop.submit(Request(state=member(1.0), max_steps=50, tol=1.0))
    m_b = loop.submit(Request(state=member(0.6), max_steps=2))
    res = loop.run(max_rounds=60)
    assert res[m_c].status == "converged"
    assert res[m_c].residual is not None and res[m_c].residual < 1.0
    assert res[m_b].status == "completed" and res[m_b].steps == 2


def test_serving_rejects_mismatched_state_at_submit():
    """A malformed request must be rejected AT SUBMIT, never queued or
    half-admitted: wrong field count, wrong dtype, wrong shape."""
    from implicitglobalgrid_tpu.serving import Request

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    loop, _ = _mk_loop(capacity=1)
    good = _req(1.0, 2)
    loop.submit(good)  # defines the pool signature; occupies the one slot
    with pytest.raises(ValueError, match="field"):
        loop.submit(Request(state=good.state[:1], max_steps=2))
    wrong_dtype = tuple(A.astype("float32") for A in good.state)
    with pytest.raises(ValueError, match="signature"):
        loop.submit(Request(state=wrong_dtype, max_steps=2))
    # queue-bound requests are validated too (the slot is full)
    with pytest.raises(ValueError, match="field"):
        loop.submit(Request(state=(), max_steps=2))
    res = loop.run(max_rounds=5)  # the good member is unharmed
    assert len(res) == 1 and next(iter(res.values())).status == "completed"


def test_serving_resume_refuses_live_members(tmp_path):
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    ck = str(tmp_path / "pool")
    loop, _ = _mk_loop(capacity=1, checkpoint_every=1, checkpoint_dir=ck)
    loop.submit(_req(1.0, 3))
    loop.run_round()
    loop2, _ = _mk_loop(capacity=1, checkpoint_every=1, checkpoint_dir=ck)
    r = _req(1.1, 2)
    loop2.submit(r)  # live member: resume must refuse to clobber it
    with pytest.raises(RuntimeError, match="live members"):
        loop2.resume()


def test_serving_tol_on_model_without_residual_raises():
    from implicitglobalgrid_tpu.serving import Request

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    loop, _ = _mk_loop(capacity=1)
    r = _req(1.0, 2)
    r.tol = 0.1
    with pytest.raises(ValueError, match="no PT residual"):
        loop.submit(r)


def test_serving_checkpoint_resume_mid_flight(tmp_path):
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    ck = str(tmp_path / "pool")
    loop, params = _mk_loop(capacity=2, steps_per_round=1,
                            checkpoint_every=1, checkpoint_dir=ck)
    m0 = loop.submit(_req(1.0, 4, tenant="a"))
    m1 = loop.submit(_req(1.2, 4, tenant="b"))
    loop.run_round()
    loop.run_round()
    mid_state = _batched.member_state(loop._state, 0)

    loop2, _ = _mk_loop(capacity=2, steps_per_round=1,
                        checkpoint_every=1, checkpoint_dir=ck)
    loop2.prime(mid_state)
    assert loop2.resume()
    assert loop2.rounds == 2 and loop2.active_members == 2
    assert loop2.slots[0].member == m0 and loop2.slots[0].steps == 2
    res = loop2.run(max_rounds=10)
    # the resumed pool finishes both members with the original budgets
    assert res[m0].status == "completed" and res[m0].steps == 4
    assert res[m1].status == "completed"


# -- batched gather -----------------------------------------------------------


def test_gather_member_slices_one_member():
    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    fields = [_rand_field(10 + i) for i in range(3)]
    B = _batched.stack_fields(*fields)
    for b in (0, 2):
        got = igg.gather(B, member=b)
        want = igg.gather(fields[b])
        np.testing.assert_array_equal(got, want)
    # batched field without member= is rejected, not misread
    with pytest.raises(ValueError, match="member=k"):
        igg.gather(B)
    with pytest.raises(ValueError, match="member must be in"):
        igg.gather(B, member=7)
