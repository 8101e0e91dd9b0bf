"""Group-schedule loop shape (`models/_fused.run_group_schedule`), its
carry-copy-free paired fori body, and the deep-z envelope gate coupling
(`ops/pallas_stencil`) — advisor r4 findings."""

import importlib
import itertools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models._fused import pick_body, run_group_schedule
from implicitglobalgrid_tpu.ops.pallas_stencil import fused_support_error
from implicitglobalgrid_tpu.utils import telemetry
from implicitglobalgrid_tpu.utils.hlo_analysis import (
    parse_computations,
    while_body_copies,
)


def _traced(chunks):
    """Run a schedule under jit; return (result, body trace count)."""
    calls = []

    def body(ki, c):
        calls.append(ki)
        return c + ki

    out = jax.jit(lambda c: run_group_schedule(chunks, body, c))(jnp.float32(0))
    return float(out), len(calls)


def test_short_schedule_fully_unrolled():
    out, ncalls = _traced([2] * 5)
    assert out == 10.0
    assert ncalls == 5  # no fori_loop at all


def test_long_uniform_schedule_keeps_unrolled_groups():
    """A 12-group production schedule must keep the unrolled-group pipelining
    win on unroll_limit groups, fori-looping only the excess (advisor r4:
    the old shape sent the whole run through the fori_loop, silently losing
    the documented 15-30% speedup for nsteps=24 at fused_k=2)."""
    out, ncalls = _traced([2] * 12)
    assert out == 24.0
    # 8 unrolled traces + the paired fori body's trace(s), two groups each;
    # strictly fewer than full unroll, strictly more than fori-only.
    assert 10 <= ncalls <= 12


def test_ragged_schedule_counts_tail_against_limit():
    out, ncalls = _traced([6] * 10 + [4])
    assert out == 64.0
    # 7 unrolled prefix + 1 ragged tail + the odd excess group unrolled
    # before the paired fori body's trace(s), two groups each
    assert 11 <= ncalls <= 13


def test_all_or_nothing_shape_for_xla_cadences():
    """`fori_excess_only=False` (the porous XLA cadence): a uniform run past
    the limit is ENTIRELY fori-looped — the fori boundary is the fusion
    barrier its bit-identity contract relies on — while a ragged tail and
    within-limit runs still unroll."""
    calls = []

    def body(ki, c):
        calls.append(ki)
        return c + ki

    out = jax.jit(
        lambda c: run_group_schedule(
            [2] * 3, body, c, unroll_limit=1, fori_excess_only=False
        )
    )(jnp.float32(0))
    assert float(out) == 6.0
    assert len(calls) <= 2  # fori trace only, no unrolled groups
    calls.clear()
    out = jax.jit(
        lambda c: run_group_schedule(
            [6, 4], body, c, unroll_limit=1, fori_excess_only=False
        )
    )(jnp.float32(0))
    assert float(out) == 10.0
    assert calls == [6, 4]  # prefix of one group: fully unrolled


_FIELD = (64, 128)


def _opaque_body(ki, c):
    """One opaque call with a fresh output, standing in for an out-of-place
    Pallas kernel launch (fusion cannot reach inside it)."""
    return jax.pure_callback(
        lambda x: np.asarray(np.sin(x) + ki, x.dtype),
        jax.ShapeDtypeStruct(c.shape, c.dtype), c,
    )


def _compiled_schedule(chunks, **kw):
    f = jax.jit(lambda c: run_group_schedule(chunks, _opaque_body, c, **kw))
    x = jnp.linspace(0.0, 1.0, _FIELD[0] * _FIELD[1],
                     dtype=jnp.float32).reshape(_FIELD)
    return f(x), f.lower(x).compile().as_text()


@pytest.mark.parametrize("chunks", [[1] * 12, [1] * 12 + [2]],
                         ids=["even_excess", "odd_excess_ragged"])
def test_paired_fori_body_has_no_carry_copy(chunks):
    """The kernel shape runs its fori excess two groups per iteration: the
    second group's output takes the carry's buffer, so the while body holds
    no whole-field copy (one group per iteration pays one per iteration,
    the control below) — and the values are the fully unrolled schedule's,
    bit for bit."""
    out, txt = _compiled_schedule(chunks)
    assert "while(" in txt  # the excess (4 or 5 groups) still loops
    assert while_body_copies(txt, _FIELD[0] * _FIELD[1]) == []
    ref, _ = _compiled_schedule(chunks, unroll_limit=len(chunks))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_xla_cadence_shape_keeps_one_group_per_iteration():
    """`fori_excess_only=False` keeps one group per fori iteration (the
    porous XLA cadence's fusion barrier between groups) — and with it the
    carry copy the kernel shape removes."""
    out, txt = _compiled_schedule([1] * 3, unroll_limit=1,
                                  fori_excess_only=False)
    (body,) = {b for b, _ in while_body_copies(txt, _FIELD[0] * _FIELD[1])}
    calls = [l for l in parse_computations(txt)[body]
             if "custom-call(" in l]
    assert len(calls) == 1
    ref, _ = _compiled_schedule([1] * 3, unroll_limit=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fori_group_counters():
    """``schedule.fori_paired_groups`` counts the groups a compiled fused
    cadence loops two per iteration (a 12-group k=4 diffusion chunk: 8
    unrolled + 4 paired); ``schedule.fori_single_groups`` counts the porous
    XLA cadence's one-per-iteration groups."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.models import porous_convection3d as pc

    telemetry.reset()
    state, params = diffusion3d.setup(16, 16, 128, dtype=jnp.float32,
                                      quiet=True, devices=jax.devices()[:1])
    with force_tpu_interpret_mode():
        diffusion3d.make_multi_step(params, 48, fused_k=4).lower(*state)
    igg.finalize_global_grid()
    counters = telemetry.snapshot()["counters"]
    assert counters.get("schedule.fori_paired_groups") == 4
    assert "schedule.fori_single_groups" not in counters

    telemetry.reset()
    # one device: no shard_map, whose staging traces its body more than once
    state, params = pc.setup(10, 10, 10, overlapx=4, overlapy=4, overlapz=4,
                             npt=4, quiet=True, devices=jax.devices()[:1])
    # two time steps x npt/exchange_every = 2 looped groups each
    pc.make_multi_step(params, 2, donate=False, exchange_every=2).lower(*state)
    counters = telemetry.snapshot()["counters"]
    telemetry.reset()
    assert counters.get("schedule.fori_single_groups") == 4
    assert "schedule.fori_paired_groups" not in counters


def test_deep_z_gate_and_budget_jointly_cover_by128():
    """Advisor r4: the probed crash predicate (`_deep_z_crash`: by>=128, k>4,
    n2>=512) and the VMEM budget are coupled — by=128 configs the predicate
    admits (k <= 4) at deep z must be stopped by the budget instead.  Pin
    every by=128 deep-z combination to a rejection by ONE of the two gates,
    and the probed-safe point to acceptance."""
    for k, n2 in [(2, 1024), (4, 1024), (6, 512), (6, 1024)]:
        err = fused_support_error((64, 256, n2), k, 4, 32, 128)
        assert err is not None, f"(k={k}, n2={n2}) must be rejected"
    # the hardware-validated deep-z rung stays in the envelope
    assert fused_support_error((64, 256, 512), 4, 4, 32, 128) is None


# --- the one trace-time gate (`models/_fused.pick_body`) -------------------


def _resolve(pipelined, split_ok):
    return pipelined is not False and split_ok


def _diffusion_gate(xy, z, plain_ok, zpatch_ok, split_ok, chunks_ok, pipelined):
    """The diffusion cadence's choice as it was written in its model file:
    a grid with no halo activity runs its own jitted chunk without
    pipelined bodies; a z-active grid takes the z-patch kernel first, except
    a self-neighbour z, which the kernel wraps inside its tile (since the
    diffusion family has ``z_wrap``), so it gates like an inactive z."""
    z_active = z != "inactive"
    z_self = z == "self"
    if not (xy or z_active):
        return "plain" if plain_ok else "xla"
    if z_active and not z_self and zpatch_ok:
        return "zpatch_piped" if _resolve(pipelined, split_ok) else "zpatch"
    if plain_ok:
        piped = (z_self or not z_active) and _resolve(pipelined, split_ok)
        return "plain_piped" if piped else "plain"
    return "xla_early" if pipelined else "xla"


def _acoustic_gate(xy, z, plain_ok, zpatch_ok, split_ok, chunks_ok, pipelined):
    """The acoustic cadence's choice as it was written in its model file: a
    self-neighbour z wraps inside the kernel's tile, so it gates like an
    inactive z."""
    z_active = z != "inactive"
    z_self = z == "self"
    if not (xy or z_active):
        return "plain" if plain_ok else "xla"
    if z_active and not z_self and zpatch_ok:
        return "zpatch_piped" if _resolve(pipelined, split_ok) else "zpatch"
    if plain_ok:
        piped = (z_self or not z_active) and _resolve(pipelined, split_ok)
        return "plain_piped" if piped else "plain"
    return "xla_early" if pipelined else "xla"


def _porous_gate(xy, z, plain_ok, zpatch_ok, split_ok, chunks_ok, pipelined):
    """The porous cadence's choice as it was written in its model file: one
    gate for every grid, a PT schedule without an even chunk falls back,
    and any truthy ``pipelined`` takes the early-dispatch XLA cadence."""
    z_active = z != "inactive"
    active = xy or z_active
    if chunks_ok and active and z_active and zpatch_ok:
        return "zpatch_piped" if _resolve(pipelined, split_ok) else "zpatch"
    if plain_ok and chunks_ok:
        piped = active and not z_active and _resolve(pipelined, split_ok)
        return "plain_piped" if piped else "plain"
    return "xla_early" if pipelined else "xla"


_GATES = {"diffusion3d": _diffusion_gate, "acoustic3d": _acoustic_gate,
          "porous_convection3d": _porous_gate}


@pytest.mark.parametrize("pipelined", [None, True, False],
                         ids=["auto", "piped", "serial"])
@pytest.mark.parametrize("z", ["inactive", "self", "split"])
@pytest.mark.parametrize("model", sorted(_GATES))
def test_gate_table(model, z, pipelined):
    """`_fused.pick_body` with each solver's family picks the body that
    solver's own gate picked before the three gates became one, over x/y
    activity x the plain and z-patch envelopes x the split's admissibility
    (x, for porous, whether the PT schedule has a kernel chunk)."""
    fam = importlib.import_module(
        f"implicitglobalgrid_tpu.models.{model}").FAMILY
    chunk_cases = (True, False) if model == "porous_convection3d" else (True,)
    for xy, plain_ok, zpatch_ok, split_ok, chunks_ok in itertools.product(
            (False, True), (True, False), (True, False), (True, False),
            chunk_cases):
        want = _GATES[model](xy, z, plain_ok, zpatch_ok, split_ok, chunks_ok,
                             pipelined)
        schedule_err = None if chunks_ok else "no kernel chunk"
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            got = pick_body(
                fam, active=xy or z != "inactive", z_active=z != "inactive",
                z_self=z == "self",
                plain_err=None if plain_ok else "plain envelope",
                zpatch_err=None if zpatch_ok else "z-patch envelope",
                schedule_err=schedule_err,
                split_err=lambda zpatch: None if split_ok else "split",
                pipelined=pipelined, shape=(24, 32, 128), k=2,
            )
        assert got == want, (xy, plain_ok, zpatch_ok, split_ok, chunks_ok)
