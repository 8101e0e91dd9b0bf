"""Silent-data-corruption integrity plane (ISSUE 18; docs/robustness.md).

Pins the three detectors and their contracts:

* transport checksums — per-hop XOR-fold words on the coalesced packed
  ``ppermute`` payload: clean exchanges bit-exact with zero false
  positives, an armed in-flight flip trips the RECEIVER with an
  `IntegrityError` implicating the SENDER, the flip is consumed (the
  clean cached program survives), and the integrity programs live in a
  SEPARATE jit cache so the plain path's cache keys (pinned by
  ``test_coalesced_halo``) and the ``IGG_INTEGRITY=0`` zero-overhead pin
  stay intact;
* shadow-step audit — the interpret-mode bit-compare matrix: healthy
  re-execution is bit-identical across all three models x pipelined
  on/off (zero false positives at ``IGG_INTEGRITY_EVERY=1``), and an
  injected post-step ``bit_flip`` is caught at the cadence with the
  corrupting rank implicated;
* lineage digests — a checkpoint whose bytes were flipped AFTER the
  digests were taken (the ``bit_flip:…:ckpt`` placement) passes CRC but
  fails lineage ("corrupt when saved"), and `latest_checkpoint` walks
  past the poisoned generation; the streaming verifier stays
  chunk-bounded in memory (the RSS satellite).

Plus the escalation path (classify -> policy -> fleet), the
``bit_flip`` spec grammar (pointed rejections — the fault-matrix
satellite), and the rank-uniformity census of `integrity.plan`.
"""

import json
import os
import tracemalloc
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu import integrity
from implicitglobalgrid_tpu.integrity import IntegrityError
from implicitglobalgrid_tpu.models import diffusion3d
from implicitglobalgrid_tpu.ops import halo as halo_mod
from implicitglobalgrid_tpu.utils import checkpoint as ck
from implicitglobalgrid_tpu.utils import resilience
from implicitglobalgrid_tpu.utils import telemetry as tele
from jax.experimental.pallas.tpu import force_tpu_interpret_mode
from implicitglobalgrid_tpu.utils.resilience import (
    FaultInjector,
    RunGuard,
    guarded_time_loop,
)


def _counter(name: str) -> int:
    return tele.snapshot()["counters"].get(name, 0)


# --- transport checksum primitives ------------------------------------------


def test_fold_words_xor_round_trip():
    """`append_checksum`/`split_and_verify` round-trip the exact payload
    with a clean verdict; any single flipped bit — payload OR checksum
    word — trips the recomputed fold."""
    words = jnp.asarray(
        np.random.default_rng(0).integers(0, 2**32, 37, dtype=np.uint32)
    )
    wire = integrity.append_checksum(words)
    assert wire.shape == (38,)
    payload, bad = integrity.split_and_verify(wire)
    assert np.array_equal(np.asarray(payload), np.asarray(words))
    assert not bool(np.asarray(bad))
    for pos in (0, 17, 37):  # payload head, middle, the checksum word
        flipped = wire.at[pos].set(wire[pos] ^ 1)
        _, bad = integrity.split_and_verify(flipped)
        assert bool(np.asarray(bad)), f"flip at word {pos} not caught"
    # the degenerate hop: an empty payload folds to the zero word
    assert int(integrity.fold_words(words[:0])) == 0


def test_checksum_covers_nan_and_negative_zero_bits():
    """The fold runs over the unsigned word view, so byte patterns a float
    compare can never distinguish (-0.0 vs +0.0, NaN payload bits) still
    change the checksum."""
    a = jnp.asarray(np.array([np.nan, -0.0, 1.0]).view(np.uint64))
    b = jnp.asarray(np.array([np.nan, +0.0, 1.0]).view(np.uint64))
    assert int(integrity.fold_words(a)) != int(integrity.fold_words(b))


# --- transport checksums in the exchange ------------------------------------


def _grid_and_fields():
    igg.init_global_grid(12, 12, 12, periodx=1, periody=1, quiet=True)
    T = igg.zeros((12, 12, 12)) + 1.5
    C = igg.ones((12, 12, 12))
    return T, C


def test_transport_checksum_clean_exchange_no_false_positive(monkeypatch):
    monkeypatch.setenv("IGG_INTEGRITY", "1")
    T, C = _grid_and_fields()
    want_T, want_C = igg.update_halo(T + 0, C + 0)
    # a second exchange on already-consistent fields is a bitwise no-op
    oT, oC = igg.update_halo(want_T + 0, want_C + 0)
    assert np.array_equal(np.asarray(oT), np.asarray(want_T))
    assert np.array_equal(np.asarray(oC), np.asarray(want_C))
    # checksummed programs live in their own cache: the plain cache keys
    # (pinned by test_coalesced_halo) must not grow integrity entries
    assert halo_mod._integrity_jit_cache
    assert all(len(k) == 6 for k in halo_mod._integrity_jit_cache)


def test_transport_checksum_trips_receiver_and_implicates_sender(monkeypatch):
    monkeypatch.setenv("IGG_INTEGRITY", "1")
    T, C = _grid_and_fields()
    base = _counter("integrity.transport_mismatches")
    halo_mod.arm_transport_flip(3)
    with pytest.raises(IntegrityError) as ei:
        igg.update_halo(T + 0, C + 0)
    err = ei.value
    assert err.detector == "transport_checksum"
    assert err.implicated_rank == 3  # the flipping SENDER, named by a peer
    assert err.dim in (0, 1, 2)
    assert err.fields  # the hop's field labels ride the error
    assert _counter("integrity.transport_mismatches") >= base + 1
    # the flip was CONSUMED (it is part of the program cache key): the
    # next exchange runs the clean cached program and must not trip
    oT, oC = igg.update_halo(T + 0, C + 0)
    assert np.array_equal(np.asarray(oT), np.asarray(T))
    assert np.array_equal(np.asarray(oC), np.asarray(C))


def test_transport_checksum_single_field_routes_packed(monkeypatch):
    """Single-field exchanges (normally the unpacked singleton group) must
    also carry the checksum word — the wire form covers every hop."""
    monkeypatch.setenv("IGG_INTEGRITY", "1")
    T, _ = _grid_and_fields()
    out = igg.update_halo(T + 0)
    assert np.array_equal(np.asarray(out), np.asarray(T))
    halo_mod.arm_transport_flip(0)
    with pytest.raises(IntegrityError):
        igg.update_halo(T + 0)


def test_integrity_off_is_zero_overhead(monkeypatch):
    """``IGG_INTEGRITY=0`` pins everything off — like ``IGG_TELEMETRY=0``:
    no checksummed programs compiled, the audit cadence forced to 0 even
    when ``IGG_INTEGRITY_EVERY`` is set."""
    monkeypatch.setenv("IGG_INTEGRITY", "0")
    monkeypatch.setenv("IGG_INTEGRITY_EVERY", "3")
    halo_mod._integrity_jit_cache.clear()
    T, C = _grid_and_fields()
    igg.update_halo(T + 0, C + 0)
    assert not halo_mod._integrity_jit_cache
    guard = RunGuard()
    assert guard.integrity_every == 0
    assert not guard.enabled


def test_integrity_unset_honors_audit_cadence(monkeypatch):
    """Unset ``IGG_INTEGRITY`` leaves transport checksums off but honors
    the ``IGG_INTEGRITY_EVERY`` audit cadence (the tri-state contract)."""
    monkeypatch.delenv("IGG_INTEGRITY", raising=False)
    monkeypatch.setenv("IGG_INTEGRITY_EVERY", "2")
    halo_mod._integrity_jit_cache.clear()
    T, C = _grid_and_fields()
    igg.update_halo(T + 0, C + 0)
    assert not halo_mod._integrity_jit_cache  # checksums not armed
    guard = RunGuard()
    assert guard.integrity_every == 2
    assert guard.enabled


# --- shadow-step audit -------------------------------------------------------


_MATRIX = [
    ("diffusion3d", ("T", "Cp"), {}),
    ("acoustic3d", ("P", "Vx", "Vy", "Vz"), dict(periodz=1)),
    ("porous_convection3d", ("T", "Pf", "qDx", "qDy", "qDz"),
     dict(periodz=1, npt=5)),
]


@pytest.mark.parametrize("name,names,extra", _MATRIX,
                         ids=[m[0] for m in _MATRIX])
@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["serialized", "pipelined"])
def test_shadow_audit_healthy_bit_identical(name, names, extra, pipelined):
    """The interpret-mode matrix: at ``integrity_every=1`` every committed
    step is re-executed and bit-compared — healthy runs must re-execute
    bit-identically (zero false positives) for all three models under
    both the serialized and the boundary-first pipelined cadence."""
    from implicitglobalgrid_tpu import models

    model = getattr(models, name)
    setup_extra = dict(extra)
    npt = setup_extra.pop("npt", None)
    kw = dict(devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
              overlapx=4, overlapy=4, overlapz=4, quiet=True,
              dtype=jnp.float32, **setup_extra)
    if npt is not None:
        kw["npt"] = npt
    state, params = model.setup(24, 32, 64, **kw)
    base = _counter("integrity.audits")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with force_tpu_interpret_mode():
            step = model.make_multi_step(
                params, 2, donate=False, fused_k=2, fused_tile=(8, 16),
                pipelined=pipelined,
            )
            guard = RunGuard(integrity_every=1, names=names)
            assert guard.enabled
            state = guarded_time_loop(
                step, state, 1, guard=guard, sync_every_step=True,
            )
    jax.block_until_ready(state)
    assert _counter("integrity.audits") == base + 1


def test_shadow_audit_catches_state_bit_flip(fault_injection):
    """One flipped mantissa bit in the committed post-step state — finite,
    invisible to the NaN/Inf guard — trips the audit at the cadence with
    ``detector=shadow_audit``."""
    state, params = diffusion3d.setup(12, 12, 12, quiet=True)
    fault_injection("bit_flip:step2:T")
    step = diffusion3d.make_step(params, donate=False)
    guard = RunGuard(integrity_every=1, names=("T", "Cp"))
    base = _counter("integrity.audit_mismatches")
    with pytest.raises(IntegrityError) as ei:
        guarded_time_loop(step, state, 4, guard=guard, sync_every_step=True)
    assert ei.value.detector == "shadow_audit"
    assert ei.value.step == 2
    assert ei.value.implicated_rank is not None
    assert _counter("integrity.audit_mismatches") == base + 1


def test_shadow_audit_guard_invisible_without_integrity(fault_injection):
    """The same ``bit_flip`` with the integrity plane OFF sails through the
    NaN/Inf guard — the exact gap the plane exists to close (and why
    ``bit_flip`` is opt-in, never part of the default chaos draw)."""
    state, params = diffusion3d.setup(12, 12, 12, quiet=True)
    fault_injection("bit_flip:step2:T")
    step = diffusion3d.make_step(params, donate=False)
    guard = RunGuard(guard_every=1, policy="raise", names=("T", "Cp"))
    assert guard.integrity_every == 0
    out = guarded_time_loop(
        step, state, 3, guard=guard, sync_every_step=True
    )
    assert np.all(np.isfinite(np.asarray(out[0])))  # corrupt but finite


def test_serving_pool_audits_sampled_member(monkeypatch):
    """A batched pool audits one round-robin-sampled member per audited
    round through the SAME compiled multi-step; healthy rounds pass."""
    from implicitglobalgrid_tpu.serving import Request, ServingLoop

    monkeypatch.setenv("IGG_INTEGRITY_EVERY", "1")
    state, params = diffusion3d.setup(12, 12, 12, quiet=True)
    loop = ServingLoop(diffusion3d, params, capacity=1, steps_per_round=1)
    assert loop.integrity_every == 1
    loop.submit(Request(state=state, max_steps=2, tenant="t0"))
    base = _counter("integrity.audits")
    results = loop.run(max_rounds=4)
    assert len(results) == 1
    assert _counter("integrity.audits") >= base + 2


# --- bit_flip fault grammar (fault-matrix satellite) -------------------------


def test_bit_flip_spec_round_trips():
    inj = FaultInjector.from_spec("bit_flip:step3:T:proc2")
    assert inj.kind == "bit_flip" and inj.step == 3
    assert inj.field == "T" and inj.target == 2
    assert inj.spec() == "bit_flip:step3:T:proc2"
    inj = FaultInjector.from_spec("bit_flip:step4:transport")
    assert inj.field == "transport" and inj.target is None
    inj = FaultInjector.from_spec("bit_flip:step5:ckpt:proc1")
    assert inj.field == "ckpt" and inj.target == 1
    assert FaultInjector.from_spec("bit_flip:step6").field is None


def test_bit_flip_spec_rejects_bare_integer_component():
    with pytest.raises(ValueError, match="bare integer"):
        FaultInjector.from_spec("bit_flip:step3:2")


def test_bit_flip_rejects_nonexistent_field():
    """A spec naming a field the run does not have must fail POINTEDLY at
    fire time, listing the run's actual fields."""
    state, params = diffusion3d.setup(12, 12, 12, quiet=True)
    inj = FaultInjector.from_spec("bit_flip:step1:Temperature")
    with pytest.raises(ValueError) as ei:
        inj.maybe_bit_flip(tuple(state), 1, names=("T", "Cp"))
    msg = str(ei.value)
    assert "Temperature" in msg and "T" in msg and "Cp" in msg


def test_bit_flip_not_in_default_chaos_kinds():
    """Guard-invisible by design: a default chaos storm drawing bit_flip
    without the integrity plane armed would silently falsify results."""
    assert "bit_flip" in resilience.FAULT_KINDS
    assert "bit_flip" not in resilience.CHAOS_KINDS


def test_halo_corrupt_documented_as_guard_visible_twin():
    """The fault matrix names ``halo_corrupt`` the guard-VISIBLE twin of
    ``bit_flip`` (NaN payload vs finite flip) — pinned in the injector
    docstring so the matrix and the code cannot drift."""
    doc = FaultInjector.__doc__
    assert "bit_flip" in doc and "halo_corrupt" in doc
    assert "guard" in doc.lower()


# --- lineage digests ---------------------------------------------------------


def test_lineage_chains_and_detects_poisoned_generation(
    tmp_path, fault_injection
):
    igg.init_global_grid(12, 12, 12, quiet=True)
    T = igg.zeros((12, 12, 12)) + 1.5
    C = igg.ones((12, 12, 12))
    d = str(tmp_path / "ck")

    p4 = ck.save_checkpoint(d, (T, C), 4)
    assert ck.verify_checkpoint(p4) is None
    lin4 = ck.checkpoint_meta(p4)["lineage"]
    assert len(lin4["fields"]) == 2 and lin4["prev_step"] is None
    assert all(f["digest"] and f["chain"] for f in lin4["fields"])

    p6 = ck.save_checkpoint(d, (T, C), 6)
    lin6 = ck.checkpoint_meta(p6)["lineage"]
    assert lin6["prev_step"] == 4
    # same state -> same digest; the CHAIN still rolls forward
    assert lin6["fields"][0]["digest"] == lin4["fields"][0]["digest"]
    assert lin6["fields"][0]["chain"] != lin4["fields"][0]["chain"]

    # ckpt-placement flip: digests taken from the live arrays, bytes
    # flipped before the writer -> CRC passes, lineage convicts
    fault_injection("bit_flip:step8:ckpt")
    p8 = ck.save_checkpoint(d, (T, C), 8)
    problem = ck.verify_checkpoint(p8)
    assert problem is not None
    assert "already corrupt when saved" in problem

    # the fallback walks PAST the poisoned generation
    best = ck.latest_checkpoint(d)
    assert best is not None and best.endswith("step_00000006")
    with pytest.raises(ValueError, match="already corrupt"):
        ck.restore_checkpoint(p8)
    state, step, _ = ck.restore_checkpoint(best)
    assert step == 6
    assert np.array_equal(np.asarray(state[0]), np.asarray(T))


def test_lineage_ignores_legacy_meta(tmp_path):
    """Generations saved before the lineage section verify clean (the
    format stays readable both ways)."""
    igg.init_global_grid(12, 12, 12, quiet=True)
    T = igg.ones((12, 12, 12))
    d = str(tmp_path / "ck")
    p = ck.save_checkpoint(d, (T,), 1)
    meta_path = os.path.join(p, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    del meta["lineage"]
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    assert ck.verify_checkpoint(p) is None


def test_streaming_verifier_memory_bounded(tmp_path):
    """The integrity sweep must not spike RSS: digesting a shard streams
    `STREAM_CHUNK` slices, never a whole member (the ``rss_growth``
    anomaly rule must not fire on our own verifier)."""
    from implicitglobalgrid_tpu.integrity import lineage

    big = np.random.default_rng(0).random((4, 1 << 20))  # 32 MiB payload
    path = str(tmp_path / "shards_p0.npz")
    np.savez(path, f0_o0_0_0=big.view(np.uint8).reshape(-1),
             f0_o0_0_0_shape=np.asarray(big.shape))
    del big
    tracemalloc.start()
    digests = lineage.stream_npz_block_digests(path)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert set(digests) == {"f0_o0_0_0"}
    assert peak < 8 * lineage.STREAM_CHUNK, (
        f"streaming verifier peaked at {peak} bytes"
    )
    # and the streamed digest equals the in-memory one
    raw = np.load(path)["f0_o0_0_0"]
    assert digests["f0_o0_0_0"] == lineage.block_digest(raw)


# --- escalation: classify -> policy -> fleet ---------------------------------


def test_sdc_bundle_classifies_and_implicates_sender():
    from implicitglobalgrid_tpu.supervisor.classify import classify

    ev = {
        "bundles": {1: [{"reason": "sdc",
                         "info": {"detector": "transport_checksum",
                                  "implicated_rank": 0}}]},
        "alerts": [], "events": [],
    }
    inc = classify((1, 1), ev)
    assert inc.kind == "silent_corruption"
    assert inc.ranks == (0,)  # the SENDER, not the detecting rank
    assert inc.detail["bundle_rank"] == 1
    assert inc.detail["detector"] == "transport_checksum"


def test_sdc_policy_quarantines_on_first_strike():
    from implicitglobalgrid_tpu.supervisor.classify import Incident
    from implicitglobalgrid_tpu.supervisor.policy import (
        RecoveryPolicy,
        SupervisorState,
        decide,
    )

    inc = Incident(kind="silent_corruption", ranks=(2,), rcs=(0, 0, 1),
                   detail={"detector": "shadow_audit"})
    state = SupervisorState()
    state.record_incident(inc)
    d = decide(inc, state, RecoveryPolicy(), ladder_len=3)
    assert d.action == "quarantine"  # no strike accrual for a liar
    assert d.quarantined == (2,) and d.rung == 1
    d = decide(inc, SupervisorState(rung=2), RecoveryPolicy(), ladder_len=3)
    assert d.action == "give_up" and d.quarantined == (2,)


def test_sdc_pool_quarantined_not_respawned():
    from implicitglobalgrid_tpu.fleet.policy import (
        FleetPolicy,
        FleetState,
        decide_pool,
    )
    from implicitglobalgrid_tpu.supervisor.classify import Incident

    inc = Incident(kind="sdc", ranks=(3,), rcs=(None,),
                   detail={"pool": "p0", "devices": "tpu:0-3",
                           "detector": "shadow_audit"})
    d = decide_pool(inc, FleetState(), FleetPolicy())
    assert d.action == "quarantine"
    assert d.quarantined == ("tpu:0-3",)
    assert "respawn" in d.reason  # the reason explains why not respawn


# --- rank-uniformity census --------------------------------------------------


def test_integrity_plan_census_rank_uniform():
    from implicitglobalgrid_tpu.analysis.collectives import (
        check_rank_consistency,
        integrity_plan_censuses,
    )

    censuses = list(integrity_plan_censuses(None))
    assert censuses
    for census in censuses:
        assert check_rank_consistency(census) == []


def test_integrity_plan_checksums_add_no_collective():
    from implicitglobalgrid_tpu.integrity.plan import integrity_plan

    plain = integrity_plan(True, checksums=False, audit_every=0, step=5,
                           exchange_dims=3)
    summed = integrity_plan(True, checksums=True, audit_every=0, step=5,
                            exchange_dims=3)
    assert len(plain) == len(summed) == 3  # payload-only delta, same hops
    audited = integrity_plan(True, checksums=True, audit_every=5, step=5,
                             exchange_dims=3)
    assert len(audited) == 4  # exactly one cadence-keyed psum
    assert audited[-1] == ("psum", "audit-compare")
    off_cadence = integrity_plan(True, checksums=True, audit_every=5,
                                 step=6, exchange_dims=3)
    assert len(off_cadence) == 3
