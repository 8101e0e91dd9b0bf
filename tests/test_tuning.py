"""Autotuned kernel & schedule configs (ISSUE 13, docs/performance.md).

The tuner's whole contract in one suite: the cache is a refusing,
atomically-published schema (never a crash, never a silently-applied stale
config), the static prior keeps over-budget candidates away from
measurement, the search is deterministic given deterministic measurements,
a cache hit measures NOTHING, and a tuned config is a pure schedule
substitution — bit-identical results on the oracle matrix for all three
models.  The SPMD half (rank-0-decides + broadcast over real gloo hops)
lives in `tests/_distributed_worker.py`; the rank-divergence POSITIVE
fixture here proves the `collective-consistency` analyzer catches a
rank-keyed cache lookup.
"""

import json
import os

import numpy as np
import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu import tuning
from implicitglobalgrid_tpu.models import (
    acoustic3d,
    diffusion3d,
    porous_convection3d,
)
from implicitglobalgrid_tpu.utils import telemetry as tele

_here = os.path.dirname(os.path.abspath(__file__))
_repo = os.path.dirname(_here)


def _tune_counters():
    snap = tele.snapshot()
    return {k: v for k, v in snap.get("counters", {}).items()
            if k.startswith("tune.")}


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """A fresh primary cache dir (env-wired) with NO seed fallback, so a
    test's lookups can never hit the committed chip entries."""
    d = str(tmp_path / "tunecache")
    monkeypatch.setenv("IGG_TUNE_CACHE", d)
    return tuning.TuneCache(primary=d, fallbacks=())


# -- keys + schema ------------------------------------------------------------


def test_keys_distinct_and_filenames_stable():
    base = dict(batch=0, backend="tpu", topology="t")
    k1 = tuning.make_key("diffusion3d", (256, 256, 256), "float32", **base)
    variants = [
        tuning.make_key("diffusion3d", (256, 256, 256), "float64", **base),
        tuning.make_key("diffusion3d", (128, 256, 256), "float32", **base),
        tuning.make_key("acoustic3d", (256, 256, 256), "float32", **base),
        tuning.make_key("diffusion3d", (256, 256, 256), "float32",
                        backend="tpu", topology="other", batch=0),
        tuning.make_key("diffusion3d", (256, 256, 256), "float32",
                        backend="tpu", topology="t", batch=1),
        tuning.make_key("porous_convection3d", (256, 256, 256), "float32",
                        extra={"npt": 12}, **base),
        tuning.make_key("porous_convection3d", (256, 256, 256), "float32",
                        extra={"npt": 10}, **base),
    ]
    names = {tuning.entry_filename(k) for k in variants}
    assert tuning.entry_filename(k1) not in names
    assert len(names) == len(variants)  # every key component keys
    # same inputs -> same digest (the lookup path depends on it)
    k1b = tuning.make_key("diffusion3d", (256, 256, 256), "float32", **base)
    assert tuning.entry_filename(k1) == tuning.entry_filename(k1b)
    with pytest.raises(ValueError, match="unknown model"):
        tuning.make_key("nope", (8, 8, 8), "float32", **base)


def test_validate_entry_contract():
    key = tuning.make_key("diffusion3d", (16, 16, 16), "float32",
                          backend="cpu", topology="t")
    good = tuning.new_entry(key, {"fused_k": 4, "fused_tile": [32, 64]})
    tuning.validate_entry(good)  # round-trips
    for mutate, match in (
        (lambda d: d.update(schema_version=99), "schema version"),
        (lambda d: d["config"].update(npt=12), "pure substitution"),
        (lambda d: d["config"].update(fused_k=3), r"\[2, 8\] ladder"),
        (lambda d: d["config"].update(fused_tile="big"), "2 positive ints"),
        (lambda d: d.update(source=""), "provenance"),
        (lambda d: d["key"].update(size=[0, 1, 2]), "3 positive ints"),
    ):
        doc = json.loads(json.dumps(good))
        mutate(doc)
        with pytest.raises(ValueError, match=match):
            tuning.validate_entry(doc)
    with pytest.raises(ValueError, match="without fused_k"):
        tuning.new_entry(key, {"fused_tile": [32, 64]})


def test_cache_roundtrip_refusals_and_atomicity(tune_cache):
    key = tuning.make_key("diffusion3d", (16, 16, 16), "float32",
                          backend="cpu", topology="t")
    entry = tuning.new_entry(key, {"exchange_every": 2}, source="test")
    path = tune_cache.store(key, entry)
    assert not os.path.exists(path + ".tmp")  # atomic publish, no debris
    got = tune_cache.lookup(key)
    assert got["config"] == {"exchange_every": 2}

    # version-mismatch refusal: a future schema must read as a MISS
    doc = json.load(open(path))
    doc["schema_version"] = tuning.SCHEMA_VERSION + 1
    json.dump(doc, open(path, "w"))
    assert tune_cache.lookup(key) is None
    assert "schema version" in tune_cache.last_refusal

    # corrupt-entry fallback to default: also a miss, reason recorded
    with open(path, "w") as f:
        f.write('{"schema_version": 1, "key": {tru')
    assert tune_cache.lookup(key) is None
    assert "corrupt" in tune_cache.last_refusal

    # key drift: a valid entry under the WRONG filename must not serve
    other = tuning.make_key("diffusion3d", (32, 32, 32), "float32",
                            backend="cpu", topology="t")
    tune_cache.store(key, entry)
    os.replace(path, tune_cache.path_for(other))
    assert tune_cache.lookup(other) is None
    assert "key drift" in tune_cache.last_refusal

    # layered lookup: the fallback serves what the primary lacks
    layered = tuning.TuneCache(primary=tune_cache.primary + ".empty",
                               fallbacks=(tune_cache.primary,))
    tune_cache.store(key, entry)
    assert layered.lookup(key)["config"] == {"exchange_every": 2}
    assert tune_cache.clear() >= 1
    assert tune_cache.lookup(key) is None


# -- candidate space + prior --------------------------------------------------


def test_candidate_space_ladders_and_rejections():
    cands, rejected = tuning.candidate_space(
        "diffusion3d", (256, 256, 256), 4, nsteps=24)
    cfgs = [c["config"] for c in cands]
    assert cfgs[0] == {}  # the default is always first (and always measured)
    ks = {c.get("fused_k") for c in cfgs if "fused_k" in c}
    assert ks == {2, 4, 6, 8}  # nsteps=24 admits the whole even ladder
    assert any("fused_tile" in c for c in cfgs)  # tile ladder enumerated
    # no grid -> nothing to exchange: no exchange_every, no coalesce twins
    assert not any("exchange_every" in c for c in cfgs)
    assert not any("coalesce" in c for c in cfgs)
    assert any("nothing to amortize" in r["error"] for r in rejected)
    # modeled prior: temporal blocking must model FEWER bytes than default
    default_b = cands[0]["modeled"]["bytes_per_step"]
    fused = next(c for c in cands if c["config"].get("fused_k") == 4)
    assert fused["modeled"]["bytes_per_step"] < default_b
    assert fused["modeled"]["vmem_bytes"] > 0

    # a non-128 minor dim rejects the whole kernel ladder with the reason
    cands8, rejected8 = tuning.candidate_space(
        "diffusion3d", (8, 8, 8), 4, nsteps=8)
    assert [c["config"] for c in cands8] == [{}]
    assert all("128" in r["error"] or "amortize" in r["error"]
               or "multiple" in r["error"] for r in rejected8)


def test_vmem_ladder_prunes_before_measurement(monkeypatch):
    # (a) the env ladder at enumeration: IGG_VMEM_MB shrinks every kernel
    # budget, so the fused candidates are rejected by the envelope itself
    monkeypatch.setenv("IGG_VMEM_MB", "4")
    cands, rejected = tuning.candidate_space(
        "diffusion3d", (256, 256, 256), 4, nsteps=24)
    assert [c["config"] for c in cands] == [{}]
    # the envelope's auto-tile flow reports a ladder with NO fitting rung
    # (every rung failed the scaled VMEM budget)
    assert any("no tuned tile candidate" in r["error"] for r in rejected)
    monkeypatch.delenv("IGG_VMEM_MB")

    # (b) the explicit prune budget: an over-budget candidate lands in the
    # cut with the reason and NEVER reaches the measure callable
    cands, _ = tuning.candidate_space(
        "diffusion3d", (256, 256, 256), 4, nsteps=24)
    big = [c for c in cands if c["modeled"]["vmem_bytes"] > 1024]
    assert big, "expected kernel candidates with a modeled working set"
    survivors, cut = tuning.prune(cands, topk=99, vmem_budget_bytes=1024)
    assert [c["config"] for c in survivors
            if c["modeled"]["vmem_bytes"] > 1024] == []
    assert all("VMEM" in c["error"] for c in cut
               if c["modeled"]["vmem_bytes"] > 1024)
    measured = [c["config"] for c in survivors]
    for c in big:
        assert c["config"] not in measured

    # (c) topk: the default always survives, the rest rank by the prior
    survivors, cut = tuning.prune(cands, topk=3)
    assert len(survivors) == 3 and survivors[0]["config"] == {}
    ranked = [tuning.modeled_seconds(c["modeled"]) for c in survivors[1:]]
    assert ranked == sorted(ranked)
    with pytest.raises(ValueError, match="topk"):
        tuning.prune(cands, topk=0)


# -- resolve: determinism, cache hit, telemetry -------------------------------


def _grid16():
    igg.init_global_grid(16, 16, 16, overlapx=4, overlapy=4, overlapz=4,
                         quiet=True)
    from implicitglobalgrid_tpu.parallel.grid import global_grid

    return global_grid()


def test_search_deterministic_and_second_call_hits(tune_cache):
    gg = _grid16()
    calls = []

    def measure(cfg):
        calls.append(json.dumps(cfg, sort_keys=True))
        return 0.25 if cfg.get("exchange_every") == 2 else 1.0

    before = _tune_counters()
    cfg1 = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, measure=measure)
    first_calls = list(calls)
    assert cfg1 == {"exchange_every": 2}
    assert len(first_calls) >= 2  # default + at least the winner

    # determinism: same inputs, fresh cache -> same winner, same order
    tune_cache.clear()
    calls.clear()
    cfg2 = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, measure=measure)
    assert cfg2 == cfg1 and calls == first_calls

    # cache hit: zero measurement, pinned via the counters
    calls.clear()
    cfg3 = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, measure=measure)
    after = _tune_counters()
    assert cfg3 == cfg1 and calls == []
    assert after.get("tune.cache_hit", 0) - before.get("tune.cache_hit", 0) == 1
    assert (after.get("tune.candidates_measured", 0)
            - before.get("tune.candidates_measured", 0)) == 2 * len(first_calls)
    assert after.get("tune.cache_miss", 0) - before.get("tune.cache_miss", 0) == 2
    assert (after.get("tune.candidates_pruned", 0)
            > before.get("tune.candidates_pruned", 0))

    # the persisted entry carries provenance + the tuner census
    entry = tune_cache.lookup(tuning.make_key(
        "diffusion3d", gg.nxyz, "float32", gg=gg, nsteps=4))
    assert entry["source"] == "search"
    assert entry["tuner"]["measured"] == len(first_calls)

    # the igg.tune span wrapped each resolve (rank-tagged winner events
    # ride the standard event log; the span is the timing surface)
    from implicitglobalgrid_tpu.utils.tracing import span_summary

    assert "igg.tune" in span_summary()


def test_degenerate_point_is_never_persisted(tune_cache):
    """nsteps=5 admits NO cadence candidate on this grid (odd, non-128
    minor): the resolve must return the default WITHOUT storing (or
    measuring) anything, and a cadence-admissible nsteps afterwards still
    finds its real win."""
    gg = _grid16()
    calls = []

    def measure(cfg):
        calls.append(cfg)
        return 0.25 if cfg.get("exchange_every") == 2 else 1.0

    cfg = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=5, gg=gg,
        cache=tune_cache, measure=measure)
    assert cfg == {} and calls == []  # nothing measured either
    assert not os.path.isdir(tune_cache.primary) or \
        os.listdir(tune_cache.primary) == []
    # a cadence-admissible nsteps (its own schedule-class key) still
    # finds the real win afterwards
    cfg4 = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, measure=measure)
    assert cfg4 == {"exchange_every": 2} and calls


def test_schedule_class_keys_chunk_sizes_apart():
    """nsteps keys only through its admissibility class: 24 and 48 share a
    winner (same ladder), 16 tunes its own point, porous is class-exempt
    (its cadence chunks npt)."""
    base = dict(batch=0, backend="tpu", topology="t")
    k24 = tuning.make_key("diffusion3d", (256,) * 3, "float32", nsteps=24,
                          **base)
    k48 = tuning.make_key("diffusion3d", (256,) * 3, "float32", nsteps=48,
                          **base)
    k16 = tuning.make_key("diffusion3d", (256,) * 3, "float32", nsteps=16,
                          **base)
    assert k24 == k48 and k24["schedule"] == "w2.4.6.8"
    assert k16 != k24 and k16["schedule"] == "w2.4.8"
    assert tuning.schedule_class("porous_convection3d", 7) == "npt"
    assert tuning.schedule_class("diffusion3d", 5) == "none"


def test_incompatible_hit_researches_without_overwriting(tune_cache):
    """A HAND-SEEDED winner whose cadence cannot divide the live nsteps
    (a resolve-written one cannot — the key's schedule class forbids it)
    must not silently under-tune: the hit falls through to a fresh search
    for THIS nsteps — and the stored entry survives untouched."""
    gg = _grid16()
    key = tuning.make_key("diffusion3d", gg.nxyz, "float32", gg=gg,
                          nsteps=4)
    tune_cache.store(key, tuning.new_entry(
        key, {"fused_k": 6}, source="hand-seed"))
    calls = []

    def measure(cfg):
        calls.append(cfg)
        return 0.25 if cfg.get("exchange_every") == 2 else 1.0

    cfg = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, measure=measure)
    assert cfg == {"exchange_every": 2} and calls  # searched, not projected
    assert tune_cache.lookup(key)["config"] == {"fused_k": 6}  # no thrash
    # cache-only mode never applies the incompatible winner either
    assert tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, allow_search=False) == {}


def test_unreadable_entry_degrades_to_a_miss(tune_cache):
    """The never-crash contract covers OSError too: a directory squatting
    on the entry's filename (or an unreadable file) must read as a miss,
    not abort make_multi_step."""
    key = tuning.make_key("diffusion3d", (16, 16, 16), "float32",
                          backend="cpu", topology="t")
    os.makedirs(tune_cache.path_for(key))  # IsADirectoryError on open()
    assert tune_cache.lookup(key) is None
    assert "unreadable" in tune_cache.last_refusal
    # the CLI listing survives it too: unreadable rows carry a None doc
    assert [doc for _p, doc in tune_cache.entries()] == [None]


def test_resolve_without_measure_needs_cache(tune_cache):
    gg = _grid16()
    with pytest.raises(ValueError, match="no measure callable"):
        tuning.resolve_tuned_config("diffusion3d", gg.nxyz, "float32",
                                    nsteps=4, gg=gg, cache=tune_cache)
    # allow_search=False is the no-surprise mode: a miss is the default
    assert tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, allow_search=False) == {}


def test_telemetry_disabled_is_a_noop(tune_cache, monkeypatch):
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    gg = _grid16()
    cfg = tuning.resolve_tuned_config(
        "diffusion3d", gg.nxyz, "float32", nsteps=4, gg=gg,
        cache=tune_cache, measure=lambda c: 1.0)
    assert isinstance(cfg, dict)  # no crash, no registry writes


def test_explicit_kwargs_win_and_skip_the_search(tune_cache, monkeypatch):
    gg = _grid16()
    key = tuning.make_key("diffusion3d", gg.nxyz, np.dtype("float64"), gg=gg)
    tune_cache.store(key, tuning.new_entry(key, {"exchange_every": 4},
                                           source="test"))
    state, params = diffusion3d.setup(16, 16, 16, init_grid=False)
    from implicitglobalgrid_tpu.tuning.search import apply_tuned_config

    kwargs = dict(fused_k=None, fused_tile=None, exchange_every=2,
                  pipelined=None, coalesce=None)
    out = apply_tuned_config("diffusion3d", diffusion3d, params, 4,
                             dict(kwargs))
    assert out == kwargs  # pinned kwarg -> untouched, no resolve
    # and the full entry point honors the pin too (the cached
    # exchange_every=4 would not even divide nsteps=6)
    step = diffusion3d.make_multi_step(params, 6, donate=False,
                                       exchange_every=2, autotune=True)
    assert callable(step)


def test_hide_comm_run_skips_the_search(tune_cache):
    """hide_comm schedules the per-step path; every cadence candidate
    conflicts with it (the builders raise on the combination), so
    autotune=True must SKIP cleanly — not crash mid-search on the first
    fused/exchange candidate build."""
    import jax

    state, params = diffusion3d.setup(
        16, 16, 16, hide_comm=True,
        overlapx=4, overlapy=4, overlapz=4, quiet=True,
    )
    step = diffusion3d.make_multi_step(params, 4, donate=False,
                                       autotune=True)
    out = jax.block_until_ready(step(*state))
    assert out[0].shape == state[0].shape
    # nothing searched, nothing persisted
    assert not os.path.isdir(tune_cache.primary) or \
        os.listdir(tune_cache.primary) == []


def test_project_config_drops_an_undividable_cadence():
    from implicitglobalgrid_tpu.tuning.search import project_config

    cfg = {"fused_k": 4, "fused_tile": [32, 64], "pipelined": True,
           "coalesce": False}
    assert project_config("diffusion3d", cfg, nsteps=6) == {"coalesce": False}
    assert project_config("diffusion3d", cfg, nsteps=8) == cfg
    # the porous cadence chunks npt, not nsteps: exempt
    assert project_config("porous_convection3d", {"fused_k": 6},
                          nsteps=7) == {"fused_k": 6}


# -- bit-exactness: tuning changes schedule, never results --------------------

#: (model module, model name, setup kwargs, tuned config, nsteps) — each on
#: the deep-halo DECOMPOSED oracle grid the repo's cadence-equivalence
#: tests pin bitwise (8-device (2,2,2) mesh, overlap 4, non-periodic: 12
#: real internal boundaries; a periodic wrap re-fuses the program and
#: trades bitwise for the documented fusion-rounding ULPs).  The cached
#: config is a nontrivial schedule change (slab cadence; the acoustic row
#: also flips the coalesce lever).
_ORACLE = (
    (diffusion3d, "diffusion3d", {}, {"exchange_every": 2}, 4),
    (acoustic3d, "acoustic3d", {}, {"exchange_every": 2, "coalesce": False},
     4),
    (porous_convection3d, "porous_convection3d", {"npt": 4},
     {"exchange_every": 2}, 2),
)


@pytest.mark.parametrize("module,name,setup_kw,config,nsteps", _ORACLE,
                         ids=[r[1] for r in _ORACLE])
def test_tuned_config_bit_identical_to_default(module, name, setup_kw,
                                               config, nsteps, tune_cache):
    import jax

    grid_kw = dict(overlapx=4, overlapy=4, overlapz=4, quiet=True)

    def run(**mk_kwargs):
        state, params = module.setup(16, 16, 16, **setup_kw, **grid_kw)
        step = module.make_multi_step(params, nsteps, donate=False,
                                      **mk_kwargs)
        out = jax.block_until_ready(step(*state))
        got = np.asarray(igg.gather(out[0]))
        key = tuning.make_key(
            name, (16, 16, 16), params.dtype,
            gg=igg.get_global_grid(), nsteps=nsteps,
            extra={"npt": setup_kw["npt"]} if "npt" in setup_kw else None,
        )
        igg.finalize_global_grid()
        return got, key

    ref, key = run()
    tune_cache.store(key, tuning.new_entry(key, config, source="test"))
    tuned, _ = run(autotune=True)
    # owned cells bit-identical: the tuned cadence changed the SCHEDULE
    # (slab exchanges, coalescing) and nothing else
    np.testing.assert_array_equal(tuned, ref)
    # and the resolve really served the seeded winner, not a fresh search
    assert tune_cache.lookup(key)["source"] == "test"


# -- seeding from the recorded trajectory -------------------------------------


def test_seed_from_bench_ingests_the_recorded_winners(tune_cache):
    # the earlier runtime's rounds, kept as fixtures (tests/test_perf_gate.py)
    traj = os.path.join(_here, "data", "bench_trajectory")
    entries = tuning.seed_from_bench(traj, tune_cache, backend="tpu")
    assert entries, "the recorded BENCH rounds carry seedable extras"
    by_key = {(e["key"]["model"], tuple(e["key"]["size"]),
               e["key"]["extra"].get("npt")): e for e in entries}
    porous = by_key[("porous_convection3d", (256, 256, 256), 12)]
    assert porous["config"] == {"fused_k": 6}
    assert porous["source"] == "seed:bench_r04"  # provenance per entry
    assert porous["measured"]["teff_gbs"] == pytest.approx(989.35)
    # the npt=10 ragged win seeds its own key (npt keys, never tunes)
    assert ("porous_convection3d", (256, 256, 256), 10) in by_key
    assert by_key[("diffusion3d", (512, 512, 512), None)]["config"] == {
        "fused_k": 4, "fused_tile": [32, 128]}
    # what seed wrote is exactly what the committed layer ships
    committed = {os.path.basename(p) for p, _ in
                 tuning.TuneCache(primary=tuning.SEED_DIR,
                                  fallbacks=()).entries()}
    written = {os.path.basename(tune_cache.path_for(e["key"]))
               for e in entries}
    assert written == committed


# -- the tune-cache-valid analyzer --------------------------------------------


def test_tune_cache_valid_analyzer_fires_on_seeded_defects(tmp_path):
    from implicitglobalgrid_tpu.analysis.tunecache import cache_findings

    d = str(tmp_path)
    key = tuning.make_key("diffusion3d", (256, 256, 256), "float32",
                          backend="tpu", topology="t")
    good = tuning.new_entry(key, {"fused_k": 4}, source="test")

    # stale schema
    doc = json.loads(json.dumps(good))
    doc["schema_version"] = 0
    json.dump(doc, open(os.path.join(d, tuning.entry_filename(key)), "w"))
    # corrupt
    open(os.path.join(d, "broken.json"), "w").write("{nope")
    # inadmissible config: the tile does not divide the keyed volume
    # (schema-valid and correctly filed — only the admissibility gate fires)
    key512 = tuning.make_key("diffusion3d", (512, 512, 512), "float32",
                             backend="tpu", topology="t")
    bad = tuning.new_entry(key512, {"fused_k": 4, "fused_tile": [100, 100]},
                           source="test")
    json.dump(bad, open(os.path.join(d, tuning.entry_filename(key512)), "w"))
    # key drift: valid entry under a wrong filename
    json.dump(good, open(os.path.join(d, "drifted.json"), "w"))

    codes = sorted(f.code for f in cache_findings(d))
    assert codes == ["entry-corrupt", "inadmissible-config", "key-drift",
                     "stale-schema"]
    assert all(f.severity == "ERROR" for f in cache_findings(d))


def test_committed_seed_layer_is_clean_and_registered():
    from implicitglobalgrid_tpu.analysis import available_analyzers
    from implicitglobalgrid_tpu.analysis.core import Context
    from implicitglobalgrid_tpu.analysis.tunecache import run

    assert "tune-cache-valid" in available_analyzers()
    assert run(Context()) == []


# -- SPMD consistency: the rank-keyed-lookup fixture --------------------------


def test_control_plan_ignores_rank_identity():
    from implicitglobalgrid_tpu.tuning.search import control_plan

    for hit, n in ((True, 0), (False, 4)):
        plans = {control_plan(is_root=r, hit=hit, n_measured=n)
                 for r in (True, False)}
        assert len(plans) == 1  # rank identity must not shape the schedule
    assert control_plan(True, False, 2) == (
        ("broadcast_control", "cache-decision"),
        ("measure_candidate", 0), ("measure_candidate", 1),
        ("broadcast_control", "winner"),
    )


def test_analyzer_catches_a_rank_keyed_cache_lookup():
    """The POSITIVE fixture of the ISSUE-13 deadlock class: a tuner whose
    ranks each trust their own disk.  Rank 1's local hit skips the
    measurement collectives rank 0 enters — the exact
    `_gather_chunked`-style divergence the collective-consistency detector
    must pin as CRITICAL."""
    from implicitglobalgrid_tpu.analysis.collectives import (
        check_rank_consistency,
        tuning_plan_censuses,
    )
    from implicitglobalgrid_tpu.analysis.core import Context
    from implicitglobalgrid_tpu.analysis.ir import RankCensus
    from implicitglobalgrid_tpu.tuning.search import control_plan

    divergent = RankCensus(
        name="host/tune_resolve[rank-keyed-lookup]",
        sequences={
            0: control_plan(is_root=True, hit=False, n_measured=3),
            1: control_plan(is_root=False, hit=True, n_measured=0),
        },
    )
    findings = check_rank_consistency(divergent)
    assert len(findings) == 1
    f = findings[0]
    assert f.code == "rank-divergent-sequence" and f.severity == "CRITICAL"
    assert "hangs the fabric" in f.message

    # and the REAL resolve's censuses (registered providers) are clean
    for census in tuning_plan_censuses(Context()):
        assert check_rank_consistency(census) == []


# -- the perf-gate wiring -----------------------------------------------------


def test_tuned_speedup_is_gated_and_catches_a_doctored_record():
    from implicitglobalgrid_tpu.analysis import perf

    assert "tuned_speedup" in perf.GATED_KEYS
    ref = {"value": 100.0, "extras": {"tuned_vs_default": {
        "diffusion": {"tuned_speedup": 1.5, "t_default_ms": 3.0},
        "porous": {"tuned_speedup": 2.5},
    }}}
    got = perf.gate_metrics(ref)
    assert got["tuned_vs_default.diffusion.tuned_speedup"] == 1.5
    assert "tuned_vs_default.diffusion.t_default_ms" not in got  # wall time
    # a doctored slower-tuned candidate drops the ratio past the band
    doctored = json.loads(json.dumps(ref))
    doctored["extras"]["tuned_vs_default"]["diffusion"]["tuned_speedup"] = 1.0
    cmp = perf.compare_metrics(perf.gate_metrics(doctored),
                               perf.gate_metrics(ref), waivers=[])
    assert [r["metric"] for r in cmp["regressions"]] == [
        "tuned_vs_default.diffusion.tuned_speedup"]
    # within-band drift passes
    ok = json.loads(json.dumps(ref))
    ok["extras"]["tuned_vs_default"]["diffusion"]["tuned_speedup"] = 1.4
    assert perf.compare_metrics(perf.gate_metrics(ok),
                                perf.gate_metrics(ref),
                                waivers=[])["regressions"] == []


# -- the CLI ------------------------------------------------------------------


@pytest.fixture(scope="module")
def igg_tune_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "igg_tune", os.path.join(_repo, "scripts", "igg_tune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_sweep_dry_run_prints_the_pruned_table(igg_tune_cli, tmp_path,
                                                   monkeypatch, capsys):
    monkeypatch.setenv("IGG_TUNE_CACHE", str(tmp_path))
    rc = igg_tune_cli.main([
        "sweep", "--model", "diffusion3d", "--n", "16", "--nsteps", "4",
        "--overlap", "4", "--dry-run", "--json", "--cache", str(tmp_path),
    ])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["dry_run"] is True and out["winner"] is None
    statuses = {r["status"] for r in out["rows"]}
    assert "survivor" in statuses  # the pruned candidate table, no timing
    assert out["rows"][0]["config"] == {}
    assert os.listdir(str(tmp_path)) == []  # dry run persists NOTHING
    assert not igg.grid_is_initialized()  # the sweep cleans up its grid


def test_cli_sweep_measures_and_persists(igg_tune_cli, tmp_path, monkeypatch,
                                         capsys):
    monkeypatch.setenv("IGG_TUNE_STEPS", "1")
    rc = igg_tune_cli.main([
        "sweep", "--model", "diffusion3d", "--n", "8", "--nsteps", "2",
        "--overlap", "4", "--topk", "2", "--json", "--cache", str(tmp_path),
    ])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["winner"] is not None
    assert any(r.get("t_chunk_s") for r in out["rows"]
               if r["status"] == "measured")
    files = os.listdir(str(tmp_path))
    assert len(files) == 1 and files[0].startswith("diffusion3d_8x8x8")
    # show lists it; clear removes exactly it
    assert igg_tune_cli.main(["show", "--cache", str(tmp_path)]) == 0
    assert "search" in capsys.readouterr().out
    assert igg_tune_cli.main(["clear", "--cache", str(tmp_path)]) == 0
    assert os.listdir(str(tmp_path)) == []


def test_cli_seed_dry_run_matches_committed(igg_tune_cli, tmp_path,
                                            monkeypatch, capsys):
    monkeypatch.setattr(igg_tune_cli, "REPO",
                        os.path.join(_here, "data", "bench_trajectory"))
    rc = igg_tune_cli.main(["seed", "--dry-run", "--json",
                            "--cache", str(tmp_path)])
    entries = json.loads(capsys.readouterr().out)
    assert rc == 0 and len(entries) >= 4
    assert os.listdir(str(tmp_path)) == []  # dry run writes nothing
    assert all(e["source"].startswith("seed:bench_r") for e in entries)
