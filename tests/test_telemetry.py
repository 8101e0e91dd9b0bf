"""Telemetry subsystem tests (docs/observability.md).

Covers the registry semantics (counter/gauge/histogram, disabled-mode
no-op), the JSONL event-log schema round-trip, the per-step metrics every
model's ``run()`` emits (wall time, steps/s, T_eff), the named profiler
annotations landing in compiled-HLO op metadata (the toolchain-independent
stand-in for a live `jax.profiler` capture on this CPU-only environment),
and the Prometheus/JSON exposition of `igg.dump_metrics`.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils import telemetry as tele


@pytest.fixture(autouse=True)
def _fresh_registry():
    tele.reset()
    yield
    tele.reset()


# -- Registry semantics -------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    c = tele.counter("t.count")
    c.inc()
    c.inc(4)
    assert tele.counter("t.count") is c  # one instance per name
    assert c.value == 5

    g = tele.gauge("t.gauge")
    g.set(2.5)
    g.set(7)
    assert tele.gauge("t.gauge").value == 7.0

    h = tele.histogram("t.hist")
    for v in range(1, 101):
        h.record(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["sum"] == pytest.approx(5050.0)
    assert s["mean"] == pytest.approx(50.5)
    assert 40 <= s["p50"] <= 61 and s["p90"] >= s["p50"] and s["p99"] >= s["p90"]

    snap = tele.snapshot()
    assert snap["counters"]["t.count"] == 5
    assert snap["gauges"]["t.gauge"] == 7.0
    assert snap["histograms"]["t.hist"]["count"] == 100


def test_histogram_reservoir_bounded_and_deterministic():
    h = tele.histogram("t.res")
    for v in range(10_000):
        h.record(float(v))
    assert h.count == 10_000
    assert len(h._samples) == tele.RESERVOIR_SIZE
    # Seeded PRNG: the same record sequence yields the same reservoir.
    h2 = tele.Histogram("t.res2")
    for v in range(10_000):
        h2.record(float(v))
    assert h._samples == h2._samples


def test_disabled_mode_takes_zero_allocation_branch(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    assert not tele.enabled()
    # The acceptance contract: disabled accessors return the SHARED no-op
    # singleton (no per-call allocation) and the step loop is None, so the
    # models' loops reduce to one `is not None` check per step.
    assert tele.counter("t.x") is tele.NOOP
    assert tele.gauge("t.y") is tele.NOOP
    assert tele.histogram("t.z") is tele.NOOP
    tele.NOOP.inc()
    tele.NOOP.set(1.0)
    tele.NOOP.record(1.0)
    assert tele.step_loop("m", bytes_per_step=8) is None
    tele.event("t.never", foo=1)
    assert list(tmp_path.iterdir()) == []  # no event file, no registry entry
    snap = tele.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


def test_disabled_model_run_records_nothing(monkeypatch):
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    from implicitglobalgrid_tpu.models import diffusion3d

    diffusion3d.run(1, 8, 8, 8, quiet=True)
    assert tele.snapshot()["counters"] == {}


# -- Compile counters ---------------------------------------------------------


def _compile_counters():
    c = tele.snapshot()["counters"]
    return {k: c.get(k, 0) for k in
            ("compile.backend", "compile.cache_hits", "compile.cache_misses")}


def test_compile_counters_count_backend_compiles():
    """A new program adds one backend compile (and one ``igg.compile`` span
    naming it), a repeat call adds none; the counting survives a reset."""
    from implicitglobalgrid_tpu.utils import tracing

    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 - 1.0)
    assert tele.enabled()
    tele.reset()
    tracing.reset()
    f(x)
    assert _compile_counters()["compile.backend"] == 1
    assert tele.snapshot()["counters"]["compile.backend_seconds"] > 0
    f(x)
    assert _compile_counters()["compile.backend"] == 1
    (span,) = [r for r in tracing.span_records() if r["name"] == "igg.compile"]
    assert "lambda" in span["args"]["fun_name"] and span["dur"] > 0
    tele.reset()  # the listener stays: the next program still counts
    jax.jit(lambda v: v * 4.0)(x)
    assert _compile_counters()["compile.backend"] == 1
    tracing.reset()


def test_compile_cache_miss_then_hit(tmp_path):
    """On a fresh persistent compile cache, a program is one miss (compiled
    and written), then — in-memory caches cleared — one hit."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    x = jnp.arange(5.0)
    f = jax.jit(lambda v: v * 5.0 + 2.0)
    try:
        for k, v in zip(keys, (True, str(tmp_path), 0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        assert tele.enabled()
        tele.reset()
        f(x)
        assert _compile_counters() == {
            "compile.backend": 1, "compile.cache_hits": 0,
            "compile.cache_misses": 1}
        jax.clear_caches()
        f(x)
        assert _compile_counters() == {
            "compile.backend": 2, "compile.cache_hits": 1,
            "compile.cache_misses": 1}
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc.reset_cache()


# -- Event log ----------------------------------------------------------------


def test_event_jsonl_schema_roundtrip(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    import time

    t0 = time.time()
    tele.event("unit.test", step=3, detail="abc")
    tele.event("unit.test2", nested={"a": 1})
    path = tmp_path / "events.jsonl"  # single process = rank 0
    assert path.is_file()
    events = tele.read_events(path)
    assert [e["type"] for e in events] == ["unit.test", "unit.test2"]
    e = events[0]
    # Schema: absolute timestamp, rank/pid/coords tags, payload verbatim.
    assert {"ts", "type", "rank", "pid", "coords"} <= set(e)
    assert t0 <= e["ts"] <= time.time()
    assert e["rank"] == 0 and e["pid"] == os.getpid()
    assert e["step"] == 3 and e["detail"] == "abc"
    assert events[1]["nested"] == {"a": 1}
    # Append-only: a second emitter call extends, never truncates.
    tele.event("unit.test3")
    assert len(tele.read_events(path)) == 3


def test_event_coords_tagged_when_grid_up(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    tele.event("before.grid")
    igg.init_global_grid(8, 8, 8, quiet=True)
    tele.event("with.grid")
    events = tele.read_events(tmp_path / "events.jsonl")
    assert events[0]["coords"] is None
    assert events[1]["coords"] == list(igg.get_global_grid().coords)


def test_event_rank_hint_during_bringup(monkeypatch, tmp_path):
    """Bring-up events (before the runtime can answer process_index) must be
    tagged and FILED under the rank `init_distributed` staged via
    `set_rank_hint` — not misattributed to rank 0 (code-review finding)."""
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    tele.set_rank_hint(3)
    tele.event("bringup.retry")
    path = tmp_path / "events.p3.jsonl"
    assert path.is_file()
    (e,) = tele.read_events(path)
    assert e["rank"] == 3
    tele.reset()  # reset drops the hint with the registry
    tele.event("after.reset")
    (e2,) = tele.read_events(tmp_path / "events.jsonl")
    assert e2["rank"] == 0


def test_watchdog_deadline_exceeded_event(monkeypatch, tmp_path):
    """A watchdog scope outliving its deadline leaves the timeline marker
    (the observable proxy for the faulthandler dump)."""
    import time as _time

    from implicitglobalgrid_tpu.utils.resilience import watchdog

    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    with watchdog(0.05):
        _time.sleep(0.12)
    events = tele.read_events(tmp_path / "events.jsonl")
    (e,) = [x for x in events if x["type"] == "watchdog.deadline_exceeded"]
    assert e["elapsed_s"] > e["timeout_s"] == 0.05
    snap = tele.snapshot()
    assert snap["counters"]["resilience.watchdog_deadline_exceeded"] == 1


def test_event_non_serializable_payload_stringified(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    tele.event("odd.payload", obj=object())
    (e,) = tele.read_events(tmp_path / "events.jsonl")
    assert "object object" in e["obj"]


# -- Per-step metrics from the models' run loops ------------------------------


@pytest.mark.parametrize(
    "model_name,run_kwargs,nt",
    [
        ("diffusion3d", {}, 3),
        ("acoustic3d", {}, 2),
        ("porous_convection3d", {"npt": 2}, 1),
    ],
)
def test_model_run_emits_per_step_metrics(model_name, run_kwargs, nt):
    import importlib

    mod = importlib.import_module(
        f"implicitglobalgrid_tpu.models.{model_name}"
    )
    mod.run(nt, 8, 8, 8, quiet=True, **run_kwargs)
    snap = tele.snapshot()
    assert snap["counters"][f"{model_name}.steps"] == nt
    step_s = snap["histograms"][f"{model_name}.step_seconds"]
    assert step_s["count"] == nt and step_s["min"] > 0
    teff = snap["histograms"][f"{model_name}.t_eff_gbs"]
    assert teff["count"] == nt and teff["min"] > 0
    assert snap["gauges"][f"{model_name}.steps_per_s"] > 0


def test_heartbeat_line_and_event(monkeypatch, tmp_path, capfd):
    monkeypatch.setenv("IGG_HEARTBEAT_EVERY", "1")
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    from implicitglobalgrid_tpu.models import diffusion3d

    diffusion3d.run(2, 8, 8, 8, quiet=True)
    err = capfd.readouterr().err
    assert "[igg.telemetry] diffusion3d step" in err
    assert "T_eff" in err
    events = tele.read_events(tmp_path / "events.jsonl")
    types = [e["type"] for e in events]
    assert types.count("heartbeat") == 2
    # init emits the clock-sync anchor first (utils.tracing), then the run
    assert types[0] == "clock.sync"
    assert types[1] == "run.start" and types[-1] == "run.complete"
    hb = next(e for e in events if e["type"] == "heartbeat")
    assert hb["model"] == "diffusion3d" and hb["t_eff_gbs"] > 0
    # single-process run: no skew probe ran and no serving pool exists, so
    # the extended context attaches neither section (absence is explicit)
    assert "skew" not in hb and "serving" not in hb


def test_teff_bytes_model():
    igg.init_global_grid(8, 8, 8, quiet=True)
    T = igg.zeros((8, 8, 8), "float32")
    V = igg.zeros((9, 8, 8), "float32")
    # 2 * sum(global nbytes): each must-stream field once in + once out.
    assert tele.teff_bytes([T]) == 2 * T.nbytes
    assert tele.teff_bytes([T, V]) == 2 * (T.nbytes + V.nbytes)


# -- Instrumented hot paths ---------------------------------------------------


def test_update_halo_counters():
    igg.init_global_grid(
        8, 8, 8, periodx=1, overlapx=4, overlapy=4, overlapz=4, quiet=True
    )
    T = igg.zeros((8, 8, 8), "float64")
    T = igg.update_halo(T)
    T = igg.update_halo(T, width=2)
    snap = tele.snapshot()
    assert snap["counters"]["halo.exchanges"] == 2
    assert snap["counters"]["halo.fields"] == 2
    # Slab payload model: all three dims are active on the default 2x2x2
    # mesh (periodic x + interior neighbors), 2 slabs/dim of 8*8 f64 planes;
    # the width-2 call moves twice the width-1 call's bytes.
    per_plane = 8 * 8 * 8  # elements * itemsize
    w1 = 3 * 2 * per_plane
    assert snap["counters"]["halo.bytes"] == w1 + 2 * w1
    assert snap["histograms"]["halo.slab_bytes"]["count"] == 2


def test_gather_registry_fold():
    from implicitglobalgrid_tpu.ops import gather as gather_mod

    igg.init_global_grid(8, 8, 8, quiet=True)
    A = igg.zeros((8, 8, 8), "float32")
    got = igg.gather(A)
    assert got is not None
    snap = tele.snapshot()
    assert snap["counters"]["gather.calls"] == 1
    assert snap["counters"]["gather.calls.local"] == 1
    assert snap["counters"]["gather.host_bytes"] == got.nbytes
    # The compat alias mirrors the registry's last-call view.
    assert gather_mod.last_gather_stats["path"] == "local"


def test_checkpoint_events_and_counters(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path / "tele"))
    igg.init_global_grid(8, 8, 8, quiet=True)
    T = igg.zeros((8, 8, 8), "float32")
    ckdir = tmp_path / "ck"
    path = igg.save_checkpoint(ckdir, (T,), 2)
    igg.restore_checkpoint(path, like=(T,))
    igg.save_checkpoint(ckdir, (T,), 4)
    igg.prune_checkpoints(ckdir, keep=1)
    snap = tele.snapshot()
    assert snap["counters"]["checkpoint.saves"] == 2
    assert snap["counters"]["checkpoint.restores"] == 1
    assert snap["counters"]["checkpoint.prunes"] == 1
    events = tele.read_events(tmp_path / "tele" / "events.jsonl")
    types = [e["type"] for e in events]
    # the init-time clock-sync anchor leads, then the checkpoint sequence
    assert types == [
        "clock.sync",
        "checkpoint.saved",
        "checkpoint.restore",
        "checkpoint.saved",
        "checkpoint.prune",
    ]
    restore = next(e for e in events if e["type"] == "checkpoint.restore")
    assert restore["mode"] == "same_topology" and restore["step"] == 2


def test_corrupt_checkpoint_fallback_event(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path / "tele"))
    igg.init_global_grid(8, 8, 8, quiet=True)
    T = igg.zeros((8, 8, 8), "float32")
    ckdir = tmp_path / "ck"
    igg.save_checkpoint(ckdir, (T,), 2)
    newest = igg.save_checkpoint(ckdir, (T,), 4)
    shard = os.path.join(newest, "shards_p0.npz")
    with open(shard, "r+b") as f:
        f.seek(10)
        f.write(b"\xff")
    latest = igg.latest_checkpoint(ckdir)
    assert latest.endswith("step_00000002")
    events = tele.read_events(tmp_path / "tele" / "events.jsonl")
    fb = [e for e in events if e["type"] == "checkpoint.fallback"]
    assert fb and "corrupt" in fb[0]["problem"]
    assert tele.snapshot()["counters"]["checkpoint.fallbacks"] >= 1


# -- Named profiler annotations ----------------------------------------------
#
# This toolchain cannot run a TPU profiler capture; the toolchain-
# independent check (the ISSUE's jaxpr-level fallback) is that the
# `named_scope` names land in the compiled executable's op metadata — the
# exact strings a Perfetto trace groups ops under.


def test_pipelined_schedule_scopes_in_compiled_hlo():
    from implicitglobalgrid_tpu.models._fused import (
        run_pipelined_group_schedule,
    )

    def boundary(ki, c):
        return (c * 2.0,), ["pend"]

    def interior(ki, c, b_out, pend):
        return jnp.sin(b_out[0]) + c

    def f(x):
        return run_pipelined_group_schedule([1, 1], boundary, interior, x)

    txt = jax.jit(f).lower(jnp.ones((8,))).compile().as_text()
    assert "igg_ring_pass" in txt
    assert "igg_interior_pass" in txt


def test_slab_exchange_scopes_in_compiled_hlo():
    from jax.sharding import PartitionSpec as P

    from implicitglobalgrid_tpu.ops.halo import (
        begin_slab_exchange,
        finish_slab_exchange,
    )
    from jax import shard_map

    igg.init_global_grid(8, 8, 8, periodx=1, quiet=True)
    gg = igg.get_global_grid()

    def local(T):
        pends = begin_slab_exchange((T,), (0, 1, 2), width=1)
        (T,) = finish_slab_exchange((T,), pends)
        return T

    mapped = shard_map(
        local,
        mesh=gg.mesh,
        in_specs=(P("x", "y", "z"),),
        out_specs=P("x", "y", "z"),
        check_vma=False,
    )
    T = igg.zeros((8, 8, 8), "float32")
    txt = jax.jit(mapped).lower(T).compile().as_text()
    assert "igg_slab_exchange_begin" in txt
    assert "igg_slab_exchange_finish" in txt
    # Trace-time counters: one begin/finish schedule was traced.
    snap = tele.snapshot()
    assert snap["counters"]["halo.begin_slab_traces"] == 1
    assert snap["counters"]["halo.finish_slab_traces"] == 1


# -- Public surface: snapshot + dumps -----------------------------------------


def test_dump_metrics_json_and_prometheus(tmp_path):
    tele.counter("d.count").inc(3)
    tele.gauge("d.gauge").set(1.5)
    h = tele.histogram("d.hist")
    for v in (1.0, 2.0, 3.0):
        h.record(v)
    json_path, prom_path = igg.dump_metrics(tmp_path / "metrics")
    with open(json_path) as f:
        snap = json.load(f)
    assert snap["counters"]["d.count"] == 3
    assert snap["histograms"]["d.hist"]["count"] == 3
    prom = open(prom_path).read()
    assert "# TYPE igg_d_count_total counter" in prom
    assert "igg_d_count_total 3" in prom
    assert "# TYPE igg_d_gauge gauge" in prom
    assert "# TYPE igg_d_hist summary" in prom
    assert 'igg_d_hist{quantile="0.5"} 2.0' in prom
    assert "igg_d_hist_sum 6.0" in prom and "igg_d_hist_count 3" in prom
    # Every sample line is `name[{labels}] value` with a numeric value.
    for line in prom.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(None, 1)
        assert name.startswith("igg_")
        float(value)


def test_snapshot_is_json_serializable():
    tele.counter("s.c").inc()
    tele.histogram("s.h").record(0.25)
    igg.init_global_grid(8, 8, 8, quiet=True)
    snap = igg.telemetry_snapshot()
    rt = json.loads(json.dumps(snap))
    assert rt["counters"]["s.c"] == 1
    assert rt["coords"] == list(igg.get_global_grid().coords)


# -- Batched serving metrics + event schema (ISSUE 8) -------------------------


def test_serving_metrics_and_event_schema(monkeypatch, tmp_path):
    """The serving loop's observability contract (docs/observability.md):
    ``serving.active_members`` tracks the pool live, the retire family of
    counters splits by outcome, per-member T_eff is recorded per round,
    per-tenant step counters accumulate, and every ``serving.*`` event is
    tagged with member/slot/tenant."""
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path / "tele"))
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.serving import Request, ServingLoop

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    _, params = diffusion3d.setup(8, 8, 8, init_grid=False)
    loop = ServingLoop(diffusion3d, params, capacity=2, steps_per_round=1)

    def req(scale, steps, tenant):
        s, _ = diffusion3d.setup(8, 8, 8, init_grid=False, ic_scale=scale)
        return Request(state=s, max_steps=steps, tenant=tenant)

    loop.submit(req(1.0, 2, "alice"))
    loop.submit(req(1.1, 1, "bob"))
    snap = tele.snapshot()
    assert snap["gauges"]["serving.active_members"] == 2
    loop.run(max_rounds=10)

    snap = tele.snapshot()
    c = snap["counters"]
    assert c["serving.admitted_total"] == 2
    assert c["serving.retired_total"] == 2
    assert c.get("serving.evicted_total", 0) == 0
    assert c["serving.tenant.alice.steps"] == 2
    assert c["serving.tenant.bob.steps"] == 1
    assert c["serving.rounds"] == loop.rounds
    assert snap["gauges"]["serving.active_members"] == 0
    # per-member T_eff tagging: one histogram sample per active member per
    # round (round 1: both members, round 2: alice alone)
    assert snap["histograms"]["serving.member_t_eff_gbs"]["count"] == 3

    events = tele.read_events(tmp_path / "tele" / "events.jsonl")
    serving = [e for e in events if e["type"].startswith("serving.")]
    assert {e["type"] for e in serving} == {"serving.admit",
                                           "serving.retire"}
    for e in serving:
        assert {"member", "slot", "tenant"} <= set(e), e
    retires = [e for e in serving if e["type"] == "serving.retire"]
    assert {e["tenant"] for e in retires} == {"alice", "bob"}
    assert all(e["status"] == "completed" for e in retires)


def test_serving_disabled_telemetry_is_noop(monkeypatch):
    """``IGG_TELEMETRY=0``: the loop still serves, nothing is recorded."""
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.serving import Request, ServingLoop

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    s, params = diffusion3d.setup(8, 8, 8, init_grid=False)
    loop = ServingLoop(diffusion3d, params, capacity=1, steps_per_round=1)
    m = loop.submit(Request(state=s, max_steps=1, tenant="x"))
    res = loop.run(max_rounds=5)
    assert res[m].status == "completed"
    monkeypatch.delenv("IGG_TELEMETRY")
    assert tele.snapshot()["counters"] == {}


def test_gather_member_counter_folds_into_gather_family(monkeypatch):
    from implicitglobalgrid_tpu.models import _batched

    igg.init_global_grid(8, 8, 8, quiet=True)
    A = igg.zeros((8, 8, 8), "float32")
    B = _batched.stack_fields(A, A)
    got = igg.gather(B, member=1)
    assert got is not None and got.shape == (16, 16, 16)  # dims (2,2,2)
    snap = tele.snapshot()
    assert snap["counters"]["gather.member_calls"] == 1
    assert snap["counters"]["gather.calls"] == 1  # the slice gather itself


# -- Tenant-series cardinality cap (ISSUE 10 satellite) -----------------------


def test_tenant_counter_caps_distinct_series(monkeypatch):
    """Tenant strings arrive from requests: the per-tenant counter family
    must stay bounded.  Past ``IGG_TELEMETRY_MAX_TENANTS`` distinct
    tenants, new ones fold into ``serving.tenant.__other__.steps`` while
    existing tenants keep their own series — and the family's TOTAL stays
    exact."""
    monkeypatch.setenv("IGG_TELEMETRY_MAX_TENANTS", "2")
    tele.tenant_counter("alice").inc(3)
    tele.tenant_counter("bob").inc(2)
    # cap reached: carol and dave fold into the overflow series
    tele.tenant_counter("carol").inc(5)
    tele.tenant_counter("dave").inc(7)
    # existing tenants keep attributing to their own series
    tele.tenant_counter("alice").inc(1)
    c = tele.snapshot()["counters"]
    tenant_keys = {k for k in c if k.startswith("serving.tenant.")}
    assert tenant_keys == {
        "serving.tenant.alice.steps",
        "serving.tenant.bob.steps",
        tele.TENANT_OVERFLOW,
    }
    assert c["serving.tenant.alice.steps"] == 4
    assert c["serving.tenant.bob.steps"] == 2
    assert c[tele.TENANT_OVERFLOW] == 12
    assert sum(c[k] for k in tenant_keys) == 18  # nothing lost to the cap


def test_tenant_counter_default_cap_and_disabled(monkeypatch):
    monkeypatch.delenv("IGG_TELEMETRY_MAX_TENANTS", raising=False)
    for i in range(tele.MAX_TENANTS_DEFAULT + 5):
        tele.tenant_counter(f"t{i}").inc()
    c = tele.snapshot()["counters"]
    distinct = [
        k for k in c
        if k.startswith("serving.tenant.") and k != tele.TENANT_OVERFLOW
    ]
    assert len(distinct) == tele.MAX_TENANTS_DEFAULT
    assert c[tele.TENANT_OVERFLOW] == 5
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    assert tele.tenant_counter("x") is tele.NOOP


def test_serving_loop_tenant_flood_stays_bounded(monkeypatch):
    """Regression: the serving loop's per-tenant counters ride
    `tenant_counter`, so a flood of one-request tenants cannot grow the
    registry unboundedly."""
    monkeypatch.setenv("IGG_TELEMETRY_MAX_TENANTS", "3")
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.serving import Request, ServingLoop

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, quiet=True)
    s, params = diffusion3d.setup(8, 8, 8, init_grid=False)
    loop = ServingLoop(diffusion3d, params, capacity=2, steps_per_round=1)
    for i in range(6):
        si, _ = diffusion3d.setup(8, 8, 8, init_grid=False,
                                  ic_scale=1.0 + 0.01 * i)
        loop.submit(Request(state=si, max_steps=1, tenant=f"tenant{i}"))
    res = loop.run(max_rounds=20)
    assert len(res) == 6
    c = tele.snapshot()["counters"]
    tenant_keys = [k for k in c if k.startswith("serving.tenant.")]
    assert len(tenant_keys) <= 4  # 3 distinct + __other__
    assert sum(c[k] for k in tenant_keys) == 6  # every step attributed


# -- Prometheus exposition edge cases (ISSUE 10 satellite) --------------------


def _parse_prometheus(text: str) -> dict:
    """Minimal text-format (0.0.4) parser for the round-trip check:
    ``{metric name: {"type": ..., "samples": {sample name+labels: value}}}``.
    Samples attach to the preceding ``# TYPE`` block and must belong to it
    (name prefix match) — raises on anything a standard scraper would
    reject (sample before its header, duplicate headers, non-numeric
    value, malformed line)."""
    out: dict = {}
    current = None
    for line in text.splitlines():
        if not line.strip():
            raise ValueError("blank line in exposition")
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split()
            if name in out:
                raise ValueError(f"duplicate TYPE for {name}")
            out[name] = {"type": mtype, "samples": {}}
            current = name
            continue
        if line.startswith("#"):
            continue
        parts = line.rsplit(None, 1)
        if len(parts) != 2:
            raise ValueError(f"malformed sample line {line!r}")
        name_labels, value = parts
        if current is None or not name_labels.startswith(current):
            raise ValueError(f"sample outside its TYPE block: {line!r}")
        out[current]["samples"][name_labels] = float(value)
    return out


def test_prometheus_name_sanitization_edge_cases():
    # dots, hyphens and a LEADING DIGIT: all must sanitize to a valid
    # Prometheus name (the igg_ prefix also rescues the leading digit).
    tele.counter("9starts.with-digit").inc(2)
    tele.gauge("weird-gauge.name-x").set(1.0)
    text = tele.prometheus_text()
    parsed = _parse_prometheus(text)
    assert "igg_9starts_with_digit_total" in parsed
    assert parsed["igg_9starts_with_digit_total"]["type"] == "counter"
    assert "igg_weird_gauge_name_x" in parsed
    import re

    for name in parsed:
        assert re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name), name


def test_prometheus_empty_histogram_exposition():
    tele.histogram("h.empty")  # created, never recorded
    text = tele.prometheus_text()
    parsed = _parse_prometheus(text)
    h = parsed["igg_h_empty"]
    assert h["type"] == "summary"
    # no quantile lines (the reservoir is empty), but sum/count present
    assert h["samples"] == {"igg_h_empty_sum": 0.0, "igg_h_empty_count": 0.0}
    assert "None" not in text


def test_prometheus_roundtrip_against_snapshot():
    tele.counter("rt.count").inc(7)
    tele.gauge("rt.gauge").set(-2.5)
    h = tele.histogram("rt.hist")
    for v in (1.0, 2.0, 4.0):
        h.record(v)
    snap = tele.snapshot()
    parsed = _parse_prometheus(tele.prometheus_text(snap))
    assert parsed["igg_rt_count_total"]["samples"]["igg_rt_count_total"] == 7.0
    assert parsed["igg_rt_gauge"]["samples"]["igg_rt_gauge"] == -2.5
    hs = parsed["igg_rt_hist"]["samples"]
    assert hs["igg_rt_hist_sum"] == 7.0
    assert hs["igg_rt_hist_count"] == 3.0
    assert hs['igg_rt_hist{quantile="0.5"}'] == snap["histograms"]["rt.hist"]["p50"]
    # every registry metric surfaced exactly once
    assert len(parsed) == 3


# -- Enriched heartbeat (ISSUE 10 satellite) ----------------------------------


def test_heartbeat_attaches_skew_and_serving_context(monkeypatch, tmp_path):
    """docs/observability.md heartbeat schema: when the skew gauges and
    the serving occupancy gauges exist, the rank-0 heartbeat event carries
    them; when they don't, the sections are absent (pinned by
    test_heartbeat_line_and_event)."""
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("IGG_HEARTBEAT_EVERY", "1")
    # a straggler probe and a serving pool published earlier this process
    tele.gauge("skew.step_seconds_max_over_min").set(3.5)
    tele.gauge("skew.slowest_rank").set(1)
    tele.gauge("serving.active_members").set(2)
    tele.gauge("serving.queue_depth").set(4)
    loop = tele.step_loop("m", bytes_per_step=8, total_steps=1)
    loop.on_step(1)
    events = tele.read_events(tmp_path / "events.jsonl")
    hb = next(e for e in events if e["type"] == "heartbeat")
    assert hb["skew"] == {
        "step_seconds_max_over_min": 3.5,
        "slowest_rank": 1.0,
    }
    assert hb["serving"] == {"active_members": 2.0, "queue_depth": 4.0}


# -- Rolling SLO windows (ISSUE 11) -------------------------------------------


def test_histogram_rolling_windows(monkeypatch):
    monkeypatch.setenv("IGG_SLO_WINDOW_S", "10")
    h = tele.histogram("w.hist")
    # window 1: [t=0, 10)
    for v in (1.0, 2.0, 3.0):
        h.record(v, now=0.0)
    w = h.window_summary(now=5.0)
    assert w["count"] == 3 and w["window_s"] == 10.0 and w["windows"] == 1
    assert w["p50"] == 2.0
    # window 2 opens at t=12: the old window slides into the ring
    h.record(100.0, now=12.0)
    w = h.window_summary(now=12.0)
    assert w["count"] == 4 and w["windows"] == 2
    assert w["p99"] == 100.0
    # beyond the horizon (SLO_WINDOWS * 10s) old windows fall out...
    w = h.window_summary(now=12.1 + tele.SLO_WINDOWS * 10)
    assert w is None
    # ...while the LIFETIME reservoir keeps everything
    s = h.summary()
    assert s["count"] == 4 and s["max"] == 100.0


def test_window_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("IGG_SLO_WINDOW_S", "1")
    h = tele.histogram("w.ring")
    for i in range(20):  # 20 windows, ring keeps SLO_WINDOWS
        h.record(float(i), now=float(i))
    assert len(h._win_ring) == tele.SLO_WINDOWS - 1
    w = h.window_summary(now=19.0)
    # the live view spans only the last SLO_WINDOWS windows' samples
    assert w["count"] == tele.SLO_WINDOWS
    assert w["p50"] == float(19 - tele.SLO_WINDOWS // 2)


def test_windows_absent_until_first_record_and_when_disabled(monkeypatch):
    h = tele.histogram("w.lazy")
    assert h._win_cur is None and h._win_ring is None  # lazy allocation
    assert h.window_summary() is None
    assert "window" not in h.summary()
    monkeypatch.setenv("IGG_TELEMETRY", "0")
    # the disabled-mode singleton allocates nothing — no windows anywhere
    noop = tele.histogram("w.never")
    assert noop is tele.NOOP
    noop.record(1.0, now=0.0)
    assert "w.never" not in tele.snapshot()["histograms"]


def test_concurrent_scrape_hammer():
    """ISSUE 11 satellite: a reader thread snapshots/renders the exposition
    in a tight loop while the main thread records — the exact
    /metrics-during-step-loop interleaving.  Any exception on either side
    (or a torn histogram summary) fails the pin."""
    import threading

    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                snap = tele.snapshot()
                text = tele.prometheus_text(snap)
                for name, s in snap["histograms"].items():
                    # invariants a torn read would break
                    assert s["count"] >= 0
                    if s["count"]:
                        assert s["min"] <= s["max"]
                assert text.endswith("\n")
        except Exception as e:  # pragma: no cover - the failure path
            errors.append(e)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    h = tele.histogram("hammer.hist")
    c = tele.counter("hammer.count")
    g = tele.gauge("hammer.gauge")
    for i in range(3000):
        h.record(float(i % 97))
        c.inc()
        g.set(float(i))
        if i % 500 == 0:
            tele.histogram(f"hammer.h{i}").record(1.0)  # registry growth
    stop.set()
    t.join(timeout=10)
    assert not t.is_alive()
    assert errors == []
    snap = tele.snapshot()
    assert snap["counters"]["hammer.count"] == 3000
    assert snap["histograms"]["hammer.hist"]["count"] == 3000


# -- proc RSS gauge (ISSUE 11 satellite) --------------------------------------


def test_proc_rss_bytes_reads_something():
    rss = tele.proc_rss_bytes()
    # Linux CI: /proc/self/statm must resolve; a python process with jax
    # loaded sits far above 10 MB
    assert rss is not None and rss > 10 * 1024 * 1024


def test_heartbeat_publishes_rss_gauge(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_HEARTBEAT_EVERY", "1")
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    loop = tele.step_loop("m", bytes_per_step=8, total_steps=1)
    loop.on_step(1)
    assert tele.snapshot()["gauges"]["proc.rss_bytes"] > 0


# -- progress record (the live plane's last-step-age source) ------------------


def test_note_progress_roundtrip():
    assert tele.last_progress() is None
    tele.note_progress("m", 0, init=True)
    p = tele.last_progress()
    assert p["init"] and not p["done"] and p["step"] == 0
    tele.note_progress("m", 3)
    p = tele.last_progress()
    assert not p["init"] and p["step"] == 3 and p["age_s"] >= 0
    tele.note_progress("m", 3, done=True)
    assert tele.last_progress()["done"]
    tele.reset()
    assert tele.last_progress() is None


def test_step_loop_progress_lifecycle(monkeypatch, tmp_path):
    monkeypatch.setenv("IGG_TELEMETRY_DIR", str(tmp_path))
    loop = tele.step_loop("m", total_steps=2)
    assert tele.last_progress()["init"]  # bring-up/compile phase marked
    loop.on_step(1)
    p = tele.last_progress()
    assert p["step"] == 1 and not p["init"] and not p["done"]
    loop.finish(2)
    assert tele.last_progress()["done"]
