"""Seeded fixtures for the `igg.analysis` suite (docs/static-analysis.md).

Each analyzer is pinned BOTH ways: a deliberately-broken fixture it must
fire on (a rank-divergent collective, a knob read inside jit, a bogus
alias, a malformed perm), and a clean twin it must stay quiet on — an
analyzer that cannot tell the two apart is a broken lint, not a clean
tree.  The framework itself (fingerprints, baseline workflow, changed-only
selection, exit codes) is tested here too; the real package's full-suite
run lives in `tests/test_lint_suite.py`.
"""

import json
import os
import sys
import textwrap

import pytest

from implicitglobalgrid_tpu.analysis import core
from implicitglobalgrid_tpu.analysis.core import (
    Baseline,
    Context,
    Finding,
    Report,
    select_for_paths,
)


def _fixture_ctx(tmp_path, sources: dict) -> Context:
    """A Context whose package root is a throwaway package built from
    ``{relative path: source}`` — the AST passes scan it instead of the
    real package."""
    pkg = tmp_path / "fixture_pkg"
    for rel, src in sources.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Context(repo_root=str(tmp_path), package_root=str(pkg))


# -- framework: Finding / fingerprints ---------------------------------------


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError, match="severity"):
        Finding(analyzer="a", code="c", severity="FATAL", message="m")


def test_fingerprint_survives_message_and_line_drift():
    a = Finding(analyzer="a", code="c", severity="ERROR", message="old",
                path="p.py", line=10, symbol="f", anchor="K")
    b = Finding(analyzer="a", code="c", severity="ERROR", message="reworded",
                path="p.py", line=99, symbol="f", anchor="K")
    c = Finding(analyzer="a", code="c", severity="ERROR", message="old",
                path="p.py", line=10, symbol="f", anchor="OTHER")
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != c.fingerprint


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(
        {"suppressions": [{"fingerprint": "abc", "justification": "  "}]}
    ))
    with pytest.raises(ValueError, match="justification"):
        Baseline.load(str(path))
    path.write_text(json.dumps(
        {"suppressions": [{"fingerprint": "abc",
                           "justification": "documented contract"}]}
    ))
    base = Baseline.load(str(path))
    f = Finding(analyzer="a", code="c", severity="ERROR", message="m")
    assert base.match(f) is None
    assert "abc" in base.suppressions


def test_shipped_baseline_is_well_formed():
    base = Baseline.load(core.DEFAULT_BASELINE)
    assert base.suppressions, "the shipped baseline lost its entries"
    for entry in base.suppressions.values():
        assert len(entry["justification"]) > 40  # a reason, not a mute


def test_report_exit_codes():
    err = Finding(analyzer="a", code="c", severity="ERROR", message="m")
    warn = Finding(analyzer="a", code="c", severity="WARNING", message="m")
    assert Report().exit_code() == 0
    assert Report(findings=[warn]).exit_code() == 0
    assert Report(findings=[warn]).exit_code(strict=True) == 1
    assert Report(findings=[err]).exit_code() == 1
    assert Report(errors={"a": "boom"}).exit_code() == 2


# -- framework: runner + baseline + changed-only ------------------------------


def _register_fake_analyzer(tmp_path, monkeypatch, body: str,
                            modname: str = "igg_fake_pass"):
    """Install a one-analyzer registry whose pass is ``body`` (a module
    defining ``run(ctx)``), returning its name.  ``modname`` must be
    unique per test — `AnalyzerSpec.load` goes through the import cache."""
    mod = tmp_path / f"{modname}.py"
    mod.write_text(textwrap.dedent(body))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.delitem(sys.modules, modname, raising=False)
    spec = core.AnalyzerSpec(
        name="fake", module=modname, func="run", title="fixture",
        paths=("implicitglobalgrid_tpu/ops/**",),
    )
    monkeypatch.setattr(core, "REGISTRY", {"fake": spec})
    return "fake"


_FAKE_PASS = """
    from implicitglobalgrid_tpu.analysis.core import Finding

    def run(ctx):
        yield Finding(analyzer="fake", code="seeded", severity="ERROR",
                      message="seeded finding", symbol="s", anchor="a")
"""


def test_run_reports_and_baselines_and_flags_stale(tmp_path, monkeypatch):
    _register_fake_analyzer(tmp_path, monkeypatch, _FAKE_PASS)
    report = core.run(baseline=None)
    assert [f.code for f in report.findings] == ["seeded"]
    assert report.exit_code() == 1

    fp = report.findings[0].fingerprint
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"suppressions": [
        {"fingerprint": fp, "justification": "seeded fixture, intentional"},
        {"fingerprint": "dead0000dead0000",
         "justification": "left over from a removed pass"},
    ]}))
    report = core.run(baseline=str(base))
    assert report.findings == []
    assert report.exit_code() == 0  # suppressed + stale do not fail
    assert [f.fingerprint for f, _ in report.suppressed] == [fp]
    assert report.stale_suppressions == ["dead0000dead0000"]
    assert "matched no finding" in report.human()


def test_run_changed_only_selects_by_declared_paths(tmp_path, monkeypatch):
    _register_fake_analyzer(tmp_path, monkeypatch, _FAKE_PASS)
    hit = core.run(baseline=None,
                   changed_paths=["implicitglobalgrid_tpu/ops/halo.py"])
    assert hit.ran == ["fake"] and len(hit.findings) == 1
    miss = core.run(baseline=None, changed_paths=["docs/usage.md"])
    assert miss.ran == [] and miss.skipped == ["fake"]
    assert miss.findings == []


def test_run_keep_going_traps_analyzer_crashes(tmp_path, monkeypatch):
    _register_fake_analyzer(
        tmp_path, monkeypatch,
        "def run(ctx):\n    raise RuntimeError('boom')\n",
        modname="igg_fake_crashing_pass",
    )
    with pytest.raises(RuntimeError, match="boom"):
        core.run(baseline=None)
    report = core.run(baseline=None, keep_going=True)
    assert "RuntimeError: boom" in report.errors["fake"]
    assert report.exit_code() == 2


def test_run_rejects_unknown_analyzer():
    with pytest.raises(ValueError, match="unknown analyzer"):
        core.run(["no-such-pass"])


def test_changed_only_selection_of_the_real_registry():
    # Framework changes select everything; subsystem paths select their
    # declared analyzers; unrelated paths select nothing.
    assert set(select_for_paths(["scripts/igg_lint.py"])) == set(core.REGISTRY)
    ops = select_for_paths(["implicitglobalgrid_tpu/ops/halo.py"])
    assert "collective-consistency" in ops and "collective-budget" in ops
    docs = select_for_paths(["docs/usage.md"])
    assert docs == ["knob-decl"]
    assert select_for_paths(["README.md"]) == []


# -- collective-consistency: rank census --------------------------------------


def _census(sequences):
    from implicitglobalgrid_tpu.analysis.ir import RankCensus

    return RankCensus(name="fixture", sequences=sequences)


def test_divergence_detector_fires_on_rank_divergent_collective():
    from implicitglobalgrid_tpu.analysis.collectives import (
        check_rank_consistency,
    )

    op_a = ("ppermute", ("x",), ("f32[8]",))
    op_b = ("psum", ("x",), ("f32[8]",))
    # rank 1 swaps the op kind at position 1 — the deadlock class
    found = check_rank_consistency(
        _census({0: (op_a, op_b), 1: (op_a, op_a)})
    )
    assert [f.code for f in found] == ["rank-divergent-sequence"]
    assert found[0].severity == "CRITICAL"
    assert "op 1" in found[0].message


def test_divergence_detector_fires_on_sequence_length_mismatch():
    from implicitglobalgrid_tpu.analysis.collectives import (
        check_rank_consistency,
    )

    op = ("ppermute", ("x",), ("f32[8]",))
    found = check_rank_consistency(_census({0: (op, op), 1: (op,)}))
    assert len(found) == 1
    assert "2 collective(s)" in found[0].message


def test_divergence_detector_quiet_on_identical_sequences():
    from implicitglobalgrid_tpu.analysis.collectives import (
        check_rank_consistency,
    )

    op = ("ppermute", ("x",), ("f32[8]",))
    assert check_rank_consistency(
        _census({r: (op, op) for r in range(8)})
    ) == []
    assert check_rank_consistency(_census({})) == []


def test_census_provider_registration_feeds_the_detector():
    from implicitglobalgrid_tpu.analysis import collectives as C

    def provider(ctx):
        yield _census({0: (("psum", ("x",), ("f32[4]",)),), 1: ()})

    C.register_census_provider(provider)
    try:
        found = C.host_plan_findings(Context())
    finally:
        C.CENSUS_PROVIDERS.remove(provider)
    assert any(
        f.code == "rank-divergent-sequence" and f.symbol == "fixture"
        for f in found
    )


def test_gather_plan_census_is_clean_and_covers_the_real_plan():
    """The PR-1 flaky-gather watch item as a static invariant: the real
    `collective_plan` must be rank-independent over the census configs."""
    from implicitglobalgrid_tpu.analysis import collectives as C

    censuses = list(C.gather_plan_censuses(Context()))
    assert len(censuses) == len(C._GATHER_PLAN_CONFIGS)
    for census in censuses:
        assert C.check_rank_consistency(census) == []
        # every simulated rank present, root included
        assert len(census.sequences) >= 1


def test_fleet_plan_census_registered_and_clean():
    """ISSUE 16: the fleet tier's in-band directive schedule joins the
    same deadlock detector as the gather/tuner/supervisor plans — the
    REAL `fleet_plan` must be rank- and fence-uniform over every action."""
    from implicitglobalgrid_tpu.analysis import collectives as C
    from implicitglobalgrid_tpu.fleet.policy import FLEET_ACTIONS

    assert C.fleet_plan_censuses in C.CENSUS_PROVIDERS
    censuses = list(C.fleet_plan_censuses(Context()))
    assert len(censuses) == 2 * len(FLEET_ACTIONS)
    for census in censuses:
        assert C.check_rank_consistency(census) == [], census.name
        assert len(census.sequences) == 4


def test_fleet_plan_census_catches_rank_keyed_directive():
    """Seeded POSITIVE fixture (ISSUE 16): a fleet directive keyed on
    rank-LOCAL fence state — one zombie rank skipping the adopt-replay
    broadcast its pool-mates enter — is the `_gather_chunked` hang class
    wearing a fleet hat, and the detector must pin it CRITICAL."""
    from implicitglobalgrid_tpu.analysis import collectives as C
    from implicitglobalgrid_tpu.analysis.ir import RankCensus
    from implicitglobalgrid_tpu.fleet.policy import fleet_plan

    census = RankCensus(
        name="host/fleet_plan[broken-rank-keyed-fence]",
        sequences={
            rank: fleet_plan(rank == 0, "respawn", stale=(rank == 2))
            for rank in range(4)
        },
    )
    findings = C.check_rank_consistency(census)
    assert findings and findings[0].severity == "CRITICAL"
    assert findings[0].code == "rank-divergent-sequence"


def test_gather_collective_plan_ignores_is_root_and_covers_ragged_tail():
    import numpy as np

    from implicitglobalgrid_tpu.ops.gather import collective_plan

    dims, batch = (3, 2), 4  # 6 blocks, batch 4 -> one ragged tail of 2
    root_plan = collective_plan(dims, batch, is_root=True)
    assert root_plan == collective_plan(dims, batch, is_root=False)
    sizes = [len(sels) for _, sels in root_plan]
    assert sizes == [4, 2]
    flat = [s for _, sels in root_plan for s in sels]
    assert flat == list(range(int(np.prod(dims))))


# -- collective-consistency: AST rank-guard pass ------------------------------


_GUARDED = """
    from jax import lax

    def exchange(x, rank):
        if rank == 0:
            x = lax.psum(x, "x")
        return x
"""

_CLEAN = """
    from jax import lax

    def exchange(x, rank):
        x = lax.psum(x, "x")          # every rank, unconditionally
        if rank == 0:
            x = x * 2                 # rank-dependent HOST math is fine
        if x.ndim == 3:
            x = lax.pmax(x, "x")      # non-rank predicate is fine
        return x
"""


def test_rank_guard_pass_fires_on_guarded_collective(tmp_path):
    from implicitglobalgrid_tpu.analysis import collectives as C

    ctx = _fixture_ctx(tmp_path, {"mod.py": _GUARDED})
    found = C.ast_findings(ctx)
    assert [f.code for f in found] == ["rank-guarded-collective"]
    f = found[0]
    assert f.severity == "CRITICAL" and f.symbol == "exchange"
    assert f.anchor == "psum" and "rank" in f.message


def test_rank_guard_pass_quiet_on_unconditional_collective(tmp_path):
    from implicitglobalgrid_tpu.analysis import collectives as C

    ctx = _fixture_ctx(tmp_path, {"mod.py": _CLEAN})
    assert C.ast_findings(ctx) == []


def test_rank_guard_pass_sees_the_early_return_form(tmp_path):
    """The commonest shape of the PR-1 divergence: non-roots bail out
    BEFORE the collective, so the collective sits after the guard, not
    inside it."""
    from implicitglobalgrid_tpu.analysis import collectives as C

    src = """
        from jax import lax

        def exchange(x, rank):
            if rank != 0:
                return x
            return lax.psum(x, "x")
    """
    found = C.ast_findings(_fixture_ctx(tmp_path / "pos", {"m.py": src}))
    assert [f.code for f in found] == ["rank-guarded-collective"]
    assert "rank" in found[0].message

    # early return on a NON-rank predicate stays quiet
    quiet = """
        from jax import lax

        def exchange(x):
            if x.ndim != 3:
                return x
            return lax.psum(x, "x")
    """
    assert C.ast_findings(
        _fixture_ctx(tmp_path / "neg", {"q.py": quiet})
    ) == []


def test_rank_guard_pass_sees_ternaries_and_nested_guards(tmp_path):
    from implicitglobalgrid_tpu.analysis import collectives as C

    src = """
        from jax import lax

        def f(x, gg):
            return lax.psum(x, "x") if gg.coords[0] == 0 else x
    """
    found = C.ast_findings(_fixture_ctx(tmp_path, {"m.py": src}))
    assert [f.code for f in found] == ["rank-guarded-collective"]
    assert "coords" in found[0].message


# -- collective-consistency: traced-census structure checks -------------------


class _StubEntry:
    name = "stub"
    mesh_shape = {"x": 2}

    def __init__(self, ops):
        self._ops = ops

    def collectives(self):
        return self._ops


def _op(perm, path=(), kind="ppermute"):
    from implicitglobalgrid_tpu.analysis.ir import CollectiveOp

    return CollectiveOp(kind=kind, axes=("x",), perm=perm, payload_bytes=0,
                        shapes=("f32[4]",), path=path)


def test_perm_checks_fire_on_malformed_permutes():
    from implicitglobalgrid_tpu.analysis.collectives import _perm_findings

    dup_src = _perm_findings(_StubEntry([_op(((0, 1), (0, 0)))]))
    assert [f.code for f in dup_src] == ["malformed-permute"]
    assert "duplicate sources" in dup_src[0].message

    dup_dst = _perm_findings(_StubEntry([_op(((0, 1), (1, 1)))]))
    assert "duplicate targets" in dup_dst[0].message

    oob = _perm_findings(_StubEntry([_op(((0, 5),))]))
    assert "outside the axis size" in oob[0].message


def test_perm_checks_fire_on_collective_under_cond():
    from implicitglobalgrid_tpu.analysis.collectives import _perm_findings

    found = _perm_findings(
        _StubEntry([_op(((0, 1), (1, 0)), path=("while", "cond"))])
    )
    assert [f.code for f in found] == ["collective-under-cond"]
    assert found[0].severity == "CRITICAL"


def test_perm_checks_quiet_on_valid_partial_permutation():
    from implicitglobalgrid_tpu.analysis.collectives import _perm_findings

    # a PROC_NULL-masked edge hop: partial perm, no dup, in range
    assert _perm_findings(_StubEntry([_op(((0, 1),))])) == []


# -- knob-binding -------------------------------------------------------------


_KNOB_IN_TRACE = """
    import os
    from jax import jit

    def body(x):
        scale = int(os.environ.get("IGG_FIXTURE_SCALE", "1"))
        return x * scale

    stepper = jit(body)
"""

_KNOB_HOST_SIDE = """
    import os
    from jax import jit

    def _scale():
        return int(os.environ.get("IGG_FIXTURE_SCALE", "1"))

    def make_step():
        scale = _scale()              # resolved HOST-side, then closed over

        def body(x):
            return x * scale

        return jit(body)
"""


def test_knob_binding_fires_on_env_read_inside_jit(tmp_path):
    from implicitglobalgrid_tpu.analysis.knobs import run_knob_binding

    found = run_knob_binding(_fixture_ctx(tmp_path, {"m.py": _KNOB_IN_TRACE}))
    assert [f.code for f in found] == ["env-read-in-trace"]
    f = found[0]
    assert f.anchor == "IGG_FIXTURE_SCALE" and f.severity == "ERROR"
    assert "TRACE time" in f.message


def test_knob_binding_quiet_when_knob_resolved_host_side(tmp_path):
    from implicitglobalgrid_tpu.analysis.knobs import run_knob_binding

    found = run_knob_binding(
        _fixture_ctx(tmp_path, {"m.py": _KNOB_HOST_SIDE})
    )
    assert found == []


def test_knob_binding_follows_calls_and_accessor_args(tmp_path):
    """The package idiom: a traced closure calling an accessor that calls
    the generic reader — the knob name rides the constant first arg."""
    from implicitglobalgrid_tpu.analysis.knobs import run_knob_binding

    src = """
        import os
        from jax import lax
        from .cfg import int_env

        def make(n):
            def inner(c, x):
                return c, x * int_env("IGG_FIXTURE_DEPTH")

            def body(x):
                return lax.scan(inner, 0, x)

            return body
    """
    cfg = """
        import os

        def int_env(name):
            return int(os.environ.get(name, "0"))
    """
    found = run_knob_binding(
        _fixture_ctx(tmp_path, {"m.py": src, "cfg.py": cfg})
    )
    assert [f.anchor for f in found] == ["IGG_FIXTURE_DEPTH"]


def test_real_package_knob_binding_matches_the_baseline():
    """Triage pin: every knob-binding finding on the REAL package is one of
    the four baselined per-trace contracts — a new traced env read must
    show up here (and fail tier-1 via test_lint_suite) until triaged."""
    from implicitglobalgrid_tpu.analysis.knobs import run_knob_binding

    base = Baseline.load(core.DEFAULT_BASELINE)
    found = run_knob_binding(Context())
    unbaselined = [f for f in found if base.match(f) is None]
    assert unbaselined == [], [f.message for f in unbaselined]
    assert {f.anchor for f in found} == {
        "IGG_COALESCE", "IGG_TELEMETRY", "IGG_VMEM_MB",
        # ISSUE 10: begin/finish_slab_exchange's trace-time spans read the
        # ring capacity — same documented contract as IGG_TELEMETRY
        "IGG_TRACE_RING",
    }


# -- knob-decl ----------------------------------------------------------------


def test_knob_decl_fires_on_undeclared_and_undocumented(tmp_path):
    from implicitglobalgrid_tpu.analysis.knobs import knob_decl_findings

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text('import os\nos.environ.get("IGG_BOGUS")\n')
    config = tmp_path / "config.py"
    config.write_text('"""knob table: (none)"""\n')
    usage = tmp_path / "usage.md"
    usage.write_text("# usage\n")
    found = knob_decl_findings(str(tmp_path), str(pkg), str(config),
                               str(usage))
    assert sorted(f.code for f in found) == [
        "undeclared-knob", "undocumented-knob",
    ]
    assert all(f.symbol == "IGG_BOGUS" for f in found)

    config.write_text('"""table: IGG_BOGUS"""\n')
    usage.write_text("| `IGG_BOGUS` | fixture row |\n")
    assert knob_decl_findings(str(tmp_path), str(pkg), str(config),
                              str(usage)) == []


# -- pallas-aliasing ----------------------------------------------------------


def test_alias_pair_validation_fires_on_bogus_pairs():
    from implicitglobalgrid_tpu.analysis.aliasing import validate_alias_pairs

    a = ((8, 8), "float32")
    b = ((8, 9), "float32")
    assert validate_alias_pairs([(0, 0)], [a], [a]) == []
    assert "out of range" in validate_alias_pairs([(2, 0)], [a], [a])[0]
    assert "out of range" in validate_alias_pairs([(0, 3)], [a], [a])[0]
    probs = validate_alias_pairs([(0, 0), (1, 0)], [a, a], [a])
    assert any("two inputs" in p for p in probs)
    probs = validate_alias_pairs([(0, 0)], [b], [a])
    assert any("shape+dtype" in p for p in probs)


_BAD_ALIAS = """
    import jax.experimental.pallas as pl

    def build(kernel, shapes):
        return pl.pallas_call(
            kernel, out_shape=shapes,
            input_output_aliases={0: 0, 1: 0},
        )
"""

_GOOD_ALIAS = """
    import jax.experimental.pallas as pl

    def build(kernel, shapes):
        return pl.pallas_call(
            kernel, out_shape=shapes,
            input_output_aliases={0: 0, 1: 1},
        )
"""


def test_aliasing_ast_pass_fires_on_duplicate_output_alias(tmp_path):
    from implicitglobalgrid_tpu.analysis import aliasing

    found = aliasing.ast_findings(
        _fixture_ctx(tmp_path, {"k.py": _BAD_ALIAS})
    )
    assert [f.code for f in found] == ["bad-alias-literal"]
    assert "two inputs on one" in found[0].message


def test_aliasing_ast_pass_quiet_on_injective_alias(tmp_path):
    from implicitglobalgrid_tpu.analysis import aliasing

    assert aliasing.ast_findings(
        _fixture_ctx(tmp_path, {"k.py": _GOOD_ALIAS})
    ) == []


def test_aliasing_ast_pass_fires_on_negative_donation(tmp_path):
    from implicitglobalgrid_tpu.analysis import aliasing

    src = """
        from jax import jit

        def make(f):
            return jit(f, donate_argnums=(-1,))
    """
    found = aliasing.ast_findings(_fixture_ctx(tmp_path, {"d.py": src}))
    assert [f.code for f in found] == ["bad-donate-literal"]


# -- overlap-independence -----------------------------------------------------


def _shard_mapped_jaxpr(body, nargs=1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from implicitglobalgrid_tpu.analysis.ir import unwrap_inner
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    mapped = shard_map(body, mesh=mesh, in_specs=(P("x"),) * nargs,
                       out_specs=(P("x"),) * nargs, check_vma=False)
    args = (jnp.zeros((8,), jnp.float32),) * nargs
    return unwrap_inner(jax.make_jaxpr(mapped)(*args).jaxpr)


def test_independence_pairs_counts_dataflow_freedom():
    from jax import lax
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.analysis.ir import independence_pairs

    ring = [(0, 1), (1, 0)]
    is_k = lambda e: e.primitive.name == "sin"  # noqa: E731

    def dependent(x):
        return (jnp.sin(lax.ppermute(x, "x", ring)),)

    pairs, nk, nc = independence_pairs(
        _shard_mapped_jaxpr(dependent), is_kernel=is_k
    )
    assert (pairs, nk, nc) == (0, 1, 1)

    def independent(x, z):
        return jnp.sin(x), lax.ppermute(z, "x", ring)

    pairs, nk, nc = independence_pairs(
        _shard_mapped_jaxpr(independent, nargs=2), is_kernel=is_k
    )
    assert (pairs, nk, nc) == (1, 1, 1)


def test_eqn_presence_classifies_collective_envelopes():
    """A pjit/custom-vjp envelope whose body is all collectives must join
    the census as a collective (the coalesced `_packed_transport` shape);
    one containing none of either stays out."""
    import jax
    from jax import lax

    from implicitglobalgrid_tpu.analysis.ir import _eqn_presence

    ring = [(0, 1), (1, 0)]

    def body(x):
        wrapped = jax.jit(lambda v: lax.ppermute(v, "x", ring))
        return (wrapped(x) + 1.0,)

    jaxpr = _shard_mapped_jaxpr(body)
    by_name = {e.primitive.name: e for e in jaxpr.eqns}
    assert _eqn_presence(by_name["jit"]) == (False, True)
    assert _eqn_presence(by_name["add"]) == (False, False)


# -- collective-budget --------------------------------------------------------


def _hlo_fixture(n_perm: int, *, bad_start: bool = False) -> str:
    """Synthetic optimized-HLO text with ``n_perm`` collective-permutes in
    the shape `utils.hlo_analysis.collective_payloads` parses."""
    lines = ["ENTRY %main (p0: f32[6,6]) -> f32[6,6] {",
             "  %p0 = f32[6,6]{1,0} parameter(0)"]
    for i in range(n_perm):
        lines.append(
            f"  %cp{i} = f32[6,6]{{1,0}} collective-permute(%p0), "
            f"source_target_pairs={{{{0,1}},{{1,0}}}}"
        )
    if bad_start:
        # async-start whose tuple halves do NOT match -> raw-sum fallback
        lines.append(
            "  %cps = (f32[6,6]{1,0}, f32[4,6]{1,0}, u32[]) "
            "collective-permute-start(%p0), source_target_pairs={{0,1}}"
        )
    lines += ["  ROOT %r = f32[6,6]{1,0} add(%p0, %p0)", "}"]
    return "\n".join(lines)


def test_hlo_budget_cross_check_fires_and_stays_quiet():
    from implicitglobalgrid_tpu.analysis.budget import hlo_budget_findings

    # porous budget: 1 pair x 3 dims = 6 permutes allowed
    assert hlo_budget_findings(_hlo_fixture(6)) == []

    over = hlo_budget_findings(_hlo_fixture(8))
    assert [f.code for f in over] == ["hlo-budget-exceeded"]
    assert "split the coalesced hops" in over[0].message

    empty = hlo_budget_findings(_hlo_fixture(0))
    assert "hlo-census-broken" in [f.code for f in empty]


def test_hlo_budget_cross_check_flags_unaccounted_payloads():
    from implicitglobalgrid_tpu.analysis.budget import hlo_budget_findings

    found = hlo_budget_findings(_hlo_fixture(5, bad_start=True))
    assert [f.code for f in found] == ["hlo-payload-fallback"]
    assert found[0].severity == "WARNING"


def test_batched_census_fires_on_collective_count_mismatch():
    """Seeded positive fixture (ISSUE 8): a batched-exchange census whose
    B>1 counts differ from the B=1 baseline must fail — the
    B-for-the-price-of-1 claim as a static invariant — and the matching
    census must stay quiet."""
    from implicitglobalgrid_tpu.analysis.budget import (
        batched_census_findings,
    )

    base = {"x": 2, "y": 2, "z": 2}
    # clean: identical counts at every B
    assert batched_census_findings(
        {"diffusion": {1: dict(base), 4: dict(base)}}
    ) == []

    # regression: the B=4 exchange re-serialized per member in x
    found = batched_census_findings(
        {"diffusion": {1: dict(base), 4: {"x": 8, "y": 2, "z": 2}}}
    )
    assert [f.code for f in found] == ["batched-budget-mismatch"]
    assert found[0].symbol == "diffusion/batch4"
    assert "re-serialized" in found[0].message

    # a baseline that saw no collectives is a broken census, not a pass
    assert [
        f.code
        for f in batched_census_findings(
            {"porous": {1: {"x": 0, "y": 0, "z": 0}}}
        )
    ] == ["census-broken"]


def test_batched_census_real_trace_is_b_invariant():
    """The REAL traced census: every model's coalesced exchange must emit
    identical per-dimension ppermute counts at B=1 and B=4 (tier-1 also
    runs this through the suite's `budget.run`)."""
    from implicitglobalgrid_tpu.analysis.budget import (
        BATCHED_CENSUS_B,
        batched_budget_findings,
        batched_exchange_census,
    )

    census = batched_exchange_census()
    assert set(census) == {"diffusion", "acoustic", "porous"}
    for model, variants in census.items():
        assert variants[1] == variants[BATCHED_CENSUS_B], (model, variants)
        assert sum(variants[1].values()) > 0, (model, variants)
    assert batched_budget_findings() == []


def test_entry_budget_census_fires_on_per_field_regression():
    """The suite path counts the SHARED traced entries: a coalesce=True
    entry showing per-field collective counts must fire, and a control
    entry that lost its collectives must flag the census itself."""
    from implicitglobalgrid_tpu.analysis.budget import entry_budget_findings

    from implicitglobalgrid_tpu.analysis.ir import CollectiveOp

    def entry(name, axis_counts):
        ops = []
        for axis, cnt in axis_counts.items():
            ops += [
                CollectiveOp(kind="ppermute", axes=(axis,), perm=((0, 1),),
                             payload_bytes=0, shapes=("f32[4]",), path=())
            ] * cnt
        stub = _StubEntry(ops)
        stub.name = name
        return stub

    # diffusion (1 field): coalesced entry regressed to 6 permutes in x
    found = entry_budget_findings(
        [
            entry("exchange/diffusion[coalesce=True]", {"x": 6, "y": 2, "z": 2}),
            entry("exchange/diffusion[coalesce=False]", {"x": 2}),
        ],
        budget_pairs={"diffusion": 1},
    )
    assert [f.code for f in found] == ["budget-exceeded"]
    assert found[0].symbol == "diffusion/dim0"

    # clean twin stays quiet
    assert entry_budget_findings(
        [
            entry("exchange/diffusion[coalesce=True]", {"x": 2, "y": 2, "z": 2}),
            entry("exchange/diffusion[coalesce=False]", {"x": 2}),
        ],
        budget_pairs={"diffusion": 1},
    ) == []

    # a missing entry is a broken census, not a clean run
    assert [
        f.code
        for f in entry_budget_findings([], budget_pairs={"diffusion": 1})
    ] == ["census-broken"]


def test_budget_analyzer_fires_when_budget_tightened_to_zero():
    """Liveness: with an impossible budget the census must report every
    exchanged dimension — proving it sees the real collectives (the clean
    run on the true budget is tier-1's test_collective_budget)."""
    from implicitglobalgrid_tpu.analysis.budget import budget_findings

    found = budget_findings(budget_pairs={"diffusion": 0})
    assert [f.code for f in found] == ["budget-exceeded"] * 3
    assert {f.symbol for f in found} == {
        "diffusion/dim0", "diffusion/dim1", "diffusion/dim2",
    }


# -- hlo-cost -----------------------------------------------------------------


class _CostCtx:
    """Stub Context: a traced exchange entry + an HLO text, nothing else."""

    def __init__(self, entries, hlo):
        self._entries, self._hlo = entries, hlo

    def exchange_entries(self):
        return self._entries

    def exchange_hlo(self):
        return self._hlo


def _traced_exchange_stub(payload_bytes_list):
    from implicitglobalgrid_tpu.analysis.ir import (
        EXCHANGE_HLO_PROGRAM,
        CollectiveOp,
    )

    ops = [
        CollectiveOp(kind="ppermute", axes=("x",), perm=((0, 1),),
                     payload_bytes=b, shapes=(f"f32[{b // 4}]",), path=())
        for b in payload_bytes_list
    ]
    stub = _StubEntry(ops)
    stub.name = EXCHANGE_HLO_PROGRAM
    return stub


def test_cost_text_census_counts_the_hlo_structure():
    from implicitglobalgrid_tpu.analysis.costmodel import text_census

    c = text_census(_hlo_fixture(6))
    assert c["collective_permutes"] == 6
    assert c["collective_payload_bytes"] == 6 * 144  # f32[6,6] per hop
    assert c["payload_fallbacks"] == 0
    assert c["fusions"] == 0 and c["kernel_launches"] == 0


def test_payload_crosscheck_byte_exact_and_fires_on_mismatch():
    from implicitglobalgrid_tpu.analysis.costmodel import (
        payload_crosscheck_findings,
    )

    # byte-exact twin: 6 traced hops of 144 B vs 6 compiled permutes
    clean = payload_crosscheck_findings(
        _CostCtx([_traced_exchange_stub([144] * 6)], _hlo_fixture(6))
    )
    assert clean == []

    # a widened hop (the seeded 2x payload regression) must fire
    widened = payload_crosscheck_findings(
        _CostCtx([_traced_exchange_stub([288] + [144] * 5), ],
                 _hlo_fixture(6))
    )
    assert [f.code for f in widened] == ["payload-mismatch"]

    # a lost hop fires too (count is part of the identity)
    lost = payload_crosscheck_findings(
        _CostCtx([_traced_exchange_stub([144] * 5)], _hlo_fixture(6))
    )
    assert [f.code for f in lost] == ["payload-mismatch"]

    # a raw-sum fallback is its own failure, declared by the parser
    fb = payload_crosscheck_findings(
        _CostCtx([_traced_exchange_stub([144] * 5 + [240])],
                 _hlo_fixture(5, bad_start=True))
    )
    assert "payload-fallback" in [f.code for f in fb]

    # no traced twin at all = a broken cross-check, never a clean pass
    gone = payload_crosscheck_findings(_CostCtx([], _hlo_fixture(6)))
    assert [f.code for f in gone] == ["crosscheck-broken"]


def _cost_baseline(metrics, tolerances=None):
    return {
        "version": 1,
        "tolerances": tolerances or {"flops": 0.02, "*": 0.0},
        "programs": {
            "prog": {
                "metrics": dict(metrics),
                "justifications": {m: "pinned by fixture" for m in metrics},
            }
        },
    }


def test_compare_census_fires_on_inflated_payload_and_defused_kernel():
    from implicitglobalgrid_tpu.analysis.costmodel import compare_census

    base = _cost_baseline(
        {"collective_payload_bytes": 8064, "kernel_launches": 3,
         "flops": 1000}
    )
    clean = {"prog": {"collective_payload_bytes": 8064,
                      "kernel_launches": 3, "flops": 1000}}
    assert compare_census(clean, base) == []

    # the seeded 2x payload inflation (acceptance fixture) fails the gate
    doubled = {"prog": {"collective_payload_bytes": 16128,
                        "kernel_launches": 3, "flops": 1000}}
    found = compare_census(doubled, base)
    assert [f.code for f in found] == ["cost-regression"]
    assert found[0].anchor == "collective_payload_bytes"

    # one defused extra kernel launch fails too (structural = exact band)
    defused = {"prog": {"collective_payload_bytes": 8064,
                        "kernel_launches": 4, "flops": 1000}}
    found = compare_census(defused, base)
    assert [f.code for f in found] == ["cost-regression"]
    assert found[0].anchor == "kernel_launches"


def test_compare_census_tolerance_bands_and_two_sided_drift():
    from implicitglobalgrid_tpu.analysis.costmodel import compare_census

    base = _cost_baseline({"flops": 1000, "kernel_launches": 3})
    inside = {"prog": {"flops": 1010, "kernel_launches": 3}}  # +1% < 2%
    assert compare_census(inside, base) == []
    outside = {"prog": {"flops": 1030, "kernel_launches": 3}}  # +3% > 2%
    assert [f.code for f in compare_census(outside, base)] == [
        "cost-regression"
    ]
    # an IMPROVEMENT outside the band is news, not silent drift
    better = {"prog": {"flops": 900, "kernel_launches": 3}}
    found = compare_census(better, base)
    assert [f.code for f in found] == ["cost-regression"]
    assert "improved" in found[0].message


def test_compare_census_reports_lost_and_unbaselined():
    from implicitglobalgrid_tpu.analysis.costmodel import compare_census

    base = _cost_baseline({"flops": 1000})
    # the toolchain stopped reporting a gated metric: blind spot, ERROR
    lost = compare_census({"prog": {"kernel_launches": 3}}, base)
    codes = {f.code for f in lost}
    assert "metric-lost" in codes and "metric-unbaselined" in codes
    # a program disappearing from the matrix is an ERROR as well
    assert [f.code for f in compare_census({}, base)] == ["program-missing"]
    # a new program with no baseline entry is a WARNING nudge to refresh
    extra = compare_census(
        {"prog": {"flops": 1000}, "prog2": {"flops": 5}}, base
    )
    assert [f.code for f in extra] == ["program-unbaselined"]
    assert extra[0].severity == "WARNING"


def test_cost_baseline_loader_enforces_the_audit_contract(tmp_path):
    from implicitglobalgrid_tpu.analysis import costmodel

    p = tmp_path / "cost_baseline.json"
    p.write_text(json.dumps({
        "version": 1,
        "programs": {"prog": {"metrics": {"flops": 1},
                              "justifications": {"flops": "  "}}},
    }))
    with pytest.raises(ValueError, match="unjustified"):
        costmodel.load_baseline(str(p))
    p.write_text(json.dumps({"version": 99, "programs": {}}))
    with pytest.raises(ValueError, match="version"):
        costmodel.load_baseline(str(p))


# -- grad-soundness -----------------------------------------------------------


def test_dropper_scan_fires_on_bitcast_in_tangent_path():
    """The seeded PR-5 class: a bitcast transport with NO custom VJP on the
    differentiable path must be CRITICAL (jax.grad silently zeroes every
    cotangent through it)."""
    import jax
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.analysis.gradflow import dropper_findings

    def broken(x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(u, jnp.float32) * 2.0

    jaxpr = jax.make_jaxpr(broken)(jnp.ones(4, jnp.float32))
    found = dropper_findings(jaxpr.jaxpr, "fixture/broken")
    assert [f.severity for f in found] == ["CRITICAL"]
    assert found[0].code == "cotangent-dropper"
    assert "bitcast_convert_type" in found[0].message
    assert "_packed_transport" in found[0].fix_hint
    # in-repo source locations are REPO-RELATIVE: the fingerprint hashes
    # the path, so an absolute checkout prefix would pin baselines (and
    # the SARIF artifact URIs) to one machine
    assert found[0].path and not os.path.isabs(found[0].path)
    assert found[0].path.startswith("tests/")


def test_dropper_scan_fires_on_float_to_int_cast_and_warns_stop_gradient():
    import jax
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.analysis.gradflow import dropper_findings

    def int_cast(x):
        return x.astype(jnp.int32).astype(jnp.float32) * 2.0

    jaxpr = jax.make_jaxpr(int_cast)(jnp.ones(4, jnp.float32))
    found = dropper_findings(jaxpr.jaxpr, "fixture/cast")
    assert [f.severity for f in found] == ["CRITICAL"]

    def stopped(x):
        return jax.lax.stop_gradient(x) * 2.0

    jaxpr = jax.make_jaxpr(stopped)(jnp.ones(4, jnp.float32))
    found = dropper_findings(jaxpr.jaxpr, "fixture/stop")
    assert [f.severity for f in found] == ["WARNING"]


def test_dropper_scan_quiet_off_the_tangent_path_and_under_custom_vjp():
    import jax
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.analysis.gradflow import dropper_findings

    # bitcast feeding only a side computation that never reaches the
    # outputs' dataflow from the float inputs: int operand = not tainted
    def side(x, idx):
        shifted = jax.lax.bitcast_convert_type(idx, jnp.int32)
        return x * 2.0, shifted

    jaxpr = jax.make_jaxpr(side)(
        jnp.ones(4, jnp.float32), jnp.ones(4, jnp.uint32)
    )
    assert dropper_findings(jaxpr.jaxpr, "fixture/side") == []

    # the registered-VJP envelope is the documented fix and is exempt
    @jax.custom_vjp
    def packed(x):
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(u, jnp.float32)

    packed.defvjp(lambda x: (packed(x), None), lambda _, g: (g,))

    def wrapped(x):
        return packed(x) * 2.0

    jaxpr = jax.make_jaxpr(wrapped)(jnp.ones(4, jnp.float32))
    assert dropper_findings(jaxpr.jaxpr, "fixture/protected") == []


def test_real_packed_transport_runs_clean_and_exemption_is_alive():
    """The negative fixture of the ISSUE: the coalesced exchange's
    `_packed_transport` (registered VJP) scans clean — and the control
    proves the custom-vjp exemption is what keeps it clean (the bitcast
    transport IS there underneath)."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.analysis import gradflow, ir
    from implicitglobalgrid_tpu.ops import halo

    igg.init_global_grid(8, 8, 8, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    try:
        gg = igg.get_global_grid()
        fields = ir.model_field_structs("porous", 8)

        def body(*fs):
            return halo.exchange_dims_multi(fs, (0, 1, 2), width=1,
                                            coalesce=True)

        jaxpr = ir.unwrap_inner(ir._trace_mapped(body, fields, gg).jaxpr)
    finally:
        igg.finalize_global_grid()

    assert gradflow.dropper_findings(jaxpr, "exchange/porous") == []

    # liveness control: descending past the protection must surface the
    # packed transport's bitcasts — the exemption does real work
    import pytest as _pytest

    mp = _pytest.MonkeyPatch()
    try:
        mp.setattr(gradflow, "_PROTECTED", ())
        unprotected = gradflow.dropper_findings(jaxpr, "exchange/porous")
    finally:
        mp.undo()
    assert any(
        f.code == "cotangent-dropper" and "bitcast" in f.anchor
        for f in unprotected
    )


class _StubGrad:
    def __init__(self, name, grad_n, primal_n):
        self.name = name
        self._counts = (grad_n, primal_n)

    def collective_counts(self):
        return self._counts


def test_backward_collective_census_separates_healthy_from_sunk():
    from implicitglobalgrid_tpu.analysis.gradflow import census_findings

    # healthy: VJP issues strictly more collectives than the primal
    assert census_findings([_StubGrad("grad/x", 66, 6)]) == []

    # the PR-5 failure shape: VJP count == primal count (no backward hops)
    sunk = census_findings([_StubGrad("grad/x", 5, 5)])
    assert [f.code for f in sunk] == ["cotangent-sink"]
    assert sunk[0].severity == "CRITICAL"

    # a primal with zero collectives means the census itself went blind
    blind = census_findings([_StubGrad("grad/x", 3, 0)])
    assert [f.code for f in blind] == ["census-broken"]


# -- changed-files ref mode (--changed-only=REF) ------------------------------


def _git_fixture_repo(tmp_path):
    import subprocess

    repo = tmp_path / "repo"
    repo.mkdir()

    def git(*args):
        subprocess.run(["git", *args], cwd=repo, check=True,
                       capture_output=True)
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo, capture_output=True,
            text=True,
        ).stdout.strip()

    git("init", "-q")
    git("config", "user.email", "t@example.com")
    git("config", "user.name", "t")
    (repo / "base.txt").write_text("base\n")
    git("add", "base.txt")
    base_sha = git("commit", "-qm", "base")
    git("checkout", "-qb", "feature")
    (repo / "feat.txt").write_text("feat\n")
    git("add", "feat.txt")
    git("commit", "-qm", "feat")
    return repo, base_sha


def test_changed_files_ref_mode_sees_committed_diffs(tmp_path):
    """On a CLEAN checkout `git status` selects nothing — the CI hole the
    satellite fixes; ref mode diffs against the merge-base instead, and the
    two censuses union when the worktree is dirty too."""
    from implicitglobalgrid_tpu.analysis.core import changed_files

    repo, base_sha = _git_fixture_repo(tmp_path)

    assert changed_files(str(repo)) == []  # clean checkout: status empty
    assert changed_files(str(repo), ref=base_sha) == ["feat.txt"]

    (repo / "dirty.txt").write_text("wip\n")  # untracked joins the union
    got = changed_files(str(repo), ref=base_sha)
    assert set(got) == {"feat.txt", "dirty.txt"}
    assert changed_files(str(repo)) == ["dirty.txt"]  # status mode unchanged


def test_changed_files_ref_mode_raises_on_bad_ref(tmp_path):
    """A bad ref must RAISE, not silently select zero analyzers — an empty
    census would green-light a PR that was never linted."""
    from implicitglobalgrid_tpu.analysis.core import changed_files

    repo, _ = _git_fixture_repo(tmp_path)
    with pytest.raises(RuntimeError, match="merge-base"):
        changed_files(str(repo), ref="no-such-ref-xyz")


# -- SARIF export -------------------------------------------------------------


def _sarif_fixture_report():
    from implicitglobalgrid_tpu.analysis.core import Finding, Report

    dropper = Finding(
        analyzer="grad-soundness", code="cotangent-dropper",
        severity="CRITICAL",
        message="fixture: bitcast on the tangent path",
        path="implicitglobalgrid_tpu/ops/halo.py", line=12,
        symbol="exchange/porous", anchor="bitcast[f32]",
        fix_hint="wrap the transport in jax.custom_vjp",
    )
    cost = Finding(
        analyzer="hlo-cost", code="cost-regression", severity="ERROR",
        message="fixture: payload bytes doubled",
        symbol="exchange/porous[coalesce=True]",
        anchor="collective_payload_bytes",
    )
    suppressed = Finding(
        analyzer="knob-binding", code="env-read-in-trace",
        severity="WARNING", message="fixture: traced knob read",
        path="implicitglobalgrid_tpu/ops/halo.py", symbol="f",
        anchor="IGG_FIXTURE",
    )
    return Report(
        findings=[cost, dropper],
        suppressed=[(suppressed, "documented per-call contract")],
        ran=["grad-soundness", "hlo-cost", "knob-binding"],
        skipped=["knob-decl"],
    )


def test_sarif_export_matches_the_golden_file():
    """The full artifact is pinned byte-for-byte (sorted keys, stable
    ordering, no timestamps) — CI consumers parse this exact shape, so any
    schema drift must be a reviewed diff of the golden file."""
    from implicitglobalgrid_tpu.analysis.sarif import report_to_sarif

    golden_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "igg_lint_golden.sarif",
    )
    got = json.dumps(report_to_sarif(_sarif_fixture_report()), indent=2,
                     sort_keys=True) + "\n"
    with open(golden_path, encoding="utf-8") as f:
        assert got == f.read()


def test_sarif_results_carry_fingerprints_and_suppressions():
    from implicitglobalgrid_tpu.analysis.sarif import report_to_sarif

    report = _sarif_fixture_report()
    sarif = report_to_sarif(report)
    run0 = sarif["runs"][0]
    assert sarif["version"] == "2.1.0"
    assert run0["tool"]["driver"]["name"] == "igg-lint"

    results = run0["results"]
    assert len(results) == 3  # 2 active + 1 suppressed
    fps = {f.fingerprint for f in report.findings} | {
        f.fingerprint for f, _ in report.suppressed
    }
    assert {
        r["partialFingerprints"]["iggLintFingerprint/v1"] for r in results
    } == fps
    sup = [r for r in results if "suppressions" in r]
    assert len(sup) == 1
    assert sup[0]["suppressions"][0]["justification"] == (
        "documented per-call contract"
    )
    # CRITICAL maps to SARIF "error" but keeps its name in properties
    crit = next(r for r in results
                if r["ruleId"] == "grad-soundness/cotangent-dropper")
    assert crit["level"] == "error"
    assert crit["properties"]["iggSeverity"] == "CRITICAL"
    assert crit["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 12


def test_sarif_rule_level_is_worst_severity_regardless_of_order():
    """A rule spanning severities (cotangent-dropper: CRITICAL bitcast vs
    WARNING stop_gradient) must advertise its WORST case even when a
    milder finding appears first — rule metadata must not flip with
    finding order."""
    from implicitglobalgrid_tpu.analysis.core import Finding, Report
    from implicitglobalgrid_tpu.analysis.sarif import report_to_sarif

    def f(sev, anchor):
        return Finding(analyzer="grad-soundness", code="cotangent-dropper",
                       severity=sev, message="m", symbol="s", anchor=anchor)

    report = Report(findings=[f("WARNING", "stop_gradient"),
                              f("CRITICAL", "bitcast")],
                    ran=["grad-soundness"])
    rule = report_to_sarif(report)["runs"][0]["tool"]["driver"]["rules"][0]
    assert rule["defaultConfiguration"]["level"] == "error"
