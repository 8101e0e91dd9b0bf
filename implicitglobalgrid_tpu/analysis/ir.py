"""Traced-IR producers for the analysis suite.

Three IRs feed the analyzers: the package AST (built by `core.Context`),
the traced jaxprs produced here, and optimized-HLO text (parsed by
`utils.hlo_analysis` — the HLO-level analyzers reuse that module wholesale).

This module traces the package's public entry points under the production
config matrix on the 8-device virtual CPU mesh:

* **exchange entries** — `exchange_dims_multi` over each model's production
  field set (plain, staggered faces, padded layout, begin/finish slab
  pipeline), coalesce auto/off, on one grid that has BOTH periodic and
  PROC_NULL transports;
* **cadence entries** — each model's fused `make_multi_step` program,
  pipelined on/off (the kernels trace under the TPU Pallas
  interpreter, `pltpu.force_tpu_interpret_mode`).

Everything here is TRACE-only (`jax.make_jaxpr`): no executable is built, no
device computation runs — which is what makes a full-matrix census cheap
enough for tier-1.  Each producer manages its own grid (init/finalize), so
callers need no grid state; conftest's finalize-after-test fixture composes.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import numpy as np

#: jaxpr primitive names that move data across ranks.  ``ppermute`` is the
#: halo transport; the reductions appear in gather/guard paths.
COLLECTIVE_PRIMS = (
    "ppermute",
    "psum",
    "pmax",
    "pmin",
    "pmean",
    "all_gather",
    "all_to_all",
    "reduce_scatter",
    "pbroadcast",
)

#: Control-flow primitives whose sub-jaxprs we descend into, tracking the
#: nesting path.  A collective under ``cond`` is a deadlock hazard (a
#: rank-divergent predicate runs the collective on some ranks only); under
#: ``while``/``scan`` it is fine when the trip count is a trace-time
#: constant, which jax guarantees for ``fori_loop``/``scan``.
_SUBJAXPR_PARAM_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                       "body_jaxpr", "branches")


@dataclass(frozen=True)
class CollectiveOp:
    """One collective equation in a traced program."""

    kind: str            # primitive name
    axes: tuple          # mesh axis name(s) it operates over
    perm: tuple | None   # ppermute source->target pairs (positions on axis)
    payload_bytes: int   # sum of operand aval bytes
    shapes: tuple        # operand aval strings
    path: tuple          # enclosing higher-order primitive names

    @property
    def signature(self) -> tuple:
        """The cross-rank identity of the op: what every rank must agree
        on for the collective to match up (kind, axes, payload)."""
        return (self.kind, self.axes, self.shapes)


@dataclass(frozen=True)
class TracedEntry:
    """One traced entry point of the config matrix."""

    name: str            # e.g. "cadence/diffusion[pipelined=True]"
    kind: str            # "exchange" | "cadence"
    config: dict
    jaxpr: object        # the inner (shard_map-unwrapped) jaxpr
    mesh_shape: dict     # axis name -> size
    admissible: bool = True  # pipelined configs: did the schedule engage?

    def collectives(self) -> list:
        return collect_collectives(self.jaxpr)


@dataclass(frozen=True)
class RankCensus:
    """Per-rank ordered collective sequences of one entry point.

    ``sequences`` maps a rank key (coords tuple, process index, or any
    hashable label) to the ordered tuple of signature records that rank
    issues.  The divergence detector's invariant: ALL values are equal —
    one rank running a different sequence is the `_gather_chunked` hang
    class (PR 1) and MUST/GSPMD's classic deadlock condition.
    """

    name: str
    sequences: dict = field(default_factory=dict)


# -- jaxpr walking ------------------------------------------------------------


def _sub_jaxprs(eqn):
    """(param_key, jaxpr) sub-programs of one equation, ClosedJaxpr-unwrapped."""
    for k, v in eqn.params.items():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield k, x.jaxpr
            elif hasattr(x, "eqns"):
                yield k, x


def iter_eqns(jaxpr, path=()):
    """Yield ``(eqn, path)`` over a jaxpr and its sub-jaxprs in program
    order; ``path`` is the tuple of enclosing primitive names.  Does not
    descend into ``pallas_call`` bodies — a kernel's internal DMA control
    flow is not rank-level communication."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        if eqn.primitive.name == "pallas_call":
            continue
        for _, sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, path + (eqn.primitive.name,))


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:  # noqa: BLE001 — abstract tokens etc.
        return 0


def collect_collectives(jaxpr) -> list:
    """Ordered `CollectiveOp` records of a traced program."""
    out = []
    for eqn, path in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            continue
        axes = eqn.params.get("axis_name", eqn.params.get("axes", ()))
        if not isinstance(axes, tuple):
            axes = (axes,)
        perm = eqn.params.get("perm")
        out.append(
            CollectiveOp(
                kind=name,
                axes=tuple(str(a) for a in axes),
                perm=tuple(map(tuple, perm)) if perm is not None else None,
                payload_bytes=sum(_aval_bytes(v.aval) for v in eqn.invars),
                shapes=tuple(str(v.aval) for v in eqn.invars),
                path=path,
            )
        )
    return out


def unwrap_inner(jaxpr):
    """The analysis view of a traced SPMD program: the shard_map body,
    unwrapped past the kernel-vs-fallback ``custom_vjp`` envelope
    (`fused_with_xla_grad` nests the whole cadence under one eqn)."""
    sms = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    inner = sms[0].params["jaxpr"] if sms else jaxpr
    if hasattr(inner, "eqns") is False and hasattr(inner, "jaxpr"):
        inner = inner.jaxpr
    while (
        len(inner.eqns) == 1
        and "custom_vjp" in inner.eqns[0].primitive.name
    ):
        inner = inner.eqns[0].params["call_jaxpr"].jaxpr
    return inner


def rank_roles(entry: TracedEntry, coords: tuple) -> list[str]:
    """Per-op send/recv role of one rank (``"sr"``/``"s"``/``"r"``/``""``),
    derived from each ppermute's perm — the debugging view of a census
    entry (which rank moves payload in which hop)."""
    axes = list(entry.mesh_shape)
    pos = dict(zip(axes, coords))
    roles = []
    for op in entry.collectives():
        role = ""
        if op.kind == "ppermute" and op.perm is not None and op.axes:
            p = pos.get(op.axes[0], 0)
            role = (
                ("s" if any(s == p for s, _ in op.perm) else "")
                + ("r" if any(d == p for _, d in op.perm) else "")
            )
        roles.append(role)
    return roles


# -- traced entry producers ---------------------------------------------------


def model_field_structs(model: str, n: int):
    """The model's exchanged field set as traced shapes (staggered ``n+1``
    faces like the real states; f32 like the production configs).  Shared
    with the collective-budget analyzer — one field census for both."""
    import jax
    import jax.numpy as jnp

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    cell = (n, n, n)
    faces = [
        tuple(n + (1 if d == ax else 0) for d in range(3)) for ax in range(3)
    ]
    if model == "diffusion":
        return (s(cell),)
    if model == "acoustic":
        return (s(cell), *map(s, faces))
    if model == "porous":
        return (s(cell), *map(s, faces), s(cell))
    raise ValueError(model)


def _trace_mapped(body, fields, gg, out_fields=None):
    """shard_map + make_jaxpr a local-block body over global-shaped args.

    ``out_fields`` overrides the output structure when it differs from the
    inputs (a traced VJP takes seeds + primals but returns one cotangent
    per primal — `trace_grad_entries`); default: outputs mirror inputs.

    Fields of rank > NDIMS carry a leading BATCH/ensemble axis
    (`models._batched` layout): the batch axis stays replicated
    (``P(None, 'x', 'y', 'z')``) and is not multiplied by the mesh dims —
    the tracing convention the batched-exchange census relies on.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from .. import AXIS_NAMES, NDIMS
    from jax import shard_map

    def spec(f):
        nbatch = max(f.ndim - NDIMS, 0)
        return P(*(None,) * nbatch, *AXIS_NAMES[: f.ndim - nbatch])

    specs = tuple(spec(f) for f in fields)
    out_specs = (
        specs if out_fields is None else tuple(spec(f) for f in out_fields)
    )
    mapped = shard_map(
        body, mesh=gg.mesh, in_specs=specs, out_specs=out_specs,
        check_vma=False,
    )

    def gshape(f):
        nbatch = max(f.ndim - NDIMS, 0)
        return f.shape[:nbatch] + tuple(
            s * gg.dims[i] for i, s in enumerate(f.shape[nbatch:])
        )

    gargs = tuple(
        jax.ShapeDtypeStruct(gshape(f), f.dtype) for f in fields
    )
    return jax.make_jaxpr(mapped)(*gargs)


def trace_exchange_entries(n: int = 8) -> list:
    """The halo-exchange half of the config matrix.

    One grid — dims (2,2,2), periodic z — exercises PROC_NULL and periodic
    transports together; per model the production field set is traced with
    the coalesced and the per-field exchange, plus the padded-faces layout
    and the begin/finish slab pipeline (the pipelined schedules' exchange).
    """
    import implicitglobalgrid_tpu as igg
    from ..ops import halo

    entries = []
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    try:
        gg = igg.get_global_grid()
        mesh_shape = {a: int(s) for a, s in zip(igg.AXIS_NAMES, gg.dims)}
        for model in ("diffusion", "acoustic", "porous"):
            fields = model_field_structs(model, n)
            for coalesce in (True, False):

                def body(*fs, _c=coalesce):
                    return halo.exchange_dims_multi(
                        fs, (0, 1, 2), width=1, coalesce=_c
                    )

                entries.append(
                    TracedEntry(
                        name=f"exchange/{model}[coalesce={coalesce}]",
                        kind="exchange",
                        config={"model": model, "coalesce": coalesce},
                        jaxpr=unwrap_inner(
                            _trace_mapped(body, fields, gg).jaxpr
                        ),
                        mesh_shape=mesh_shape,
                    )
                )

        # Padded-faces layout (the fused cadences' exchange geometry).
        from ..ops.pallas_leapfrog import pad_faces

        fields4 = model_field_structs("acoustic", n)

        # pad_faces changes shapes, so the body returns the ORIGINAL fields
        # to keep in/out specs symmetric; the exchange still traces fully.
        def padded_body(C, Vx, Vy, Vz):
            Vxp, Vyp, Vzp = pad_faces(Vx, Vy, Vz)
            halo.update_halo_padded_faces(
                C, Vxp, Vyp, Vzp, width=1, coalesce=True
            )
            return C, Vx, Vy, Vz

        entries.append(
            TracedEntry(
                name="exchange/padded_faces",
                kind="exchange",
                config={"layout": "pad_faces"},
                jaxpr=unwrap_inner(
                    _trace_mapped(padded_body, fields4, gg).jaxpr
                ),
                mesh_shape=mesh_shape,
            )
        )

        # Early-dispatch slab pipeline (begin/finish).
        def slab_body(*fs):
            pends = halo.begin_slab_exchange(fs, (0, 1, 2), width=1)
            return halo.finish_slab_exchange(fs, pends)

        entries.append(
            TracedEntry(
                name="exchange/slab_pipeline",
                kind="exchange",
                config={"layout": "begin/finish"},
                jaxpr=unwrap_inner(
                    _trace_mapped(
                        slab_body, model_field_structs("porous", n), gg
                    ).jaxpr
                ),
                mesh_shape=mesh_shape,
            )
        )
    finally:
        igg.finalize_global_grid()
    return entries


# -- compiled programs (the optimized-HLO IR) ---------------------------------


@dataclass(frozen=True)
class CompiledProgram:
    """One XLA:CPU-compiled program of the config matrix.

    ``text`` is the optimized-HLO text (`utils.hlo_analysis` parses it);
    ``memory``/``cost`` carry the toolchain's own buffer-assignment and
    cost-analysis numbers (`memory_analysis`/`cost_analysis` — empty dicts
    where a toolchain does not expose them, and the cost model reports
    that as a lost metric rather than silently passing).
    """

    name: str
    kind: str            # "exchange" | "cadence"
    config: dict
    text: str
    memory: dict
    cost: dict


#: The compiled half of the config matrix.  The exchange program shares its
#: NAME (and grid/field config) with the traced entry of the same name, so
#: the cost model's payload cross-check compares the SAME program across
#: the jaxpr and optimized-HLO IRs.  Cadences compile pipelined=True — the
#: production schedule whose fusion/collective structure the baseline pins.
EXCHANGE_HLO_PROGRAM = "exchange/porous[coalesce=True]"
#: Ensemble size of the batched compiled programs (ISSUE 8): the batched
#: exchange must keep the unbatched program's collective count with
#: payload bytes scaled ×B — pinned by the cost baseline's
#: ``collective_permutes`` / ``collective_payload_bytes`` metrics.
BATCH_HLO_B = 4
BATCHED_EXCHANGE_PROGRAM = f"exchange/porous[coalesce=True,batch={BATCH_HLO_B}]"
BATCHED_CADENCE_PROGRAM = f"cadence/diffusion[batch={BATCH_HLO_B}]"
COMPILED_MATRIX = (
    EXCHANGE_HLO_PROGRAM,
    "cadence/diffusion[pipelined=True]",
    "cadence/acoustic[pipelined=True]",
    "cadence/porous[pipelined=True]",
    BATCHED_EXCHANGE_PROGRAM,
    BATCHED_CADENCE_PROGRAM,
)


def _compiled_stats(compiled) -> tuple[dict, dict]:
    """(memory, cost) numbers of one compiled executable, best-effort."""
    memory, cost = {}, {}
    try:
        ma = compiled.memory_analysis()
        memory = {
            "temp_bytes": int(ma.temp_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
        }
    except Exception:  # noqa: BLE001 — backend without memory stats
        pass
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        for key, out in (("flops", "flops"), ("bytes accessed", "bytes_accessed")):
            if key in ca:
                cost[out] = float(ca[key])
    except Exception:  # noqa: BLE001 — backend without cost analysis
        pass
    return memory, cost


def compile_program(name: str) -> CompiledProgram:
    """Compile one named program of `COMPILED_MATRIX` (XLA:CPU).

    Callers go through `core.Context.compiled_program`, which caches per
    config — the budget analyzer's HLO cross-check and the cost model's
    census share ONE compile of the exchange instead of building it twice.
    """
    if name == EXCHANGE_HLO_PROGRAM:
        return _compile_exchange_program()
    if name == BATCHED_EXCHANGE_PROGRAM:
        return _compile_batched_exchange_program()
    if name == BATCHED_CADENCE_PROGRAM:
        return _compile_batched_cadence_program()
    for model in ("diffusion", "acoustic", "porous"):
        if name == f"cadence/{model}[pipelined=True]":
            return _compile_cadence_program(model)
    raise ValueError(
        f"unknown compiled program {name!r}; matrix: {COMPILED_MATRIX}"
    )


def _compile_exchange_program(model: str = "porous", n: int = 8) -> CompiledProgram:
    """The porous 5-field coalesced exchange, compiled.

    The richest exchange program — where the PR-5 message-combining
    evidence (30 → 6 collective-permutes) lives.  Same grid (2,2,2)
    periodic-z and same ``n`` as `trace_exchange_entries`, so the traced
    twin of the same name is byte-comparable hop for hop.
    """
    import jax

    import implicitglobalgrid_tpu as igg
    from ..ops import halo

    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    try:
        gg = igg.get_global_grid()
        fields = model_field_structs(model, n)

        def body(*fs):
            return halo.exchange_dims_multi(fs, (0, 1, 2), width=1,
                                            coalesce=True)

        from jax.sharding import PartitionSpec as P

        from .. import AXIS_NAMES
        from jax import shard_map

        specs = tuple(P(*AXIS_NAMES[: f.ndim]) for f in fields)
        mapped = shard_map(
            body, mesh=gg.mesh, in_specs=specs, out_specs=specs,
            check_vma=False,
        )
        gargs = tuple(
            jax.ShapeDtypeStruct(
                tuple(s * gg.dims[i] for i, s in enumerate(f.shape)),
                f.dtype,
            )
            for f in fields
        )
        compiled = jax.jit(mapped).lower(*gargs).compile()
        memory, cost = _compiled_stats(compiled)
        return CompiledProgram(
            name=f"exchange/{model}[coalesce=True]",
            kind="exchange",
            config={"model": model, "n": n, "coalesce": True},
            text=compiled.as_text(),
            memory=memory,
            cost=cost,
        )
    finally:
        igg.finalize_global_grid()


def _compile_batched_exchange_program(model: str = "porous", n: int = 8,
                                      B: int | None = None) -> CompiledProgram:
    """The porous coalesced exchange under a vmapped B-member ensemble axis,
    compiled — the optimized-HLO half of the B-for-the-price-of-1 evidence:
    the cost baseline pins its ``collective_permutes`` EQUAL to the
    unbatched twin's and its ``collective_payload_bytes`` at ×B."""
    import jax

    import implicitglobalgrid_tpu as igg
    from ..ops import halo

    B = BATCH_HLO_B if B is None else B
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    try:
        gg = igg.get_global_grid()
        fields = model_field_structs(model, n)

        def single(*fs):
            return halo.exchange_dims_multi(fs, (0, 1, 2), width=1,
                                            coalesce=True)

        def body(*fs):
            return jax.vmap(single)(*fs)

        from ..models._batched import _batched_spec
        from jax import shard_map

        # THE batched-layout spec (`models._batched`): one definition for
        # the traced census, the serving pool and these compiled programs.
        specs = tuple(_batched_spec(f.ndim + 1) for f in fields)
        mapped = shard_map(
            body, mesh=gg.mesh, in_specs=specs, out_specs=specs,
            check_vma=False,
        )
        gargs = tuple(
            jax.ShapeDtypeStruct(
                (B,) + tuple(s * gg.dims[i] for i, s in enumerate(f.shape)),
                f.dtype,
            )
            for f in fields
        )
        compiled = jax.jit(mapped).lower(*gargs).compile()
        memory, cost = _compiled_stats(compiled)
        return CompiledProgram(
            name=f"exchange/{model}[coalesce=True,batch={B}]",
            kind="exchange",
            config={"model": model, "n": n, "coalesce": True, "batch": B},
            text=compiled.as_text(),
            memory=memory,
            cost=cost,
        )
    finally:
        igg.finalize_global_grid()


def _compile_batched_cadence_program(n: int = 8, B: int | None = None,
                                     nt: int = 2) -> CompiledProgram:
    """The batched diffusion serving cadence, compiled: ``make_multi_step(
    exchange_every=2, batch=True)`` on a deep-halo 2-device grid — the
    production shape of `serving.ServingLoop`'s round step (XLA cadence;
    the fused kernels' batched structure is covered by the vmap census,
    keeping this build seconds-cheap)."""
    import jax

    import implicitglobalgrid_tpu as igg
    from ..models import diffusion3d

    import jax.numpy as jnp

    B = BATCH_HLO_B if B is None else B
    # setup OUTSIDE the try (like `_compile_exchange_program`): if a
    # caller's grid is live, setup raises BEFORE the finally exists — the
    # teardown must only ever finalize the grid THIS function created.
    # dtype pinned like `_cadence_setup_kwargs`: the census must not
    # depend on the process's x64 default.
    state, params = diffusion3d.setup(
        n, n, n, devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        overlapx=4, overlapy=4, overlapz=4, quiet=True,
        dtype=jnp.float32,
    )
    try:
        from ..models._batched import stack_states

        bstate = stack_states([state] * B)
        step = diffusion3d.make_multi_step(
            params, nt, donate=False, exchange_every=2, batch=True
        )
        gg = igg.get_global_grid()
        from ..models._batched import _batched_spec
        from jax import shard_map

        spec = _batched_spec(4)  # the one batched-layout definition
        mapped = shard_map(
            step.__wrapped__, mesh=gg.mesh,
            in_specs=(spec,) * 2, out_specs=(spec,) * 2, check_vma=False,
        )
        compiled = jax.jit(mapped).lower(*bstate).compile()
        memory, cost = _compiled_stats(compiled)
        return CompiledProgram(
            name=f"cadence/diffusion[batch={B}]",
            kind="cadence",
            config={"model": "diffusion", "n": n, "batch": B, "nt": nt,
                    "exchange_every": 2},
            text=compiled.as_text(),
            memory=memory,
            cost=cost,
        )
    finally:
        if igg.grid_is_initialized():
            igg.finalize_global_grid()


def compile_exchange_hlo(model: str = "porous", n: int = 8) -> str:
    """Optimized-HLO text of the porous coalesced exchange (back-compat
    text-only view of `_compile_exchange_program`)."""
    return _compile_exchange_program(model, n).text


#: Cadence matrix: one admissible pipelined config per model (from the
#: pipelined-schedule test matrix) traced with pipelined on AND off.
_CADENCES = (
    ("diffusion", dict(nloc=(40, 32, 128), nt=4, k=2, tile=(8, 16),
                       periods={})),
    ("acoustic", dict(nloc=(24, 32, 128), nt=4, k=2, tile=(8, 16),
                      periods={"periodz": 1})),
    ("porous", dict(nloc=(24, 32, 128), nt=2, k=2, tile=(8, 16),
                    periods={"periodz": 1}, npt=5)),
)

_MODEL_MODULES = {
    "diffusion": "diffusion3d",
    "acoustic": "acoustic3d",
    "porous": "porous_convection3d",
}


def _cadence_setup_kwargs(cfg) -> dict:
    """`setup(...)` kwargs of one cadence config (2-device x-split grid)."""
    import jax
    import jax.numpy as jnp

    kw = dict(
        devices=jax.devices()[:2], dimx=2, dimy=1, dimz=1,
        overlapx=2 * cfg["k"], overlapy=2 * cfg["k"],
        overlapz=2 * cfg["k"], quiet=True, dtype=jnp.float32,
        **cfg["periods"],
    )
    if "npt" in cfg:
        kw["npt"] = cfg["npt"]
    return kw


def trace_cadence_entries() -> list:
    """Trace each model's fused multi-step cadence, pipelined on/off.

    Trace-only through the TPU Pallas interpreter — no execution.  A
    pipelined config that falls back to the serialized schedule (warn-once
    path) is recorded with ``admissible=False`` so the overlap analyzer can
    distinguish "no overlap possible" from "overlap lost".
    """
    import importlib

    import jax
    from jax.sharding import PartitionSpec as P

    import implicitglobalgrid_tpu as igg
    from jax import shard_map
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    entries = []
    for model, cfg in _CADENCES:
        mod = importlib.import_module(
            "implicitglobalgrid_tpu.models." + _MODEL_MODULES[model]
        )
        for pipelined in (False, True):
            try:
                state, params = mod.setup(
                    *cfg["nloc"], **_cadence_setup_kwargs(cfg)
                )
                admissible = True
                with force_tpu_interpret_mode():
                    with warnings.catch_warnings(record=True) as caught:
                        warnings.simplefilter("always")
                        step = mod.make_multi_step(
                            params, cfg["nt"], donate=False,
                            fused_k=cfg["k"], fused_tile=cfg["tile"],
                            pipelined=pipelined,
                        )
                        gg = igg.get_global_grid()
                        nf = len(state)
                        mapped = shard_map(
                            step.__wrapped__, mesh=gg.mesh,
                            in_specs=(P(*igg.AXIS_NAMES),) * nf,
                            out_specs=(P(*igg.AXIS_NAMES),) * nf,
                            check_vma=False,
                        )
                        jaxpr = jax.make_jaxpr(mapped)(*state)
                    if pipelined and any(
                        "not admissible" in str(w.message) for w in caught
                    ):
                        admissible = False
                mesh_shape = {
                    a: int(s) for a, s in zip(igg.AXIS_NAMES, gg.dims)
                }
            finally:
                # a failed trace must not leak the grid into the next
                # config's setup (or a later analyzer's init)
                if igg.grid_is_initialized():
                    igg.finalize_global_grid()
            entries.append(
                TracedEntry(
                    name=f"cadence/{model}[pipelined={pipelined}]",
                    kind="cadence",
                    config={"model": model, "pipelined": pipelined, **cfg},
                    jaxpr=unwrap_inner(jaxpr.jaxpr),
                    mesh_shape=mesh_shape,
                    admissible=admissible,
                )
            )
    return entries


def _compile_cadence_program(model: str) -> CompiledProgram:
    """Compile one model's fused cadence (pipelined=True, the `_CADENCES`
    config) through the TPU Pallas interpreter — the optimized-HLO view
    of the production multi-step program the cost model pins.  One XLA:CPU
    build per model, seconds each; `Context` caches the result."""
    import importlib

    import jax
    from jax.sharding import PartitionSpec as P

    import implicitglobalgrid_tpu as igg
    from jax import shard_map
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    cfg = dict(_CADENCES)[model]
    mod = importlib.import_module(
        "implicitglobalgrid_tpu.models." + _MODEL_MODULES[model]
    )
    try:
        state, params = mod.setup(*cfg["nloc"], **_cadence_setup_kwargs(cfg))
        with force_tpu_interpret_mode():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                step = mod.make_multi_step(
                    params, cfg["nt"], donate=False,
                    fused_k=cfg["k"], fused_tile=cfg["tile"],
                    pipelined=True,
                )
                gg = igg.get_global_grid()
                nf = len(state)
                mapped = shard_map(
                    step.__wrapped__, mesh=gg.mesh,
                    in_specs=(P(*igg.AXIS_NAMES),) * nf,
                    out_specs=(P(*igg.AXIS_NAMES),) * nf,
                    check_vma=False,
                )
                compiled = jax.jit(mapped).lower(*state).compile()
        memory, cost = _compiled_stats(compiled)
        return CompiledProgram(
            name=f"cadence/{model}[pipelined=True]",
            kind="cadence",
            config={"model": model, "pipelined": True, **cfg},
            text=compiled.as_text(),
            memory=memory,
            cost=cost,
        )
    finally:
        if igg.grid_is_initialized():
            igg.finalize_global_grid()


# -- traced VJP producers (grad-soundness) ------------------------------------


@dataclass(frozen=True)
class GradTrace:
    """One differentiable entry point traced through `jax.vjp`.

    ``jaxpr`` is the inner jaxpr of the whole VJP program (forward replay +
    backward pass — seeds and primals in, one cotangent per primal out);
    ``primal_jaxpr`` is the matching primal-only trace.  The grad-soundness
    census compares their collective counts: a cross-boundary cotangent
    MUST ride collectives backward, so a VJP trace whose collective count
    does not exceed the primal's has dropped its cross-rank gradient — the
    PR-5 bitcast-without-VJP class, statically.
    """

    name: str
    kind: str            # "exchange" | "cadence"
    config: dict
    jaxpr: object
    primal_jaxpr: object

    def collective_counts(self) -> tuple[int, int]:
        """(grad_collectives, primal_collectives)."""
        return (
            len(collect_collectives(self.jaxpr)),
            len(collect_collectives(self.primal_jaxpr)),
        )


def trace_grad_entries(n: int = 8) -> list:
    """VJP traces of every differentiable entry point.

    Two families (trace-only, no execution):

    * the coalesced exchange of each model's production field set — the
      `_packed_transport` custom-VJP path (PR 5's hand-written transpose);
    * each model's fused multi-step cadence — the `fused_with_xla_grad`
      family (primal replays the fused body, backward differentiates the
      XLA twin).

    Seeds are passed as leading ARGUMENTS (not synthesized inside), so the
    traced program's cotangent outputs carry real dataflow from the seed
    inputs — the census counts collectives, which only appear when the
    backward pass actually transports cotangents across ranks.
    """
    import importlib

    import jax
    from jax.sharding import PartitionSpec as P

    import implicitglobalgrid_tpu as igg
    from ..ops import halo
    from jax import shard_map
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    entries = []

    # Exchange family: one grid, all models, coalesce=True (the packed
    # transport whose custom VJP the census proves alive).
    igg.init_global_grid(n, n, n, dimx=2, dimy=2, dimz=2, periodz=1,
                         quiet=True)
    try:
        gg = igg.get_global_grid()
        for model in ("diffusion", "acoustic", "porous"):
            fields = model_field_structs(model, n)
            nf = len(fields)

            def body(*fs):
                return halo.exchange_dims_multi(fs, (0, 1, 2), width=1,
                                                coalesce=True)

            def grad_body(*args, _body=body, _nf=nf):
                seeds, prims = args[:_nf], args[_nf:]
                _, vjp = jax.vjp(_body, *prims)
                return vjp(tuple(seeds))

            gj = _trace_mapped(grad_body, fields * 2, gg, out_fields=fields)
            pj = _trace_mapped(body, fields, gg)
            entries.append(
                GradTrace(
                    name=f"grad/exchange/{model}",
                    kind="exchange",
                    config={"model": model, "coalesce": True},
                    jaxpr=unwrap_inner(gj.jaxpr),
                    primal_jaxpr=unwrap_inner(pj.jaxpr),
                )
            )
    finally:
        igg.finalize_global_grid()

    # Cadence family: the fused multi-step of each model (serialized
    # schedule — the default production grad path; the pipelined twin's
    # structure is covered by `overlap-independence`).
    for model, cfg in _CADENCES:
        mod = importlib.import_module(
            "implicitglobalgrid_tpu.models." + _MODEL_MODULES[model]
        )
        try:
            state, params = mod.setup(
                *cfg["nloc"], **_cadence_setup_kwargs(cfg)
            )
            with force_tpu_interpret_mode():
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    step = mod.make_multi_step(
                        params, cfg["nt"], donate=False,
                        fused_k=cfg["k"], fused_tile=cfg["tile"],
                        pipelined=False,
                    )
                    gg = igg.get_global_grid()
                    nf = len(state)

                    def grad_body(*args, _step=step, _nf=nf):
                        seeds, prims = args[:_nf], args[_nf:]
                        _, vjp = jax.vjp(_step.__wrapped__, *prims)
                        return vjp(tuple(seeds))

                    specs = (P(*igg.AXIS_NAMES),) * nf
                    mapped = shard_map(
                        grad_body, mesh=gg.mesh, in_specs=specs * 2,
                        out_specs=specs, check_vma=False,
                    )
                    gj = jax.make_jaxpr(mapped)(*state, *state)
                    pm = shard_map(
                        step.__wrapped__, mesh=gg.mesh, in_specs=specs,
                        out_specs=specs, check_vma=False,
                    )
                    pj = jax.make_jaxpr(pm)(*state)
        finally:
            if igg.grid_is_initialized():
                igg.finalize_global_grid()
        entries.append(
            GradTrace(
                name=f"grad/cadence/{model}",
                kind="cadence",
                config={"model": model, **cfg},
                jaxpr=unwrap_inner(gj.jaxpr),
                primal_jaxpr=unwrap_inner(pj.jaxpr),
            )
        )
    return entries


# -- kernel identification (shared by overlap + aliasing) ---------------------


def is_kernel_eqn(eqn) -> bool:
    """A Pallas kernel launch: a ``pallas_call`` eqn, or an envelope whose
    body holds kernel launches and no collectives — the kernels' cached
    ``jax.jit(pallas_call)`` builders under their per-member
    ``custom_vmap`` wrapper (`ops._fused_envelope.per_member`)."""
    return _eqn_presence(eqn) == (True, False)


def iter_pallas_calls(jaxpr):
    """Yield every ``pallas_call`` eqn in a program (all nesting levels)."""
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            yield eqn


def _eqn_presence(eqn) -> tuple:
    """``(has_kernel, has_collective)`` anywhere inside one equation.

    The classification the independence census keys on: a ``pjit`` or
    ``custom_vjp`` envelope containing only kernel launches IS a kernel
    (the cached ``jax.jit(pallas_call)`` builders); one containing only
    collectives IS a collective (the coalesced exchange's
    ``_packed_transport`` custom-VJP envelope, PR 5); one containing BOTH
    is a composite sub-program (`fused_with_xla_grad` wraps a whole
    cadence step per iteration) that must be analyzed at its own level.
    """
    if eqn.primitive.name == "pallas_call":
        return True, False
    if eqn.primitive.name in COLLECTIVE_PRIMS:
        return False, True
    has_k = has_c = False
    for _, sub in _sub_jaxprs(eqn):
        for e, _ in iter_eqns(sub):
            if e.primitive.name == "pallas_call":
                has_k = True
            elif e.primitive.name in COLLECTIVE_PRIMS:
                has_c = True
            if has_k and has_c:
                return True, True
    return has_k, has_c


def independence_pairs(jaxpr, *, is_kernel=None, is_collective=None):
    """Count (kernel, collective) pairs with NO transitive dependency in
    either direction among the direct equations of ``jaxpr`` — the
    dataflow freedom the pipelined schedule exists to create, asserted
    below the compiler.

    Returns ``(free_pairs, n_kernels, n_collectives)``.  By default an
    equation counts as a kernel/collective by CONTENT (`_eqn_presence`):
    kernel-only and collective-only envelopes join the census at this
    level, while composite envelopes containing both (the per-step
    ``fused_with_xla_grad`` custom-VJP wrapper) are recursed into and
    their counts summed — each wrapped step body is its own independence
    scope.  Predicates are injectable so tests can probe the counter with
    stand-in "kernels" (injection disables the composite recursion and
    restores the literal top-level census).  Generalized from
    ``tests/test_pipelined_schedule.py`` (ISSUE 2's structural-overlap
    evidence) to run across all models.
    """
    composites = []
    if is_kernel is None and is_collective is None:
        presence = {id(e): _eqn_presence(e) for e in jaxpr.eqns}
        is_kernel = lambda e: presence[id(e)] == (True, False)  # noqa: E731
        is_collective = lambda e: presence[id(e)] == (False, True)  # noqa: E731
        composites = [e for e in jaxpr.eqns if presence[id(e)] == (True, True)]
    else:
        is_kernel = is_kernel or is_kernel_eqn
        is_collective = is_collective or (
            lambda e: e.primitive.name == "ppermute"
        )
    producer = {}
    for e in jaxpr.eqns:
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

    kernels = [e for e in jaxpr.eqns if is_kernel(e)]
    colls = [e for e in jaxpr.eqns if is_collective(e)]
    kc = {id(e): closure(e) for e in kernels}
    pairs = 0
    for c in colls:
        cc = closure(c)
        for k in kernels:
            if id(k) not in cc and id(c) not in kc[id(k)]:
                pairs += 1
    nk, nc = len(kernels), len(colls)
    for comp in composites:
        for _, sub in _sub_jaxprs(comp):
            p, k, c = independence_pairs(sub)
            pairs += p
            nk += k
            nc += c
    return pairs, nk, nc
