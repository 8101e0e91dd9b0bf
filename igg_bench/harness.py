"""One run of one cell: set-up, the measured window, the check, the trace.

Everything that belongs to one configuration, traffic mix, solver or metric
is a file of its own, found by the name `BENCHMARK.json` gives it:

* ``igg_bench/configs/<config>.json``  (the path is the config's ``file``)
* ``igg_bench/traffic/<traffic>.json``
* ``igg_bench/solvers/<solver>.py``    (named by the config's ``solver``)
* ``igg_bench/metrics/<metric>.py``    (each with ``read(ctx) -> float | None``)
* ``igg_bench/limits/<workload>.json`` (the limit of each number compared)

The timed path is the library's public one: `init_global_grid` (inside the
solver's ``setup``), then ``make_multi_step``, called back to back.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import contextlib
import os
import tempfile
import time
import warnings
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(Exception):
    """A cell that cannot run as specified (no result is printed)."""


# -- finding things by name ---------------------------------------------------


def load_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_module(root, kind, name):
    path = os.path.join(root, "igg_bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"igg_bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Grid:
    """Per-chip block, chip layout and boundaries of one cell."""

    local_shape: tuple
    dims: tuple
    overlaps: tuple
    periodic: tuple
    itemsize: int

    @property
    def extents(self):
        """Global de-duplicated extents of a cell-centred field."""
        return tuple(
            self.dims[d] * (self.local_shape[d] - self.overlaps[d])
            + (0 if self.periodic[d] else self.overlaps[d])
            for d in range(3)
        )

    def init_kwargs(self):
        kw = {}
        for d, a in enumerate("xyz"):
            kw[f"dim{a}"] = self.dims[d]
            kw[f"overlap{a}"] = self.overlaps[d]
            kw[f"period{a}"] = int(self.periodic[d])
        return kw


def make_grid(cfg, traffic) -> Grid:
    over = list(cfg["overlap"])
    for a, o in traffic.get("overlap", {}).items():
        over["xyz".index(a)] = o
    periodic = tuple("xyz"[d] in traffic.get("periodic", "") for d in range(3))
    return Grid(tuple(cfg["local_shape"]), tuple(cfg["dims"]), tuple(over),
                periodic, np.dtype(cfg["dtype"]).itemsize)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    grid: Grid
    solver: object
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric, workload):
    return workload in metric.get("workloads", [workload])


def load_cell(root, spec, workload) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload named {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(root, configs[w["config"]]["file"])
    traffic = load_json(root, os.path.join("igg_bench", "traffic", f"{w['traffic']}.json"))
    limits = load_json(root, os.path.join("igg_bench", "limits", f"{workload}.json"))
    return Cell(
        name=workload, chips=w["chips"], cfg=cfg, traffic=traffic,
        grid=make_grid(cfg, traffic),
        solver=load_module(root, "solvers", cfg["solver"]),
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


# -- clocks -------------------------------------------------------------------


def process_age_s(fallback_t0):
    """Seconds since this process started (kernel start time, 10 ms ticks)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - fallback_t0


def sync(state):
    """Wait for ``state``, then fetch one element of its first shard: an
    end for a timed window that holds even where `block_until_ready`
    returned early (`benchmarks/run.py::_sync`)."""
    import jax

    jax.block_until_ready(state)
    shard = state[0].addressable_shards[0].data
    float(shard[(0,) * shard.ndim])


# -- the run ------------------------------------------------------------------


def seeded_state(cell, state, seed):
    """The solver's initial fields from ``seed``, made on the device in one
    jitted call, in the layout and sharding of the program's own state."""
    import jax
    import jax.numpy as jnp

    from igg_bench import fields

    g = cell.grid
    p = jnp.asarray(cell.solver.draw(seed))
    shapes = [a.shape for a in state]
    dtypes = [a.dtype for a in state]

    def make(p):
        out = []
        for name, shape, dt in zip(cell.solver.FIELDS, shapes, dtypes):
            idx, ext = fields.global_index(shape, g.dims, g.overlaps, g.periodic,
                                           g.local_shape)
            out.append(cell.solver.initial(name, idx, ext, g.periodic, p).astype(dt))
        return tuple(out)

    return jax.jit(make, out_shardings=tuple(a.sharding for a in state))(p)


def first_element(state):
    """A one-element copy of the first field's first local shard: a token
    that is ready when the call that produced ``state`` has finished."""
    shard = state[0].addressable_shards[0].data
    return shard[(slice(0, 1),) * shard.ndim]


def copy_state(state):
    import jax.numpy as jnp

    return tuple(jnp.array(a, copy=True) for a in state)


def sampled_call(seed) -> int:
    """Index of the window's call whose input and output are checked: one
    of the calls 2 to 5, drawn from the seed."""
    return 1 + int(np.random.default_rng([seed, 7]).integers(0, 4))


def rel_err(got, want) -> float:
    """max |got - want| / max |want| over one field (inf if not finite)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise BenchError(f"shape {got.shape} != reference shape {want.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def compare(cell, before, after, dtype="float32"):
    """The reference from ``before`` (host arrays on the global grid) against
    ``after``: the relative error of each field."""
    import jax

    with jax.default_device(jax.devices()[0]):
        want = cell.solver.reference(before, cell.grid, cell.cfg, cell.traffic,
                                     np.dtype(dtype) if dtype != "bfloat16"
                                     else jax.numpy.bfloat16)
        want = [np.asarray(w, np.float32) for w in want]
    return [rel_err(a, w) for a, w in zip(after, want)]


def run(root, spec, workload, seed, seconds, trace, devices, *, t0,
        wrap_step=None, log=None):
    """One run of ``workload``: returns the result line (a dict), whose last
    key ``checks`` holds the compared numbers.  ``wrap_step`` replaces the
    timed step (the fault tests); ``log`` receives progress lines.  A
    kernel or pipelined path that falls back to another is an error."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=r"(fused_k=|pipelined=True)",
                                category=RuntimeWarning)
        return _run(root, spec, workload, seed, seconds, trace, devices, t0,
                    wrap_step, log)


def _run(root, spec, workload, seed, seconds, trace, devices, t0, wrap_step, log):
    import jax

    import implicitglobalgrid_tpu as igg

    log = log or (lambda msg: None)
    cell = load_cell(root, spec, workload)
    devices = list(devices)[: cell.chips]
    if len(devices) < cell.chips:
        raise BenchError(f"{workload} needs {cell.chips} chips, found {len(devices)}")

    # -- set-up: grid, seeded state, compile, warm-up ---------------------------
    before_grid = process_age_s(t0)
    state, params = cell.solver.setup(cell.cfg, cell.grid, devices)
    state = seeded_state(cell, state, seed)
    jax.block_until_ready(state)
    step = cell.solver.make_step(params, cell.cfg, cell.traffic)
    if wrap_step is not None:
        step = wrap_step(step)
    tc = time.perf_counter()
    grid_s = process_age_s(t0) - before_grid
    compiled = step.lower(*state).compile()
    compile_s = time.perf_counter() - tc
    state = copy_state(state)  # warms up the snapshot copy
    state = step(*state)
    first_element(state).block_until_ready()
    sync(state)
    j = sampled_call(seed)
    units = cell.solver.units_per_call(cell.cfg, cell.traffic)
    setup_s = process_age_s(t0)
    log(f"set-up {setup_s:.3f} s: start and imports {before_grid:.3f} s, grid and "
        f"state {grid_s:.3f} s, compile {compile_s:.3f} s, warm-up call "
        f"{setup_s - before_grid - grid_s - compile_s:.3f} s; sampled call {j}")

    # -- the measured window -----------------------------------------------------
    # Calls go back to back with one more queued behind the running one: the
    # host waits for call i-1 (a one-element token of its output) only after
    # dispatching call i, so a host delay shorter than a call leaves the
    # device busy.  The window ends when the last call's state is fetched.
    tracer = _Tracer(trace)
    calls, snap_in, snap_out, pending = 0, None, None, None
    with tracer:
        w0 = time.perf_counter()
        while True:
            with tracer.annotate("call", calls):
                if calls == j:
                    with tracer.annotate("snapshot", calls):
                        snap_in = copy_state(state)
                with tracer.annotate("dispatch", calls):
                    state = step(*state)
                    token = first_element(state)
                if calls == j:
                    with tracer.annotate("snapshot", calls):
                        snap_out = copy_state(state)
                if pending is not None:
                    with tracer.annotate("wait", calls):
                        pending.block_until_ready()
                pending = token
            calls += 1
            if calls > j and time.perf_counter() - w0 >= seconds:
                break
        sync(state)
        window_s = time.perf_counter() - w0
    log(f"window {window_s:.3f} s, {calls} calls")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    del state
    before = [np.asarray(igg.gather(a, dedup=True)) for a in snap_in]
    after = [np.asarray(igg.gather(a, dedup=True)) for a in snap_out]
    del snap_in, snap_out
    igg.finalize_global_grid()

    ctx = SimpleNamespace(
        cell=cell, seed=seed, calls=calls, window_s=window_s, setup_s=setup_s,
        compile_s=compile_s, units_per_call=units,
        a_eff_bytes=cell.solver.a_eff_bytes(cell.cfg, cell.grid),
        groups_per_call=cell.solver.groups_per_call(cell.cfg, cell.traffic),
        least_bytes_per_group=cell.solver.least_bytes_per_group(cell.cfg, cell.grid),
        peaks=_peaks(root, devices[0].device_kind), trace=None,
    )
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "device_kind": devices[0].device_kind, "count": len(devices),
           "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        ctx.trace = tracer.reduce(len(devices), compiled.as_text())
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module(root, "metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- the check, once the window has closed and the program's state is freed --
    errs = compare(cell, before, after)
    log("rel_err per field: " + ", ".join(
        f"{n} {e!r}" for n, e in zip(cell.solver.FIELDS, errs)))
    checks = {"rel_err": {"value": max(errs), "limit": cell.limits["rel_err"]}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": calls, "failed": 0 if correct else 1,
            "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def _peaks(root, kind):
    table = load_json(root, os.path.join("igg_bench", "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in igg_bench/peaks.json")
    return table[kind]


class _Tracer:
    """The profiler around the window when ``--trace 1``; inert otherwise."""

    def __init__(self, on):
        self.on = on
        self.dir = None

    def __enter__(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="igg_bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host annotations only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation("igg_bench.window")
            self._window.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            self._window.__exit__(*exc)
            jax.profiler.stop_trace()
        return False

    def annotate(self, name, i):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"igg_bench.{name}", step=i)

    def reduce(self, ndev, hlo_text):
        import shutil

        from igg_bench import trace as _trace

        try:
            return _trace.reduce_dir(self.dir, ndev, hlo_text)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
