"""3-D acoustic wave on a staggered grid: the velocity-pressure leapfrog.

    V_d -= dt / rho * d(P)/d(x_d)   on interior faces
    P   -= dt * K * div(V)           on all cells

``P`` sits at cell centres, ``Vx``, ``Vy``, ``Vz`` on the faces of their own
axis (``n + 1`` faces along it, ``n`` cells across).  Along a non-periodic
dimension a face field's first and last face (the rigid walls) and its first
and last cell across (the faces next to a side wall) stay fixed; along a
periodic one every face is updated and the field wraps (``n`` faces on the
global grid, face ``i`` between cells ``i - 1`` and ``i``).  The constants
are the ones `models/acoustic3d.py` documents: K = rho = 1, a box of side
10 and dt = min(h) / c / 2.  The reference below is written from these
equations in plain `jax.numpy` and imports nothing of the program; `setup`
and `make_step` are the only functions here that do.
"""

from __future__ import annotations

import numpy as np

from igg_bench import fields

FIELDS = ("P", "Vx", "Vy", "Vz")
K, RHO, LENGTHS = 1.0, 1.0, (10.0, 10.0, 10.0)

#: arrays a step must stream by the reference's T_eff convention
#: (`benchmarks/run.py::bench_acoustic`): P, Vx, Vy, Vz read and written
A_EFF_ARRAYS = 8

#: the program's telemetry counter of fused-kernel fallbacks
FALLBACK_COUNTER = "fused.fallback"


def setup(cfg, grid, devices):
    """The program's grid and solver parameters, through its public path."""
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.models import acoustic3d

    return acoustic3d.setup(
        *grid.local_shape, dtype=jnp.dtype(cfg["dtype"]), quiet=True,
        devices=devices, **grid.init_kwargs(),
    )


def _fallbacks():
    """The program's count of fused-kernel fallbacks so far (0 where it
    keeps no such counter)."""
    from implicitglobalgrid_tpu.utils import telemetry

    return telemetry.snapshot()["counters"].get(FALLBACK_COUNTER, 0)


class _KernelPathOnly:
    """The program's multi-step callable, refused at lowering if the
    program counted a kernel fallback while tracing it: a cell that ran the
    XLA cadence must not pass as the leapfrog kernel's cell."""

    def __init__(self, step):
        self.step = step

    def lower(self, *state):
        from igg_bench.harness import BenchError

        before = _fallbacks()
        lowered = self.step.lower(*state)
        if _fallbacks() > before:
            raise BenchError("the fused leapfrog kernel fell back to the XLA path")
        return lowered

    def __call__(self, *state):
        return self.step(*state)


def make_step(params, cfg, traffic):
    from implicitglobalgrid_tpu.models import acoustic3d

    tile = cfg.get("fused_tile")
    return _KernelPathOnly(acoustic3d.make_multi_step(
        params, traffic["steps_per_call"], fused_k=cfg["fused_k"],
        fused_tile=tuple(tile) if tile else None,
        pipelined=cfg.get("pipelined"),
    ))


def units_per_call(cfg, traffic) -> int:
    """Time steps per call: the unit `teff_gbs` counts."""
    return traffic["steps_per_call"]


def a_eff_bytes(cfg, grid) -> int:
    return A_EFF_ARRAYS * int(np.prod(grid.local_shape)) * grid.itemsize


def groups_per_call(cfg, traffic) -> int:
    """Kernel groups per call: one group advances ``fused_k`` steps."""
    return traffic["steps_per_call"] // cfg["fused_k"]


def least_bytes_per_group(cfg, grid) -> int:
    """Least HBM bytes one group must move: P, Vx, Vy, Vz read once and
    written once, at their logical (unpadded) sizes (the padded layout's
    junk planes and a periodic-z cadence's patches and exports are left
    out, so this is a lower bound)."""
    n0, n1, n2 = grid.local_shape
    cells = n0 * n1 * n2
    faces = (n0 + 1) * n1 * n2 + n0 * (n1 + 1) * n2 + n0 * n1 * (n2 + 1)
    return 2 * (cells + faces) * grid.itemsize


def draw(seed: int) -> np.ndarray:
    """Initial-field parameters from the seed: 3 pressure bumps (extents in
    units of the grid, scaled per cell), and 4 noise salts, one per field."""
    rng = np.random.default_rng(seed)
    p = fields.draw_bumps(rng, 3, (1.0, 1.0, 1.0), (50.0, 150.0))
    salts = rng.integers(0, 2**24, size=4)  # exact in float32
    return np.concatenate([p.ravel(), salts]).astype(np.float32)


def _moving(g, extents, periodic):
    """1 where a face field's value is updated, 0 on the fixed faces: along
    each non-periodic dimension, the first and last index."""
    import jax.numpy as jnp

    m = jnp.ones(g[0].shape, jnp.bool_)
    for d in range(3):
        if not periodic[d]:
            m = m & (g[d] > 0) & (g[d] < extents[d] - 1)
    return m.astype(jnp.float32)


def initial(name, g, extents, periodic, p):
    """Value of field ``name`` at global indices ``g``: pressure bumps with
    noise; velocities of seeded noise, zero on the fixed faces (the rigid
    walls, as the program's own set-up holds them)."""
    import jax.numpy as jnp

    salt = p[15 + FIELDS.index(name)].astype(jnp.uint32)
    if name == "P":
        scale = jnp.asarray(list(extents) + [min(extents), 1.0], jnp.float32)
        bumps = p[:15].reshape(3, 5).astype(jnp.float32) * scale
        return fields.bumps(g, extents, periodic, bumps) + fields.hash_uniform(g, salt)
    return (fields.hash_uniform(g, salt) - 0.5) * _moving(g, extents, periodic)


def constants(grid):
    """dx, dy, dz and dt as the program's set-up computes them."""
    h = [LENGTHS[d] / (grid.extents[d] - 1) for d in range(3)]
    dt = min(h) / (K / RHO) ** 0.5 / 2.0
    return h, dt


def reference(state, grid, cfg, traffic, dtype):
    """``steps_per_call`` leapfrog steps from ``state = (P, Vx, Vy, Vz)`` on
    the global de-duplicated grid, every operation in ``dtype`` (no matrix
    products, so no matmul precision applies)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    h, dt = constants(grid)
    per = grid.periodic

    def run(P, Vx, Vy, Vz):
        # Fields enter as arguments, never as captured constants: a traced
        # constant would make each seed's program new to the compile cache.
        c = lambda x: jnp.asarray(x, dtype)
        grad_coef = [c(dt / RHO / x) for x in h]
        inv_h = [c(1.0 / x) for x in h]
        div_coef = c(dt * K)
        moving = []
        for V in (Vx, Vy, Vz):
            m = jnp.ones(V.shape, jnp.bool_)
            for d in range(3):
                if not per[d]:
                    i = lax.broadcasted_iota(jnp.int32, V.shape, d)
                    m = m & (i > 0) & (i < V.shape[d] - 1)
            moving.append(m.astype(dtype))

        def grad(P, d):
            """P(i) - P(i-1) on the faces of axis ``d``: 0 on the two wall
            faces of a non-periodic axis, wrapped on a periodic one."""
            if per[d]:
                return P - jnp.roll(P, 1, d)
            pad = [(1, 1) if e == d else (0, 0) for e in range(3)]
            return jnp.pad(jnp.diff(P, axis=d), pad)

        def div(V, d):
            """V(i+1) - V(i) at the cells of axis ``d``."""
            return jnp.roll(V, -1, d) - V if per[d] else jnp.diff(V, axis=d)

        def step(_, s):
            P, *V = s
            V = [V[d] - grad_coef[d] * grad(P, d) * moving[d] for d in range(3)]
            P = P - div_coef * sum(div(V[d], d) * inv_h[d] for d in range(3))
            return (P, *V)

        return lax.fori_loop(0, traffic["steps_per_call"], step, (P, Vx, Vy, Vz))

    return jax.jit(run)(*(jnp.asarray(a, dtype) for a in state))
