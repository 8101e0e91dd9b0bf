"""3-D heat diffusion: the reference's headline example.

    dT/dt = lam / Cp * laplacian(T),  explicit Euler, dt = min(h^2) * cp_min / lam / 8.1

Boundary planes of a non-periodic dimension stay fixed; a periodic dimension
wraps.  The reference below is written from these equations in plain
`jax.numpy` and imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from igg_bench import fields

FIELDS = ("T", "Cp")
LAM, CP_MIN, LENGTHS = 1.0, 1.0, (10.0, 10.0, 10.0)

#: arrays a step must stream by the reference's T_eff convention
#: (`benchmarks/run.py::bench_diffusion`): T read and written
A_EFF_ARRAYS = 2


def setup(cfg, grid, devices):
    """The program's grid and solver parameters, through its public path."""
    import jax.numpy as jnp

    from implicitglobalgrid_tpu.models import diffusion3d

    state, params = diffusion3d.setup(
        *grid.local_shape, dtype=jnp.dtype(cfg["dtype"]), quiet=True,
        devices=devices, **grid.init_kwargs(),
    )
    return state, params


def make_step(params, cfg, traffic):
    from implicitglobalgrid_tpu.models import diffusion3d

    tile = cfg.get("fused_tile")
    return diffusion3d.make_multi_step(
        params, traffic["steps_per_call"], fused_k=cfg["fused_k"],
        fused_tile=tuple(tile) if tile else None,
        pipelined=cfg.get("pipelined"),
    )


def units_per_call(cfg, traffic) -> int:
    """Time steps per call: the unit `teff_gbs` counts."""
    return traffic["steps_per_call"]


def a_eff_bytes(cfg, grid) -> int:
    return A_EFF_ARRAYS * int(np.prod(grid.local_shape)) * grid.itemsize


def groups_per_call(cfg, traffic) -> int:
    """Kernel groups per call: one group advances ``fused_k`` steps."""
    return traffic["steps_per_call"] // cfg["fused_k"]


def least_bytes_per_group(cfg, grid) -> int:
    """Least HBM bytes one group must move: T and Cp read once, T written
    once (the z patch and export of a periodic-z cadence are left out, so
    this is a lower bound)."""
    return 3 * int(np.prod(grid.local_shape)) * grid.itemsize


def draw(seed: int) -> np.ndarray:
    """Initial-field parameters from the seed: 3 temperature bumps, 2 heat
    capacity bumps, 2 noise salts (extents in cells are fixed per cell,
    so the bumps are drawn in units of a 256-cell grid and scaled)."""
    rng = np.random.default_rng(seed)
    t = fields.draw_bumps(rng, 3, (1.0, 1.0, 1.0), (50.0, 150.0))
    c = fields.draw_bumps(rng, 2, (1.0, 1.0, 1.0), (2.0, 6.0))
    salts = rng.integers(0, 2**24, size=2)  # exact in float32
    return np.concatenate([t.ravel(), c.ravel(), salts]).astype(np.float32)


def _unpack(p, extents):
    import jax.numpy as jnp

    scale = jnp.asarray(list(extents) + [min(extents), 1.0], jnp.float32)
    t = p[:15].reshape(3, 5).astype(jnp.float32) * scale
    c = p[15:25].reshape(2, 5).astype(jnp.float32) * scale
    return t, c, p[25].astype(jnp.uint32), p[26].astype(jnp.uint32)


def initial(name, g, extents, periodic, p):
    """Value of field ``name`` at global indices ``g``."""
    t, c, s0, s1 = _unpack(p, extents)
    if name == "T":
        return fields.bumps(g, extents, periodic, t) + fields.hash_uniform(g, s0)
    return (CP_MIN + fields.bumps(g, extents, periodic, c)
            + 0.5 * fields.hash_uniform(g, s1))


def constants(grid):
    """dx, dy, dz and dt as the reference example sets them."""
    h = [LENGTHS[d] / (grid.extents[d] - 1) for d in range(3)]
    dt = min(x * x for x in h) * CP_MIN / LAM / 8.1
    return h, dt


def reference(state, grid, cfg, traffic, dtype):
    """``steps_per_call`` steps from ``state = (T, Cp)`` on the global
    de-duplicated grid, every operation in ``dtype``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    h, dt = constants(grid)

    def run(T, Cp):
        # Fields enter as arguments, never as captured constants: a traced
        # constant would make each seed's program new to the compile cache.
        mask = jnp.ones(T.shape, dtype)
        for d in range(3):
            if not grid.periodic[d]:
                idx = lax.broadcasted_iota(jnp.int32, T.shape, d)
                mask = mask * ((idx > 0) & (idx < T.shape[d] - 1)).astype(dtype)
        coef = (jnp.asarray(dt * LAM, dtype) / Cp) * mask
        inv_h2 = [jnp.asarray(1.0 / (x * x), dtype) for x in h]

        def step(_, T):
            lap = sum((jnp.roll(T, -1, d) - 2 * T + jnp.roll(T, 1, d)) * inv_h2[d]
                      for d in range(3))
            return T + coef * lap

        return lax.fori_loop(0, traffic["steps_per_call"], step, T), Cp

    return jax.jit(run)(*(jnp.asarray(a, dtype) for a in state))
